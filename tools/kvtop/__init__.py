"""kvtop — a dependency-free live console for the federated fleet view.

Renders the ``FleetFederator`` snapshot (ISSUE 20) the way ``top``
renders processes: one row per pod with its tier-ladder fill bars,
SLO-burn state, drain/breaker flags; a fleet header with the derived
health score and its sparkline over the delta-ring history; the top
tenants by burn; and the pods' flight-recorder counters. Stdlib only
(curses + urllib) so it runs anywhere the repo does.

Two data sources, same renderer:

- ``--url http://scorer:8080`` — poll a deployed scorer's
  ``GET /debug/fleet`` (the scorer must run with ``OBS_FED=1``);
- an in-process ``FleetFederator`` handed to :func:`fetch_snapshot` —
  how the tests drive the console without sockets.

``python -m tools.kvtop --url ... [--interval 2] [--plain] [--once]``.
``--plain`` skips curses (CI/pipes); ``--once`` renders one frame and
exits.
"""

from __future__ import annotations

import json
import urllib.request
from typing import Optional

#: eight-step bar/sparkline ramp (the classic braille-free heat ramp)
RAMP = "▁▂▃▄▅▆▇█"


def fetch_snapshot(
    source, timeout_s: float = 5.0, limit: int = 60
) -> dict:
    """One ``/debug/fleet``-shaped payload from either source: a scorer
    base URL (str) or an in-process ``FleetFederator``-like object (any
    object with ``scrape()``/``history()``/``snapshot()``)."""
    if isinstance(source, str):
        url = source.rstrip("/") + f"/debug/fleet?limit={limit}"
        with urllib.request.urlopen(url, timeout=timeout_s) as resp:
            return json.loads(resp.read().decode("utf-8"))
    snapshot = source.scrape()
    return {
        "enabled": True,
        **snapshot,
        "history": source.history(limit=limit),
        **source.snapshot(),
    }


def _bar(fill: Optional[float], width: int = 10) -> str:
    """``[####----] 42%`` fill bar; ``--`` for an unknown fill."""
    if fill is None:
        return "[" + " " * width + "]  --"
    fill = min(max(fill, 0.0), 1.0)
    n = round(fill * width)
    return "[" + "#" * n + "-" * (width - n) + f"] {fill * 100:3.0f}%"


def sparkline(values, width: int = 24) -> str:
    """History values in [0, 1] (None = gap) as a RAMP sparkline."""
    vals = list(values)[-width:]
    out = []
    for v in vals:
        if v is None:
            out.append(" ")
        else:
            v = min(max(v, 0.0), 1.0)
            out.append(RAMP[min(int(v * len(RAMP)), len(RAMP) - 1)])
    return "".join(out)


def _worst_burn(row: dict) -> Optional[float]:
    burn = row.get("slo_burn") or {}
    rates = [
        r
        for windows in burn.values()
        for r in windows.values()
        if r is not None
    ]
    return max(rates) if rates else None


def render_plain(payload: dict, width: int = 78) -> str:
    """The whole fleet view as plain text — what curses mode paints line
    by line and what ``--plain``/tests print verbatim."""
    lines = []
    if not payload.get("enabled", False):
        return "kvtop: federation disabled (start the scorer with OBS_FED=1)"
    fleet = payload.get("fleet") or {}
    score = fleet.get("health_score")
    history = payload.get("history") or []
    lines.append(
        f"kvtop — fleet seq {payload.get('seq', '?')}"
        f"  pods {fleet.get('pods_ok', 0)} ok"
        f" / {fleet.get('pods_failed', 0)} failed"
        f"  scrape {payload.get('scrape_s', 0.0) * 1e3:.1f}ms"
    )
    lines.append(
        "health "
        + (f"{score:.2f} " if score is not None else " --  ")
        + sparkline([h.get("health_score") for h in history])
    )
    for tier, t in (fleet.get("tiers") or {}).items():
        lines.append(
            f"  fleet {tier:<10} {_bar(t.get('fill'))}"
            f"  {t.get('used', 0)}/{t.get('total', 0)} pages"
        )
    lines.append("-" * width)
    # -- pods x tiers heat view ---------------------------------------------
    pods = payload.get("pods") or {}
    tenant_burn_total: dict[str, float] = {}
    for name in sorted(pods):
        row = pods[name]
        if not row.get("ok"):
            why = row.get("skipped") or row.get("error") or "unreachable"
            lines.append(f"{name:<16} DOWN ({why})")
            continue
        flags = []
        if row.get("draining"):
            flags.append("DRAINING")
        open_breakers = [
            ep for ep, st in (row.get("breakers") or {}).items()
            if st == "open"
        ]
        if open_breakers:
            flags.append(f"breaker:{','.join(sorted(open_breakers))}")
        if (row.get("quarantine") or {}).get("quarantined", 0) > 0:
            flags.append("QUARANTINE")
        burn = _worst_burn(row)
        if burn is not None and burn >= 1.0:
            flags.append(f"BURN {burn:.1f}x")
        queue = row.get("queue") or {}
        lines.append(
            f"{name:<16}"
            f" q {queue.get('waiting') or 0:>3}+{queue.get('running') or 0:<3}"
            f" behind {row.get('events_behind', 0):>3}"
            + (f"  {' '.join(flags)}" if flags else "")
        )
        for tier, t in (row.get("tiers") or {}).items():
            lines.append(f"    {tier:<10} {_bar(t.get('fill'))}")
        for tenant, windows in (row.get("tenant_burn") or {}).items():
            rates = [
                r
                for objs in windows.values()
                for r in objs.values()
                if r is not None
            ] if isinstance(windows, dict) else []
            if rates:
                tenant_burn_total[tenant] = max(
                    tenant_burn_total.get(tenant, 0.0), max(rates)
                )
    # -- top tenants by burn -------------------------------------------------
    if tenant_burn_total:
        lines.append("-" * width)
        lines.append("top tenants by SLO burn:")
        ranked = sorted(
            tenant_burn_total.items(), key=lambda kv: -kv[1]
        )[:5]
        for tenant, burn in ranked:
            lines.append(f"  {tenant:<24} {burn:6.2f}x")
    # -- flight-recorder events ----------------------------------------------
    flights = {
        name: row["flight"]
        for name, row in pods.items()
        if row.get("ok") and row.get("flight")
    }
    if flights:
        lines.append("-" * width)
        lines.append("flight recorders:")
        for name in sorted(flights):
            fl = flights[name]
            lines.append(
                f"  {name:<16} triggers {fl.get('triggers', 0)}"
                f"  events {fl.get('events_recorded', 0)}"
                f"  dumps {fl.get('dumps_written', 0)}"
            )
    return "\n".join(lines)
