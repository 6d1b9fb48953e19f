"""From the profiler's ``.xplane.pb`` to numbers: busy/idle share of each
chip, device time per operation and per jitted module, the longest idle
gaps and what the host was doing in them.

The reduction works on a plain form, so that tests can hand it a trace
made by hand:

    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops",
                          "events": [{"name", "start_ns", "dur_ns",
                                      "text"}]}]}]

``text`` is the event's name plus its string stats (``long_name``,
``tf_op``, ...): kernel names are matched against it, because the trace
shows a Pallas kernel under its custom call's HLO name and carries the
kernel's own name only in the stats.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "/host:"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def planes_from_profile(profile) -> list[dict]:
    """``jax.profiler.ProfileData`` -> the plain form."""
    planes = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                text = [ev.name]
                for key, value in ev.stats:
                    if isinstance(value, str):
                        text.append(f"{key}={value}")
                events.append({
                    "name": ev.name, "start_ns": float(ev.start_ns),
                    "dur_ns": float(ev.duration_ns), "text": " ".join(text),
                })
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def load(trace_dir: str) -> list[dict]:
    from jax.profiler import ProfileData

    return planes_from_profile(ProfileData.from_file(find_xplane(trace_dir)))


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def _line(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def device_planes(planes) -> list[dict]:
    return [p for p in planes
            if p["name"].startswith(DEVICE_PREFIX) and _line(p, OPS_LINE)]


def _host_events(planes):
    for p in planes:
        if p["name"].startswith(HOST_PREFIX):
            for line in p["lines"]:
                for ev in line["events"]:
                    yield line["name"], ev


def span_s(planes) -> float:
    """The traced window on the trace's own clock: from the first event to
    the last, over the device planes and the host planes. Busy time is read
    from the same trace, so the idle share has one clock."""
    starts, ends = [], []
    for p in planes:
        if not p["name"].startswith((DEVICE_PREFIX, HOST_PREFIX)):
            continue
        for line in p["lines"]:
            for e in line["events"]:
                starts.append(e["start_ns"])
                ends.append(e["start_ns"] + e["dur_ns"])
    return (max(ends) - min(starts)) / 1e9 if starts else 0.0


def reduce(planes, top: int = 10) -> dict:
    """busy_s (mean over chips), window_s (``span_s``), per-op and
    per-module device seconds (mean over chips), and the longest idle gaps
    of the first chip, each named by the host event that overlaps it most
    (or ``unattributed``)."""
    devs = device_planes(planes)
    window_s = span_s(planes)
    if not devs:
        return {"chips": 0, "busy_s": 0.0, "window_s": window_s, "ops": {},
                "ops_text": {}, "modules": {}, "module_calls": {},
                "idle_gaps": [], "device_ops": []}
    n = len(devs)
    busy, ops, ops_text, modules, calls = 0.0, {}, {}, {}, {}
    for p in devs:
        events = _line(p, OPS_LINE)["events"]
        merged = union(
            (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
        )
        busy += sum(end - start for start, end in merged) / 1e9
        for e in events:
            ops[e["name"]] = ops.get(e["name"], 0.0) + e["dur_ns"] / 1e9 / n
            ops_text.setdefault(e["name"], e["text"])
        mod_line = _line(p, MODULES_LINE)
        for e in (mod_line["events"] if mod_line else ()):
            # "jit_decode_steps(1234567)" -> "jit_decode_steps"
            name = e["name"].split("(")[0]
            modules[name] = modules.get(name, 0.0) + e["dur_ns"] / 1e9 / n
            calls[name] = calls.get(name, 0) + 1.0 / n

    first = union(
        (e["start_ns"], e["start_ns"] + e["dur_ns"])
        for e in _line(devs[0], OPS_LINE)["events"]
    )
    gaps = sorted(
        ((b[0] - a[1], a[1], b[0]) for a, b in zip(first, first[1:])),
        reverse=True,
    )[:top]
    host = list(_host_events(planes))
    idle_gaps = []
    for length, start, end in gaps:
        best, best_overlap = "unattributed", 0.0
        for line_name, ev in host:
            lo = max(start, ev["start_ns"])
            hi = min(end, ev["start_ns"] + ev["dur_ns"])
            if hi - lo > best_overlap:
                best, best_overlap = f"{line_name}:{ev['name']}", hi - lo
        idle_gaps.append([best[:120], length / 1e9])
    kinds = {}
    for name, secs in ops.items():
        kind = op_kind(name)
        kinds[kind] = kinds.get(kind, 0.0) + secs
    device_ops = [[kind, secs] for kind, secs in
                  sorted(kinds.items(), key=lambda kv: -kv[1])[:top]]
    return {"chips": n, "busy_s": busy / n, "window_s": window_s,
            "ops": ops, "ops_text": ops_text, "modules": modules,
            "module_calls": calls, "idle_gaps": idle_gaps,
            "device_ops": device_ops}


def op_kind(name: str) -> str:
    """``%paged_attention.7 = bf16[16,8,8,128]{...} custom-call(...)`` ->
    ``paged_attention bf16[16,8,8,128]``: the instruction's name without its
    number, and the first array type of its result."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    return f"{base} {shape.group(0)}" if shape else base


def idle_share(reduced: dict) -> float:
    return 1.0 - reduced["busy_s"] / reduced["window_s"]


def time_matching(reduced: dict, needle: str) -> float:
    """Device seconds (mean over chips) of the operations whose name or
    string stats carry ``needle``."""
    return sum(secs for name, secs in reduced["ops"].items()
               if needle in reduced["ops_text"].get(name, name))


def module_mean_s(reduced: dict, needle: str):
    """Mean device seconds of one call of the modules whose name carries
    ``needle``; None where the trace has none."""
    secs = sum(v for k, v in reduced["modules"].items() if needle in k)
    n = sum(v for k, v in reduced["module_calls"].items() if needle in k)
    return secs / n if n else None
