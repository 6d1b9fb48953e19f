"""What the readers of the admission's spans share: the spans ``admit`` and
``admit.<part>`` that the program opens inside ``engine.schedule``
(``Engine.part``; the parts are ``server/engine.py``'s ``ADMIT_PARTS``), read
from the traced seconds of a run, for the first chip's replica alone.

The spans carry ``step`` (the engine's step number, as every ``engine.*``
span does), ``replica`` and, on ``admit``, ``seq`` and ``tokens``. All but
``replica`` are integers, which ``trace_reduce.planes_from_profile`` leaves
out of an event's ``text``, so ``load`` hands the profile to that function
and then writes every stat into the text of the host's ``admit*`` and
``engine.*`` events: once a run, under this file's own key on ``run.trace``.
The form stays ``trace_reduce``'s plain form, so a test hands these functions
planes made by hand and ``idle_gap_share.shares`` reads the same planes.

Everything here is a time inside the traced window. A program without the
spans (one that predates them) gives ``{}``, and every reader ``None``."""

from __future__ import annotations

import bisect
import os
import re

from chipbench import trace_reduce

TRACE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "out", "trace"
)
CACHE = "admit_times"  # on run.trace: one load serves every reader
ADMIT = "admit"
PHASE_PREFIX = "engine."
OTHER = "other"  # ``admit`` less the parts inside it
REST = "rest"  # every phase of an admitting step that has no name below
STEP_PHASES_NAMED = ("schedule", "prefill_build", "prefill_put")
STAT = re.compile(r"(\w+)=(\S+)")


def _mine(name: str) -> bool:
    return name == ADMIT or name.startswith(ADMIT + ".")


def load(trace_dir: str = TRACE_DIR) -> list[dict]:
    """``trace_reduce.load``'s planes, the ``text`` of the host's ``admit*``
    and ``engine.*`` events given EVERY stat (``key=value``), the integer
    ones too: one read of the file, the benchmark's own walk over it, and
    one more over the host planes alone."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    planes = trace_reduce.planes_from_profile(profile)
    for plane, made in zip(profile.planes, planes):
        if not made["name"].startswith(trace_reduce.HOST_PREFIX):
            continue
        for line, made_line in zip(plane.lines, made["lines"]):
            for ev, event in zip(line.events, made_line["events"]):
                name = event["name"]
                if _mine(name) or name.startswith(PHASE_PREFIX):
                    event["text"] = " ".join(
                        [name] + [f"{k}={v}" for k, v in ev.stats])
    return planes


def spans_of(planes):
    """(the first chip's idle intervals inside the trace's own span, the
    ``admit*`` spans of its replica, the ``engine.*`` spans of its replica),
    spans as ``(start, end, name, stats)`` sorted by start; None where the
    trace has no device plane."""
    devs = trace_reduce.device_planes(planes)
    if not devs:
        return None
    # "/device:TPU:0" -> "tpu:0", as Engine.replica says it
    replica = devs[0]["name"][len("/device:"):].lower()
    first, last = float("inf"), float("-inf")
    admits, phases = [], []
    for plane in planes:
        host = plane["name"].startswith(trace_reduce.HOST_PREFIX)
        if not (host or plane["name"].startswith(trace_reduce.DEVICE_PREFIX)):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                start, end = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
                first, last = min(first, start), max(last, end)
                name = ev["name"]
                mine = _mine(name)
                if not (host and (mine or name.startswith(PHASE_PREFIX))):
                    continue
                stats = dict(STAT.findall(ev["text"]))
                if stats.get("replica") != replica:
                    continue
                (admits if mine else phases).append((start, end, name, stats))
    ops = next(line for line in devs[0]["lines"]
               if line["name"] == trace_reduce.OPS_LINE)
    busy = trace_reduce.union(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops["events"]
    )
    # the trace's own span, as device_idle_share and idle_gap_share take it
    edges = [first] + [t for span in busy for t in span] + [last]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return idle, sorted(admits), sorted(phases)


def parts(admits) -> dict:
    """``{"admits", "rollbacks", "tokens", "ns": {"admit", <part>...,
    "other"}}`` of the ``admit*`` spans: a part's nanoseconds are those of
    all its spans, ``other`` those of the ``admit`` spans under no part (a
    part lies inside the ``admit`` span that contains its start; the
    scheduler's roll-back follows its ``admit`` span and is inside none)."""
    whole = [s for s in admits if s[2] == ADMIT]
    ns = {ADMIT: sum(end - start for start, end, _, _ in whole)}
    starts = [s[0] for s in whole]
    inside = 0.0
    for start, end, name, _ in admits:
        if name == ADMIT:
            continue
        part = name[len(ADMIT) + 1:]
        ns[part] = ns.get(part, 0.0) + end - start
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < whole[i][1]:
            inside += end - start
    ns[OTHER] = ns[ADMIT] - inside
    return {
        "admits": len(whole),
        "rollbacks": sum(s[2] == ADMIT + ".rollback" for s in admits),
        "tokens": sum(int(s[3].get("tokens", 0)) for s in whole),
        "ns": ns,
    }


def admitting_steps(admits) -> set:
    """The steps (the spans' ``step``) that admitted a sequence: more
    ``admit`` spans than roll-backs, each of which undoes one of them."""
    stood = {}
    for _, _, name, stats in admits:
        if name == ADMIT:
            stood[stats.get("step")] = stood.get(stats.get("step"), 0) + 1
        elif name == ADMIT + ".rollback":
            stood[stats.get("step")] = stood.get(stats.get("step"), 0) - 1
    return {step for step, n in stood.items() if n > 0 and step is not None}


def step_idle(idle, admits, phases) -> dict:
    """``{"steps", "ns": {"schedule", "prefill_build", "prefill_put",
    "rest"}}``: the first chip's idle nanoseconds under the ``engine.*``
    spans of the admitting steps, by phase (``rest``: every other phase of
    such a step; the ``loop`` span that carries a step's number is the one
    BEFORE it)."""
    steps = admitting_steps(admits)
    ns = dict.fromkeys(STEP_PHASES_NAMED + (REST,), 0.0)
    i = 0
    mine = [p for p in phases if p[3].get("step") in steps]
    for a, b in idle:  # both lists are sorted and neither overlaps itself
        while i < len(mine) and mine[i][1] <= a:
            i += 1
        j = i
        while j < len(mine) and mine[j][0] < b:
            start, end, name, _ = mine[j]
            phase = name[len(PHASE_PREFIX):]
            key = phase if phase in STEP_PHASES_NAMED else REST
            ns[key] += max(0.0, min(b, end) - max(a, start))
            j += 1
    return {"steps": len(steps), "ns": ns}


def reduce(planes) -> dict:
    """``{**parts(...), "step_idle": step_idle(...)}``; ``{}`` where the
    trace has no device plane or no ``admit`` span."""
    found = spans_of(planes)
    if found is None:
        return {}
    idle, admits, phases = found
    out = parts(admits)
    if not out["admits"]:
        return {}
    out["step_idle"] = step_idle(idle, admits, phases)
    return out


def of_run(run):
    """``reduce`` of a traced run's planes, computed once; None off the
    chip, without a trace, or for a program without the spans."""
    if run.trace is None or not run.trace["chips"]:
        return None
    if CACHE not in run.trace:
        run.trace[CACHE] = reduce(load())
    return run.trace[CACHE] or None
