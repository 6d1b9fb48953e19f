"""Whose time the chip's idle time is: the share of the first chip's idle
time, inside the trace's own span, during which a span ``engine.<phase>``
of that chip's own replica was open on the host (``Engine.phase``; the
suffix is the phase). ``idle_gap_share.unattributed`` is the rest — idle
time under no span of the program — so a cell's entries sum to 100.

The reduced trace keeps ten gaps only, so the planes are loaded again from
the run's trace directory. The spans of one engine loop tile its time and
never overlap; the runtime's own events (``np.asarray``, ``PjitFunction``)
and another replica's spans are not read. Never reported off the chip."""

import os

from chipbench import trace_reduce

TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "out", "trace"
)
SPAN_PREFIX = "engine."
CACHE = "idle_gap_share"  # on run.trace: one load serves every suffix


def shares(planes) -> dict:
    """{phase: % of the first chip's idle time, ..., "unattributed": %};
    empty where the trace has no device plane, no idle time, or no span of
    the program (one that predates them)."""
    devs = trace_reduce.device_planes(planes)
    if not devs:
        return {}
    # "/device:TPU:0" -> "tpu:0", as Engine.replica says it
    replica = devs[0]["name"][len("/device:"):].lower()
    first, last, spans = float("inf"), float("-inf"), []
    for plane in planes:
        host = plane["name"].startswith(trace_reduce.HOST_PREFIX)
        if not (host or plane["name"].startswith(trace_reduce.DEVICE_PREFIX)):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                start, end = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
                first, last = min(first, start), max(last, end)
                if (host and ev["name"].startswith(SPAN_PREFIX)
                        and f"replica={replica}" in ev["text"].split()):
                    spans.append((start, end, ev["name"][len(SPAN_PREFIX):]))
    if not spans:
        return {}
    spans.sort()
    ops = next(line for line in devs[0]["lines"]
               if line["name"] == trace_reduce.OPS_LINE)
    busy = trace_reduce.union(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops["events"]
    )
    # the trace's own span, as device_idle_share takes it
    edges = [first] + [t for span in busy for t in span] + [last]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    idle_ns = sum(b - a for a, b in idle)
    if not idle_ns:
        return {}
    out, i = {}, 0
    for a, b in idle:  # both lists are sorted and neither overlaps itself
        while i < len(spans) and spans[i][1] <= a:
            i += 1
        j = i
        while j < len(spans) and spans[j][0] < b:
            start, end, phase = spans[j]
            out[phase] = out.get(phase, 0.0) + max(0.0, min(b, end) - max(a, start))
            j += 1
    out = {phase: 100.0 * ns / idle_ns for phase, ns in out.items()}
    out["unattributed"] = 100.0 - sum(out.values())
    return out


def read(run, phase):
    if run.trace is None or not run.trace["chips"]:
        return None
    if CACHE not in run.trace:
        run.trace[CACHE] = shares(trace_reduce.load(TRACE_DIR))
    found = run.trace[CACHE]
    return found.get(phase, 0.0) if found else None
