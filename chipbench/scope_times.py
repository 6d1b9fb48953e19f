"""What the readers of the model's named scopes share: device time of the
operations inside one kind of module (``decode_steps``, ``denoise_steps``,
``prefill``), by the part of the model they belong to.

The program puts ``jax.named_scope("model.<part>")`` around the model's
parts (``models/llama.py``: ``MODEL_SCOPES``); the compiler keeps the scope
in each instruction's ``op_name``, and a v5e's trace carries that as the
string stat ``tf_op`` (``jit(decode_steps)/model.moe_router/reduce_sum:``)
of the event's METADATA, which ``jax.profiler.ProfileData``, and so
``trace_reduce.planes_from_profile``, does not hand out:
``xplane_meta.load`` reads it from the same file, by plane and event name,
and it is read here beside the event's own ``text``. An event belongs to the
part whose ``model.<part>`` stands LAST in the two (the innermost scope), a
fusion to that of the instruction the compiler named it after. The
compiler's own asynchronous copies and slices (``copy-start``,
``slice-done``: weights fetched ahead) carry no ``tf_op`` and so no part.
An operation belongs to the module event that contains its start on the
same plane. Where events nest (a ``while`` or a ``conditional`` around its
body's operations, a fusion around a prefetch's ``custom-call``), a stretch
of time goes to the innermost event that has a part, so no nanosecond is
counted twice and the parts, with ``unscoped`` (busy time under no part),
sum to the modules' busy time.

``trace_reduce.reduce`` keeps one text a name and sums over modules, so the
planes are loaded again from the run's trace directory: once a run, for
every reader, under this file's own key on ``run.trace``."""

from __future__ import annotations

import bisect
import os
import re

from chipbench import trace_reduce, xplane_meta

TRACE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "out", "trace"
)
CACHE = "scope_times"  # on run.trace: one load serves every reader
UNSCOPED = "unscoped"
SCOPE = re.compile(r"model\.([a-z_]+)")


def model_scopes():
    """The program's own tuple of names; None for a program without them."""
    from llm_d_kv_cache_manager_tpu.models import llama

    return getattr(llama, "MODEL_SCOPES", None)


def part_of(text: str, scopes) -> str | None:
    found = [p for p in SCOPE.findall(text) if p in scopes]
    return found[-1] if found else None


def load_trace(trace_dir: str = TRACE_DIR) -> tuple[list, dict]:
    """(the run's planes, ``xplane_meta.load``'s texts of the same file)."""
    return (trace_reduce.load(trace_dir),
            xplane_meta.load(trace_reduce.find_xplane(trace_dir)))


def _self_times(events, part_of_event, out: dict) -> None:
    """Adds to ``out`` the nanoseconds of ``events`` (one module call's,
    sorted by start) by part: each instant to the innermost open event that
    has a part, else to ``unscoped``."""
    stack = []  # (end, part in force while this event is the innermost)
    cursor = 0.0

    def advance(to: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= to:
            end, part = stack.pop()
            if end > cursor:
                out[part] = out.get(part, 0.0) + end - cursor
                cursor = end
        if stack and to > cursor:
            part = stack[-1][1]
            out[part] = out.get(part, 0.0) + to - cursor
        cursor = max(cursor, to)

    for ev in events:
        start = ev["start_ns"]
        advance(start)
        part = part_of_event(ev) or (stack[-1][1] if stack else UNSCOPED)
        stack.append((start + ev["dur_ns"], part))
    advance(float("inf"))


def module_parts(planes, needle: str, scopes, meta_texts=None) -> dict | None:
    """``{"calls": module calls, "ns": {part: nanoseconds, "unscoped": ...}}``
    over the modules whose name carries ``needle``, mean over the chips;
    None where the trace has no such module or none of its operations has a
    part (a program from before the scopes). ``meta_texts``: ``xplane_meta.
    load``'s, read beside each event's own ``text``."""
    devs = trace_reduce.device_planes(planes)
    calls, ns = 0, {}
    for plane in devs:
        texts = (meta_texts or {}).get(plane["name"], {})
        known = {}  # an instruction runs once a call: one search a text

        def part_of_event(ev, texts=texts, known=known):
            key = ev["text"]
            if key not in known:
                known[key] = part_of(
                    f"{key} {texts.get(ev['name'], '')}", scopes)
            return known[key]

        mods = next((line["events"] for line in plane["lines"]
                     if line["name"] == trace_reduce.MODULES_LINE), ())
        mods = sorted((m["start_ns"], m["start_ns"] + m["dur_ns"])
                      for m in mods if needle in m["name"].split("(")[0])
        if not mods:
            continue
        calls += len(mods)
        starts = [m[0] for m in mods]
        inside = [[] for _ in mods]
        ops = next(line["events"] for line in plane["lines"]
                   if line["name"] == trace_reduce.OPS_LINE)
        for ev in ops:
            i = bisect.bisect_right(starts, ev["start_ns"]) - 1
            if i >= 0 and ev["start_ns"] < mods[i][1]:
                inside[i].append(ev)
        for events in inside:
            events.sort(key=lambda e: (e["start_ns"], -e["dur_ns"]))
            _self_times(events, part_of_event, ns)
    if not calls or not any(part != UNSCOPED for part in ns):
        return None
    n = len(devs)
    return {"calls": calls / n, "ns": {p: v / n for p, v in ns.items()}}


def decode_module(cfg) -> str:
    """The name of the cell's decode-side module."""
    return "denoise_steps" if getattr(cfg, "block_length", 0) else "decode_steps"


def of_run(run) -> dict:
    """``{"decode": module_parts(...) or None, "prefill": ...}`` of a traced
    run, computed once; every entry None for a program without the scopes."""
    if CACHE not in run.trace:
        scopes = model_scopes()
        found = {"decode": None, "prefill": None}
        if scopes:
            planes, texts = load_trace()
            found = {
                "decode": module_parts(
                    planes, decode_module(run.model_cfg), scopes, texts),
                "prefill": module_parts(planes, "prefill", scopes, texts),
            }
        run.trace[CACHE] = found
    return run.trace[CACHE]


def part_ms(run, side: str, part: str):
    """Milliseconds of ``part`` (one of ``MODEL_SCOPES``, or ``unscoped``) a
    call of the ``side`` (``decode`` / ``prefill``) modules; 0.0 for a part
    the traced calls have no operation of; None off the chip, without a
    trace, or for a program without the scopes."""
    if run.trace is None or not run.trace["chips"]:
        return None
    found = of_run(run)[side]
    if found is None:
        return None
    return found["ns"].get(part, 0.0) / found["calls"] / 1e6
