"""Mean wall time of one phase of the engine loop per engine step:
``Engine.step_stats[<phase>_s]`` over ``steps``, window's end less window's
start, all replicas together (switched on for the traced run only). The
suffix is the phase (``server/engine.py``'s ``STEP_PHASES``), or ``prefill``
for its five summed. Per step of any kind, so a cell's entries add up to the
loop's period: ``step_ms_mean`` + ``step_phase_ms.loop``. The ``*_fetch``
phases wait for the device; the others are host work. A host time."""


def read(run, phase):
    key = f"{phase}_s"
    secs = steps = 0.0
    for after, before in zip(run.step_after, run.step_before):
        if key not in after or key not in before:
            return None  # a program without this phase
        secs += after[key] - before[key]
        steps += after["steps"] - before["steps"]
    return 1e3 * secs / steps if steps else None
