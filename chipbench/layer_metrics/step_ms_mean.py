"""Mean wall time of an engine step: the sum of Engine.step_stats' phases
over its steps, through the in-process pod handle (switched on for the
traced run only). A host time that ends in the sample fetch, not a device
time."""

PHASES = ("schedule_s", "prefill_s", "decode_s", "publish_s")


def read(run):
    secs = steps = 0.0
    for a, b in zip(run.step_after, run.step_before):
        secs += sum(a[k] - b[k] for k in PHASES)
        steps += a["steps"] - b["steps"]
    return 1e3 * secs / steps if steps else None
