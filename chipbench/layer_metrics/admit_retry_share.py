"""The share of admission attempts whose hashing was thrown away: the spans
``admit.rollback`` (an ``allocate`` out of pages, or the scheduler's when the
fresh suffix is over the step's budget) over the spans ``admit`` of the
first chip's replica inside the traced seconds, in per cent. None for a
program without the spans. No entry lists it yet: no cell's traffic rolls an
admission back, so it would read a constant 0 (PERF.md, section 3)."""

from chipbench import admit_times


def read(run):
    found = admit_times.of_run(run)
    if found is None:
        return None
    return 100.0 * found["rollbacks"] / found["admits"]
