"""Milliseconds of one part of an admission, an admission: the seconds of
the spans ``admit.<part>`` (``Engine.part``; the suffix is one of
``server/engine.py``'s ``ADMIT_PARTS``: ``hash``, ``walk``, ``window``,
``state``, ``pages``, ``rollback``) of the first chip's replica inside the
traced seconds, over the count of ``admit`` spans (one a call of
``BlockManager.allocate``). ``admit_ms.other`` is the ``admit`` spans' time
under no part, so a cell's parts and ``other`` sum to the mean ``admit`` span
(and what the scheduler's roll-backs took, which follow theirs).
``chipbench/admit_times.py`` has how the spans are read. 0.0 for a part the
traced admissions have no span of; None for a program without the spans."""

from chipbench import admit_times


def read(run, part):
    found = admit_times.of_run(run)
    if found is None:
        return None
    return found["ns"].get(part, 0.0) / found["admits"] / 1e6
