"""Prompt tokens of an admission: the ``tokens`` stat of the ``admit`` spans
(``Engine.part``, one a call of ``BlockManager.allocate``) of the first
chip's replica inside the traced seconds, mean over the spans: what
``admit_ms.hash`` is to be divided by, and a property of the traffic mix, not
of the program. None for a program without the spans."""

from chipbench import admit_times


def read(run):
    found = admit_times.of_run(run)
    if found is None:
        return None
    return found["tokens"] / found["admits"]
