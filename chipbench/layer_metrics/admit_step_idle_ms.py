"""What a step that admits costs the chip, by phase: the first chip's idle
milliseconds, inside the traced seconds, under the ``engine.<phase>`` spans
of the steps (the spans' ``step``) that hold an ``admit`` span a roll-back
did not undo, a step. The suffix is ``schedule``, ``prefill_build``,
``prefill_put`` or ``rest`` (every other phase of such a step), so a cell's
four sum to the idle time of an admitting step. ``chipbench/admit_times.py``
has how the spans are read. None for a program without the spans, and
where no traced step admitted."""

from chipbench import admit_times


def read(run, phase):
    found = admit_times.of_run(run)
    if found is None or not found["step_idle"]["steps"]:
        return None
    idle = found["step_idle"]
    return idle["ns"][phase] / idle["steps"] / 1e6
