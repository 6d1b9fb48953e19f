"""What ``Engine.phase`` and ``Engine.part`` hand out with
``obs_step_timing`` off, kept below the modules that time themselves so
that the block manager and the scheduler, which the engine imports, can
name it too."""

from __future__ import annotations


class _NoPhase:
    """One shared object: no clock read, no allocation."""

    __slots__ = ()

    def __enter__(self) -> "_NoPhase":
        return self

    def __exit__(self, *_exc) -> None:
        pass

    def add(self, **_counts) -> None:
        pass


NO_PHASE = _NoPhase()


def no_part(name: str = "", **_stats) -> _NoPhase:
    """``Engine.part`` of a ``BlockManager`` or ``Scheduler`` that no engine
    owns (an engine sets its own at construction)."""
    return NO_PHASE
