"""Requests whose prefill ran in the window over prefill dispatches: real
rows of the max_prefill_batch (8) rows every dispatch computes. Counts
from /stats before and after."""


def read(run):
    dispatches = sum(
        a["prefill"]["dispatches"] - b["prefill"]["dispatches"]
        for a, b in zip(run.stats_after, run.stats_before)
    )
    if not dispatches:
        return None
    return (len(run.good) + len(run.failed) + len(run.in_flight)) / dispatches
