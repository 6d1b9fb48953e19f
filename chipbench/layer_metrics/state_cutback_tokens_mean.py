"""Tokens of a context-pool hit that an admission prefilled again because
the hit was cut back to the last boundary whose state snapshot was held: the
growth of ``/stats``' ``state_cutback_tokens`` over that of
``state_admissions`` in the window. With threads whose lengths are no
multiples of the stride this is the price of the stride (about 350 tokens an
admission at 512 in ``threads``); more says snapshots were lost
(``state_cutback_lost_share``). None where the program does not report the
counts or admitted nothing."""

from chipbench import kda_counts


def read(run):
    counts = kda_counts.pool_deltas(run)
    if counts is None or not counts["state_admissions"]:
        return None
    return counts["state_cutback_tokens"] / counts["state_admissions"]
