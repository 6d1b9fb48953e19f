"""Admissions whose nearest snapshot boundary at or under their context-pool
hit had lost its snapshot (they were cut back further, or to position 0)
over the admissions of the window: the growth of ``/stats``'
``state_cutback_lost`` over that of ``state_admissions``. 0 in ``threads``:
anything else says the cell lost a resident thread's snapshot and prefilled
more than a stride of it again. What it cannot see: a scorer that sent the
request here for the pages counts it as a hit all the same. None where the
program does not report the counts or admitted nothing."""

from chipbench import kda_counts


def read(run):
    counts = kda_counts.pool_deltas(run)
    if counts is None or not counts["state_admissions"]:
        return None
    return 100.0 * counts["state_cutback_lost"] / counts["state_admissions"]
