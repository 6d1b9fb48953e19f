"""Forwards a block costs a lane, mean over the window: all lane forwards
over the blocks that became final (``block_counters``): the denoising
forwards a block's schedule and threshold ask for, plus the committing
one."""

from chipbench import block_counters


def read(run):
    d = block_counters.deltas(run)
    return d["lane_forwards"] / d["blocks_final"] if d and d["blocks_final"] else None
