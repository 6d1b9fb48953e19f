"""Share of lane forwards that fix no token and only store a final block's
keys and values (``commit_lane_forwards`` over all lane forwards,
``block_counters``), in per cent: what fusing the commit with the next
block's first denoising forward would save."""

from chipbench import block_counters


def read(run):
    d = block_counters.deltas(run)
    return d and 100.0 * d["commit_lane_forwards"] / d["lane_forwards"]
