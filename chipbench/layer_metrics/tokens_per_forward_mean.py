"""Tokens a lane gains a forward, mean over the window: rows fixed
(``block_tokens_fixed``) over all lane forwards, denoising and committing
(``block_counters``). One token a lane a step is 1.0; ``denoising_steps`` 4
of ``block_length`` 4 reads 4 / 5, 2 reads 4 / 3."""

from chipbench import block_counters


def read(run):
    d = block_counters.deltas(run)
    return d and d["block_tokens_fixed"] / d["lane_forwards"]
