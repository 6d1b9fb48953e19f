"""What the readers of a window model's counters share: ``program_counts``'
window growth of the fused decode path's counters (``Engine.step_stats``, on
in the traced run only, all replicas together) with ``window_ctx_tokens``
(the positions the sliding layers of the dispatches' real lanes read: the sum
of ``min(context, window)``) beside ``attn_ctx_tokens`` (a full layer's), and
the growth of ``/stats``' window-pool counts. None where the program does
not count one of them (a program from before the counters, as the parent of
the PR that added them)."""

from chipbench import program_counts

STEP_KEY = "window_ctx_tokens"
POOL_KEYS = ("window_pages_dropped", "window_pages_evicted",
             "window_short_hits", "window_short_hit_tokens")


def deltas(run):
    """``program_counts.deltas`` with ``window_ctx_tokens``; None without."""
    out = program_counts.deltas(run)
    if out is None:
        return None
    out[STEP_KEY] = 0
    for after, before in zip(run.step_after, run.step_before):
        if STEP_KEY not in after or STEP_KEY not in before:
            return None
        out[STEP_KEY] += after[STEP_KEY] - before[STEP_KEY]
    return out


def pool_deltas(run):
    """The window's growth of the block manager's monotone window counts,
    all replicas together; None where ``/stats`` does not carry them."""
    out = dict.fromkeys(POOL_KEYS, 0)
    for after, before in zip(run.stats_after, run.stats_before):
        for key in POOL_KEYS:
            if key not in after or key not in before:
                return None
            out[key] += after[key] - before[key]
    return out
