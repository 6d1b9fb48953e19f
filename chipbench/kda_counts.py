"""What the readers of a linear-attention model's counters share:
``scmoe_counts``' window growth of the fused decode path's counters (on in
the traced run only, all replicas together, with the places a routed layer
that is told what it holds counts), and the growth of ``/stats``' state-pool
counts (``StatePool.stats``). None where the program does not count one of
them (a program from before the counters, as the parent of the PR that added
them) or the model has no state pool of slots."""

from chipbench import scmoe_counts

POOL_KEYS = ("state_admissions", "state_snapshots_taken", "state_restores",
             "state_snapshots_evicted", "state_cutback_tokens",
             "state_cutback_lost")


def is_linear(run) -> bool:
    return bool(getattr(run.model_cfg, "kda_head_dim", 0))


def deltas(run):
    return scmoe_counts.deltas(run) if is_linear(run) else None


def pool_deltas(run):
    """The window's growth of the block manager's monotone state-pool
    counts, all replicas together; None where ``/stats`` does not carry
    them."""
    out = dict.fromkeys(POOL_KEYS, 0)
    for after, before in zip(run.stats_after, run.stats_before):
        for key in POOL_KEYS:
            if key not in after or key not in before:
                return None
            out[key] += after[key] - before[key]
    return out
