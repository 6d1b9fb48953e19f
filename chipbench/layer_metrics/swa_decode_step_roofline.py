"""The least time a decode step of a window-and-full model that holds a share of
its experts could take over the time it took: ``costs_swa.
decode_step_min_s`` (the larger of the step's bytes over the HBM bandwidth
and its FLOPs over the bf16 peak) fed ONLY what the program counted: the real
lanes a forward (``decode_rows`` / ``decode_dispatches``), the positions a
full layer read of the context pool a forward (``attn_ctx_tokens`` /
``decode_forwards``) and a sliding layer of the window pool
(``window_ctx_tokens``), the held experts a routed layer read
(``experts_touched``; every place on a held expert is a row of its grouped
matmuls: at most lanes x top-k), over the mean device time of the module
``decode_steps`` in the trace (a dispatch's fused steps times one step's
least): the share of the whole step. The counters are the window's: read at
its close (``run.py``, ``on_close``), as the traced steps are. None where the
program does not count (the parent of the PR that added the counters), the
model is no such model, or the trace holds no such module."""

from chipbench import costs_swa, swa_counts, trace_reduce

MODULE = "decode_steps"


def read(run):
    cfg = run.model_cfg
    if run.trace is None or not getattr(cfg, "sliding_window", 0):
        return None
    counts = swa_counts.deltas(run)
    step_s = trace_reduce.module_mean_s(run.trace, MODULE)
    if counts is None or not step_s or not counts["decode_dispatches"]:
        return None
    forwards = counts["decode_forwards"]
    if not forwards or not counts["routed_layers"]:
        return None
    lanes = counts["decode_rows"] / counts["decode_dispatches"]
    touched = counts["experts_touched"] / forwards / counts["routed_layers"]
    least_s = costs_swa.decode_step_min_s(
        cfg, run.peaks, lanes=lanes,
        ctx_tokens=counts["attn_ctx_tokens"] / forwards,
        window_tokens=counts["window_ctx_tokens"] / forwards,
        experts_touched=touched,
        # the rows the held experts' grouped matmuls computed are not counted
        # apart for a sigmoid router's share: one a touched expert at least
        held_rows=touched,
    )
    steps = forwards / counts["decode_dispatches"]
    return 100.0 * steps * least_s / step_s
