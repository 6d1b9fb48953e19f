"""The least time a decode step of a window-and-full model that holds every
expert could take over the time it took: ``costs_prerouted.
decode_step_min_s`` (the larger of the step's bytes over the HBM bandwidth
and its FLOPs over the bf16 peak) fed ONLY what the program counted: the real
lanes a forward (``decode_rows`` / ``decode_dispatches``), the positions a
full layer read of the context pool a forward (``attn_ctx_tokens`` /
``decode_forwards``) and a sliding layer of the window pool
(``window_ctx_tokens``), the experts a layer read (``experts_touched``), over
the mean device time of the module ``decode_steps`` in the trace (a
dispatch's fused steps times one step's least): the share of the whole step.
The counters are the window's: read at its close (``run.py``, ``on_close``),
as the traced steps are. None where the program does not count, the model is
no such model, or the trace holds no such module."""

from chipbench import costs_prerouted, swa_counts, trace_reduce

MODULE = "decode_steps"


def read(run):
    cfg = run.model_cfg
    if run.trace is None or not getattr(cfg, "sliding_window", 0):
        return None
    if not getattr(cfg, "router_before_attention", False):
        return None
    counts = swa_counts.deltas(run)
    step_s = trace_reduce.module_mean_s(run.trace, MODULE)
    if counts is None or not step_s or not counts["decode_dispatches"]:
        return None
    forwards = counts["decode_forwards"]
    if not forwards or not counts["routed_layers"]:
        return None
    least_s = costs_prerouted.decode_step_min_s(
        cfg, run.peaks,
        lanes=counts["decode_rows"] / counts["decode_dispatches"],
        ctx_tokens=counts["attn_ctx_tokens"] / forwards,
        window_tokens=counts["window_ctx_tokens"] / forwards,
        experts_touched=(
            counts["experts_touched"] / forwards / counts["routed_layers"]),
    )
    steps = forwards / counts["decode_dispatches"]
    return 100.0 * steps * least_s / step_s
