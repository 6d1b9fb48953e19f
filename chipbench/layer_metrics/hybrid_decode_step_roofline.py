"""The least time a decode step of a hybrid model (convolution layers with some
that attend) could take over the time it took, from the program's own counts
alone: ``costs_hybrid.decode_step_min_bytes`` (operator weights by kind, the
dense FFNs, the experts read, the head, the live keys and values of the
attention layers ALONE, one state slot a convolution layer a lane) fed the
real lanes a forward (``decode_rows`` / ``decode_dispatches``), their context
rows a forward (``attn_ctx_tokens`` / ``decode_forwards``) and the distinct
experts a routed layer a forward the program counted on the device
(``decode_experts_touched_mean``), / the chip's HBM bandwidth, over the mean
device time of the module ``decode_steps`` in the trace (a dispatch's fused
steps times the bytes of one). No expectation over a router's draws (it reads
31.6 of 32 experts a layer where the program counts 25-27), no 10 Hz sample,
no request lengths. The counters are the window's: read at its close
(``run.py``, ``on_close``). Bound: HBM bandwidth. None where the program does
not count (one from before the counters), the model has no convolution layer,
or the trace holds no such module."""

from chipbench import costs_hybrid, program_counts, trace_reduce

MODULE = "decode_steps"


def read(run):
    cfg = run.model_cfg
    if run.trace is None or "conv" not in (
            getattr(cfg, "layer_types", None) or ()):
        return None
    counts = program_counts.deltas(run)
    step_s = trace_reduce.module_mean_s(run.trace, MODULE)
    if counts is None or not step_s:
        return None
    dispatches, forwards = counts["decode_dispatches"], counts["decode_forwards"]
    experts = program_counts.experts_touched_per_layer(counts)
    if not dispatches or not counts["attn_ctx_tokens"] or experts is None:
        return None
    least_s = forwards / dispatches * costs_hybrid.decode_step_min_bytes(
        cfg, counts["decode_rows"] / dispatches,
        counts["attn_ctx_tokens"] / forwards, experts_touched=experts,
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
