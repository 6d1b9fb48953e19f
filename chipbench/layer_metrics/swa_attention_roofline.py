"""The least time the decode attention kernels' calls of one decode step
could take over the time they took: the keys and values the step's live
lanes must read (``costs_swa.attention_min_bytes``: the positions the program
counted a forward, ``attn_ctx_tokens`` x 4096 B in the one full layer and
``window_ctx_tokens`` x 4096 B in each sliding one) / the chip's HBM
bandwidth, over the device time of the kernels whose name holds
``paged_attention`` (the full layer's call and the sliding layers'
``paged_attention_window``: one kernel with one more static argument) divided
by the forwards the trace holds (the calls of the module ``decode_steps``
times the steps fused in each). Bound: HBM bandwidth. None where the program
does not count the positions (a program from before the counter, or a model
with no window pool) or the trace holds no such kernel."""

from chipbench import costs_swa, swa_counts, trace_reduce

KERNEL = "paged_attention"
MODULE = "decode_steps"


def read(run):
    if run.trace is None or not getattr(run.model_cfg, "sliding_window", 0):
        return None
    counts = swa_counts.deltas(run)
    calls = sum(n for name, n in run.trace["module_calls"].items()
                if MODULE in name)
    kernel_s = trace_reduce.time_matching(run.trace, KERNEL)
    if (counts is None or not counts["decode_forwards"]
            or not counts["decode_dispatches"] or not calls or not kernel_s):
        return None
    forwards = counts["decode_forwards"]
    least_s = costs_swa.attention_min_bytes(
        run.model_cfg, counts["attn_ctx_tokens"] / forwards,
        counts["window_ctx_tokens"] / forwards,
    ) / run.peaks["hbm_bytes_per_s"]
    steps = forwards / counts["decode_dispatches"]
    return 100.0 * least_s / (kernel_s / (calls * steps))
