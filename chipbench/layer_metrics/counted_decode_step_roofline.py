"""The least time a decode step could take over the time it took, from the
program's own counts alone: ``costs.decode_step_min_bytes`` fed the real
lanes a forward (``decode_rows`` / ``decode_dispatches``), their mean context
(``attn_ctx_tokens`` over lanes x forwards) and, for a routed model, the
distinct experts a layer a forward the program counted on the device
(``decode_experts_touched_mean``), / the chip's HBM bandwidth, over the mean
device time of the module ``decode_steps`` in the trace (a dispatch's fused
steps times the bytes of one). No expectation over independent draws, no 10
Hz sample, no request lengths. The counters are the window's: read at its
close (``run.py``, ``on_close``), as the traced steps are. Bound: HBM
bandwidth. None where the program does not count (one from before the
counters) or the trace holds no such module."""

from chipbench import costs, program_counts, trace_reduce

MODULE = "decode_steps"


def read(run):
    if run.trace is None:
        return None
    counts = program_counts.deltas(run)
    step_s = trace_reduce.module_mean_s(run.trace, MODULE)
    if counts is None or not step_s:
        return None
    dispatches, forwards = counts["decode_dispatches"], counts["decode_forwards"]
    if not dispatches or not forwards or not counts["decode_rows"]:
        return None
    cfg = run.model_cfg
    experts = None
    if cfg.n_experts:
        experts = program_counts.experts_touched_per_layer(counts)
        if experts is None:
            return None
    lanes = counts["decode_rows"] / dispatches
    steps = forwards / dispatches
    least_s = steps * costs.decode_step_min_bytes(
        cfg, lanes, counts["attn_ctx_tokens"] / (lanes * forwards),
        experts_touched=experts,
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
