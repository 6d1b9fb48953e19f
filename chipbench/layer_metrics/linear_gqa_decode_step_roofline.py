"""The least time a decode step of a hybrid of linear attention and GQA that
holds a share of its experts could take over the time it took:
``costs_kda_gqa.decode_step_min_s`` (the larger of the WHOLE step's bytes over
the HBM bandwidth and its FLOPs over the bf16 peak: weights, the lanes' K and
V contexts in the GQA layers, their state slots read and written in the
linear ones, the experts touched) fed ONLY what the program counted in ONE
model: the real lanes a forward (``decode_rows`` / ``decode_dispatches``),
their context rows a forward (``attn_ctx_tokens`` / ``decode_forwards``), the
held experts a routed layer read (``experts_touched``) and the rows its
grouped matmuls computed (``held_places``), over the mean device time of the
module ``decode_steps`` in the trace. The counters are the window's (read at
its close, as the traced steps are). None where the program does not count,
the model is no such model (a latent pool between its linear layers is
``linear_decode_step_roofline``'s), or the trace holds no such module."""

from chipbench import costs_kda_gqa, kda_counts, scmoe_counts, trace_reduce

MODULE = "decode_steps"


def read(run):
    if run.trace is None or getattr(run.model_cfg, "kv_lora_rank", 0):
        return None
    counts = kda_counts.deltas(run)
    step_s = trace_reduce.module_mean_s(run.trace, MODULE)
    if counts is None or not step_s or not counts["decode_dispatches"]:
        return None
    forwards = counts["decode_forwards"]
    per_layer = scmoe_counts.layer_forwards(counts)
    least_s = costs_kda_gqa.decode_step_min_s(
        run.model_cfg, run.peaks,
        lanes=counts["decode_rows"] / counts["decode_dispatches"],
        ctx_tokens=counts["attn_ctx_tokens"] / forwards,
        experts_touched=counts["experts_touched"] / per_layer,
        held_rows=counts["held_places"] / per_layer,
    )
    steps = forwards / counts["decode_dispatches"]
    return 100.0 * steps * least_s / step_s
