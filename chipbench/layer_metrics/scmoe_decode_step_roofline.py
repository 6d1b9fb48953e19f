"""The least time a decode step of a double-layer model that holds a share of
its experts could take over the time it took: ``costs_scmoe.
decode_step_min_s`` (the larger of the step's bytes over the HBM bandwidth
and its FLOPs over the bf16 peak) fed ONLY what the program counted: the real
lanes a forward (``decode_rows`` / ``decode_dispatches``), their context rows
a forward (``attn_ctx_tokens`` / ``decode_forwards``), the held experts a
routed layer read (``experts_touched``) and the rows its grouped matmuls
computed (``held_places``), over the mean device time of the module
``decode_steps`` in the trace (a dispatch's fused steps times one step's
least). No expectation over a router's draws, no 10 Hz sample, no request
lengths. The counters are the window's: read at its close (``run.py``,
``on_close``), as the traced steps are. None where the program does not count
(the parent of the PR that added the counters), the model is no such model,
or the trace holds no such module."""

from chipbench import costs_scmoe, scmoe_counts, trace_reduce

MODULE = "decode_steps"


def read(run):
    cfg = run.model_cfg
    if run.trace is None or not getattr(cfg, "double_layer", False):
        return None
    counts = scmoe_counts.deltas(run)
    step_s = trace_reduce.module_mean_s(run.trace, MODULE)
    if counts is None or not step_s or not counts["decode_dispatches"]:
        return None
    forwards = counts["decode_forwards"]
    per_layer = scmoe_counts.layer_forwards(counts)
    least_s = costs_scmoe.decode_step_min_s(
        cfg, run.peaks,
        lanes=counts["decode_rows"] / counts["decode_dispatches"],
        ctx_tokens=counts["attn_ctx_tokens"] / forwards,
        experts_touched=counts["experts_touched"] / per_layer,
        held_rows=counts["held_places"] / per_layer,
    )
    steps = forwards / counts["decode_dispatches"]
    return 100.0 * steps * least_s / step_s
