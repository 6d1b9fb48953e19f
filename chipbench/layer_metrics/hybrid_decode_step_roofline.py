"""The least time a decode step of a hybrid model (convolution layers with
some that attend) could take over the time it took: the bytes the step must
read (``costs_hybrid.decode_step_min_bytes``: operator weights by kind, the
dense FFNs, the expected experts for the dispatch's real lanes, the head, the
live keys and values of the attention layers ALONE, one state slot a
convolution layer a lane) / the chip's HBM bandwidth, over the mean device
time of the module ``decode_steps`` in the trace. Bound: HBM bandwidth.

Lanes and context a dispatch are the program's own counts
(``step_stats["decode_rows"]`` and ``["attn_ctx_tokens"]`` over
``["decode_dispatches"]``), not an estimate from the requests. The counters
are read after the window's close, not at it (PERF.md 7 (g)): the dispatches
of the emptying tail (fewer lanes, so fewer experts and less context a step)
are averaged with the window's, so the bytes a step read LOW against the
traced steps, all of which lie inside the window, and the share reads low by
the same few per cent ``mla_decode_roofline`` does. None where the program
does not count the context (a program from before the counter), the model
has no convolution layer, or the trace holds no such module."""

from chipbench import costs_hybrid, trace_reduce

MODULE = "decode_steps"
KEYS = ("attn_ctx_tokens", "decode_rows", "decode_dispatches")


def read(run):
    cfg = run.model_cfg
    if run.trace is None or not getattr(cfg, "layer_types", None):
        return None
    totals = dict.fromkeys(KEYS, 0)
    for after, before in zip(run.step_after, run.step_before):
        if any(k not in after or k not in before for k in KEYS):
            return None
        for k in KEYS:
            totals[k] += after[k] - before[k]
    step_s = trace_reduce.module_mean_s(run.trace, MODULE)
    dispatches = totals["decode_dispatches"]
    if not step_s or not dispatches or not totals["attn_ctx_tokens"]:
        return None
    least_s = costs_hybrid.decode_step_min_bytes(
        cfg, totals["decode_rows"] / dispatches,
        totals["attn_ctx_tokens"] / dispatches,
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
