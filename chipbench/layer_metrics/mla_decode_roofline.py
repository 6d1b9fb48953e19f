"""The least time the ``mla_decode`` kernel's calls of one decode step could
take over the time they took: the live lanes' latent rows in every attention
of the pool (``costs_mla.n_attentions``: two a double layer), as held
(``costs_mla.mla_decode_bytes`` of the context rows the program counted a
dispatch: ``step_stats["latent_ctx_tokens"]`` over ``decode_dispatches``) /
the chip's HBM bandwidth, over the kernel's device time in the trace divided
by the calls of the module ``decode_steps`` (one kernel call an attention a
step). Bound: HBM bandwidth (the matmuls of 32 heads against a row are 60
FLOP a byte, a quarter of the chip's ridge). None where the program does not
count the rows (a program from before the counter, or a model with no latent
pool) or the trace holds no such kernel."""

from chipbench import costs_mla, trace_reduce

KERNEL = "mla_decode"
MODULE = "decode_steps"
KEYS = ("latent_ctx_tokens", "decode_dispatches")


def read(run):
    if run.trace is None or not getattr(run.model_cfg, "kv_lora_rank", 0):
        return None
    tokens = dispatches = 0
    for after, before in zip(run.step_after, run.step_before):
        if any(k not in after or k not in before for k in KEYS):
            return None
        tokens += after[KEYS[0]] - before[KEYS[0]]
        dispatches += after[KEYS[1]] - before[KEYS[1]]
    calls = sum(n for name, n in run.trace["module_calls"].items()
                if MODULE in name)
    kernel_s = trace_reduce.time_matching(run.trace, KERNEL)
    if not tokens or not dispatches or not calls or not kernel_s:
        return None
    least_s = costs_mla.mla_decode_bytes(
        run.model_cfg, tokens / dispatches) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_s / calls)
