"""The least time the ``block_attention`` kernel's calls of one forward
could take over the time they took: the live lanes' context keys and values
in every layer (``costs_block_diffusion.block_context_bytes``) / the chip's
HBM bandwidth, over the kernel's device time in the trace divided by the
calls of the module ``denoise_steps`` (one kernel call a layer a forward).
Bound: HBM bandwidth. The gather that lays the context out for the kernel is
another operation and not in the kernel's time."""

from chipbench import costs_block_diffusion as costs_bd
from chipbench import trace_reduce

KERNEL = "block_attention"
MODULE = "denoise_steps"


def read(run):
    if run.trace is None or not getattr(run.model_cfg, "block_length", 0):
        return None
    calls = sum(n for name, n in run.trace["module_calls"].items()
                if MODULE in name)
    kernel_s = trace_reduce.time_matching(run.trace, KERNEL)
    if not calls or not kernel_s:
        return None
    lanes, mean_ctx = costs_bd.live_lanes_and_context(run)
    least_s = costs_bd.block_context_bytes(
        run.model_cfg, lanes, mean_ctx) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_s / calls)
