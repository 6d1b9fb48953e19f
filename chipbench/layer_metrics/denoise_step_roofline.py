"""The least time a denoising forward could take over the time it took:
bytes the forward must read (``costs_block_diffusion.denoise_forward_min_
bytes``: the weights its lanes x block_length rows touch, the head, the
window's mean live KV) / the chip's HBM bandwidth, over the mean device time
of the module ``denoise_steps`` in the trace. The experts among the weights
are those the program counted on the device (``block_counters.experts_
touched_per_layer``), not an expectation: rows do not route independently.
Bound: HBM bandwidth."""

from chipbench import block_counters
from chipbench import costs_block_diffusion as costs_bd
from chipbench import trace_reduce

MODULE = "denoise_steps"


def read(run):
    cfg = run.model_cfg
    if run.trace is None or not getattr(cfg, "block_length", 0):
        return None
    step_s = trace_reduce.module_mean_s(run.trace, MODULE)
    if not step_s:
        return None
    experts = None
    if cfg.n_experts:
        experts = block_counters.experts_touched_per_layer(run)
        if experts is None:
            return None
    lanes, mean_ctx = costs_bd.live_lanes_and_context(run)
    least_s = costs_bd.denoise_forward_min_bytes(
        cfg, lanes, mean_ctx, experts) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
