"""The least time a decode step could take over the time it took: bytes the
step must read (costs.decode_step_min_bytes: the weights the batch touches,
with the expected number of distinct experts, + the window's mean live KV) / the chip's HBM bandwidth, over the
mean device time of the decode module in the trace. Bound: HBM bandwidth."""

from chipbench import costs, trace_reduce

MODULE = "decode_steps"


def read(run):
    if run.trace is None:
        return None
    step_s = trace_reduce.module_mean_s(run.trace, MODULE)
    if not step_s:
        return None
    # mean context of a running request: prompt + half of its output
    ctx = [r["prompt_len"] + r["max_tokens"] / 2 for r in run.good]
    mean_ctx = sum(ctx) / len(ctx) if ctx else 0.0
    lanes_busy = run.lanes
    if run.running_samples:
        lanes_busy = sum(s[0] for s in run.running_samples) / len(
            run.running_samples
        )
    cfg = run.model_cfg
    touched = (costs.expected_experts_touched(cfg, lanes_busy)
               if cfg.n_experts else None)
    least_s = costs.decode_step_min_bytes(
        cfg, lanes_busy, mean_ctx, experts_touched=touched
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
