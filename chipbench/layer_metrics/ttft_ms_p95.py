"""95th percentile of the time to first token as ttft_ms_p50 defines it, over
the window's completed requests (left out under 200 of them: ten samples
must lie beyond it). Not an end-to-end metric: at 0.8 x the knee it
spreads by ~20 % between two runs of one schedule (PERF.md, section 2)."""

from chipbench import metrics


def read(run):
    if not metrics.tail_supported(len(run.good), 95):
        return None
    return metrics.percentile([metrics.ttft_s(r) * 1e3 for r in run.good], 95)
