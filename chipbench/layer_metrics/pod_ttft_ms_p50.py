"""Median of the responses' ttft_s alone: arrival at the pod -> first token
(queue wait + prefill), on the pod's clock."""

from chipbench.metrics import percentile


def read(run):
    xs = [r["body"]["ttft_s"] * 1e3 for r in run.good]
    return percentile(xs, 50) if xs else None
