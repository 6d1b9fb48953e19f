"""Median of the responses' ``queue_s``: taken by the pod (``submit()``'s
clock read) -> first prefill dispatch, staging included; on the pod's
clock. The part of ``ttft_ms_p50`` that is waiting, plus the staged wait
that ``ttft_ms_p50`` leaves out (``staged_wait_ms_p50``)."""

from chipbench.metrics import percentile


def read(run):
    xs = [r["body"]["queue_s"] * 1e3 for r in run.good if "queue_s" in r["body"]]
    return percentile(xs, 50) if xs else None
