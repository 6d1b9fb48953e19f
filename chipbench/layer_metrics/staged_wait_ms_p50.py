"""Median of the responses' ``staged_s``: taken by the pod -> taken by the
engine loop (the Sequence made), that is the wait behind the step in
progress. The response's ``ttft_s`` starts only where this ends, so
``ttft_ms_p50`` leaves it out and ``itl_ms_p50``'s numerator holds it."""

from chipbench.metrics import percentile


def read(run):
    xs = [r["body"]["staged_s"] * 1e3 for r in run.good
          if "staged_s" in r["body"]]
    return percentile(xs, 50) if xs else None
