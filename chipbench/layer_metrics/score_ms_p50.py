"""Median of the gateway's span around POST /score_completions (client's
clock): tokenise, hash, index lookup and the HTTP hop."""

from chipbench.metrics import percentile


def read(run):
    spans = [(r["scored"] - r["start"]) * 1e3 for r in run.good]
    return percentile(spans, 50) if spans else None
