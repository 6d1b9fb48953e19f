"""95th percentile of actual - due send time of the open-loop generator: a
starved generator must not read as a fast server."""

from chipbench.metrics import percentile


def read(run):
    return percentile(run.late_s, 95) * 1e3 if run.late_s else None
