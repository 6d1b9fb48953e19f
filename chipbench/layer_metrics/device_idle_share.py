"""1 - union of device-op intervals / traced window, mean over chips."""

from chipbench import trace_reduce


def read(run):
    if run.trace is None or not run.trace["chips"]:
        return None
    return 100.0 * trace_reduce.idle_share(run.trace)
