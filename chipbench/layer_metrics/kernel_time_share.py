"""Share of device busy time in the Mosaic custom calls of one kernel, from
the trace. The suffix of the metric's name is the kernel's: the trace shows
a Pallas kernel under its custom call's HLO name and carries the kernel's
own name in the string stats, and both are searched
(``kernel_time_share.paged_attention``, ``.flash_prefill``, ``.gmm``)."""

from chipbench import trace_reduce


def read(run, kernel):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    return 100.0 * trace_reduce.time_matching(run.trace, kernel) / (
        run.trace["busy_s"]
    )
