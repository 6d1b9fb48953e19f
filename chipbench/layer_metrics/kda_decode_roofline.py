"""The least time the ``kda_decode`` kernel's calls of one decode step could
take over the time they took: every real lane's matrices read and written in
every linear layer, with the step's operands (``costs_kda.kda_decode_bytes``
of the lanes the program counted a dispatch: ``step_stats["decode_rows"]``
over ``decode_dispatches``) / the chip's HBM bandwidth, over the kernel's
device time in the trace divided by the calls of the module ``decode_steps``
(one kernel call a linear layer a step). Bound: HBM bandwidth (7 vector FLOPs
a value of the matrices against 8 bytes moved). The kernel runs a program a
lane of the dispatch, idle lanes included (their slot is the reserved one),
so with lanes free the share reads under what the busy lanes' alone would.
None where the program does not count (a program from before the counters),
the model has no linear layer, or the trace holds no such kernel."""

from chipbench import costs_kda, kda_counts, trace_reduce

KERNEL = "kda_decode"
MODULE = "decode_steps"


def read(run):
    if run.trace is None:
        return None
    counts = kda_counts.deltas(run)
    calls = sum(n for name, n in run.trace["module_calls"].items()
                if MODULE in name)
    kernel_s = trace_reduce.time_matching(run.trace, KERNEL)
    if (counts is None or not counts["decode_dispatches"] or not calls
            or not kernel_s):
        return None
    least_s = costs_kda.kda_decode_bytes(
        run.model_cfg, counts["decode_rows"] / counts["decode_dispatches"]
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_s / calls)
