"""Distinct experts the rows of one decode-side forward chose in one routed
layer, as the program counted them on the device: the window's growth of
``step_stats["experts_touched"]`` (``llama._moe_mlp_routed``: the experts
whose weights the grouped matmuls read, padded lanes' rows included; fetched
with the burst's tokens) over that of ``decode_forwards`` (dispatches x fused
steps) and ``/stats``' ``routed_layers``. Under block diffusion it is
``block_counters.experts_touched_per_layer``. ``step_after`` is read at the
window's close, so the emptying tail (fewer lanes, so fewer experts) is not
in it. None for a program that does not count them on the path the cell runs."""

from chipbench import program_counts


def read(run):
    counts = program_counts.deltas(run)
    if counts is None:
        return None
    return program_counts.experts_touched_per_layer(counts)
