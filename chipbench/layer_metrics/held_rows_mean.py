"""Rows the grouped matmuls of one routed layer computed in one decode
forward: the growth of ``step_stats["held_places"]`` (places whose expert
this process holds: ``llama._moe_mlp_routed``'s group sizes, summed on the
device) over forwards x ``routed_layers``. Lanes x top-k x held / router
outputs for an even router (64 x 12 x 16 / 768 = 16). None for a program
that does not count them."""

from chipbench import scmoe_counts


def read(run):
    counts = scmoe_counts.deltas(run)
    if counts is None:
        return None
    return counts["held_places"] / scmoe_counts.layer_forwards(counts)
