"""The share of the experts this process holds that a routed layer read in a
decode forward (mean over layers and forwards): the growth of
``step_stats["experts_touched"]`` over forwards x ``routed_layers`` x
``/stats``' ``experts_held``. It says how near the one-rank share is to its
deployment, which batches enough tokens to touch every expert (100 %): the
grouped matmuls are bound by the weights they read. ``step_after`` is read at
the window's close, so the emptying tail is not in it. None for a program
that does not count them."""

from chipbench import scmoe_counts


def read(run):
    counts = scmoe_counts.deltas(run)
    if counts is None or not counts["experts_held"]:
        return None
    return (100.0 * counts["experts_touched"]
            / scmoe_counts.layer_forwards(counts) / counts["experts_held"])
