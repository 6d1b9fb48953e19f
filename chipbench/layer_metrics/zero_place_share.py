"""The share of the routed layers' places (a row's top-k choices) that fell
on zero-compute experts in the window's decode forwards, as the program
counted them on the device: the growth of ``step_stats["zero_places"]`` over
that of ``["routed_places"]`` (``llama._moe_mlp_routed``, fetched with the
burst's tokens; padded lanes' rows included in both). A third for a router
that spreads its places evenly over 512 + 256 outputs; a trained router
moves it by token. None for a program that does not count them."""

from chipbench import scmoe_counts


def read(run):
    counts = scmoe_counts.deltas(run)
    if counts is None:
        return None
    return 100.0 * counts["zero_places"] / counts["routed_places"]
