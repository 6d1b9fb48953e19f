"""``decode_scope_ms.kda`` in a cell whose linear layers go through low-rank
pairs: the convolution, both pairs, the kernel and the gated norm of three
layers of 64 heads, a forward."""

from chipbench import prerouted_counts

_read = prerouted_counts.sibling("decode_scope_ms")


def read(run):
    return _read(run, "kda")
