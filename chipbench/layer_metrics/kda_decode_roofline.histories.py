"""``kda_decode_roofline`` in a cell whose linear layers have 64 heads: a
lane's program reads and writes 4 MiB of matrices a layer (twice
``threads``'), by ``costs_kda.kda_decode_bytes`` of the lanes the program
counted."""

from chipbench import prerouted_counts

read = prerouted_counts.sibling("kda_decode_roofline")
