"""``window_ctx_share`` in a cell where 44 % of the requests run on a context
shorter than the window: a sliding layer then reads what a full layer reads,
and the window saves it nothing (predicted about 56 %, against ``longdocs``'
24.7 where every context is two windows or more)."""

from chipbench import prerouted_counts

read = prerouted_counts.sibling("window_ctx_share")
