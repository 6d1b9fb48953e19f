"""``state_cutback_tokens_mean`` at a stride of 1024 under histories of 7680,
24320 and 56576 tokens: a hit is cut back by 512, 768 or 256 tokens
(predicted about 500 an admission, Zipf over the three lengths in turn)."""

from chipbench import prerouted_counts

read = prerouted_counts.sibling("state_cutback_tokens_mean")
