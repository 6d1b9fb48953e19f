"""``window_short_hit_share`` in a cell whose resident prefixes are a quarter
of a window, one window and three: a prefix shorter than the window lives
whole in the window pool, and nothing is given back behind it. 0 here as in
``longdocs``: anything else says the window pool lost a prefix's last window
(or a short prefix's whole context) and its tokens were prefilled again."""

from chipbench import prerouted_counts

read = prerouted_counts.sibling("window_short_hit_share")
