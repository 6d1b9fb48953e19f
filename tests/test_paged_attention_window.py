"""A sliding layer's decode call (``paged_attention`` with ``window=``)
against its oracle, on window tables narrower than one step of the walk
(``tests/window_walks.py``); tables of several steps, bfloat16 pools and the
layer as an operand are in ``tests/test_paged_attention_window_steps.py``."""

import jax.numpy as jnp
import pytest

from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from window_walks import WINDOW_WALKS, check_walk, window_setup

#: a table of 12 pages of 16 (or 7 of 4) is less than one step of 16 pages
ONE_STEP = [case for case, (_, _, pages, _) in WINDOW_WALKS.items() if pages < 16]


class TestWindowWalk:
    @pytest.mark.parametrize("fresh", [False, True], ids=["resident", "fresh"])
    @pytest.mark.parametrize("case", ONE_STEP)
    def test_matches_reference(self, case, fresh):
        check_walk(case, fresh)

    def test_a_window_pool_holds_no_int8_codes(self):
        ps, window, pages, lens = WINDOW_WALKS["pages-of-4-window-of-8"]
        q, k, v, tables, _, abs_lens, _, _ = window_setup(24, ps, pages, lens)
        scales = jnp.ones((3, k.shape[1], 2), jnp.float32)
        with pytest.raises(ValueError, match="no int8"):
            paged_attention(
                q, k.astype(jnp.int8), v.astype(jnp.int8), tables, abs_lens,
                k_scale=scales, v_scale=scales, interpret=True, window=window)
