"""A sliding layer's decode call (``paged_attention`` with ``window=``)
against its oracle, on window tables narrower than one step of the walk
(``tests/window_walks.py``); tables of several steps, bfloat16 pools and the
layer as an operand are in ``tests/test_paged_attention_window_steps.py``."""

import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
)
from window_walks import WINDOW_WALKS, window_setup, with_fresh_written

#: a table of 12 pages of 16 (or 7 of 4) is less than one step of 16 pages
ONE_STEP = [case for case, (_, _, pages, _) in WINDOW_WALKS.items() if pages < 16]


class TestWindowWalk:
    @pytest.mark.parametrize("fresh", [False, True], ids=["resident", "fresh"])
    @pytest.mark.parametrize("case", ONE_STEP)
    def test_matches_reference(self, case, fresh):
        ps, window, pages, lens = WINDOW_WALKS[case]
        q, k, v, tables, starts, abs_lens, fk, fv = window_setup(21, ps, pages, lens)
        layer = 2  # of a five-dimensional pool, as the served program passes it
        if fresh:
            k_ref, v_ref = with_fresh_written(k, v, tables, lens, fk, fv, layer, ps)
            args = (fk, fv)
        else:
            k_ref, v_ref, args = k[layer], v[layer], ()
        got = paged_attention(
            q, k, v, tables, abs_lens, *args, interpret=True, layer=layer,
            window=window, table_start=starts)
        want = paged_attention_reference(
            q, k_ref, v_ref, tables, abs_lens, window=window, table_start=starts)
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        for i, n in enumerate(lens):  # no NaN from a never-written VMEM slot
            assert (float(jnp.abs(got[i]).max()) == 0.0) == (n == 0)

    def test_a_window_pool_holds_no_int8_codes(self):
        ps, window, pages, lens = WINDOW_WALKS["pages-of-4-window-of-8"]
        q, k, v, tables, _, abs_lens, _, _ = window_setup(24, ps, pages, lens)
        scales = jnp.ones((3, k.shape[1], 2), jnp.float32)
        with pytest.raises(ValueError, match="no int8"):
            paged_attention(
                q, k.astype(jnp.int8), v.astype(jnp.int8), tables, abs_lens,
                k_scale=scales, v_scale=scales, interpret=True, window=window)
