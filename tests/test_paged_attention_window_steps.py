"""A sliding layer's decode call (``paged_attention`` with ``window=``)
against its oracle, on window tables of several steps of the walk
(``tests/window_walks.py``), on bfloat16 pools, and with the layer as a
traced operand; tables narrower than one step and the int8 refusal are in
``tests/test_paged_attention_window.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
)
from window_walks import (
    WINDOW_WALKS,
    check_walk,
    window_setup,
    with_fresh_written,
)

#: a table of 72 pages of 16 is four and a half steps of 16 pages
SEVERAL_STEPS = [case for case, (_, _, pages, _) in WINDOW_WALKS.items() if pages >= 16]


class TestWindowWalk:
    @pytest.mark.parametrize("fresh", [False, True], ids=["resident", "fresh"])
    @pytest.mark.parametrize("case", SEVERAL_STEPS)
    def test_matches_reference(self, case, fresh):
        check_walk(case, fresh)

    @pytest.mark.parametrize(
        "case", ["window-starts-mid-page", "history-ends-mid-block",
                 "lanes-of-length-0-beside-live-ones"])
    def test_bfloat16_pools(self, case):
        ps, window, pages, lens = WINDOW_WALKS[case]
        q, k, v, tables, starts, abs_lens, fk, fv = window_setup(
            22, ps, pages, lens, dtype=jnp.bfloat16)
        k_ref, v_ref = with_fresh_written(k, v, tables, lens, fk, fv, 1, ps)
        got = paged_attention(
            q, k, v, tables, abs_lens, fk, fv, interpret=True, layer=1,
            window=window, table_start=starts)
        want = paged_attention_reference(
            q, k_ref, v_ref, tables, abs_lens, window=window, table_start=starts)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2)
        assert got.dtype == jnp.bfloat16

    def test_the_layer_is_an_operand(self):
        """One trace serves every sliding layer: ``layer`` may be a traced
        value, and each layer reads its own pages."""
        ps, window, pages, lens = WINDOW_WALKS["window-starts-mid-page"]
        q, k, v, tables, _, _, fk, fv = window_setup(23, ps, pages, lens)
        rel = jnp.array(lens, jnp.int32)
        traces = []

        @jax.jit
        def call(layer):
            traces.append(layer)
            return paged_attention(
                q, k, v, tables, rel, fk, fv, window=window, scale=0.2,
                interpret=True, layer=layer)

        for layer in range(3):
            k_ref, v_ref = with_fresh_written(k, v, tables, lens, fk, fv, layer, ps)
            want = paged_attention_reference(
                q, k_ref, v_ref, tables, rel, window=window, scale=0.2)
            np.testing.assert_allclose(
                call(jnp.int32(layer)), want, rtol=2e-5, atol=2e-5)
        assert len(traces) == 1
