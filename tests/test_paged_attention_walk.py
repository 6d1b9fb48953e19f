"""The full-context decode call (``paged_attention`` without ``window=``)
walks a lane's own pages (PR 57): against its oracle on tables of several
steps of the walk (``tests/window_walks.py``: ``FULL_WALKS``), over tables
that are one run and that hold none, with the current token resident and
handed in, and the layer as a traced operand. Groups of 6, 7 and 8, bfloat16
pools, wide tables, int8 pools and a ``tp`` shard's heads are in
``tests/test_paged_attention_walk_forms.py`` (a file's cases share the
programs the first of them compiled; the two files are two workers' work);
the sliding layers' call on the same body in
``tests/test_paged_attention_window*.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    KEY_BLOCK,
    WIDE_TABLE_TOKENS,
    paged_attention,
    paged_attention_reference,
    walk_step_pages,
)
from window_walks import (
    FULL_TABLES,
    FULL_WALKS,
    check_full_walk,
    full_setup,
    with_fresh_written,
)


class TestFullWalk:
    @pytest.mark.parametrize("kind", FULL_TABLES)
    @pytest.mark.parametrize("fresh", [False, True], ids=["resident", "fresh"])
    @pytest.mark.parametrize("case", list(FULL_WALKS))
    def test_matches_reference(self, case, fresh, kind):
        check_full_walk(case, fresh, kind)

    def test_the_layer_is_an_operand(self):
        """One trace serves every full layer of a model: ``layer`` may be a
        traced value, and each layer reads its own pages."""
        ps, hists = FULL_WALKS["history-ends-mid-step"]
        q, k, v, tables, lens, fk, fv = full_setup(33, ps, hists, "one-run")
        sl = jnp.array(lens, jnp.int32)
        traces = []

        @jax.jit
        def call(layer):
            traces.append(layer)
            return paged_attention(
                q, k, v, tables, sl, fk, fv, scale=0.2, interpret=True,
                layer=layer)

        for layer in range(3):
            k_ref, v_ref = with_fresh_written(k, v, tables, lens, fk, fv, layer, ps)
            want = paged_attention_reference(q, k_ref, v_ref, tables, sl, scale=0.2)
            np.testing.assert_allclose(
                call(jnp.int32(layer)), want, rtol=2e-5, atol=2e-5)
        assert len(traces) == 1

    def test_a_python_layer_is_no_static_argument(self):
        """... and a Python int a layer, as the served program passes it, is
        one compiled kernel too."""
        ps, hists = FULL_WALKS["history-ends-mid-page"]
        q, k, v, tables, lens, fk, fv = full_setup(34, ps, hists, "no-run")
        sl = jnp.array(lens, jnp.int32)
        before = paged_attention._cache_size()
        outs = [
            paged_attention(q, k, v, tables, sl, fk, fv, interpret=True, layer=li)
            for li in range(3)
        ]
        # (none where an earlier test of the process compiled these shapes)
        assert paged_attention._cache_size() <= before + 1
        assert not np.allclose(outs[0], outs[1])

    def test_a_dead_tail_past_the_pool_is_not_read(self):
        """Table words past a lane's last live page may hold anything, ids
        past the pool that go on a run among them."""
        ps, hists = FULL_WALKS["history-ends-mid-step"]
        want = check_full_walk("history-ends-mid-step", True, "one-run")
        q, k, v, tables, lens, fk, fv = full_setup(31, ps, hists, "one-run")
        wild = np.array(tables)
        for row, n in zip(wild, lens):
            live = -(-(n - 1) // ps)
            row[live:] = (row[live - 1] if live else k.shape[1]) + 1 + np.arange(
                len(row) - live)
        got = paged_attention(
            q, k, v, jnp.asarray(wild), jnp.array(lens, jnp.int32), fk, fv,
            interpret=True, layer=2)
        np.testing.assert_array_equal(got, want)


def test_a_step_follows_what_the_call_can_see():
    """``walk_step_pages``: whole lane tiles of keys, no wider than the
    table; ``KEY_BLOCK`` tokens, and twice that for a full-context call over
    a table of ``WIDE_TABLE_TOKENS`` or more."""
    wide = WIDE_TABLE_TOKENS // 16
    assert walk_step_pages(259, 16, window=4096) == KEY_BLOCK // 16
    assert walk_step_pages(2176, 16) == walk_step_pages(wide, 16) == 2 * KEY_BLOCK // 16
    assert walk_step_pages(2176, 16, window=4096) == KEY_BLOCK // 16
    assert walk_step_pages(wide - 1, 16) == walk_step_pages(848, 16) == KEY_BLOCK // 16
    assert walk_step_pages(3, 16) == walk_step_pages(3, 16, window=64) == 8
    assert walk_step_pages(7, 4, window=8) == 32  # a lane tile of pages of 4
    for width in (1, 8, 96, 848, 4096):
        assert walk_step_pages(width, 16) % 8 == 0
        assert walk_step_pages(width, 16) <= max(-(-width // 8) * 8, 8)
