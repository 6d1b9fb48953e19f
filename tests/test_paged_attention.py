"""Pallas paged-attention kernel vs pure-jnp oracle (interpret mode on CPU;
the same kernel compiles for TPU via Mosaic)."""

import jax
import numpy as np
import pytest
import jax.numpy as jnp

from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
    paged_window_attention,
)


def _setup(seed, B, NH, NKV, D, PS, NPAGES, MAXP, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.array(rng.standard_normal((B, NH, D)), dtype)
    k = jnp.array(rng.standard_normal((NPAGES, PS, NKV, D)) * 0.3, dtype)
    v = jnp.array(rng.standard_normal((NPAGES, PS, NKV, D)), dtype)
    # unique pages per sequence (engine invariant: no aliasing between live seqs)
    ids = rng.permutation(NPAGES)[: B * MAXP].reshape(B, MAXP)
    bt = jnp.array(ids, jnp.int32)
    return q, k, v, bt


class TestPagedAttentionKernel:
    @pytest.mark.parametrize(
        "B,NH,NKV,D,PS,MAXP,lens",
        [
            (1, 1, 1, 128, 16, 2, [17]),
            (3, 8, 2, 128, 16, 4, [5, 64, 33]),
            (2, 4, 4, 64, 8, 3, [24, 1]),  # MHA (group=1)
            (4, 8, 1, 128, 16, 2, [32, 31, 16, 9]),  # MQA
        ],
    )
    def test_matches_reference(self, B, NH, NKV, D, PS, MAXP, lens):
        NPAGES = B * MAXP + 2
        q, k, v, bt = _setup(0, B, NH, NKV, D, PS, NPAGES, MAXP)
        sl = jnp.array(lens, jnp.int32)
        ref = paged_attention_reference(q, k, v, bt, sl)
        out = paged_attention(q, k, v, bt, sl, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_5d_pool_layer_index_matches_sliced_pool(self):
        """Passing the full multi-layer pool with `layer=li` must equal
        attention over the sliced per-layer pool — the 5-D operand is the
        form the decode body uses so XLA never materializes a per-layer
        pool copy around the custom call (the pool-size decode cliff)."""
        rng = np.random.default_rng(7)
        L, B, NH, NKV, D, PS, MAXP = 3, 2, 4, 2, 64, 8, 3
        NPAGES = B * MAXP + 1
        q = jnp.array(rng.standard_normal((B, NH, D)), jnp.float32)
        k5 = jnp.array(rng.standard_normal((L, NPAGES, PS, NKV, D)), jnp.float32)
        v5 = jnp.array(rng.standard_normal((L, NPAGES, PS, NKV, D)), jnp.float32)
        bt = jnp.array(
            rng.permutation(NPAGES)[: B * MAXP].reshape(B, MAXP), jnp.int32
        )
        sl = jnp.array([13, 20], jnp.int32)
        for li in range(L):
            ref = paged_attention_reference(q, k5[li], v5[li], bt, sl)
            out = paged_attention(q, k5, v5, bt, sl, layer=li, interpret=True)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
            )

    def test_zero_length_sequence_is_zero_not_nan(self):
        q, k, v, bt = _setup(1, B=2, NH=4, NKV=2, D=64, PS=8, NPAGES=6, MAXP=2)
        sl = jnp.array([0, 16], jnp.int32)
        out = paged_attention(q, k, v, bt, sl, interpret=True)
        assert not bool(jnp.any(jnp.isnan(out)))
        assert float(jnp.max(jnp.abs(out[0]))) == 0.0

    def test_bfloat16_inputs(self):
        q, k, v, bt = _setup(2, B=2, NH=8, NKV=2, D=128, PS=16, NPAGES=6, MAXP=2, dtype=jnp.bfloat16)
        sl = jnp.array([20, 32], jnp.int32)
        ref = paged_attention_reference(q, k, v, bt, sl)
        out = paged_attention(q, k, v, bt, sl, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
        )

    def test_partial_last_page_masked(self):
        # seq_len cuts mid-page; garbage in the tail slots must not leak.
        B, NH, NKV, D, PS, MAXP = 1, 2, 1, 64, 8, 2
        q, k, v, bt = _setup(3, B, NH, NKV, D, PS, B * MAXP + 2, MAXP)
        # Poison the slots beyond seq_len in the last used page.
        sl_val = 11  # page 1, slot 3
        last_page = int(bt[0, 1])
        k = k.at[:, last_page, 3:].set(1e4)
        v = v.at[:, last_page, 3:].set(1e4)
        sl = jnp.array([sl_val], jnp.int32)
        ref = paged_attention_reference(q, k, v, bt, sl)
        out = paged_attention(q, k, v, bt, sl, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
        assert float(jnp.max(jnp.abs(out))) < 100.0


class TestFreshKV:
    """Deferred-write contract: kernel with (history-only pages + fresh K/V
    args) must equal the kernel with the token already written to pages."""

    def test_fresh_kv_matches_written_pages(self):
        import numpy as np
        from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
            paged_attention,
            paged_attention_reference,
        )

        rng = np.random.default_rng(11)
        b, nq, nkv, d, ps, pages, maxp = 3, 8, 4, 32, 4, 32, 6
        q = jnp.asarray(rng.standard_normal((b, nq, d)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((pages, ps, nkv, d)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((pages, ps, nkv, d)), jnp.float32)
        # Distinct pages per sequence so writes don't collide.
        bt = jnp.asarray(
            rng.permutation(pages - 1)[: b * maxp].reshape(b, maxp) + 1, jnp.int32
        )
        seq_lens = jnp.asarray([1, ps + 2, 2 * ps], jnp.int32)  # incl. current
        fk = jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.float32)
        fv = jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.float32)

        # Write the current token into its page slot, then run both paths.
        kp_w, vp_w = kp, vp
        for i in range(b):
            pos = int(seq_lens[i]) - 1
            page = int(bt[i, pos // ps])
            slot = pos % ps
            kp_w = kp_w.at[page, slot].set(fk[i])
            vp_w = vp_w.at[page, slot].set(fv[i])

        written = paged_attention(q, kp_w, vp_w, bt, seq_lens, interpret=True)
        fresh = paged_attention(
            q, kp, vp, bt, seq_lens, fk, fv, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(fresh), np.asarray(written), atol=2e-5
        )
        # And both agree with the oracle on the written pages.
        ref = paged_attention_reference(q, kp_w, vp_w, bt, seq_lens)
        np.testing.assert_allclose(np.asarray(fresh), np.asarray(ref), atol=2e-5)

    def test_fresh_kv_inactive_lane_zeros(self):
        import numpy as np
        from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention

        rng = np.random.default_rng(12)
        b, nq, nkv, d, ps, pages, maxp = 2, 4, 2, 32, 4, 8, 2
        q = jnp.asarray(rng.standard_normal((b, nq, d)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((pages, ps, nkv, d)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((pages, ps, nkv, d)), jnp.float32)
        bt = jnp.zeros((b, maxp), jnp.int32)
        seq_lens = jnp.asarray([3, 0], jnp.int32)  # lane 1 inactive
        fk = jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.float32)
        fv = jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.float32)
        out = paged_attention(q, kp, vp, bt, seq_lens, fk, fv, interpret=True)
        assert bool(jnp.all(out[1] == 0.0))
        assert bool(jnp.any(out[0] != 0.0))


# A sliding layer's call (``window=``): a program a lane that walks the
# lane's window table itself, ``KEY_BLOCK`` tokens of page tiles a step.
# (page size, window, table pages, lengths counted from the table's first
# slot): with pages of 16 a step holds 16 pages (``KEY_BLOCK`` 256), so a
# table of 12 pages is narrower than one step and one of 72 holds four and a
# half.
_WINDOW_WALKS = {
    "window-starts-mid-page": (16, 100, 12, [150, 183, 101]),
    "window-starts-on-a-pages-first-slot": (16, 96, 12, [160, 112, 176]),
    "history-shorter-than-the-window": (16, 256, 12, [40, 1, 17, 160]),
    "history-fills-its-last-block": (16, 1024, 72, [1152, 1151, 1040]),
    "history-ends-mid-block": (16, 1000, 72, [1100, 700, 513]),
    "window-of-two-steps-and-a-page": (16, 513, 72, [1137, 1138, 529]),
    "lanes-of-length-0-beside-live-ones": (16, 100, 12, [0, 150, 0, 31]),
    "pages-of-4-window-of-8": (4, 8, 7, [21, 9, 3, 8, 0]),
}


def _window_setup(seed, ps, pages, lens, dtype=jnp.float32, layers=3):
    """Pools of ``layers`` layers, a window table a lane (distinct pages,
    none of them page 0, which pads the tables' dead tails and is poisoned),
    a start a lane and the absolute lengths."""
    rng = np.random.default_rng(seed)
    b, nh, nkv, d = len(lens), 6, 2, 32
    total = b * pages + 1
    q = jnp.array(rng.standard_normal((b, nh, d)), dtype)
    k = jnp.array(rng.standard_normal((layers, total, ps, nkv, d)) * 0.5, dtype)
    v = jnp.array(rng.standard_normal((layers, total, ps, nkv, d)), dtype)
    k = k.at[:, 0].set(1e4)
    v = v.at[:, 0].set(1e4)
    tables = rng.permutation(total - 1)[: b * pages].reshape(b, pages) + 1
    for i, n in enumerate(lens):  # past a lane's pages: the caller's padding
        tables[i, -(-n // ps):] = 0
    starts = rng.integers(0, 5, b) * ps
    fk = jnp.array(rng.standard_normal((b, nkv, d)), dtype)
    fv = jnp.array(rng.standard_normal((b, nkv, d)), dtype)
    return (q, k, v, jnp.array(tables, jnp.int32), jnp.array(starts, jnp.int32),
            jnp.array(lens, jnp.int32) + jnp.array(starts, jnp.int32), fk, fv)


def _with_fresh_written(k, v, tables, lens, fk, fv, layer, ps):
    """Layer ``layer`` of the pools with each live lane's current token in
    its slot: what the oracle reads."""
    k, v = k[layer], v[layer]
    for i, n in enumerate(np.asarray(lens)):
        if n:
            page = int(tables[i, (n - 1) // ps])
            k = k.at[page, (n - 1) % ps].set(fk[i])
            v = v.at[page, (n - 1) % ps].set(fv[i])
    return k, v


class TestWindowWalk:
    @pytest.mark.parametrize("fresh", [False, True], ids=["resident", "fresh"])
    @pytest.mark.parametrize("case", list(_WINDOW_WALKS))
    def test_matches_reference(self, case, fresh):
        ps, window, pages, lens = _WINDOW_WALKS[case]
        q, k, v, tables, starts, abs_lens, fk, fv = _window_setup(21, ps, pages, lens)
        layer = 2  # of a five-dimensional pool, as the served program passes it
        if fresh:
            k_ref, v_ref = _with_fresh_written(k, v, tables, lens, fk, fv, layer, ps)
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

    @pytest.mark.parametrize(
        "case", ["window-starts-mid-page", "history-ends-mid-block",
                 "lanes-of-length-0-beside-live-ones"])
    def test_bfloat16_pools(self, case):
        ps, window, pages, lens = _WINDOW_WALKS[case]
        q, k, v, tables, starts, abs_lens, fk, fv = _window_setup(
            22, ps, pages, lens, dtype=jnp.bfloat16)
        k_ref, v_ref = _with_fresh_written(k, v, tables, lens, fk, fv, 1, ps)
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
        ps, window, pages, lens = _WINDOW_WALKS["window-starts-mid-page"]
        q, k, v, tables, _, _, fk, fv = _window_setup(23, ps, pages, lens)
        rel = jnp.array(lens, jnp.int32)
        traces = []

        @jax.jit
        def call(layer):
            traces.append(layer)
            return paged_window_attention(
                q, k, v, tables, rel, fk, fv, window=window, scale=0.2,
                interpret=True, layer=layer)

        for layer in range(3):
            k_ref, v_ref = _with_fresh_written(k, v, tables, lens, fk, fv, layer, ps)
            want = paged_attention_reference(
                q, k_ref, v_ref, tables, rel, window=window, scale=0.2)
            np.testing.assert_allclose(
                call(jnp.int32(layer)), want, rtol=2e-5, atol=2e-5)
        assert len(traces) == 1

    def test_a_window_pool_holds_no_int8_codes(self):
        ps, window, pages, lens = _WINDOW_WALKS["pages-of-4-window-of-8"]
        q, k, v, tables, _, abs_lens, _, _ = _window_setup(24, ps, pages, lens)
        scales = jnp.ones((3, k.shape[1], 2), jnp.float32)
        with pytest.raises(ValueError, match="no int8"):
            paged_attention(
                q, k.astype(jnp.int8), v.astype(jnp.int8), tables, abs_lens,
                k_scale=scales, v_scale=scales, interpret=True, window=window)
