"""Pallas paged-attention kernel vs pure-jnp oracle (interpret mode on CPU;
the same kernel compiles for TPU via Mosaic). A sliding layer's call
(``window=``) is in ``tests/test_paged_attention_window.py`` and
``tests/test_paged_attention_window_steps.py``."""

import numpy as np
import pytest
import jax.numpy as jnp

from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
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
