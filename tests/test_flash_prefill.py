"""Pallas flash-prefill kernel (ops/flash_prefill.py) parity tests.

Oracle: `prefill_with_paged_context` (the XLA scan flash). Runs the kernel
in interpreter mode on CPU across GQA/MHA/MQA geometries, 4 and 8 KV heads,
causal and block-causal chunks, cold and warm context (the pages read where
they lie in the five-dimensional pool), padding and multi-block shapes;
through `llama.prefill` / the engine end to end in
``tests/test_flash_prefill_integration.py``. The call compiled for a
described v5e is read in ``tests/test_pool_layout.py``; on-chip numerics are
re-checked, compiled, by ``chip_smoke.py``'s kernel phase (round-1 lesson:
Mosaic can miscompile what the interpreter gets right).
"""

import numpy as np
import pytest
import jax.numpy as jnp

from llm_d_kv_cache_manager_tpu.ops.attention import prefill_with_paged_context
from llm_d_kv_cache_manager_tpu.ops.flash_prefill import flash_prefill_paged

PS = 8  # page size


def _setup(rng, b, s, n_q, n_kv, d, total_pages, max_ctx_pages, ctx_lens, n_valid):
    q = jnp.asarray(rng.standard_normal((b, s, n_q, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), jnp.float32)
    k_pages = jnp.asarray(
        rng.standard_normal((total_pages, PS, n_kv, d)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.standard_normal((total_pages, PS, n_kv, d)), jnp.float32
    )
    # distinct pages per sequence
    perm = rng.permutation(total_pages - 1)[: b * max_ctx_pages] + 1
    block_tables = jnp.asarray(perm.reshape(b, max_ctx_pages), jnp.int32)
    ctx_lens = jnp.asarray(ctx_lens, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    return q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid


def _compare(q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid,
             atol=2e-5, block_length=0, in_place=None, **kernel_kw):
    """The kernel against the oracle. ``in_place`` = (k_pool, v_pool, tables,
    layer) hands the kernel another view of the same context (a layer of a
    five-dimensional pool, `_pages_where_they_lie`); the oracle keeps the
    clean four-dimensional one."""
    b, s = q.shape[:2]
    positions = ctx_lens[:, None] + jnp.arange(s)[None, :]
    valid = jnp.arange(s)[None, :] < n_valid[:, None]
    ref = prefill_with_paged_context(
        q, k, v, k_pages, v_pages, block_tables, ctx_lens,
        positions=positions, valid=valid, block_length=block_length,
    )
    if in_place is not None:
        k_pages, v_pages, block_tables, kernel_kw["layer"] = in_place
    # Only valid query rows are meaningful (the engine reads nothing else;
    # the kernel zeroes them, the oracle attends context from them).
    mask = np.asarray(valid)[:, :, None, None]
    got = flash_prefill_paged(
        q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid,
        interpret=True, block_length=block_length, **kernel_kw,
    )
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(
        np.asarray(got) * mask, np.asarray(ref) * mask, atol=atol, rtol=1e-4
    )
    return np.asarray(got)


#: (n_q, n_kv): GQA, MHA, MQA, and the cells' 4 and 8 KV heads
_GEOMETRIES = [
    pytest.param(8, 2, id="gqa"),
    pytest.param(4, 4, id="mha"),
    pytest.param(8, 1, id="mqa"),
    pytest.param(8, 4, id="kv4"),
    pytest.param(16, 8, id="kv8"),
]
_TABLE_PAGES = 4
#: context lengths against a table of 4 pages of 8: none, under one page,
#: on a page boundary, one past it, the table's full width
_CTX_LENS = [
    pytest.param(0, id="ctx0"),
    pytest.param(5, id="under-a-page"),
    pytest.param(PS, id="page-boundary"),
    pytest.param(PS + 1, id="one-past"),
    pytest.param(_TABLE_PAGES * PS, id="table-width"),
]


def _on_a_block(ctx_lens, block_length):
    """A block-causal chunk starts on a block boundary (the kernel's
    contract): context lengths up to the next multiple of the block."""
    if block_length < 2:
        return list(ctx_lens)
    return [-(-c // block_length) * block_length for c in ctx_lens]


def _pages_where_they_lie(rng, args, layers=3, layer=1):
    """The same call over a five-dimensional pool: the context in ``layer``,
    NaN in every other layer and in page 0, and page 0 in every table entry
    at or past a row's context (the engine's padding): reading any of them
    into the result shows."""
    q, k, v, k_pages, v_pages, bt, ctx_lens, n_valid = args
    pools = []
    for pages in (k_pages, v_pages):
        pool = np.full((layers, *pages.shape), np.nan, np.float32)
        pool[layer] = np.asarray(pages)
        pool[layer, 0] = np.nan
        pools.append(jnp.asarray(pool))
    n_pages = -(-np.asarray(ctx_lens) // PS)
    live = np.arange(bt.shape[1])[None, :] < n_pages[:, None]
    return pools[0], pools[1], jnp.asarray(np.where(live, np.asarray(bt), 0)), layer


class TestFlashPrefillParity:
    @pytest.mark.parametrize("block_length", [0, 4], ids=["causal", "block4"])
    @pytest.mark.parametrize("n_q,n_kv", _GEOMETRIES)
    def test_every_context_length_in_one_batch(self, n_q, n_kv, block_length):
        """Lanes with no context and padded lanes beside live ones, each of
        the context lengths in one dispatch, read from the pool in place."""
        rng = np.random.default_rng(n_q * 10 + n_kv + block_length)
        ctx = _on_a_block([0, 5, PS, PS + 1, _TABLE_PAGES * PS, 0, 17], block_length)
        args = _setup(
            rng, b=len(ctx), s=8, n_q=n_q, n_kv=n_kv, d=16, total_pages=40,
            max_ctx_pages=_TABLE_PAGES, ctx_lens=ctx,
            n_valid=[8, 8, 4, 8, 8, 0, 0],
        )
        got = _compare(
            *args, block_length=block_length,
            in_place=_pages_where_they_lie(rng, args),
        )
        # a padded lane (no valid query) is zeros, context or not
        assert not got[[5, 6]].any()

    @pytest.mark.parametrize("block_length", [0, 4], ids=["causal", "block4"])
    @pytest.mark.parametrize("ctx_len", _CTX_LENS)
    def test_context_length_beside_a_cold_lane(self, ctx_len, block_length):
        rng = np.random.default_rng(100 + ctx_len + block_length)
        (ctx_len,) = _on_a_block([ctx_len], block_length)
        args = _setup(
            rng, b=2, s=12, n_q=8, n_kv=4, d=16, total_pages=16,
            max_ctx_pages=_TABLE_PAGES, ctx_lens=[ctx_len, 0], n_valid=[12, 7],
        )
        want = _compare(*args, block_length=block_length)
        # and with the table's dead entries on the NaN page 0
        got = _compare(
            *args, block_length=block_length,
            in_place=_pages_where_they_lie(rng, args, layers=2, layer=1),
        )
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("block_length", [0, 4], ids=["causal", "block4"])
    def test_context_over_several_steps(self, block_length):
        """A context longer than the keys of one step (128 here): the
        double-buffered page copies, a last step that is part full, and a
        lane that ends a step earlier than its neighbour."""
        rng = np.random.default_rng(7 + block_length)
        args = _setup(
            rng, b=3, s=8, n_q=8, n_kv=4, d=16, total_pages=128,
            max_ctx_pages=40, ctx_lens=_on_a_block([300, 131, 128], block_length),
            n_valid=[8, 8, 5],
        )
        want = _compare(*args, block_length=block_length, key_block=128)
        got = _compare(
            *args, block_length=block_length, key_block=128,
            in_place=_pages_where_they_lie(rng, args),
        )
        np.testing.assert_array_equal(got, want)

    def test_the_layer_is_the_kernels_own_addressing(self):
        """Every layer of one five-dimensional pool, by ``layer`` alone,
        against that layer handed over as a four-dimensional pool."""
        rng = np.random.default_rng(11)
        args = _setup(
            rng, b=2, s=8, n_q=8, n_kv=2, d=16, total_pages=16,
            max_ctx_pages=3, ctx_lens=[20, 9], n_valid=[8, 6],
        )
        q, k, v, _, _, bt, ctx_lens, n_valid = args
        k5 = jnp.asarray(rng.standard_normal((3, 16, PS, 2, 16)), jnp.float32)
        v5 = jnp.asarray(rng.standard_normal((3, 16, PS, 2, 16)), jnp.float32)
        outs = []
        for layer in range(3):
            got = flash_prefill_paged(
                q, k, v, k5, v5, bt, ctx_lens, n_valid, interpret=True,
                layer=layer,
            )
            want = flash_prefill_paged(
                q, k, v, k5[layer], v5[layer], bt, ctx_lens, n_valid,
                interpret=True,
            )
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            outs.append(np.asarray(got))
        assert not np.allclose(outs[0], outs[1])
        assert not np.allclose(outs[1], outs[2])

    @pytest.mark.parametrize(
        "n_q,n_kv",
        [(8, 2), (4, 4), (8, 1)],  # GQA, MHA, MQA
        ids=["gqa", "mha", "mqa"],
    )
    def test_head_geometries_with_context(self, n_q, n_kv):
        rng = np.random.default_rng(0)
        args = _setup(
            rng, b=2, s=24, n_q=n_q, n_kv=n_kv, d=16, total_pages=64,
            max_ctx_pages=4, ctx_lens=[32, 17], n_valid=[24, 24],
        )
        _compare(*args)

    def test_cold_prefill_no_context(self):
        rng = np.random.default_rng(1)
        args = _setup(
            rng, b=2, s=32, n_q=4, n_kv=2, d=16, total_pages=16,
            max_ctx_pages=2, ctx_lens=[0, 0], n_valid=[32, 20],
        )
        _compare(*args)

    def test_zero_max_ctx_pages_path(self):
        """max_ctx == 0 (engine cold batch with no context table width)."""
        rng = np.random.default_rng(2)
        b, s, n_q, n_kv, d = 2, 16, 4, 2, 16
        q = jnp.asarray(rng.standard_normal((b, s, n_q, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), jnp.float32)
        k_pages = jnp.zeros((4, PS, n_kv, d), jnp.float32)
        v_pages = jnp.zeros((4, PS, n_kv, d), jnp.float32)
        block_tables = jnp.zeros((b, 0), jnp.int32)
        ctx_lens = jnp.zeros((b,), jnp.int32)
        n_valid = jnp.asarray([s, s - 3], jnp.int32)
        _compare(q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid)

    def test_multi_block_q_and_k(self):
        """Sequence long enough to span several q and k blocks with tiny
        block sizes — exercises the carry across k-steps and the causal
        clamping of chunk block indices."""
        rng = np.random.default_rng(3)
        b, s, n_q, n_kv, d = 2, 64, 4, 2, 16
        args = _setup(
            rng, b=b, s=s, n_q=n_q, n_kv=n_kv, d=d, total_pages=64,
            max_ctx_pages=6, ctx_lens=[48, 5], n_valid=[64, 40],
        )
        q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid = args
        positions = ctx_lens[:, None] + jnp.arange(s)[None, :]
        valid = jnp.arange(s)[None, :] < n_valid[:, None]
        ref = prefill_with_paged_context(
            q, k, v, k_pages, v_pages, block_tables, ctx_lens,
            positions=positions, valid=valid,
        )
        got = flash_prefill_paged(
            q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid,
            interpret=True, q_block=16, key_block=128,
        )
        mask = np.asarray(valid)[:, :, None, None]
        np.testing.assert_allclose(
            np.asarray(got) * mask, np.asarray(ref) * mask, atol=2e-5, rtol=1e-4
        )

    def test_bf16_inputs(self):
        rng = np.random.default_rng(4)
        args = _setup(
            rng, b=1, s=16, n_q=4, n_kv=2, d=16, total_pages=16,
            max_ctx_pages=2, ctx_lens=[9], n_valid=[16],
        )
        q, k, v, k_pages, v_pages, bt, cl, nv = (
            a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a for a in args
        )
        _compare(q, k, v, k_pages, v_pages, bt, cl, nv, atol=2e-2)
