"""A GQA group of 7 (28 query heads on 4 key/value heads of 128:
SmallThinker's) through both attention kernels in the interpreter, against
``ops/attention.py``'s ``jax.numpy`` oracle: blocks and scratch of ``[n_kv, 7,
128]`` in the decode kernels, ``bq * group`` = 112 score rows a tile in the
prefill kernel (``align = 16 // gcd(16, 7)`` is 16, which no other model's
group gives). Mosaic decides the compiled form on the chip; what is held
here is that no index of either kernel assumes a group that divides 16.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops.attention import prefill_with_paged_context
from llm_d_kv_cache_manager_tpu.ops.flash_prefill import flash_prefill_paged
from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention

PS, N_Q, N_KV, HD = 16, 28, 4, 128
PAGES = 24
TOL = dict(atol=3e-5, rtol=3e-5)


def _pools(rng, layers=2):
    shape = (layers, PAGES, PS, N_KV, HD)
    k = jnp.asarray(0.5 * rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    return k.at[:, 0].set(1e4), v.at[:, 0].set(1e4)  # page 0 pads the tables


def _tables(rng, rows, width, lens):
    tables = rng.permutation(PAGES - 1)[: rows * width].reshape(rows, width) + 1
    for i, n in enumerate(lens):
        tables[i, -(-n // PS):] = 0
    return jnp.asarray(tables, jnp.int32)


# (window, where each lane's table starts, the lanes' contexts counted from
# there, the current token included)
DECODE = {
    "full-layer": (0, [0, 0, 0], [70, 1, 33]),
    "window-table-starts-mid-context": (64, [32, 0, 16], [90, 17, 64]),
}


@pytest.mark.parametrize("case", list(DECODE))
def test_the_decode_kernels_at_a_group_of_7(case):
    window, starts, lens = DECODE[case]
    rng = np.random.default_rng(7)
    k_pool, v_pool = _pools(rng)
    b, layer = len(lens), 1
    tables = _tables(rng, b, 6, lens)
    q = jnp.asarray(rng.normal(size=(b, N_Q, HD)), jnp.float32)
    fk = jnp.asarray(rng.normal(size=(b, N_KV, HD)), jnp.float32)
    fv = jnp.asarray(rng.normal(size=(b, N_KV, HD)), jnp.float32)
    starts = jnp.asarray(starts, jnp.int32)
    abs_lens = jnp.asarray(lens, jnp.int32) + starts
    seen = dict(window=window, table_start=starts) if window else {}
    got = paged_attention(
        q, k_pool, v_pool, tables, abs_lens, fk, fv, interpret=True,
        layer=layer, **seen)
    # the oracle: a chunk of one token behind a context of ``len - 1``
    want = prefill_with_paged_context(
        q[:, None], fk[:, None], fv[:, None], k_pool[layer], v_pool[layer],
        tables, abs_lens - 1, positions=(abs_lens - 1)[:, None], **seen)[:, 0]
    np.testing.assert_allclose(got, want, **TOL)


# (window, table starts, contexts, valid chunk tokens of 40)
PREFILL = {
    "full-layer-cold-and-warm": (0, [0, 0], [0, 37], [40, 23]),
    "window-table-starts-mid-context": (64, [16, 0], [75, 9], [40, 31]),
}


@pytest.mark.parametrize("case", list(PREFILL))
def test_the_prefill_kernel_at_a_group_of_7(case):
    window, starts, ctx, n_valid = PREFILL[case]
    rng = np.random.default_rng(8)
    k_pool, v_pool = _pools(rng)
    b, s, layer = len(ctx), 40, 1
    starts = jnp.asarray(starts, jnp.int32)
    tables = _tables(rng, b, 5, [c - int(st) for c, st in zip(ctx, starts)])
    q = jnp.asarray(rng.normal(size=(b, s, N_Q, HD)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, N_KV, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, N_KV, HD)), jnp.float32)
    ctx = jnp.asarray(ctx, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    positions = ctx[:, None] + jnp.arange(s)[None, :]
    valid = jnp.arange(s)[None, :] < n_valid[:, None]
    seen = dict(window=window, table_start=starts) if window else {}
    got = flash_prefill_paged(
        q, k, v, k_pool, v_pool, tables, ctx, n_valid, interpret=True,
        layer=layer, **seen)
    want = prefill_with_paged_context(
        q, k, v, k_pool[layer], v_pool[layer], tables, ctx,
        positions=positions, valid=valid, **seen)
    for row in range(b):
        n = int(n_valid[row])
        np.testing.assert_allclose(got[row, :n], want[row, :n], **TOL)
