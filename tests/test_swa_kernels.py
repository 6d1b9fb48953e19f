"""The sliding layers' kernels alone, against their ``jax.numpy`` oracles: a
window table that starts mid-context in the decode and the prefill kernel
(a run of window pages as one copy: ``tests/test_swa_kernel_runs.py``). The
served programs that call them are held to the reference in
``tests/test_swa.py``.
"""

import jax.numpy as jnp
import numpy as np

from llm_d_kv_cache_manager_tpu.models import TINY_SWA_MOE
from llm_d_kv_cache_manager_tpu.ops.attention import prefill_with_paged_context
from llm_d_kv_cache_manager_tpu.ops.flash_prefill import flash_prefill_paged
from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
)

PS = 4
W = TINY_SWA_MOE.sliding_window


# -- the kernels against their oracles, a table that starts mid-context --------
def _pool(rng, pages, n_kv=2, hd=16):
    return jnp.asarray(rng.normal(size=(pages, PS, n_kv, hd)), jnp.float32)


def test_decode_kernel_with_a_window_table_that_starts_mid_context():
    rng = np.random.default_rng(5)
    k_pool, v_pool = _pool(rng, 12), _pool(rng, 12)
    q = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    # contexts of 21, 9 and 3 tokens: tables start at positions 12, 0 and 0
    tables = jnp.asarray([[3, 7, 1, 9], [5, 2, 8, 0], [4, 0, 0, 0]], jnp.int32)
    starts = jnp.asarray([12, 0, 0], jnp.int32)
    lens = jnp.asarray([21, 9, 3], jnp.int32)
    for window in (W, W - 1, 3):
        got = paged_attention(
            q, k_pool, v_pool, tables, lens, interpret=True, window=window,
            table_start=starts)
        want = paged_attention_reference(
            q, k_pool, v_pool, tables, lens, window=window, table_start=starts)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # ... and the window is what is seen: the same call over the whole table
    whole = paged_attention_reference(q, k_pool, v_pool, tables, lens - starts)
    assert not np.allclose(got[1], whole[1], atol=1e-3)


def test_prefill_kernel_with_a_window_table_that_starts_mid_context():
    rng = np.random.default_rng(6)
    k_pool, v_pool = _pool(rng, 12), _pool(rng, 12)
    s = 11
    q = jnp.asarray(rng.normal(size=(2, s, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, s, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, s, 2, 16)), jnp.float32)
    tables = jnp.asarray([[3, 7, 1], [5, 0, 0]], jnp.int32)
    starts = jnp.asarray([8, 0], jnp.int32)
    ctx = jnp.asarray([18, 2], jnp.int32)
    n_valid = jnp.asarray([s, 7], jnp.int32)
    positions = ctx[:, None] + jnp.arange(s)[None, :]
    valid = jnp.arange(s)[None, :] < n_valid[:, None]
    for window in (W, 5):
        got = flash_prefill_paged(
            q, k, v, k_pool, v_pool, tables, ctx, n_valid, interpret=True,
            window=window, table_start=starts)
        want = prefill_with_paged_context(
            q, k, v, k_pool, v_pool, tables, ctx, positions=positions,
            valid=valid, window=window, table_start=starts)
        for row in range(2):
            n = int(n_valid[row])
            np.testing.assert_allclose(
                got[row, :n], want[row, :n], atol=2e-5, rtol=2e-5)
