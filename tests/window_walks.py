"""The window walks of the sliding layers' decode call (``paged_attention``
with ``window=``: a program a lane that walks the lane's window table itself,
``KEY_BLOCK`` tokens of page tiles a step), and the pools, tables and oracle
inputs a walk is run on, and the comparison itself. Shared by
``tests/test_paged_attention_window.py`` (tables narrower than one step) and
``tests/test_paged_attention_window_steps.py`` (tables of several steps).
Not collected.
"""

import jax.numpy as jnp
import numpy as np

from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
)

# A sliding layer's call (``window=``): a program a lane that walks the
# lane's window table itself, ``KEY_BLOCK`` tokens of page tiles a step.
# (page size, window, table pages, lengths counted from the table's first
# slot): with pages of 16 a step holds 16 pages (``KEY_BLOCK`` 256), so a
# table of 12 pages is narrower than one step and one of 72 holds four and a
# half.
WINDOW_WALKS = {
    "window-starts-mid-page": (16, 100, 12, [150, 183, 101]),
    "window-starts-on-a-pages-first-slot": (16, 96, 12, [160, 112, 176]),
    "history-shorter-than-the-window": (16, 256, 12, [40, 1, 17, 160]),
    "history-fills-its-last-block": (16, 1024, 72, [1152, 1151, 1040]),
    "history-ends-mid-block": (16, 1000, 72, [1100, 700, 513]),
    "window-of-two-steps-and-a-page": (16, 513, 72, [1137, 1138, 529]),
    "lanes-of-length-0-beside-live-ones": (16, 100, 12, [0, 150, 0, 31]),
    "pages-of-4-window-of-8": (4, 8, 7, [21, 9, 3, 8, 0]),
}


def window_setup(seed, ps, pages, lens, dtype=jnp.float32, layers=3):
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


def with_fresh_written(k, v, tables, lens, fk, fv, layer, ps):
    """Layer ``layer`` of the pools with each live lane's current token in
    its slot: what the oracle reads."""
    k, v = k[layer], v[layer]
    for i, n in enumerate(np.asarray(lens)):
        if n:
            page = int(tables[i, (n - 1) // ps])
            k = k.at[page, (n - 1) % ps].set(fk[i])
            v = v.at[page, (n - 1) % ps].set(fv[i])
    return k, v


def check_walk(case: str, fresh: bool) -> None:
    """The kernel (interpreted) against its oracle on ``WINDOW_WALKS[case]``,
    the current token resident or handed in as an operand (``fresh``)."""
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
