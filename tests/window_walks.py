"""The walks of the decode call (``paged_attention``: a program a lane that
walks the lane's table itself, ``walk_step_pages`` pages of tiles a step), and
the pools, tables and oracle inputs a walk is run on, and the comparison
itself. The sliding layers' (``window=``): ``WINDOW_WALKS``, shared by
``tests/test_paged_attention_window.py`` (tables narrower than one step) and
``tests/test_paged_attention_window_steps.py`` (tables of several steps). The
full-context call's: ``FULL_WALKS``, for ``tests/test_paged_attention_walk.py``.
Not collected.
"""

import jax.numpy as jnp
import numpy as np

from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    WIDE_TABLE_TOKENS,
    paged_attention,
    paged_attention_reference,
    walk_step_pages,
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


# The full-context call: a walk from the table's first page to the page of
# the last historical token. (page size, table pages, the lanes' histories:
# the tokens resident in pages, in steps of the walk and slots beyond them),
# so a case is what its name says whatever ``walk_step_pages`` gives: a
# table is ``FULL_TABLE_STEPS`` steps wide and a page more. Four lanes a
# case: the cases of one page size are one compiled program a process.
FULL_TABLE_STEPS = 3
FULL_WALKS = {
    "lanes-of-length-0-beside-live-ones": (16, [None, (1, 7), None, (0, 31)]),
    "history-ends-mid-page": (16, [(0, 5), (1, 21), (2, 40), (0, 0)]),
    "history-ends-on-a-pages-last-slot": (16, [(0, 16), (1, 32), (2, 16), (0, 48)]),
    "history-ends-mid-step": (16, [(1, 72), (2, 24), (0, 8), (2, 200)]),
    "history-ends-on-a-steps-last-slot": (16, [(1, 0), (2, 0), (3, 0), (1, 0)]),
    "the-table-is-full": (16, [(3, 15), (3, 14), (3, 0), (2, 255)]),
    "pages-of-4": (4, [(0, 3), (1, 0), (3, 2), None]),
}
#: how a lane's pages lie in the pool: one run a lane (ids ascending by
#: one, which the kernel copies a group at once), or no two in a row
FULL_TABLES = ("one-run", "no-run")


def full_setup(seed, ps, hists, kind, dtype=jnp.float32, layers=3,
               heads=(2, 3), d=32, wide=False):
    """Pools of ``layers`` layers, a block table a lane (``kind`` of
    ``FULL_TABLES``; distinct pages, none of them page 0, which pads the
    tables' dead tails and is poisoned), the lanes' lengths with the current
    token, and that token's key and value. ``heads``: (KV heads, group).
    ``wide``: a table of ``WIDE_TABLE_TOKENS``, whose step is twice as long."""
    rng = np.random.default_rng(seed)
    n_kv, group = heads
    width = WIDE_TABLE_TOKENS // ps if wide else 1
    for _ in range(4):  # a step is no wider than the table: a fixed point
        step = walk_step_pages(width, ps)
        width = max(width, FULL_TABLE_STEPS * step + 1)
    lens = [0 if h is None else h[0] * step * ps + h[1] + 1 for h in hists]
    assert max(lens) <= width * ps  # the current token's slot is in the table
    b = len(lens)
    total = b * width + 1
    q = jnp.array(rng.standard_normal((b, n_kv * group, d)), dtype)
    k = jnp.array(rng.standard_normal((layers, total, ps, n_kv, d)) * 0.5, dtype)
    v = jnp.array(rng.standard_normal((layers, total, ps, n_kv, d)), dtype)
    k = k.at[:, 0].set(1e4)
    v = v.at[:, 0].set(1e4)
    tables = np.arange(1, total).reshape(b, width)
    if kind == "no-run":
        tables = tables[:, ::-1].copy()
    for i, n in enumerate(lens):  # past a lane's pages: the caller's padding
        tables[i, -(-n // ps):] = 0
    fk = jnp.array(rng.standard_normal((b, n_kv, d)), dtype)
    fv = jnp.array(rng.standard_normal((b, n_kv, d)), dtype)
    return q, k, v, jnp.array(tables, jnp.int32), lens, fk, fv


def check_full_walk(case, fresh, kind, layer=2, **setup):
    """The full-context kernel (interpreted) against its oracle on
    ``FULL_WALKS[case]``, the current token resident or handed in as an
    operand (``fresh``), over tables of ``kind``. Returns the output."""
    ps, hists = FULL_WALKS[case]
    q, k, v, tables, lens, fk, fv = full_setup(31, ps, hists, kind, **setup)
    k_ref, v_ref = with_fresh_written(k, v, tables, lens, fk, fv, layer, ps)
    if not fresh:  # every token resident: the pools the oracle reads
        k, v = k.at[layer].set(k_ref), v.at[layer].set(v_ref)
    sl = jnp.array(lens, jnp.int32)
    got = paged_attention(
        q, k, v, tables, sl, *((fk, fv) if fresh else ()), interpret=True,
        layer=layer)
    want = paged_attention_reference(q, k_ref, v_ref, tables, sl)
    assert bool(jnp.all(jnp.isfinite(got)))
    tol = 2e-5 if q.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)
    assert got.dtype == q.dtype
    for i, n in enumerate(lens):  # no NaN from a never-written VMEM slot
        assert (float(jnp.abs(got[i]).max()) == 0.0) == (n == 0)
    return got
