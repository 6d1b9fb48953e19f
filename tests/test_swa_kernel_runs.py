"""A run of window pages is one copy (``ops/_page_copies.py``): the sliding
layers' decode kernel over tables of runs, of none and of broken ones, against
its ``jax.numpy`` oracle. The kernels over a table that starts mid-context are
in ``tests/test_swa_kernels.py``, which was one file with this until it passed
120 cpu-seconds of a whole run (eight programs of 10-14 s here).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops._page_copies import (
    RUN_PAGES,
    count_run_pages,
)
from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
    walk_step_pages,
)

PS = 4

#: (window, lengths from the table's first slot): pages of 4, so a window of
#: 64 is one group of 16 pages (and a page more where it starts inside one)
#: and a step of 32 pages holds it; the first visible page is slot ``(length
#: - window) // 4`` of the table
_RUN_WINDOWS = {
    "first-page-on-a-group-boundary": (64, [64, 96, 128]),
    "first-page-inside-a-group": (64, [76, 101, 139]),
    "short-histories": (64, [0, 1, 4, 32, 33, 36]),
    "two-steps": (160, [170, 301, 164]),
}
_RUN_WINDOW_POOL = 256


def _window_run_tables(kind, lens, width, window):
    rng = np.random.default_rng(9)
    tables = np.zeros((len(lens), width), np.int32)
    at = 1
    if kind == "the-dead-tail-goes-on-past-the-pool":
        # the last lane's pages end with the pool's last page
        at = _RUN_WINDOW_POOL - sum(-(-n // PS) + 1 for n in lens) + 1
    for row, n in zip(tables, lens):
        pages = -(-n // PS)
        ids = np.arange(at, at + pages)
        at += pages + 1
        if kind == "shuffled":
            ids = rng.permutation(ids)
        elif kind == "descending":
            ids = ids[::-1]
        elif kind == "broken-in-the-middle-of-a-group":
            # ... of the first group from the lane's first visible page
            ids = ids + (np.arange(pages) >= max(n - window, 0) // PS + 3)
            at += 1
        row[:pages] = ids
        if kind == "the-dead-tail-goes-on-past-the-pool":
            row[pages:] = (ids[-1] if pages else 0) + 1 + np.arange(width - pages)
    return tables


@pytest.mark.parametrize("walk", list(_RUN_WINDOWS))
@pytest.mark.parametrize("kind, fresh", [
    # the served call takes the current token as an operand (fresh); a
    # table of runs and one of none also with every token resident
    ("one-run", True), ("one-run", False), ("shuffled", True),
    ("shuffled", False), ("descending", True),
    ("broken-in-the-middle-of-a-group", True),
    ("the-dead-tail-goes-on-past-the-pool", True),
])
def test_window_kernel_over_tables_of_runs(kind, walk, fresh):
    window, lens = _RUN_WINDOWS[walk]
    rng = np.random.default_rng(3)
    b, n_kv, hd, layer = len(lens), 2, 16, 1
    width = -(-max(lens) // PS) + 3
    tables = _window_run_tables(kind, lens, width, window)
    pools = rng.normal(size=(2, 2, _RUN_WINDOW_POOL, PS, n_kv, hd)).astype(np.float32)
    live = np.zeros(_RUN_WINDOW_POOL, bool)
    for row, n in zip(tables, lens):
        live[row[: -(-n // PS)]] = True
    pools[:, :, ~live] = np.nan  # whatever no lane holds must not be read
    pools[:, 0] *= 1e3  # another layer's pages would be seen
    q = jnp.asarray(rng.normal(size=(b, 4, hd)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(2, b, n_kv, hd)), jnp.float32)
    clean = np.where(live[:, None, None, None], pools[:, layer], 0.0)
    if fresh:  # the oracle reads the current token from its slot
        for i, n in enumerate(lens):
            if n:
                clean[:, tables[i, (n - 1) // PS], (n - 1) % PS] = new[:, i]
    got = paged_attention(
        q, jnp.asarray(pools[0]), jnp.asarray(pools[1]), jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32), *(new if fresh else ()), window=window,
        scale=0.25, interpret=True, layer=jnp.int32(layer),
    )
    held = np.where(
        np.arange(width)[None, :] < -(-np.asarray(lens)[:, None] // PS), tables, 0
    )
    want = paged_attention_reference(
        q, jnp.asarray(clean[0]), jnp.asarray(clean[1]), jnp.asarray(held),
        jnp.asarray(lens, jnp.int32), window=window, scale=0.25,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # the case is what its name says: groups count from the first visible page
    hist = np.asarray(lens) - fresh
    first = np.maximum(np.asarray(lens) - window, 0) // PS
    pages, in_runs = count_run_pages(
        tables, first, np.maximum(-(-hist // PS) - first, 0),
        walk_step_pages(width, PS, window), _RUN_WINDOW_POOL,
    )
    assert pages == sum(
        max(-(-h // PS) - f, 0) for h, f in zip(hist.tolist(), first.tolist())
    )
    # every whole group of a step of 32 pages (``KEY_BLOCK`` 256 / 4 would
    # be 64: the table is narrower)
    step = walk_step_pages(width, PS, window)
    whole = sum(
        min(step, n - at) // RUN_PAGES * RUN_PAGES
        for n in np.maximum(-(-hist // PS) - first, 0).tolist()
        for at in range(0, n, step)
    )
    if kind in ("shuffled", "descending"):
        assert in_runs == 0
    elif kind == "broken-in-the-middle-of-a-group":
        assert in_runs < whole or not whole
    else:
        assert in_runs == whole
