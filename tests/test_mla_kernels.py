"""The latent attention kernel over tables of runs: a run of pages that lie
together is one copy (``ops/_page_copies.py``), whatever the table holds past
a lane's pages. The kernel against its oracle on plain tables, and the served
programs that call it, are in ``tests/test_mla.py``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops._page_copies import (
    RUN_PAGES,
    count_run_pages,
)
from llm_d_kv_cache_manager_tpu.ops.mla_attention import (
    mla_paged_attention,
    mla_paged_attention_reference,
)

PS = 4


# -- a run of pages is one copy (ops/_page_copies.py) ---------------------------
#: pages a lane: none, one, a group, a group and one, a step and some, two
#: steps and some (``key_block`` 32 is a step of 32 pages of 4 rows)
_RUN_LANES = [0, 1, RUN_PAGES, RUN_PAGES + 1, 38, 75]
_RUN_POOL = 256


def _run_tables(kind, width):
    """A table a lane of ``_RUN_LANES`` pages, laid out as ``kind`` says, and
    rows of ``ctx`` tokens (a lane's last page half full where it has one).
    The dead tail of every row holds page 0, which is NaN, unless ``kind``
    says what it holds."""
    rng = np.random.default_rng(7)
    tables = np.zeros((len(_RUN_LANES), width), np.int32)
    at = 1
    for row, n in zip(tables, _RUN_LANES):
        ids = np.arange(at, at + n)
        at += n + 2
        if kind == "shuffled":
            ids = rng.permutation(ids)
        elif kind == "descending":
            ids = ids[::-1]
        elif kind == "broken-in-the-middle-of-a-group":
            ids = ids + (np.arange(n) >= 3) + (np.arange(n) >= RUN_PAGES + 5)
            at += 2
        elif kind in ("ends-at-the-pools-last-page",
                      "the-dead-tail-goes-on-as-a-run"):
            ids = ids - ids[-1:] + _RUN_POOL - 1
        row[:n] = ids
        if kind == "the-dead-tail-goes-on-as-a-run":
            # ... past the pool: a copy that took it for a run would start
            # where no page is (the interpreter then reads other pages)
            row[n:] = (ids[-1] if n else 0) + 1 + np.arange(width - n)
        elif kind == "the-dead-tail-holds-garbage":
            row[n:] = rng.integers(-5, 2 * _RUN_POOL, width - n)
    return tables


@pytest.mark.parametrize("s", [1, 20], ids=["decode", "chunk"])
@pytest.mark.parametrize("kind", [
    "one-run", "shuffled", "descending", "broken-in-the-middle-of-a-group",
    "ends-at-the-pools-last-page", "the-dead-tail-goes-on-as-a-run",
    "the-dead-tail-holds-garbage",
])
def test_kernel_over_tables_of_runs(kind, s):
    rng = np.random.default_rng(1)
    b, heads, dk, dv, layers, layer = len(_RUN_LANES), 4, 40, 32, 2, 1
    width = max(_RUN_LANES) + 5
    tables = _run_tables(kind, width)
    q = jnp.asarray(rng.normal(size=(b, s, heads, dk)), jnp.float32)
    fresh = jnp.asarray(rng.normal(size=(b, s, dk)), jnp.float32)
    pool = rng.normal(size=(layers, _RUN_POOL, PS, dk)).astype(np.float32)
    live = np.zeros(_RUN_POOL, bool)
    for row, n in zip(tables, _RUN_LANES):
        live[row[:n]] = True
    pool[:, ~live] = np.nan  # whatever no lane holds must not be read
    pool[0] *= 1e3  # another layer's rows would be seen
    ctxs = [max(n * PS - 2, 0) for n in _RUN_LANES]
    args = (jnp.asarray(tables), jnp.asarray(ctxs, jnp.int32),
            jnp.full((b,), s, jnp.int32))
    got = mla_paged_attention(
        q, fresh, jnp.asarray(pool), *args, dv=dv, scale=0.3, interpret=True,
        key_block=32, layer=layer,
    )
    # the oracle gathers a table's every entry: give it the live ones alone
    clean = np.where(
        np.arange(width)[None, :] < np.asarray(_RUN_LANES)[:, None], tables, 0
    )
    clean_pool = np.where(live[:, None, None], pool[layer], 0.0)
    want = mla_paged_attention_reference(
        q, fresh, jnp.asarray(clean_pool), jnp.asarray(clean), *args[1:],
        dv=dv, scale=0.3,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    # the case is what its name says
    pages, in_runs = count_run_pages(
        tables, 0, -(-np.asarray(ctxs) // PS), 32, _RUN_POOL
    )
    assert pages == sum(_RUN_LANES)
    if kind in ("shuffled", "descending"):
        assert in_runs == 0
    elif kind == "broken-in-the-middle-of-a-group":
        assert 0 < in_runs < pages
    else:  # every whole group of a step of 32 pages
        assert in_runs == sum(
            min(32, n - at) // RUN_PAGES * RUN_PAGES
            for n in _RUN_LANES for at in range(0, n, 32)
        ) > 0
