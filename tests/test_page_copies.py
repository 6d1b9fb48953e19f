"""``ops/_page_copies.py``: a step's page copies, a run of pages as one.

A small interpreted kernel walks random tables through ``for_step_pages``
(start and wait at once, a descriptor counted as it is made) and hands back
what landed in its slot: every live page has to land where a copy a page
would put it, and the descriptors the kernel made have to be what
``count_run_pages`` (the numpy twin ``Engine._count_decode_dispatch`` counts
with) says of the same tables.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_d_kv_cache_manager_tpu.ops import _page_copies
from llm_d_kv_cache_manager_tpu.ops._page_copies import (
    count_run_pages,
    for_step_pages,
    group_pages,
)

PS, D = 4, 8


def _walk_kernel(tables_ref, first_ref, n_ref, pool_ref, out_ref, made_ref,
                 buf, sem, *, step_pages, steps):
    lane = pl.program_id(0)
    made_ref[0, 0] = 0

    def act(copy):
        copy.start()
        copy.wait()
        made_ref[0, 0] = made_ref[0, 0] + 1

    for step in range(steps):
        buf[...] = jnp.full_like(buf, -1.0)
        for_step_pages(
            act, tables_ref, lane, first_ref[lane] + step * step_pages,
            jnp.clip(n_ref[lane] - step * step_pages, 0, step_pages),
            jnp.int32(1), ((pool_ref, buf, sem.at[0]),),
        )
        out_ref[0, step] = buf[...]


def walk(tables, first, n_pages, pool, step_pages):
    """``(what landed [lanes, steps, step_pages, PS, D], descriptors made
    [lanes])`` of layer 1 of ``pool``."""
    lanes, width = tables.shape
    steps = -(-width // step_pages)
    landed, made = pl.pallas_call(
        functools.partial(_walk_kernel, step_pages=step_pages, steps=steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(lanes,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((1, steps, step_pages, PS, D),
                             lambda b, *_: (b, 0, 0, 0, 0)),
                pl.BlockSpec((1, 1), lambda b, *_: (b, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((step_pages, PS, D), jnp.float32),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((lanes, steps, step_pages, PS, D), jnp.float32),
            jax.ShapeDtypeStruct((lanes, 1), jnp.int32),
        ],
        interpret=True,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(first, jnp.int32),
      jnp.asarray(n_pages, jnp.int32), pool)
    return np.asarray(landed), np.asarray(made)[:, 0]


def tables_of_runs(rng, lanes, width, pool_pages):
    """Rows made of runs of 1 to 48 consecutive ids from anywhere in the
    pool (its last page among them), ascending and, now and then, one that
    descends."""
    tables = np.zeros((lanes, width), np.int32)
    for row in tables:
        at = 0
        while at < width:
            n = int(min(rng.integers(1, 49), width - at))
            start = int(rng.integers(1, pool_pages - n + 1))
            ids = np.arange(start, start + n)
            row[at:at + n] = ids[::-1] if rng.random() < 0.1 else ids
            at += n
    return tables


@pytest.mark.parametrize("run_pages", [2, 4, 8, 16])
@pytest.mark.parametrize("step_pages", [8, 12, 16])
def test_the_kernel_makes_the_copies_the_twin_counts(
    monkeypatch, run_pages, step_pages
):
    monkeypatch.setattr(_page_copies, "RUN_PAGES", run_pages)
    rng = np.random.default_rng(100 * run_pages + step_pages)
    lanes, width, pool_pages = 12, 64, 96
    pool = rng.normal(size=(2, pool_pages, PS, D)).astype(np.float32)
    tables = tables_of_runs(rng, lanes, width, pool_pages)
    first = rng.integers(0, 6, lanes)
    n_pages = np.minimum(rng.integers(0, width + 1, lanes), width - first)
    n_pages[:2] = 0, 1
    first[2], n_pages[2] = 0, width  # a whole table
    # a dead tail may hold anything: ids past the pool that go on a run
    for row, lo in zip(tables, first + n_pages):
        row[lo:] = row[lo - 1] + 1 + np.arange(width - lo) if lo else pool_pages

    landed, made = walk(tables, first, n_pages, jnp.asarray(pool), step_pages)
    g = group_pages(step_pages, pool_pages)
    assert g == min(run_pages, step_pages)
    for lane in range(lanes):
        live = tables[lane, first[lane]:first[lane] + n_pages[lane]]
        got = landed[lane].reshape(-1, PS, D)
        np.testing.assert_array_equal(got[:len(live)], pool[1, live])
        assert (got[len(live):] == -1.0).all()  # nothing else was copied
        pages, in_runs = count_run_pages(
            tables[lane:lane + 1], first[lane], n_pages[lane], step_pages,
            pool_pages,
        )
        assert pages == len(live)
        assert made[lane] == pages - in_runs + in_runs // g
    # ... and the whole array at once is the lanes' sum
    pages, in_runs = count_run_pages(
        tables, first, n_pages, step_pages, pool_pages
    )
    assert pages == n_pages.sum()
    assert made.sum() == pages - in_runs + in_runs // g
    assert 0 < in_runs < pages


@pytest.mark.parametrize("width", [24, 100, 160, 300])
def test_the_full_calls_walk_is_what_the_twin_counts(width):
    """A full layer's ``paged_attention`` (PR 57): from the table's first
    page, ``walk_step_pages`` pages a step, groups of ``RUN_PAGES``, as
    ``Engine._count_ctx_pages`` counts ``full_ctx_pages`` /
    ``full_ctx_run_pages``."""
    from llm_d_kv_cache_manager_tpu.ops.paged_attention import walk_step_pages

    rng = np.random.default_rng(width)
    lanes, pool_pages = 6, 400
    step_pages = walk_step_pages(width, PS)
    pool = rng.normal(size=(2, pool_pages, PS, D)).astype(np.float32)
    tables = tables_of_runs(rng, lanes, width, pool_pages)
    tables[0] = np.arange(5, 5 + width)  # one run
    tables[1] = np.arange(width, 0, -1)  # none
    n_pages = rng.integers(1, width + 1, lanes)
    n_pages[:3] = width, width, 0
    for row, lo in zip(tables, n_pages):  # dead tails that go on a run
        row[lo:] = row[lo - 1] + 1 + np.arange(width - lo) if lo else pool_pages
    first = np.zeros(lanes, np.int64)
    landed, made = walk(tables, first, n_pages, jnp.asarray(pool), step_pages)
    g = group_pages(step_pages, pool_pages)
    assert g == min(_page_copies.RUN_PAGES, step_pages)
    for lane in range(lanes):
        live = tables[lane, :n_pages[lane]]
        got = landed[lane].reshape(-1, PS, D)
        np.testing.assert_array_equal(got[:len(live)], pool[1, live])
        pages, in_runs = count_run_pages(
            tables[lane:lane + 1], 0, n_pages[lane], step_pages, pool_pages)
        assert pages == len(live)
        assert made[lane] == pages - in_runs + in_runs // g
    pages, in_runs = count_run_pages(tables, 0, n_pages, step_pages, pool_pages)
    assert pages == n_pages.sum() and 0 < in_runs < pages
    # the lane that is one run: every whole group of every step
    one = count_run_pages(tables[:1], 0, width, step_pages, pool_pages)[1]
    assert one == sum(
        min(step_pages, width - at) // g * g for at in range(0, width, step_pages)
    )
    assert count_run_pages(tables[1:2], 0, width, step_pages, pool_pages)[1] == 0


@pytest.mark.parametrize("row, n_pages, want", [
    pytest.param(range(10, 26), 16, 16, id="one-run"),
    pytest.param(range(25, 9, -1), 16, 0, id="descending"),
    pytest.param([3, 9, 4, 8, 5, 7, 6, 2, 1, 12, 11, 14, 13, 16, 15, 10], 16, 0,
                 id="shuffled"),
    pytest.param([*range(10, 14), *range(15, 27)], 16, 8,
                 id="broken-in-the-middle-of-a-group"),
    pytest.param([*range(10, 18), *range(30, 38)], 16, 16,
                 id="broken-between-groups"),
    pytest.param(range(10, 26), 15, 8, id="the-last-group-is-not-whole"),
    pytest.param(range(10, 26), 8, 8, id="ends-at-the-last-live-page"),
    pytest.param(range(10, 26), 7, 0, id="shorter-than-a-group"),
    pytest.param(range(10, 26), 0, 0, id="no-page"),
])
def test_the_twin_on_tables_made_by_hand(monkeypatch, row, n_pages, want):
    monkeypatch.setattr(_page_copies, "RUN_PAGES", 8)
    assert count_run_pages([list(row)], 0, n_pages, 16, 64) == (n_pages, want)


def test_the_twin_counts_groups_from_the_lanes_first_page(monkeypatch):
    monkeypatch.setattr(_page_copies, "RUN_PAGES", 8)
    row = [0, 0, 0, *range(10, 26), 0]
    # from slot 3 the groups are [10..17] and [18..25]; from slot 0 the
    # second is [15..22] and the third is not whole
    assert count_run_pages([row], 3, 16, 16, 64) == (16, 16)
    assert count_run_pages([row], 0, 19, 16, 64) == (19, 8)
    # steps of 4 pages are groups of 4, and so is a pool of 4 pages
    assert count_run_pages([row], 3, 16, 4, 64) == (16, 16)
    assert count_run_pages([[1, 2, 3, 1, 2, 3, 0, 0]], 0, 6, 16, 4) == (6, 0)
    assert count_run_pages([[0, 1, 2, 3, 1, 2, 3, 0]], 0, 4, 16, 4) == (4, 4)
