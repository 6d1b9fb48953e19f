"""A step's page copies, for the kernels that walk a lane's table themselves.

A pool is ``[layers, pages, page_size, ...]``, so pages ``p, p + 1, ..., p +
G - 1`` of one layer are one contiguous stretch of HBM, and a free list that
hands out ascending ids gives a sequence such stretches. What a copy costs
such a kernel is STARTING it (about 0.05 us a descriptor on a TPU v5e,
whether it moves 20 KiB or 32 KiB), not its bytes. So a step's pages are
walked in groups of ``RUN_PAGES``: a group whose pages are all live and whose
pool ids are consecutive (a RUN) is one ``make_async_copy`` of
``pool.at[layer, pl.ds(first_id, G)]``; any other group is copied a page at a
time. The decision is read from the table row the kernel already holds in
SMEM, ``G - 1`` compares a group, and is rebuilt from the same words when the
copies are waited for, as the handles are.

- ``for_step_pages`` is the loop (``ops/mla_attention.py::_mla_kernel`` and
  ``ops/paged_attention.py::_walk_decode_kernel``, the full-context call and
  the sliding layers', call it, at start and at wait time);
  ``group_is_run`` its predicate.
- ``count_run_pages`` is the predicate's numpy twin over a dispatch's whole
  table array, for ``Engine._count_ctx_pages`` (``step_stats["ctx_pages"]``
  / ``["ctx_run_pages"]`` of the latent or the window walk,
  ``["full_ctx_pages"]`` / ``["full_ctx_run_pages"]`` of a full layer's;
  ``tests/test_page_copies.py`` holds the two to one answer).

Groups count from a step's first slot, and a group that reaches past the
lane's last live page is never a run: a dead table tail may hold anything
and is not read.

On a TPU v5e, the eight layers' ``mla_decode`` calls of a `docqa` step (32
lanes x 12-29k rows, 5.9 GB, 64 pages a step) took 17.2 ms with a copy a page
in a loop, whatever the table held, and (chip runs, PR 45: PERF.md section 6):

    G                        4      8      16     32
    tables that are one run  14.9   14.2   11.3   11.2  ms
    tables that hold no run  14.9   14.2   13.3   13.1  ms

At 4 and 8 a run reads what no run reads, to 0.02 ms: both branches of so
short a group cost their copies' issue slots whichever is taken (the branch
seems predicated, not jumped). From 16 on the branch is real: a run is at the
call's floor (5.9 GB at 530 GB/s; one descriptor of 64 pages read 11.3 ms
too) and a group of single copies without a loop around them still beats the
loop. The smallest such G keeps the most pages in runs once a pool's free
list is mixed. The window kernel's four calls of a `longdocs` step (K and V,
16 pages a step): 5.12 ms before, 4.82 over runs, 4.96 over none.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: pages a group (``G``): the most one descriptor copies (the sweep is in the
#: module's docstring). A step of fewer pages is one group.
RUN_PAGES = 16


def group_pages(step_pages: int, pool_pages: int) -> int:
    """Pages a group of a step of ``step_pages`` pages over a pool of
    ``pool_pages`` (a descriptor is no longer than the pool)."""
    return max(min(RUN_PAGES, step_pages, pool_pages), 1)


def group_is_run(ids):
    """Whether a group's pool ids ``ids`` (a scalar a page, every page live)
    are one run: ``ids[j] == ids[0] + j`` throughout."""
    ok = lax.eq(ids[1], lax.add(ids[0], np.int32(1)))
    for j in range(2, len(ids)):
        ok = lax.bitwise_and(ok, lax.eq(ids[j], lax.add(ids[0], np.int32(j))))
    return ok


def for_step_pages(act, table_ref, lane, first, n_live, layer, streams,
                   rolled: bool = False):
    """``act`` on the copy of every live page of one step, runs as one.

    ``table_ref[lane, first + i]`` is the pool id of the step's slot ``i``,
    ``n_live`` the slots that hold history (the step's leading ones),
    ``streams`` a tuple of ``(pool_ref, buf_ref, sem_ref)``: the pool ``[L,
    P, page_size, ...]`` where it lies, the VMEM slot ``[step pages,
    page_size, ...]`` the step lands in, and the DMA semaphore of that slot
    (K and V are two streams of one table). The handles are rebuilt
    identically at start and at wait time (the standard Pallas async-copy
    idiom), and so is a group's decision, from the same words.

    Whole groups come first, each straight-line code: its ``G`` table words
    are read once, for the compares and for the copies, and a group that is
    no run is ``G`` copies with no loop around them. What is left of a
    step's live pages (fewer than ``G``) is a loop of single copies, so no
    word past the lane's last live page is read.

    ``rolled``: a group that is no run is the loop of single copies too, so
    the function writes three copies a stream (the run, the group's loop,
    the tail's) in place of ``G + 2``. Two callers ask for it. The Pallas interpreter, for every
    kernel: it writes some 220 lines of HLO a copy, a kernel holds this
    function three times over its streams, and 108 copies a kernel made
    every served program of the CPU tests cost 3.5 s to lower and compile
    where it had cost 0.4 (PR 56). And the full-context ``paged_attention``
    on the chip (PR 57: ``ops/paged_attention.py`` says what a warm start
    costs a program that holds the kernel, and what the loop costs a step).
    The latent kernel and the sliding layers' call keep, on the chip, the
    straight group they were swept with."""
    g = group_pages(streams[0][1].shape[0], streams[0][0].shape[1])

    def word(i):
        return table_ref[lane, first + i]

    def copy_page(page, i):
        for pool_ref, buf_ref, sem_ref in streams:
            act(pltpu.make_async_copy(
                pool_ref.at[layer, page], buf_ref.at[i], sem_ref
            ))

    def one_page(i, carry):
        copy_page(word(i), i)
        return carry

    n_groups = n_live // g if g > 1 else 0

    def one_group(gi, carry):
        base = gi * g
        ids = [word(base + j) for j in range(g)]
        is_run = group_is_run(ids)

        @pl.when(is_run)
        def _run():
            for pool_ref, buf_ref, sem_ref in streams:
                act(pltpu.make_async_copy(
                    pool_ref.at[layer, pl.ds(ids[0], g)],
                    buf_ref.at[pl.ds(base, g)],
                    sem_ref,
                ))

        @pl.when(jnp.logical_not(is_run))
        def _pages():
            if rolled:
                jax.lax.fori_loop(base, base + g, one_page, 0)
            else:
                for j, page in enumerate(ids):
                    copy_page(page, base + j)

        return carry

    if g > 1:
        jax.lax.fori_loop(0, n_groups, one_group, 0)
    jax.lax.fori_loop(n_groups * g, n_live, one_page, 0)


def count_run_pages(tables, first, n_pages, step_pages: int, pool_pages: int):
    """``(live pages, pages copied in a run)`` of a dispatch's table array
    ``[lanes, width]``: the numpy twin of ``for_step_pages``' decision.
    Lane ``l`` copies ``tables[l, first[l] : first[l] + n_pages[l]]``,
    ``step_pages`` a step from ``first[l]``, each step in groups of
    ``group_pages(step_pages, pool_pages)``. One pass over the table (which
    neighbours are consecutive, summed along a row) and a compare a group:
    0.36 ms for 32 lanes of 2176 pages, on the host beside the device."""
    tables = np.asarray(tables)
    lanes, width = tables.shape
    # a value a lane (or one for all), against [lane, group]
    first = np.broadcast_to(first, (lanes,))[:, None]
    n_pages = np.broadcast_to(n_pages, (lanes,))[:, None]
    live = int(np.clip(np.minimum(n_pages, width - first), 0, None).sum())
    g = group_pages(step_pages, pool_pages)
    if g == 1:
        return live, live
    if g > min(width, n_pages.max()):
        return live, 0  # no lane holds a whole group
    # the groups that end inside their step, by their first page's place
    # among a lane's live pages
    starts = (
        np.arange(-(-width // step_pages))[:, None] * step_pages
        + np.arange(step_pages // g) * g
    ).ravel()
    cols = first + starts
    whole = (starts + g <= n_pages) & (cols + g <= width)
    # joined[l, x]: the slots before x whose successor holds the next pool id
    joined = np.zeros((lanes, width), np.int32)
    np.cumsum(tables[:, 1:] == tables[:, :-1] + 1, axis=1, out=joined[:, 1:])
    at = np.clip(cols, 0, width - g)
    lane = np.arange(lanes)[:, None]
    runs = whole & (joined[lane, at + g - 1] - joined[lane, at] == g - 1)
    return live, int(runs.sum()) * g
