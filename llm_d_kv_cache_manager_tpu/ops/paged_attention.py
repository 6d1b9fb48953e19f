"""Paged decode attention: one walked Pallas TPU kernel + reference implementation.

The serving engine stores KV in fixed-size pages (blocks) scattered across a
pool; at decode each sequence reads its pages via a block table. This is the
hot op the reference ecosystem gets from vLLM's CUDA paged attention — here
it is a TPU kernel designed for the hardware. ``paged_attention`` is the one
entry and ``_walk_decode_kernel`` the one body: a program a LANE that walks
the lane's own pages (the sliding layers' call since PR 44, every call since
PR 57; before that the full-context call was a program a (lane, table page)
through the ``BlockSpec`` pipeline, whether the lane had a page there or not).

- KV pool layout ``[n_layers, total_pages, page_size, n_kv_heads, head_dim]``:
  page-major, so one page's full KV tile ``[page_size, n_kv, head_dim]`` is
  a single contiguous block (lane dim = head_dim = 128-friendly) and pages
  with consecutive ids one stretch; the engine's per-token write slice
  ``[n_kv, head_dim]`` stays minor-contiguous (default XLA layout, no
  conversion copies).
- Grid ``(batch,)``; the pools whole in ``ANY`` memory space, read in
  place; the table, the lengths and the LAYER as scalar prefetch, so a
  model's full layers share one kernel and its sliding layers another.
- A loop from the first page that holds a visible slot (page 0 for a layer
  that sees its whole context) to the page of the last historical token,
  ``walk_step_pages`` pages a step: the kernel copies those page tiles of K
  and of V into one of two VMEM slots with ``make_async_copy`` (a group of
  pages whose pool ids are consecutive as ONE copy:
  ``_page_copies.for_step_pages``, PR 45), starts the next step's copies
  before it computes, and makes one float32 online-softmax update over the
  whole block, every KV head at once (GQA: query heads blocked ``[group,
  head_dim]`` against one KV head). Work follows the lane's live pages, not
  the table's width; a lane of length 0 runs no step.
- The current token's K/V ride as operands (``has_fresh``) and merge after
  the loop, so the caller writes the pool once for all layers.
- int8 pools (``k_scale`` / ``v_scale``): the codes are copied as they lie,
  the lane's scales come gathered in table order as a lane's ``BlockSpec``
  operand, and the scores and the probabilities take them in float32.

**The full-context call** (``window=0``; ``paged_attention`` in a trace):
every layer that sees its whole context, int8 pools, ``tp`` shards.
**A sliding layer's call** (``window=``; ``paged_attention_window`` in a
trace): the same body over the window pools and a lane's window table.

On a TPU v5e at `longdocs`' shape (32 lanes, 8 KV heads, a group of 6) the
four sliding layers' calls (a 259-page table) took 15.9 ms a decode step
under the program-a-page kernel, bound by issuing 33 000 programs, and 4.3
ms walked (chip run, PR 44: PERF.md section 6). The full-context call alone
(chip runs, PR 56: bare calls over synthetic tables at the cells' shapes,
every full layer of a decode step, ms a step; "runs": a lane's pages lie
together, "none": no two in a row; the program-a-page kernel read the same
over both):

                                            a page a   tokens a step, walked
    shape (lanes, table pages, contexts)    program    256    512    1024
    longdocs   32 x 2176, 8-33k, 1 layer     20.28     4.17   3.78   3.66  runs
                                                       4.32   4.39   4.73  none
    agentloop  32 x 640, 2-9k, 3 layers      17.82     3.22   3.27   3.56  runs
                                                       3.97   4.00   4.21  none
    mixedlen   32 x 848, 1-13k, 2 layers     13.74     2.53   2.54   2.72  runs
                                                       3.13   3.13   3.25  none
    sessions   16 x 240, 1-4k, 2 layers       2.66     0.65   0.66   0.77  runs
    reasoning  16 x 96, 128-1536, 2 layers    1.00     0.31   0.33   0.38  runs

Between the two widths the rule divides, at `longdocs`' heads and one layer
(chip run, PR 56 after review; contexts a quarter, a half and all of the
table; 256 / 512 tokens a step): 1024 pages, 4-15k: 2.01 / 1.86 over runs,
2.08 / 2.13 over none; 1408 pages, 6-21k: 2.75 / 2.52 and 2.86 / 2.91; 2176
pages again: 4.24 / 3.84 and 4.41 / 4.46. From 16k tokens of table on, 512
reads 7-9 % under 256 over runs and 1-2 % over it where no two pages lie
together; under it (the 4-KV-head shapes above) the two tie or 256 leads.

`longdocs`' call moves 2.32 GB (2.84 ms at 819 GB/s: 75 % at 512 over runs);
a lane's last step computes a whole block for what is left of it, so a short
lane pays for a long step, and a long lane (65-130 steps of 256) pays for
each step's start: ``walk_step_pages`` takes 256 and, from a table of
``WIDE_TABLE_TOKENS`` on, 512.

**What a warm start costs a program that holds the kernel, and why the
full-context call's group is a loop** (chip runs, PR 57:
``tools/warm_start_probe.py``, every full layer's call of a `sessions` decode
step as one jitted function on the chip machine's host; PERF.md section 6 has
`longdocs`' shape and the whole set-up a program). Seconds:

                                 .trace() .lower()  .compile()  from a warm cache:  first call
    kernel (copy descriptors)                        cache off   read + load         - second
    a page a program, 5 kernels    0.11     0.09      0.19        0.012              0.001
    walked, straight group (108)   0.76     1.52      0.67        0.021              0.002
    walked, rolled group (18)      0.25     0.65      0.45        0.016              0.001
    ... and its loop folded (12)   0.19     0.72      0.64        0.015              0.001

A cache hit is 10-20 ms whatever the kernel (entries of 47-104 KB, executables
of 0.6-2.3 MB) and the first call is the second plus a millisecond or two.
What a warm start pays is JAX's own trace and lowering of the body, which run
in every process BEFORE the cache can be asked (its key is the lowered
module): some 15 ms a copy descriptor on that host, 2 s a program for the
straight group's 108 (three places write the step's copies, two streams, a
run copy + 16 single copies + a tail loop each), once a decode width and once
in the reference check. PR 56 built that form and the driver refused it for
`sessions`' warm set-up (39.4 -> 44.2 s, bound 10 %). So the full-context call
writes a group that is no run as a loop of single copies
(``_page_copies.for_step_pages(rolled=)``), as the Pallas interpreter is given
every call (it writes 220 lines of HLO a copy, and every served program of the
CPU tests holds this kernel), and writes a step's copies at two places, not
three: its loop begins a step early, and step -1 starts step 0's copies and
attends over nothing (12 descriptors). The probe's first ``.lower()`` of a
process holds some 0.4 s that a second lowering in the same process does not
pay (0.28 s), so a served program shows less than the table: the singles that
bring `sessions` a decode width took 2.58 / 2.56 / 1.72 s at the parent, 3.20 /
2.86 / 2.56 with the group rolled alone and 2.43 / 2.09 / 1.78 with the loop
folded too, and three warm set-ups of `sessions` read 40.1 -> 39.7, 40.1 ->
38.8 and 40.1 -> 41.7 s (`agentloop` 83.9 -> 83.9). What the forms cost a step:
the folded loop's extra pass a lane read `decode_scope_ms.attn` 3.17 -> 3.35 ms
in `agentloop` (96 programs a step) and nothing in `sessions`; the rolled
group reads the straight one's time to 2 % over tables that are runs at all
five shapes and 8-10 % over it where no two pages lie together (2.6 % at
`reasoning`'s), and the cells copy 85-91 % of their pages in runs; 512 tokens
a step still read 5 % under 256 over runs at 2176 pages and tie over none.
The sliding layers' call keeps the straight group it was swept with (PR 45),
from the call's ``window``, no argument of a caller's; its Mosaic text is what
it was before PR 57 to the byte.

CPU tests run the same kernel with ``interpret=True`` (the caller's choice —
asking for the compiled kernel off-TPU raises).
``paged_attention_reference`` is the numerics oracle.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import require_tpu_unless_interpret
from ._page_copies import for_step_pages

_NEG_INF = float("-inf")
# Finite, for the kernel: a block whose every slot is masked must give
# exp(-1e30 - -1e30) = 1, zeroed by the mask multiply, not inf - inf = NaN.
_MASKED = -1e30

#: tokens of page tiles a step of the kernel copies and attends over
#: (``KEY_BLOCK / page_size`` pages of K and of V into each of two VMEM
#: slots). On a TPU v5e the four sliding layers' calls of a `longdocs` decode
#: step (32 lanes, 8 KV heads, a group of 6, 256-257 live pages of a 259-page
#: table) took 5.02 / 4.32 / 4.29 / 4.59 / 5.12 ms at 128 / 256 / 384 / 512 /
#: 1024 tokens a step: a lane's last step holds one page and computes a whole
#: block, and the copies alone are 3.0-3.5 ms (chip runs, PR 44: PERF.md
#: section 6). With a run of pages as one copy (``_page_copies``; chip runs,
#: PR 45, another table and pool than PR 44's): 5.12 ms before, 4.82 / 4.74
#: at 256 / 512 over tables that are one run, 4.96 / 5.16 over tables that
#: hold none: 256 is kept.
KEY_BLOCK = 256
#: the table width (tokens) from which a full-context call's step is two
#: ``KEY_BLOCK``: 512 tokens a step read 7.5 / 8.4 / 9.4 % under 256 over
#: tables of 1024 / 1408 / 2176 pages whose contexts lie in runs (2.4 / 1.9 /
#: 1.1 % over it where no two pages lie together), and the same or 2 % over
#: it at 848 and 640 pages (chip runs, PR 56: the module's docstring)
WIDE_TABLE_TOKENS = 16384
#: what the kernel may use of a v5e core's 128 MiB of VMEM (the compiler's
#: default scoped limit is 16 MiB)
_VMEM_LIMIT = 64 * 1024 * 1024


def _walk_decode_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32: a scalar, so every layer's call is one kernel
    tables_ref,  # [batch, table_pages] int32: the lanes' tables
    seq_lens_ref,  # [batch] int32, counted from the table's first slot
    # operands
    q_ref,  # [1, n_kv, group, head_dim]
    k_pool_ref,  # [L, P, page_size, n_kv, head_dim]: the whole pool (ANY)
    v_pool_ref,
    *refs,  # [k_scale_ref, v_scale_ref,] [fresh_k_ref, fresh_v_ref,]
    #         out_ref, k_buf, v_buf, sem
    window: int,
    page_size: int,
    block_pages: int,
    table_pages: int,
    scale: float,
    has_fresh: bool,
    quantized: bool,
    rolled: bool,
):
    """One lane's decode attention, every KV head at once.

    The token at ``seq_len - 1`` sees the ``window`` slots that end with
    itself (``window`` 0: every slot before it). The program walks its
    lane's table from the first page that holds a visible slot to the page
    of the last historical token, ``block_pages`` pages a step: it copies
    those page tiles ``pool[layer, table[b, page]]`` of K and of V into one
    of two VMEM slots itself, starts the next step's copies before it
    computes, and makes one online-softmax update over the whole block. A
    lane of length 0 runs no step.

    ``has_fresh``: the current token's K/V arrive as operands ([1, n_kv, 1,
    d] blocks) instead of from the pages, which then hold only the ``seq_len
    - 1`` historical tokens, and merge after the loop. This lets the caller
    defer the pool write until after attention — one batched scatter per
    step, never a pool rebuild.

    ``quantized`` (``KV_QUANT_HBM=int8``, full-context calls): the pools
    hold int8 codes and the copies move HALF the bytes. The lane's scales, a
    page and KV head, arrive in table order as ``[1, steps, n_kv, 1,
    block_pages]`` float32 blocks; a step spreads its row over the block's
    keys (a 0/1 matmul: a page's slots share its scale) and the scores take
    K's, the probabilities V's, in float32: ``q . (s c) = s (q . c)``, so
    full-width pages never exist anywhere. The current token stays
    full-precision: fresh K/V never round-trip through int8.

    ``rolled``: a group of pages that is no run is a loop of single copies
    (``for_step_pages``): the full-context call and every interpreted one."""
    if quantized:
        k_scale_ref, v_scale_ref = refs[:2]
        refs = refs[2:]
    if has_fresh:
        fresh_k_ref, fresh_v_ref, out_ref, k_buf, v_buf, sem = refs
    else:
        out_ref, k_buf, v_buf, sem = refs
    b = pl.program_id(0)
    layer = layer_ref[0]
    seq_len = seq_lens_ref[b]
    # tokens resident in the pages, as far as the table reaches
    hist = jnp.minimum(seq_len - 1 if has_fresh else seq_len,
                       table_pages * page_size)
    if window:
        low = jnp.maximum(seq_len - window, 0)  # the first visible slot
        first_page = low // page_size
    else:
        low = first_page = 0
    n_pages = jnp.maximum(pl.cdiv(hist, page_size) - first_page, 0)
    n_steps = pl.cdiv(n_pages, block_pages)
    block = block_pages * page_size
    n_kv, group, head_dim = q_ref.shape[1:]
    q = q_ref[0].astype(jnp.float32)  # [n_kv, group, d]

    def for_live_pages(step, act):
        """``act`` on the (K, V) copies of the pages of ``step`` that hold
        history, a run of pages as one copy (``_page_copies``)."""
        slot = step % 2
        first = step * block_pages
        for_step_pages(
            act, tables_ref, b, first_page + first,
            jnp.minimum(block_pages, n_pages - first), layer,
            ((k_pool_ref, k_buf.at[slot], sem.at[0, slot]),
             (v_pool_ref, v_buf.at[slot], sem.at[1, slot])),
            rolled=rolled,
        )

    def merge(state, k, v, visible, scales=None):
        """One online-softmax update: ``k`` / ``v`` ``[n_kv, keys, d]``
        float32, ``visible`` ``[n_kv, group, keys]`` or None (all),
        ``scales`` the keys' ``(K, V)`` scales ``[n_kv, 1, keys]`` where
        ``k`` / ``v`` are codes."""
        m_prev, l_prev, acc = state
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale  # [n_kv, group, keys]
        if scales is not None:
            scores = scores * scales[0]
        if visible is not None:
            scores = jnp.where(visible, scores, _MASKED)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)
        if visible is not None:
            # the multiply (not the mask value alone) zeroes a masked slot
            probs = probs * visible
        return (
            m_new,
            l_prev * alpha + jnp.sum(probs, axis=-1, keepdims=True),
            acc * alpha + jax.lax.dot_general(
                probs if scales is None else probs * scales[1], v,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ),
        )

    if quantized:
        # [page, key]: 1 where the key is one of the page's slots
        key = jax.lax.broadcasted_iota(jnp.int32, (n_kv, block_pages, block), 2)
        page = jax.lax.broadcasted_iota(jnp.int32, (n_kv, block_pages, block), 1)
        own = jnp.logical_and(
            key >= page * page_size, key < (page + 1) * page_size
        ).astype(jnp.float32)

    def key_scales(scale_ref, step):
        """A step's scales a key, ``[n_kv, 1, block]``: each page's over its
        ``page_size`` slots."""
        return jax.lax.dot_general(
            scale_ref[0, step], own, (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    def attend(step, state):
        """Step ``step``'s pages, landed in their slot, merged into ``state``."""
        slot = step % 2
        start = (first_page + step * block_pages) * page_size
        # The tiles arrive [keys, n_kv, d]; heads go first for the batched
        # dots. Slots past the live pages hold what an earlier step or call
        # left (or nothing yet): a zero probability times a stray NaN would
        # still be NaN, so those values are zeroed, not only masked.
        # A slot is [pages, page_size, n_kv, d], as a run lies in the pool:
        # merging its two leading dimensions moves nothing.
        keys = (block, n_kv, head_dim)
        tok = start + jax.lax.broadcasted_iota(jnp.int32, keys, 0)
        k = jnp.swapaxes(k_buf[slot].reshape(keys).astype(jnp.float32), 0, 1)
        v = jnp.swapaxes(
            jnp.where(
                tok < hist, v_buf[slot].reshape(keys).astype(jnp.float32), 0.0
            ), 0, 1,
        )
        slot_idx = start + jax.lax.broadcasted_iota(
            jnp.int32, (n_kv, group, block), 2
        )
        if window:
            visible = jnp.logical_and(slot_idx >= low, slot_idx < hist)
        else:
            visible = slot_idx < hist
        scales = None
        if quantized:
            scales = (key_scales(k_scale_ref, step), key_scales(v_scale_ref, step))
        return merge(state, k, v, visible, scales)

    def wait_for(step):
        for_live_pages(step, lambda copy: copy.wait())

    def prefetch(step):
        """Stream the NEXT step's pages under this step's compute."""
        @pl.when(step + 1 < n_steps)
        def _prefetch_next():
            for_live_pages(step + 1, lambda copy: copy.start())

    def empty():
        return (
            jnp.full((n_kv, group, 1), _MASKED, jnp.float32),
            jnp.zeros((n_kv, group, 1), jnp.float32),
            jnp.zeros((n_kv, group, head_dim), jnp.float32),
        )

    if window:
        # the sliding layers' call, as PR 44 wrote and PRs 44-45 swept it:
        # the first step's copies start ahead of the loop
        @pl.when(n_steps > 0)
        def _prologue():
            for_live_pages(0, lambda copy: copy.start())

        def step_body(step, state):
            wait_for(step)
            prefetch(step)
            return attend(step, state)

        state = jax.lax.fori_loop(0, n_steps, step_body, empty())
    else:
        # the full-context call writes a step's copies twice, not three
        # times (a warm start pays for each: the module's docstring): step
        # -1 starts step 0's copies and attends over nothing
        def landed(step, state):
            wait_for(step)
            return attend(step, state)

        def step_body(step, state):
            prefetch(step)
            return jax.lax.cond(
                step >= 0, functools.partial(landed, step),
                lambda state: state, state,
            )

        state = jax.lax.fori_loop(-1, n_steps, step_body, empty())
    if has_fresh:
        # The current token is a one-slot block, always visible to itself;
        # a lane of length 0 holds no token and keeps its zeros.
        kf = fresh_k_ref[0].astype(jnp.float32)  # [n_kv, 1, d]
        vf = fresh_v_ref[0].astype(jnp.float32)
        merged = merge(state, kf, vf, None)
        state = tuple(
            jnp.where(seq_len > 0, new, old) for new, old in zip(merged, state)
        )
    _, denom, acc = state
    safe_l = jnp.where(denom == 0.0, 1.0, denom)  # len-0 lane -> zeros, not NaN
    out_ref[0] = (acc / safe_l).astype(out_ref.dtype)


def walk_step_pages(table_pages: int, page_size: int, window: int = 0) -> int:
    """Pages a step of the kernel, from what the call can see: ``KEY_BLOCK``
    tokens, twice that for a full-context call (no ``window``) over a table
    of ``WIDE_TABLE_TOKENS`` or more, in whole lane tiles of keys, no wider
    than the table."""
    tokens = KEY_BLOCK
    if not window and table_pages * page_size >= WIDE_TABLE_TOKENS:
        tokens *= 2
    lane_pages = 128 // math.gcd(128, page_size)
    return -(-min(tokens // page_size, table_pages) // lane_pages) * lane_pages


def _step_scales(scales, layer, block_tables, block_pages):
    """A lane's scales ``[batch, steps, n_kv, 1, block_pages]`` of an int8
    pool's ``[L, P, n_kv]``: its pages' rows in table order, a step of the
    walk a block, pages on the lanes (``_walk_decode_kernel``). As many words
    as the table has, times ``n_kv``."""
    batch, table_pages = block_tables.shape
    rows = scales[layer][block_tables]  # [batch, table_pages, n_kv]
    steps = -(-table_pages // block_pages)
    rows = jnp.pad(rows, ((0, 0), (0, steps * block_pages - table_pages), (0, 0)))
    rows = rows.reshape(batch, steps, block_pages, -1)
    return jnp.swapaxes(rows, 2, 3)[:, :, :, None, :].astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("page_size", "scale", "interpret", "window")
)
def paged_attention(
    q: jnp.ndarray,  # [batch, n_heads, head_dim]
    k_pages: jnp.ndarray,  # [(n_layers,) total_pages, page_size, n_kv, head_dim]
    v_pages: jnp.ndarray,  # same
    block_tables: jnp.ndarray,  # [batch, max_pages] int32; pad slots with 0
    seq_lens: jnp.ndarray,  # [batch] int32
    fresh_k: Optional[jnp.ndarray] = None,  # [batch, n_kv_heads, head_dim]
    fresh_v: Optional[jnp.ndarray] = None,
    *,
    k_scale: Optional[jnp.ndarray] = None,  # [(n_layers,) total_pages, n_kv] f32
    v_scale: Optional[jnp.ndarray] = None,
    page_size: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
    layer=0,
    window: int = 0,
    table_start: Optional[jnp.ndarray] = None,  # [batch] int32
) -> jnp.ndarray:
    """Batched single-token (decode) paged attention.

    Returns [batch, n_heads, head_dim]. ``block_tables`` entries beyond a
    sequence's page count name no page the kernel copies (the engine pads
    them with 0; an int8 pool's scale rows are gathered for the whole table,
    a word past the pool clamped, and the rows of dead entries masked).

    With ``fresh_k``/``fresh_v``, the current token's K/V come from these
    arguments and the pages are treated as holding only the ``seq_len - 1``
    historical tokens — the caller may then write the pool *after*
    attention in one batched scatter (no per-layer pool rebuild).

    Pools may be passed as the FULL multi-layer array
    ``[n_layers, pages, ps, n_kv, hd]`` with ``layer`` selecting the
    layer inside the kernel. This matters: slicing ``k_pages[li]`` outside
    would make XLA materialize a full per-layer pool copy per call (custom
    calls cannot take slice views); with the 5-D operand the custom call
    reads the carry buffer in place and copies only the table's live pages.
    ``layer`` is an operand (a scalar-prefetch word), not a static argument,
    so a model's full layers share one trace and one lowering of the kernel
    a program, and its sliding layers another.

    With ``k_scale``/``v_scale`` (``KV_QUANT_HBM=int8``), the pools hold
    int8 codes and the lanes' per-page-per-(layer, kv_head) f32 scales ride
    as two extra operands, gathered here in table order — half the page
    bytes, the scales applied in float32 inside the kernel. kvlint pins the
    full operand order against tools/kvlint/kernel_abi.json.

    ``window`` > 0 (a sliding layer; ``k_pages`` / ``v_pages`` are then the
    window pools and ``block_tables`` a row's window table): the token sees
    the last ``window`` positions, itself among them. ``table_start`` is the
    position the table's first slot stands for, a row (None: 0): it comes
    off ``seq_lens`` here, so the kernel's operands are the ones above and
    its walk is within the window table, whatever the context. That call is
    named ``paged_attention_window`` in the trace, the full-context one
    ``paged_attention``.
    """
    batch, n_heads, head_dim = q.shape
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    quantized = k_scale is not None
    if window and quantized:
        raise ValueError("a window pool holds no int8 codes")
    if k_pages.ndim == 4:  # single-layer callers: free bitcast, layer 0
        k_pages = k_pages[None]
        v_pages = v_pages[None]
        if quantized:
            k_scale = k_scale[None]
            v_scale = v_scale[None]
        layer = 0
    _L, _total, ps, n_kv_heads, _hd = k_pages.shape
    page_size = ps if page_size is None else page_size
    if scale is None:
        scale = head_dim**-0.5
    require_tpu_unless_interpret("paged_attention", interpret)
    group = n_heads // n_kv_heads
    table_pages = block_tables.shape[1]
    if (fresh_k is None) != (fresh_v is None):
        raise ValueError("fresh_k and fresh_v must be passed together")
    has_fresh = fresh_k is not None

    block_tables = block_tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    if table_start is not None:
        seq_lens = jnp.maximum(seq_lens - table_start.astype(jnp.int32), 0)
    block_pages = walk_step_pages(table_pages, page_size, window)
    q_blocked = q.reshape(batch, n_kv_heads, group, head_dim)
    layer_word = jnp.asarray(layer, jnp.int32).reshape(1)

    def lane_index(b, *_):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, n_kv_heads, group, head_dim), lane_index),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [layer_word, block_tables, seq_lens, q_blocked, k_pages, v_pages]
    if quantized:
        # Appended after v_pages, before the fresh operands: the order is
        # part of the kernel ABI (tools/kvlint/kernel_abi.json).
        k_scale = _step_scales(k_scale, layer_word[0], block_tables, block_pages)
        v_scale = _step_scales(v_scale, layer_word[0], block_tables, block_pages)
        scale_spec = pl.BlockSpec(
            (1, *k_scale.shape[1:]), lambda b, *_: (b, 0, 0, 0, 0)
        )
        in_specs.append(scale_spec)
        in_specs.append(scale_spec)
        inputs.append(k_scale)
        inputs.append(v_scale)
    if has_fresh:
        in_specs.append(pl.BlockSpec((1, n_kv_heads, 1, head_dim), lane_index))
        in_specs.append(pl.BlockSpec((1, n_kv_heads, 1, head_dim), lane_index))
        inputs.append(fresh_k.reshape(batch, n_kv_heads, 1, head_dim))
        inputs.append(fresh_v.reshape(batch, n_kv_heads, 1, head_dim))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_kv_heads, group, head_dim), lane_index),
        scratch_shapes=[
            pltpu.VMEM(
                (2, block_pages, page_size, n_kv_heads, head_dim), k_pages.dtype
            ),
            pltpu.VMEM(
                (2, block_pages, page_size, n_kv_heads, head_dim), v_pages.dtype
            ),
            pltpu.SemaphoreType.DMA((2, 2)),  # (K, V) x slot
        ],
    )
    kernel = functools.partial(
        _walk_decode_kernel,
        window=window,
        page_size=page_size,
        block_pages=block_pages,
        table_pages=table_pages,
        scale=scale,
        has_fresh=has_fresh,
        quantized=quantized,
        # a warm start pays for every copy the body writes (the module's
        # docstring); the window call keeps the group it was swept with
        rolled=interpret or not window,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, n_kv_heads, group, head_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="paged_attention_window" if window else "paged_attention",
    )(*inputs)
    return out.reshape(batch, n_heads, head_dim)


def paged_attention_reference(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    scale: Optional[float] = None,
    window: int = 0,
    table_start: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Pure-jnp oracle: gather pages per sequence, mask, softmax. ``window``
    / ``table_start`` as ``paged_attention`` takes them (every token of
    ``seq_lens`` is in the pages here)."""
    batch, n_heads, head_dim = q.shape
    _, page_size, n_kv_heads, _ = k_pages.shape
    group = n_heads // n_kv_heads
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = head_dim**-0.5

    # Gather per-sequence K/V: [batch, n_kv, max_pages*page_size, d]
    gathered_k = k_pages[block_tables]  # [batch, max_pages, ps, n_kv, d]
    gathered_v = v_pages[block_tables]
    gathered_k = jnp.moveaxis(
        gathered_k.reshape(batch, max_pages * page_size, n_kv_heads, head_dim), 1, 2
    )
    gathered_v = jnp.moveaxis(
        gathered_v.reshape(batch, max_pages * page_size, n_kv_heads, head_dim), 1, 2
    )

    qf = q.astype(jnp.float32).reshape(batch, n_kv_heads, group, head_dim)
    scores = jnp.einsum("bhgd,bhtd->bhgt", qf, gathered_k.astype(jnp.float32)) * scale
    token_idx = jnp.arange(max_pages * page_size)[None, None, None, :]
    if table_start is not None:
        seq_lens = jnp.maximum(seq_lens - table_start, 0)
    mask = token_idx < seq_lens[:, None, None, None]
    if window:
        mask &= token_idx >= (seq_lens - window)[:, None, None, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # len-0 seqs
    out = jnp.einsum("bhgt,bhtd->bhgd", probs, gathered_v.astype(jnp.float32))
    return out.reshape(batch, n_heads, head_dim).astype(q.dtype)
