"""Paged decode attention: two Pallas TPU kernels + reference implementation.

The serving engine stores KV in fixed-size pages (blocks) scattered across a
pool; at decode each sequence reads its pages via a block table. This is the
hot op the reference ecosystem gets from vLLM's CUDA paged attention — here
it is a TPU kernel designed for the hardware. ``paged_attention`` is the one
entry; which call is which:

**The full-context call** (``_decode_kernel``: every layer that sees its
whole context, int8 pools, ``tp`` shards), a program a (lane, table page):

- KV pool layout ``[total_pages, page_size, n_kv_heads, head_dim]``:
  page-major, so one page's full KV tile ``[page_size, n_kv, head_dim]`` is
  a single contiguous block (lane dim = head_dim = 128-friendly) — one
  contiguous DMA per page, and the engine's per-token write slice
  ``[n_kv, head_dim]`` stays minor-contiguous (default XLA layout, no
  conversion copies).
- Grid ``(batch, max_pages)`` — every KV head of a (sequence, page) pair in
  one program, 8× fewer grid steps than a per-head grid — with the block
  table and sequence lengths as scalar prefetch: the BlockSpec index_map
  dereferences the block table so Pallas's pipeline DMAs exactly the pages
  each sequence owns — gather without a gather op.
- Online softmax (flash-style m/l/acc scratch carried across the page axis)
  in float32; GQA handled by blocking query heads [group, head_dim] against
  one KV head.

**A sliding layer's call** (``window=``; ``paged_window_attention`` /
``_window_decode_kernel``, named ``paged_attention_window`` in the trace;
PR 44), a program a lane that walks the lane's window itself:

- Grid ``(batch,)``; the window pools whole in ``ANY`` memory space, read
  in place; the window table, the lengths and the LAYER as scalar prefetch,
  so a model's sliding layers share one kernel.
- A loop from the first page that holds a visible slot to the page of the
  last historical token, ``KEY_BLOCK / page_size`` pages a step: the kernel
  copies those page tiles of K and of V into one of two VMEM slots with
  ``make_async_copy`` (a group of pages whose pool ids are consecutive as
  ONE copy: ``_page_copies.for_step_pages``, PR 45), starts the next step's
  copies before it computes,
  and makes one float32 online-softmax update over the whole block. Work
  follows the live window, not the table's width; a lane of length 0 runs
  no step.
- On a TPU v5e at `longdocs`' shape (32 lanes, 8 KV heads, a group of 6, a
  259-page table, four sliding layers) the one-page kernel took 15.9 ms a
  decode step for 2.2 GB of keys and values, bound by issuing 33 000
  programs; this one takes 4.3 ms (chip run, PR 44: PERF.md section 6).

The seam between them is in ``paged_attention`` (ROADMAP S12): the
full-context call moves onto the window call's body, and ``_decode_kernel``
goes, once the benchmark's ``decode_step_roofline`` counts what the program
counts (ROADMAP D9 (0)).

CPU tests run the same kernels with ``interpret=True`` (the caller's choice —
asking for the compiled kernel off-TPU raises);
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
# Finite, for the window kernel: a block whose every slot is masked must give
# exp(-1e30 - -1e30) = 1, zeroed by the mask multiply, not inf - inf = NaN.
_MASKED = -1e30

#: tokens of page tiles a step of the window kernel copies and attends over
#: (``KEY_BLOCK / page_size`` pages of K and of V into each of two VMEM slots).
#: On a TPU v5e the four sliding layers' calls of a `longdocs` decode step (32
#: lanes, 8 KV heads, a group of 6, 256-257 live pages of a 259-page table)
#: took 5.02 / 4.32 / 4.29 / 4.59 / 5.12 ms at 128 / 256 / 384 / 512 / 1024
#: tokens a step: a lane's last step holds one page and computes a whole
#: block, and the copies alone are 3.0-3.5 ms (chip runs, PR 44: PERF.md
#: section 6). With a run of pages as one copy (``_page_copies``; chip runs,
#: PR 45, another table and pool than PR 44's): 5.12 ms before, 4.82 / 4.74
#: at 256 / 512 over tables that are one run, 4.96 / 5.16 over tables that
#: hold none: 256 is kept.
KEY_BLOCK = 256
#: what the window kernel may use of a v5e core's 128 MiB of VMEM (the
#: compiler's default scoped limit is 16 MiB)
_VMEM_LIMIT = 64 * 1024 * 1024

#: pages per scale block: the int8 pool's scale operand ``[L, pages, n_kv]``
#: is tiled (8, 128) over its last two dims, and Mosaic wants a block's
#: second-minor dim divisible by 8 — so a program fetches the 8-page group
#: holding its page's scale row and picks the row in-kernel.
_SCALE_ROWS = 8


def _page_scale_column(scale_ref, page):
    """This page's per-kv-head scales as a ``[n_kv, 1]`` column.

    ``scale_ref`` is the ``[1, 8, n_kv]`` block of 8 consecutive pages'
    scale rows. The row arrives with heads on lanes; the page tile
    ``[page_size, n_kv, d]`` has heads on sublanes, so the row is turned
    into a column by a masked reduce over an identity mask — plain 2-D
    select/reduce ops, no lane→sublane relayout for Mosaic to refuse."""
    blk = scale_ref[0]  # [8, n_kv] f32
    n_kv = blk.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
    row = jnp.sum(
        jnp.where(rows == page % _SCALE_ROWS, blk, 0.0), axis=0, keepdims=True
    )  # [1, n_kv]
    eye = jax.lax.broadcasted_iota(
        jnp.int32, (n_kv, n_kv), 0
    ) == jax.lax.broadcasted_iota(jnp.int32, (n_kv, n_kv), 1)
    return jnp.sum(
        jnp.where(eye, jnp.broadcast_to(row, (n_kv, n_kv)), 0.0),
        axis=1, keepdims=True,
    )  # [n_kv, 1]


def _decode_kernel(
    # scalar prefetch
    block_tables_ref,  # [batch, max_pages] int32
    seq_lens_ref,  # [batch] int32
    # blocks (scale refs only when quantized; fresh refs only when has_fresh)
    q_ref,  # [1, n_kv, group, head_dim]
    k_ref,  # [1, 1, page_size, n_kv, head_dim] (leading layer dim)
    v_ref,  # [1, 1, page_size, n_kv, head_dim]
    *refs,  # [k_scale_ref, v_scale_ref,] [fresh_k_ref, fresh_v_ref,]
    #        out_ref, m_ref, l_ref, acc_ref
    page_size: int,
    scale: float,
    has_fresh: bool,
    quantized: bool,
):
    """All KV heads of one (sequence, page) in a single program: 8× fewer
    grid steps than a per-head grid, one fully-contiguous page tile
    ``[page_size, n_kv, d]`` per K/V DMA.

    ``has_fresh``: the current token's K/V arrive as function inputs
    ([1, n_kv, 1, d] blocks) instead of from the pages, and pages hold only
    the ``seq_len - 1`` historical tokens. This lets the caller defer the
    pool write until after attention — one batched scatter per step, never
    a pool rebuild.

    ``quantized`` (``KV_QUANT_HBM=int8``): the page pools hold int8 codes
    and the pipeline DMAs HALF the HBM→VMEM bytes per page — the decode
    hot loop is DMA-bound, so this is a bandwidth win on top of the 2×
    capacity win. Per-page-per-(layer, kv_head) f32 scales ride as two
    extra pipelined operands (same block-table deref: each program sees
    the 8-page group holding its page's scale row, ``_SCALE_ROWS``) and
    the codes dequantize IN-REGISTER
    to f32 before the online softmax — full-width pages never exist
    anywhere. The ``has_fresh`` current-token path stays full-precision:
    fresh K/V arrive unquantized and never round-trip through int8."""
    if quantized:
        k_scale_ref, v_scale_ref = refs[0], refs[1]  # [1, 8, n_kv] f32
        refs = refs[2:]
    if has_fresh:
        fresh_k_ref, fresh_v_ref, out_ref, m_ref, l_ref, acc_ref = refs
    else:
        out_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    seq_len = seq_lens_ref[b]
    hist = seq_len - 1 if has_fresh else seq_len  # tokens resident in pages

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Only pages holding historical tokens contribute.
    @pl.when(p * page_size < hist)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [n_kv, group, d]
        # Page tile arrives [page_size, n_kv, d] (one fully-contiguous
        # block); swap to head-major for the batched dot.
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            # int8 codes → f32, per-(layer, kv_head) page scale broadcast
            # over slots and lanes. Registers only; VMEM holds the codes.
            page = block_tables_ref[b, p]
            k = k * _page_scale_column(k_scale_ref, page)[None]
            v = v * _page_scale_column(v_scale_ref, page)[None]
        k = jnp.swapaxes(k, 0, 1)  # [n_kv, ps, d]
        v = jnp.swapaxes(v, 0, 1)

        # Batched over kv heads: [n_kv, group, page_size]
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale

        # Mask slots at/after the historical length within this page.
        token_idx = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, dimension=2
        )
        scores = jnp.where(token_idx < hist, scores, _NEG_INF)

        m_prev = m_ref[:, :, :1]  # [n_kv, group, 1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)  # [n_kv, group, page_size]

        l_ref[:] = l_ref[:] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            probs, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(p == n_pages - 1)
    def _finalize():
        if has_fresh:
            # Merge the current token's K/V (always visible to itself).
            @pl.when(seq_len > 0)
            def _merge_fresh():
                # Same dot_general shapes as _compute with page_size == 1 —
                # the current token is a one-slot virtual page.
                q = q_ref[0].astype(jnp.float32)  # [n_kv, group, d]
                kf = fresh_k_ref[0].astype(jnp.float32)  # [n_kv, 1, d]
                vf = fresh_v_ref[0].astype(jnp.float32)
                s_f = jax.lax.dot_general(
                    q, kf, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                ) * scale  # [n_kv, group, 1]
                m_prev = m_ref[:, :, :1]
                m_new = jnp.maximum(m_prev, s_f)
                alpha = jnp.exp(m_prev - m_new)
                p_f = jnp.exp(s_f - m_new)  # [n_kv, group, 1]
                l_ref[:] = l_ref[:] * alpha + p_f
                acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                    p_f, vf, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )
                m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

        denom = l_ref[:, :, :1]
        safe_l = jnp.where(denom == 0.0, 1.0, denom)  # len-0 seq → zeros, not NaN
        out_ref[0] = (acc_ref[:] / safe_l).astype(out_ref.dtype)


def _window_decode_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32: a scalar, so every sliding layer's call is one kernel
    tables_ref,  # [batch, table_pages] int32: the lanes' window tables
    seq_lens_ref,  # [batch] int32, counted from the table's first slot
    # operands
    q_ref,  # [1, n_kv, group, head_dim]
    k_pool_ref,  # [L, P, page_size, n_kv, head_dim]: the whole pool (ANY)
    v_pool_ref,
    *refs,  # [fresh_k_ref, fresh_v_ref,] out_ref, k_buf, v_buf, sem
    window: int,
    page_size: int,
    block_pages: int,
    table_pages: int,
    scale: float,
    has_fresh: bool,
):
    """One lane's sliding-window decode attention, every KV head at once.

    The token at ``seq_len - 1`` sees the ``window`` slots that end with
    itself. The program walks its lane's window table from the first page
    that holds a visible slot to the page of the last historical token,
    ``block_pages`` pages a step: it copies those page tiles ``pool[layer,
    table[b, page]]`` of K and of V into one of two VMEM slots itself, starts
    the next step's copies before it computes, and makes one online-softmax
    update over the whole block. A lane of length 0 runs no step; the current
    token's K/V merge after the loop as in ``_decode_kernel``."""
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
    low = jnp.maximum(seq_len - window, 0)  # the first visible slot
    first_page = low // page_size
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
        )

    def merge(state, k, v, visible):
        """One online-softmax update: ``k`` / ``v`` ``[n_kv, keys, d]``
        float32, ``visible`` ``[n_kv, group, keys]`` or None (all)."""
        m_prev, l_prev, acc = state
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale  # [n_kv, group, keys]
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
                probs, v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ),
        )

    @pl.when(n_steps > 0)
    def _prologue():
        for_live_pages(0, lambda copy: copy.start())

    def step_body(step, state):
        for_live_pages(step, lambda copy: copy.wait())

        # Stream the NEXT step's pages under this step's compute.
        @pl.when(step + 1 < n_steps)
        def _prefetch_next():
            for_live_pages(step + 1, lambda copy: copy.start())

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
        visible = jnp.logical_and(slot_idx >= low, slot_idx < hist)
        return merge(state, k, v, visible)

    state = jax.lax.fori_loop(0, n_steps, step_body, (
        jnp.full((n_kv, group, 1), _MASKED, jnp.float32),
        jnp.zeros((n_kv, group, 1), jnp.float32),
        jnp.zeros((n_kv, group, head_dim), jnp.float32),
    ))
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


def window_step_pages(table_pages: int, page_size: int) -> int:
    """Pages a step of the window kernel: ``KEY_BLOCK`` tokens in whole lane
    tiles of keys, no wider than the table."""
    lane_pages = 128 // math.gcd(128, page_size)
    return -(-min(KEY_BLOCK // page_size, table_pages) // lane_pages) * lane_pages


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def paged_window_attention(
    q: jnp.ndarray,  # [batch, n_heads, head_dim]
    k_pages: jnp.ndarray,  # [n_layers, window_pages, page_size, n_kv, head_dim]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [batch, table_pages] int32: window tables
    seq_lens: jnp.ndarray,  # [batch] int32, counted from the table's first slot
    fresh_k: Optional[jnp.ndarray] = None,  # [batch, n_kv_heads, head_dim]
    fresh_v: Optional[jnp.ndarray] = None,
    *,
    window: int,
    scale: float,
    interpret: bool = False,
    layer=0,
) -> jnp.ndarray:
    """A sliding layer's decode attention (``paged_attention`` sends its
    ``window`` calls here): a program a lane that walks the lane's window
    itself, ``KEY_BLOCK`` tokens of page tiles a step. ``layer`` is an
    operand, not a static argument, so a model's sliding layers share one
    trace and one lowering of the kernel a program. The call is named
    ``paged_attention_window`` in the trace."""
    require_tpu_unless_interpret("paged_window_attention", interpret)
    batch, n_heads, head_dim = q.shape
    _L, _total, page_size, n_kv_heads, _hd = k_pages.shape
    group = n_heads // n_kv_heads
    table_pages = block_tables.shape[1]
    has_fresh = fresh_k is not None
    block_pages = window_step_pages(table_pages, page_size)
    block = block_pages * page_size

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
        _window_decode_kernel,
        window=window,
        page_size=page_size,
        block_pages=block_pages,
        table_pages=table_pages,
        scale=scale,
        has_fresh=has_fresh,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, n_kv_heads, group, head_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="paged_attention_window",
    )(*inputs)
    return out.reshape(batch, n_heads, head_dim)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "interpret", "layer", "window"),
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
    layer: int = 0,
    window: int = 0,
    table_start: Optional[jnp.ndarray] = None,  # [batch] int32
) -> jnp.ndarray:
    """Batched single-token (decode) paged attention.

    Returns [batch, n_heads, head_dim]. ``block_tables`` entries beyond a
    sequence's page count must be valid page indices (e.g. 0); they are
    masked out, never read into the result.

    With ``fresh_k``/``fresh_v``, the current token's K/V come from these
    arguments and the pages are treated as holding only the ``seq_len - 1``
    historical tokens — the caller may then write the pool *after*
    attention in one batched scatter (no per-layer pool rebuild).

    Pools may be passed as the FULL multi-layer array
    ``[n_layers, pages, ps, n_kv, hd]`` with ``layer`` selecting the
    layer inside the kernel's index map. This matters: slicing
    ``k_pages[li]`` outside would make XLA materialize a full per-layer
    pool copy per call (custom calls cannot take slice views); with the
    5-D operand the custom call reads the carry buffer in place and DMAs
    only the block-table pages.

    With ``k_scale``/``v_scale`` (``KV_QUANT_HBM=int8``), the pools hold
    int8 codes and the per-page-per-(layer, kv_head) f32 scales ride as
    two extra pipelined operands — half the page DMA bytes, dequantized
    in-register inside the kernel. The scalar-prefetch operand set
    (block_tables, seq_lens) is IDENTICAL in both variants; kvlint pins
    the full operand order against tools/kvlint/kernel_abi.json.

    ``window`` > 0 (a sliding layer; ``k_pages`` / ``v_pages`` are then the
    window pools and ``block_tables`` a row's window table): the token sees
    the last ``window`` positions, itself among them. ``table_start`` is the
    position the table's first slot stands for, a row (None: 0): it comes
    off ``seq_lens`` here, so the kernel's operands are the ones above and
    its grid is as wide as the window table, whatever the context. The call
    is named ``paged_attention_window`` in the trace.
    """
    batch, n_heads, head_dim = q.shape
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    quantized = k_scale is not None
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
    max_pages = block_tables.shape[1]
    if (fresh_k is None) != (fresh_v is None):
        raise ValueError("fresh_k and fresh_v must be passed together")
    has_fresh = fresh_k is not None

    block_tables = block_tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    if table_start is not None:
        seq_lens = jnp.maximum(seq_lens - table_start.astype(jnp.int32), 0)
    if window:
        # The seam (ROADMAP S12): a sliding layer's call walks its window
        # in ``paged_window_attention``; the full-context call below stays a
        # program a page until D9 (0) lets it move onto that body, and
        # ``_decode_kernel`` goes then.
        if quantized:
            raise ValueError("a window pool holds no int8 codes")
        return paged_window_attention(
            q, k_pages, v_pages, block_tables, seq_lens, fresh_k, fresh_v,
            window=window, scale=scale, interpret=interpret, layer=layer,
        )

    q_blocked = q.reshape(batch, n_kv_heads, group, head_dim)
    grid = (batch, max_pages)

    def q_index(b, p, bt, sl):
        return (b, 0, 0, 0)

    def kv_index(b, p, bt, sl):
        return (layer, bt[b, p], 0, 0, 0)

    def out_index(b, p, bt, sl):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, n_kv_heads, group, head_dim), q_index),
        pl.BlockSpec((1, 1, page_size, n_kv_heads, head_dim), kv_index),
        pl.BlockSpec((1, 1, page_size, n_kv_heads, head_dim), kv_index),
    ]
    inputs = [block_tables, seq_lens, q_blocked, k_pages, v_pages]
    if quantized:
        # Same block-table deref as the page tiles, so each program's
        # pipeline stage carries its page's 8-page group of [n_kv] scale
        # rows alongside the codes (_SCALE_ROWS). Appended after v_pages,
        # before fresh operands — order is part of the kernel ABI
        # (tools/kvlint/kernel_abi.json).
        def scale_index(b, p, bt, sl):
            return (layer, bt[b, p] // _SCALE_ROWS, 0)

        scale_block = (1, _SCALE_ROWS, n_kv_heads)
        in_specs.append(pl.BlockSpec(scale_block, scale_index))
        in_specs.append(pl.BlockSpec(scale_block, scale_index))
        inputs.append(k_scale)
        inputs.append(v_scale)
    if has_fresh:
        in_specs.append(pl.BlockSpec((1, n_kv_heads, 1, head_dim), q_index))
        in_specs.append(pl.BlockSpec((1, n_kv_heads, 1, head_dim), q_index))
        inputs.append(fresh_k.reshape(batch, n_kv_heads, 1, head_dim))
        inputs.append(fresh_v.reshape(batch, n_kv_heads, 1, head_dim))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_kv_heads, group, head_dim), out_index),
        scratch_shapes=[
            pltpu.VMEM((n_kv_heads, group, 128), jnp.float32),
            pltpu.VMEM((n_kv_heads, group, 128), jnp.float32),
            pltpu.VMEM((n_kv_heads, group, head_dim), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _decode_kernel,
        page_size=page_size,
        scale=scale,
        has_fresh=has_fresh,
        quantized=quantized,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, n_kv_heads, group, head_dim), q.dtype),
        interpret=interpret,
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
