"""Paged decode attention: Pallas TPU kernel + reference implementation.

The serving engine stores KV in fixed-size pages (blocks) scattered across a
pool; at decode each sequence reads its pages via a block table. This is the
hot op the reference ecosystem gets from vLLM's CUDA paged attention — here
it is a TPU kernel designed for the hardware:

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

CPU tests run the same kernel with ``interpret=True`` (the caller's choice —
asking for the compiled kernel off-TPU raises);
``paged_attention_reference`` is the numerics oracle.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import require_tpu_unless_interpret

_NEG_INF = float("-inf")

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
    window: int = 0,
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
    fresh K/V arrive unquantized and never round-trip through int8.

    ``window`` > 0 (a sliding layer): the token at ``seq_len - 1`` sees the
    ``window`` positions that end with itself and no earlier one. Slots are
    numbered from the table's first (the caller's ``table_start`` is already
    taken off ``seq_len``), so a slot is visible when it lies at or after
    ``seq_len - window``; a page that holds no such slot is skipped like one
    past the history. 0 is the program it always was, op for op."""
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

    # Only pages holding historical tokens contribute (inside the window,
    # where the layer has one).
    live = p * page_size < hist
    if window:
        live = jnp.logical_and(live, (p + 1) * page_size > seq_len - window)

    @pl.when(live)
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
        visible = token_idx < hist
        if window:
            visible = jnp.logical_and(visible, token_idx >= seq_len - window)
        scores = jnp.where(visible, scores, _NEG_INF)

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

    q_blocked = q.reshape(batch, n_kv_heads, group, head_dim)
    block_tables = block_tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    if table_start is not None:
        seq_lens = jnp.maximum(seq_lens - table_start.astype(jnp.int32), 0)

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
        window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, n_kv_heads, group, head_dim), q.dtype),
        interpret=interpret,
        name="paged_attention_window" if window else None,
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
