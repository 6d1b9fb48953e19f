"""Pallas flash-prefill kernel: paged context read where it lies + fresh chunk.

The prefill and block-attention hot op (the decode kernel is
`paged_attention.py`). One online softmax over [cached context ++ fresh
chunk], entirely in VMEM; the [s, T] score matrix never exists.

- **The context is read from the pool in place.** The kernel takes the
  whole ``[L, P, page_size, n_kv, head_dim]`` pools in ``ANY`` memory space
  (the donated carry buffers: no slice, no gather, no head-major copy
  outside) and copies WHOLE PAGE TILES ``[page_size, n_kv, head_dim]``,
  addressed by ``(layer, block_tables[b, page])`` from the scalar-prefetched
  table, into double-buffered VMEM with ``make_async_copy``: several pages a
  step, the next step's pages in flight under this step's matmuls. A page
  tile is one contiguous block of the pool and cuts no tiled axis (a
  per-head cut ``pool[page, :, h, :]`` goes through the sublane-tiled
  ``n_kv`` axis and Mosaic refuses it), so every KV head of a (row, query
  block) is served by one program and the heads are separated after the
  tile is in VMEM (``swapaxes``, batched ``dot_general`` over heads) as
  ``paged_attention._walk_decode_kernel`` does.
- Grid ``(batch, q_blocks, chunk_key_blocks)``. The context phase is a loop
  INSIDE the first chunk step whose trip count is the row's live context
  (``ceil(ctx_len / keys a step)``; 0 for a row or a query block with no
  valid query): steps past ``ctx_len`` do not exist, pages past it are
  never fetched (table entries there are the caller's padding), so work
  follows the live context and not the table's width. The chunk phase
  walks the fresh keys' blocks on the grid; its BlockSpec index map clamps
  steps past the causal frontier to the previous block and Pallas skips
  the re-fetch.
- Score tiles are ``[n_kv, bq*group, keys]`` — query rows × GQA group
  collapsed to one MXU-friendly row dimension, every KV head batched.
  Operands stay in their dtype (bf16 in serving) with float32
  accumulation; m/l/acc scratch is float32.
- The tiles adapt to what the call can see: query rows a block from the
  chunk length and ``n_kv * group`` under ``MAX_SCORE_ROWS``; keys a step
  (pages a step x ``page_size``) from the table's width under
  ``KEY_BLOCK``. A block-diffusion forward (4
  query rows x 16 lanes, 4 KV heads) and a 512-row prefill chunk (8 KV
  heads) run this one body.

What this replaced, and what it costs (TPU v5e). Until PR 31 the caller
sliced the pool by layer, gathered every table page and copied the gather
head-major before the call: five XLA ops, 1.33 s of a 4.26 s traced window
of `sdar-30b-a3b.blockgen` against 0.12 s for the kernel they fed, and
`slice_bitcast_fusion_bf16_8192_16_8_128_` 0.205 s in `qwen3-32b.sessions`
(PERF_LEDGER.jsonl, PR 30). None of them is in a compiled program now
(``python -m tools.aot_pool_copies``). The call alone, all layers of a
forward, on the chip (PERF.md section 6, PR 31): 16 lanes x 4 rows over
128-1536 tokens of context 9.17 -> 1.12 ms, over 128 tokens each 9.10 ->
0.32 ms; one 100-token row over 2048 tokens 13.17 -> 1.49 ms. About 12 000
page copies of 16 KiB a `blockgen` forward bound the kernel, not their bytes.

Contract (what the serving engine guarantees):
- chunk queries occupy CONSECUTIVE positions (`positions[b, i] = start + i`)
  so in-chunk causality is index order;
- ``valid`` is a right-padding mask (True prefix), reduced to a per-seq
  count; fully-padded query rows produce zeros;
- ``ctx_lens[b] <= block_tables.shape[1] * page_size``; table entries at or
  past ``ceil(ctx_len / page_size)`` are never read;
- with a static ``block_length`` B > 1 (generation by diffusion over
  blocks, ``models/llama.py``) the chunk is causal between blocks of B
  positions and full inside one, and the chunk STARTS on a block boundary
  (``positions[b, 0] % B == 0``), so a block is B consecutive chunk
  indices: key ``k`` is visible to query ``q`` when
  ``k < (q // B + 1) * B``. That kernel is named ``block_attention`` in
  the trace. B of 0 or 1 is the causal program, op for op.

`prefill_with_paged_context` (attention.py) is the numerics oracle; parity
is tested across GQA/MHA/MQA in interpret mode, the call is compiled for a
described v5e in ``tests/test_pool_layout.py`` and, compiled, checked on
the chip by ``chip_smoke.py``'s kernel phase (round-1 lesson: Mosaic can
miscompile — always check numerics on the chip).
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

# Finite: a fully-masked score row must yield exp(-1e30 - -1e30) = 1,
# zeroed by the mask multiply — float('-inf') would produce inf-inf = NaN.
_NEG_INF = -1e30

#: most keys a step (context or chunk) and most query rows a block: on a
#: TPU v5e 256 keys a step read `blockgen`'s contexts fastest (128 at 128
#: tokens of context, 256 over 128-1536; 512 and 1024 slower) and a prefill
#: chunk's as fast as 512 (chip runs, PR 31: PERF.md section 6).
KEY_BLOCK = 256
QUERY_BLOCK = 256
#: cap on n_kv * bq * group score rows a program — with ``KEY_BLOCK`` it
#: bounds the [n_kv, rows, keys] f32 score tile (2 MiB) and the f32 m/l/acc
#: scratch (3 MiB)
MAX_SCORE_ROWS = 2048
#: what the kernel may use of a v5e core's 128 MiB of VMEM (the compiler's
#: default scoped limit, 16 MiB, holds the cells' tiles, not 16 heads' of MHA)
_VMEM_LIMIT = 64 * 1024 * 1024


def _visible_through(q_idx, block_length: int):
    """The last chunk index a query at chunk index ``q_idx`` sees: itself
    (causal), or the end of its block of ``block_length`` indices."""
    if block_length > 1:
        return (q_idx // block_length + 1) * block_length - 1
    return q_idx


def _flash_prefill_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32 — a scalar, so every layer's call is one kernel
    ctx_lens_ref,  # [batch] int32
    n_valid_ref,  # [batch] int32
    bt_ref,  # [batch, max(table pages, 1)] int32 block tables
    # operands
    q_ref,  # [1, n_kv, bq*g, d] — head-major, row r is query r // g
    k_pool_ref,  # [L, P, ps, n_kv, d] — the whole pool, where it lies (ANY)
    v_pool_ref,
    ck_ref,  # [1, n_kv, bk_chunk, d]
    cv_ref,
    out_ref,  # [1, n_kv, bq*g, d]
    m_ref,  # [n_kv, bq*g, 128] f32 scratch
    l_ref,  # [n_kv, bq*g, 128] f32 scratch
    acc_ref,  # [n_kv, bq*g, d] f32 scratch
    ctx_k_buf,  # [2, bk_ctx, n_kv, d] VMEM — double-buffered page tiles
    ctx_v_buf,
    sem,  # DMA semaphores [2 (k, v), 2 (slot)]
    *,
    bq: int,
    bk_ctx: int,
    bk_chunk: int,
    group: int,
    page_size: int,
    table_pages: int,
    scale: float,
    block_length: int,
    window: int,
):
    b = pl.program_id(0)
    qb = pl.program_id(1)
    cks = pl.program_id(2)
    n_valid = n_valid_ref[b]
    rows = bq * group
    pages_per_step = bk_ctx // page_size

    # q-row index (within the chunk) per score row: row r ↔ query r // g.
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
    # A query block past the row's valid count (a padded row has none)
    # reads nothing and computes nothing; its output is zeros.
    q_live = qb * bq < n_valid

    def attend(k, v, mask):
        """One online-softmax step over a block of keys, every KV head at
        once: k, v ``[n_kv, bk, d]``, ``mask`` ``[rows, bk]`` shared by the
        heads."""
        # Native dtype (bf16 in serving): the q@k dot runs bf16×bf16 on the
        # MXU with f32 accumulation via preferred_element_type.
        scores = jax.lax.dot_general(
            q_ref[0], k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [n_kv, rows, bk] f32
        mask = mask[None]
        scores = jnp.where(mask, scores, _NEG_INF)
        m_prev = m_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # The mask multiply (not the -inf alone) zeroes masked lanes: on a
        # fully-masked row m_new == _NEG_INF and exp(0) == 1.
        probs = jnp.exp(scores - m_new) * mask
        l_ref[:] = l_ref[:] * alpha + jnp.broadcast_to(
            jnp.sum(probs, axis=-1, keepdims=True), l_ref.shape
        )
        # probs cast to the KV dtype: keeps the p@v dot on the fast MXU
        # path (bf16×bf16, f32 accumulation) — standard flash practice.
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            probs.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    def context_phase():
        """Keys are cached-context tokens, all of which precede every chunk
        query: visibility is just k_idx < ctx_len."""
        layer = layer_ref[0]
        ctx_len = jnp.minimum(ctx_lens_ref[b], table_pages * page_size)
        n_pages = pl.cdiv(ctx_len, page_size)
        n_steps = jnp.where(q_live, pl.cdiv(ctx_len, bk_ctx), 0)

        def for_live_pages(step, act):
            """``act`` on the (K, V) copy of every page of ``step`` that
            holds context. The handles are rebuilt identically at start
            and at wait time (the standard Pallas async-copy idiom)."""
            slot = step % 2
            first = step * pages_per_step

            def one_page(i, carry):
                page = bt_ref[b, first + i]
                dst = pl.ds(i * page_size, page_size)
                act(pltpu.make_async_copy(
                    k_pool_ref.at[layer, page], ctx_k_buf.at[slot, dst],
                    sem.at[0, slot],
                ))
                act(pltpu.make_async_copy(
                    v_pool_ref.at[layer, page], ctx_v_buf.at[slot, dst],
                    sem.at[1, slot],
                ))
                return carry

            jax.lax.fori_loop(
                0, jnp.minimum(pages_per_step, n_pages - first), one_page, 0
            )

        @pl.when(n_steps > 0)
        def _prologue():
            for_live_pages(0, lambda copy: copy.start())

        def ctx_step(step, carry):
            for_live_pages(step, lambda copy: copy.wait())

            # Stream the NEXT step's pages under this step's compute.
            @pl.when(step + 1 < n_steps)
            def _prefetch_next():
                for_live_pages(step + 1, lambda copy: copy.start())

            slot = step % 2
            # The tiles arrive [keys, n_kv, d]; heads go first for the
            # batched dots (through f32, as the decode kernel swaps them).
            tok = step * bk_ctx + jax.lax.broadcasted_iota(
                jnp.int32, ctx_v_buf.shape[1:], 0
            )
            k = ctx_k_buf[slot].astype(jnp.float32)
            # Slots past the live pages hold what an earlier step or call
            # left (or nothing yet): a zero probability times a stray NaN
            # would still be NaN, so those values are zeroed, not only
            # masked in the scores.
            v = jnp.where(tok < ctx_len, ctx_v_buf[slot].astype(jnp.float32), 0.0)
            k = jnp.swapaxes(k, 0, 1).astype(ctx_k_buf.dtype)  # [n_kv, bk, d]
            v = jnp.swapaxes(v, 0, 1).astype(ctx_v_buf.dtype)
            k_idx = step * bk_ctx + jax.lax.broadcasted_iota(
                jnp.int32, (rows, bk_ctx), 1
            )
            mask = (k_idx < ctx_len) & (qb * bq + q_idx < n_valid)
            if window:
                # the query at chunk index i stands ``ctx_len + i`` slots
                # after the table's first and sees ``window`` slots back
                mask &= k_idx + window > ctx_len + qb * bq + q_idx
            attend(k, v, mask)
            return carry

        jax.lax.fori_loop(0, n_steps, ctx_step, 0)

    @pl.when(cks == 0)
    def _init_and_context():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        if table_pages:
            context_phase()

    # ---- chunk phase: causal within the chunk (consecutive positions →
    # index order; through the end of the query's block when block_length
    # > 1), bounded by the per-sequence valid count.
    q_end = _visible_through(qb * bq + bq - 1, block_length)

    @pl.when(
        jnp.logical_and(
            q_live,
            jnp.logical_and(cks * bk_chunk <= q_end, cks * bk_chunk < n_valid),
        )
    )
    def _chunk_step():
        k_idx = cks * bk_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (rows, bk_chunk), 1
        )
        # [rows, 1], broadcasts over lanes
        q_pos = _visible_through(qb * bq + q_idx, block_length)
        mask = (k_idx <= q_pos) & (k_idx < n_valid) & (q_idx < n_valid - qb * bq)
        if window:
            mask &= k_idx + window > q_pos
        attend(ck_ref[0], cv_ref[0], mask)

    @pl.when(cks == pl.num_programs(2) - 1)
    def _finalize():
        denom = l_ref[:, :, :1]
        safe_l = jnp.where(denom == 0.0, 1.0, denom)  # fully-masked rows → zeros
        out_ref[0] = (acc_ref[:] / safe_l).astype(out_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel_name(block_length: int):
    """The kernel's name in the trace: a query block with a non-causal tail
    is ``block_attention``; the causal kernel keeps the name it has (that
    of its function)."""
    return "block_attention" if block_length > 1 else None


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "interpret", "q_block", "key_block", "block_length",
        "window",
    ),
)
def flash_prefill_paged(
    q: jnp.ndarray,  # [batch, seq, n_heads, head_dim] — fresh chunk
    k: jnp.ndarray,  # [batch, seq, n_kv_heads, head_dim]
    v: jnp.ndarray,  # [batch, seq, n_kv_heads, head_dim]
    k_pages: jnp.ndarray,  # [(n_layers,) total_pages, page_size, n_kv, head_dim]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [batch, max_ctx_pages] int32 (pad with 0)
    ctx_lens: jnp.ndarray,  # [batch] int32
    n_valid: jnp.ndarray,  # [batch] int32 — valid chunk tokens (right-pad)
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
    q_block: int = QUERY_BLOCK,
    key_block: int = KEY_BLOCK,
    block_length: int = 0,
    layer=0,
    window: int = 0,
    table_start: Optional[jnp.ndarray] = None,  # [batch] int32
) -> jnp.ndarray:
    """Pallas flash prefill over [paged context ++ fresh chunk].

    Drop-in for `prefill_with_paged_context` under the engine's contract
    (consecutive chunk positions, right-padding); `n_valid` replaces the
    boolean `valid` mask. Returns [batch, seq, n_heads, head_dim].
    ``block_length`` > 1: block-causal chunk (module docstring; the chunk
    starts on a block boundary).

    The pools are the FULL multi-layer arrays ``[n_layers, pages, ps, n_kv,
    hd]`` with ``layer`` picking the layer inside the kernel's own page
    addressing, as in ``paged_attention``: the custom call reads the carry
    buffers in place and moves only the pages a row's ``ctx_len`` covers.
    ``layer`` is an operand, not a static argument: a model's layers then
    share one trace and one lowering of the kernel a program (a static
    layer lowered it once a layer, which is in ``setup_s`` at every start,
    warm cache or not: 30 s in `blockgen`, chip run, PR 31).
    The compiled program holds no instruction shaped like a layer's slice
    or a gathered context (``tests/test_pool_layout.py``,
    ``python -m tools.aot_pool_copies``). A four-dimensional pool is one
    layer (a free bitcast, layer 0). Under ``tp`` each shard passes its
    head slice of the pool and its page tile is ``[ps, n_kv / tp, hd]``.

    ``window`` > 0 (a sliding layer; the pools are then the window pools and
    ``block_tables`` a row's window table): a query sees the ``window``
    positions that end with itself, in the context and in the chunk.
    ``table_start`` is the position the table's first slot stands for, a row
    (None: 0); it comes off ``ctx_lens`` here, so the kernel's operands are
    the ones it always had and the copies follow the live window.
    """
    b, s, n_q, d = q.shape
    if window and block_length > 1:
        raise ValueError("no block mask over a window")
    if table_start is not None:
        ctx_lens = ctx_lens - table_start
    n_kv = k.shape[2]
    group = n_q // n_kv
    if scale is None:
        scale = d**-0.5
    require_tpu_unless_interpret("flash_prefill_paged", interpret)
    if k_pages.ndim == 4:  # single-layer callers
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    if n_kv == 1 and k_pages.dtype.itemsize < 4 and not interpret:
        # A 16-bit pool with one KV head (a shard's, under tp = n_kv_heads)
        # is tiled two rows deep over that axis of one, and Mosaic cuts no
        # page tile out of it ("slice shape must be aligned to tiling (2)").
        # The engine's rule sends such a pool to the XLA prefill.
        raise NotImplementedError(
            "flash_prefill_paged: one KV head a shard in a 16-bit pool has "
            "no page tile Mosaic can copy; use the XLA prefill "
            "(EngineConfig.prefill_attn='auto' or 'xla')"
        )

    page_size = k_pages.shape[2]
    table_pages = block_tables.shape[1]
    # Query rows a block: from the chunk length, under the cap on score
    # rows over every head; bq * group a whole number of bf16 sublane tiles.
    align = 16 // math.gcd(16, group)
    bq = min(q_block, MAX_SCORE_ROWS // (n_kv * group)) // align * align
    bq = min(max(bq, align), _round_up(s, align))
    # Keys a step: whole lane tiles; the context's step is also whole
    # pages, and no wider than the table.
    step_keys = _round_up(key_block, 128)
    bk_chunk = min(step_keys, _round_up(s, 128))
    bk_ctx = _round_up(
        min(step_keys, max(table_pages * page_size, 1)),
        math.lcm(page_size, 128),
    )
    s_padq = _round_up(s, bq)
    s_padk = _round_up(s, bk_chunk)
    n_qblocks = s_padq // bq
    n_chunk_blocks = s_padk // bk_chunk
    rows = bq * group

    # Head-major, the group folded into the rows: [b, n_kv, s_pad * g, d]
    # (queries) / [b, n_kv, s_pad, d] (fresh keys and values).
    qp = jnp.moveaxis(
        jnp.pad(q, ((0, 0), (0, s_padq - s), (0, 0), (0, 0))).reshape(
            b, s_padq, n_kv, group, d
        ),
        1,
        2,
    ).reshape(b, n_kv, s_padq * group, d)
    kp = jnp.moveaxis(jnp.pad(k, ((0, 0), (0, s_padk - s), (0, 0), (0, 0))), 1, 2)
    vp = jnp.moveaxis(jnp.pad(v, ((0, 0), (0, s_padk - s), (0, 0), (0, 0))), 1, 2)
    if not table_pages:  # the no-context program: a table nothing reads
        block_tables = jnp.zeros((b, 1), jnp.int32)

    def q_index(b_, qb, cks, *_):
        return (b_, 0, qb, 0)

    def chunk_index(b_, qb, cks, layer_, cl, nv, bt):
        # causal frontier: blocks past this q-block's last row are clamped
        # to the previous block → Pallas skips the re-fetch
        causal_last = _visible_through(qb * bq + bq - 1, block_length) // bk_chunk
        needed = jnp.maximum(-(-nv[b_] // bk_chunk), 1)
        return (b_, 0, jnp.minimum(jnp.minimum(cks, causal_last), needed - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n_qblocks, n_chunk_blocks),
        in_specs=[
            pl.BlockSpec((1, n_kv, rows, d), q_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, n_kv, bk_chunk, d), chunk_index),
            pl.BlockSpec((1, n_kv, bk_chunk, d), chunk_index),
        ],
        out_specs=pl.BlockSpec((1, n_kv, rows, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((n_kv, rows, 128), jnp.float32),
            pltpu.VMEM((n_kv, rows, 128), jnp.float32),
            pltpu.VMEM((n_kv, rows, d), jnp.float32),
            pltpu.VMEM((2, bk_ctx, n_kv, d), k_pages.dtype),
            pltpu.VMEM((2, bk_ctx, n_kv, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _flash_prefill_kernel,
        bq=bq,
        bk_ctx=bk_ctx,
        bk_chunk=bk_chunk,
        group=group,
        page_size=page_size,
        table_pages=table_pages,
        scale=scale,
        block_length=block_length,
        window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, s_padq * group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=_kernel_name(block_length),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        ctx_lens.astype(jnp.int32),
        n_valid.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        qp,
        k_pages,
        v_pages,
        kp,
        vp,
    )
    # [b, n_kv, s_pad * g, d] -> [b, s, n_q, d]
    out = out.reshape(b, n_kv, s_padq, group, d)
    return jnp.moveaxis(out, 1, 2)[:, :s].reshape(b, s, n_q, d)
