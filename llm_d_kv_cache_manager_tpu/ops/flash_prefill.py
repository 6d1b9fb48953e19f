"""Pallas flash-prefill kernel: paged context + fresh causal chunk.

The prefill hot op (SURVEY §7 hard part (b), second half — the decode
kernel is `paged_attention.py`). The XLA-scan flash in `attention.py`
bounds memory but leaves MXU utilization on the table: every scan step
re-materializes its score tile through XLA's generic fusion, and the
virtual-key concat copies the whole context. This kernel runs one online
softmax over [cached context ++ fresh chunk] entirely in VMEM:

- Grid ``(batch, n_kv, q_blocks, k_steps)``; the k-step axis is innermost
  and walks the context blocks first, then the chunk's causal blocks, with
  flash m/l/acc scratch carried across the whole walk — the [s, T] score
  matrix never exists, in HBM or VMEM.
- Context and chunk keys are separate inputs with separate block sizes;
  their BlockSpec index maps CLAMP the k-step: steps past a sequence's
  real ``ctx_len`` (or past the causal frontier in the chunk phase) map to
  the previous block index, and Pallas skips the re-fetch — DMA traffic is
  proportional to the tokens actually attended, per sequence.
- Score tiles are ``[bq*group, bk]`` — query rows × GQA group collapsed to
  one MXU-friendly row dimension (1024 rows at bq=256, g=4).
- Context K/V are gathered from the page pool by one XLA gather before the
  call (`k_pages[block_tables]`), the same gather the XLA path does — but
  the concat copy and per-step fusion overhead are gone.

Contract (what the serving engine guarantees):
- chunk queries occupy CONSECUTIVE positions (`positions[b, i] = start + i`)
  so in-chunk causality is index order;
- ``valid`` is a right-padding mask (True prefix), reduced to a per-seq
  count; fully-padded query rows produce zeros;
- with a static ``block_length`` B > 1 (generation by diffusion over
  blocks, ``models/llama.py``) the chunk is causal between blocks of B
  positions and full inside one, and the chunk STARTS on a block boundary
  (``positions[b, 0] % B == 0``), so a block is B consecutive chunk
  indices: key ``k`` is visible to query ``q`` when
  ``k < (q // B + 1) * B``. That kernel is named ``block_attention`` in
  the trace. B of 0 or 1 is the causal program, op for op.

`prefill_with_paged_context` (attention.py) is the numerics oracle; parity
is tested across GQA/MHA/MQA in interpret mode and, compiled, on the chip
by ``chip_smoke.py``'s kernel phase (round-1 lesson: Mosaic can miscompile —
always check numerics on the chip).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import require_tpu_unless_interpret

# Finite: a fully-masked score row must yield exp(-1e30 - -1e30) = 1,
# zeroed by the mask multiply — float('-inf') would produce inf-inf = NaN.
_NEG_INF = -1e30

#: default key-block (lane-tiled) and query-block (sublane-tiled) sizes.
#: (256, 1024) comes from a sweep on a rig that is gone (its script was
#: retired in PR 30) and has no TPU v5e measurement: ROADMAP.md S5. The
#: reasoning then: fewer, larger k-steps amortize per-step overhead.
KEY_BLOCK = 1024
QUERY_BLOCK = 256
#: cap on bq*group score rows — bounds the [rows, bk] f32 score tile and
#: the f32 scratch so high-group (MQA-ish) geometries fit in 16 MB VMEM
MAX_SCORE_ROWS = 1024


def _visible_through(q_idx, block_length: int):
    """The last chunk index a query at chunk index ``q_idx`` sees: itself
    (causal), or the end of its block of ``block_length`` indices."""
    if block_length > 1:
        return (q_idx // block_length + 1) * block_length - 1
    return q_idx


def _flash_prefill_kernel(
    # scalar prefetch
    ctx_lens_ref,  # [batch] int32
    n_valid_ref,  # [batch] int32
    # blocks (all head-major: the blocked head axis must stay out of the
    # last two dims, which Mosaic requires to be (8,128)-tiled or full)
    q_ref,  # [1, 1, bq, g, d]
    ctx_k_ref,  # [1, 1, bk_ctx, d]
    ctx_v_ref,  # [1, 1, bk_ctx, d]
    ck_ref,  # [1, 1, bk_chunk, d]
    cv_ref,  # [1, 1, bk_chunk, d]
    out_ref,  # [1, 1, bq, g, d]
    m_ref,  # [bq*g, 128] f32 scratch
    l_ref,  # [bq*g, 128] f32 scratch
    acc_ref,  # [bq*g, d] f32 scratch
    *,
    bq: int,
    bk_ctx: int,
    bk_chunk: int,
    group: int,
    n_ctx_blocks: int,
    scale: float,
    block_length: int = 0,
):
    b = pl.program_id(0)
    qb = pl.program_id(2)
    ks = pl.program_id(3)
    n_ksteps = pl.num_programs(3)
    ctx_len = ctx_lens_ref[b]
    n_valid = n_valid_ref[b]

    @pl.when(ks == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    d = q_ref.shape[-1]
    rows = bq * group

    def flash_update(scores, mask, v):
        # scores [rows, bk] f32 pre-masked to _NEG_INF, v [bk, d]
        m_prev = m_ref[:, :1]
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
            probs.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    def q_rows():
        # Native dtype (bf16 in serving): the q@k dot runs bf16×bf16 on
        # the MXU with f32 accumulation via preferred_element_type.
        q = q_ref[0, 0]  # [bq, g, d]
        return q.reshape(rows, d)

    # q-row index (within the chunk) per score row: row r ↔ query r // g.
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group

    in_ctx_phase = ks < n_ctx_blocks if n_ctx_blocks else False

    # ---- context phase: keys are cached-context tokens, all of which
    # precede every chunk query; visibility is just k_idx < ctx_len.
    if n_ctx_blocks:

        @pl.when(jnp.logical_and(in_ctx_phase, ks * bk_ctx < ctx_len))
        def _ctx_step():
            k = ctx_k_ref[0, 0]  # [bk_ctx, d]
            v = ctx_v_ref[0, 0]
            scores = (
                jax.lax.dot_general(
                    q_rows(), k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [rows, bk_ctx] f32
            k_idx = ks * bk_ctx + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1
            )
            mask = (k_idx < ctx_len) & (qb * bq + q_idx < n_valid)
            flash_update(jnp.where(mask, scores, _NEG_INF), mask, v)

    # ---- chunk phase: causal within the chunk (consecutive positions →
    # index order; through the end of the query's block when block_length
    # > 1), bounded by the per-sequence valid count.
    cks = ks - n_ctx_blocks
    q_end = _visible_through(qb * bq + bq - 1, block_length)

    @pl.when(
        jnp.logical_and(
            jnp.logical_not(in_ctx_phase),
            jnp.logical_and(cks * bk_chunk <= q_end, cks * bk_chunk < n_valid),
        )
    )
    def _chunk_step():
        k = ck_ref[0, 0]  # [bk_chunk, d]
        v = cv_ref[0, 0]
        scores = (
            jax.lax.dot_general(
                q_rows(), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [rows, bk_chunk] f32
        k_idx = cks * bk_chunk + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        # [rows, 1], broadcasts over lanes
        q_pos = _visible_through(qb * bq + q_idx, block_length)
        mask = (k_idx <= q_pos) & (k_idx < n_valid) & (q_idx < n_valid - qb * bq)
        flash_update(jnp.where(mask, scores, _NEG_INF), mask, v)

    @pl.when(ks == n_ksteps - 1)
    def _finalize():
        denom = l_ref[:, :1]
        safe_l = jnp.where(denom == 0.0, 1.0, denom)  # fully-masked rows → zeros
        out = (acc_ref[:] / safe_l).reshape(bq, group, d)
        out_ref[0, 0] = out.astype(out_ref.dtype)


def _flash_prefill_kernel_dma(
    # scalar prefetch
    ctx_lens_ref,  # [batch] int32
    n_valid_ref,  # [batch] int32
    bt_ref,  # [batch, max_ctx_pages] int32 block tables
    # blocks
    q_ref,  # [1, 1, bq, g, d]
    k_pages_ref,  # [P, ps, n_kv, d] — FULL pool, HBM (ANY memory space)
    v_pages_ref,  # [P, ps, n_kv, d]
    ck_ref,  # [1, 1, bk_chunk, d]
    cv_ref,  # [1, 1, bk_chunk, d]
    out_ref,  # [1, 1, bq, g, d]
    m_ref,  # [bq*g, 128] f32 scratch
    l_ref,  # [bq*g, 128] f32 scratch
    acc_ref,  # [bq*g, d] f32 scratch
    ctx_k_buf,  # [2, bk_ctx, d] VMEM — double-buffered context keys
    ctx_v_buf,  # [2, bk_ctx, d]
    sem_k,  # DMA semaphores [2]
    sem_v,  # DMA semaphores [2]
    *,
    bq: int,
    bk_ctx: int,
    bk_chunk: int,
    group: int,
    n_ctx_blocks: int,
    scale: float,
    page_size: int,
    block_length: int = 0,
):
    """Direct-paged-DMA variant: context K/V pages are copied from the
    HBM pool into double-buffered VMEM by in-kernel ``make_async_copy``
    (block-table dereference via scalar prefetch), skipping the pre-call
    XLA gather — one full HBM round-trip of context KV per layer
    (pool read + contiguous-buffer write) that the gather variant pays
    before the kernel even starts. Step N+1's pages stream in while step
    N computes (start at N, wait at N+1), so the DMA latency hides under
    the MXU the same way the blocked-operand pipeline hides the gather
    variant's reads."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    qb = pl.program_id(2)
    ks = pl.program_id(3)
    n_ksteps = pl.num_programs(3)
    ctx_len = ctx_lens_ref[b]
    n_valid = n_valid_ref[b]
    pages_per_step = bk_ctx // page_size
    max_pages = bt_ref.shape[1]
    # Steps that actually carry context data for this sequence.
    needed_steps = pl.cdiv(ctx_len, bk_ctx)

    def ctx_copies(slot, step):
        """The step's page copies (handles are reconstructed identically
        at start and wait time — the standard Pallas async-copy idiom)."""
        out = []
        for i in range(pages_per_step):  # static trip count
            # Pages past the table edge clamp to a real page; their tokens
            # sit past ctx_len and are masked in the score step.
            page = bt_ref[b, jnp.minimum(step * pages_per_step + i, max_pages - 1)]
            dst = pl.ds(i * page_size, page_size)
            out.append(
                (
                    pltpu.make_async_copy(
                        k_pages_ref.at[page, :, h, :],
                        ctx_k_buf.at[slot, dst, :],
                        sem_k.at[slot],
                    ),
                    pltpu.make_async_copy(
                        v_pages_ref.at[page, :, h, :],
                        ctx_v_buf.at[slot, dst, :],
                        sem_v.at[slot],
                    ),
                )
            )
        return out

    def start_step(step):
        for ck_copy, cv_copy in ctx_copies(step % 2, step):
            ck_copy.start()
            cv_copy.start()

    def wait_step(step):
        for ck_copy, cv_copy in ctx_copies(step % 2, step):
            ck_copy.wait()
            cv_copy.wait()

    @pl.when(ks == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if n_ctx_blocks:
        # Prologue: kick off step 0 before anything waits on it.
        @pl.when(jnp.logical_and(ks == 0, needed_steps > 0))
        def _prologue():
            start_step(0)

    d = q_ref.shape[-1]
    rows = bq * group

    def flash_update(scores, mask, v):
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new) * mask
        l_ref[:] = l_ref[:] * alpha + jnp.broadcast_to(
            jnp.sum(probs, axis=-1, keepdims=True), l_ref.shape
        )
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            probs.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    def q_rows():
        q = q_ref[0, 0]  # [bq, g, d]
        return q.reshape(rows, d)

    q_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
    in_ctx_phase = ks < n_ctx_blocks if n_ctx_blocks else False

    if n_ctx_blocks:

        @pl.when(jnp.logical_and(in_ctx_phase, ks < needed_steps))
        def _ctx_step():
            wait_step(ks)
            # Stream the NEXT step's pages under this step's compute.
            @pl.when(ks + 1 < needed_steps)
            def _prefetch_next():
                start_step(ks + 1)

            k = ctx_k_buf[ks % 2]  # [bk_ctx, d]
            v = ctx_v_buf[ks % 2]
            scores = (
                jax.lax.dot_general(
                    q_rows(), k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            k_idx = ks * bk_ctx + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1
            )
            mask = (k_idx < ctx_len) & (qb * bq + q_idx < n_valid)
            flash_update(jnp.where(mask, scores, _NEG_INF), mask, v)

    cks = ks - n_ctx_blocks
    q_end = _visible_through(qb * bq + bq - 1, block_length)

    @pl.when(
        jnp.logical_and(
            jnp.logical_not(in_ctx_phase),
            jnp.logical_and(cks * bk_chunk <= q_end, cks * bk_chunk < n_valid),
        )
    )
    def _chunk_step():
        k = ck_ref[0, 0]
        v = cv_ref[0, 0]
        scores = (
            jax.lax.dot_general(
                q_rows(), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        k_idx = cks * bk_chunk + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        q_pos = _visible_through(qb * bq + q_idx, block_length)
        mask = (k_idx <= q_pos) & (k_idx < n_valid) & (q_idx < n_valid - qb * bq)
        flash_update(jnp.where(mask, scores, _NEG_INF), mask, v)

    @pl.when(ks == pl.num_programs(3) - 1)
    def _finalize():
        denom = l_ref[:, :1]
        safe_l = jnp.where(denom == 0.0, 1.0, denom)
        out = (acc_ref[:] / safe_l).reshape(bq, group, d)
        out_ref[0, 0] = out.astype(out_ref.dtype)


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
        "scale", "interpret", "q_block", "key_block", "ctx_mode",
        "block_length",
    ),
)
def flash_prefill_paged(
    q: jnp.ndarray,  # [batch, seq, n_heads, head_dim] — fresh chunk
    k: jnp.ndarray,  # [batch, seq, n_kv_heads, head_dim]
    v: jnp.ndarray,  # [batch, seq, n_kv_heads, head_dim]
    k_pages: jnp.ndarray,  # [total_pages, page_size, n_kv_heads, head_dim]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [batch, max_ctx_pages] int32 (pad with 0)
    ctx_lens: jnp.ndarray,  # [batch] int32
    n_valid: jnp.ndarray,  # [batch] int32 — valid chunk tokens (right-pad)
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
    q_block: int = QUERY_BLOCK,
    key_block: int = KEY_BLOCK,
    ctx_mode: str = "gather",
    block_length: int = 0,
) -> jnp.ndarray:
    """Pallas flash prefill over [paged context ++ fresh chunk].

    Drop-in for `prefill_with_paged_context` under the engine's contract
    (consecutive chunk positions, right-padding); `n_valid` replaces the
    boolean `valid` mask. Returns [batch, seq, n_heads, head_dim].
    ``block_length`` > 1: block-causal chunk (module docstring; the chunk
    starts on a block boundary).

    ``ctx_mode`` picks how context K/V reach the kernel:

    - ``"gather"`` — one XLA gather (``k_pages[block_tables]``) builds a
      contiguous per-sequence context buffer before the call; the kernel
      streams it through auto-pipelined blocked operands. Costs a full
      HBM round-trip of context KV (pool read + buffer write) per layer.
    - ``"dma"`` — the kernel DMAs pages straight from the pool into
      double-buffered VMEM (in-kernel ``make_async_copy`` driven by the
      scalar-prefetched block table), skipping that round-trip. Falls
      back to gather when the key block is not page-aligned.

      STATUS — interpret-validated, blocked on real TPU by the pool
      layout: Mosaic requires HBM memref slices to respect the (8, 128)
      tiling of the last two dims, and the pool's head-minor layout
      ``[P, ps, n_kv, d]`` makes the per-head page slice
      ``pool[page, :, h, :]`` a width-1 cut through the sublane-tiled
      ``n_kv`` axis ("Slice shape along dimension 2 must be aligned to
      tiling (8)"). Copying whole pages instead would DMA ``n_kv``× the
      needed bytes per head-walk — strictly worse than the gather. The
      unblocking layout is head-major ``[P, n_kv, ps, d]`` (the slice
      then cuts a non-tiled dim), but that layout de-optimizes the
      decode kernel's contiguous page tile and the token-write scatter
      — the dominant serving phase — so it is not worth flipping for a
      bounded ~8 % warm-prefill win (ROADMAP: measured rejections).
    """
    b, s, n_q, d = q.shape
    n_kv = k.shape[2]
    group = n_q // n_kv
    if scale is None:
        scale = d**-0.5
    require_tpu_unless_interpret("flash_prefill_paged", interpret)
    if ctx_mode not in ("gather", "dma"):
        raise ValueError(f"unknown ctx_mode {ctx_mode!r}")

    page_size = k_pages.shape[1]
    max_ctx = block_tables.shape[1] * page_size
    bk_ctx = min(key_block, _round_up(max_ctx, 128)) if max_ctx else 0
    n_ctx_blocks = -(-max_ctx // bk_ctx) if max_ctx else 0
    use_dma = (
        ctx_mode == "dma"
        and max_ctx > 0
        and bk_ctx % page_size == 0
    )
    if use_dma and not interpret:
        # Fail fast with the design rationale instead of Mosaic's tiling
        # error at first dispatch (see the docstring's STATUS note).
        raise NotImplementedError(
            "ctx_mode='dma' is interpret-only: the pool's head-minor "
            "layout [P, ps, n_kv, d] makes the per-head page slice "
            "violate Mosaic's (8, 128) HBM tiling; a head-major pool "
            "would unblock it at the cost of the decode kernel's "
            "contiguous page tile (see flash_prefill_paged docstring)"
        )
    if use_dma:
        return _flash_prefill_dma(
            q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid,
            scale=scale, interpret=interpret, q_block=q_block,
            bk_ctx=bk_ctx, n_ctx_blocks=n_ctx_blocks, key_block=key_block,
            block_length=block_length,
        )

    # Gather the cached context once (page-major pool → per-seq contiguous)
    # and go head-major: the blocked head axis must stay out of the last
    # two dims (Mosaic tiling constraint).
    if max_ctx:
        ctx_k = jnp.moveaxis(k_pages[block_tables].reshape(b, max_ctx, n_kv, d), 1, 2)
        ctx_v = jnp.moveaxis(v_pages[block_tables].reshape(b, max_ctx, n_kv, d), 1, 2)
        pad_c = n_ctx_blocks * bk_ctx - max_ctx
        if pad_c:
            ctx_k = jnp.pad(ctx_k, ((0, 0), (0, 0), (0, pad_c), (0, 0)))
            ctx_v = jnp.pad(ctx_v, ((0, 0), (0, 0), (0, pad_c), (0, 0)))
    else:
        # Degenerate no-context call: a single dummy block, never computed
        # (ctx_len == 0 skips the phase) — keeps the spec machinery uniform.
        bk_ctx, n_ctx_blocks = 128, 0
        ctx_k = jnp.zeros((b, n_kv, bk_ctx, d), k.dtype)
        ctx_v = jnp.zeros((b, n_kv, bk_ctx, d), v.dtype)

    bq = max(8, min(q_block, MAX_SCORE_ROWS // group // 8 * 8))
    bq = min(bq, _round_up(s, 8))
    bk_chunk = min(key_block, _round_up(s, 128))
    s_padq = _round_up(s, bq)
    s_padk = _round_up(s, bk_chunk)
    n_qblocks = s_padq // bq
    n_chunk_blocks = s_padk // bk_chunk

    # [b, n_kv, s_pad, g, d] / [b, n_kv, s_pad, d]
    qp = jnp.moveaxis(
        jnp.pad(q, ((0, 0), (0, s_padq - s), (0, 0), (0, 0))).reshape(
            b, s_padq, n_kv, group, d
        ),
        1,
        2,
    )
    kp = jnp.moveaxis(jnp.pad(k, ((0, 0), (0, s_padk - s), (0, 0), (0, 0))), 1, 2)
    vp = jnp.moveaxis(jnp.pad(v, ((0, 0), (0, s_padk - s), (0, 0), (0, 0))), 1, 2)

    ctx_lens = ctx_lens.astype(jnp.int32)
    n_valid = n_valid.astype(jnp.int32)
    n_ksteps = n_ctx_blocks + n_chunk_blocks
    grid = (b, n_kv, n_qblocks, n_ksteps)

    def q_index(b_, h, qb, ks, cl, nv):
        return (b_, h, qb, 0, 0)

    def ctx_index(b_, h, qb, ks, cl, nv):
        # Clamp past-the-data steps to the previous block → Pallas skips
        # the re-fetch; DMA ∝ real ctx_len. In the chunk phase this pins
        # to the last fetched context block (no fetch at all).
        needed = jnp.maximum(-(-cl[b_] // bk_ctx), 1)
        return (b_, h, jnp.minimum(ks, needed - 1), 0)

    def chunk_index(b_, h, qb, ks, cl, nv):
        cks = jnp.maximum(ks - n_ctx_blocks, 0)
        # causal frontier: blocks past this q-block's last row are clamped
        causal_last = _visible_through(qb * bq + bq - 1, block_length) // bk_chunk
        needed = jnp.maximum(-(-nv[b_] // bk_chunk), 1)
        return (b_, h, jnp.minimum(jnp.minimum(cks, causal_last), needed - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, group, d), q_index),
            pl.BlockSpec((1, 1, bk_ctx, d), ctx_index),
            pl.BlockSpec((1, 1, bk_ctx, d), ctx_index),
            pl.BlockSpec((1, 1, bk_chunk, d), chunk_index),
            pl.BlockSpec((1, 1, bk_chunk, d), chunk_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, group, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((bq * group, 128), jnp.float32),
            pltpu.VMEM((bq * group, 128), jnp.float32),
            pltpu.VMEM((bq * group, d), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _flash_prefill_kernel,
        bq=bq,
        bk_ctx=bk_ctx,
        bk_chunk=bk_chunk,
        group=group,
        n_ctx_blocks=n_ctx_blocks,
        scale=scale,
        block_length=block_length,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, s_padq, group, d), q.dtype),
        interpret=interpret,
        name=_kernel_name(block_length),
    )(ctx_lens, n_valid, qp, ctx_k, ctx_v, kp, vp)
    # [b, n_kv, s_pad, g, d] -> [b, s, n_q, d]
    return jnp.moveaxis(out, 1, 2)[:, :s].reshape(b, s, n_q, d)


def _flash_prefill_dma(
    q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid,
    *, scale, interpret, q_block, bk_ctx, n_ctx_blocks, key_block,
    block_length=0,
):
    """Direct-paged-DMA dispatch path of ``flash_prefill_paged``: the
    FULL pools enter the kernel in HBM (ANY memory space) and page tiles
    stream into double-buffered VMEM via in-kernel async copies — no
    pre-gathered context buffer exists at any point."""
    b, s, n_q, d = q.shape
    n_kv = k.shape[2]
    group = n_q // n_kv
    page_size = k_pages.shape[1]

    bq = max(8, min(q_block, MAX_SCORE_ROWS // group // 8 * 8))
    bq = min(bq, _round_up(s, 8))
    bk_chunk = min(key_block, _round_up(s, 128))
    s_padq = _round_up(s, bq)
    s_padk = _round_up(s, bk_chunk)
    n_qblocks = s_padq // bq
    n_chunk_blocks = s_padk // bk_chunk

    qp = jnp.moveaxis(
        jnp.pad(q, ((0, 0), (0, s_padq - s), (0, 0), (0, 0))).reshape(
            b, s_padq, n_kv, group, d
        ),
        1,
        2,
    )
    kp = jnp.moveaxis(jnp.pad(k, ((0, 0), (0, s_padk - s), (0, 0), (0, 0))), 1, 2)
    vp = jnp.moveaxis(jnp.pad(v, ((0, 0), (0, s_padk - s), (0, 0), (0, 0))), 1, 2)

    n_ksteps = n_ctx_blocks + n_chunk_blocks
    grid = (b, n_kv, n_qblocks, n_ksteps)

    def q_index(b_, h, qb, ks, cl, nv, bt):
        return (b_, h, qb, 0, 0)

    def chunk_index(b_, h, qb, ks, cl, nv, bt):
        cks = jnp.maximum(ks - n_ctx_blocks, 0)
        causal_last = _visible_through(qb * bq + bq - 1, block_length) // bk_chunk
        needed = jnp.maximum(-(-nv[b_] // bk_chunk), 1)
        return (b_, h, jnp.minimum(jnp.minimum(cks, causal_last), needed - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, group, d), q_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, bk_chunk, d), chunk_index),
            pl.BlockSpec((1, 1, bk_chunk, d), chunk_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, group, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((bq * group, 128), jnp.float32),
            pltpu.VMEM((bq * group, 128), jnp.float32),
            pltpu.VMEM((bq * group, d), jnp.float32),
            pltpu.VMEM((2, bk_ctx, d), k_pages.dtype),
            pltpu.VMEM((2, bk_ctx, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )

    kernel = functools.partial(
        _flash_prefill_kernel_dma,
        bq=bq,
        bk_ctx=bk_ctx,
        bk_chunk=bk_chunk,
        group=group,
        n_ctx_blocks=n_ctx_blocks,
        scale=scale,
        page_size=page_size,
        block_length=block_length,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, s_padq, group, d), q.dtype),
        interpret=interpret,
        name=_kernel_name(block_length),
    )(
        ctx_lens.astype(jnp.int32),
        n_valid.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        qp,
        k_pages,
        v_pages,
        kp,
        vp,
    )
    return jnp.moveaxis(out, 1, 2)[:, :s].reshape(b, s, n_q, d)
