"""Prefill (causal) attention.

Single fused einsum path that XLA tiles onto the MXU. The [s_q, s_k] score
tensor is materialized, which is fine for the chunked-prefill sizes the
engine schedules (it bounds chunk length); a Pallas flash-prefill kernel is
the planned upgrade for long unchunked prefills. GQA is handled by reshaping
query heads into (kv_head, group) blocks.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def causal_prefill_attention(
    q: jnp.ndarray,  # [batch, seq, n_heads, head_dim]
    k: jnp.ndarray,  # [batch, seq, n_kv_heads, head_dim]
    v: jnp.ndarray,  # [batch, seq, n_kv_heads, head_dim]
    *,
    positions: Optional[jnp.ndarray] = None,  # [batch, seq] absolute positions
    valid: Optional[jnp.ndarray] = None,  # [batch, seq] bool — False = padding
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Causal self-attention over one contiguous chunk (prefill).

    When ``positions`` is given, the causal mask uses absolute positions so
    chunked prefill (later chunks attending into earlier KV) composes; for
    the single-chunk case the default arange mask applies. ``valid`` marks
    padding positions whose keys must never be attended.
    Returns [batch, seq, n_heads, head_dim].
    """
    b, s, n_q, d = q.shape
    n_kv = k.shape[2]
    group = n_q // n_kv
    if scale is None:
        scale = d**-0.5

    qf = q.astype(jnp.float32).reshape(b, s, n_kv, group, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # [b, n_kv, group, s_q, s_k]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    mask = positions[:, None, None, :, None] >= positions[:, None, None, None, :]
    if valid is not None:
        mask = mask & valid[:, None, None, None, :]
    scores = jnp.where(mask, scores, -jnp.inf)

    probs = jax.nn.softmax(scores, axis=-1)
    # A fully-masked query row (padding query) softmaxes to NaN; zero it.
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vf)
    return out.reshape(b, s, n_q, d).astype(q.dtype)


#: key-block length for the online-softmax prefill scan. 512 keeps the
#: per-block score tile MXU-sized while bounding live memory to
#: O(seq × block) instead of O(seq²).
FLASH_KEY_BLOCK = 512

_NEG_INF = -1e30


def _flash_over_keys(
    qf: jnp.ndarray,  # [b, s, n_kv, group, d] f32
    k_all: jnp.ndarray,  # [b, n_kv, T, d]
    v_all: jnp.ndarray,  # [b, n_kv, T, d]
    k_valid: jnp.ndarray,  # [b, T] bool
    k_pos: jnp.ndarray,  # [b, T] int32 (visibility: k_pos <= q_pos)
    q_pos: jnp.ndarray,  # [b, s] int32
    scale: float,
    block: int,
    return_accumulators: bool = False,
    init_state=None,
    block_length: int = 0,
    window: int = 0,
) -> jnp.ndarray:
    """Online-softmax (flash) attention over a virtual key sequence, scanned
    in key blocks so the [s, T] score matrix is never materialized — the
    memory shape XLA wants for long-context prefill on TPU (score tile
    [s, block] is reused across scan iterations).

    With ``return_accumulators`` the raw flash state ``(m, l, acc)`` is
    returned instead of the normalized output, and ``init_state`` seeds
    the scan from prior accumulators — together they let a caller chain
    exact partial attentions over disjoint key ranges (the ring-attention
    body scans each rotating payload this way, one blocked flash pass per
    ring step).

    ``block_length`` > 1 (static) widens the visibility from causal to
    block-causal: a query sees every key up to the end of its own block of
    ``block_length`` absolute positions (``models/llama.py``: generation
    by diffusion over blocks). 0 and 1 are the causal program, op for
    op. ``window`` > 0 (static) narrows it: a query also sees no key more
    than ``window - 1`` positions before itself."""
    b, s, n_kv, group, d = qf.shape
    T = k_all.shape[2]
    if block_length > 1:
        # the last position of each query's block: k_pos <= that
        q_pos = (q_pos // block_length + 1) * block_length - 1
    # Short key sequences (cache-cold short prompts) shrink the block to a
    # lane-aligned size instead of padding up to a full block of masked work.
    block = min(block, -(-T // 128) * 128)
    n_blocks = -(-T // block)
    pad = n_blocks * block - T
    if pad:
        k_all = jnp.pad(k_all, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_all = jnp.pad(v_all, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_valid = jnp.pad(k_valid, ((0, 0), (0, pad)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)))

    kb = k_all.reshape(b, n_kv, n_blocks, block, d).transpose(2, 0, 1, 3, 4)
    vb = v_all.reshape(b, n_kv, n_blocks, block, d).transpose(2, 0, 1, 3, 4)
    valb = k_valid.reshape(b, n_blocks, block).transpose(1, 0, 2)
    posb = k_pos.reshape(b, n_blocks, block).transpose(1, 0, 2)

    if init_state is None:
        m0 = jnp.full((b, n_kv, group, s), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n_kv, group, s), jnp.float32)
        acc0 = jnp.zeros((b, n_kv, group, s, d), jnp.float32)
    else:
        m0, l0, acc0 = init_state

    def body(carry, blk):
        m, denom, acc = carry
        kblk, vblk, vblk_valid, pblk = blk
        scores = jnp.einsum(
            "bqhgd,bhtd->bhgqt", qf, kblk.astype(jnp.float32)
        ) * scale  # [b, n_kv, g, s, block]
        mask = (
            vblk_valid[:, None, None, None, :]
            & (pblk[:, None, None, None, :] <= q_pos[:, None, None, :, None])
        )
        if window:
            mask &= (
                pblk[:, None, None, None, :] + window
                > q_pos[:, None, None, :, None]
            )
        scores = jnp.where(mask, scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None]) * mask
        corr = jnp.exp(m - m_new)
        denom = denom * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqt,bhtd->bhgqd", p, vblk.astype(jnp.float32)
        )
        return (m_new, denom, acc), None

    (m, denom, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, valb, posb))
    if return_accumulators:
        return m, denom, acc
    out = acc / jnp.where(denom > 0, denom, 1.0)[..., None]
    # [b, n_kv, g, s, d] -> [b, s, n_kv, g, d]
    return out.transpose(0, 3, 1, 2, 4)


def prefill_with_paged_context(
    q: jnp.ndarray,  # [batch, seq, n_heads, head_dim] — the fresh chunk
    k: jnp.ndarray,  # [batch, seq, n_kv_heads, head_dim]
    v: jnp.ndarray,  # [batch, seq, n_kv_heads, head_dim]
    k_pages: jnp.ndarray,  # [total_pages, page_size, n_kv_heads, head_dim]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [batch, max_ctx_pages] int32 (pad with 0)
    ctx_lens: jnp.ndarray,  # [batch] int32 — tokens of cached context
    *,
    positions: jnp.ndarray,  # [batch, seq] absolute positions of the chunk
    valid: Optional[jnp.ndarray] = None,  # [batch, seq] padding mask
    scale: Optional[float] = None,
    k_scales: Optional[jnp.ndarray] = None,  # [total_pages, n_kv] f32
    v_scales: Optional[jnp.ndarray] = None,  # (KV_QUANT_HBM: int8 pools)
    block_length: int = 0,  # > 1: full inside a block, causal between
    window: int = 0,  # > 0: a query sees the ``window`` positions ending at it
    table_start: Optional[jnp.ndarray] = None,  # [batch]: the table's first position
) -> jnp.ndarray:
    """Chunked prefill attending to prefix-cached pages *and* causally within
    the fresh chunk.

    This is what turns a prefix-cache hit into skipped compute: the shared
    prefix's K/V already live in the page pool (written by whichever request
    computed them — RoPE is absolute so they are position-correct), and the
    request only prefills its suffix. Context tokens all precede the chunk,
    so cross-attention to them needs only the ctx_len mask, not a causal one.

    With ``block_length`` > 1 the chunk is causal between blocks of that
    many absolute positions and full inside one (position ``i`` sees ``j``
    iff ``j // B <= i // B``); 0 and 1 are the causal program.

    With ``window`` > 0 (a sliding layer) the context keys stand at their
    own positions, ``table_start`` (a row; None: 0) + their slot in the
    table, and a query sees only the ``window`` positions that end with it.

    One online softmax over the virtual key sequence [context ++ chunk],
    flash-scanned in ``FLASH_KEY_BLOCK``-sized key blocks (memory stays
    O(seq × block), enabling multi-k-token prefills). Returns
    [batch, seq, n_heads, head_dim].
    """
    b, s, n_q, d = q.shape
    n_kv = k.shape[2]
    group = n_q // n_kv
    if scale is None:
        scale = d**-0.5
    max_ctx = block_tables.shape[1] * k_pages.shape[1]

    qf = q.astype(jnp.float32).reshape(b, s, n_kv, group, d)

    # Context keys/values gathered per sequence: [b, n_kv, max_ctx, d].
    ctx_k = k_pages[block_tables]  # [b, max_ctx_pages, ps, n_kv, d]
    ctx_v = v_pages[block_tables]
    if k_scales is not None:
        # KV_QUANT_HBM=int8: pools hold codes; widen the gathered context
        # (chunk-sized, not pool-sized) with the per-page-per-head scales.
        ctx_k = ctx_k.astype(jnp.float32) * (
            k_scales[block_tables][:, :, None, :, None]
        )
        ctx_v = ctx_v.astype(jnp.float32) * (
            v_scales[block_tables][:, :, None, :, None]
        )
        ctx_k = ctx_k.astype(k.dtype)
        ctx_v = ctx_v.astype(v.dtype)
    ctx_k = jnp.moveaxis(ctx_k.reshape(b, max_ctx, n_kv, d), 1, 2)
    ctx_v = jnp.moveaxis(ctx_v.reshape(b, max_ctx, n_kv, d), 1, 2)

    # Virtual key sequence: [context ++ chunk]. Context keys are visible to
    # every query (they strictly precede the chunk): position -1 ≤ any
    # q_pos ≥ 0. Chunk keys follow causal position order.
    k_all = jnp.concatenate([ctx_k, jnp.moveaxis(k, 1, 2)], axis=2)
    v_all = jnp.concatenate([ctx_v, jnp.moveaxis(v, 1, 2)], axis=2)
    ctx_valid = jnp.arange(max_ctx)[None, :] < ctx_lens[:, None]
    ctx_pos = jnp.full((b, max_ctx), -1, jnp.int32)
    if window:
        start = (
            jnp.zeros((b,), jnp.int32) if table_start is None
            else table_start.astype(jnp.int32)
        )
        ctx_pos = jnp.arange(max_ctx, dtype=jnp.int32)[None, :] + start[:, None]
        ctx_valid = ctx_pos < ctx_lens[:, None]
    chunk_valid = (
        valid if valid is not None else jnp.ones((b, s), bool)
    )
    k_valid = jnp.concatenate([ctx_valid, chunk_valid], axis=1)
    k_pos = jnp.concatenate([ctx_pos, positions.astype(jnp.int32)], axis=1)

    out = _flash_over_keys(
        qf, k_all, v_all, k_valid, k_pos, positions.astype(jnp.int32),
        scale, FLASH_KEY_BLOCK, block_length=block_length, window=window,
    )
    return out.reshape(b, s, n_q, d).astype(q.dtype)
