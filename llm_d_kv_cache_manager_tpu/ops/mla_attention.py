"""Latent (MLA) paged attention in the absorbed form: Pallas TPU kernel +
``jax.numpy`` oracle.

A latent model's pool holds ONE row a token a layer, ``[c | k_r]``: the
normed latent (``kv_lora_rank`` wide) beside the rotated key every head
shares (``models/llama.py``). In the absorbed form a query head is
``[q_n W_kb^T | q_r]``, as wide as the row, its score against a token is one
dot product with the row, and the softmax-weighted sum is taken over the
row's first ``dv = kv_lora_rank`` columns (``W_vb`` is applied outside). So
every head of a sequence reads the SAME row, once, as key and as value:
multi-query attention with one KV head of width 576/512 and a group of
all 32 heads, which is what this kernel computes.

- **One kernel, decode and warm prefill.** ``mla_paged_attention`` takes
  ``s`` query rows a sequence over ``[paged context ++ the s fresh rows]``;
  decode is ``s == 1`` (the fresh row is the token's own, written to the
  pool after attention like every other model's). In the trace the call is
  named ``mla_decode`` where ``s == 1`` and ``mla_prefill`` otherwise.
- **The pool is read in place** (PR 31's page-tile reader,
  ``flash_prefill.py``): the whole ``[L, P, page_size, width]`` pool in
  ``ANY`` memory space, the layer a scalar-prefetch word, page tiles
  ``[page_size, width]`` copied by ``(layer, block_tables[b, page])`` into
  double-buffered VMEM, ``KEY_BLOCK / page_size`` pages a step, the next
  step's pages in flight under this step's matmuls, and only as far as the
  row's context reaches. A group of pages whose pool ids are consecutive is
  ONE copy (``_page_copies.for_step_pages``, PR 45: what a copy costs here
  is starting it). There is no head axis to swap: the tile is the key
  operand as it lands, and its first ``dv`` columns the value operand.
- Score tiles are ``[bq * heads, keys]`` (query rows x heads collapsed to
  the MXU's row dimension), float32 online softmax, operands in the pool's
  dtype with float32 accumulation. No temporary grows with rows x context
  x heads: the context is never gathered nor expanded.

Contract: the one of ``flash_prefill_paged`` (consecutive positions,
right-padded ``n_valid``, table entries past ``ceil(ctx_len / page_size)``
never read; a dead table tail may hold anything).

``mla_paged_attention_reference`` is the oracle (gathered pages, one
softmax); ``tests/test_mla.py`` holds the interpreted kernel to it,
``tests/test_pool_layout.py`` compiles the call for a described v5e and
``chip_smoke.py``'s kernel phase checks it, compiled, on the chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import require_tpu_unless_interpret
from ._page_copies import for_step_pages

# finite, as in flash_prefill: a fully masked row must give exp(0) = 1,
# zeroed by the mask multiply, not inf - inf
_NEG_INF = -1e30

#: most context keys a step (pages a step x ``page_size``): 64 pages of 16
#: tokens. On a TPU v5e the decode call over 32 lanes x 12-29k tokens, all 8
#: layers, took 23.2 / 18.7 / 16.9 ms at 256 / 512 / 1024 keys a step (chip
#: run, PR 32: about 0.05 us a page copy and 0.5 us a step). Read again once
#: a run of pages is one copy (chip runs, PR 45, tables that are one run):
#: 13.1 / 11.3 / 10.5 ms at 512 / 1024 / 2048 there, but 4.97 / 5.36 ms at
#: 1024 / 2048 over 64 lanes x 64 heads x 1-5k tokens (`turns`) and 10.0 /
#: 10.8 ms for a question's 128 rows over 20k tokens: 1024 is kept.
KEY_BLOCK = 1024
#: cap on bq * heads score rows a program: with ``KEY_BLOCK`` it bounds the
#: f32 score tile (8 MiB) and the f32 accumulator (4 MiB at dv = 512)
MAX_SCORE_ROWS = 2048
_VMEM_LIMIT = 64 * 1024 * 1024


def _mla_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    ctx_lens_ref,  # [batch] int32
    n_valid_ref,  # [batch] int32
    bt_ref,  # [batch, max(table pages, 1)] int32
    # operands
    q_ref,  # [1, rows, dk] — row r is query r // heads, head r % heads
    pool_ref,  # [L, P, page_size, dk] — the whole pool, where it lies (ANY)
    fresh_ref,  # [1, bk_chunk, dk] — the chunk's own latent rows
    out_ref,  # [1, rows, dv]
    m_ref,  # [rows, 128] f32 scratch
    l_ref,  # [rows, 128] f32 scratch
    acc_ref,  # [rows, dv] f32 scratch
    ctx_buf,  # [2, bk_ctx / page_size, page_size, dk] VMEM — page tiles
    sem,  # DMA semaphores [2 (slot)]
    *,
    bq: int,
    heads: int,
    bk_ctx: int,
    bk_chunk: int,
    page_size: int,
    table_pages: int,
    scale: float,
    dv: int,
    interpret: bool,
):
    b = pl.program_id(0)
    qb = pl.program_id(1)
    cks = pl.program_id(2)
    n_valid = n_valid_ref[b]
    rows = bq * heads
    pages_per_step = bk_ctx // page_size

    q_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // heads
    q_live = qb * bq < n_valid

    def attend(kv, mask):
        """One online-softmax step over a block of rows ``kv [bk, dk]``:
        key as it is, value its first ``dv`` columns; ``mask [rows, bk]``."""
        scores = jax.lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, bk] f32
        scores = jnp.where(mask, scores, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new) * mask
        l_ref[:] = l_ref[:] * alpha + jnp.broadcast_to(
            jnp.sum(probs, axis=-1, keepdims=True), l_ref.shape
        )
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            probs.astype(kv.dtype), kv[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    def context_phase():
        layer = layer_ref[0]
        ctx_len = jnp.minimum(ctx_lens_ref[b], table_pages * page_size)
        n_pages = pl.cdiv(ctx_len, page_size)
        n_steps = jnp.where(q_live, pl.cdiv(ctx_len, bk_ctx), 0)

        def for_live_pages(step, act):
            slot = step % 2
            first = step * pages_per_step
            for_step_pages(
                act, bt_ref, b, first,
                jnp.minimum(pages_per_step, n_pages - first), layer,
                ((pool_ref, ctx_buf.at[slot], sem.at[slot]),),
                rolled=interpret,
            )

        @pl.when(n_steps > 0)
        def _prologue():
            for_live_pages(0, lambda copy: copy.start())

        def ctx_step(step, carry):
            for_live_pages(step, lambda copy: copy.wait())

            @pl.when(step + 1 < n_steps)
            def _prefetch_next():
                for_live_pages(step + 1, lambda copy: copy.start())

            slot = step % 2
            # Slots past the live pages hold what an earlier step or call
            # left: a zero probability times a stray NaN is NaN, so those
            # rows are zeroed, not only masked in the scores.
            # (a slot is [pages, page_size, dk] so that a run lands in it
            # as it lies in the pool; merging the two leading dimensions
            # moves no bytes at a page of whole sublane tiles)
            rows_kv = ctx_buf[slot].reshape(bk_ctx, ctx_buf.shape[-1])
            tok = step * bk_ctx + jax.lax.broadcasted_iota(
                jnp.int32, rows_kv.shape, 0
            )
            kv = jnp.where(tok < ctx_len, rows_kv, 0).astype(ctx_buf.dtype)
            k_idx = step * bk_ctx + jax.lax.broadcasted_iota(
                jnp.int32, (rows, bk_ctx), 1
            )
            mask = (k_idx < ctx_len) & (qb * bq + q_idx < n_valid)
            attend(kv, mask)
            return carry

        jax.lax.fori_loop(0, n_steps, ctx_step, 0)

    @pl.when(cks == 0)
    def _init_and_context():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        if table_pages:
            context_phase()

    # ---- the chunk's own rows: causal by chunk index
    @pl.when(
        jnp.logical_and(
            q_live,
            jnp.logical_and(
                cks * bk_chunk <= qb * bq + bq - 1, cks * bk_chunk < n_valid
            ),
        )
    )
    def _chunk_step():
        k_idx = cks * bk_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (rows, bk_chunk), 1
        )
        q_pos = qb * bq + q_idx
        mask = (k_idx <= q_pos) & (k_idx < n_valid) & (q_pos < n_valid)
        attend(fresh_ref[0], mask)

    @pl.when(cks == pl.num_programs(2) - 1)
    def _finalize():
        denom = l_ref[:, :1]
        safe_l = jnp.where(denom == 0.0, 1.0, denom)  # fully masked -> zeros
        out_ref[0] = (acc_ref[:] / safe_l).astype(out_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def ctx_step_pages(
    table_pages: int, page_size: int, key_block: int = KEY_BLOCK
) -> int:
    """Table pages a context step of the kernel: ``key_block`` keys in whole
    lane tiles and whole pages, no more than the table holds."""
    keys = _round_up(
        min(_round_up(key_block, 128), max(table_pages * page_size, 1)),
        math.lcm(page_size, 128),
    )
    return keys // page_size


@functools.partial(
    jax.jit, static_argnames=("dv", "scale", "interpret", "key_block"),
)
def mla_paged_attention(
    q: jnp.ndarray,  # [batch, s, heads, dk] — absorbed queries [q_c | q_r]
    fresh: jnp.ndarray,  # [batch, s, dk] — the s rows' own latent rows
    pool: jnp.ndarray,  # [(n_layers,) total_pages, page_size, dk]
    block_tables: jnp.ndarray,  # [batch, max_ctx_pages] int32
    ctx_lens: jnp.ndarray,  # [batch] int32 — tokens resident in the pages
    n_valid: jnp.ndarray,  # [batch] int32 — valid fresh rows (right-pad)
    *,
    dv: int,  # columns of a row that are the value (kv_lora_rank)
    scale: float,
    interpret: bool = False,
    key_block: int = KEY_BLOCK,
    layer=0,
) -> jnp.ndarray:
    """Absorbed latent attention over [paged context ++ fresh rows]:
    returns ``[batch, s, heads, dv]`` (the weighted sums of latents; the
    caller applies ``W_vb``). ``layer`` is an operand (every layer of a
    program shares one lowering). Padded query rows give zeros."""
    b, s, heads, dk = q.shape
    require_tpu_unless_interpret("mla_paged_attention", interpret)
    if pool.ndim == 3:  # single-layer callers
        pool, layer = pool[None], 0
    page_size = pool.shape[2]
    table_pages = block_tables.shape[1]

    # Query rows a block: bq * heads a whole number of bf16 sublane tiles.
    align = 16 // math.gcd(16, heads)
    bq = max(MAX_SCORE_ROWS // heads // align * align, align)
    bq = min(bq, _round_up(s, align))
    # Fresh keys a step: one sublane tile where the chunk is that short
    # (decode: one row), else whole lane tiles.
    bk_chunk = 16 if s <= 16 else min(_round_up(key_block, 128), _round_up(s, 128))
    step_pages = ctx_step_pages(table_pages, page_size, key_block)
    bk_ctx = step_pages * page_size
    s_padq = _round_up(s, bq)
    s_padk = _round_up(s, bk_chunk)
    rows = bq * heads

    qp = jnp.pad(q, ((0, 0), (0, s_padq - s), (0, 0), (0, 0))).reshape(
        b, s_padq * heads, dk
    )
    fp = jnp.pad(fresh, ((0, 0), (0, s_padk - s), (0, 0))).astype(pool.dtype)
    if not table_pages:
        block_tables = jnp.zeros((b, 1), jnp.int32)

    def q_index(b_, qb, cks, *_):
        return (b_, qb, 0)

    def chunk_index(b_, qb, cks, layer_, cl, nv, bt):
        causal_last = (qb * bq + bq - 1) // bk_chunk
        needed = jnp.maximum(-(-nv[b_] // bk_chunk), 1)
        return (b_, jnp.minimum(jnp.minimum(cks, causal_last), needed - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, s_padq // bq, s_padk // bk_chunk),
        in_specs=[
            pl.BlockSpec((1, rows, dk), q_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bk_chunk, dk), chunk_index),
        ],
        out_specs=pl.BlockSpec((1, rows, dv), q_index),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
            pltpu.VMEM((2, step_pages, page_size, dk), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _mla_kernel, bq=bq, heads=heads, bk_ctx=bk_ctx, bk_chunk=bk_chunk,
        page_size=page_size, table_pages=table_pages, scale=scale, dv=dv,
        interpret=interpret,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s_padq * heads, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mla_decode" if s == 1 else "mla_prefill",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        ctx_lens.astype(jnp.int32),
        n_valid.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        qp.astype(pool.dtype),
        pool,
        fp,
    )
    return out.reshape(b, s_padq, heads, dv)[:, :s]


def mla_paged_attention_reference(
    q: jnp.ndarray,  # [batch, s, heads, dk]
    fresh: jnp.ndarray,  # [batch, s, dk]
    pool_l: jnp.ndarray,  # [total_pages, page_size, dk] — one layer
    block_tables: jnp.ndarray,
    ctx_lens: jnp.ndarray,
    n_valid: jnp.ndarray,
    *,
    dv: int,
    scale: float,
) -> jnp.ndarray:
    """Pure-jnp oracle: gather every table page, one masked softmax over
    [context ++ fresh rows] in float32. Dead table entries are read and
    masked (their values zeroed first, so a NaN there stays out)."""
    b, s, heads, dk = q.shape
    page_size = pool_l.shape[1]
    t = block_tables.shape[1] * page_size
    ctx = pool_l[block_tables].reshape(b, t, dk).astype(jnp.float32)
    ctx_ok = jnp.arange(t)[None, :] < ctx_lens[:, None]  # [b, t]
    ctx = jnp.where(ctx_ok[..., None], ctx, 0.0)
    keys = jnp.concatenate([ctx, fresh.astype(jnp.float32)], axis=1)
    qi = jnp.arange(s)
    fresh_ok = (qi[None, :, None] >= qi[None, None, :]) & (
        qi[None, None, :] < n_valid[:, None, None]
    )  # [b, s, s]
    mask = jnp.concatenate(
        [jnp.broadcast_to(ctx_ok[:, None, :], (b, s, t)), fresh_ok], axis=2
    ) & (qi[None, :, None] < n_valid[:, None, None])
    scores = jnp.einsum("bqhd,bkd->bhqk", q.astype(jnp.float32), keys) * scale
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # padded rows -> zeros
    out = jnp.einsum("bhqk,bkd->bqhd", probs, keys[..., :dv])
    return out.astype(q.dtype)
