"""Ring attention: sequence-parallel causal attention for long-context prefill.

The reference never runs a model, so sequence scaling has no analogue there
(SURVEY §5); in this framework long context is first-class and the engine's
single-chip ceiling is ``max_model_len``. Ring attention removes it: the
sequence is sharded over a mesh axis (``sp``), every device computes flash
attention for its query shard while K/V shards rotate around the ring via
``jax.lax.ppermute`` — ICI-neighbor traffic only, no all-gather, and peak
memory O(seq/n · block) per chip.

The math is the blockwise online-softmax merge (same accumulator discipline
as ``ops.attention._flash_over_keys``): each ring step contributes a partial
(max, sum, acc) that is merged exactly, so the result is bit-consistent with
single-device flash attention up to float-associativity.

Layout notes (TPU-first):
- Q/K/V stay ``[b, s/n, heads, d]`` per shard; each ring step runs a
  BLOCKED flash scan over the held payload, so score tiles stay
  ``[s/n, key_block]`` regardless of payload length.
- The rotation count is static (mesh size), so the whole ring unrolls inside
  one jit: XLA overlaps each step's ppermute with the previous step's
  compute (double-buffered collective-permute).
- Causality is enforced with absolute positions: shard *i* holds positions
  ``i·s/n … (i+1)·s/n − 1``; a whole ring step whose K shard lies entirely
  in the query shard's future contributes nothing and its FLOPs are skipped
  by masking (the lax.scan stays shape-static as XLA requires).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _ring_body(carry, _, *, axis_name, qf, q_pos, scale, n_shards):
    """One ring step: attend my query shard to the K/V payload currently
    held — as a BLOCKED flash scan (``ops.attention._flash_over_keys``
    seeded with the carried accumulators), so the score tile stays
    [s_q, key_block] however long the rotating payload is (the payload
    carries context slices in the sp-prefill path; an unblocked
    [s_q, s_k] tile would grow linearly with context and OOM exactly in
    the long-context regime this path exists for) — then pass the
    payload to the next device on the ring."""
    from ..ops.attention import FLASH_KEY_BLOCK, _flash_over_keys

    k_cur, v_cur, kpos_cur, kvalid_cur, m, denom, acc = carry

    m_new, l_new, acc_new = _flash_over_keys(
        qf,
        jnp.moveaxis(k_cur, 1, 2),  # [b, s_k, n_kv, d] -> [b, n_kv, s_k, d]
        jnp.moveaxis(v_cur, 1, 2),
        kvalid_cur,
        kpos_cur,
        q_pos,
        scale,
        FLASH_KEY_BLOCK,
        return_accumulators=True,
        init_state=(m, denom, acc),
    )

    # Rotate K/V/pos/validity to the next device; neighbor-only ICI traffic.
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
    v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
    kpos_nxt = jax.lax.ppermute(kpos_cur, axis_name, perm)
    kvalid_nxt = jax.lax.ppermute(kvalid_cur, axis_name, perm)
    return (k_nxt, v_nxt, kpos_nxt, kvalid_nxt, m_new, l_new, acc_new), None


def ring_attention_shard(
    q: jnp.ndarray,  # [b, s_shard, n_heads, d]
    k: jnp.ndarray,  # [b, s_k_shard, n_kv_heads, d]
    v: jnp.ndarray,  # [b, s_k_shard, n_kv_heads, d]
    *,
    axis_name: str = "sp",
    scale: Optional[float] = None,
    q_pos: Optional[jnp.ndarray] = None,  # [b, s_shard] absolute positions
    k_pos: Optional[jnp.ndarray] = None,  # [b, s_k_shard] key positions
    k_valid: Optional[jnp.ndarray] = None,  # [b, s_k_shard] key padding mask
    init_state: Optional[tuple] = None,  # (m, l, acc) seed for the flash state
) -> jnp.ndarray:
    """Per-shard ring attention body. Must run inside ``shard_map`` (or pmap)
    over ``axis_name``; q (and k/v) are this device's sequence shard.

    Defaults reproduce plain causal self-attention over the global
    sequence (positions derived from the shard index). The engine's
    sp-prefill passes a LONGER rotating key payload than the query shard
    ([context slice ++ chunk slice], so ``k_pos``/``k_valid`` are
    decoupled from ``q_pos``): context keys ride at position -1 (visible
    to every chunk query), chunk keys at absolute positions, padding
    masked — the ring merge is exact, so the result equals a
    single-device online softmax over [context ++ chunk].
    """
    b, s, n_q, d = q.shape
    s_k = k.shape[1]
    n_kv = k.shape[2]
    group = n_q // n_kv
    if scale is None:
        scale = d**-0.5
    n_shards = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)

    if q_pos is None:
        q_pos = (my * s + jnp.arange(s))[None, :].astype(jnp.int32)
        q_pos = jnp.broadcast_to(q_pos, (b, s))
    if k_pos is None:
        if s_k != s:
            raise ValueError("k_pos required when k length differs from q")
        k_pos = q_pos  # at step 0 each device holds its own K shard
    if k_valid is None:
        k_valid = jnp.ones((b, s_k), bool)

    qf = q.astype(jnp.float32).reshape(b, s, n_kv, group, d)
    if init_state is None:
        m0 = jnp.full((b, n_kv, group, s), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n_kv, group, s), jnp.float32)
        acc0 = jnp.zeros((b, n_kv, group, s, d), jnp.float32)
    else:
        m0, l0, acc0 = init_state

    body = partial(
        _ring_body,
        axis_name=axis_name,
        qf=qf,
        q_pos=q_pos,
        scale=scale,
        n_shards=n_shards,
    )
    (_, _, _, _, m, denom, acc), _ = jax.lax.scan(
        body, (k, v, k_pos, k_valid, m0, l0, acc0), None, length=n_shards
    )

    out = acc / jnp.where(denom > 0, denom, 1.0)[..., None]
    # A query with no visible keys cannot happen here (it always sees
    # itself), so no NaN guard is needed beyond the denom>0 clamp.
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, n_q, d).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [b, seq, n_heads, d] — seq divisible by mesh axis size
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Sequence-parallel causal attention over ``mesh[axis_name]``.

    Shards the sequence dimension, runs the ring under ``shard_map``, and
    returns the output with the same (sequence-sharded) layout. Jit-able and
    composable with tp sharding on the head dimension of the surrounding
    projections.
    """
    n = mesh.shape[axis_name]
    if q.shape[1] % n != 0:
        raise ValueError(
            f"seq len {q.shape[1]} not divisible by mesh axis "
            f"{axis_name!r} of size {n}"
        )
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        partial(ring_attention_shard, axis_name=axis_name, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
