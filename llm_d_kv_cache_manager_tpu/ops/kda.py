"""The delta-rule recurrence of a linear-attention layer whose state is a
matrix a head (Kimi Delta Attention, arXiv:2510.26692): the decode kernel
over a pool of state slots, and the chunked prefill of the same recurrence.

A head keeps ``S [K, V]`` float32. One token, with ``q, k [K]`` (the caller
has normed and scaled them), ``v [V]``, the log decay ``g [K]`` (<= 0, a
channel of the key) and ``beta``:

    S <- diag(exp(g)) S;  u = v - S^T k;  S <- S + beta k u^T;  o = S^T q

- ``kda_decode`` (Pallas): one program a lane over the pool ``[layers,
  slots, H, K, V]`` in place. The lane's state is read from the slot
  ``read_slots[lane]`` and the new state written to ``write_slots[lane]``
  through ``input_output_aliases``: equal for a lane that keeps its slot;
  where they differ the slot read is left as it was, which is how a snapshot
  is taken and how one is restored from (``server/block_manager.py``,
  ``StatePool``: no state is ever copied slot to slot). A lane marked
  ``fresh`` starts from zeros, whatever its slot holds. The per-token
  operands that weigh the state's ROWS (``q``, ``k``, ``beta k``,
  ``exp(g)``) come transposed and side by side, ``[lanes, K, 4 H]``, so that
  a head's column is one lane of a 128-lane tile and broadcasts along the
  state's rows; ``v`` and the output are rows ``[lanes, H, V]``. Everything
  is float32 on the VPU: per head ~130 vector operations over the 16
  registers of its state, against 128 KiB read and written.
- ``kda_decode_reference``: the ``jax.numpy`` oracle (gather, step,
  scatter), the path of ``attn_impl="xla"``-style callers and the tests.
- ``kda_chunked``: the prefill. Chunks of ``CHUNK`` tokens under
  ``lax.scan`` carrying ``S``; inside a chunk the WY form: with ``G`` the
  running sum of ``g`` (a channel), ``E[t, i] = exp(G_t - G_i)`` (``i <= t``:
  never positive, so nothing overflows however strong the decay),
  ``A[t, i] = sum_c k_t E[t, i] k_i`` (``i < t``) and ``P[t, i]`` the same
  with ``q_t`` (``i <= t``):

      (I + A diag(beta)) U = V - (K * exp(G)) S0      (unit lower triangular)
      O = (Q * exp(G)) S0 + P diag(beta) U
      S' = diag(exp(G_C)) S0 + (K * exp(G_C - G) * beta)^T U

  plain matrix products at ``Precision.HIGHEST`` (the state is float32 and a
  rounded product would be a rounded state); a token that is padding has
  ``g = 0`` and ``beta = 0`` and leaves the state as it was. A later
  ``perf_opt`` PR finds it under ``model.kda`` by the name ``kda_chunked``.
- ``kda_recurrent``: the same recurrence token by token (``lax.scan`` over
  ``_step``), what the chunked form is held to in ``tests/test_kda_kernels``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import require_tpu_unless_interpret

#: tokens a chunk of ``kda_chunked``
CHUNK = 64

_VMEM_LIMIT = 64 * 1024 * 1024
_HI = jax.lax.Precision.HIGHEST


def _step(S, q, k, v, g, beta):
    """One token of the recurrence for arrays with any leading axes: ``S
    [..., K, V]``, ``q, k, g [..., K]``, ``v [..., V]``, ``beta [...]``.
    Returns (new state, output ``[..., V]``)."""
    S = S * jnp.exp(g)[..., :, None]
    u = v - jnp.sum(S * k[..., :, None], axis=-2)
    S = S + (beta[..., None] * k)[..., :, None] * u[..., None, :]
    return S, jnp.sum(S * q[..., :, None], axis=-2)


def kda_recurrent(q, k, v, g, beta, S0):
    """Token by token: ``q, k, g [b, s, H, K]``, ``v [b, s, H, V]``, ``beta
    [b, s, H]``, ``S0 [b, H, K, V]``, all float32. Returns (outputs ``[b, s,
    H, V]``, final state)."""
    def one(S, x):
        S, o = _step(S, *x)
        return S, o

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    S, o = jax.lax.scan(one, S0, xs)
    return jnp.moveaxis(o, 0, 1), S


def kda_chunked(q, k, v, g, beta, S0, chunk: int = CHUNK):
    """The recurrence over ``s`` tokens from ``S0`` in chunks (module
    docstring); shapes as ``kda_recurrent``. ``s`` is padded to whole chunks
    with tokens that leave the state alone."""
    with jax.named_scope("kda_chunked"):
        b, s, H, K = q.shape
        pad = -s % chunk
        if pad:
            q, k, v, g = (
                jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                for x in (q, k, v, g)
            )
            beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        n = (s + pad) // chunk

        def chunks(x):  # [b, s, H, ...] -> [n, b, H, chunk, ...]
            x = x.reshape(b, n, chunk, *x.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        eye = jnp.eye(chunk, dtype=jnp.float32)

        def one(S, x):
            qc, kc, vc, gc, bc = x  # [b, H, C, K] ...; bc [b, H, C]
            G = jnp.cumsum(gc, axis=-2)
            # E[t, i, c] = exp(G_t - G_i) where i <= t, else 0
            diff = G[..., :, None, :] - G[..., None, :, :]
            E = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
            kE = kc[..., None, :, :] * E  # [b, H, t, i, K]: k_i E[t, i]
            A = jnp.where(strict, jnp.sum(kc[..., :, None, :] * kE, -1), 0.0)
            P = jnp.sum(qc[..., :, None, :] * kE, -1)  # zero above the diagonal
            eG = jnp.exp(G)
            rhs = vc - jnp.einsum("bhck,bhkv->bhcv", kc * eG, S, precision=_HI)
            U = jax.scipy.linalg.solve_triangular(
                eye + A * bc[..., None, :], rhs, lower=True, unit_diagonal=True
            )
            o = jnp.einsum(
                "bhck,bhkv->bhcv", qc * eG, S, precision=_HI
            ) + jnp.einsum(
                "bhti,bhiv->bhtv", P * bc[..., None, :], U, precision=_HI
            )
            to_end = jnp.exp(G[..., -1:, :] - G) * kc * bc[..., None]
            S = S * eG[..., -1, :, None] + jnp.einsum(
                "bhck,bhcv->bhkv", to_end, U, precision=_HI
            )
            return S, o

        S, o = jax.lax.scan(one, S0, tuple(chunks(x) for x in (q, k, v, g, beta)))
        # [n, b, H, chunk, V] -> [b, s, H, V]
        o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1).reshape(b, n * chunk, H, -1)
        return o[:, :s], S


def _decode_operands(q, k, g, beta):
    """``[lanes, K, 4 H]``: ``q | k | beta k | exp(g)`` transposed."""
    rows = jnp.concatenate([q, k, beta[..., None] * k, jnp.exp(g)], axis=1)
    return jnp.swapaxes(rows, 1, 2)  # [b, 4H, K] -> [b, K, 4H]


def _kda_decode_kernel(
    read_ref, write_ref, fresh_ref, layer_ref,  # scalar prefetch
    cols_ref,  # [K, 4H]
    v_ref,  # [H, V]
    s_ref,  # [H, K, V]: the slot read
    o_ref,  # [H, V]
    s_out_ref,  # [H, K, V]: the slot written
):
    del read_ref, write_ref, layer_ref
    H = v_ref.shape[0]
    fresh = fresh_ref[pl.program_id(0)] != 0
    for h in range(H):
        q = cols_ref[:, h : h + 1]  # [K, 1]
        k = cols_ref[:, H + h : H + h + 1]
        bk = cols_ref[:, 2 * H + h : 2 * H + h + 1]
        decay = cols_ref[:, 3 * H + h : 3 * H + h + 1]
        S = jnp.where(fresh, 0.0, s_ref[h]) * decay
        u = v_ref[h : h + 1, :] - jnp.sum(S * k, axis=0, keepdims=True)
        S = S + bk * u
        s_out_ref[h] = S
        o_ref[h : h + 1, :] = jnp.sum(S * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode(
    pool: jnp.ndarray,  # [layers, slots, H, K, V] float32
    q: jnp.ndarray,  # [lanes, H, K] float32
    k: jnp.ndarray,
    v: jnp.ndarray,  # [lanes, H, V]
    g: jnp.ndarray,  # [lanes, H, K]: log decay
    beta: jnp.ndarray,  # [lanes, H]
    read_slots: jnp.ndarray,  # [lanes] int32
    write_slots: jnp.ndarray,  # [lanes] int32
    fresh: jnp.ndarray,  # [lanes] bool/int32: start from zeros
    layer,  # int32 scalar: the pool's layer
    *,
    interpret: bool = False,
):
    """One token a lane (module docstring). Returns (outputs ``[lanes, H,
    V]`` float32, the pool, updated in place). No two lanes may write one
    slot, and no lane may read a slot another writes (the padded lanes' slot
    0 is written by all of them and read by nobody who cares)."""
    require_tpu_unless_interpret("kda_decode", interpret)
    lanes, H, K = q.shape
    V = v.shape[-1]
    cols = _decode_operands(q, k, g, beta)

    def lane_block(i, *_):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(lanes,),
        in_specs=[
            pl.BlockSpec((None, K, 4 * H), lane_block),
            pl.BlockSpec((None, H, V), lane_block),
            pl.BlockSpec(
                (None, None, H, K, V),
                lambda i, rd, wr, fr, ly: (ly[0], rd[i], 0, 0, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((None, H, V), lane_block),
            pl.BlockSpec(
                (None, None, H, K, V),
                lambda i, rd, wr, fr, ly: (ly[0], wr[i], 0, 0, 0),
            ),
        ],
    )
    out, pool = pl.pallas_call(
        _kda_decode_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((lanes, H, V), jnp.float32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the scalar-prefetch words: the pool is the seventh
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT,
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="kda_decode",
    )(
        read_slots.astype(jnp.int32), write_slots.astype(jnp.int32),
        fresh.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        cols, v, pool,
    )
    return out, pool


def kda_decode_reference(
    pool, q, k, v, g, beta, read_slots, write_slots, fresh, layer
):
    """``kda_decode`` in ``jax.numpy``: gather the lanes' slots, one step,
    scatter (flat slots, one index a (layer, lane), as the convolution
    state's: a layer axis in the window would copy the pool)."""
    L, slots = pool.shape[:2]
    flat = pool.reshape(L * slots, *pool.shape[2:])
    S = flat[layer * slots + read_slots]
    S = jnp.where(fresh.astype(bool)[:, None, None, None], 0.0, S)
    S, o = _step(S, q, k, v, g, beta)
    flat = flat.at[layer * slots + write_slots].set(S)
    return o, flat.reshape(pool.shape)
