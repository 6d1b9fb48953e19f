"""Grouped (ragged) matmul for MoE routed dispatch on the MXU.

The routed MoE pipeline (``models/llama._moe_mlp_routed``) sorts the
``n*k`` (token, slot) rows by expert so each expert's rows form one
contiguous segment, then needs ``out[r] = lhs[r] @ rhs[g(r)]`` where
``g(r)`` is the expert owning row ``r``. ``jax.lax.ragged_dot`` expresses
this but ran far below MXU utilization at our shapes in rounds 3–4 (on
today's chip: not measured), and XLA does not fuse int8 dequantization
into its group-streamed operand.

Two kernels, one wrapper:

- **bf16/f32**: the Pallas megablox ``gmm``
  (``jax.experimental.pallas.ops.tpu.megablox`` — tiled MXU grouped
  matmul; boundary tiles are visited once per intersecting group with
  masked stores, so there is no capacity padding and no dropped tokens).
- **int8 experts** (``QuantizedTensor`` rhs): our own kernel below, same
  tiling scheme, with the two int8-specific pieces megablox rejects:
  the int8 payload tile is DMA'd at half the HBM bytes and converted to
  f32 IN VMEM right before the MXU dot (the fusion ``ragged_dot``
  can't do), and the per-output-channel scale — constant along the
  contraction axis, so it commutes out of the dot — is applied as a
  per-row gathered multiply on the output, where XLA fuses it into the
  consuming elementwise ops.

No reference counterpart: the reference delegates model execution to
vLLM; this is in-tree TPU serving work (SURVEY §7 stage 4-5).
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.quant import QuantizedTensor
from ._mosaic import require_tpu_unless_interpret

# -- the tiles, chosen for the TPU v5e from what a call can see -----------
#
# Both kernels walk (row tile, group) pairs: a visit multiplies the WHOLE
# ``tm``-row tile by one expert's ``[tk, tn]`` tiles and stores that group's
# rows alone, and there are ``row tiles + touched groups - 1`` visits
# whatever ``tm`` is. So ``tm`` sets a visit's FLOPs and ``(tk, tn)`` the
# grid steps its matrix is streamed in. Measured on one v5e chip at the
# seven sparse cells' shapes (PERF.md section 6, PR 49).

#: Rows of a v5e MXU (128 x 128). A taller row tile buys no utilisation
#: there, it only multiplies the rows a visit masks away: a decode call has a
#: handful of real rows an expert.
MXU_ROWS = 128

#: What a visit's tiles may take of the 16 MiB a Pallas call's scoped VMEM is
#: on a v5e unless the call asks for more (megablox's ``gmm`` cannot). The
#: compiler's own count is ``tile_bytes`` plus up to 0.3 MiB.
VMEM_TILE_BUDGET = 15 * 2**20

#: The widest dimension that may run as one unaligned full-width tile.
UNALIGNED_DIM_MAX = 1024


def _tile_choices(dim: int) -> list[int]:
    """The lane-aligned tiles that divide ``dim`` exactly (the kernels skip
    remainder-tile masking). A dim with no such divisor runs as ONE
    full-width tile — fine for small (tiny-test) geometries, but a LARGE
    unaligned dim would silently blow VMEM with no pointer at the cause, so
    that case fails loudly instead."""
    if dim % 128 == 0:
        return [t for t in range(128, dim + 1, 128) if dim % t == 0]
    if dim > UNALIGNED_DIM_MAX:
        raise ValueError(
            f"gmm kernel tiling: dim {dim} is not 128-aligned and exceeds "
            f"{UNALIGNED_DIM_MAX} (a full-width tile would exhaust VMEM); "
            "use moe_gmm='xla' (ragged_dot) for this geometry"
        )
    return [dim]


def tile_bytes(
    tm: int, tk: int, tn: int, lhs_itemsize: int, rhs_itemsize: int
) -> int:
    """VMEM a visit holds: the weight tile and the ``lhs`` tile double
    buffered, the float32 output tile double buffered and the float32
    accumulator (the int8 kernel's conversion to float32 is not held: the
    compiler counts the same for it)."""
    return (
        2 * tk * tn * rhs_itemsize
        + 2 * tm * tk * lhs_itemsize
        + 3 * tm * tn * 4
    )


def gmm_tiling(
    rows: int, d: int, f: int, lhs_itemsize: int, rhs_itemsize: int
) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` of a ``[rows, d] x [E, d, f]`` call on a TPU v5e.

    ``tm``: the rows cut evenly into the fewest tiles no taller than the
    MXU, in whole bf16 sublane packs of 16 (192 rows are two tiles of 96 and
    no padding). ``tk``: the whole contraction wherever a 128-wide tile of
    it fits, so a visit has no accumulation steps and consecutive visits of
    one group find its tile where it is; ``tn``: the widest that then fits
    ``VMEM_TILE_BUDGET``. One whole expert matrix a visit where that fits
    (``[2048, 768]``, ``[2560, 768]``), ``[2048, 896]`` of ``[2048, 1792]``,
    ``[3072, 1024]`` of ``[3072, 3072]``, ``[6144, 256]`` of ``[6144, 2048]``."""
    row_tiles = -(-rows // MXU_ROWS)
    tm = -(-rows // (16 * row_tiles)) * 16
    fits = [
        (tk, tn)
        for tk in _tile_choices(d)
        for tn in _tile_choices(f)
        if tile_bytes(tm, tk, tn, lhs_itemsize, rhs_itemsize)
        <= VMEM_TILE_BUDGET
    ]
    tk, tn = max(fits)
    return tm, tk, tn


def grouped_matmul(
    lhs: jnp.ndarray,  # [rows, d] group-sorted (expert-contiguous) rows
    rhs: Union[jnp.ndarray, QuantizedTensor],  # [E, d, f] expert stack
    group_sizes: jnp.ndarray,  # [E] int32 rows per expert
    *,
    row_group_ids: Optional[jnp.ndarray] = None,  # [rows] expert of row
    interpret: bool = False,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """``out[r] = lhs[r] @ rhs[g(r)]`` over expert-contiguous rows.

    With a ``QuantizedTensor`` rhs, ``row_group_ids`` (the sorted expert id
    per row — the caller already has it) is required to apply the
    per-output-channel scales to the output rows.

    ``use_kernel=False`` falls back to ``jax.lax.ragged_dot`` with
    whole-stack dequantization — the parity oracle for tests.

    ``group_sizes`` may sum to FEWER than ``rows``: the rows past the last
    group belong to no expert (a prefill dispatch's padding,
    ``llama._moe_mlp_routed(valid=)``). Both kernels walk (row tile, group)
    pairs of the groups alone, so the tiles that hold only such rows are
    never visited; what the output holds there is UNDEFINED on the kernel
    path (megablox leaves it as allocated; ``ragged_dot`` writes zeros) and
    the caller selects it away, never multiplies it. ``row_group_ids`` of
    such rows must still index ``rhs`` (the caller clips them).
    """
    quantized = isinstance(rhs, QuantizedTensor)
    if quantized and row_group_ids is None:
        raise ValueError("row_group_ids required for quantized rhs")
    if not use_kernel:
        if quantized:  # whole-stack dequantization
            rhs = rhs.q.astype(lhs.dtype) * rhs.scale.astype(lhs.dtype)
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    require_tpu_unless_interpret("grouped_matmul", interpret)
    if quantized:
        # rhs.q [E, d, f] int8, rhs.scale [E, 1, f] f32
        out = _gmm_int8(lhs, rhs.q, group_sizes, interpret=interpret)  # f32
        # Per-row scale: scale[g(r), 0, :] — fuses downstream.
        row_scale = rhs.scale[row_group_ids, 0, :]  # [rows, f]
        return (out * row_scale).astype(lhs.dtype)
    return _gmm_library(lhs, rhs, group_sizes, interpret=interpret)


def _gmm_library(lhs, rhs, group_sizes, *, interpret: bool):
    from jax.experimental.pallas.ops.tpu.megablox import gmm as mb_gmm

    rows, d = lhs.shape
    tm, tk, tn = gmm_tiling(
        rows, d, rhs.shape[2], lhs.dtype.itemsize, rhs.dtype.itemsize
    )
    # megablox requires m % tm == 0: pad rows (beyond every group — the
    # pad region's output is garbage and sliced off).
    pad = (-rows) % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = mb_gmm(
        lhs,
        rhs,
        group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32,
        tiling=(tm, tk, tn),
        interpret=interpret,
    )
    return out[:rows].astype(lhs.dtype)


# -- int8-rhs grouped matmul kernel --------------------------------------
#
# Same scheme as megablox gmm: grid (tiles_n, active_m_tiles, tiles_k)
# where the middle dimension walks (m-tile, group) intersections in row
# order — a boundary m-tile spanning G groups is visited G times, each
# visit computing the full tile on the MXU but storing only its own
# group's rows. Group metadata (which group / which m-tile per grid step)
# comes from the library's make_group_metadata; lhs rows are pre-padded to
# a tile multiple and the pad region (beyond every group) is sliced off.


def _int8_gmm_kernel(
    group_metadata, lhs_ref, q_ref, out_ref, acc_ref, *, tiles_k, tm, tn
):
    group_offsets, group_ids, m_tile_ids = group_metadata
    grid_id = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 tile -> f32 happens HERE, in VMEM: HBM only ever streams the
    # 1-byte payload.
    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...].astype(jnp.float32),
        q_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k_i == tiles_k - 1)
    def _store():
        # Store only this visit's group rows; preserve rows written by the
        # other groups sharing this m-tile (visited at adjacent grid ids).
        group_id = group_ids[grid_id]
        start = group_offsets[group_id]
        end = group_offsets[group_id + 1]
        row = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + (
            m_tile_ids[grid_id] * tm
        )
        mask = (row >= start) & (row < end)
        out_ref[...] = jax.lax.select(mask, acc_ref[...], out_ref[...])


def _gmm_int8(lhs, q, group_sizes, *, interpret: bool):
    """Grouped matmul with an int8 expert stack; returns f32 [rows, f].

    Scales are NOT applied here — per-output-channel scales commute out of
    the contraction and are cheaper as a fused elementwise on the output.
    """
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    rows, d = lhs.shape
    n_groups, _, f = q.shape
    tm, tk, tn = gmm_tiling(rows, d, f, lhs.dtype.itemsize, q.dtype.itemsize)
    tiles_k = d // tk
    tiles_n = f // tn

    pad = (-rows) % tm
    m = rows + pad
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))

    group_metadata, num_active_tiles = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32),
        m=m,
        tm=tm,
        start_group=jnp.asarray(0, jnp.int32),
        num_nonzero_groups=n_groups,
        visit_empty_groups=False,
    )

    def lhs_index(n_i, grid_id, k_i, meta):
        _, _, m_tile_ids = meta
        del n_i
        return m_tile_ids[grid_id], k_i

    def q_index(n_i, grid_id, k_i, meta):
        _, group_ids, _ = meta
        return group_ids[grid_id], k_i, n_i

    def out_index(n_i, grid_id, k_i, meta):
        _, _, m_tile_ids = meta
        del k_i
        return m_tile_ids[grid_id], n_i

    flops = 2 * m * d * f
    bytes_accessed = (
        lhs.size * lhs.itemsize * tiles_n + d * f * q.itemsize + m * f * 4
    )
    out = pl.pallas_call(
        functools.partial(_int8_gmm_kernel, tiles_k=tiles_k, tm=tm, tn=tn),
        out_shape=jax.ShapeDtypeStruct((m, f), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_index),
                pl.BlockSpec((None, tk, tn), q_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, num_active_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=flops, bytes_accessed=bytes_accessed, transcendentals=0
        ),
        interpret=interpret,
    )(group_metadata, lhs, q)
    return out[:rows]
