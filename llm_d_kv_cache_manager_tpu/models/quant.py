"""Weight-only int8 quantization for serving.

Why: a bf16 8B-parameter checkpoint is ~16 GB — the whole HBM of a v5e
chip, leaving nothing for the KV page pool. Symmetric per-output-channel
int8 halves weight bytes (8B fits with room for KV) and halves the HBM
weight traffic that dominates decode, where every matmul is
memory-bound. XLA fuses the dequant (convert + broadcast multiply) into
the dot's operand read on TPU, so no full-size bf16 copy of a weight is
ever resident.

Scheme: for every matmul weight laid out ``[..., in, out]`` (all of this
model family's weights — see ``llama.init_params``), the scale is the
per-output-channel symmetric max over the contraction axis::

    scale = max(|w|, axis=-2, keepdims=True) / 127     # [..., 1, out]
    q     = round(w / scale)  in int8

Dequant is exact in the scale and bounded by scale/2 per element. Norms,
biases, and the MoE router (tiny, and routing decisions are precision
sensitive) stay in the model dtype; the embedding is quantizable but off
by default (gather + lm-head sharing makes its error budget tighter).

No reference counterpart: the reference (llm-d-kv-cache-manager)
delegates model execution to vLLM; this is part of the in-tree TPU
serving stack.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

#: Parameter names eligible for quantization (matmul weights only).
QUANTIZABLE = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
     "conv_in", "conv_out", "wg"}
)


@jax.tree_util.register_pytree_node_class
@dataclass
class QuantizedTensor:
    """An int8 weight + its per-output-channel f32 scale, as one pytree
    node so quantized params flow through jit/device_put/checkpointing
    like any other leaf pair."""

    q: Any  # int8, original weight shape [..., in, out]
    scale: Any  # f32, [..., 1, out]

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim


def quantize_tensor(w: jnp.ndarray) -> QuantizedTensor:
    """Symmetric per-output-channel int8 quantization over axis -2."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q=q, scale=scale)


def materialize(p: Any, dtype: Any) -> jnp.ndarray:
    """Dequantize (or pass through) a weight for use in a matmul.

    Inside jit this is convert+multiply, which XLA fuses into the
    consuming dot's operand stream — int8 bytes are what cross HBM.
    """
    if isinstance(p, QuantizedTensor):
        return p.q.astype(dtype) * p.scale.astype(dtype)
    return p


def quantize_params(
    params: Any, *, quantize_embed: bool = False, quantize_experts: bool = False
) -> Any:
    """Return the param tree with every eligible matmul weight replaced
    by a :class:`QuantizedTensor`. Leaves everything else untouched.

    MoE expert stacks (3-D ``[E, in, out]`` weights) are SKIPPED by
    default (conservative — expert numerics are routing-sensitive). With
    ``quantize_experts=True`` they run through the Pallas grouped-matmul
    kernel's in-VMEM dequant while halving expert HBM (``ragged_dot``
    cannot fuse the dequant; ``moe_gmm="auto"`` selects the kernel
    whenever the engine is not interpreting).
    """

    def convert(d: dict) -> dict:
        out = {}
        for name, v in d.items():
            if name == "layers":
                out[name] = [convert(layer) for layer in v]
            elif isinstance(v, dict):  # a double layer's ``moe`` / ``second``
                out[name] = convert(v)
            elif name in QUANTIZABLE and (
                getattr(v, "ndim", 2) == 2 or quantize_experts
            ):
                out[name] = quantize_tensor(v)
            elif name == "embed" and quantize_embed:
                out[name] = quantize_tensor(v)
            else:
                out[name] = v
        return out

    return convert(params)


# -- paged-KV quantization (host-DRAM tier + transfer wire) -----------------
#
# Symmetric per-page-per-head int8 for KV page slices of shape
# ``[n_layers, page_size, n_kv_heads, head_dim]``. One scale per
# (layer, kv_head) per page — coarse enough that scales are noise on the
# wire (n_layers * n_kv_heads f32 vs page_size * head_dim int8 payload),
# fine enough that an outlier head cannot poison the whole page's
# resolution. Deliberately numpy, not jax: both call sites (host-tier
# spill/restore and the transfer wire) already live on the host side of
# the batched-mover fence, so quantizing there adds zero device work and
# the Pallas paged-attention path never sees an int8 page.

#: modes accepted by the ``KV_QUANT`` knob
KV_QUANT_MODES = ("int8",)

#: modes accepted by the ``KV_QUANT_HBM`` knob (ISSUE 16). ``float8_e4m3``
#: is the declared follow-on storage mode: recognized here so the knob
#: surface is stable, but rejected with NotImplementedError at engine
#: init until the kernel grows an fp8 dequant path.
KV_QUANT_HBM_MODES = ("int8", "float8_e4m3")


def kv_scale_shape(page_shape: tuple[int, ...]) -> tuple[int, ...]:
    """Scale array shape for one quantized KV page slice."""
    n_layers, _, n_kv_heads, _ = page_shape
    return (n_layers, 1, n_kv_heads, 1)


def kv_hbm_scale_shape(pool_shape: tuple[int, ...]) -> tuple[int, ...]:
    """Scale pool shape for an int8 HBM KV pool
    ``[n_layers, total_pages, page_size, n_kv_heads, head_dim]`` →
    ``[n_layers, total_pages, n_kv_heads]``. One f32 scale per page per
    (layer, kv_head) — the SAME granularity as the host tier's
    :func:`kv_scale_shape`, so a page's codes and scales copy between
    tiers (and onto the PR 6 wire triple) with a reshape, never a
    dequant→requant round trip."""
    n_layers, total_pages, _, n_kv_heads, _ = pool_shape
    return (n_layers, total_pages, n_kv_heads)


def dequantize_kv_pool(
    q: np.ndarray, scales: np.ndarray, dtype: Any
) -> np.ndarray:
    """Full-width view of an int8 HBM pool ``[..., P, ps, n_kv, hd]`` with
    per-page scales ``[..., P, n_kv]`` — the tests' / oracle's view; the
    serving path never materializes this (the kernel dequantizes
    in-register)."""
    q32 = np.asarray(q, np.float32)
    s = np.asarray(scales, np.float32)[..., None, :, None]
    return (q32 * s).astype(dtype)


def quantize_kv_page(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Quantize one KV page slice ``[n_layers, page_size, n_kv_heads, hd]``
    to int8 with per-(layer, kv_head) symmetric f32 scales. Error per
    element is bounded by ``scale / 2``; zeros round-trip exactly."""
    x32 = np.asarray(x, np.float32)
    amax = np.max(np.abs(x32), axis=(1, 3), keepdims=True)
    scale = np.maximum(amax, 1e-8).astype(np.float32) / 127.0
    q = np.clip(np.rint(x32 / scale), -127, 127).astype(np.int8)
    return q, scale


def dequantize_kv_page(
    q: np.ndarray, scale: np.ndarray, dtype: Any
) -> np.ndarray:
    """Inverse of :func:`quantize_kv_page` into ``dtype`` (the engine's KV
    pool dtype — pages re-enter the paged-attention path full-width)."""
    return (q.astype(np.float32) * np.asarray(scale, np.float32)).astype(dtype)


def is_quantized(params: Any) -> bool:
    return any(
        isinstance(leaf, QuantizedTensor)
        for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
        )
    )


def param_bytes(params: Any) -> int:
    """Total bytes of a param tree (counts int8 weights at 1 byte)."""
    return sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params))
