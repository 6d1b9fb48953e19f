"""Sharding rules: Megatron-style TP layout expressed as PartitionSpecs.

GSPMD does the collective insertion; these specs only say where tensors
live. Layout per transformer block (scaling-book recipe):

- ``wq/wk/wv``           column-parallel  → shard output dim on ``tp``
- ``wo``                 row-parallel     → shard input dim on ``tp``
  (XLA emits the reduce-scatter/all-reduce after the contraction)
- ``w_gate/w_up``        column-parallel
- ``w_down``             row-parallel
- norms/biases           replicated (biases of column-parallel layers are
  sharded with their outputs)
- ``embed``/``lm_head``  shard the vocab/output dim
- KV pages               shard ``n_kv_heads`` on ``tp`` (head-parallel
  cache; requires n_kv_heads % tp == 0)

Batch dims shard on ``dp``.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.llama import LlamaConfig, Params


def _layer_specs(
    cfg: LlamaConfig, tp: int = 1, routed: bool = True, conv: bool = False,
    sliding: bool = False, linear: bool = False,
) -> dict[str, P]:
    """``routed``: the layer's FFN is the routed one (False for the leading
    dense layers of a model with ``first_k_dense``). ``conv``: its operator
    is a gated short convolution (replicated: the engine refuses tp > 1 for
    a model with such layers). ``sliding``: it attends over a window (the
    leaf ``window``; the engine refuses tp > 1 for such a model too).
    ``linear``: its mixer is a delta-rule linear attention (replicated: tp >
    1 is refused for such a model)."""
    specs = {
        "attn_norm": P(),
        "wq": P(None, "tp"),
        "wk": P(None, "tp"),
        "wv": P(None, "tp"),
        "wo": P("tp", None),
        "mlp_norm": P(),
        "w_gate": P(None, "tp"),
        "w_up": P(None, "tp"),
        "w_down": P("tp", None),
    }
    if cfg.kv_lora_rank:
        # Latent attention: the down-projection and its norm are shared by
        # every head (replicated); the up-projection is column-parallel
        # over heads. (The engine refuses tp > 1 for a latent pool.)
        del specs["wk"], specs["wv"]
        specs.update(wkv_a=P(), kv_norm=P(), wkv_b=P(None, "tp"))
        if cfg.q_lora_rank:  # the low-rank query pair instead of ``wq``
            del specs["wq"]
            specs.update(wq_a=P(), q_a_norm=P(), wq_b=P(None, "tp"))
    if cfg.n_experts and routed:
        # MoE FFN: expert-parallel when the expert count divides the tp
        # axis (each device holds E/tp whole experts; the combine's
        # contraction over E becomes a psum over ICI), else fall back to
        # Megatron-style sharding of the expert-intermediate dim.
        specs["router"] = P()
        if cfg.n_experts % tp == 0:
            specs["w_gate"] = P("tp", None, None)
            specs["w_up"] = P("tp", None, None)
            specs["w_down"] = P("tp", None, None)
        else:
            specs["w_gate"] = P(None, None, "tp")
            specs["w_up"] = P(None, None, "tp")
            specs["w_down"] = P(None, "tp", None)
        if cfg.moe_scoring == "sigmoid" or cfg.moe_router_bias:
            specs["router_bias"] = P()
        if cfg.n_shared_experts:
            specs.update(
                ws_gate=P(None, "tp"), ws_up=P(None, "tp"), ws_down=P("tp", None)
            )
    if cfg.qkv_bias:
        specs["bq"] = P("tp")
        specs["bk"] = P("tp")
        specs["bv"] = P("tp")
    if cfg.qk_norm:
        # Per-head-dim scale, identical across heads → replicated.
        specs["q_norm"] = P()
        specs["k_norm"] = P()
    if cfg.attn_output_gate:
        specs["wg"] = P(None, "tp")  # column-parallel over heads, as ``wq``
    if cfg.sandwich_norm:
        specs.update(attn_post_norm=P(), mlp_post_norm=P())
    if sliding:
        specs["window"] = P()
    if cfg.router_before_attention and "router" in specs:
        specs["preroute"] = P()  # the leaf that says where the router reads
    if conv:
        for name in (
            "wq", "wk", "wv", "wo", "bq", "bk", "bv", "q_norm", "k_norm", "wg",
        ):
            specs.pop(name, None)
        specs.update(conv_in=P(), conv_w=P(), conv_out=P())
    if linear:
        for name in (
            "wq", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
            "wk", "wv", "bq", "bk", "bv", "q_norm", "k_norm", "wg",
        ):
            specs.pop(name, None)
        names = ["kda_qkv", "kda_conv_w", "kda_dt_bias", "kda_A_log", "kda_wb",
                 "kda_o_norm", "wo"]
        # the decay's projection and the output gate: each the full matrix
        # or the low-rank pair, as ``init_params`` makes them
        for name, low_rank in (("kda_wf", cfg.kda_lora),
                               ("kda_wg", cfg.kda_channel_gate)):
            names += [name + "_down", name + "_up"] if low_rank else [name]
        specs.update(dict.fromkeys(names, P()))
    return specs


def _double_layer_specs(cfg: LlamaConfig, tp: int) -> dict[str, Any]:
    """A published double layer: two dense halves and, under ``moe``, the
    routed FFN's own parameters (``init_params``' tree)."""
    half = _layer_specs(cfg, tp, routed=False)
    routed = _layer_specs(cfg, tp, routed=True)
    return {
        **half, "second": dict(half),
        "moe": {k: v for k, v in routed.items() if k not in half
                or k in ("w_gate", "w_up", "w_down")},
    }


def param_specs(cfg: LlamaConfig, tp: int = 1) -> dict[str, Any]:
    """PartitionSpec pytree matching ``init_params``' structure."""
    specs: dict[str, Any] = {
        "embed": P("tp", None),  # vocab-sharded; gather rides ICI
        "final_norm": P(),
        "layers": [
            _double_layer_specs(cfg, tp) if cfg.double_layer
            else _layer_specs(
                cfg, tp, routed=i >= cfg.first_k_dense,
                conv=cfg.layer_kind(i) == "conv",
                sliding=cfg.layer_kind(i) == "sliding",
                linear=cfg.layer_kind(i) == "linear",
            )
            for i in range(cfg.n_layers)
        ],
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def param_shardings(mesh: Mesh, cfg: LlamaConfig, params: Params | None = None):
    """NamedSharding pytree for ``params``.

    When ``params`` is given and contains int8-quantized weights
    (``models/quant.QuantizedTensor``), each one gets a matching pair of
    shardings: the int8 payload follows the weight's spec; its scale
    (shape ``[..., 1, out]``) follows the same spec with the contraction
    axis (size 1 — unpartitionable) replicated.
    """
    specs = param_specs(cfg, tp=mesh.shape.get("tp", 1))
    if params is None:
        return jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )
    from ..models.quant import QuantizedTensor

    def to_sharding(spec: P, p):
        if isinstance(p, QuantizedTensor):
            entries = list(spec) + [None] * (p.ndim - len(spec))
            scale_entries = list(entries)
            scale_entries[-2] = None
            return QuantizedTensor(
                q=NamedSharding(mesh, P(*entries)),
                scale=NamedSharding(mesh, P(*scale_entries)),
            )
        return NamedSharding(mesh, spec)

    return jax.tree.map(
        to_sharding,
        specs,
        params,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params: Params, mesh: Mesh, cfg: LlamaConfig) -> Params:
    """Place a (host or single-device) param pytree onto the mesh."""
    return jax.device_put(params, param_shardings(mesh, cfg, params))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Token batches: shard the leading batch dim on dp, replicate across tp."""
    return NamedSharding(mesh, P("dp"))


def kv_pages_sharding(mesh: Mesh) -> NamedSharding:
    """KV pools [n_layers, pages, page_size, n_kv_heads, hd]: head-parallel."""
    return NamedSharding(mesh, P(None, None, None, "tp"))
