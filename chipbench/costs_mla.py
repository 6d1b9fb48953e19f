"""Operations and bytes of a latent-attention (MLA) sparse decoder with
shared experts and leading dense layers, computed from shapes, beside
``costs.py`` (which counts ``2 x layers x n_kv x hd`` bytes a token and per-
head projections, and which no later PR edits). ``cfg`` is the program's
``LlamaConfig`` (or anything with the same fields): only sizes are read.

The cache of such a model holds one row a token a layer, ``kv_lora_rank +
qk_rope_head_dim`` values, held in whole tiles of 128 lanes
(``row_values_held``: 576 in 640). The decode kernel (``mla_decode``) reads
every live lane's rows once a layer a step, whatever the number of heads:
its bound is HBM bandwidth.
"""

from __future__ import annotations

from chipbench import costs

LANE_TILE = 128


def row_values(cfg) -> int:
    """Values of one token's latent row: the normed latent and the rotated
    shared key (576)."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def row_values_held(cfg) -> int:
    """... as the pool holds it: whole 128-lane tiles (640)."""
    return -(-row_values(cfg) // LANE_TILE) * LANE_TILE


def latent_bytes_per_token_per_layer(cfg, held: bool = True) -> int:
    values = row_values_held(cfg) if held else row_values(cfg)
    return values * costs.itemsize(cfg)


def n_attentions(cfg) -> int:
    """The latent attentions of the depth that is run, which is the layer
    axis of the pool: two a published layer for a double layer
    (``longcat-flash-omni``: 8 for 4 layers), the layers that are not
    linear ones where ``layer_types`` names kinds (``ling-3.0-flash``: 1 of
    7), every layer otherwise (``kanana-2-30b-a3b``: 8 of 8)."""
    if getattr(cfg, "double_layer", False):
        return 2 * cfg.n_layers
    kinds = list(getattr(cfg, "layer_types", None) or ())[: cfg.n_layers]
    return cfg.n_layers - kinds.count("linear_attention")


def latent_bytes_per_token(cfg, held: bool = True) -> int:
    """One token's rows in every attention: what ``/stats``'
    ``kv_bytes_per_token`` must read (8 x 1280 in ``docqa`` and in
    ``turns``, 1 x 1280 in ``threads``; 8 x 1152 if the row were held
    unpadded)."""
    return n_attentions(cfg) * latent_bytes_per_token_per_layer(cfg, held)


def attn_params_per_layer(cfg) -> int:
    d, heads = cfg.hidden_size, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return (d * heads * qk + d * row_values(cfg)
            + cfg.kv_lora_rank * heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + heads * cfg.v_head_dim * d)


def shared_params_per_layer(cfg) -> int:
    return 3 * cfg.hidden_size * cfg.n_shared_experts * cfg.moe_inter


def expert_layer_params(cfg, experts: float = None) -> float:
    """One expert layer with ``experts`` routed experts read (default: all
    of them resident), the router, its bias and the shared experts."""
    d = cfg.hidden_size
    e = cfg.n_experts if experts is None else experts
    return (attn_params_per_layer(cfg) + e * 3 * d * cfg.moe_inter
            + d * cfg.n_experts + cfg.n_experts + shared_params_per_layer(cfg))


def dense_layer_params(cfg) -> int:
    return attn_params_per_layer(cfg) + 3 * cfg.hidden_size * cfg.intermediate_size


def resident_weight_bytes(cfg) -> int:
    """Embedding, head and every layer (``first_k_dense`` dense ones, the
    rest expert layers), in the served dtype; norm vectors left out."""
    dense = min(cfg.first_k_dense, cfg.n_layers)
    params = (2 * costs.head_params(cfg) + dense * dense_layer_params(cfg)
              + (cfg.n_layers - dense) * expert_layer_params(cfg))
    return int(costs.itemsize(cfg) * params)


def mla_decode_bytes(cfg, ctx_tokens: float) -> float:
    """What the ``mla_decode`` kernel's calls of ONE decode step (one call
    an attention) must read: ``ctx_tokens`` latent rows (the live lanes'
    contexts, summed: ``step_stats["latent_ctx_tokens"]`` a dispatch) in
    every attention, as held. Queries, the fresh rows and the outputs are
    under a thousandth at the cell's contexts and left out."""
    return ctx_tokens * latent_bytes_per_token(cfg, held=True)


def mla_decode_flops(cfg, ctx_tokens: float) -> float:
    """Matmul FLOPs of the same calls: every head's score against a row's
    ``row_values`` and its sum over the row's ``kv_lora_rank``."""
    per_row = 2 * cfg.n_heads * (row_values(cfg) + cfg.kv_lora_rank)
    return n_attentions(cfg) * ctx_tokens * per_row


def decode_step_min_bytes(cfg, lanes: int, ctx_tokens: float,
                          experts_touched: float) -> float:
    """The least a decode step must read from HBM: every layer's attention
    weights, the dense layers' FFN, ``experts_touched`` routed experts a
    layer (what the program counted: a caller passes a count, never an
    expectation over a router's draws) with the router and the shared
    experts, the head, one embedding row a lane, and the live latent rows
    (a model of single layers that all attend: ``kanana-2-30b-a3b``)."""
    dense = min(cfg.first_k_dense, cfg.n_layers)
    params = (dense * dense_layer_params(cfg)
              + (cfg.n_layers - dense) * expert_layer_params(cfg, experts_touched)
              + costs.head_params(cfg) + lanes * cfg.hidden_size)
    return costs.itemsize(cfg) * params + mla_decode_bytes(cfg, ctx_tokens)
