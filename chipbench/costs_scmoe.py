"""Operations and bytes of a shortcut-connected sparse decoder with latent
attention (LongCat-Flash's double layer: two latent attentions with a
low-rank query path, two dense FFNs and one routed FFN a published layer),
of which one process holds a SHARE of the routed experts, computed from
shapes beside ``costs.py`` and ``costs_mla.py`` (which no later PR edits).
``cfg`` is the program's ``LlamaConfig`` (or anything with the same
fields): only sizes are read.

Nothing here guesses a count: the experts a decode step reads are an
argument (what the program counted on the device,
``step_stats["experts_touched"]``), never an expectation over a random
router's draws; a step's cost without it is not defined.
"""

from __future__ import annotations

from chipbench import costs, costs_mla


def attention_params(cfg) -> int:
    """One latent attention: the query's pair (down, up), the key/value
    down-projection with the shared rope key, its up-projection, the
    output projection (90.57 M at the published widths)."""
    d, heads = cfg.hidden_size, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return (d * cfg.q_lora_rank + cfg.q_lora_rank * heads * qk
            + d * costs_mla.row_values(cfg)
            + cfg.kv_lora_rank * heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + heads * cfg.v_head_dim * d)


def dense_ffn_params(cfg) -> int:
    return 3 * cfg.hidden_size * cfg.intermediate_size


def expert_params(cfg) -> int:
    """One routed expert's SwiGLU (37.75 M)."""
    return 3 * cfg.hidden_size * cfg.moe_inter


def router_params(cfg) -> int:
    """The classifier over every output it scores (routed and zero experts)
    and the correction bias."""
    outputs = cfg.n_experts + cfg.n_zero_experts
    return cfg.hidden_size * outputs + outputs


def layer_params(cfg, experts: float) -> float:
    """One published layer with ``experts`` routed experts read or held:
    two attentions, two dense FFNs, the router, the experts."""
    return (2 * attention_params(cfg) + 2 * dense_ffn_params(cfg)
            + router_params(cfg) + experts * expert_params(cfg))


def resident_weight_bytes(cfg) -> int:
    """Embedding, head and every layer with the experts this process holds,
    in the served dtype; norm vectors left out."""
    params = (2 * costs.head_params(cfg)
              + cfg.n_layers * layer_params(cfg, cfg.experts_held))
    return int(costs.itemsize(cfg) * params)


def latent_bytes_per_token(cfg) -> int:
    """One token's rows in every attention, as held (two attentions a
    published layer: 8 x 1280 in the cell): what ``/stats``'
    ``kv_bytes_per_token`` must read."""
    return 2 * cfg.n_layers * costs_mla.latent_bytes_per_token_per_layer(cfg)


def decode_step_min_bytes(cfg, lanes: float, ctx_tokens: float,
                          experts_touched: float) -> float:
    """The least a decode step must read from HBM: every layer's two
    attentions, two dense FFNs and router, ``experts_touched`` held experts
    a routed layer (counted, not expected), the head, one embedding row a
    lane, and ``ctx_tokens`` latent rows in every attention (the live
    lanes' contexts, summed: ``step_stats["attn_ctx_tokens"]`` a forward)."""
    params = (cfg.n_layers * layer_params(cfg, experts_touched)
              + costs.head_params(cfg) + lanes * cfg.hidden_size)
    return (costs.itemsize(cfg) * params
            + ctx_tokens * latent_bytes_per_token(cfg))


def decode_step_flops(cfg, lanes: float, ctx_tokens: float,
                      held_rows: float) -> float:
    """Matmul FLOPs of the same step: 2 a weight a lane for the attentions'
    projections, the dense FFNs, the router and the head; 2 a weight for
    each of the ``held_rows`` rows a routed layer's grouped matmuls compute
    (counted: ``step_stats["held_places"]`` a layer a forward; a zero
    expert's place is a multiply-add of ``hidden`` values, left out); every
    head's score against a latent row and its sum over the latent, an
    attention."""
    per_lane = (cfg.n_layers * (2 * attention_params(cfg)
                                + 2 * dense_ffn_params(cfg)
                                + cfg.hidden_size * (cfg.n_experts + cfg.n_zero_experts))
                + costs.head_params(cfg))
    attn = (2 * cfg.n_layers * ctx_tokens * 2 * cfg.n_heads
            * (costs_mla.row_values(cfg) + cfg.kv_lora_rank))
    return (2 * lanes * per_lane
            + 2 * cfg.n_layers * held_rows * expert_params(cfg) + attn)


def decode_step_min_s(cfg, peaks: dict, lanes: float, ctx_tokens: float,
                      experts_touched: float, held_rows: float) -> float:
    """The least time of the step on a chip with ``peaks``: the larger of
    its bytes over the HBM bandwidth and its FLOPs over the bf16 peak (at 64
    lanes the bytes: 10 GB against a third of a TFLOP)."""
    return max(
        decode_step_min_bytes(cfg, lanes, ctx_tokens, experts_touched)
        / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lanes, ctx_tokens, held_rows)
        / peaks["bf16_flops_per_s"],
    )
