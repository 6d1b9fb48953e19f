"""Operations and bytes of a sparse decoder with window and full attention
layers in one model (Trinity / afmoe: GQA with an output gate, a dense FFN in
the leading layers, then routed experts beside a shared one), of which one
process holds a SHARE of the routed experts, computed from shapes beside
``costs.py`` (which no later PR edits). ``cfg`` is the program's
``LlamaConfig`` (or anything with the same fields): only sizes are read.

Nothing here guesses a count: the experts a decode step reads and the
positions its layers read of their pools are arguments (what the program
counted: ``step_stats["experts_touched"]``, ``attn_ctx_tokens`` for a full
layer, ``window_ctx_tokens`` for a sliding one), never an expectation; a
step's cost without them is not defined.
"""

from __future__ import annotations

from chipbench import costs


def attention_params(cfg) -> int:
    """One attention: ``wq``, ``wk``, ``wv``, ``wo`` and the output gate
    ``wg`` (62.91 M at the published widths; the per-head norms left out)."""
    d, q, kv = cfg.hidden_size, cfg.n_heads * cfg.hd, cfg.n_kv_heads * cfg.hd
    return d * q + 2 * d * kv + q * d + d * q


def dense_ffn_params(cfg) -> int:
    return 3 * cfg.hidden_size * cfg.intermediate_size


def expert_params(cfg) -> int:
    """One routed expert's SwiGLU, and the shared expert's (28.31 M)."""
    return 3 * cfg.hidden_size * cfg.moe_inter


def router_params(cfg) -> int:
    """The classifier over every expert it scores and the bias."""
    return cfg.hidden_size * cfg.n_experts + cfg.n_experts


def layers_of(cfg) -> tuple[int, int, int]:
    """(full layers, sliding layers, routed layers) of the depth that is
    run: the first ``n_layers`` kinds of the published list."""
    kinds = list(cfg.layer_types)[: cfg.n_layers]
    sliding = kinds.count("sliding_attention")
    return len(kinds) - sliding, sliding, cfg.n_layers - cfg.first_k_dense


def model_params(cfg, experts: float) -> float:
    """Every layer with ``experts`` routed experts read or held in each
    routed layer: the attentions, the leading dense FFNs, then a router, the
    shared experts and the routed ones a layer."""
    _, _, routed = layers_of(cfg)
    return (cfg.n_layers * attention_params(cfg)
            + cfg.first_k_dense * dense_ffn_params(cfg)
            + routed * (router_params(cfg)
                        + (cfg.n_shared_experts + experts) * expert_params(cfg)))


def resident_weight_bytes(cfg) -> int:
    """Embedding, head and every layer with the experts this process holds,
    in the served dtype; norm vectors left out."""
    return int(costs.itemsize(cfg) * (
        2 * costs.head_params(cfg) + model_params(cfg, cfg.experts_held)))


def kv_bytes_per_token_per_layer(cfg) -> int:
    """Keys and values of one token in one layer (4096 at 8 heads of 128)."""
    return 2 * cfg.n_kv_heads * cfg.hd * costs.itemsize(cfg)


def kv_bytes_per_token(cfg) -> int:
    """One token's slot in the context pool, the full layers' (4096 in the
    cell): what ``/stats``' ``kv_bytes_per_token`` must read."""
    return layers_of(cfg)[0] * kv_bytes_per_token_per_layer(cfg)


def window_bytes_per_token(cfg) -> int:
    """One token's slot in the window pool, the sliding layers' (16384 in
    the cell): what ``/stats``' ``window_bytes_per_token`` must read."""
    return layers_of(cfg)[1] * kv_bytes_per_token_per_layer(cfg)


def attention_min_bytes(cfg, ctx_tokens: float, window_tokens: float) -> float:
    """The keys and values a decode step's attention must read: ``ctx_tokens``
    positions in every full layer (the live lanes' contexts, summed) and
    ``window_tokens`` in every sliding one (their ``min(context, window)``)."""
    return (ctx_tokens * kv_bytes_per_token(cfg)
            + window_tokens * window_bytes_per_token(cfg))


def decode_step_min_bytes(cfg, lanes: float, ctx_tokens: float,
                          window_tokens: float, experts_touched: float) -> float:
    """The least a decode step must read from HBM: every layer's attention,
    the dense FFNs, a routed layer's router, shared experts and
    ``experts_touched`` held experts (counted, not expected), the head's
    slice, one embedding row a lane, and the keys and values above."""
    params = (model_params(cfg, experts_touched) + costs.head_params(cfg)
              + lanes * cfg.hidden_size)
    return (costs.itemsize(cfg) * params
            + attention_min_bytes(cfg, ctx_tokens, window_tokens))


def decode_step_flops(cfg, lanes: float, ctx_tokens: float,
                      window_tokens: float, held_rows: float) -> float:
    """Matmul FLOPs of the same step: 2 a weight a lane for the attentions,
    the dense FFNs, the routers, the shared experts and the head; 2 a weight
    for each of the ``held_rows`` rows a routed layer's grouped matmuls
    compute; every query head's score against a position and its sum over
    the values, a layer."""
    full, sliding, routed = layers_of(cfg)
    per_lane = (cfg.n_layers * attention_params(cfg)
                + cfg.first_k_dense * dense_ffn_params(cfg)
                + routed * (cfg.hidden_size * cfg.n_experts
                            + cfg.n_shared_experts * expert_params(cfg))
                + costs.head_params(cfg))
    attn = 4 * cfg.n_heads * cfg.hd * (
        full * ctx_tokens + sliding * window_tokens)
    return (2 * lanes * per_lane
            + 2 * routed * held_rows * expert_params(cfg) + attn)


def decode_step_min_s(cfg, peaks: dict, lanes: float, ctx_tokens: float,
                      window_tokens: float, experts_touched: float,
                      held_rows: float) -> float:
    """The least time of the step on a chip with ``peaks``: the larger of
    its bytes over the HBM bandwidth and its FLOPs over the bf16 peak (at 32
    lanes the bytes: 8 GB against a fifth of a TFLOP)."""
    return max(
        decode_step_min_bytes(
            cfg, lanes, ctx_tokens, window_tokens, experts_touched)
        / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lanes, ctx_tokens, window_tokens, held_rows)
        / peaks["bf16_flops_per_s"],
    )
