"""Operations and bytes of a hybrid sparse decoder whose layers are gated
short convolutions with some layers of GQA (``LlamaConfig.layer_types``;
LFM2-8B-A1B), leading dense layers and routed experts without a shared one,
computed from shapes, beside ``costs.py`` (which counts every layer as one
that attends and keeps keys and values, and which no later PR edits). ``cfg``
is the program's ``LlamaConfig`` (or anything with the same fields): only
sizes are read.

Such a model's cache is two things a page: keys and values of the layers that
attend (``kv_bytes_per_token``) and, for each convolution layer, one state
slot of ``conv_L_cache - 1`` rows of the hidden size, whatever the page's
length (``state_bytes_per_page``). A decode step reads, a lane, the live keys
and values of the attention layers and ONE state slot a convolution layer,
and writes one back: its bound is HBM bandwidth, and nearly all of its bytes
are weights.
"""

from __future__ import annotations

from chipbench import costs


def kinds(cfg) -> list:
    """"conv" or "attention" for each of the ``n_layers`` layers run."""
    types = cfg.layer_types or ()
    return ["conv" if i < len(types) and types[i] == "conv" else "attention"
            for i in range(cfg.n_layers)]


def n_conv(cfg) -> int:
    return kinds(cfg).count("conv")


def n_attn(cfg) -> int:
    return kinds(cfg).count("attention")


def conv_params_per_layer(cfg) -> int:
    """``conv_in [d, 3d]``, ``conv_out [d, d]`` and the filter's taps."""
    d = cfg.hidden_size
    return 4 * d * d + cfg.conv_L_cache * d


def operator_params(cfg) -> int:
    """Every layer's operator: convolution or attention, as the kinds say."""
    return (n_conv(cfg) * conv_params_per_layer(cfg)
            + n_attn(cfg) * costs.attn_params_per_layer(cfg))


def dense_ffn_params(cfg) -> int:
    return 3 * cfg.hidden_size * cfg.intermediate_size


def expert_ffn_params(cfg, experts: float = None) -> float:
    """One expert layer's FFN with ``experts`` routed experts read (default:
    all of them resident), the router and its bias."""
    d = cfg.hidden_size
    e = cfg.n_experts if experts is None else experts
    return e * 3 * d * cfg.moe_inter + d * cfg.n_experts + cfg.n_experts


def n_dense(cfg) -> int:
    return min(cfg.first_k_dense, cfg.n_layers)


def resident_params(cfg) -> float:
    """Embedding (the head is tied to it: once), every layer's operator,
    the leading dense FFNs and the expert layers; norm vectors left out."""
    heads = (1 if cfg.tie_word_embeddings else 2) * costs.head_params(cfg)
    return (heads + operator_params(cfg) + n_dense(cfg) * dense_ffn_params(cfg)
            + (cfg.n_layers - n_dense(cfg)) * expert_ffn_params(cfg))


def resident_weight_bytes(cfg) -> int:
    return int(costs.itemsize(cfg) * resident_params(cfg))


def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one token in the layers that attend: what
    ``/stats``' ``kv_bytes_per_token`` must read (3 x 2 x 8 x 64 x 2 = 6144
    in the cell)."""
    return 2 * n_attn(cfg) * cfg.n_kv_heads * cfg.hd * costs.itemsize(cfg)


def state_bytes_per_page(cfg) -> int:
    """Every convolution layer's state slot of one page (11 x 8192)."""
    return (n_conv(cfg) * (cfg.conv_L_cache - 1) * cfg.hidden_size
            * costs.itemsize(cfg))


def state_bytes_per_token(cfg, page: int) -> int:
    """... over the page's token slots: what ``/stats``'
    ``state_bytes_per_token`` must read (5632 at 16-token pages)."""
    return state_bytes_per_page(cfg) // page


def page_bytes(cfg, page: int) -> int:
    """One page in every pool (188 416 in the cell)."""
    return page * kv_bytes_per_token(cfg) + state_bytes_per_page(cfg)


def decode_step_min_bytes(cfg, lanes: float, ctx_tokens: float,
                          experts_touched: float) -> float:
    """The least a decode step must read from HBM: every layer's operator
    weights by kind, the dense layers' FFN, ``experts_touched`` routed
    experts an expert layer (what the program counted: a caller passes a
    count, never an expectation over a router's draws) with the router, the
    head, one embedding row a lane, ``ctx_tokens`` live keys and values of
    the attention layers (the real lanes' contexts, summed:
    ``step_stats["attn_ctx_tokens"]`` a forward) and one state slot a
    convolution layer a lane."""
    dense = n_dense(cfg)
    params = (operator_params(cfg) + dense * dense_ffn_params(cfg)
              + (cfg.n_layers - dense) * expert_ffn_params(cfg, experts_touched)
              + costs.head_params(cfg) + lanes * cfg.hidden_size)
    return (costs.itemsize(cfg) * params
            + ctx_tokens * kv_bytes_per_token(cfg)
            + lanes * state_bytes_per_page(cfg))
