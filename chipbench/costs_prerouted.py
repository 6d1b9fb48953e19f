"""Operations and bytes of a sparse decoder whose every layer is an attention
(full or sliding) and a set of routed experts ALL held by this process, with
a router that reads the layer's input (SmallThinker: GQA without gate or q/k
norm, 64 ReGLU experts top-6, no shared expert, no dense layer), computed
from shapes beside ``costs.py`` (which no later PR edits). ``cfg`` is the
program's ``LlamaConfig`` (or anything with the same fields): only sizes are
read. Where the router reads changes no count: its ``d x E`` classifier is
read once a layer either way.

Nothing here guesses a count: the experts a decode step reads and the
positions its layers read of their pools are arguments (what the program
counted: ``step_stats["experts_touched"]``, ``attn_ctx_tokens`` for a full
layer, ``window_ctx_tokens`` for a sliding one), never an expectation; a
step's cost without them is not defined.
"""

from __future__ import annotations

from chipbench import costs


def attention_params(cfg) -> int:
    """One attention: ``wq``, ``wk``, ``wv``, ``wo`` (20.97 M at the
    published widths: 2560 x 3584 + 2 x 2560 x 512 + 3584 x 2560)."""
    return costs.attn_params_per_layer(cfg)


def router_params(cfg) -> int:
    """The classifier over every expert (163 840); no bias."""
    return cfg.hidden_size * cfg.n_experts


def expert_params(cfg) -> int:
    """One routed expert's gated FFN (5.898 M: 3 x 2560 x 768)."""
    return 3 * cfg.hidden_size * cfg.moe_inter


def layers_of(cfg) -> tuple[int, int]:
    """(full layers, sliding layers) of the depth that is run: the first
    ``n_layers`` kinds of the published list."""
    kinds = list(cfg.layer_types)[: cfg.n_layers]
    sliding = kinds.count("sliding_attention")
    return len(kinds) - sliding, sliding


def model_params(cfg, experts: float) -> float:
    """Every layer with ``experts`` experts read or held in each: the
    attention, the router and the experts."""
    return cfg.n_layers * (attention_params(cfg) + router_params(cfg)
                           + experts * expert_params(cfg))


def resident_weight_bytes(cfg) -> int:
    """Embedding, head and every layer with every expert, in the served
    dtype; norm vectors left out (7.93 GB at 8 layers)."""
    return int(costs.itemsize(cfg) * (
        2 * costs.head_params(cfg) + model_params(cfg, cfg.n_experts)))


def kv_bytes_per_token_per_layer(cfg) -> int:
    """Keys and values of one token in one layer (2048 at 4 heads of 128)."""
    return 2 * cfg.n_kv_heads * cfg.hd * costs.itemsize(cfg)


def kv_bytes_per_token(cfg) -> int:
    """One token's slot in the context pool, the full layers' (4096 in the
    cell): what ``/stats``' ``kv_bytes_per_token`` must read."""
    return layers_of(cfg)[0] * kv_bytes_per_token_per_layer(cfg)


def window_bytes_per_token(cfg) -> int:
    """One token's slot in the window pool, the sliding layers' (12288 in
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
    router and ``experts_touched`` experts (counted, not expected), the head,
    one embedding row a lane, and the keys and values above."""
    params = (model_params(cfg, experts_touched) + costs.head_params(cfg)
              + lanes * cfg.hidden_size)
    return (costs.itemsize(cfg) * params
            + attention_min_bytes(cfg, ctx_tokens, window_tokens))


def decode_step_flops(cfg, lanes: float, ctx_tokens: float,
                      window_tokens: float) -> float:
    """Matmul FLOPs of the same step: 2 a weight a lane for the attentions,
    the routers, the ``top-k`` experts a lane takes in every layer and the
    head; every query head's score against a position and its sum over the
    values, a layer."""
    full, sliding = layers_of(cfg)
    per_lane = (cfg.n_layers * (
        attention_params(cfg) + router_params(cfg)
        + cfg.n_experts_per_tok * expert_params(cfg))
        + costs.head_params(cfg))
    attn = 4 * cfg.n_heads * cfg.hd * (
        full * ctx_tokens + sliding * window_tokens)
    return 2 * lanes * per_lane + attn


def decode_step_min_s(cfg, peaks: dict, lanes: float, ctx_tokens: float,
                      window_tokens: float, experts_touched: float) -> float:
    """The least time of the step on a chip with ``peaks``: the larger of
    its bytes over the HBM bandwidth and its FLOPs over the bf16 peak (at 32
    lanes the bytes: 8 GB against a twentieth of a TFLOP)."""
    return max(
        decode_step_min_bytes(
            cfg, lanes, ctx_tokens, window_tokens, experts_touched)
        / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lanes, ctx_tokens, window_tokens)
        / peaks["bf16_flops_per_s"],
    )
