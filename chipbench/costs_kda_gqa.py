"""Operations and bytes of a hybrid of delta-rule linear attention and gated
GQA without positions over sparse experts (Solar-Open2 / solar_open2), of
which one process holds a SHARE of the routed experts, computed from shapes
beside ``costs.py`` and ``costs_kda.py`` (which no later PR edits). ``cfg`` is
the program's ``LlamaConfig`` (or anything with the same fields): only sizes
are read.

A linear layer is ``costs_kda``'s (a sequence's ``n_heads`` matrices ``[K,
V]`` float32 and the carried convolution rows, read and written a layer a
step whatever the context) with the decay's projection and the output gate
through low-rank pairs of the head's size as rank; a GQA layer reads every
live lane's whole context of keys and values a step. Both in ONE decode
step: ``decode_step_min_s`` is the least time of the whole of it.

Nothing here guesses a count: the lanes, the context rows, the experts a
decode step reads and the rows its grouped matmuls compute are arguments
(what the program counted: ``step_stats["decode_rows"]``,
``attn_ctx_tokens``, ``experts_touched``, ``held_places``), never an
expectation.
"""

from __future__ import annotations

from chipbench import costs, costs_kda


def layers_of(cfg) -> tuple[int, int]:
    """(linear layers, GQA layers) of the depth that is run: the first
    ``n_layers`` kinds of the published pattern; every layer is routed."""
    linear = costs_kda.layers_of(cfg)[0]
    return linear, cfg.n_layers - linear


def kda_params(cfg) -> int:
    """One linear mixer: ``[q | k | v]`` and the output projection (four of
    ``hidden x heads x K``), the two low-rank pairs (``hidden x rank + rank
    x heads x K`` each, the rank a head's size), ``beta`` (a column a head)
    the convolution's taps, the gate's bias, ``A_log`` and the head's norm:
    137,732,288 at the published widths."""
    d, hk = cfg.hidden_size, cfg.n_heads * cfg.kda_head_dim
    rank = cfg.kda_head_dim
    return (4 * d * hk + 2 * (d * rank + rank * hk) + d * cfg.n_heads
            + cfg.kda_conv_kernel * 3 * hk + hk + cfg.n_heads + rank)


def gqa_params(cfg) -> int:
    """One gated GQA layer: q, the gate and the output projection (``hidden
    x heads x head``), k and v (``hidden x kv heads x head``): 109,051,904."""
    d, hd = cfg.hidden_size, cfg.hd
    return 3 * d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd


def routed_fixed_params(cfg) -> int:
    """What every routed layer reads whatever is chosen: the router with
    its bias and the shared expert."""
    return (costs_kda.router_params(cfg)
            + cfg.n_shared_experts * costs_kda.expert_params(cfg))


def model_params(cfg, experts: float) -> float:
    """Every layer that is run with ``experts`` routed experts read or held
    in each."""
    linear, gqa = layers_of(cfg)
    return (linear * kda_params(cfg) + gqa * gqa_params(cfg)
            + cfg.n_layers * (routed_fixed_params(cfg)
                              + experts * costs_kda.expert_params(cfg)))


def resident_weight_bytes(cfg) -> int:
    """Embedding, head and every layer with the experts this process holds,
    in the served dtype; norm vectors left out."""
    held = cfg.n_experts if cfg.expert_count is None else cfg.expert_count
    return int(costs.itemsize(cfg) * (
        2 * costs.head_params(cfg) + model_params(cfg, held)))


def kv_bytes_per_token(cfg) -> int:
    """One token's keys and values in the GQA layers that are run (1 x 8
    heads x 128 x 2 x bf16 = 4096 B in the cell): ``/stats``'
    ``kv_bytes_per_token`` must read this."""
    return (layers_of(cfg)[1] * 2 * cfg.n_kv_heads * cfg.hd
            * costs.itemsize(cfg))


def decode_step_min_bytes(cfg, lanes: float, ctx_tokens: float,
                          experts_touched: float) -> float:
    """The least a decode step must move through HBM: every layer's mixer
    weights, ``experts_touched`` held experts a routed layer (counted) with
    the router and the shared expert, the head, one embedding row a lane;
    every real lane's state read and written in every linear layer
    (matrices and carried rows); ``ctx_tokens`` tokens of keys and values in
    every GQA layer (the live lanes' contexts, summed)."""
    linear = layers_of(cfg)[0]
    weights = costs.itemsize(cfg) * (
        model_params(cfg, experts_touched) + costs.head_params(cfg)
        + lanes * cfg.hidden_size)
    state = 2 * lanes * linear * costs_kda.state_bytes_per_layer(cfg)
    return weights + state + ctx_tokens * kv_bytes_per_token(cfg)


def decode_step_flops(cfg, lanes: float, ctx_tokens: float,
                      held_rows: float) -> float:
    """Matmul FLOPs of the same step: 2 a weight a lane for the mixers'
    projections, the routers, the shared experts and the head; 2 a weight for
    each of the ``held_rows`` rows a routed layer's grouped matmuls compute
    (counted); every query head's score against a context token and its
    weighted sum of the values (4 x heads x head), a GQA layer; the
    recurrence's vector FLOPs."""
    linear, gqa = layers_of(cfg)
    per_lane = (linear * kda_params(cfg) + gqa * gqa_params(cfg)
                + cfg.n_layers * (
                    cfg.hidden_size * cfg.n_experts
                    + cfg.n_shared_experts * costs_kda.expert_params(cfg))
                + costs.head_params(cfg))
    attn = gqa * ctx_tokens * 4 * cfg.n_heads * cfg.hd
    return (2 * lanes * per_lane
            + 2 * cfg.n_layers * held_rows * costs_kda.expert_params(cfg)
            + attn + costs_kda.kda_decode_flops(cfg, lanes))


def decode_step_min_s(cfg, peaks: dict, lanes: float, ctx_tokens: float,
                      experts_touched: float, held_rows: float) -> float:
    """The least time of the step on a chip with ``peaks``: the larger of
    its bytes over the HBM bandwidth and its FLOPs over the bf16 peak (at 32
    lanes over a mean context of 25k the bytes: about 8 GB against a fifth
    of a TFLOP)."""
    return max(
        decode_step_min_bytes(cfg, lanes, ctx_tokens, experts_touched)
        / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lanes, ctx_tokens, held_rows)
        / peaks["bf16_flops_per_s"],
    )
