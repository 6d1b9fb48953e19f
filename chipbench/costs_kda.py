"""Operations and bytes of a hybrid of delta-rule linear attention and latent
attention over sparse experts (Ling-3.0 / bailing_hybrid), of which one
process holds a SHARE of the routed experts, computed from shapes beside
``costs.py`` (which no later PR edits). ``cfg`` is the program's
``LlamaConfig`` (or anything with the same fields): only sizes are read.

A linear layer keeps, a sequence, ``n_heads`` matrices ``[K, V]`` float32
(``kda_head_dim`` both) and the ``kda_conv_kernel - 1`` newest rows of its
``[q | k | v]`` convolution inputs. Its decode kernel (``kda_decode``) reads
every live lane's matrices and writes them back, a layer a step, whatever
the context: its bound is HBM bandwidth (4 FLOP a byte).

Nothing here guesses a count: the lanes, the experts a decode step reads and
the latent rows it reads are arguments (what the program counted:
``step_stats["decode_rows"]``, ``experts_touched``, ``attn_ctx_tokens``),
never an expectation.
"""

from __future__ import annotations

from chipbench import costs, costs_mla

STATE_ITEMSIZE = 4  # the matrices are float32 whatever the model's dtype


def layers_of(cfg) -> tuple[int, int, int]:
    """(linear layers, latent layers, routed layers) of the depth that is
    run: the first ``n_layers`` kinds of the published pattern."""
    kinds = list(cfg.layer_types)[: cfg.n_layers]
    linear = kinds.count("linear_attention")
    return linear, len(kinds) - linear, cfg.n_layers - cfg.first_k_dense


def kda_params(cfg) -> int:
    """One linear mixer: ``[q | k | v]``, the gate's projection and the
    output projection (five of ``hidden x heads x K``: 52.43 M at the
    published widths), ``beta`` and the output gate (a column a head each);
    the convolution's taps, the bias, ``A_log`` and the norm left out."""
    d, hk = cfg.hidden_size, cfg.n_heads * cfg.kda_head_dim
    return 5 * d * hk + 2 * d * cfg.n_heads


def conv_row_values(cfg) -> int:
    """Values of a sequence's carried convolution rows in one layer."""
    return (cfg.kda_conv_kernel - 1) * 3 * cfg.n_heads * cfg.kda_head_dim


def state_bytes_per_layer(cfg) -> int:
    """A sequence's state in one linear layer: the matrices (2 MiB) and the
    carried rows (72 KiB)."""
    return (cfg.n_heads * cfg.kda_head_dim**2 * STATE_ITEMSIZE
            + conv_row_values(cfg) * costs.itemsize(cfg))


def state_bytes_per_snapshot(cfg) -> int:
    """... in every linear layer that is run: what a snapshot, and a live
    slot, weighs (6 x 2.07 MiB = 12.42 MiB in the cell); ``/stats``'
    ``state_bytes_per_snapshot`` must read this."""
    return layers_of(cfg)[0] * state_bytes_per_layer(cfg)


def state_bytes_per_token(cfg, stride: int) -> int:
    """What prefix caching costs a token in the state pool: a snapshot
    every ``stride`` tokens (24.8 KiB at 512 beside the latent row's 1280
    B)."""
    return state_bytes_per_snapshot(cfg) // stride


def expert_params(cfg) -> int:
    """One routed expert's SwiGLU, and the shared expert's (5.90 M)."""
    return 3 * cfg.hidden_size * cfg.moe_inter


def router_params(cfg) -> int:
    return cfg.hidden_size * cfg.n_experts + cfg.n_experts


def dense_ffn_params(cfg) -> int:
    return 3 * cfg.hidden_size * cfg.intermediate_size


def model_params(cfg, experts: float) -> float:
    """Every layer that is run with ``experts`` routed experts read or held
    in each routed layer."""
    linear, latent, routed = layers_of(cfg)
    return (linear * kda_params(cfg)
            + latent * costs_mla.attn_params_per_layer(cfg)
            + cfg.first_k_dense * dense_ffn_params(cfg)
            + routed * (router_params(cfg)
                        + (cfg.n_shared_experts + experts) * expert_params(cfg)))


def resident_weight_bytes(cfg) -> int:
    """Embedding, head and every layer with the experts this process
    holds, in the served dtype; norm vectors left out."""
    held = cfg.n_experts if cfg.expert_count is None else cfg.expert_count
    return int(costs.itemsize(cfg) * (
        2 * costs.head_params(cfg) + model_params(cfg, held)))


def kda_decode_bytes(cfg, lanes: float) -> float:
    """What the ``kda_decode`` kernel's calls of ONE decode step (one call a
    linear layer) must move: every real lane's matrices read and written,
    and the step's operands (q, k, beta k and the decay as float32 columns, v
    in and the output out as float32 rows: six rows of ``heads x K``)."""
    linear = layers_of(cfg)[0]
    hk = cfg.n_heads * cfg.kda_head_dim
    return linear * lanes * (
        2 * hk * cfg.kda_head_dim * STATE_ITEMSIZE + 6 * hk * STATE_ITEMSIZE)


def kda_decode_flops(cfg, lanes: float) -> float:
    """Vector FLOPs of the same calls: the decay (1), ``S^T k`` (2), the
    rank-one update (2) and ``S^T q`` (2) a value of the matrices."""
    linear = layers_of(cfg)[0]
    return linear * lanes * 7 * cfg.n_heads * cfg.kda_head_dim**2


def latent_bytes_per_token(cfg) -> int:
    """One token's latent rows in the latent layers that are run, as held
    (1 x 1280 in the cell): what ``/stats``' ``kv_bytes_per_token`` reads."""
    return layers_of(cfg)[1] * costs_mla.latent_bytes_per_token_per_layer(cfg)


def decode_step_min_bytes(cfg, lanes: float, ctx_tokens: float,
                          experts_touched: float) -> float:
    """The least a decode step must move through HBM: every layer's mixer
    weights, the dense FFN, ``experts_touched`` held experts a routed layer
    (counted) with the router and the shared expert, the head, one embedding
    row a lane; every real lane's state read and written in every linear
    layer (matrices and carried rows); ``ctx_tokens`` latent rows in every
    latent layer (the live lanes' contexts, summed)."""
    linear = layers_of(cfg)[0]
    weights = costs.itemsize(cfg) * (
        model_params(cfg, experts_touched) + costs.head_params(cfg)
        + lanes * cfg.hidden_size)
    state = 2 * lanes * linear * state_bytes_per_layer(cfg)
    return weights + state + ctx_tokens * latent_bytes_per_token(cfg)


def decode_step_flops(cfg, lanes: float, ctx_tokens: float,
                      held_rows: float) -> float:
    """Matmul FLOPs of the same step: 2 a weight a lane for the mixers'
    projections, the dense FFN, the routers, the shared expert and the head;
    2 a weight for each of the ``held_rows`` rows a routed layer's grouped
    matmuls compute (counted); every head's score against a latent row and
    its sum over the latent, a latent layer; the recurrence's vector FLOPs."""
    linear, latent, routed = layers_of(cfg)
    per_lane = (linear * kda_params(cfg)
                + latent * costs_mla.attn_params_per_layer(cfg)
                + cfg.first_k_dense * dense_ffn_params(cfg)
                + routed * (cfg.hidden_size * cfg.n_experts
                            + cfg.n_shared_experts * expert_params(cfg))
                + costs.head_params(cfg))
    attn = latent * ctx_tokens * 2 * cfg.n_heads * (
        costs_mla.row_values(cfg) + cfg.kv_lora_rank)
    return (2 * lanes * per_lane + 2 * routed * held_rows * expert_params(cfg)
            + attn + kda_decode_flops(cfg, lanes))


def decode_step_min_s(cfg, peaks: dict, lanes: float, ctx_tokens: float,
                      experts_touched: float, held_rows: float) -> float:
    """The least time of the step on a chip with ``peaks``: the larger of
    its bytes over the HBM bandwidth and its FLOPs over the bf16 peak (at 64
    lanes the bytes: about 5 GB against a tenth of a TFLOP)."""
    return max(
        decode_step_min_bytes(cfg, lanes, ctx_tokens, experts_touched)
        / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lanes, ctx_tokens, held_rows)
        / peaks["bf16_flops_per_s"],
    )
