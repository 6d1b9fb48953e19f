"""Operations and bytes the algorithm needs, computed from shapes. Kept
with the benchmark so that no PR that claims a gain can change them.

``cfg`` is the program's ``LlamaConfig`` (or anything with the same
fields): only sizes are read.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``. A device that is not in the
    table is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json"
        )
    return table[device_kind]


def itemsize(cfg) -> int:
    import numpy as np

    return np.dtype(cfg.dtype).itemsize


def attn_params_per_layer(cfg) -> int:
    d, hd = cfg.hidden_size, cfg.hd
    return (d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
            + cfg.n_heads * hd * d)


def ffn_params_per_layer(cfg) -> int:
    """All FFN weights resident in one layer (every expert, and the
    router, for a sparse layer)."""
    d = cfg.hidden_size
    if cfg.n_experts:
        return cfg.n_experts * 3 * d * cfg.moe_inter + d * cfg.n_experts
    return 3 * d * cfg.intermediate_size


def ffn_params_touched_per_token(cfg) -> int:
    d = cfg.hidden_size
    if cfg.n_experts:
        return (cfg.n_experts_per_tok * 3 * d * cfg.moe_inter
                + d * cfg.n_experts)
    return 3 * d * cfg.intermediate_size


def head_params(cfg) -> int:
    return cfg.vocab_size * cfg.hidden_size


def resident_weight_bytes(cfg) -> int:
    """Embedding + output head + every layer, in the served dtype (norm
    vectors left out: under a thousandth)."""
    tied = getattr(cfg, "tie_word_embeddings", False)
    per_layer = attn_params_per_layer(cfg) + ffn_params_per_layer(cfg)
    return itemsize(cfg) * (
        (1 if tied else 2) * head_params(cfg) + cfg.n_layers * per_layer
    )


def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one token in every layer, in the pool's dtype."""
    return 2 * cfg.n_layers * cfg.n_kv_heads * cfg.hd * itemsize(cfg)


def expected_experts_touched(cfg, lanes: float) -> float:
    """Distinct experts a decode step of ``lanes`` tokens reads in one layer
    when each token picks its top-k uniformly (a random router):
    E * (1 - (1 - k/E) ** lanes). 16 lanes x top-8 of 128: 82. An
    expectation to hold a count against (the program counts 60: its rows do
    not route independently); nothing that divides by a traced time calls
    it."""
    e, k = cfg.n_experts, cfg.n_experts_per_tok
    return e * (1.0 - (1.0 - k / e) ** lanes)


def decode_step_min_bytes(cfg, lanes: int, mean_context_tokens: float,
                          experts_touched: int = None) -> float:
    """The least a decode step must read from HBM: every layer's attention
    weights, the FFN weights the batch touches (all of a dense FFN; for a
    sparse one ``experts_touched`` experts, default every expert: an upper
    bound, so a roofline share passes what the program counted,
    ``program_counts.experts_touched_per_layer``, never an expectation), the
    output head, one embedding row a lane, and each lane's live keys and
    values."""
    d = cfg.hidden_size
    if cfg.n_experts:
        e = cfg.n_experts if experts_touched is None else experts_touched
        ffn = e * 3 * d * cfg.moe_inter + d * cfg.n_experts
    else:
        ffn = ffn_params_per_layer(cfg)
    weights = itemsize(cfg) * (
        cfg.n_layers * (attn_params_per_layer(cfg) + ffn)
        + head_params(cfg) + lanes * d
    )
    return weights + lanes * mean_context_tokens * kv_bytes_per_token(cfg)


def flops_per_token(cfg, context_tokens: float, with_head: bool = True) -> float:
    """Matmul FLOPs of one token's forward pass: 2 per weight touched, plus
    attention's 4 * heads * head_dim per context token per layer."""
    per_layer = 2 * (attn_params_per_layer(cfg)
                     + ffn_params_touched_per_token(cfg))
    attn = 4 * cfg.n_heads * cfg.hd * context_tokens
    head = 2 * head_params(cfg) if with_head else 0
    return cfg.n_layers * (per_layer + attn) + head
