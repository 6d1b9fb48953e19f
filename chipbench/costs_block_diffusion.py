"""Bytes that generation by diffusion over blocks needs, computed from
shapes, beside ``costs.py`` (which no later PR edits either). ``cfg`` is the
program's ``LlamaConfig`` (or anything with the same fields, ``block_length``
among them): only sizes are read.

A forward of ``llama.denoise_steps`` runs ``block_length`` rows a lane, so it
touches the weights a decode step touches, for ``lanes * block_length`` rows
(the experts among them counted by the program), and reads each lane's live
keys and values once, whatever the number of rows. Both bounds are HBM
bandwidth: at 64 rows the matmuls are far below the chip's FLOP/s.
"""

from __future__ import annotations

from chipbench import costs


def block_context_bytes(cfg, lanes: float, mean_context_tokens: float) -> float:
    """Keys and values of the live lanes' contexts in every layer: what the
    ``block_attention`` kernel's calls of ONE forward (one call a layer) must
    read. The block's own rows (``block_length`` a lane) are left out: under
    a hundredth at the cell's contexts."""
    return lanes * mean_context_tokens * costs.kv_bytes_per_token(cfg)


def denoise_forward_min_bytes(cfg, lanes: float, mean_context_tokens: float,
                              experts_touched: float) -> float:
    """The least one denoising (or committing) forward must read from HBM:
    every layer's attention weights, the FFN weights its rows touch (all of
    a dense FFN; for a sparse one the router and ``experts_touched`` experts
    a layer), the output head, one embedding row a row, and each lane's
    live keys and values.

    ``experts_touched`` is the mean number of distinct experts the forward's
    rows chose in a layer, as the program counted it (``step_stats[
    "experts_touched"]``; unused for a dense FFN). The expectation for
    ``lanes * block_length`` rows x top-k independent draws
    (``costs.expected_experts_touched``, 126 of 128 at the cell's size) is
    an overcount: a block's masked rows are one token and the deep layers
    of a random model route alike (71-73 counted, PERF.md section 5)."""
    rows = lanes * cfg.block_length
    d = cfg.hidden_size
    if cfg.n_experts:
        ffn = experts_touched * 3 * d * cfg.moe_inter + d * cfg.n_experts
    else:
        ffn = costs.ffn_params_per_layer(cfg)
    weights = costs.itemsize(cfg) * (
        cfg.n_layers * (costs.attn_params_per_layer(cfg) + ffn)
        + costs.head_params(cfg) + rows * d
    )
    return weights + block_context_bytes(cfg, lanes, mean_context_tokens)


def live_lanes_and_context(run) -> tuple[float, float]:
    """(lanes busy, mean context of a running request) of a window,
    estimated: the 10 Hz samples of the first replica's running sequences,
    and prompt + half the output over the completed requests (the block
    path counts neither lanes nor context rows: PERF.md section 7)."""
    ctx = [r["prompt_len"] + r["max_tokens"] / 2 for r in run.good]
    lanes = run.lanes
    if run.running_samples:
        lanes = sum(s[0] for s in run.running_samples) / len(run.running_samples)
    return lanes, (sum(ctx) / len(ctx) if ctx else 0.0)
