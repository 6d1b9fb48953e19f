"""The plain reference of a dense decoder (Qwen3), from the published
description (the Qwen3 report and ``modeling_qwen3.py`` of the source the
configuration names): pre-norm decoder layer; GQA attention with per-head
RMSNorm on q and k before RoPE (half-split rotation, theta from the
config), causal softmax in f32; SwiGLU FFN ``down(silu(gate x) * up x)``.
The attention, the layer loop and the head are ``chipbench/reference.py``'s
(shared with ``moe``); the FFN is here.

Tolerances. The system computes in bf16 (weights, activations, KV pages)
with f32 accumulation; the reference computes the same bf16 weights in f32.
Each bf16 rounding is 2^-9 relative and is taken after every matmul, norm and
residual, so the logits differ by about 1 % of the largest logit. Every
number below: my chip runs, PR 24 (``probe_reference.py`` makes them again).

Qwen3-32B widths, 5 layers, 17 seeds: worst position 1.05e-2 to 1.45e-2,
median position 0.89e-2 to 1.06e-2. With an int8 pool (``KV_QUANT_HBM=int8``,
two seeds) the worst position reads 1.57e-2 and 1.70e-2 and the median
1.38e-2 and 1.45e-2. So ``max`` 3e-2 holds the mathematics and a stray
position, and ``p50`` 1.25e-2 holds the precision: five standard deviations
above the seeds' medians, and an int8 pool fails it. int8 weights: not
measured (both trees do not fit beside each other at depth 5), so nothing is
claimed.
"""

from __future__ import annotations

import jax.numpy as jnp

from chipbench import reference as common

#: bf16 system against the f32 reference (reasons above): the worst position
#: and the median position
TOL_BF16 = {"max": 3e-2, "p50": 1.25e-2}


def _ffn(layer, cfg, x):
    f32 = jnp.float32
    out = common._swiglu(x, layer["w_gate"].astype(f32),
                         layer["w_up"].astype(f32),
                         layer["w_down"].astype(f32))
    return out, jnp.full(x.shape[0], jnp.inf, f32)


def forward(params, cfg, tokens):
    """(logits [s, vocab] f32, None: nothing is routed)."""
    if cfg.n_experts:
        raise ValueError("reference 'dense' does not fit the model")
    return common.decoder_forward(params, cfg, tokens, _ffn)[0], None
