"""The plain reference of a sparse decoder (Qwen3-MoE), from the published
description (the Qwen3 report and ``modeling_qwen3_moe.py`` of the source the
configuration names): the attention of ``dense`` (pre-norm layer, GQA with
per-head RMSNorm on q and k before RoPE, causal softmax in f32); the FFN is a
softmax router over all experts, top-k, gates renormalised to sum to one
(``norm_topk_prob``), SwiGLU experts. Every expert is evaluated for every
token and weighted by its gate (zero where not selected) — the masked-dense
form of the same mathematics, expert by expert so that only one expert is
cast to f32 at a time. The attention, the layer loop and the head are
``chipbench/reference.py``'s (shared with ``dense``); the FFN is here.

Tolerances (what an error is, and why bf16 costs about 1 % of the largest
logit: ``dense``'s docstring). My chip runs, PR 27, Qwen3-30B-A3B widths, 8
layers: 170 seeds with every compared position's error and router gap kept
(``probe_reference.py`` prints the compared numbers again; PR 24's 12 seeds
and PR 27's 24 first ones read the same ranges).

What the whole model reads is routing, not arithmetic. The program rounds the
router's logits to bf16 (spacing 0.008 to 0.016 near the top-8 boundary) and
computes them from bf16 activations, so a token whose 8th and 9th router
logit lie close takes another expert than the reference does. With each layer
run alone (embedding -> that layer -> head), where a position's logits
depend on the routing of its own token only, 647 of 12240 positions (1 to 8
of a seed's 72) read 5e-2 to 3.2e-1, all others under 1.42e-2, and nothing
lies between: a swapped expert, not arithmetic. Through 8 layers nearly every
position has such a token in its context: worst position 5.3e-2 to 1.37e-1
(mean 9.0e-2, standard deviation 1.7e-2), median position 1.2e-2 to 5.9e-2
(mean 3.5e-2, s.d. 0.9e-2), and the lower precisions do not stand out of
that (int8 pool, 15 seeds: median 1.3e-2 to 6.3e-2; int8 experts and weights,
4 layers, 7 seeds: 4.4e-2 to 8.0e-2).

So the whole-model numbers hold the mathematics and the layers alone hold the
precision, each against the fault it can tell from a sound run:

- ``max`` 0.3 and ``p50`` 0.15 against a program that is not this model. The
  controls: the last layer's weights used twice reads worst 0.53 to 0.64,
  median 0.475 to 0.565 (6 seeds); two layers in swapped order 1.47 to 1.57
  and 1.26 to 1.48 (3 seeds). ``max`` is 2.2 x the sound runs' largest and 0.57 x the
  controls' smallest; ``p50`` 2.5 x and 0.3 x. (PR 24 had ``p50`` at 6.5e-2
  to refuse int8 experts too: 1.1 x the largest sound seed and three standard
  deviations above their mean, no limit that holds; the driver's check of PR
  27 met a ``reasoning`` run that was not correct.)
- ``layer_p75`` 1.1e-2: the third quartile of the 72 positions of the layers
  alone. A quarter of them may be swapped (a seed has at most 8) without
  moving it, so it needs no rule for which tokens are decided. Sound: 0.777e-2
  to 0.899e-2 (mean 0.833e-2, s.d. 0.023e-2). An int8 pool: 1.41e-2 to
  1.65e-2 (15 seeds, mean 1.52e-2, s.d. 0.06e-2); int8 experts and weights:
  2.69e-2 to 3.01e-2 (4 layers, 7 seeds; sound at 4 layers reads as at 8).
  The limit is 1.22 x the sound runs' largest (11 of their s.d. above their
  mean) and 0.78 x the controls' smallest (7 of theirs below their mean). The
  two are 1.6 x apart and not 3 x: on one layer's logits an int8 pool reads
  1.8 x what bf16 alone does, no more. What holds the limit is that either
  side moves by about 3 % from seed to seed. It does not see a
  fault in under a quarter of the positions (one layer, the prefill's
  position alone).

PR 24 and PR 27's first sessions compared the worst layer-alone position
among tokens whose gap is at least ``ROUTER_GAP_MIN``, with a floor on their
share: a max over a set drawn by a threshold, open on three sides (a swap
above the gap: 1 of 50 seeds at 0.03, the widest gap seen to swap 0.0341; the
worst sound position 1.27e-2 against 1.5e-2; 20 to 49 decided of 72 against a
floor of 18). The result line still tells that split (``layer_rel_err``,
``layer_positions``, ``layer_tied_*``): it says whether a run's errors are
swaps; nothing is decided by it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import reference as common

#: bf16 system against the f32 reference (reasons above): the worst and the
#: median position of the whole model, the third quartile of the positions
#: of the layers alone
TOL_BF16 = {"max": 0.3, "p50": 0.15, "layer_p75": 1.1e-2}
#: told in the result line, compared with nothing: a token is called decided
#: where its last selected expert leads the first unselected one by this much
#: in router logits
ROUTER_GAP_MIN = 0.05


def _ffn(layer, cfg, x):
    """(output, router gap): the gap is the distance, in router logits,
    between the last expert a token selects and the first it does not."""
    f32 = jnp.float32
    router_logits = x @ layer["router"].astype(f32)
    edge = jax.lax.top_k(router_logits, cfg.n_experts_per_tok + 1)[0]
    gap = edge[:, -2] - edge[:, -1]
    weights = jax.nn.softmax(router_logits, axis=-1)
    topv, topi = jax.lax.top_k(weights, cfg.n_experts_per_tok)
    if cfg.norm_topk_prob:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    # gate of every expert for every token; zero where not selected
    gates = jnp.zeros_like(weights).at[
        jnp.arange(x.shape[0])[:, None], topi
    ].set(topv)

    def one_expert(acc, e):
        y = common._swiglu(x, layer["w_gate"][e].astype(f32),
                           layer["w_up"][e].astype(f32),
                           layer["w_down"][e].astype(f32))
        return acc + gates[:, e, None] * y, None

    acc, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          jnp.arange(cfg.n_experts))
    return acc, gap


def forward(params, cfg, tokens):
    """(logits [s, vocab] f32, router gap [s]: each token's smallest over
    the layers)."""
    if not cfg.n_experts:
        raise ValueError("reference 'moe' does not fit the model")
    return common.decoder_forward(params, cfg, tokens, _ffn)
