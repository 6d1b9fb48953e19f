"""Plain references for the blocks the cells use, and the check that
decides the reference part of ``correct``.

A reference is a straightforward float32 ``jax.numpy`` forward under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, nothing imported from the program's model code. Written from the
published descriptions:

- ``dense`` (Qwen3): pre-norm decoder layer; GQA attention with per-head
  RMSNorm on q and k before RoPE (half-split rotation, theta from the
  config), causal softmax in f32; SwiGLU FFN ``down(silu(gate x) * up x)``.
- ``moe`` (Qwen3-MoE): the same attention; the FFN is a softmax router over
  all experts, top-k, gates renormalised to sum to one (``norm_topk_prob``),
  SwiGLU experts. Every expert is evaluated for every token and weighted by
  its gate (zero where not selected) — the masked-dense form of the same
  mathematics, expert by expert so that only one expert is cast to f32 at a
  time.

The reference reads the engine's own parameters (the tree
``llama.init_params`` builds: ``embed, final_norm, lm_head, layers[i].{
attn_norm, wq, wk, wv, wo, q_norm, k_norm, mlp_norm, w_gate, w_up, w_down,
router}``) and casts what it touches to f32, layer by layer.

Tolerances. An error is max|delta| over the vocabulary / the reference's
largest |logit|, at one compared position (the last of the prompt, each
decode step). The system computes in bf16 (weights, activations, KV pages)
with f32 accumulation; the reference computes the same bf16 weights in f32.
Each bf16 rounding is 2^-9 relative and is taken after every matmul, norm and
residual, so the logits differ by about 1 % of the largest logit. Every
number below: my chip runs, PR 24 (``probe_reference.py`` makes them again).

- ``dense``, Qwen3-32B widths, 5 layers, 17 seeds: worst position 1.05e-2 to
  1.45e-2, median position 0.89e-2 to 1.06e-2. With an int8 pool
  (``KV_QUANT_HBM=int8``, two seeds) the worst position reads 1.57e-2 and
  1.70e-2 and the median 1.38e-2 and 1.45e-2. So ``max`` 3e-2 holds the
  mathematics and a stray position, and ``p50`` 1.25e-2 holds the
  precision: five standard deviations above the seeds' medians, and an int8
  pool fails it. int8 weights: not measured (both trees do not fit beside
  each other at depth 5), so nothing is claimed.
- ``moe``, Qwen3-30B-A3B widths, 8 layers, 12 seeds: worst position 6.1e-2
  to 1.2e-1, median 2.4e-2 to 4.2e-2, the same with ``ragged_dot`` in place
  of the megablox kernel (6.7e-2 / 3.7e-2 against 6.8e-2 / 3.6e-2, one seed).
  The excess over ``dense`` is routing, and the chip shows it: the program
  rounds the router's logits to bf16, whose spacing near the top-8 boundary
  (logits of 1-2) is 0.008, and the gap between a token's 8th and 9th logit
  is under 0.03 at 28-46 % of the positions. With each layer run alone
  (embedding -> that layer -> head), where a position's logits depend on the
  routing of its own token only, the positions whose gap is at least
  ``ROUTER_GAP_MIN`` ("decided": 39-53 of 72) differ by 0.99e-2 to 1.09e-2
  at worst, kernel and ``ragged_dot`` alike, and the others by up to 2.7e-1:
  a swapped expert, not arithmetic. So the whole model is held loosely
  (``max`` 0.3: a missing renormalisation, a wrong expert, dropped tokens;
  ``p50`` 6.5e-2, 1.5 x the largest median) and the precision is held by
  the layers alone: ``layer`` 1.5e-2 on the decided positions, 1.4 x the
  worst measured. int8 experts and weights read 1.26e-1 there (4 layers, one
  seed) and an int8 pool 1.72e-2 (8 layers, one seed): both fail.

The float32 tiny presets of the CPU tests agree to ``TOL_F32``
(accumulation order only).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

#: bf16 system against the f32 reference, by block (reasons above): the
#: worst position, the median position and, where tokens are routed, the
#: worst decided position of a layer alone
TOL_BF16 = {
    "dense": {"max": 3e-2, "p50": 1.25e-2},
    "moe": {"max": 0.3, "p50": 6.5e-2, "layer": 1.5e-2},
}
#: f32 system (the CPU tests' tiny presets) against the f32 reference
TOL_F32 = 2e-4
#: a token is decided where its last selected expert leads the first
#: unselected one by this much in router logits (reasons above)
ROUTER_GAP_MIN = 0.03
#: the layer check says nothing unless this share of its positions is decided
LAYER_DECIDED_MIN = 0.25

PROMPT_TOKENS = 128
DECODE_STEPS = 8
SEQUENCES = 2
HEAD_BLOCKS = 8


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def _rope(x, positions, theta):
    """x [s, heads, hd]; half-split rotation (the HF convention)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(layer, cfg, x):
    f32 = jnp.float32
    s = x.shape[0]
    n_q, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ layer["wq"].astype(f32)).reshape(s, n_q, hd)
    k = (x @ layer["wk"].astype(f32)).reshape(s, n_kv, hd)
    v = (x @ layer["wv"].astype(f32)).reshape(s, n_kv, hd)
    if cfg.qk_norm:
        q = _rms(q, layer["q_norm"].astype(f32), cfg.rms_norm_eps)
        k = _rms(k, layer["k_norm"].astype(f32), cfg.rms_norm_eps)
    pos = jnp.arange(s)
    q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    group = n_q // n_kv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, n_q * hd)
    return out @ layer["wo"].astype(f32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _ffn_dense(layer, cfg, x):
    f32 = jnp.float32
    out = _swiglu(x, layer["w_gate"].astype(f32), layer["w_up"].astype(f32),
                  layer["w_down"].astype(f32))
    return out, jnp.full(x.shape[0], jnp.inf, f32)


def _ffn_moe(layer, cfg, x):
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
        y = _swiglu(x, layer["w_gate"][e].astype(f32),
                    layer["w_up"][e].astype(f32),
                    layer["w_down"][e].astype(f32))
        return acc + gates[:, e, None] * y, None

    acc, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          jnp.arange(cfg.n_experts))
    return acc, gap


BLOCKS = {"dense": _ffn_dense, "moe": _ffn_moe}


def _layer_fn(cfg, block: str):
    ffn = BLOCKS[block]

    @jax.jit
    def layer_forward(layer, h):
        eps = cfg.rms_norm_eps
        f32 = h.dtype
        h = h + _attention(
            layer, cfg, _rms(h, layer["attn_norm"].astype(f32), eps))
        out, gap = ffn(
            layer, cfg, _rms(h, layer["mlp_norm"].astype(f32), eps))
        return h + out, gap

    return layer_forward


def forward(params, cfg, tokens, block: str):
    """Logits [s, vocab] (f32) at every position of one sequence."""
    return forward_with_gaps(params, cfg, tokens, block)[0]


def forward_with_gaps(params, cfg, tokens, block: str):
    """(logits [s, vocab], router gap [s]: each token's smallest over the
    layers; infinite for a block that routes nothing)."""
    if block not in BLOCKS:
        raise ValueError(f"no reference block {block!r}")
    if (block == "moe") != bool(cfg.n_experts):
        raise ValueError(f"reference block {block!r} does not fit the model")
    if (cfg.qkv_bias or cfg.norm_offset or cfg.scale_embeddings
            or cfg.rope_scaling is not None or cfg.tie_word_embeddings
            or cfg.hidden_act != "silu"):
        raise ValueError("the reference does not describe this model")
    f32 = jnp.float32
    layer_forward = _layer_fn(cfg, block)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        gaps = jnp.full(len(tokens), jnp.inf, f32)
        for layer in params["layers"]:
            h, gap = layer_forward(layer, h)
            gaps = jnp.minimum(gaps, gap)
        h = _rms(h, params["final_norm"].astype(f32), cfg.rms_norm_eps)
        # the head in column blocks: one f32 copy of a 150k-row vocabulary
        # would not fit beside the resident engine
        edges = np.linspace(0, cfg.vocab_size, HEAD_BLOCKS + 1).astype(int)
        logits = jnp.concatenate([
            _head(params["lm_head"][:, a:b], h)
            for a, b in zip(edges[:-1], edges[1:])
        ], axis=-1)
    return logits, gaps


@jax.jit
def _head(lm_head, h):
    with jax.default_matmul_precision("highest"):
        return h @ lm_head.astype(jnp.float32)


def system_logits(engine, tokens, steps: int, interpret: bool,
                  params=None, cfg=None):
    """The system's side, the smoke's ``tp_phase`` pattern: ``llama.prefill``
    with the engine's own params and chosen ``prefill_attn`` into a small
    scratch pool of the engine's own pool type (int8 pages with their scale
    pools under ``KV_QUANT_HBM=int8``), then ``llama.decode_step`` through
    that pool, greedy. ``params``/``cfg`` narrow it to some of the engine's
    layers. Returns (logits [steps + 1, vocab], the tokens fed after the
    prompt)."""
    from llm_d_kv_cache_manager_tpu.models import llama

    params = engine.params if params is None else params
    cfg = engine.model_cfg if cfg is None else cfg
    ps = engine.page_size
    quant = engine.config.kv_quant_hbm
    s = len(tokens)
    n_pages = -(-(s + steps) // ps)
    dev = engine._replicated
    k_pages, v_pages = llama.init_kv_pages(
        cfg, n_pages + 1, ps, kv_quant_hbm=quant, sharding=dev)
    scales = {}
    if quant == "int8":
        scales["k_scales"], scales["v_scales"] = llama.init_kv_scales(
            cfg, n_pages + 1, sharding=dev)

    def put(x, dtype=np.int32):
        return jax.device_put(np.asarray(x, dtype), dev)

    def step(out):
        # (logits, k_pages, v_pages[, k_scales, v_scales])
        if scales:
            scales["k_scales"], scales["v_scales"] = out[3:]
        return out[:3]

    positions = np.arange(s)[None, :]
    logits, k_pages, v_pages = step(llama.prefill(
        params, cfg, put([tokens]), put(positions),
        put(np.ones((1, s), bool), bool), k_pages, v_pages,
        put(1 + positions // ps), put(positions % ps),
        put(np.zeros((1, 0))), put(np.zeros((1,))), mesh=engine.mesh,
        attn_impl=engine.prefill_attn, interpret=interpret, **scales,
    ))
    out = [np.asarray(logits, np.float32)[0]]
    fed = []
    bt = put(1 + np.arange(n_pages)[None, :])
    for i in range(steps):
        nxt = int(np.argmax(out[-1]))
        fed.append(nxt)
        logits, k_pages, v_pages = step(llama.decode_step(
            params, cfg, put([nxt]), put([s + i]), k_pages, v_pages,
            bt, put([s + i + 1]), page_size=ps, interpret=interpret,
            mesh=engine.mesh, **scales,
        ))
        out.append(np.asarray(logits, np.float32)[0])
    return np.stack(out), fed


def _compare(engine, params, truth, cfg, block, prompt, steps, interpret):
    """Per compared position (the last of the prompt and each decode step):
    max|delta| over the vocabulary / the reference's largest |logit|, and
    the position's router gap. The system runs ``params``, the reference
    reads ``truth``. None where the system's logits are not finite."""
    got, fed = system_logits(engine, prompt, steps, interpret, params, cfg)
    if not np.isfinite(got).all():
        return None
    ref, gaps = forward_with_gaps(truth, cfg, prompt + fed, block)
    # row i: the logits after prompt + i fed tokens, on both sides
    ref = np.asarray(ref, np.float32)[len(prompt) - 1:]
    err = np.abs(got - ref).max(axis=1) / (np.abs(ref).max() + 1e-9)
    return err, np.asarray(gaps)[len(prompt) - 1:]


def check(engine, block: str, seed: int, interpret: bool,
          prompt_tokens: int = PROMPT_TOKENS,
          steps: int = DECODE_STEPS, truth=None) -> dict:
    """Prefill, then decoding through the cache, against the reference's
    full forward over the grown sequence. Logits, never sampled tokens.
    ``ok`` holds every tolerance of the block (the module's docstring).
    ``truth`` is the tree the reference reads where the engine's is a
    quantised form of it (``probe_reference.py``); else the engine's own."""
    cfg = engine.model_cfg
    truth = engine.params if truth is None else truth
    bf16 = cfg.dtype == jnp.bfloat16
    tol = TOL_BF16[block] if bf16 else {"max": TOL_F32, "p50": TOL_F32,
                                        "layer": TOL_F32}
    rng = np.random.default_rng([int(seed), 5])

    def prompt():
        return rng.integers(33, 127, prompt_tokens).tolist()

    out = {"tol": tol, "sequences": SEQUENCES, "prompt_tokens": prompt_tokens,
           "decode_steps": steps}
    errs = []
    for _ in range(SEQUENCES):
        one = _compare(engine, engine.params, truth, cfg, block, prompt(),
                       steps, interpret)
        errs += [float("inf")] if one is None else one[0].tolist()
    out["rel_err"] = max(errs)
    out["rel_err_p50"] = float(np.median(errs))
    ok = out["rel_err"] <= tol["max"] and out["rel_err_p50"] <= tol["p50"]
    if "layer" in tol and cfg.n_experts:
        # each layer alone (embedding -> that layer -> head), where a
        # position's logits depend on the routing of its own token only
        cfg1 = dataclasses.replace(cfg, n_layers=1)
        decided, tied = [], []
        for layer, true in zip(engine.params["layers"], truth["layers"]):
            one = _compare(engine, {**engine.params, "layers": [layer]},
                           {**truth, "layers": [true]}, cfg1, block, prompt(),
                           steps, interpret)
            if one is None:
                decided.append(float("inf"))
                continue
            err, gaps = one
            decided += err[gaps >= ROUTER_GAP_MIN].tolist()
            tied += err[gaps < ROUTER_GAP_MIN].tolist()
        out["layer_rel_err"] = max(decided, default=float("inf"))
        out["layer_positions"] = len(decided)
        out["layer_tied_positions"] = len(tied)
        out["layer_tied_rel_err"] = max(tied, default=0.0)
        ok = (ok and out["layer_rel_err"] <= tol["layer"]
              and len(decided) >= LAYER_DECIDED_MIN * (len(decided) + len(tied)))
    out["ok"] = bool(ok)
    return out
