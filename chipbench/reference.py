"""What the plain references share, and the check that decides the
reference part of ``correct``.

A reference is a file, ``references/<name>.py``, found by the name a
configuration gives under ``chipbench.reference`` (``load``; there is no
table of names). It is a straightforward float32 ``jax.numpy`` forward under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, nothing imported from the program's model code. Its docstring
states the published description it follows and the reason for each of its
tolerances. It gives:

- ``forward(params, cfg, tokens) -> (logits [s, vocab] f32, gaps [s] or
  None)``: the logits at every position of one sequence and, where tokens
  are routed, each token's router gap (its smallest over the layers);
- ``TOL_BF16``: its bounds for a bf16 system against the f32 reference:
  ``max`` (the worst compared position), ``p50`` (the median position) and,
  where tokens are routed, ``layer_p75`` (the third quartile of the
  positions of every layer run alone), with ``ROUTER_GAP_MIN`` beside it
  (the gap that splits the positions the result line tells of);
- optionally ``system(engine, tokens, steps, interpret, params=None,
  cfg=None) -> (logits [steps + 1, vocab], fed)``: what the program's own
  served programs produce for that input. Absent, ``system_logits`` here
  (``llama.prefill`` then ``llama.decode_step``, one causal token a step). A
  model whose generation step is another brings its own, so that ``correct``
  is decided on the path the window times.

The comparison itself (``common_check``: the positions compared, the norms,
``ok``) is one for every reference and no file replaces it.

Here: what the references in the tree share (``_rms``, ``_rope``,
``_swiglu``, ``_attention``: causal GQA with per-head RMSNorm on q and k;
``decoder_forward``: pre-norm layers and the column-blocked head around the
FFN a reference gives), the default system side, the comparison, and
``check`` / ``forward`` / ``forward_with_gaps`` as resolvers over the files.
A reference with another mask, cache or step writes that part in its file.

The reference reads the engine's own parameters (the tree
``llama.init_params`` builds: ``embed, final_norm, lm_head, layers[i].{
attn_norm, wq, wk, wv, wo, q_norm, k_norm, mlp_norm, w_gate, w_up, w_down,
router}``) and casts what it touches to f32, layer by layer.

An error is max|delta| over the vocabulary / the reference's largest
|logit|, at one compared position (the last of the prompt, each decode
step). The float32 tiny presets of the CPU tests agree to ``TOL_F32``
(accumulation order only).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.fleet import BenchFailure

HERE = os.path.dirname(os.path.abspath(__file__))

#: f32 system (the CPU tests' tiny presets) against the f32 reference
TOL_F32 = 2e-4

PROMPT_TOKENS = 128
DECODE_STEPS = 8
SEQUENCES = 2
HEAD_BLOCKS = 8


def load(name: str):
    """The module ``references/<name>.py``."""
    path = os.path.join(HERE, "references", f"{name}.py")
    if not os.path.isfile(path):
        raise BenchFailure(f"no reference {name!r}: looked for {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + name.replace(".", "_").replace("-", "_"),
        path,
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- what the references in the tree share -----------------------------------
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


def _layer_fn(cfg, ffn, attention):
    @jax.jit
    def layer_forward(layer, h):
        eps = cfg.rms_norm_eps
        f32 = h.dtype
        h = h + attention(
            layer, cfg, _rms(h, layer["attn_norm"].astype(f32), eps))
        out, gap = ffn(
            layer, cfg, _rms(h, layer["mlp_norm"].astype(f32), eps))
        return h + out, gap

    return layer_forward


def decoder_forward(params, cfg, tokens, ffn, attention=_attention):
    """(logits [s, vocab], gaps [s]) of a pre-norm decoder: embedding, then
    per layer ``h += attention(layer, cfg, norm(h))`` and ``h += ffn(layer,
    cfg, norm(h))[0]``, the final norm and the head. ``ffn`` returns (output,
    router gap [s]: infinite where it routes nothing); the gaps returned are
    each token's smallest over the layers."""
    if (cfg.qkv_bias or cfg.norm_offset or cfg.scale_embeddings
            or cfg.rope_scaling is not None or cfg.tie_word_embeddings
            or cfg.hidden_act != "silu"):
        raise ValueError("the reference does not describe this model")
    f32 = jnp.float32
    layer_forward = _layer_fn(cfg, ffn, attention)
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


# -- the system's side, unless the reference brings its own ------------------
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


# -- the comparison -----------------------------------------------------------
def _compare(engine, params, truth, cfg, ref, prompt, steps, interpret):
    """Per compared position (the last of the prompt and each decode step):
    max|delta| over the vocabulary / the reference's largest |logit|, and
    the position's router gap (infinite where ``ref`` routes nothing). The
    system runs ``params``, the reference ``ref`` reads ``truth``. None
    where the system's logits are not finite."""
    system = getattr(ref, "system", system_logits)
    got, fed = system(engine, prompt, steps, interpret, params, cfg)
    if not np.isfinite(got).all():
        return None
    want, gaps = ref.forward(truth, cfg, prompt + fed)
    # row i: the logits after prompt + i fed tokens, on both sides
    want = np.asarray(want, np.float32)[len(prompt) - 1:]
    err = np.abs(got - want).max(axis=1) / (np.abs(want).max() + 1e-9)
    if gaps is None:
        return err, np.full(len(err), np.inf, np.float32)
    return err, np.asarray(gaps)[len(prompt) - 1:]


def common_check(engine, ref, seed: int, interpret: bool,
                 prompt_tokens: int = PROMPT_TOKENS,
                 steps: int = DECODE_STEPS, truth=None) -> dict:
    """The system's side (``ref.system``, else prefill then decoding through
    the cache) against the reference's full forward over the grown sequence.
    Logits, never sampled tokens. ``ok`` holds every tolerance of ``ref``
    (its docstring). ``truth`` is the tree the reference reads where the
    engine's is a quantised form of it (``probe_reference.py``); else the
    engine's own."""
    cfg = engine.model_cfg
    truth = engine.params if truth is None else truth
    bf16 = cfg.dtype == jnp.bfloat16
    tol = ref.TOL_BF16 if bf16 else dict.fromkeys(ref.TOL_BF16, TOL_F32)
    rng = np.random.default_rng([int(seed), 5])

    def prompt():
        return rng.integers(33, 127, prompt_tokens).tolist()

    out = {"tol": tol, "sequences": SEQUENCES, "prompt_tokens": prompt_tokens,
           "decode_steps": steps}
    errs = []
    for _ in range(SEQUENCES):
        one = _compare(engine, engine.params, truth, cfg, ref, prompt(),
                       steps, interpret)
        errs += [float("inf")] if one is None else one[0].tolist()
    out["rel_err"] = max(errs)
    out["rel_err_p50"] = float(np.median(errs))
    ok = out["rel_err"] <= tol["max"] and out["rel_err_p50"] <= tol["p50"]
    if "layer_p75" in tol:
        # each layer alone (embedding -> that layer -> head), where a
        # position's logits depend on the routing of its own token only
        cfg1 = dataclasses.replace(cfg, n_layers=1)
        alone, decided, tied = [], [], []
        for layer, true in zip(engine.params["layers"], truth["layers"]):
            one = _compare(engine, {**engine.params, "layers": [layer]},
                           {**truth, "layers": [true]}, cfg1, ref, prompt(),
                           steps, interpret)
            if one is None:
                alone.append(float("inf"))
                continue
            err, gaps = one
            alone += err.tolist()
            decided += err[gaps >= ref.ROUTER_GAP_MIN].tolist()
            tied += err[gaps < ref.ROUTER_GAP_MIN].tolist()
        # compared: the third quartile of every position of every layer
        out["layer_rel_err_p75"] = (
            float(np.quantile(alone, 0.75)) if np.isfinite(alone).all()
            else float("inf"))  # a layer whose logits are not finite
        # told, not compared: the worst position on either side of
        # ``ROUTER_GAP_MIN`` (a swapped expert reads 5e-2 to 3e-1)
        out["layer_rel_err"] = max(decided, default=0.0)
        out["layer_positions"] = len(decided)
        out["layer_tied_positions"] = len(tied)
        out["layer_tied_rel_err"] = max(tied, default=0.0)
        ok = ok and out["layer_rel_err_p75"] <= tol["layer_p75"]
    out["ok"] = bool(ok)
    return out


# -- by name ------------------------------------------------------------------
def check(engine, name: str, seed: int, interpret: bool, **sizes) -> dict:
    """``common_check`` with ``references/<name>.py`` (``sizes``:
    ``prompt_tokens``, ``steps`` and ``truth``)."""
    return common_check(engine, load(name), seed, interpret, **sizes)


def forward(params, cfg, tokens, name: str):
    """Logits [s, vocab] (f32) at every position of one sequence."""
    return load(name).forward(params, cfg, tokens)[0]


def forward_with_gaps(params, cfg, tokens, name: str):
    """(logits [s, vocab], router gap [s]: each token's smallest over the
    layers; infinite for a reference that routes nothing)."""
    logits, gaps = load(name).forward(params, cfg, tokens)
    if gaps is None:
        gaps = jnp.full(len(tokens), jnp.inf, jnp.float32)
    return logits, gaps
