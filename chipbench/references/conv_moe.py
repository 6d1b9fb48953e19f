"""The plain reference of a hybrid sparse decoder whose layers are gated
short convolutions with one attention layer in four (``LiquidAI/LFM2-8B-A1B``,
``model_type: lfm2_moe``), from the published description (the catalog's
``config`` and ``described_as``, and ``modeling_lfm2_moe.py`` of the source the
configuration names, as ISSUE 34 writes its equations down). Float32
``jax.numpy`` under ``default_matmul_precision("highest")``, nothing imported
from the program's model code, the whole sequence at once: no cache, no
state, no pages.

Every layer is ``h += Op(RMSNorm(h; attn_norm)); h += FFN(RMSNorm(h;
mlp_norm))``. What a layer's operator and FFN are is read from its own
parameters (the harness runs every layer alone as a one-layer model:
``reference.py``'s ``layer_p75``).

- A layer with ``conv_in``: ``[B | C | x] = u conv_in`` (three parts of the
  hidden size, in that order); ``z = B * x``; ``c_t = w_0 z_{t-2} + w_1
  z_{t-1} + w_2 z_t`` (``conv_w [3, d]``: one three-tap filter a channel,
  causal, zeros before the sequence; written here as three shifted
  products); ``Op = (C * c) conv_out``. No position enters.
- A layer with ``wq``: GQA, RMSNorm over each head's values of ``q`` and of
  ``k`` (learned weight), rotation by halves at the absolute position, causal
  softmax at ``1 / sqrt(head size)``, ``Op = o wo``. In query blocks, so that
  the probe's 8k-token prefix fits.
- FFN without a ``router``: SwiGLU. With one: ``s = sigmoid(x router)``;
  chosen = the k largest of ``s + b`` (``b`` the expert bias, which chooses
  and does not weigh); gates ``s`` of the chosen / (their sum + 1e-6) x
  ``routed_scaling_factor``; ``sum_e g_e SwiGLU_e(x)``; no shared expert. The
  1e-6 is the family's modelling code's (DeepSeek-V3's, which ``mla_moe``
  follows, has 1e-20).
- A final RMSNorm; the head is the embedding, transposed (tied).

It reads the tree ``llama.init_params`` builds for such a model:
``attn_norm, mlp_norm``; ``conv_in, conv_w, conv_out`` or ``wq, wk, wv, wo,
q_norm, k_norm``; ``router, router_bias, w_gate/w_up/w_down [E, ...]`` or a
dense layer's ``w_gate/w_up/w_down``; ``embed``, ``final_norm``.

``system`` is this reference's own system side, so that ``correct`` covers the
served programs and every way convolution state reaches a token: a COLD
prefill of the prompt's first half (whole pages), a WARM prefill of the rest
but its last token against those pages (its first token takes its state from
the slot of a finished, cached page: what a prefix hit does), a one-token
prefill of the last token that starts INSIDE a page (its whole state comes
from the slot the chunk before left there: a chunk boundary inside a page),
then the decode steps through ``llama.decode_step``, the first of which, at
the harness's sizes, is the first position after a page boundary and takes its
state straight from the finished page's slot. For a one-layer tree it makes
the pools of the kind of layer it finds.

Tolerances (what an error is: ``reference.py``). My chip runs, PR 34, at the
published widths, 14 layers: the harness's own check in the cell's runs (two
prompts of 128 tokens, 8 decode steps) and ``probe_conv_moe.py``, at the TIMED
sizes (seed 11: an 8192-token prefix filled in 1024-token pieces, a 128-token
turn, 8 decode steps over a page boundary) and at the harness's (seeds 12, 13:
one prompt), with the five controls that must read not correct (the probe's
docstring). PERF.md section 6 has every line.

As in ``moe`` and ``mla_moe`` the whole model reads routing: 4 experts of 32
are chosen by sigmoid scores (25 to 40 of a run's layer-alone positions have
their 4th and 5th choice within ``ROUTER_GAP_MIN``), a chosen expert weighs
about a quarter of the routed sum, and a token whose 4th and 5th choice lie
within bf16's rounding of the router's logits takes another expert than the
reference does: the layers alone read up to 0.51 at such a position, and
through 12 expert layers the worst position reads 0.15 to 0.37 and the median
position 0.05 to 0.25. Only the layers run alone hold the precision.

- ``layer_p75`` 1.0e-2: the third quartile of ALL positions of the layers run
  alone (14 layers x 9 positions a prompt; ``ROUTER_GAP_MIN`` sets none
  aside). Convolution layers and dense FFNs route nothing and read the
  arithmetic alone (conv + dense 0.60e-2 to 0.62e-2, conv + experts 0.57e-2 to
  0.60e-2, attention + experts 0.63e-2 to 0.71e-2). Sound: 0.599e-2 to
  0.640e-2. The nearest precision below the stated one, every matmul weight
  and expert rounded through int8: 1.96e-2 to 2.02e-2 (timed sizes and the
  harness's, three seeds), not correct by this limit (at the timed sizes by
  ``p50``'s too, by ``max``'s never). The limit is 1.56 x the sound runs'
  largest and 0.51 x the control's smallest. The bias that weighs reads
  3.96e-2 here and fails by this limit alone (its ``max`` 0.22 and ``p50`` 0.16
  pass); reversed taps and exchanged gates read 1.10 and 1.11.
- ``p50`` 0.5 and ``max`` 0.7 against a program that is not this model, as far
  as routing lets them. Sound: ``p50`` 0.053 (timed sizes: 8k tokens of
  context average the swaps out) to 0.251 (one prompt at the harness's sizes),
  ``max`` 0.149 to 0.370. The controls that are another model: state slots
  zeroed before the turn's warm prefills 0.95 to 1.00 and 1.19 to 1.28 (three
  seeds; the layers alone cannot see it: two positions of nine a layer, under
  the quartile), taps reversed 1.31 and 1.45, gates exchanged 1.23 and 1.31.
  ``p50`` is 2.0 x the sound runs' largest and 0.53 x the controls' smallest,
  ``max`` 1.9 x and 0.59 x. (A first ``p50`` of 0.2 turned one sound prompt
  false: 0.251 at seed 13.)
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as common

#: bf16 system against the f32 reference (readings: PERF.md section 6, PR 34):
#: the worst and the median position of the whole model, the third quartile
#: of the positions of the layers alone
TOL_BF16 = {"max": 0.7, "p50": 0.5, "layer_p75": 1.0e-2}
#: told in the result line, compared with nothing: the distance, in choice
#: scores (sigmoid + bias), between the last chosen expert and the first that
#: is not (``mla_moe``'s)
ROUTER_GAP_MIN = 0.0125
#: what keeps the renormalised gates' sum from zero (the published code's)
GATE_EPS = 1e-6
#: query rows a block of the attention: the score tile is [heads, block,
#: keys] whatever the sequence's length
QUERY_BLOCK = 512


def _shift(z, n: int):
    """``z [s, d]`` moved ``n`` tokens later, zeros before the sequence."""
    if n == 0:
        return z
    return jnp.concatenate([jnp.zeros_like(z[:n]), z[:-n]], axis=0)


def _conv(layer, cfg, u):
    f32 = jnp.float32
    gate_b, gate_c, x = jnp.split(u @ layer["conv_in"].astype(f32), 3, axis=-1)
    z = gate_b * x
    taps = layer["conv_w"].astype(f32)  # [K, d]; the last weighs z_t
    k = taps.shape[0]
    c = sum(taps[j] * _shift(z, k - 1 - j) for j in range(k))
    return (gate_c * c) @ layer["conv_out"].astype(f32)


def _attention(layer, cfg, x):
    f32 = jnp.float32
    s = x.shape[0]
    n_q, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ layer["wq"].astype(f32)).reshape(s, n_q, hd)
    k = (x @ layer["wk"].astype(f32)).reshape(s, n_kv, hd)
    v = (x @ layer["wv"].astype(f32)).reshape(s, n_kv, hd)
    q = common._rms(q, layer["q_norm"].astype(f32), cfg.rms_norm_eps)
    k = common._rms(k, layer["k_norm"].astype(f32), cfg.rms_norm_eps)
    pos = jnp.arange(s)
    q = common._rope(q, pos, cfg.rope_theta)
    k = common._rope(k, pos, cfg.rope_theta)
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    out = []
    for lo in range(0, s, QUERY_BLOCK):  # the same numbers, a block at a time
        hi = min(lo + QUERY_BLOCK, s)
        scores = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / np.sqrt(hd)
        causal = pos[lo:hi, None] >= pos[None, :hi]
        probs = jax.nn.softmax(
            jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", probs, v[:hi]))
    return jnp.concatenate(out).reshape(s, n_q * hd) @ layer["wo"].astype(f32)


def _operator(layer, cfg, x):
    return (_conv if "conv_in" in layer else _attention)(layer, cfg, x)


def _ffn(layer, cfg, x):
    """(output, router gap [s]; infinite where the layer routes nothing)."""
    f32 = jnp.float32
    if "router" not in layer:
        out = common._swiglu(x, layer["w_gate"].astype(f32),
                             layer["w_up"].astype(f32),
                             layer["w_down"].astype(f32))
        return out, jnp.full(x.shape[0], jnp.inf, f32)
    k = cfg.n_experts_per_tok
    scores = jax.nn.sigmoid(x @ layer["router"].astype(f32))
    edge, topi = jax.lax.top_k(scores + layer["router_bias"].astype(f32), k + 1)
    gap = edge[:, -2] - edge[:, -1]
    topi = topi[:, :k]
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    if cfg.norm_topk_prob:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + GATE_EPS)
    topv = topv * cfg.routed_scaling_factor
    gates = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], topi
    ].set(topv)

    def one_expert(acc, e):
        y = common._swiglu(x, layer["w_gate"][e].astype(f32),
                           layer["w_up"][e].astype(f32),
                           layer["w_down"][e].astype(f32))
        return acc + gates[:, e, None] * y, None

    acc, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          jnp.arange(layer["router"].shape[1]))
    return acc, gap


@jax.jit
def _tied_head(embed_rows, h):
    with jax.default_matmul_precision("highest"):
        return h @ embed_rows.astype(jnp.float32).T


def forward(params, cfg, tokens, rows=None):
    """(logits [s, vocab] f32, router gap [s]: each token's smallest over
    the layers). ``rows``: the positions whose logits are wanted (default
    every one: the harness's contract; the probe asks for a long sequence's
    last few, whose full table would not fit)."""
    if not any("conv_in" in layer for layer in params["layers"]) and (
            cfg.layer_types is None):
        raise ValueError("reference 'conv_moe' does not fit the model")
    if (cfg.norm_offset or cfg.scale_embeddings or cfg.rope_scaling is not None
            or not cfg.tie_word_embeddings or cfg.hidden_act != "silu"
            or cfg.qkv_bias or not cfg.qk_norm or cfg.conv_bias
            or cfg.moe_scoring != "sigmoid" or cfg.n_shared_experts):
        raise ValueError("the reference does not describe this model")
    f32 = jnp.float32
    layer_forward = common._layer_fn(cfg, _ffn, _operator)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        gaps = jnp.full(len(tokens), jnp.inf, f32)
        for layer in params["layers"]:
            h, gap = layer_forward(layer, h)
            gaps = jnp.minimum(gaps, gap)
        if rows is not None:
            h, gaps = h[jnp.asarray(rows)], gaps[jnp.asarray(rows)]
        h = common._rms(h, params["final_norm"].astype(f32), cfg.rms_norm_eps)
        # the head in blocks of the vocabulary: one f32 copy of the embedding
        # beside the resident engine is not needed
        edges = np.linspace(0, cfg.vocab_size, common.HEAD_BLOCKS + 1).astype(int)
        logits = jnp.concatenate([
            _tied_head(params["embed"][a:b], h)
            for a, b in zip(edges[:-1], edges[1:])
        ], axis=-1)
    return logits, gaps


def pool_config(params, cfg):
    """``cfg`` with the depth and the layer kinds ``params`` really has (the
    harness hands a one-layer tree ``replace(cfg, n_layers=1)``, whose
    ``layer_types`` are still the whole model's)."""
    kinds = tuple("conv" if "conv_in" in layer else "full_attention"
                  for layer in params["layers"])
    return dataclasses.replace(cfg, n_layers=len(kinds), layer_types=kinds)


def pieces(s: int, page: int) -> list:
    """The prompt's prefill chunks ``[(lo, hi)]``: module docstring."""
    half = s // 2 // page * page
    if not half or half >= s - 1:
        raise ValueError("the prompt's first half must hold a whole page")
    return [(0, half), (half, s - 1), (s - 1, s)]


def system(engine, tokens, steps: int, interpret: bool, params=None, cfg=None):
    """The system's side (module docstring): (logits [steps + 1, vocab], the
    tokens fed after the prompt)."""
    from llm_d_kv_cache_manager_tpu.models import llama

    params = engine.params if params is None else params
    cfg = pool_config(params, engine.model_cfg if cfg is None else cfg)
    ps = engine.page_size
    s = len(tokens)
    n_pages = -(-(s + steps) // ps)
    dev = engine._replicated
    k_pages, v_pages = llama.init_kv_pages(cfg, n_pages + 1, ps, sharding=dev)
    state = llama.init_state_pages(cfg, n_pages + 1, sharding=dev)
    run = dict(mesh=engine.mesh, attn_impl=engine.prefill_attn,
               interpret=interpret)

    def put(x, dtype=np.int32):
        return jax.device_put(np.asarray(x, dtype), dev)

    def step(out):
        """(logits, k_pages, v_pages[, state]) -> logits; pools kept."""
        nonlocal k_pages, v_pages, state
        logits, k_pages, v_pages, *rest = out
        if rest:
            (state,) = rest
        return np.asarray(logits, np.float32)[0]

    def stateful():
        return {} if state is None else {"state_pages": state}

    table = 1 + np.arange(n_pages)
    for lo, hi in pieces(s, ps):
        positions = np.arange(lo, hi)[None, :]
        logits = step(llama.prefill(
            params, cfg, put([tokens[lo:hi]]), put(positions),
            put(np.ones((1, hi - lo), bool), bool), k_pages, v_pages,
            put(1 + positions // ps), put(positions % ps),
            put(table[None, : -(-lo // ps)]), put([lo]), **run, **stateful(),
        ))
    out = [logits]
    fed = []
    bt = put(table[None, :])
    for i in range(steps):
        nxt = int(np.argmax(out[-1]))
        fed.append(nxt)
        out.append(step(llama.decode_step(
            params, cfg, put([nxt]), put([s + i]), k_pages, v_pages, bt,
            put([s + i + 1]), page_size=ps, interpret=interpret,
            mesh=engine.mesh, **stateful(),
        )))
    return np.stack(out), fed
