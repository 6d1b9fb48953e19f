"""The plain reference of a sparse decoder with window and full attention
layers in one model (``arcee-ai/Trinity-Large-Preview``, ``model_type:
afmoe``), from the published description (the catalog's ``config`` and
``described_as``, and ``modeling_afmoe.py`` of the source the configuration
names, as ISSUE 43 writes its equations down). Float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, nothing imported from the program's
model code, the whole sequence at once: no cache, no pages, no table.

``d`` hidden, ``H`` query heads, ``G`` key/value heads, ``d_h`` head size,
window ``W``, ``E`` routed experts top-``k``, ``eps`` and ``theta`` the
configuration's.

- Embedding: ``h_0 = Emb[token] * sqrt(d)`` (``mup_enabled``).
- A layer, four RMSNorms with weights of their own: ``a = h + N2(Attn(N1
  h))``; ``h' = a + N4(F(N3 a))`` (``attn_norm, attn_post_norm, mlp_norm,
  mlp_post_norm``).
- ``Attn(x)`` at position ``t``: ``q = x W_q``, ``k = x W_k``, each head
  normed by an RMSNorm over its ``d_h`` values (one weight for q, one for k),
  ``v = x W_v``, ``g = sigmoid(x W_g)`` of ``H d_h`` values. A SLIDING layer
  (one whose parameters hold ``window``) rotates q and k at ``t`` (halves,
  the whole head) and ``t`` sees ``j`` with ``t - W < j <= t``: ``W``
  positions, itself among them. A FULL layer rotates nothing (it takes no
  positions at all) and ``t`` sees every ``j <= t``. Softmax of ``q . k /
  sqrt(d_h)``, ``H / G`` query heads a key/value head; ``Attn = (concat(heads)
  * g) W_o``. In query blocks (and, on a sliding layer, over the keys a block
  can see), so that 6-8k positions fit.
- ``F`` of a layer without a ``router``: ``(silu(x W_gate) * x W_up)
  W_down``. With one: ``Shared(x) + sum_j w_j f_{e_j}(x)``; ``s = sigmoid(x
  W_r)`` in float32 over all ``E``; the ``k`` largest of ``s + b`` are chosen
  (``b`` chooses and does not weigh); ``w_j = route_scale * s[e_j] / (sum_j
  s[e_j] + 1e-20)``; ``f_e`` and ``Shared`` are SwiGLUs. One rank's share: the
  tree holds the experts ``[expert_first, expert_first + held)``, a place
  whose expert lies elsewhere adds nothing here, the shared expert is whole.
- ``logits = RMSNorm(h_L) W_head``, the head untied (its slice of the
  vocabulary).

Departures from a naive reading, each under ``assumed`` in the configuration's
file: the gate reads the normed input (as ``W_q`` does) and multiplies before
``W_o``; halves, not pairs; ``1e-20``; ``n_group`` = ``topk_group`` = 1; the
norms are plain RMSNorms at inference (their depth scaling is an
initialisation).

It reads the tree ``llama.init_params`` builds for such a model: ``attn_norm,
attn_post_norm, mlp_norm, mlp_post_norm, wq, wk, wv, wg, wo, q_norm, k_norm``,
``window`` on a sliding layer; ``router, router_bias, w_gate/w_up/w_down
[held, ...], ws_gate/ws_up/ws_down`` or a dense layer's ``w_gate/w_up/
w_down``; ``embed, final_norm, lm_head``.

``system`` is this reference's own system side, because the harness hands it
128 tokens and the window is 4096: after the harness's prompt (a COLD
prefill) it feeds tokens of its own, the prompt's over again, in WARM chunks
of ``CHUNK`` through ``llama.prefill(..., return_all_logits=True)`` against the
two pools it makes itself (the full layers' pool with a page a block of the
whole sequence, the window pool with pages for a window and a chunk, taken
from a free list and given back as the sequence moves on, so that page ids
are reused while the run lasts), until the sequence stands at ``grow_to(cfg)``
= ``W + 3 W / 4`` positions and on to ``TAIL`` slots before a page's end (7180
at the published window: 3084 positions past it, every one compared); then
``steps`` greedy decode steps through ``llama.decode_step``, in which a window
page falls out of the lane's table (step 3) and a page boundary is crossed
(step 4). It returns the logits of every position from the prompt's last on
and all it fed. For a one-layer tree it makes the pools of the kind of layer
it finds.

Tolerances (what an error is: ``reference.py``). My chip runs, PR 43, at the
published widths, 5 layers, the harness's own check (two prompts of 128
tokens grown to 7180 positions, 8 decode steps, then every layer alone:
7053 positions a sequence, every one compared) in the cell's runs and in
``probe_swa.py`` with the eight controls that must read not correct (its
docstring). PERF.md section 6 has every line.

As in ``moe``, ``mla_moe`` and ``conv_moe`` the whole model reads routing: 4
experts of 256 are chosen by sigmoid scores, a chosen expert weighs about a
quarter of the routed sum, and a token whose 4th and 5th choice lie within
bf16's rounding of the router's logits takes another expert than the
reference does, so the worst of 14k positions reads 0.27 to 0.39 whatever
the program. What is new here is that the MEDIAN position holds the
precision too: most of a sequence's 7053 compared positions have no swap
before them that reaches them.

- ``layer_p75`` 0.8e-2: the third quartile of ALL positions of the layers run
  alone (5 layers x 7053 positions a prompt). Sound: 0.423e-2 to 0.454e-2
  (16 runs). The nearest precision below the stated one, every matmul
  weight and expert rounded through int8: 1.469e-2. The limit is 1.76 x the
  sound runs' largest and 0.54 x the control's. The window ignored (the
  sliding layers keep and see their whole context) reads 7.36e-2 here and
  fails by this limit alone: it differs from this model only past the
  window, which is why ``system`` grows a sequence to 1.75 windows (at 1.25
  it read 0.501e-2 and passed every limit); either post-norm, the gate, the
  embedding's factor and the gates not renormalised read 0.18 to 0.74.
- ``p50`` 1.25e-2: the median position of the whole model. Sound: 0.619e-2 to
  0.704e-2. Controls that only this limit holds: rope applied on the full
  layer 4.28e-2 (one layer of five: the layers alone read 0.453e-2, its
  ``max`` 0.34); int8 weights 2.26e-2. 1.78 x the sound runs' largest, 0.55 x
  the smallest control's.
- ``max`` 0.7 against a program that is not this model, as far as routing
  lets it: sound 0.27 to 0.39; the embedding's factor left out 1.00, the
  attention's post-norm left out 1.32 (1.8 x the sound runs' largest, 0.70 x
  the controls' smallest that it holds; the window, the rope, int8, the FFN's
  post-norm and the renormalisation read 0.34 to 0.60 and are held by the
  other two).

A window off by one position moves a near-uniform softmax over 4096 keys by
2e-4 and cannot be held in bfloat16 on the chip: ``tests/test_swa.py`` holds
it in float32 (windows of W - 1, W and W + 1 differ by over 100 x the
tolerance).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as common

#: bf16 system against the f32 reference (readings: PERF.md section 6, PR 43):
#: the worst and the median position of the whole model, the third quartile
#: of the positions of the layers alone
TOL_BF16 = {"max": 0.7, "p50": 1.25e-2, "layer_p75": 0.8e-2}
#: told in the result line, compared with nothing: the distance, in choice
#: scores (sigmoid + bias), between the last chosen expert and the first that
#: is not. A random router's 256 scores lie about 0.016 apart at the fourth
#: largest and bf16 moves a score by about 0.001; at ``mla_moe``'s 0.0125
#: more than half of this model's positions counted as tied (chip run, PR 43)
ROUTER_GAP_MIN = 0.002
#: what keeps the renormalised gates' sum from zero (the published code's)
GATE_EPS = 1e-20
#: query rows a block of the attention, rows a block of the dense FFN (the
#: float32 temporaries of 7k positions beside a resident engine)
QUERY_BLOCK = 256
FFN_BLOCK = 2048
#: tokens a warm chunk of ``system`` (whole pages; at most half a window)
CHUNK = 512
#: slots before a page's end at which ``system``'s fill stops
TAIL = 4


def _is_sliding(layer) -> bool:
    return "window" in layer


def _attention(layer, cfg, x):
    f32 = jnp.float32
    s = x.shape[0]
    n_q, n_kv, hd, w = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.sliding_window
    sliding = _is_sliding(layer)
    q = (x @ layer["wq"].astype(f32)).reshape(s, n_q, hd)
    k = (x @ layer["wk"].astype(f32)).reshape(s, n_kv, hd)
    v = (x @ layer["wv"].astype(f32)).reshape(s, n_kv, hd)
    gate = jax.nn.sigmoid(x @ layer["wg"].astype(f32))
    q = common._rms(q, layer["q_norm"].astype(f32), cfg.rms_norm_eps)
    k = common._rms(k, layer["k_norm"].astype(f32), cfg.rms_norm_eps)
    pos = jnp.arange(s)
    if sliding:  # a full layer takes no positions
        q = common._rope(q, pos, cfg.rope_theta)
        k = common._rope(k, pos, cfg.rope_theta)
    q = q.reshape(s, n_kv, n_q // n_kv, hd)  # the query heads of a KV head
    out = []
    for lo in range(0, s, QUERY_BLOCK):  # the same numbers, a block at a time
        hi = min(lo + QUERY_BLOCK, s)
        first = max(lo - w + 1, 0) if sliding else 0  # no key before it is seen
        scores = jnp.einsum(
            "qcgd,kcd->cgqk", q[lo:hi], k[first:hi]) / np.sqrt(hd)
        seen = pos[lo:hi, None] >= pos[None, first:hi]
        if sliding:
            seen &= pos[lo:hi, None] - pos[None, first:hi] < w
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("cgqk,kcd->qcgd", probs, v[first:hi]))
    heads = jnp.concatenate(out).reshape(s, n_q * hd)
    return (heads * gate) @ layer["wo"].astype(f32)


def _ffn(layer, cfg, x):
    """(output, router gap [s]; infinite where the layer routes nothing)."""
    f32 = jnp.float32
    if "router" not in layer:
        gate, up, down = (layer[name].astype(f32)
                          for name in ("w_gate", "w_up", "w_down"))
        out = jnp.concatenate([  # the same numbers, a block of rows at a time
            common._swiglu(x[lo: lo + FFN_BLOCK], gate, up, down)
            for lo in range(0, x.shape[0], FFN_BLOCK)])
        return out, jnp.full(x.shape[0], jnp.inf, f32)
    k = cfg.n_experts_per_tok
    held, first = layer["w_gate"].shape[0], cfg.expert_first
    scores = jax.nn.sigmoid(x @ layer["router"].astype(f32))
    edge, topi = jax.lax.top_k(scores + layer["router_bias"].astype(f32), k + 1)
    gap = edge[:, -2] - edge[:, -1]
    topi = topi[:, :k]
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + GATE_EPS)
    topv = topv * cfg.routed_scaling_factor
    gates = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], topi
    ].set(topv)

    def one_expert(acc, j):  # the j-th held expert is expert first + j
        y = common._swiglu(x, layer["w_gate"][j].astype(f32),
                           layer["w_up"][j].astype(f32),
                           layer["w_down"][j].astype(f32))
        return acc + jax.lax.dynamic_index_in_dim(
            gates, first + j, axis=1, keepdims=True) * y, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(held))
    shared = common._swiglu(x, layer["ws_gate"].astype(f32),
                            layer["ws_up"].astype(f32),
                            layer["ws_down"].astype(f32))
    return shared + routed, gap


def _layer_fn(cfg):
    @jax.jit
    def layer_forward(layer, h):
        f32, eps = h.dtype, cfg.rms_norm_eps

        def norm(name, x):
            return common._rms(x, layer[name].astype(f32), eps)

        a = h + norm("attn_post_norm",
                     _attention(layer, cfg, norm("attn_norm", h)))
        out, gap = _ffn(layer, cfg, norm("mlp_norm", a))
        return a + norm("mlp_post_norm", out), gap

    return layer_forward


def forward(params, cfg, tokens, rows=None):
    """(logits [s, vocab] f32, router gap [s]: each token's smallest over
    the layers). ``rows``: the positions whose logits are wanted (default
    every one: the harness's contract)."""
    if not (cfg.sliding_window and cfg.attn_output_gate and cfg.sandwich_norm):
        raise ValueError("reference 'swa_moe' does not fit the model")
    if (cfg.norm_offset or not cfg.scale_embeddings
            or cfg.rope_scaling is not None or cfg.tie_word_embeddings
            or cfg.hidden_act != "silu" or cfg.qkv_bias or not cfg.qk_norm
            or cfg.moe_scoring != "sigmoid" or not cfg.norm_topk_prob
            or cfg.n_shared_experts != 1 or cfg.n_zero_experts):
        raise ValueError("the reference does not describe this model")
    f32 = jnp.float32
    layer_forward = _layer_fn(cfg)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        h = h * np.sqrt(cfg.hidden_size)
        gaps = jnp.full(len(tokens), jnp.inf, f32)
        for layer in params["layers"]:
            h, gap = layer_forward(layer, h)
            gaps = jnp.minimum(gaps, gap)
        if rows is not None:
            h, gaps = h[jnp.asarray(rows)], gaps[jnp.asarray(rows)]
        h = common._rms(h, params["final_norm"].astype(f32), cfg.rms_norm_eps)
        edges = np.linspace(0, cfg.vocab_size, common.HEAD_BLOCKS + 1).astype(int)
        logits = jnp.concatenate([
            common._head(params["lm_head"][:, a:b], h)
            for a, b in zip(edges[:-1], edges[1:])
        ], axis=-1)
    return logits, gaps


# -- the system's side --------------------------------------------------------
def pool_config(params, cfg):
    """``cfg`` with the depth and the layer kinds ``params`` really has (the
    harness hands a one-layer tree ``replace(cfg, n_layers=1)``, whose
    ``layer_types`` are still the whole model's)."""
    kinds = tuple("sliding_attention" if _is_sliding(layer) else "full_attention"
                  for layer in params["layers"])
    return dataclasses.replace(cfg, n_layers=len(kinds), layer_types=kinds)


def grow_to(cfg, page: int, at_least: int = 0) -> int:
    """How long ``system`` grows a sequence: three quarters of a window past
    the window (3072 positions at the published 4096; ``at_least`` where a
    prompt is longer), then to ``TAIL`` slots (half a page where the page is
    smaller) before a page's end. A quarter was not enough: a program whose
    sliding layers see their whole context differs from this model only past
    the window, and ``layer_p75`` sees a fault only where it moves over a
    quarter of the layer-alone positions (four sliding layers of five x 3072
    of 7053 positions = 35 %; at W + 1024 16 %: chip runs, PR 43)."""
    w = cfg.sliding_window
    whole = -(-max(w + max(3 * w // 4, 2 * page), at_least) // page) * page
    return whole + page - min(TAIL, page // 2)


def first_block(pos: int, window: int, page: int) -> int:
    """The first page (block of ``page`` positions) the query at ``pos`` of a
    sliding layer can see a slot of."""
    return max(pos - window + 1, 0) // page


class WindowTable:
    """One sequence's window pages, as the engine's block manager keeps
    them: the pages of the blocks ``first..``, taken from a free list and
    given back once every position in them lies a window behind."""

    def __init__(self, n_pages: int, window: int, page: int):
        self.free = list(range(n_pages - 1, 0, -1))  # page 0 is reserved
        self.window, self.page = window, page
        self.first, self.pages = 0, []

    def move_to(self, query_pos: int, end: int) -> None:
        """Give back what the query at ``query_pos`` no longer sees, take
        pages through position ``end - 1``."""
        drop = first_block(query_pos, self.window, self.page) - self.first
        if drop > 0:
            self.free.extend(self.pages[:drop])
            del self.pages[:drop]
            self.first += drop
        while (self.first + len(self.pages)) * self.page < end:
            self.pages.append(self.free.pop())

    def row(self, width: int) -> np.ndarray:
        out = np.zeros((1, width), np.int32)
        held = self.pages[:width]
        out[0, : len(held)] = held
        return out

    def page_of(self, positions: np.ndarray) -> np.ndarray:
        return np.asarray(self.pages, np.int32)[positions // self.page - self.first]


def system(engine, tokens, steps: int, interpret: bool, params=None, cfg=None):
    """The system's side (module docstring): (logits [fed + 1, vocab], the
    tokens fed after the prompt)."""
    from llm_d_kv_cache_manager_tpu.models import llama

    params = engine.params if params is None else params
    cfg = pool_config(params, engine.model_cfg if cfg is None else cfg)
    ps, w = engine.page_size, cfg.sliding_window
    chunk = min(CHUNK, max(w // 2 // ps, 1) * ps)
    s = len(tokens)
    total = grow_to(cfg, ps, at_least=s + 2 * chunk)
    n_pages = -(-(total + steps) // ps)
    w_width = w // ps + chunk // ps + 2  # a window, a chunk, a boundary
    dev = engine._replicated
    k_pages, v_pages = llama.init_kv_pages(cfg, n_pages + 1, ps, sharding=dev)
    window_pages = llama.init_window_pages(cfg, w_width + 1, ps, sharding=dev)
    wt = WindowTable(w_width + 1, w, ps)
    run = dict(mesh=engine.mesh, attn_impl=engine.prefill_attn,
               interpret=interpret)

    def put(x, dtype=np.int32):
        return jax.device_put(np.asarray(x, dtype), dev)

    def keep(out):
        """(logits, k_pages, v_pages[, window_pages]) -> logits; pools kept."""
        nonlocal k_pages, v_pages, window_pages
        logits, k_pages, v_pages, *rest = out
        if rest:
            (window_pages,) = rest
        return np.asarray(logits, np.float32)[0]

    def windowed(**rows):
        if window_pages is None:  # a tree of full layers alone
            return {}
        return {"window_pages": window_pages, **{
            k: put(v) if not isinstance(v, tuple) else tuple(map(put, v))
            for k, v in rows.items()}}

    table = 1 + np.arange(n_pages)
    grown = list(tokens)
    fed = []
    out = []
    lo = 0
    while lo < total:
        # the harness's prompt cold, then the prompt's tokens over again
        hi = s if lo == 0 else min(lo + chunk, total)
        grown += [tokens[i % s] for i in range(len(grown), hi)]
        positions = np.arange(lo, hi)[None, :]
        wt.move_to(lo, hi)
        ctx = np.zeros((1, n_pages if lo else 0), np.int32)
        ctx[0, : lo // ps] = table[: lo // ps]
        logits = keep(llama.prefill(
            params, cfg, put([grown[lo:hi]]), put(positions),
            put(np.ones((1, hi - lo), bool), bool), k_pages, v_pages,
            put(table[positions // ps]), put(positions % ps), put(ctx),
            put([lo]), return_all_logits=lo > 0, **run,
            **windowed(window_rows=(
                wt.page_of(positions), wt.row(w_width if lo else 0),
                [wt.first * ps])),
        ))
        out.append(logits if lo else logits[None])
        lo = hi
    fed = grown[s:]
    out = list(np.concatenate(out))
    bt = put(table[None, :])
    for i in range(steps):
        nxt = int(np.argmax(out[-1]))
        fed.append(nxt)
        pos = total + i
        wt.move_to(pos, pos + 1)
        out.append(keep(llama.decode_step(
            params, cfg, put([nxt]), put([pos]), k_pages, v_pages, bt,
            put([pos + 1]), page_size=ps, interpret=interpret,
            mesh=engine.mesh, **windowed(
                window_tables=wt.row(w_width), window_start=[wt.first * ps]),
        )))
    return np.stack(out), fed
