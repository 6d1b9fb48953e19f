"""The plain reference of a sparse decoder whose router reads the layer's
input before the attention, with a full layer that takes no positions ahead
of three sliding ones (``PowerInfer/SmallThinker-21BA3B-Instruct``,
``model_type: smallthinker``), from the published description (the catalog's
``config`` and ``described_as``, and ``modeling_smallthinker.py`` /
llama.cpp's ``llm_build_smallthinker`` of the source the configuration names,
as ISSUE 54 writes its equations down). Float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, nothing imported from the program's
model code, the whole sequence at once: no cache, no pages, no table.

``h`` the residual stream entering a layer, ``d`` hidden, ``H`` query heads on
``G`` key/value heads of ``d_h``, window ``W``, ``E`` experts top-``k``:

- ``r = h W_r``: the router's ``E`` logits from the layer's INPUT, before any
  norm and before the attention (``router_input`` of the published code,
  ``ffn_gate_inp`` applied to ``inpL`` in llama.cpp).
- ``x = RMSNorm_1(h)``; ``q, k, v = x W_q, x W_k, x W_v``: no bias, no q/k
  norm. A SLIDING layer (one whose parameters hold ``window``) rotates q and
  k at ``t`` (halves, the whole head) and ``t`` sees ``j`` with ``t - W < j
  <= t``; a FULL layer rotates nothing and ``t`` sees every ``j <= t``.
  Softmax of ``q . k / sqrt(d_h)``, ``H / G`` query heads a key/value head;
  ``h = h + concat(heads) W_o``. In query blocks, so that 7k positions fit.
- ``y = RMSNorm_2(h)``; ``S`` = the ``k`` largest of ``r``; ``g = softmax``
  over those ``k`` logits alone (the published form: top-k of the logits,
  then the softmax; equal to a softmax over all ``E``, top-k, renormalised,
  which is how the program computes it: ``tests/test_prerouted.py`` holds the
  two together); ``h = h + sum_{e in S} g_e W_down,e (relu(W_gate,e y) *
  (W_up,e y))``: a ReGLU expert. No shared expert, no dense layer.
- ``logits = RMSNorm(h_L) W_head``, the head untied.

Departures from a naive reading, each under ``assumed`` in the configuration's
file: the router reads the un-normed input (the row's "router placed before
attention" does not say which side of the norm); ReLU (``described_as``:
ReGLU; the row has no ``hidden_act``); no q/k norm and no bias (no key for
either); the window's edge includes the query's own position; halves.

It reads the tree ``llama.init_params`` builds for such a model: ``attn_norm,
mlp_norm, wq, wk, wv, wo``, ``window`` on a sliding layer, ``router,
w_gate/w_up/w_down [E, ...]`` (and ``preroute``, which it does not read: this
file describes no other placement); ``embed, final_norm, lm_head``.

``system`` is this reference's own system side, ``swa_moe``'s walk (the
harness hands 128 tokens and the window is 4096; the default side never
leaves the first window): the harness's prompt cold, then WARM chunks of 512
through both pools, every one of which starts mid-window until the sequence
passes it and mid-context after, window pages given back and reused
(``swa_moe.WindowTable``), to ``W + 3 W / 4`` positions (7180 at the published
window), then greedy decode steps in which a window page falls out of the
lane's table and a page's end is crossed. Every position from the prompt's
last on is compared.

**Which columns of the head are compared** (``head_columns``). 7181 positions
of a 151936-row vocabulary are 4.4 GB of float32 logits a side a comparison,
and the harness makes ten (two sequences, eight layers alone): fetched to the
host and subtracted there they took 470-560 s of every run's set-up (chip
runs, PR 54). So a comparison of more than ``FULL_HEAD_LOGITS`` logits is
made at every ``HEAD_STRIDE``-th column of the head, on both sides by this
one rule: EVERY position is still compared, through every layer, the final
norm and the head's product, at 18992 of its 151936 columns (a random head's
columns are alike: the worst of an eighth of them reads what the worst of all
does to a tenth); the tokens ``system`` feeds are chosen over the whole
vocabulary. What it cannot see: a fault in the other columns of the head
alone. Below the limit (every test, every other caller) all columns.

Tolerances (what an error is: ``reference.py``). My chip runs, PR 54, at the
published widths, 8 layers (2 full, 6 sliding), the harness's own check in the
cell's runs and in ``probe_prerouted.py`` with its controls; PERF.md section 6
has every line.

As in ``moe`` the whole model reads routing: 6 experts of 64 are chosen by
logits the program rounds to bfloat16, a chosen expert weighs about a sixth
of the routed sum, and a token whose 6th and 7th logit lie within that
rounding takes another expert than the reference does. What differs from
every other routed model here: the logits come from the UN-NORMED stream, so
their scale is the stream's (0.02 at the first layer of seeded weights, about
1 at the eighth) and so is their rounding: the share of tied tokens is what
it is elsewhere, the gap that splits them is not one number (``ROUTER_GAP_MIN``
is told, not compared).

Readings (my chip runs, PR 54: nine seeds sound, the cell's runs and the
probe's; the controls on two seeds, one at all columns and one at every
eighth, which read alike):

- ``layer_p75`` 1.1e-2: the third quartile of ALL positions of the layers run
  alone (8 layers x 7053 positions a prompt): the precision, and the one
  limit every control fails. Sound 0.505e-2 to 0.532e-2. The nearest
  precision below the stated one, every matmul weight and expert rounded
  through int8: 2.10e-2 and 2.21e-2, not correct by this limit ALONE (its
  ``p50`` 5.5e-2 / 8.8e-2 and ``max`` 0.24 / 0.34 lie among the sound runs').
  SiLU for ReLU 0.197 / 0.204; the router fed the post-attention stream 0.95 /
  0.99. The limit is 2.1 x the sound runs' largest and 0.52 x int8's smallest.
- ``p50`` 0.3: the median position of the whole model. Sound 0.7e-2 to 9.1e-2,
  and that spread is the model's: a swapped expert early in a sequence moves
  every position after it, so a seed's median says where its first large swap
  fell (0.007, 0.013, 0.028, 0.030, 0.034, 0.061, 0.072, 0.073, 0.091: no
  limit under 0.2 would hold the next dozen seeds). The router fed the
  post-attention stream 0.565 / 0.586: 3.3 x the sound runs' largest, 0.53 x
  the control's smallest. SiLU (0.219 / 0.259) is left to ``layer_p75``.
- ``max`` 0.6 against a program that is not this model, as far as routing
  lets it: sound 0.175 to 0.413 (one swap in a layer run alone reads 0.45 to
  0.49 on every seed); the router fed the post-attention stream 0.769 / 0.827
  (1.45 x the sound runs' largest, 0.78 x the control's smallest).

A window off by one position cannot be held in bfloat16 on the chip
(``swa_moe``); ``tests/test_swa.py`` holds it in float32, and
``tests/test_prerouted.py`` this model's rows through both pools.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as common

_swa = common.load("swa_moe")

#: bf16 system against the f32 reference (readings: above, and PERF.md
#: section 6, PR 54): the worst and the median position of the whole model,
#: the third quartile of the positions of the layers alone
TOL_BF16 = {"max": 0.6, "p50": 0.3, "layer_p75": 1.1e-2}
#: told in the result line, compared with nothing: the distance, in router
#: logits, between the last chosen expert and the first that is not (the
#: logits' scale is the un-normed stream's: see above)
ROUTER_GAP_MIN = 0.002
#: query rows a block of the attention (the float32 scores of 7k positions
#: beside a resident engine)
QUERY_BLOCK = 256

#: a comparison of more logits (positions x vocabulary) than this is made at
#: every ``HEAD_STRIDE``-th column of the head (1 GiB of float32 a side)
FULL_HEAD_LOGITS = 2**28
HEAD_STRIDE = 8

WindowTable = _swa.WindowTable


def head_columns(positions: int, vocab: int) -> slice:
    """The columns of the head at which a sequence of ``positions`` is
    compared: all, or every ``HEAD_STRIDE``-th where they would be too many."""
    if positions * vocab <= FULL_HEAD_LOGITS:
        return slice(None)
    return slice(0, vocab, HEAD_STRIDE)


def _attention(layer, cfg, x):
    f32 = jnp.float32
    s = x.shape[0]
    n_q, n_kv, hd, w = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.sliding_window
    sliding = "window" in layer
    q = (x @ layer["wq"].astype(f32)).reshape(s, n_q, hd)
    k = (x @ layer["wk"].astype(f32)).reshape(s, n_kv, hd)
    v = (x @ layer["wv"].astype(f32)).reshape(s, n_kv, hd)
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
    return heads @ layer["wo"].astype(f32)


def _reglu(x, gate, up, down):
    return (jax.nn.relu(x @ gate) * (x @ up)) @ down


def _route(layer, cfg, h):
    """(gates [s, E]: zero where not chosen, router gap [s]) from the stream
    that ENTERS the layer: the ``k`` largest logits, a softmax over those."""
    k = cfg.n_experts_per_tok
    logits = h @ layer["router"].astype(jnp.float32)
    edge, topi = jax.lax.top_k(logits, k + 1)
    gap = edge[:, -2] - edge[:, -1]
    topv = jax.nn.softmax(edge[:, :k], axis=-1)
    gates = jnp.zeros_like(logits).at[
        jnp.arange(h.shape[0])[:, None], topi[:, :k]
    ].set(topv)
    return gates, gap


def _experts(layer, y, gates):
    f32 = jnp.float32

    def one_expert(acc, e):
        out = _reglu(y, layer["w_gate"][e].astype(f32),
                     layer["w_up"][e].astype(f32),
                     layer["w_down"][e].astype(f32))
        return acc + jax.lax.dynamic_index_in_dim(
            gates, e, axis=1, keepdims=True) * out, None

    acc, _ = jax.lax.scan(one_expert, jnp.zeros_like(y),
                          jnp.arange(layer["w_gate"].shape[0]))
    return acc


def _layer_fn(cfg):
    @jax.jit
    def layer_forward(layer, h):
        f32, eps = h.dtype, cfg.rms_norm_eps
        gates, gap = _route(layer, cfg, h)  # before the norm, the attention
        h = h + _attention(
            layer, cfg, common._rms(h, layer["attn_norm"].astype(f32), eps))
        y = common._rms(h, layer["mlp_norm"].astype(f32), eps)
        return h + _experts(layer, y, gates), gap

    return layer_forward


def forward(params, cfg, tokens, rows=None):
    """(logits [s, vocab] f32 on the host, router gap [s]: each token's
    smallest over the layers). ``rows``: the positions whose logits are
    wanted (default every one: the harness's contract)."""
    if not (cfg.sliding_window and cfg.router_before_attention):
        raise ValueError("reference 'swa_prerouted_moe' does not fit the model")
    if (cfg.norm_offset or cfg.scale_embeddings or cfg.qk_norm or cfg.qkv_bias
            or cfg.rope_scaling is not None or cfg.tie_word_embeddings
            or cfg.hidden_act != "relu" or cfg.moe_scoring != "softmax"
            or not cfg.norm_topk_prob or cfg.n_shared_experts
            or cfg.first_k_dense or cfg.routed_scaling_factor != 1.0
            or cfg.attn_output_gate or cfg.sandwich_norm
            or not cfg.holds_every_expert):
        raise ValueError("the reference does not describe this model")
    f32 = jnp.float32
    layer_forward = _layer_fn(cfg)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        gaps = jnp.full(len(tokens), jnp.inf, f32)
        for layer in params["layers"]:
            h, gap = layer_forward(layer, h)
            gaps = jnp.minimum(gaps, gap)
        if rows is not None:
            h, gaps = h[jnp.asarray(rows)], gaps[jnp.asarray(rows)]
        h = common._rms(h, params["final_norm"].astype(f32), cfg.rms_norm_eps)
        head = params["lm_head"][:, head_columns(len(tokens), cfg.vocab_size)]
        edges = np.linspace(0, head.shape[1], common.HEAD_BLOCKS + 1).astype(int)
        logits = jnp.concatenate([
            common._head(head[:, a:b], h)
            for a, b in zip(edges[:-1], edges[1:])
        ], axis=-1)
    return logits, gaps


# -- the system's side --------------------------------------------------------
def system(engine, tokens, steps: int, interpret: bool, params=None, cfg=None):
    """The system's side (module docstring): (logits [fed + 1, compared
    columns], the tokens fed after the prompt)."""
    from llm_d_kv_cache_manager_tpu.models import llama

    params = engine.params if params is None else params
    cfg = _swa.pool_config(params, engine.model_cfg if cfg is None else cfg)
    ps, w = engine.page_size, cfg.sliding_window
    chunk = min(_swa.CHUNK, max(w // 2 // ps, 1) * ps)
    s = len(tokens)
    total = _swa.grow_to(cfg, ps, at_least=s + 2 * chunk)
    cols = head_columns(total + steps, cfg.vocab_size)
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
        """(logits, k_pages, v_pages[, window_pages]) -> the row's logits on
        the device; pools kept."""
        nonlocal k_pages, v_pages, window_pages
        logits, k_pages, v_pages, *rest = out
        if rest:
            (window_pages,) = rest
        return logits[0]

    def windowed(**rows):
        if window_pages is None:  # a tree of full layers alone
            return {}
        return {"window_pages": window_pages, **{
            k: put(v) if not isinstance(v, tuple) else tuple(map(put, v))
            for k, v in rows.items()}}

    table = 1 + np.arange(n_pages)
    grown = list(tokens)
    out = []  # host arrays [rows, compared columns]
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
        last = logits if lo else logits[None]  # [rows, vocab]
        out.append(np.asarray(last[:, cols], np.float32))
        lo = hi
    fed = grown[s:]
    bt = put(table[None, :])
    for i in range(steps):
        fed.append(int(jnp.argmax(last[-1])))  # over the whole vocabulary
        pos = total + i
        wt.move_to(pos, pos + 1)
        last = keep(llama.decode_step(
            params, cfg, put([fed[-1]]), put([pos]), k_pages, v_pages, bt,
            put([pos + 1]), page_size=ps, interpret=interpret,
            mesh=engine.mesh, **windowed(
                window_tables=wt.row(w_width), window_start=[wt.first * ps]),
        ))[None]
        out.append(np.asarray(last[:, cols], np.float32))
    return np.concatenate(out), fed
