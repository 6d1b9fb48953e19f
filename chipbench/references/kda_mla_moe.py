"""The plain reference of a hybrid of delta-rule linear attention and latent
attention over group-limited sparse experts (``inclusionAI/Ling-3.0-flash``,
``model_type: bailing_hybrid``): Kimi Delta Attention as arXiv:2510.26692
describes it with this configuration's keys, ``kanana-2-30b-a3b``'s latent
attention (``references/mla_moe.py``: its ``_attention`` is used as it is),
DeepSeek-V3's ``noaux_tc`` routing with groups. Float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, nothing imported from the program's
model code, the recurrence TOKEN BY TOKEN under ``lax.scan`` (the served
prefill is chunked, the served decode a kernel over a pool of slots: the two
sides compute the state by other arithmetic).

Per layer, ``x = RMSNorm(h; attn_norm)``, per head ``h`` of ``H``, ``K = V =
kda_head_dim``:

**A linear layer** (it has ``kda_qkv``). A position never enters.

- ``[q~ | k~ | v~] = x W_qkv`` (``[d, 3 H K]``, no bias); a causal depthwise
  convolution of ``short_conv_kernel_size`` taps over the three (``c_t =
  sum_j w[j] z_{t - (taps - 1) + j}``, zeros before the sequence), then SiLU
  (``linear_silu``): ``q', k', v'``.
- ``q_h = q'_h / sqrt(|q'_h|^2 + 1e-6) / sqrt(K)``, ``k_h = k'_h /
  sqrt(|k'_h|^2 + 1e-6)`` (``use_qk_norm``: the l2 norm of the family).
- the gate in log space, a channel of the key: ``a = x W_f + dt_bias``
  (``W_f [d, H K]`` full rank: ``no_kda_lora``); with ``kda_safe_gate``:
  ``g_h = kda_lower_bound * sigmoid(exp(A_log_h) * a_h)`` in (-5, 0);
  without it ``g_h = -exp(A_log_h) * softplus(a_h)`` (the paper's).
- ``beta_h = sigmoid(x w_b,h)`` (``W_b [d, H]``).
- state ``S_h [K, V]`` float32: ``S <- diag(exp(g_h)) S``; ``u = v_h - S^T
  k_h``; ``S <- S + beta_h k_h u^T``; ``o_h = S^T q_h``.
- ``o_h <- RMSNorm(o_h; o_norm) * sigmoid(x w_g,h)`` (``W_g [d, H]``: one
  gate a head, ``gated_attention_proj_granularity_type: head_wise``;
  ``group_norm_size`` 1: the norm is a head's); ``h += concat(o) W_o``.

With ``g`` equal over a head's channels this is the gated delta rule:
``recurrence`` is held to ``transformers``' ``torch_recurrent_gated_delta_rule``
in ``tests/test_kda.py``.

**A latent layer**: ``mla_moe``'s, unchanged (``rope_theta`` over the 64 rope
values, pairs rotated; no output gate; the latent row normed).

**The FFN**, ``x = RMSNorm(h; mlp_norm)``. Without a router: ``h +=
SwiGLU(x)``. With one: ``s = sigmoid(x W_r)`` over all ``E``; for choosing
``c = s + bias``; the ``E`` outputs lie in ``n_group`` groups of neighbours,
a group's score is the sum of its 2 largest ``c``, the ``topk_group`` best
groups are kept and ``c`` elsewhere is -inf; the top ``k`` of what is left;
weights ``s`` of the chosen / (their sum + 1e-20) (``norm_topk_prob``) x
``routed_scaling_factor``; ``h += sum_e g_e E_e(x) + S(x)``, ``S`` the one
shared expert. The tree holds the experts ``expert_first .. + held`` (one
routing group of the deployment's eight); a place whose expert lies
elsewhere adds nothing, here as in the program, and nothing stands in for
the absent chips.

It reads the tree ``llama.init_params`` builds: a linear layer's ``kda_qkv,
kda_conv_w [taps, 3 H K], kda_wf, kda_dt_bias, kda_A_log [H], kda_wb, kda_wg,
kda_o_norm [V], wo``; a latent layer's ``wq, wkv_a, kv_norm, wkv_b, wo``; the
FFNs' as ``mla_moe``.

``system`` is this reference's own system side, so that ``correct`` covers
the state pool as the served path uses it. The prompt's first half (whole
pages) is prefilled from zero state into slot 1; the rest is prefilled
READING slot 1 and WRITING slot 2, which is how the engine restores from a
snapshot (the snapshot, slot 1, is left as it was: nothing is copied slot to
slot, ``ops/kda.py``); the first decode step reads slot 2 and writes slot 3,
which is how the engine takes a snapshot in decode (a lane that passes a
boundary goes on in a new slot and leaves the old one behind), the later
steps update slot 3 in place; then the last piece is prefilled AGAIN from
slot 1 into slot 4 and its logits must equal the first time's bit for bit
(the snapshot was not written by anything that read it). A wrong read slot,
write slot or in-place update turns ``correct`` false. The harness's prompts
are 128 tokens, under the stride of 512, so the engine's own cut-back
admission (``BlockManager.allocate``) is held by the CPU tests
(``tests/test_kda_engine.py``) and only the pool's two moves here.

Tolerances (what an error is: ``reference.py``): my chip runs, PR 47, at the
published widths and the cut of the cell (7 layers, 64 of 512 experts): the
harness's own check (two prompts of 128 tokens, 8 decode steps) in the cell's
runs over their seeds, and ``probe_kda.py`` (seed 11), whose controls steer
the program and leave the reference and the weights. PERF.md section 6 has
every line.

- ``layer_p75`` 1.15e-2: the third quartile of the positions of the layers
  run alone, where a position's logits depend on its own token's routing
  only. Sound: 0.706e-2 to 0.780e-2 (sixteen runs). The nearest precision
  below the stated bf16 weights, every matmul weight and expert rounded
  through int8: 1.69e-2, not correct by this limit alone (its ``max`` 0.18
  and ``p50`` 0.056 pass). The limit is 1.47 x the sound runs' largest and
  0.68 x the control's. The other three that fail read 0.19 (no group mask),
  0.79 (the gate after the update), 1.12 (no l2 norm).
- ``p50`` 0.4: sound 0.024 to 0.093 (the median of 18 positions: it reads
  0.02-0.05 on most seeds and 0.09 where more than half the positions carry
  a swapped expert); the gate after the update reads 1.15, no l2 norm 1.16:
  4.3 x the sound runs' largest, 0.35 x the controls'. Routing without the
  group mask reads 0.236 and passes it (``layer_p75`` holds that control, 16
  times over). As in ``mla_moe`` the whole model reads routing (8 of 512
  chosen by sigmoid scores that lie close, 40 to 47 of a seed's 63
  layer-alone positions within ``ROUTER_GAP_MIN``).
- ``max`` 0.9: sound 0.10 to 0.35; the gate after the update reads 1.26, no
  l2 norm 1.44: 2.6 x the sound runs' largest, 0.71 x the controls'. Routing
  without the group mask reads 0.39 and passes it: the worst position of a
  sound run already reads a swapped expert. It holds nothing the other two
  do not.
- **What no limit holds on the chip: a state rounded through bf16 at every
  write of the pool** (the probe's ``bf16_state``: ``layer_p75`` 0.726e-2,
  ``p50`` 0.025, ``max`` 0.16, all inside the sound runs' range). Over one
  prefill piece and 8 decode steps the rounding of the matrices (2^-9 a
  write) stays under the bf16 activations' own noise; it grows with every
  write, so a check that decoded some hundreds of steps would see it, and
  the float32 tests do (``tests/chipbench_tests/test_kda_cell.py``: 7.8e-3
  against 2e-4). ISSUE 47 asked that it fail a bound; it does not, and no
  bound was drawn in to make it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as common

_mla = common.load("mla_moe")

#: bf16 system against the f32 reference (readings: PERF.md section 6, PR 47)
TOL_BF16 = {"max": 0.9, "p50": 0.4, "layer_p75": 1.15e-2}
#: told in the result line, compared with nothing: the distance, in choice
#: scores (sigmoid + bias), between the last chosen expert and the first
#: that is not
ROUTER_GAP_MIN = 0.0125
GATE_EPS = 1e-20


def recurrence(q, k, v, g, beta, S0=None):
    """The delta rule token by token: ``q, k, g [s, H, K]``, ``v [s, H, V]``,
    ``beta [s, H]`` -> (outputs ``[s, H, V]``, final state ``[H, K, V]``)."""
    if S0 is None:
        S0 = jnp.zeros((*q.shape[1:], v.shape[-1]), jnp.float32)

    def one(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None]
        u = v_t - jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + b_t[:, None, None] * k_t[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    S, o = jax.lax.scan(one, S0, (q, k, v, g, beta))
    return o, S


def gate(layer, cfg, x):
    """The log decay ``[s, H, K]`` by the published form."""
    f32 = jnp.float32
    H, K = cfg.n_heads, cfg.kda_head_dim
    a = (x @ layer["kda_wf"].astype(f32) + layer["kda_dt_bias"].astype(f32))
    a = a.reshape(-1, H, K)
    rate = jnp.exp(layer["kda_A_log"].astype(f32))[:, None]
    if cfg.kda_safe_gate:
        return cfg.kda_lower_bound * jax.nn.sigmoid(rate * a)
    return -rate * jax.nn.softplus(a)


def _l2(t):
    return t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)


def _kda(layer, cfg, x):
    f32 = jnp.float32
    s = x.shape[0]
    H, K = cfg.n_heads, cfg.kda_head_dim
    z = x @ layer["kda_qkv"].astype(f32)
    taps = layer["kda_conv_w"].astype(f32)
    n = taps.shape[0]
    zp = jnp.concatenate([jnp.zeros((n - 1, z.shape[1]), f32), z])
    conv = jax.nn.silu(sum(taps[j] * zp[j: j + s] for j in range(n)))
    q, k, v = (t.reshape(s, H, K) for t in jnp.split(conv, 3, axis=-1))
    q, k = _l2(q) / np.sqrt(K), _l2(k)
    beta = jax.nn.sigmoid(x @ layer["kda_wb"].astype(f32))
    o, _ = recurrence(q, k, v, gate(layer, cfg, x), beta)
    o = common._rms(o, layer["kda_o_norm"].astype(f32), cfg.rms_norm_eps)
    o = o * jax.nn.sigmoid(x @ layer["kda_wg"].astype(f32))[..., None]
    return o.reshape(s, H * K) @ layer["wo"].astype(f32)


def _mixer(layer, cfg, x):
    if "kda_qkv" in layer:
        return _kda(layer, cfg, x)
    return _mla._attention(layer, cfg, x)


def choose(scores, bias, cfg):
    """(chosen ids ``[s, k]``, the gap between the last chosen and the first
    that is not): group-limited top-k on ``scores + bias``."""
    k = cfg.n_experts_per_tok
    c = scores + bias
    if cfg.n_group > 1:
        grouped = c.reshape(c.shape[0], cfg.n_group, -1)
        best = jnp.sum(
            jax.lax.top_k(grouped, min(2, grouped.shape[-1]))[0], axis=-1)
        _, kept = jax.lax.top_k(best, cfg.topk_group)
        keep = jnp.zeros(best.shape, bool).at[
            jnp.arange(c.shape[0])[:, None], kept
        ].set(True)
        c = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(c.shape)
    edge, topi = jax.lax.top_k(c, k + 1)
    return topi[:, :k], edge[:, -2] - edge[:, -1]


def _ffn(layer, cfg, x):
    """(output, router gap [s]; infinite where the layer routes nothing)."""
    f32 = jnp.float32
    if "router" not in layer:
        out = common._swiglu(x, layer["w_gate"].astype(f32),
                             layer["w_up"].astype(f32),
                             layer["w_down"].astype(f32))
        return out, jnp.full(x.shape[0], jnp.inf, f32)
    held, first = layer["w_gate"].shape[0], cfg.expert_first
    scores = jax.nn.sigmoid(x @ layer["router"].astype(f32))
    topi, gap = choose(scores, layer["router_bias"].astype(f32), cfg)
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    if cfg.norm_topk_prob:
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


def forward(params, cfg, tokens, rows=None):
    """(logits [s, vocab] f32, router gap [s]: each token's smallest over
    the layers). ``rows``: the positions whose logits are wanted (default
    every one: the harness's contract)."""
    if not (cfg.kv_lora_rank and cfg.kda_head_dim
            and cfg.moe_scoring == "sigmoid"):
        raise ValueError("reference 'kda_mla_moe' does not fit the model")
    if (cfg.norm_offset or cfg.scale_embeddings or cfg.rope_scaling is not None
            or cfg.tie_word_embeddings or cfg.hidden_act != "silu"
            or cfg.q_lora_rank or cfg.n_shared_experts != 1
            or cfg.n_zero_experts or cfg.attn_output_gate):
        raise ValueError("the reference does not describe this model")
    f32 = jnp.float32
    layer_forward = common._layer_fn(cfg, _ffn, _mixer)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
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
    kinds = tuple("linear_attention" if "kda_qkv" in layer else "full_attention"
                  for layer in params["layers"])
    return dataclasses.replace(cfg, n_layers=len(kinds), layer_types=kinds)


def system(engine, tokens, steps: int, interpret: bool, params=None, cfg=None):
    """The system's side (module docstring): (logits [steps + 1, vocab], the
    tokens fed after the prompt)."""
    from llm_d_kv_cache_manager_tpu.models import llama

    params = engine.params if params is None else params
    cfg = pool_config(params, engine.model_cfg if cfg is None else cfg)
    ps = engine.page_size
    s = len(tokens)
    half = s // 2 // ps * ps
    if not half:
        raise ValueError("the prompt's first half must hold a whole page")
    n_pages = -(-(s + steps) // ps)
    dev = engine._replicated
    k_pages, v_pages = llama.init_kv_pages(cfg, n_pages + 1, ps, sharding=dev)
    state = llama.init_kda_state(cfg, 5, sharding=dev)
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

    def stateful(*slots):
        if state is None:
            return {}
        return {"state_pages": state, "state_slots": put([list(slots)])}

    table = 1 + np.arange(n_pages)

    def piece(lo, hi, read, write):
        positions = np.arange(lo, hi)[None, :]
        return step(llama.prefill(
            params, cfg, put([tokens[lo:hi]]), put(positions),
            put(np.ones((1, hi - lo), bool), bool), k_pages, v_pages,
            put(1 + positions // ps), put(positions % ps),
            put(table[None, : lo // ps]), put([lo]), **run,
            **stateful(read, write),
        ))

    piece(0, half, 1, 1)
    out = [piece(half, s, 1, 2)]  # from the snapshot in slot 1 into slot 2
    fed = []
    bt = put(table[None, :])
    for i in range(steps):
        nxt = int(np.argmax(out[-1]))
        fed.append(nxt)
        out.append(step(llama.decode_step(
            params, cfg, put([nxt]), put([s + i]), k_pages, v_pages, bt,
            put([s + i + 1]), page_size=ps, interpret=interpret,
            mesh=engine.mesh, **stateful(2, 3, s),
        )))
    # the snapshot again: nothing that read it wrote it (the latent rows of
    # the piece are written once more, the same values)
    again = piece(half, s, 1, 4)
    if state is not None and not np.array_equal(again, out[0]):
        out[0] = np.full_like(out[0], np.nan)
    return np.stack(out), fed
