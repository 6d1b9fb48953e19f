"""A router that reads the layer's input before the attention, ReGLU experts,
a full layer without positions ahead of three sliding ones, a GQA group of 7
(``LlamaConfig.router_before_attention``; SmallThinker-21BA3B-Instruct,
``smallthinker``): the served programs through both pools against the plain
reference, at ``TINY_SMALLTHINKER`` in float32 (window 8, pages of 4: full,
sliding, sliding, sliding; 7 query heads on 1 KV head; 8 experts top-2).

The reference side is ``chipbench/references/swa_prerouted_moe.forward``
(float32, the whole sequence at once, nothing of the program's model code, no
cache). Here: every way a row meets the window on the served path (a cold
prompt, a warm prefill from a hit shorter than, equal to and longer than the
window, decode steps across window and page boundaries), the two controls
that must fail the same tolerance (the router fed ``RMSNorm_2`` of the
post-attention stream, which is every other model's placement; SiLU for
ReLU), the published routing form against the program's, and that a layer
without the mark runs what it ran. The kernels at a group of 7 are in
``tests/test_prerouted_kernels.py``, the engine in
``tests/test_prerouted_engine.py``, refusals, presets, the loader and the
trees in ``tests/test_prerouted_config.py``; the helpers shared with the
other architectures are ``tests/served_path.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import (
    TINY_QWEN3_MOE,
    TINY_SMALLTHINKER,
    llama,
)
from served_path import prompt_of, rel_err

CFG = TINY_SMALLTHINKER
PS = 4
W = CFG.sliding_window
TOL = chip_reference.TOL_F32
REF = chip_reference.load("swa_prerouted_moe")
STEPS = 2 * W + 3  # two windows and a page boundary past the prompt

#: (prompt tokens, tokens resident before the warm call): a cold prompt, then
#: hits shorter than, equal to and longer than the window, the last one not
#: at a page's end
ROWS = {
    "cold": (37, 0),
    "hit-shorter-than-the-window": (30, W - 3),
    "hit-of-one-window": (41, W),
    "hit-of-two-windows-and-three": (29, 2 * W + 3),
}


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 54)


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return served_path.reference_logits(REF, params, cfg, tokens)


def served(params, rows, steps, attn_impl, cfg=CFG):
    got, fed, _ = served_path.served(
        params, cfg, rows, steps, attn_impl, page_size=PS,
        second=served_path.WindowPages(REF.WindowTable, PS))
    return got, fed


def _rows():
    return [(prompt_of(60 + i, n), resident)
            for i, (n, resident) in enumerate(ROWS.values())]


@pytest.fixture(scope="module")
def on_the_served_path(params):
    """{kernel: [(error of the prompt's last position and every decode step,
    a row)]}: one batched call a kernel serves every case below."""
    out = {}
    for impl in ("xla", "pallas"):
        got, fed = served(params, _rows(), STEPS, impl)
        out[impl] = [
            rel_err(g, reference_logits(params, p + f)[len(p) - 1:])
            for (p, _), g, f in zip(_rows(), got, fed)
        ]
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("row", list(ROWS))
def test_the_served_path_gives_the_references_logits(
        on_the_served_path, impl, row):
    """Prefill (cold, and warm from each kind of hit) and ``STEPS`` decode
    steps, in which every sliding layer's window moves off the prompt and
    window pages are given back and reused."""
    assert on_the_served_path[impl][list(ROWS).index(row)] < TOL


# -- the two controls: the same tolerance must refuse them ---------------------
def _served_error(params, program_params, program_cfg):
    """The worst error over ``ROWS`` of a PROGRAM (its tree, its
    configuration) against the reference of the model itself."""
    got, fed = served(program_params, _rows(), 4, "xla", cfg=program_cfg)
    return max(
        rel_err(g, reference_logits(params, p + f)[len(p) - 1:])
        for (p, _), g, f in zip(_rows(), got, fed)
    )


def test_a_router_fed_the_post_attention_stream_is_refused(params):
    """Without the mark a layer routes as every other model's does: from
    ``RMSNorm_2`` of the stream after the attention. Not this model."""
    unmarked = {**params, "layers": [
        {k: v for k, v in layer.items() if k != "preroute"}
        for layer in params["layers"]
    ]}
    assert _served_error(params, unmarked, CFG) > 100 * TOL


def test_silu_for_relu_is_refused(params):
    silu = dataclasses.replace(CFG, hidden_act="silu")
    assert _served_error(params, params, silu) > 100 * TOL


def test_the_sound_program_passes_what_refuses_the_controls(params):
    assert _served_error(params, params, CFG) < TOL


# -- the published routing form and the program's ------------------------------
def test_top_k_then_softmax_is_softmax_top_k_renormalised(params):
    """The published code takes the 6 largest LOGITS and a softmax over those
    six (the reference writes that); the program's softmax branch takes a
    softmax over all, its top-k, and renormalises. The same gates."""
    layer = params["layers"][1]
    h = 3.0 * jax.random.normal(jax.random.PRNGKey(7), (33, CFG.hidden_size))
    want, _ = REF._route(layer, CFG, h)
    topv, topi = llama._moe_gates(layer, CFG, h)
    got = jnp.zeros_like(want).at[jnp.arange(33)[:, None], topi].set(topv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    assert np.asarray((want > 0).sum(-1) == CFG.n_experts_per_tok).all()


def test_the_gates_are_made_of_the_layers_input(params):
    """``_preroute`` of a marked layer is ``_moe_gates`` of the stream it is
    given, untouched by any norm; of an unmarked one, nothing."""
    layer = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 5, CFG.hidden_size))
    topv, topi = llama._preroute(layer, CFG, h)
    want_v, want_i = llama._moe_gates(layer, CFG, h)
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(topv), np.asarray(want_v))
    unmarked = {k: v for k, v in layer.items() if k != "preroute"}
    assert llama._preroute(unmarked, CFG, h) is None


@pytest.mark.parametrize("dispatch", ["routed", "dense"])
def test_every_dispatch_takes_the_gates_it_is_given(params, dispatch):
    """The routed dispatch and the dense oracle weigh and choose by the gates
    handed in, not by what their own input would give."""
    cfg = dataclasses.replace(CFG, moe_dispatch=dispatch)
    layer = params["layers"][2]
    key_h, key_y = jax.random.split(jax.random.PRNGKey(9))
    h = jax.random.normal(key_h, (2, 6, CFG.hidden_size))
    y = jax.random.normal(key_y, (2, 6, CFG.hidden_size))
    gates = llama._preroute(layer, cfg, h)
    got = llama._mlp(layer, cfg, y, interpret=True, gates=gates)
    dense_gates, _ = REF._route(layer, CFG, h.reshape(12, -1))
    want = REF._experts(layer, y.reshape(12, -1), dense_gates).reshape(y.shape)
    assert rel_err(np.asarray(got), np.asarray(want)) < TOL
    own = llama._preroute(layer, cfg, y)
    assert rel_err(np.asarray(llama._mlp(
        layer, cfg, y, interpret=True, gates=own)), np.asarray(want)) > 100 * TOL


def test_a_marked_layer_without_gates_is_refused_by_name(params):
    layer = params["layers"][0]
    y = jnp.zeros((1, 2, CFG.hidden_size))
    with pytest.raises(ValueError, match="_preroute"):
        llama._mlp(layer, CFG, y, interpret=True)
    unmarked = {k: v for k, v in layer.items() if k != "preroute"}
    with pytest.raises(ValueError, match="_preroute"):
        llama._mlp(unmarked, CFG, y, interpret=True,
                   gates=llama._preroute(layer, CFG, y))


# -- a layer without the mark runs what it ran ----------------------------------
def test_no_preroute_reaches_a_model_without_the_mark():
    """Lowered, ``decode_steps`` of a routed model whose layers lack the mark
    names no ``moe_preroute``, and the marked model's does, ahead of each
    attention."""
    def lowered(cfg):
        tree = jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
        k, v = jax.eval_shape(lambda: llama.init_kv_pages(cfg, 32, PS))
        ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        window = {}
        if cfg.sliding_window:
            window = dict(
                window_pages=jax.eval_shape(
                    lambda: llama.init_window_pages(cfg, 16, PS)),
                window_packed=ints(4, 5))
        return llama.decode_steps.lower(
            tree, cfg, ints(4, llama.burst_counts(cfg) + 2),
            ints(4, 8 + llama.DECODE_PACKED_TAIL), k, v,
            jax.ShapeDtypeStruct((2,), jnp.uint32), page_size=PS, num_steps=2,
            interpret=True, **window).as_text(debug_info=True)

    assert "moe_preroute" not in lowered(TINY_QWEN3_MOE)
    text = lowered(CFG)
    assert text.count("model.moe_preroute") >= CFG.n_layers
    assert "moe_preroute" in llama.MODEL_SCOPES


# -- the benchmark's own check, all columns and every eighth --------------------
@pytest.mark.parametrize("strided", [False, True], ids=["all-columns", "strided"])
def test_the_harness_check_through_the_references_system_side(
        params, strided, monkeypatch):
    """``reference.common_check`` as a run makes it (two prompts grown to a
    window and three quarters through both pools, decode steps, every layer
    alone): sound it passes, and where the comparison would hold more logits
    than ``FULL_HEAD_LOGITS`` both sides keep every ``HEAD_STRIDE``-th column
    of the head and every position."""
    import types

    if strided:
        monkeypatch.setattr(REF, "FULL_HEAD_LOGITS", 1000)
    assert REF.head_columns(20, CFG.vocab_size) == (
        slice(0, CFG.vocab_size, REF.HEAD_STRIDE) if strided else slice(None))
    engine = types.SimpleNamespace(
        params=params, model_cfg=CFG, page_size=PS, mesh=None,
        _replicated=jax.devices()[0], prefill_attn="xla")
    prompt = prompt_of(7, 16)
    got, fed = REF.system(engine, prompt, 4, True)
    want, gaps = REF.forward(params, CFG, prompt + fed)
    columns = CFG.vocab_size // REF.HEAD_STRIDE if strided else CFG.vocab_size
    assert got.shape == (len(fed) + 1, columns)
    assert want.shape == (len(prompt) + len(fed), columns)
    assert len(fed) > 3 * W // 4 and gaps.shape == (len(prompt) + len(fed),)
    assert rel_err(got, np.asarray(want)[len(prompt) - 1:]) < TOL
    with served_path.kept_layer_programs(chip_reference, REF):
        line = chip_reference.common_check(
            engine, REF, 5, interpret=True, prompt_tokens=16, steps=4)
    assert line["ok"] and line["layer_positions"] + line[
        "layer_tied_positions"] > 0
