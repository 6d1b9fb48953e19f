"""Window and full attention layers in one model (``LlamaConfig.
sliding_window``; Trinity-Large-Preview, ``afmoe``): the served programs
through both pools against the plain reference, at ``TINY_SWA_MOE`` in
float32 (window 8, pages of 4: sliding, sliding, sliding, full, sliding).

The reference side is ``chipbench/references/swa_moe.forward`` (float32, the
whole sequence at once, nothing of the program's model code, no cache). These
tests hold the window's edge and a tree read back from a checkpoint to it,
and that a model without sliding layers has the programs it had. Every way a
row lies in the two pools in ``llama.prefill`` and ``llama.decode_step`` (a
chunk's own keys, a window table that starts mid-context, a page reused after
it was given back) is in ``tests/test_swa_rows.py``, the benchmark's own check
through the reference's system side and one rank's share of the experts in
``tests/test_swa_harness.py``, the kernels alone in
``tests/test_swa_kernels.py``, the block manager's three rules in
``tests/test_swa_pool.py``, the engine in ``tests/test_swa_engine.py`` and
``tests/test_swa_engine_decode.py``, refusals, presets and the loader in
``tests/test_swa_config.py``; the helpers they share with the other
architectures are ``tests/served_path.py``.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import (
    TINY_MOE,
    TINY_QWEN3_MOE,
    TINY_SWA_MOE,
    llama,
)
from served_path import prompt_of, rel_err

CFG = TINY_SWA_MOE
#: every routed expert held: the uncut layer
UNCUT = dataclasses.replace(CFG, expert_first=0, expert_count=None)
PS = 4
W = CFG.sliding_window
TOL = chip_reference.TOL_F32
REF = chip_reference.load("swa_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 43)


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return served_path.reference_logits(REF, params, cfg, tokens)


def served(params, rows, steps, attn_impl, cfg=CFG):
    """``served_path.served`` through both pools: the logits a row,
    [1 + steps, vocab], and the tokens fed."""
    got, fed, _ = served_path.served(
        params, cfg, rows, steps, attn_impl, page_size=PS,
        second=served_path.WindowPages(REF.WindowTable, PS))
    return got, fed


# -- the window's edge ---------------------------------------------------------
def test_the_windows_edge():
    """Windows of W - 1, W and W + 1 positions each agree with the reference
    of that window, and differ from each other by far more than the
    tolerance: a window off by one is seen in float32."""
    prompt = prompt_of(50, 3 * W)
    seen = {}
    # (a window is a sliding layer's, whatever the depth: three programs a
    # window at two fifths of the preset's each)
    short = served_path.ONE_OF_EACH_SWA
    params = served_path.params_of(short, 43)
    for w in (W - 1, W, W + 1):
        cfg = dataclasses.replace(short, sliding_window=w)
        (got,), (fed,) = served(params, [(prompt, W)], 5, "xla", cfg=cfg)
        want = reference_logits(params, prompt + fed, cfg)[len(prompt) - 1:]
        assert rel_err(got, want) < TOL
        seen[w] = reference_logits(params, prompt, cfg)[-1]
    assert rel_err(seen[W - 1], seen[W]) > 100 * TOL
    assert rel_err(seen[W + 1], seen[W]) > 100 * TOL


# -- a model without sliding layers has the programs it had --------------------
def _names_alone(text: str) -> str:
    """A lowered program's text with the file names struck out of its
    locations: what is left names operations and scopes. A worker that ran
    ``tests/test_paged_attention_window.py`` first has that file's name in
    the locations of every function it was the first to trace, and "no
    ``paged_attention_window`` in the text" failed by it (two of the
    parent's whole runs in three)."""
    return re.sub(r'"[^"\n]*\.py"', '""', text)


@pytest.mark.parametrize("cfg", [TINY_MOE, TINY_QWEN3_MOE], ids=["moe", "qwen3"])
def test_no_window_operand_reaches_a_model_without_sliding_layers(cfg):
    """Lowered, ``decode_steps`` and a prefill program of a model without
    sliding layers take their parameters and the operands they always took,
    and nothing in them is named for a window. (Their text was compared with
    the parent commit's, byte for byte, when the window came: PERF.md
    section 6, PR 43.)"""
    params = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    k, v = jax.eval_shape(lambda: llama.init_kv_pages(cfg, 32, PS))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    leaves = len(jax.tree.leaves(params))
    programs = {
        "decode_steps": (llama.decode_steps.lower(
            params, cfg, ints(4, llama.burst_counts(cfg) + 2),
            ints(4, 8 + llama.DECODE_PACKED_TAIL), k, v,
            jax.ShapeDtypeStruct((2,), jnp.uint32), page_size=PS, num_steps=2,
            interpret=True), 5),
        "prefill_packed": (llama.prefill_packed.lower(
            params, cfg, ints(4, 5 * 16 + 4 + 1), k, v, chunk=16,
            attn_impl="pallas", interpret=True), 3),
    }
    for name, (lowered, operands) in programs.items():
        text = _names_alone(lowered.as_text(debug_info=True))
        assert "paged_attention_window" not in text, name
        assert "attn_window" not in text, name
        assert len(jax.tree.leaves(lowered.args_info)) == leaves + operands, name


def test_the_sliding_layers_calls_are_named(params):
    """The trace tells the two decode kernels apart, and the sliding layers'
    attention has a scope of its own."""
    k, v = jax.eval_shape(lambda: llama.init_kv_pages(CFG, 32, PS))
    wp = jax.eval_shape(lambda: llama.init_window_pages(CFG, 16, PS))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    text = llama.decode_steps.lower(
        jax.eval_shape(lambda: params), CFG, ints(4, llama.burst_counts(CFG) + 2),
        ints(4, 8 + llama.DECODE_PACKED_TAIL), k, v,
        jax.ShapeDtypeStruct((2,), jnp.uint32), page_size=PS, num_steps=2,
        interpret=True, window_pages=wp, window_packed=ints(4, 5),
    ).as_text(debug_info=True)
    text = _names_alone(text)
    assert "paged_attention_window" in text
    assert "model.attn_window" in text and "model.attn/" in text
    assert "attn_window" in llama.MODEL_SCOPES


def test_a_saved_state_dict_loads_to_the_references_logits():
    """The tiny model with every expert written out under the checkpoint's
    names ([out, in] matrices) and read back by a rank that holds a range
    of them: the loaded tree is that rank's tree, and the served program on
    it gives the reference's logits."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    whole = llama.init_params(jax.random.PRNGKey(9), UNCUT)
    names = {
        "attn_norm": "input_layernorm.weight",
        "attn_post_norm": "post_attention_layernorm.weight",
        "mlp_norm": "pre_mlp_layernorm.weight",
        "mlp_post_norm": "post_mlp_layernorm.weight",
        "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
        "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
        "wg": "self_attn.gate_proj.weight",
        "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
        "router": "mlp.router.gate.weight", "router_bias": "mlp.expert_bias",
    }
    ffn = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}
    sd = {"model.embed_tokens.weight": whole["embed"],
          "model.norm.weight": whole["final_norm"],
          "lm_head.weight": np.asarray(whole["lm_head"]).T}
    for i, layer in enumerate(whole["layers"]):
        p = f"model.layers.{i}."
        for ours, theirs in names.items():
            if ours in layer:
                w = np.asarray(layer[ours])
                sd[p + theirs] = w.T if w.ndim == 2 else w
        for ours, theirs in ffn.items():
            w = np.asarray(layer["w_" + ours])
            if "router" in layer:
                for j in range(CFG.n_experts):
                    sd[f"{p}mlp.experts.{j}.{theirs}.weight"] = w[j].T
                sd[f"{p}mlp.shared_experts.{theirs}.weight"] = np.asarray(
                    layer["ws_" + ours]).T
            else:
                sd[f"{p}mlp.{theirs}.weight"] = w.T
    loaded = load_hf_state_dict(sd, CFG)
    first, count = CFG.expert_first, CFG.expert_count
    for got, layer in zip(loaded["layers"], whole["layers"]):
        assert set(got) == set(layer)
        for key, want in layer.items():
            if key in ("w_gate", "w_up", "w_down") and "router" in layer:
                want = want[first:first + count]
            np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want))
    prompt = prompt_of(140, 3 * W)
    (got,), (fed,) = served(loaded, [(prompt, W)], 3, "xla")
    want = reference_logits(loaded, prompt + fed)[len(prompt) - 1:]
    assert rel_err(got, want) < TOL
