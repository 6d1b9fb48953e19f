"""The shortcut-connected double layer (``LlamaConfig.double_layer``), zero-
compute experts among the router's outputs, a routed layer that is told what
it holds, a softmax router whose bias chooses, and the low-rank query path
into the latent pool: the served programs at ``TINY_SCMOE`` in float32 against
``chipbench/references/scmoe_mla.forward`` (float32, expanded attention,
nothing of the program's model code, given the same share). The layer's parts
alone are in ``tests/test_scmoe_layers.py``, the engine in
``tests/test_scmoe_engine.py``, refusals, presets and the loader's reading of
the config in ``tests/test_scmoe_config.py``; the helpers they share with the
other architectures are ``tests/served_path.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_SCMOE, llama
from served_path import prompt_of, rel_err

CFG = TINY_SCMOE
#: every routed expert held: the uncut layer
UNCUT = dataclasses.replace(CFG, expert_first=0, expert_count=None)
PS = 4
TOL = chip_reference.TOL_F32
REF = chip_reference.load("scmoe_mla")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 7)


@pytest.fixture(scope="module")
def uncut_params():
    return served_path.params_of(UNCUT, 7)


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return served_path.reference_logits(REF, params, cfg, tokens)


def served(params, rows, steps, attn_impl, cfg=CFG):
    """``served_path.served`` through the one pool of latent rows: the
    logits a row, [1 + steps, vocab], and the tokens fed."""
    got, fed, (_, v_pages, _) = served_path.served(
        params, cfg, rows, steps, attn_impl, page_size=PS)
    assert v_pages.nbytes == 0
    return got, fed


def share_of(params, first, count):
    """The tree of the rank that holds experts ``first .. first + count - 1``
    of ``params``' (every one held there)."""
    def cut(layer):
        moe = {k: v[first:first + count] if k.startswith("w_") else v
               for k, v in layer["moe"].items()}
        return {**layer, "moe": moe}

    return {**params, "layers": [cut(layer) for layer in params["layers"]]}


# -- the served programs against the reference ---------------------------------
@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("rows", [
    pytest.param([(19, 0)], id="cold-alone"),
    pytest.param([(23, 12)], id="paged-context-alone"),
    pytest.param([(30, 16), (9, 0), (21, 8)], id="batch-of-unequal-lengths"),
])
def test_prefill_then_decode_through_the_pool(params, rows, attn_impl):
    rows = [(prompt_of(40 + i, n), r) for i, (n, r) in enumerate(rows)]
    # 6 steps from 19 / 23 / 30 / 9 / 21 tokens: every row crosses a page
    got, fed = served(params, rows, 6, attn_impl, cfg=CFG)
    for (prompt, _), logits, tokens in zip(rows, got, fed):
        want = reference_logits(params, prompt + tokens)[len(prompt) - 1:]
        assert rel_err(logits, want) < TOL


def test_a_double_layer_run_alone_is_served_by_what_it_holds(params):
    """The benchmark's layer-alone comparison: one entry of ``layers`` as a
    one-layer model is a whole published layer, two attentions in the pool."""
    cfg1 = dataclasses.replace(CFG, n_layers=1)
    prompt = prompt_of(5, 11)
    for layer in params["layers"]:
        alone = {**params, "layers": [layer]}
        got, fed = served(alone, [(prompt, 4)], 2, "xla", cfg=cfg1)
        want = reference_logits(alone, prompt + fed[0], cfg1)[len(prompt) - 1:]
        assert rel_err(got[0], want) < TOL
    k_pages, _ = jax.eval_shape(lambda: llama.init_kv_pages(cfg1, 8, PS))
    assert k_pages.shape[0] == 2


# -- the shares add up ---------------------------------------------------------
def _routed(layer, cfg, x, **kw):
    return np.asarray(llama._moe_mlp_routed(
        layer["moe"], cfg, x, interpret=True, **kw), np.float32)


def test_the_shares_of_a_four_way_split_add_up_to_the_uncut_layer(uncut_params):
    layer = uncut_params["layers"][1]
    x = jnp.asarray(
        np.random.default_rng(3).normal(size=(2, 9, CFG.hidden_size)),
        jnp.float32)
    flat = x.reshape(-1, CFG.hidden_size)
    with jax.default_matmul_precision("highest"):
        uncut_ref, _ = REF._moe(layer["moe"], UNCUT, flat)
        # the zero experts' part alone: a rank that holds no routed expert
        # the router chose... is what every rank's part shares
        topv, topi = llama._moe_gates(layer["moe"], UNCUT, flat)
    zero = np.asarray(
        jnp.sum(jnp.where(topi >= CFG.n_experts, topv, 0.0), -1)[:, None] * flat)
    total = np.zeros_like(zero)
    for rank in range(4):
        cfg = dataclasses.replace(CFG, expert_first=4 * rank, expert_count=4)
        mine = share_of(uncut_params, 4 * rank, 4)["layers"][1]
        part = _routed(mine, cfg, x).reshape(zero.shape)
        with jax.default_matmul_precision("highest"):
            want, _ = REF._moe(mine["moe"], cfg, flat)
        np.testing.assert_allclose(part, want, atol=2e-5, rtol=2e-4)
        total += part - zero  # the routed part of this rank
    np.testing.assert_allclose(total + zero, uncut_ref, atol=5e-5, rtol=2e-4)
    # ... and the program with every expert held is the uncut layer too
    np.testing.assert_allclose(
        _routed(layer, UNCUT, x).reshape(zero.shape), uncut_ref,
        atol=5e-5, rtol=2e-4)


# -- chosen by p + bias, weighed by p ------------------------------------------
def test_the_bias_chooses_and_does_not_weigh(params):
    layer = dict(params["layers"][0]["moe"])
    x = jnp.asarray(
        np.random.default_rng(1).normal(size=(7, CFG.hidden_size)), jnp.float32)
    p = np.asarray(jax.nn.softmax(x @ layer["router"], axis=-1))
    lifted = [2, 5, 17, 23]  # two routed experts, two zero experts
    layer["router_bias"] = jnp.zeros(CFG.router_outputs).at[
        jnp.asarray(lifted)].set(5.0)
    topv, topi = llama._moe_gates(layer, CFG, x)
    assert (np.sort(np.asarray(topi), axis=1) == lifted).all()
    picked = np.take_along_axis(p, np.asarray(topi), axis=1)
    # times the scaling factor, not renormalised
    np.testing.assert_allclose(topv, 6.0 * picked, rtol=1e-6)
    # without a bias in the layer the largest probabilities are chosen
    del layer["router_bias"]
    _, plain = llama._moe_gates(layer, CFG, x)
    assert (np.sort(np.asarray(plain), 1)
            == np.sort(np.argsort(-p, 1)[:, :4], 1)).all()


def _state_dict(params, n_experts_written):
    """``params`` (every expert held) under the checkpoint's names."""
    attn = {
        "wq_a": "q_a_proj.weight", "q_a_norm": "q_a_layernorm.weight",
        "wq_b": "q_b_proj.weight", "wkv_a": "kv_a_proj_with_mqa.weight",
        "kv_norm": "kv_a_layernorm.weight", "wkv_b": "kv_b_proj.weight",
        "wo": "o_proj.weight",
    }
    ffn = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}

    def put(sd, name, w):
        w = np.asarray(w)
        sd[name] = w.T if w.ndim == 2 else w

    sd = {}
    put(sd, "model.embed_tokens.weight", params["embed"].T)
    put(sd, "model.norm.weight", params["final_norm"])
    put(sd, "lm_head.weight", params["lm_head"])
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        for j, half in enumerate((layer, layer["second"])):
            put(sd, f"{p}input_layernorm.{j}.weight", half["attn_norm"])
            put(sd, f"{p}post_attention_layernorm.{j}.weight", half["mlp_norm"])
            for ours, theirs in attn.items():
                put(sd, f"{p}self_attn.{j}.{theirs}", half[ours])
            for ours, theirs in ffn.items():
                put(sd, f"{p}mlps.{j}.{theirs}.weight", half[ours])
        put(sd, p + "mlp.router.classifier.weight", layer["moe"]["router"])
        put(sd, p + "mlp.router.e_score_correction_bias",
            layer["moe"]["router_bias"])
        for e in range(n_experts_written):
            for ours, theirs in ffn.items():
                put(sd, f"{p}mlp.experts.{e}.{theirs}.weight",
                    layer["moe"][ours][e])
    return sd


def test_a_held_range_loads_its_own_experts(uncut_params):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    sd = _state_dict(uncut_params, UNCUT.n_experts)
    for cfg, want in ((UNCUT, uncut_params), (CFG, share_of(uncut_params, 4, 4))):
        loaded = load_hf_state_dict(sd, cfg)
        assert jax.tree.structure(loaded) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(want)):
            assert a.dtype == b.dtype and np.array_equal(a, b)
    prompt = prompt_of(77, 14)
    got, fed = served(loaded, [(prompt, 8)], 2, "xla", cfg=CFG)
    want = reference_logits(loaded, prompt + fed[0])[len(prompt) - 1:]
    assert rel_err(got[0], want) < TOL


@pytest.mark.parametrize("key", [
    "audio_tower.layers.0.self_attn.q_proj.weight",
    "visual.blocks.0.attn.qkv.weight",
    "model.layers.0.mlp.experts.16.gate_proj.weight",  # past the 16 scored
    "model.mtp.layers.0.eh_proj.weight",
])
def test_a_key_that_is_not_mapped_is_refused_by_name(uncut_params, key):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    sd = _state_dict(uncut_params, UNCUT.n_experts)
    sd[key] = np.zeros((2, 2), np.float32)
    with pytest.raises(NotImplementedError, match=key.split(".")[0]):
        load_hf_state_dict(sd, CFG)
