"""A model with linear-attention layers beside gated GQA layers that take no
position: the presets (``SOLAR_OPEN2_250B``, ``TINY_SOLAR_HYBRID``), the
loader on the published ``solar_open2`` config and a synthetic state dict, and
the sharding and quantisation trees. The refusals are in
``tests/test_kda_config.py`` beside those of the latent pool's sibling.
"""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import (
    SOLAR_OPEN2_250B,
    TINY_LING_HYBRID,
    TINY_SOLAR_HYBRID,
    llama,
)
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model
from served_path import prompt_of, rel_err

CFG = TINY_SOLAR_HYBRID
PS = 4
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REF = chip_reference.load("kda_gqa_moe")


def test_presets():
    big = SOLAR_OPEN2_250B
    assert _resolve_model("upstage/Solar-Open2-250B") is big
    assert _resolve_model("tiny-solar-hybrid") is CFG
    kinds = big.layer_types
    assert len(kinds) == 48 and kinds.count("linear_attention") == 36
    assert big.gqa_layers == list(range(0, 48, 4))
    assert (big.n_kda_layers, big.n_attn_layers, big.n_window_layers) == (36, 12, 0)
    assert big.kv_row_shape == (8, 128) and big.hd == 128 and big.no_rope and not big.use_rope
    assert not big.kda_full_proj and big.context_pool_name == "key/value pools"
    assert TINY_LING_HYBRID.context_pool_name == "latent pool"
    cut = dataclasses.replace(
        big, n_layers=4, vocab_size=24576, expert_first=0, expert_count=40)
    assert (cut.n_kda_layers, cut.n_attn_layers, cut.experts_held) == (3, 1, 40)
    assert cut.gqa_layers == big.gqa_layers  # as published, whatever is run
    # a slot of the cut: 3 x (4 MiB of matrices + 144 KiB of carried rows)
    assert cut.kda_state_bytes == 3 * (4 * 2**20 + 144 * 2**10) == 13_025_280
    assert cut.kda_state_bytes // 1024 == 12_720
    # the carried rows of a slot: 36 whole bf16 tiles of (16, 128), no padding
    assert cut.kda_conv_tile == (576, 128) and cut.kda_conv_row == 576 * 128
    # no layer of the model rotates; the ling preset's and a plain model's do
    assert not llama._rotates({}, big) and not llama._rotates({"window": 0}, big)
    assert llama._rotates({}, TINY_LING_HYBRID)
    # the tiny tree: the leaves a layer's kind gives it
    tree = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), CFG))["layers"]
    assert ["kda_qkv" in la for la in tree] == [False, True, True, True] * 2
    assert ["wg" in la for la in tree] == [True, False, False, False] * 2
    assert all("router" in la and "ws_gate" in la for la in tree)
    assert not any("kda_wf" in la or "kda_wg" in la for la in tree)
    assert tree[0]["wq"].shape == (64, 64) and tree[0]["wk"].shape == (64, 16)
    assert tree[1]["kda_wf_down"].shape == tree[1]["kda_wg_down"].shape == (64, 16)
    assert tree[1]["kda_wf_up"].shape == tree[1]["kda_wg_up"].shape == (16, 64)
    assert tree[1]["kda_qkv"].shape == (64, 3 * 4 * 16)
    assert tree[1]["router"].shape == (64, 8)


def _catalog_row():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Solar-Open2-250B":
                return row
    pytest.skip("the catalog has no such row")


def test_the_loader_reads_the_published_config():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = types.SimpleNamespace(**_catalog_row()["config"])
    assert config_from_hf(hf) == SOLAR_OPEN2_250B
    # a shallower file keeps the period
    cfg = config_from_hf(types.SimpleNamespace(**{
        **_catalog_row()["config"], "num_hidden_layers": 8,
        "gqa_layers": [0, 4]}))
    assert cfg.layer_types == (
        ("full_attention",) + ("linear_attention",) * 3) * 2


def _state_dict(whole, cfg):
    """``whole`` under the checkpoint's names (``[out, in]`` matrices, the
    three convolutions ``[channels, 1, taps]``, ``[q | k | v]`` apart)."""
    hk = cfg.n_heads * cfg.kda_head_dim
    sd = {"model.embed_tokens.weight": whole["embed"],
          "model.norm.weight": whole["final_norm"],
          "lm_head.weight": np.asarray(whole["lm_head"]).T}
    for i, layer in enumerate(whole["layers"]):
        p, a = f"model.layers.{i}.", f"model.layers.{i}.self_attn."
        sd[p + "input_layernorm.weight"] = layer["attn_norm"]
        sd[p + "post_attention_layernorm.weight"] = layer["mlp_norm"]
        sd[a + "o_proj.weight"] = np.asarray(layer["wo"]).T
        if "kda_qkv" in layer:
            for j, t in enumerate("qkv"):
                cols = slice(j * hk, (j + 1) * hk)
                sd[f"{a}{t}_proj.weight"] = np.asarray(layer["kda_qkv"])[:, cols].T
                sd[f"{a}{t}_conv1d.weight"] = np.asarray(
                    layer["kda_conv_w"])[:, cols].T[:, None, :]
            for ours, theirs in (
                    ("kda_wf_down", "f_a_proj"), ("kda_wf_up", "f_b_proj"),
                    ("kda_wg_down", "g_a_proj"), ("kda_wg_up", "g_b_proj"),
                    ("kda_wb", "b_proj")):
                sd[f"{a}{theirs}.weight"] = np.asarray(layer[ours]).T
            sd[a + "dt_bias"] = layer["kda_dt_bias"]
            sd[a + "A_log"] = np.asarray(layer["kda_A_log"]).reshape(1, 1, -1, 1)
            sd[a + "o_norm.weight"] = layer["kda_o_norm"]
        else:
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wg", "g_proj")):
                sd[f"{a}{theirs}.weight"] = np.asarray(layer[ours]).T
        sd[p + "mlp.gate.weight"] = np.asarray(layer["router"]).T
        sd[p + "mlp.gate.e_score_correction_bias"] = layer["router_bias"]
        for name in ("gate", "up", "down"):
            w = np.asarray(layer["w_" + name])
            for j in range(cfg.n_experts):
                sd[f"{p}mlp.experts.{j}.{name}_proj.weight"] = w[j].T
            sd[f"{p}mlp.shared_experts.{name}_proj.weight"] = np.asarray(
                layer["ws_" + name]).T
    return sd


def test_a_saved_state_dict_loads_to_the_references_logits():
    """One period of the tiny model written out under the checkpoint's names
    and read back: the loaded tree is the tree, the served program on it
    gives the reference's logits, and a rank that holds a share of the
    experts reads its own range of them."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    cfg = dataclasses.replace(CFG, n_layers=4)
    whole = llama.init_params(jax.random.PRNGKey(9), cfg)
    sd = _state_dict(whole, cfg)
    loaded = load_hf_state_dict(sd, cfg)
    assert set(loaded) == set(whole)
    for got, layer in zip(loaded["layers"], whole["layers"]):
        assert set(got) == set(layer)
        for key, want in layer.items():
            assert got[key].dtype == want.dtype, key
            np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want))
    prompt = prompt_of(58, 21)
    (got,), (fed,), _ = served_path.served(
        loaded, cfg, [(prompt, 8)], 3, "xla", page_size=PS,
        second=served_path.StateSlots())
    want = served_path.reference_logits(REF, loaded, cfg, prompt + fed)
    assert rel_err(got, want[len(prompt) - 1:]) < chip_reference.TOL_F32
    share = dataclasses.replace(cfg, expert_first=2, expert_count=4)
    held = load_hf_state_dict(sd, share)
    np.testing.assert_array_equal(
        np.asarray(held["layers"][1]["w_up"]),
        np.asarray(whole["layers"][1]["w_up"])[2:6])
    with pytest.raises(KeyError, match="f_a_proj"):
        load_hf_state_dict(
            {k: v for k, v in sd.items() if "layers.2.self_attn.f_a_proj"
             not in k}, cfg)


# -- the sharding and quantisation trees ---------------------------------------
def test_the_sharding_tree_is_the_parameter_tree():
    from llm_d_kv_cache_manager_tpu.parallel.sharding import param_specs

    for cfg in (CFG, dataclasses.replace(TINY_LING_HYBRID, kda_lora=True),
                TINY_LING_HYBRID):
        tree = jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
        specs = param_specs(cfg, tp=1)
        assert set(specs) == set(tree)
        for spec, layer in zip(specs["layers"], tree["layers"]):
            assert set(spec) == set(layer)


def test_quantised_weights_keep_the_gates_and_the_router():
    from llm_d_kv_cache_manager_tpu.models import quant

    cfg = dataclasses.replace(CFG, n_layers=2)
    params = served_path.params_of(cfg, 58)
    q = quant.quantize_params(params, quantize_experts=True)
    for got, layer in zip(q["layers"], params["layers"]):
        assert set(got) == set(layer)
        assert isinstance(got["wo"], quant.QuantizedTensor)
        assert isinstance(got["w_gate"], quant.QuantizedTensor)
        for kept in ("router", "router_bias", "attn_norm"):
            assert got[kept] is layer[kept]
    gqa, linear = q["layers"]
    assert isinstance(gqa["wg"], quant.QuantizedTensor)  # the GQA gate: a matmul
    for kept in ("kda_wf_down", "kda_wf_up", "kda_wg_down", "kda_wg_up",
                 "kda_wb", "kda_A_log", "kda_dt_bias", "kda_conv_w"):
        assert linear[kept] is params["layers"][1][kept]
    # the int8 tree runs through both pools, another model by a little
    prompt = prompt_of(59, 13)
    (want,), _, _ = served_path.served(
        params, cfg, [(prompt, 4)], 1, "xla", page_size=PS,
        second=served_path.StateSlots())
    (got,), _, _ = served_path.served(
        q, cfg, [(prompt, 4)], 1, "xla", page_size=PS,
        second=served_path.StateSlots())
    assert 1e-4 < rel_err(got[0], want[0]) < 0.3
    # ``init_params(quantize="int8")`` makes the same kinds of leaves
    made = llama.init_params(jax.random.PRNGKey(1), cfg, quantize="int8")
    assert isinstance(made["layers"][1]["kda_qkv"], quant.QuantizedTensor)
    assert made["layers"][1]["kda_wf_down"].dtype == jnp.float32
