"""A model whose router reads the layer's input (SmallThinker,
``smallthinker``): what the engine refuses by name for a window model it
refuses here, the presets, the loader on the published config (and its
refusals by name, unequal layouts first), a synthetic state dict read back to
the reference's logits, and the sharding and quantisation trees.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import (
    SMALLTHINKER_21B_A3B,
    TINY_QWEN3_MOE,
    TINY_SMALLTHINKER,
    llama,
)
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig, EngineConfig
from llm_d_kv_cache_manager_tpu.server.engine import Engine
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model
from served_path import prompt_of, rel_err

CFG = TINY_SMALLTHINKER
PS = 4
W = CFG.sliding_window
REF = chip_reference.load("swa_prerouted_moe")


# -- what the window pool does not serve is refused by name --------------------
@pytest.mark.parametrize("what, name", [
    (dict(block_manager=BlockManagerConfig(
        total_pages=32, page_size=PS, host_pages=8)), "host_pages"),
    (dict(remote_tier=True), "remote_tier"),
    (dict(kv_quant_hbm="int8"), "kv_quant_hbm"),
    (dict(tp=2), "tp > 1"),
    (dict(sp=2), "sp > 1"),
    (dict(spec_decode="prompt_lookup"), "spec_decode"),
    (dict(model=dataclasses.replace(CFG, block_length=4)), "block_length"),
    (dict(model=dataclasses.replace(CFG, sliding_window=6)), "sliding_window=6"),
    (dict(block_manager=BlockManagerConfig(
        total_pages=32, page_size=PS, window_pages=8)), "window_pages=8"),
])
def test_engine_refuses_by_name(what, name):
    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=32, page_size=PS),
        interpret=True, prefill_bucket=16)
    config = dataclasses.replace(config, **what)
    with pytest.raises(ValueError, match=name):
        Engine(config)


def test_the_pods_page_moves_are_refused_by_name():
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=32, page_size=PS),
        interpret=True, prefill_bucket=16)
    with pytest.raises(ValueError, match="sliding layers.*transfer_endpoint"):
        PodServer(PodServerConfig(
            engine=config, transfer_endpoint="tcp://127.0.0.1:0",
            publish_events=False))


def test_an_activation_that_is_not_run_is_refused_by_name():
    assert CFG.act_fn is jax.nn.relu
    with pytest.raises(ValueError, match="swish"):
        dataclasses.replace(CFG, hidden_act="swish").act_fn


# -- presets ---------------------------------------------------------------------
def test_presets():
    big = SMALLTHINKER_21B_A3B
    assert _resolve_model("PowerInfer/SmallThinker-21BA3B-Instruct") is big
    assert _resolve_model("tiny-smallthinker") is CFG
    kinds = big.layer_types
    assert len(kinds) == 52 and kinds.count("sliding_attention") == 39
    assert all(k == "full_attention" for k in kinds[0::4])  # the full one FIRST
    assert big.sliding_window_layout == big.rope_layout == [0, 1, 1, 1] * 13
    assert (big.n_heads // big.n_kv_heads, big.n_heads * big.hd,
            big.hidden_size) == (7, 3584, 2560)
    assert (big.n_experts, big.n_experts_per_tok, big.moe_inter, big.hidden_act,
            big.n_shared_experts, big.first_k_dense) == (64, 6, 768, "relu", 0, 0)
    assert big.router_before_attention and big.router_applies_softmax
    assert big.holds_every_expert and not big.qk_norm and not big.qkv_bias
    cut = dataclasses.replace(big, n_layers=8)
    assert cut.sliding_window_layout == [0, 1, 1, 1] * 13  # published, whole
    assert (cut.n_attn_layers, cut.n_window_layers) == (2, 6)
    # the pools count layers by kind, whatever their order in the period
    k, _ = jax.eval_shape(lambda: llama.init_kv_pages(cut, 64, 16))
    wk, _ = jax.eval_shape(lambda: llama.init_window_pages(cut, 32, 16))
    assert k.shape == (2, 64, 16, 4, 128) and wk.shape == (6, 32, 16, 4, 128)
    assert not TINY_QWEN3_MOE.router_before_attention
    assert TINY_QWEN3_MOE.rope_layout is None
    assert (CFG.n_heads, CFG.n_kv_heads, W) == (7, 1, 8)
    # the tree: the leaves a layer's kind gives it, the mark on every layer
    tree = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), CFG))["layers"]
    assert ["window" in layer for layer in tree] == [False, True, True, True]
    assert all("preroute" in layer and "router" in layer for layer in tree)
    assert not any({"q_norm", "bq", "wg", "ws_gate", "router_bias"} & set(layer)
                   for layer in tree)
    assert tree[0]["w_gate"].shape == (8, 64, 48)
    assert tree[0]["router"].shape == (64, 8)
    plain = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), TINY_QWEN3_MOE))
    assert not any("preroute" in layer for layer in plain["layers"])


# -- the loader ------------------------------------------------------------------
class _SmallThinkerConfig:  # the published config.json's keys (the catalog's row)
    model_type = "smallthinker"
    head_dim, hidden_size, max_position_embeddings = 128, 2560, 16384
    model_name, moe_ffn_hidden_size = "smallthinker_21b_instruct", 768
    moe_num_active_primary_experts, moe_num_primary_experts = 6, 64
    moe_primary_router_apply_softmax, norm_topk_prob = True, True
    num_attention_heads, num_hidden_layers, num_key_value_heads = 28, 52, 4
    rms_norm_eps, rope_scaling, rope_theta = 1e-06, None, 1500000
    sliding_window_size, tie_word_embeddings, vocab_size = 4096, False, 151936

    def __init__(self):
        self.rope_layout = [0, 1, 1, 1] * 13
        self.sliding_window_layout = [0, 1, 1, 1] * 13


def test_the_loader_reads_the_published_config():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    assert config_from_hf(_SmallThinkerConfig()) == SMALLTHINKER_21B_A3B


@pytest.mark.parametrize("change, name", [
    # a file whose two layouts differ is not this program's model
    (dict(rope_layout=[1] * 52), "rope_layout"),
    (dict(rope_layout=[1, 0, 0, 0] * 13), "rope_layout"),
    (dict(sliding_window_layout=[0, 1, 1] * 13), "sliding_window_layout"),
    (dict(sliding_window_layout=[0, 2, 1, 1] * 13), "sliding_window_layout"),
    (dict(sliding_window_layout=[0] * 52, rope_layout=[0] * 52),
     "without a sliding layer"),
    (dict(moe_primary_router_apply_softmax=False),
     "moe_primary_router_apply_softmax"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(moe_enable_secondary_experts=True), "moe_enable_secondary_experts"),
    (dict(moe_num_secondary_experts=4), "moe_num_secondary_experts"),
    (dict(moe_layer_layout=[0] + [1] * 51), "moe_layer_layout"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "yarn"),
])
def test_the_loader_refuses_by_name(change, name):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = _SmallThinkerConfig()
    for key, value in change.items():
        setattr(hf, key, value)
    with pytest.raises(NotImplementedError, match=name):
        config_from_hf(hf)


def test_a_saved_state_dict_loads_to_the_references_logits():
    """The tiny model written out under the checkpoint's names ([out, in]
    matrices) and read back: the loaded tree is the tree, mark and window
    leaves included, and the served program on it gives the reference's
    logits."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    whole = llama.init_params(jax.random.PRNGKey(9), CFG)
    names = {
        "attn_norm": "input_layernorm.weight",
        "mlp_norm": "post_attention_layernorm.weight",
        "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
        "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
        "router": "block_sparse_moe.primary_router.weight",
    }
    sd = {"model.embed_tokens.weight": whole["embed"],
          "model.norm.weight": whole["final_norm"],
          "lm_head.weight": np.asarray(whole["lm_head"]).T}
    for i, layer in enumerate(whole["layers"]):
        p = f"model.layers.{i}."
        for ours, theirs in names.items():
            w = np.asarray(layer[ours])
            sd[p + theirs] = w.T if w.ndim == 2 else w
        for name in ("gate", "up", "down"):
            w = np.asarray(layer["w_" + name])
            for j in range(CFG.n_experts):
                sd[f"{p}block_sparse_moe.experts.{j}.{name}.weight"] = w[j].T
    loaded = load_hf_state_dict(sd, CFG)
    assert set(loaded) == set(whole)
    for got, layer in zip(loaded["layers"], whole["layers"]):
        assert set(got) == set(layer)
        for key, want in layer.items():
            np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want))
    prompt = prompt_of(140, 3 * W)
    (got,), (fed,), _ = served_path.served(
        loaded, CFG, [(prompt, W - 3)], 3, "xla", page_size=PS,
        second=served_path.WindowPages(REF.WindowTable, PS))
    want = served_path.reference_logits(REF, loaded, CFG, prompt + fed)
    assert rel_err(got, want[len(prompt) - 1:]) < chip_reference.TOL_F32
    with pytest.raises(KeyError, match="primary_router"):
        load_hf_state_dict(
            {k: v for k, v in sd.items() if "layers.2.block_sparse_moe.primary"
             not in k}, CFG)


# -- the sharding and quantisation trees ---------------------------------------
def test_the_sharding_tree_is_the_parameter_tree():
    from llm_d_kv_cache_manager_tpu.parallel.sharding import param_specs

    tree = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), CFG))
    specs = param_specs(CFG, tp=1)
    assert set(specs) == set(tree)
    for spec, layer in zip(specs["layers"], tree["layers"]):
        assert set(spec) == set(layer)
    plain = param_specs(TINY_QWEN3_MOE, tp=1)
    assert not any("preroute" in layer for layer in plain["layers"])


def test_quantised_weights_keep_the_router_and_the_marks():
    from llm_d_kv_cache_manager_tpu.models import quant

    params = served_path.params_of(CFG, 54)
    q = quant.quantize_params(params, quantize_experts=True)
    for got, layer in zip(q["layers"], params["layers"]):
        assert set(got) == set(layer)
        assert isinstance(got["wq"], quant.QuantizedTensor)
        assert isinstance(got["w_gate"], quant.QuantizedTensor)
        for kept in ("router", "preroute", "attn_norm"):
            assert got[kept] is layer[kept]
    # the int8 tree runs, and is another model than the float one by a little
    y = jax.random.normal(jax.random.PRNGKey(3), (1, 4, CFG.hidden_size))
    layer, qlayer = params["layers"][0], q["layers"][0]
    gates = llama._preroute(layer, CFG, y)
    want = llama._mlp(layer, CFG, y, interpret=True, gates=gates)
    got = llama._mlp(qlayer, CFG, y, interpret=True,
                     gates=llama._preroute(qlayer, CFG, y))
    assert 1e-4 < rel_err(np.asarray(got), np.asarray(want)) < 0.1


def test_the_training_forward_refuses_the_model_by_name():
    """``parallel/train.py`` routes every layer from its own FFN input: a
    marked layer says so instead of running another model."""
    from llm_d_kv_cache_manager_tpu.parallel import train

    params = served_path.params_of(CFG, 54)
    with pytest.raises(ValueError, match="_preroute"):
        train.loss_fn(params, CFG, jnp.zeros((1, 8), jnp.int32), interpret=True)
