"""Convolution layers: what the pools hold by shape, the router's epsilon,
what the state does not serve refused by name (the engine, the pod's page
moves, the model programs), the presets, and the loader on the published
``lfm2_moe`` config.
"""

import dataclasses

import jax
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    LFM2_8B_A1B,
    TINY_LFM2_MOE,
    TINY_QWEN3_MOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig, EngineConfig
from llm_d_kv_cache_manager_tpu.server.engine import Engine
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model

CFG = TINY_LFM2_MOE
PS = 4


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(34), CFG)


def test_the_pools_say_what_they_hold():
    """Two KV heads of 64 share a 128-lane row (the TPU compiler pads a
    narrower minor dimension to 128 lanes in HBM whatever the array says),
    the key/value pools count the layers that attend, the state pool the
    others, a slot a page."""
    assert LFM2_8B_A1B.kv_row_shape == (4, 128)
    cut = dataclasses.replace(LFM2_8B_A1B, n_layers=14)
    assert (cut.n_conv_layers, cut.n_attn_layers) == (11, 3)
    k, v = jax.eval_shape(lambda: llama.init_kv_pages(cut, 64, 16))
    state = jax.eval_shape(lambda: llama.init_state_pages(cut, 64))
    assert k.shape == v.shape == (3, 64, 16, 4, 128)
    assert state.shape == (11, 64, 2 * 2048)
    slots = 64 * 16
    assert (k.size + v.size) * 2 // slots == 6144
    assert state.size * 2 // slots == 5632
    assert CFG.kv_row_shape == (2, 128) and CFG.kv_heads_per_row == 2
    # a model without convolution layers keeps a row a head
    assert TINY_QWEN3_MOE.kv_heads_per_row == 1


def test_the_routers_epsilon_follows_the_model(params):
    """1e-6 here (the family's modelling code), 1e-20 for every other
    sigmoid router: ``kanana-2-30b-a3b``'s arithmetic is what it was."""
    from llm_d_kv_cache_manager_tpu.models import KANANA_2_30B_A3B

    assert LFM2_8B_A1B.router_norm_eps == CFG.router_norm_eps == 1e-6
    assert KANANA_2_30B_A3B.router_norm_eps == 1e-20
    layer = next(la for la in params["layers"] if "router" in la)
    x = np.zeros((1, CFG.hidden_size), np.float32)  # every score 0.5
    gates, _ = llama._moe_gates(layer, CFG, x)
    np.testing.assert_allclose(
        np.asarray(gates), 0.5 / (0.5 * CFG.n_experts_per_tok + 1e-6), rtol=1e-6)


# -- what the state does not serve is refused by name -------------------------
@pytest.mark.parametrize("what, name", [
    (dict(spec_decode="prompt_lookup"), "spec_decode"),
    (dict(model=dataclasses.replace(CFG, block_length=4)), "block_length"),
    (dict(kv_quant_hbm="int8"), "kv_quant_hbm"),
    (dict(block_manager=BlockManagerConfig(
        total_pages=16, page_size=PS, host_pages=8)), "host_pages"),
    (dict(remote_tier=True), "remote_tier"),
    (dict(sp=2), "sp > 1"),
    (dict(tp=2), "tp > 1"),
    (dict(model=dataclasses.replace(CFG, conv_bias=True)), "conv_bias"),
    (dict(model=dataclasses.replace(CFG, conv_L_cache=1)), "conv_L_cache"),
    (dict(model=dataclasses.replace(
        CFG, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16)), "kv_lora_rank"),
])
def test_engine_refuses_by_name(what, name):
    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, prefill_bucket=16)
    config = dataclasses.replace(config, **what)
    with pytest.raises(ValueError, match="conv layers.*" + name):
        Engine(config)


@pytest.mark.parametrize("entry", [
    "transfer_endpoint", "transfer_endpoint-injected", "export_kv_blocks",
    "import_kv_blocks", "freeze_for_migration",
])
def test_page_moves_are_refused_by_name(params, entry):
    """``TRANSFER_ENDPOINT``, export, import and migration move K and V
    pages and no state: the pod refuses the endpoint at construction, the
    engine's entry points refuse any other caller."""
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, prefill_bucket=16)
    pod = PodServerConfig(
        engine=config, transfer_endpoint="tcp://127.0.0.1:0", publish_events=False)
    calls = {
        "transfer_endpoint": lambda: PodServer(pod),
        "transfer_endpoint-injected":
            lambda: PodServer(pod, engine=Engine(config, params=params)),
        "export_kv_blocks":
            lambda: Engine(config, params=params).export_kv_blocks([1, 2]),
        "import_kv_blocks":
            lambda: Engine(config, params=params).import_kv_blocks([]),
        "freeze_for_migration":
            lambda: Engine(config, params=params).freeze_for_migration("r"),
    }
    with pytest.raises(ValueError, match="conv layers.*" + entry.split("-")[0]):
        calls[entry]()


def test_the_model_programs_refuse_what_carries_no_state(params):
    """Below the engine: a program handed a tree with convolution layers
    and no state pool says so (the verify scan and the block forward call
    ``_prefill_body`` without one)."""
    k, v = llama.init_kv_pages(CFG, 8, PS)
    pos = np.arange(6)[None, :]
    with pytest.raises(ValueError, match="state pool"):
        llama.prefill(
            params, CFG, np.ones((1, 6), np.int32), pos, np.ones((1, 6), bool),
            k, v, 1 + pos // PS, pos % PS, np.zeros((1, 0), np.int32),
            np.zeros((1,), np.int32), interpret=True)
    with pytest.raises(ValueError, match="state pool"):
        llama.decode_step(
            params, CFG, np.ones((1,), np.int32), np.asarray([6]), k, v,
            np.asarray([[1, 2]]), np.asarray([7]), page_size=PS, interpret=True)


# -- presets and the loader --------------------------------------------------
def test_presets():
    assert _resolve_model("LiquidAI/LFM2-8B-A1B") is LFM2_8B_A1B
    assert _resolve_model("tiny-lfm2-moe") is CFG
    kinds = LFM2_8B_A1B.layer_types
    assert len(kinds) == 24 and kinds.count("conv") == 18
    assert [i for i, k in enumerate(kinds) if k != "conv"] == [2, 6, 10, 14, 18, 21]
    assert LFM2_8B_A1B.layer_types_published == list(kinds)
    cut = dataclasses.replace(LFM2_8B_A1B, n_layers=14)
    assert cut.layer_types_published == list(kinds)  # the published list, whole
    assert hash(cut) != hash(LFM2_8B_A1B)  # a preset stays hashable
    assert LFM2_8B_A1B.use_expert_bias and not TINY_QWEN3_MOE.use_expert_bias
    # the tiny preset: two dense layers, a period and more, heads of 64
    assert CFG.first_k_dense == 2 and CFG.layer_types[2:7] == (
        "full_attention", "conv", "conv", "conv", "full_attention")


class _Lfm2Config:  # the published config.json's keys (the catalog's row)
    model_type = "lfm2_moe"
    conv_L_cache, conv_bias = 3, False
    hidden_size, intermediate_size = 2048, 7168
    layer_types = [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
    max_position_embeddings, moe_intermediate_size = 128000, 1792
    norm_eps, norm_topk_prob = 1e-5, True
    num_attention_heads, num_dense_layers, num_experts = 32, 2, 32
    num_experts_per_tok, num_hidden_layers, num_key_value_heads = 4, 24, 8
    rope_theta, routed_scaling_factor, use_expert_bias = 1000000, 1, True
    vocab_size = 65536


def test_the_loader_reads_the_published_config():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    assert config_from_hf(_Lfm2Config()) == LFM2_8B_A1B


@pytest.mark.parametrize("change, name", [
    (dict(conv_bias=True), "conv_bias"),
    (dict(conv_L_cache=1), "conv_L_cache"),
    (dict(use_expert_bias=False), "use_expert_bias"),
    (dict(layer_types=["conv", "sliding_attention"] * 12), "layer_types"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "yarn"),
])
def test_the_loader_refuses_by_name(change, name):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = _Lfm2Config()
    for key, value in change.items():
        setattr(hf, key, value)
    with pytest.raises(NotImplementedError, match=name):
        config_from_hf(hf)
