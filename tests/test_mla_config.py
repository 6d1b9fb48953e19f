"""Latent attention: what the pool holds by shape, what a latent pool does
not serve refused by name (the engine, the pod's page moves), the presets,
and the loader on the published ``deepseek_v3`` config (with and without a
low-rank query path).
"""

import dataclasses

import jax
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    KANANA_2_30B_A3B,
    TINY_MLA_MOE,
    TINY_QWEN3_MOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig, EngineConfig
from llm_d_kv_cache_manager_tpu.server.engine import Engine
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model

CFG = TINY_MLA_MOE
PS = 4


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(11), CFG)


def test_the_pool_is_one_row_a_token_and_nothing_else():
    for cfg, values in ((KANANA_2_30B_A3B, 576), (CFG, 40)):
        k_pages, v_pages = jax.eval_shape(
            lambda cfg=cfg: llama.init_kv_pages(cfg, 8, 16)
        )
        assert cfg.latent_width == values
        # held in whole tiles of 128 lanes (the compiler pads 576 to 640 in
        # HBM whatever the array says, and Mosaic cuts no tile out of that)
        row = -(-values // 128) * 128
        assert k_pages.shape == (cfg.n_layers, 8, 16, row)
        assert v_pages.size == 0
        per_token = (k_pages.size + v_pages.size) // (8 * 16)
        assert per_token == cfg.n_layers * row
    # nothing is sized from n_kv_heads x hd (64 x 32 here, as published)
    assert KANANA_2_30B_A3B.kv_row_shape == (640,)
    assert TINY_QWEN3_MOE.kv_row_shape == (2, 24)


@pytest.mark.parametrize("what, name", [
    (dict(kv_quant_hbm="int8"), "kv_quant_hbm"),
    (dict(block_manager=BlockManagerConfig(
        total_pages=16, page_size=PS, host_pages=8)), "host_pages"),
    (dict(remote_tier=True), "remote_tier"),
    (dict(sp=2), "sp > 1"),
    (dict(tp=2), "tp > 1"),
    (dict(spec_decode="prompt_lookup"), "spec_decode"),
    (dict(model=dataclasses.replace(CFG, block_length=4)), "block_length"),
])
def test_engine_refuses_by_name(what, name):
    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, prefill_bucket=16)
    config = dataclasses.replace(config, **what)
    with pytest.raises(ValueError, match="kv_lora_rank.*" + name):
        Engine(config)


def test_the_engine_serves_group_limited_routing(params):
    """What the engine refused until PR 47: a latent model whose router
    chooses within the best groups is built and generates."""
    from llm_d_kv_cache_manager_tpu.server import SamplingParams

    cfg = dataclasses.replace(CFG, n_group=2, topk_group=1)
    engine = Engine(EngineConfig(
        model=cfg, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, prefill_bucket=16), params=params)
    seq = engine.add_request([3, 1, 4, 1, 5, 9, 2, 6], SamplingParams(max_new_tokens=3))
    plain = Engine(EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, prefill_bucket=16), params=params)
    other = plain.add_request([3, 1, 4, 1, 5, 9, 2, 6], SamplingParams(max_new_tokens=3))
    for eng in (engine, plain):
        while eng.has_work:
            eng.step()
    assert len(seq.output_tokens) == len(other.output_tokens) == 3


def test_page_export_and_import_are_refused_by_name(params):
    """``TRANSFER_ENDPOINT`` serves nothing for a latent pool: the pod
    refuses it at construction, before anything is built, and the engine's
    two entry points refuse any other caller."""
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, prefill_bucket=16)
    pod = PodServerConfig(
        engine=config, transfer_endpoint="tcp://127.0.0.1:0", publish_events=False)
    with pytest.raises(ValueError, match="kv_lora_rank.*transfer_endpoint"):
        PodServer(pod)
    engine = Engine(config, params=params)
    with pytest.raises(ValueError, match="kv_lora_rank.*export_kv_blocks"):
        engine.export_kv_blocks([1, 2])
    with pytest.raises(ValueError, match="kv_lora_rank.*import_kv_blocks"):
        engine.import_kv_blocks([])
    with pytest.raises(ValueError, match="kv_lora_rank.*transfer_endpoint"):
        PodServer(pod, engine=engine)  # an injected engine's model counts too


def test_presets():
    assert _resolve_model("tiny-mla-moe") is TINY_MLA_MOE
    cfg = _resolve_model("kakaocorp/kanana-2-30b-a3b-instruct-2601")
    assert cfg is KANANA_2_30B_A3B
    # what ``chipbench/run.py``'s built-in list reads off the preset
    assert (cfg.hd, cfg.n_kv_heads, cfg.n_experts, cfg.moe_inter) == (64, 32, 128, 768)
    shapes = jax.eval_shape(
        lambda: llama.init_params(
            jax.random.PRNGKey(0), dataclasses.replace(cfg, n_layers=2))
    )
    dense, routed = shapes["layers"]
    assert dense["w_gate"].shape == (2048, 6144)
    assert routed["wq"].shape == (2048, 32 * 192)
    assert routed["wkv_a"].shape == (2048, 576)
    assert routed["wkv_b"].shape == (512, 32 * 256)
    assert routed["wo"].shape == (32 * 128, 2048)
    assert routed["ws_gate"].shape == (2048, 1536)
    assert routed["w_gate"].shape == (128, 2048, 768)


# -- the loader: a deepseek_v3 config and state dict ---------------------------
class _KananaConfig:  # the published config.json's keys
    model_type = "deepseek_v3"
    vocab_size, hidden_size, intermediate_size = 128256, 2048, 6144
    num_hidden_layers, num_attention_heads, num_key_value_heads = 48, 32, 32
    head_dim, rope_theta, rope_scaling, rms_norm_eps = 64, 1000000, None, 1e-6
    attention_bias, tie_word_embeddings, hidden_act = False, False, "silu"
    n_routed_experts, num_experts_per_tok, moe_intermediate_size = 128, 6, 768
    n_shared_experts, norm_topk_prob, first_k_dense_replace = 2, True, 1
    kv_lora_rank, q_lora_rank, qk_nope_head_dim, qk_rope_head_dim = 512, None, 128, 64
    v_head_dim, rope_interleave, routed_scaling_factor = 128, True, 2.448
    scoring_func, topk_method, n_group, topk_group = "sigmoid", "noaux_tc", 1, 1
    moe_layer_freq = 1


def test_the_loader_reads_the_published_config():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    assert config_from_hf(_KananaConfig()) == KANANA_2_30B_A3B


def test_the_loader_reads_a_low_rank_query_path():
    """What the loader refused until PR 41: ``q_lora_rank`` is read, and a
    state dict with ``q_a_proj`` / ``q_a_layernorm`` / ``q_b_proj`` loads to
    the tree the program runs."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import (
        config_from_hf,
        load_hf_state_dict,
    )

    hf = _KananaConfig()
    hf.q_lora_rank = 1536
    assert config_from_hf(hf) == dataclasses.replace(
        KANANA_2_30B_A3B, q_lora_rank=1536)
    cfg = dataclasses.replace(
        CFG, q_lora_rank=16, n_layers=1, first_k_dense=1)
    params = llama.init_params(jax.random.PRNGKey(2), cfg)
    (layer,) = params["layers"]
    names = {
        "attn_norm": "input_layernorm.weight",
        "mlp_norm": "post_attention_layernorm.weight",
        "wq_a": "self_attn.q_a_proj.weight",
        "q_a_norm": "self_attn.q_a_layernorm.weight",
        "wq_b": "self_attn.q_b_proj.weight",
        "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
        "kv_norm": "self_attn.kv_a_layernorm.weight",
        "wkv_b": "self_attn.kv_b_proj.weight", "wo": "self_attn.o_proj.weight",
        "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
        "w_down": "mlp.down_proj.weight",
    }
    assert set(names) == set(layer)
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["final_norm"],
          "lm_head.weight": params["lm_head"].T}
    for ours, theirs in names.items():
        w = np.asarray(layer[ours])
        sd["model.layers.0." + theirs] = w.T if w.ndim == 2 else w
    loaded = load_hf_state_dict(sd, cfg)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_the_loader_reads_the_routing_groups():
    """What the loader refused until PR 47: ``n_group`` / ``topk_group`` are
    read into the fields the router runs."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = _KananaConfig()
    hf.n_group, hf.topk_group = 8, 4
    assert config_from_hf(hf) == dataclasses.replace(
        KANANA_2_30B_A3B, n_group=8, topk_group=4)


@pytest.mark.parametrize("change, name", [
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(rope_scaling={"type": "yarn", "factor": 40}), "yarn"),
])
def test_the_loader_refuses_by_name(change, name):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = _KananaConfig()
    for key, value in change.items():
        setattr(hf, key, value)
    with pytest.raises(NotImplementedError, match=name):
        config_from_hf(hf)
