"""The double layer and the held range of experts: what they do not serve
refused by name (the engine, the pod's page moves, the dispatches), the
presets, and the loader on the published ``longcat_flash`` config."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    LONGCAT_FLASH_OMNI,
    TINY_QWEN3_MOE,
    TINY_SCMOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig, EngineConfig
from llm_d_kv_cache_manager_tpu.server.engine import Engine
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model

CFG = TINY_SCMOE
#: every routed expert held: the uncut layer
UNCUT = dataclasses.replace(CFG, expert_first=0, expert_count=None)
PS = 4


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(7), CFG)


# -- what is refused, by name --------------------------------------------------
def _engine_config(**what):
    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, prefill_bucket=16)
    return dataclasses.replace(config, **what)


@pytest.mark.parametrize("what, name", [
    (dict(kv_quant_hbm="int8"), "kv_lora_rank.*kv_quant_hbm"),
    (dict(block_manager=BlockManagerConfig(
        total_pages=16, page_size=PS, host_pages=8)), "kv_lora_rank.*host_pages"),
    (dict(remote_tier=True), "kv_lora_rank.*remote_tier"),
    (dict(sp=2), "kv_lora_rank.*sp > 1"),
    (dict(tp=2), "kv_lora_rank.*tp > 1"),
    (dict(spec_decode="prompt_lookup"), "kv_lora_rank.*spec_decode"),
    (dict(model=dataclasses.replace(CFG, block_length=4)),
     "kv_lora_rank.*block_length"),
    (dict(model=dataclasses.replace(CFG, moe_dispatch="dense")),
     "experts 4..7 held.*moe_dispatch"),
    (dict(model=dataclasses.replace(CFG, expert_first=14)),
     "experts 14..17 held.*past"),
    (dict(model=dataclasses.replace(
        TINY_QWEN3_MOE, expert_count=2), tp=2), "experts 0..1 held.*tp > 1"),
])
def test_engine_refuses_by_name(what, name):
    with pytest.raises(ValueError, match=name):
        Engine(_engine_config(**what))


def test_page_export_and_import_are_refused_by_name(params):
    engine = Engine(_engine_config(), params=params)
    with pytest.raises(ValueError, match="kv_lora_rank.*export_kv_blocks"):
        engine.export_kv_blocks([1, 2])
    with pytest.raises(ValueError, match="kv_lora_rank.*import_kv_blocks"):
        engine.import_kv_blocks([])


def test_the_dispatches_refuse_what_they_do_not_run(params):
    layer = params["layers"][0]["moe"]
    x = jnp.zeros((1, 3, CFG.hidden_size), jnp.float32)
    dense = dataclasses.replace(CFG, moe_dispatch="dense")
    with pytest.raises(ValueError, match="dense.*held range"):
        llama._moe_mlp(layer, dense, x, interpret=True)
    with pytest.raises(ValueError, match="holds 4 experts.*told 16"):
        llama._moe_mlp(layer, UNCUT, x, interpret=True)


def test_presets():
    assert _resolve_model("tiny-scmoe") is TINY_SCMOE
    cfg = _resolve_model("meituan-longcat/LongCat-Flash-Omni")
    assert cfg is LONGCAT_FLASH_OMNI
    assert (cfg.router_outputs, cfg.experts_held, cfg.n_attn_layers) == (768, 512, 56)
    cut = dataclasses.replace(
        cfg, n_layers=1, vocab_size=16384, expert_first=0, expert_count=16)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cut))
    (layer,) = shapes["layers"]
    for half in (layer, layer["second"]):
        assert half["wq_a"].shape == (6144, 1536)
        assert half["wq_b"].shape == (1536, 64 * 192)
        assert half["wkv_a"].shape == (6144, 576)
        assert half["wkv_b"].shape == (512, 64 * 256)
        assert half["wo"].shape == (64 * 128, 6144)
        assert half["w_gate"].shape == (6144, 12288)
    assert layer["moe"]["router"].shape == (6144, 768)
    assert layer["moe"]["router_bias"].shape == (768,)
    assert layer["moe"]["w_gate"].shape == (16, 6144, 2048)
    assert shapes["embed"].shape == (16384, 6144)
    k_pages, v_pages = jax.eval_shape(lambda: llama.init_kv_pages(cut, 8, 16))
    assert k_pages.shape == (2, 8, 16, 640) and v_pages.size == 0


# -- the loader: a longcat_flash config and a synthetic state dict -------------
class _LongcatConfig:  # the catalog row's keys (the language model's config)
    model_type = "longcat_flash"
    attention_bias, vocab_size, hidden_size = False, 131072, 6144
    ffn_hidden_size, expert_ffn_hidden_size, num_layers = 12288, 2048, 28
    num_attention_heads, kv_lora_rank, q_lora_rank = 64, 512, 1536
    qk_rope_head_dim, v_head_dim, qk_nope_head_dim = 64, 128, 128
    mla_scale_q_lora, mla_scale_kv_lora, routed_scaling_factor = True, True, 6
    n_routed_experts, max_position_embeddings, rms_norm_eps = 512, 131072, 1e-5
    rope_theta, attention_method, zero_expert_num = 10000000, "MLA", 256
    zero_expert_type, moe_topk = "identity", 12


def test_the_loader_reads_the_published_config():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    assert config_from_hf(_LongcatConfig()) == LONGCAT_FLASH_OMNI


@pytest.mark.parametrize("change, name", [
    (dict(zero_expert_type="copy"), "zero_expert_type"),
    (dict(attention_method="GQA"), "attention_method"),
    (dict(attention_bias=True), "attention_bias"),
])
def test_the_loader_refuses_by_name(change, name):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = _LongcatConfig()
    for key, value in change.items():
        setattr(hf, key, value)
    with pytest.raises(NotImplementedError, match=name):
        config_from_hf(hf)
