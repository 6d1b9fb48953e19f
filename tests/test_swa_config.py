"""A model with sliding layers: what the window pool does not serve is
refused by name (the engine, the pod's page moves, the model programs), the
presets, and the loader on the published ``afmoe`` config.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    TINY_QWEN3_MOE,
    TINY_SWA_MOE,
    TRINITY_LARGE_PREVIEW,
    llama,
)
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig, EngineConfig
from llm_d_kv_cache_manager_tpu.server.engine import Engine
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model

CFG = TINY_SWA_MOE
PS = 4


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(43), CFG)


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


@pytest.mark.parametrize("entry", [
    "transfer_endpoint", "transfer_endpoint-injected", "export_kv_blocks",
    "import_kv_blocks", "freeze_for_migration",
])
def test_page_moves_are_refused_by_name(params, entry):
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=32, page_size=PS),
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
    with pytest.raises(ValueError, match="sliding layers.*" + entry.split("-")[0]):
        calls[entry]()


def test_the_model_programs_refuse_what_carries_no_window(params):
    ids = jnp.zeros((1, 4), jnp.int32)
    k_pages, v_pages = llama.init_kv_pages(CFG, 4, PS)
    with pytest.raises(ValueError, match="sliding layers: the window pools"):
        llama.prefill(
            params, CFG, ids, ids, ids > -1, k_pages, v_pages, ids + 1, ids,
            jnp.zeros((1, 0), jnp.int32), jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="sliding layers: the window pools"):
        llama.decode_step(
            params, CFG, ids[0, :1], ids[0, :1], k_pages, v_pages, ids + 1,
            ids[0, :1] + 1, page_size=PS, interpret=True)


# -- presets and the loader ------------------------------------------------------
def test_presets():
    big = TRINITY_LARGE_PREVIEW
    assert _resolve_model("arcee-ai/Trinity-Large-Preview") is big
    assert _resolve_model("tiny-swa-moe") is CFG
    kinds = big.layer_types
    assert len(kinds) == 60 and kinds.count("sliding_attention") == 45
    assert all(k == "full_attention" for k in kinds[3::4])
    assert big.n_window_layers == 45 and big.n_attn_layers == 15
    cut = dataclasses.replace(
        big, n_layers=5, first_k_dense=1, vocab_size=25024, expert_first=0,
        expert_count=32)
    assert cut.layer_types_published == list(kinds)  # the published list, whole
    assert (cut.n_window_layers, cut.n_attn_layers, cut.experts_held) == (4, 1, 32)
    assert hash(cut) != hash(big)
    assert CFG.layer_types[:5] == (
        "sliding_attention",) * 3 + ("full_attention", "sliding_attention")
    assert not TINY_QWEN3_MOE.n_window_layers and not TINY_QWEN3_MOE.sliding_window
    # the cut's tree: the leaves a layer's kind and place give it
    tree = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), CFG))["layers"]
    assert ["window" in layer for layer in tree] == [True] * 3 + [False, True]
    assert ["router" in layer for layer in tree] == [False] + [True] * 4
    assert all({"wg", "attn_post_norm", "mlp_post_norm"} <= set(layer)
               for layer in tree)
    assert tree[1]["w_gate"].shape == (4, 64, 48)  # the held experts
    assert tree[1]["router"].shape == (64, 8)  # every expert scored


class _TrinityConfig:  # the published config.json's keys (the catalog's row)
    model_type = "afmoe"
    global_attn_every_n_layers, head_dim, hidden_act = 4, 128, "silu"
    hidden_size, intermediate_size = 3072, 12288
    layer_types = (["sliding_attention"] * 3 + ["full_attention"]) * 15
    load_balance_coeff, max_position_embeddings = 5e-05, 262144
    moe_intermediate_size, mup_enabled, n_group = 3072, True, 1
    num_attention_heads, num_dense_layers, num_expert_groups = 48, 6, 1
    num_experts, num_experts_per_tok, num_hidden_layers = 256, 4, 60
    num_key_value_heads, num_limited_groups, num_shared_experts = 8, 1, 1
    rms_norm_eps, rope_scaling, rope_theta = 1e-05, None, 10000
    route_norm, route_scale, score_func = True, 2.448, "sigmoid"
    sliding_window, tie_word_embeddings, topk_group = 4096, False, 1
    use_grouped_mm, vocab_size = True, 200192


def test_the_loader_reads_the_published_config():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    assert config_from_hf(_TrinityConfig()) == TRINITY_LARGE_PREVIEW


def test_the_loader_reads_the_routing_groups():
    """What the loader refused until PR 47: ``n_group`` / ``topk_group`` are
    read into the fields the router runs."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = _TrinityConfig()
    hf.n_group, hf.topk_group = 4, 2
    assert config_from_hf(hf) == dataclasses.replace(
        TRINITY_LARGE_PREVIEW, n_group=4, topk_group=2)


@pytest.mark.parametrize("change, name", [
    (dict(layer_types=["conv", "sliding_attention"] * 30), "layer_types"),
    (dict(score_func="softmax"), "score_func"),
    (dict(mup_enabled=False), "mup_enabled"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "yarn"),
])
def test_the_loader_refuses_by_name(change, name):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = _TrinityConfig()
    for key, value in change.items():
        setattr(hf, key, value)
    with pytest.raises(NotImplementedError, match=name):
        config_from_hf(hf)
