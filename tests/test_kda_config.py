"""A model with linear-attention layers: what the state pool of slots does
not serve is refused by name (the engine, the pod's page moves, the model
programs), the presets, and the loader on the published ``bailing_hybrid``
config.
"""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    LING_3_FLASH,
    TINY_LING_HYBRID,
    TINY_MLA_MOE,
    TINY_SOLAR_HYBRID,
    llama,
)
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    EngineConfig,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.engine import Engine
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model

CFG = TINY_LING_HYBRID
PS = 4
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def pages(**kw):
    kw.setdefault("state_snapshot_tokens", 8)
    return BlockManagerConfig(total_pages=32, page_size=PS, **kw)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(43), CFG)


# -- what the state pool does not serve is refused by name ---------------------
@pytest.mark.parametrize("what, name", [
    (dict(block_manager=pages(host_pages=8)), "host_pages"),
    (dict(remote_tier=True), "remote_tier"),
    (dict(kv_quant_hbm="int8"), "kv_quant_hbm"),
    (dict(tp=2), "tp > 1"),
    (dict(sp=2), "sp > 1"),
    (dict(spec_decode="prompt_lookup"), "spec_decode"),
    (dict(model=dataclasses.replace(CFG, block_length=4)), "block_length"),
    (dict(model=dataclasses.replace(
        CFG, layer_types=("linear_attention", "conv") * 3,
        conv_L_cache=3)), "sliding or conv layers"),
    (dict(model=dataclasses.replace(
        CFG, expert_swiglu_limits=(0, 0, 0, 4, 0, 0))), "SwiGLU limit"),
    (dict(model=dataclasses.replace(
        CFG, shared_swiglu_limits=(0, 5, 0, 0, 0, 0))), "SwiGLU limit"),
    (dict(block_manager=pages(state_snapshot_tokens=6)),
     "state_snapshot_tokens=6"),
    (dict(block_manager=pages(state_snapshot_tokens=4),
          decode_steps_per_iter=8), "state_snapshot_tokens=4"),
    (dict(model=dataclasses.replace(CFG, kda_conv_kernel=1)), "kda_conv_kernel"),
    # (a prompt is cut where a snapshot is due by the scheduler's one mode)
    (dict(scheduler=SchedulerConfig(chunked_prefill_tokens=16)),
     "chunked_prefill_tokens"),
])
def test_engine_refuses_by_name(what, name):
    config = EngineConfig(
        model=CFG, block_manager=pages(), interpret=True, prefill_bucket=16)
    config = dataclasses.replace(config, **what)
    with pytest.raises(ValueError, match="linear_attention.*" + name):
        Engine(config)


# -- ... and beside per-head K/V pools (``TINY_SOLAR_HYBRID``) the same ---------
SOLAR = TINY_SOLAR_HYBRID


@pytest.mark.parametrize("what, name", [
    (dict(block_manager=pages(host_pages=8)), "host_pages"),
    (dict(remote_tier=True), "remote_tier"),
    (dict(kv_quant_hbm="int8"), "kv_quant_hbm"),
    (dict(tp=2), "tp > 1"),
    (dict(sp=2), "sp > 1"),
    (dict(spec_decode="prompt_lookup"), "spec_decode"),
    (dict(model=dataclasses.replace(SOLAR, block_length=4)), "block_length"),
    (dict(model=dataclasses.replace(
        SOLAR, layer_types=("linear_attention", "sliding_attention") * 4,
        sliding_window=8)), "sliding or conv layers"),
    (dict(scheduler=SchedulerConfig(chunked_prefill_tokens=16)),
     "chunked_prefill_tokens"),
    (dict(block_manager=pages(state_snapshot_tokens=6)),
     "state_snapshot_tokens=6"),
])
def test_engine_refuses_by_name_beside_kv_pools(what, name):
    """What a model with state is refused stays refused where the pool
    beside the slots holds per-head keys and values, and says so."""
    config = EngineConfig(
        model=SOLAR, block_manager=pages(), interpret=True, prefill_bucket=16)
    config = dataclasses.replace(config, **what)
    with pytest.raises(
            ValueError, match="linear_attention.*key/value pools.*" + name):
        Engine(config)


def test_an_engine_is_built_with_both_pools():
    """``kv_lora_rank == 0`` and low-rank gate projections are no refusals:
    K and V pools over the GQA layers, the state pool of slots over the
    linear ones, and ``/stats``' sizes of both."""
    engine = Engine(EngineConfig(
        model=SOLAR, block_manager=pages(state_snapshot_slots=40),
        decode_batch_size=4, scheduler=SchedulerConfig(max_prefill_batch=2),
        interpret=True, prefill_bucket=16))
    assert SOLAR.kda_lora and SOLAR.kv_lora_rank == 0
    assert (SOLAR.n_attn_layers, SOLAR.n_kda_layers) == (2, 6)
    assert engine.k_pages.shape == engine.v_pages.shape == (2, 32, PS, 1, 16)
    matrices, rows = engine.state_pages
    assert matrices.shape == (6, 4 + 2 + 40, 4, 16, 16)
    assert rows.shape == (6, 46, 9, 64)
    # 2 GQA layers x (K + V) x 1 head of 16 float32 beside a snapshot's bytes
    # over the stride
    assert engine.kv_bytes_per_token == 2 * 2 * 16 * 4
    assert engine.state_bytes_per_token == SOLAR.kda_state_bytes // 8
    assert engine.kv_block_bytes == PS * engine.kv_bytes_per_token
    stats = engine.state_pool_stats()
    assert stats["state_slots"] == 46
    assert stats["state_bytes_per_snapshot"] == SOLAR.kda_state_bytes
    with pytest.raises(ValueError, match="key/value pools.*export_kv_blocks"):
        engine.export_kv_blocks([1, 2])


def test_a_low_rank_decay_beside_the_latent_pool_is_built(params):
    """``use_kda_lora`` on the linear-and-latent model: the layer's leaves
    are the pair, and the engine takes it."""
    cfg = dataclasses.replace(CFG, kda_lora=True)
    tree = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert "kda_wf" not in tree["layers"][0]
    assert tree["layers"][0]["kda_wf_down"].shape == (64, 16)
    assert tree["layers"][0]["kda_wf_up"].shape == (16, 64)
    assert tree["layers"][0]["kda_wg"].shape == (64, 4)  # the gate stays a head's
    engine = Engine(EngineConfig(
        model=cfg, block_manager=pages(), interpret=True, prefill_bucket=16))
    assert engine.block_manager.state is not None


def test_a_clamp_on_a_layer_that_is_not_run_is_no_refusal(params):
    """The published model clamps its last layers' SwiGLU; the cut that is
    run has none, and is served."""
    cfg = dataclasses.replace(
        CFG, expert_swiglu_limits=(0,) * 6 + (4,) * 2,
        shared_swiglu_limits=(0,) * 6 + (7,) * 2)
    engine = Engine(EngineConfig(
        model=cfg, block_manager=pages(), interpret=True, prefill_bucket=16),
        params=params)
    assert engine.block_manager.state is not None


@pytest.mark.parametrize("entry", [
    "transfer_endpoint", "transfer_endpoint-injected", "export_kv_blocks",
    "import_kv_blocks", "freeze_for_migration",
])
def test_page_moves_are_refused_by_name(params, entry):
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    config = EngineConfig(
        model=CFG, block_manager=pages(), interpret=True, prefill_bucket=16)
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
    with pytest.raises(
            ValueError, match="linear_attention.*" + entry.split("-")[0]):
        calls[entry]()


def test_the_model_programs_refuse_what_carries_no_slot(params):
    ids = jnp.zeros((1, 4), jnp.int32)
    k_pages, v_pages = llama.init_kv_pages(CFG, 4, PS)
    with pytest.raises(ValueError, match="linear layers: the state pool"):
        llama.prefill(
            params, CFG, ids, ids, ids > -1, k_pages, v_pages, ids + 1, ids,
            jnp.zeros((1, 0), jnp.int32), jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="linear layers: the state pool"):
        llama.decode_step(
            params, CFG, ids[0, :1], ids[0, :1], k_pages, v_pages, ids + 1,
            ids[0, :1] + 1, page_size=PS, interpret=True)
    # the pair without the rows' slots is refused the same way
    state = llama.init_kda_state(CFG, 3)
    with pytest.raises(ValueError, match="linear layers: the state pool"):
        llama.decode_step(
            params, CFG, ids[0, :1], ids[0, :1], k_pages, v_pages, ids + 1,
            ids[0, :1] + 1, page_size=PS, interpret=True, state_pages=state)


def test_the_block_manager_refuses_a_second_pool_beside_the_slots():
    from llm_d_kv_cache_manager_tpu.server.block_manager import BlockManager

    with pytest.raises(ValueError, match="state pool of slots.*host_pages"):
        BlockManager(pages(state_slots=8, host_pages=4))
    with pytest.raises(ValueError, match="stride of whole pages"):
        BlockManager(pages(state_slots=8, state_snapshot_tokens=6))


# -- the engine's sizing ---------------------------------------------------------
def test_the_pool_has_a_slot_a_row_and_the_snapshots(params):
    engine = Engine(EngineConfig(
        model=CFG, block_manager=pages(state_snapshot_slots=40),
        decode_batch_size=4, scheduler=SchedulerConfig(max_prefill_batch=2),
        interpret=True, prefill_bucket=16), params=params)
    matrices, rows = engine.state_pages
    assert engine.block_manager.state.n_slots == 4 + 2 + 40
    assert matrices.shape == (4, 46, 4, 16, 16) and matrices.dtype == jnp.float32
    # a slot's 3 x 3 x 4 x 16 = 576 carried values as whole lanes that lie
    # together: 64 is the largest power of two up to 128 that divides them
    assert rows.shape == (4, 46, 9, 64) and CFG.kda_conv_row == 576
    # a snapshot's bytes over the stride: what prefix caching costs a token
    assert engine.state_bytes_per_token == CFG.kda_state_bytes // 8
    assert CFG.kda_state_bytes == 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    stats = engine.state_pool_stats()
    assert stats["state_slots"] == 46 and stats["state_snapshot_tokens"] == 8
    assert stats["state_bytes_per_snapshot"] == CFG.kda_state_bytes
    assert stats["state_snapshots_held"] == 0
    # every row that can hold a sequence may hold two slots at a boundary
    small = Engine(EngineConfig(
        model=CFG, block_manager=pages(), decode_batch_size=4,
        scheduler=SchedulerConfig(max_prefill_batch=2), interpret=True,
        prefill_bucket=16), params=params)
    assert small.block_manager.state.n_slots == 2 * 6 + 1
    # a model without linear layers has no such pool and reports none
    plain = Engine(EngineConfig(
        model=TINY_MLA_MOE, block_manager=pages(), interpret=True,
        prefill_bucket=16))
    assert plain.block_manager.state is None and plain.state_pool_stats() == {}
    assert plain.block_manager.config.state_slots == 0


def test_the_sizing_variables_are_read_from_the_environment(monkeypatch):
    from llm_d_kv_cache_manager_tpu.server.serve import PodServerConfig

    monkeypatch.setenv("STATE_SNAPSHOT_TOKENS", "1024")
    monkeypatch.setenv("STATE_SNAPSHOT_SLOTS", "96")
    bm = PodServerConfig.from_env().engine.block_manager
    assert (bm.state_snapshot_tokens, bm.state_snapshot_slots) == (1024, 96)
    monkeypatch.delenv("STATE_SNAPSHOT_TOKENS")
    monkeypatch.delenv("STATE_SNAPSHOT_SLOTS")
    bm = PodServerConfig.from_env().engine.block_manager
    assert (bm.state_snapshot_tokens, bm.state_snapshot_slots) == (512, 0)


# -- presets and the loader ------------------------------------------------------
def test_presets():
    big = LING_3_FLASH
    assert _resolve_model("inclusionAI/Ling-3.0-flash") is big
    assert _resolve_model("tiny-ling-hybrid") is CFG
    kinds = big.layer_types
    assert len(kinds) == 42 and kinds.count("linear_attention") == 35
    assert all(k == "full_attention" for k in kinds[5::6])
    assert (big.n_kda_layers, big.n_attn_layers, big.layer_group_size) == (35, 7, 6)
    assert big.kv_row_shape == (640,) and big.hd == 128
    cut = dataclasses.replace(
        big, n_layers=7, first_k_dense=1, vocab_size=19648, expert_first=0,
        expert_count=64)
    assert (cut.n_kda_layers, cut.n_attn_layers, cut.experts_held) == (6, 1, 64)
    assert cut.layer_group_size == 6  # the published pattern, whatever is run
    # a slot of the cut: 6 x (2 MiB of matrices + 72 KiB of carried rows)
    assert cut.kda_state_bytes == 6 * (2 * 2**20 + 72 * 2**10)
    assert cut.kda_state_bytes // 512 == 25440  # 24.8 KiB a token
    # the carried rows of a slot: 18 whole bf16 tiles of (16, 128), no padding
    assert cut.kda_conv_tile == (288, 128) and cut.kda_conv_row == 288 * 128
    assert hash(cut) != hash(big)
    assert not TINY_MLA_MOE.n_kda_layers and TINY_MLA_MOE.layer_group_size is None
    assert llama.init_kda_state(TINY_MLA_MOE, 4) is None
    # the tiny tree: the leaves a layer's kind and place give it
    tree = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), CFG))["layers"]
    assert ["kda_qkv" in la for la in tree] == [True, True, False] * 2
    assert ["wkv_a" in la for la in tree] == [False, False, True] * 2
    assert ["router" in la for la in tree] == [False] + [True] * 5
    assert tree[0]["kda_qkv"].shape == (64, 3 * 4 * 16)
    assert tree[0]["kda_conv_w"].shape == (4, 3 * 4 * 16)
    assert tree[0]["kda_A_log"].shape == (4,) and tree[0]["kda_wg"].shape == (64, 4)
    assert tree[1]["router"].shape == (64, 8)


def _catalog_row():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Ling-3.0-flash":
                return row
    pytest.skip("the catalog has no such row")


def test_the_loader_reads_the_published_config():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = types.SimpleNamespace(**_catalog_row()["config"])
    assert config_from_hf(hf) == LING_3_FLASH


def test_the_configuration_file_is_the_catalogs_row_cut():
    """Every key of the catalog's ``config`` stands in the benchmark's file
    under the same value, but the four it names as reduced."""
    with open("chipbench/configs/ling-3.0-flash.json") as f:
        ours = json.load(f)
    row = _catalog_row()
    assert ours["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if ours.get(k) != v}
    assert differ == {"num_hidden_layers", "first_k_dense_replace", "vocab_size"}
    assert set(ours["reduced"]) == differ | {"num_experts"}
    assert ours["chipbench"]["replace"]["expert_count"] == 64


@pytest.mark.parametrize("change, name", [
    (dict(score_function="softmax"), "score_function"),
    (dict(num_kv_heads_for_linear_attn=8), "num_kv_heads_for_linear_attn"),
    (dict(group_norm_size=4), "group_norm_size"),
    (dict(q_lora_rank=1536), "q_lora_rank"),
    (dict(use_nGPT=True), "use_nGPT"),
    (dict(linear_silu=False), "linear_silu"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "yarn"),
])
def test_the_loader_refuses_by_name(change, name):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = types.SimpleNamespace(**{**_catalog_row()["config"], **change})
    with pytest.raises(NotImplementedError, match=name):
        config_from_hf(hf)


def test_the_loader_reads_use_kda_lora_as_a_low_rank_decay():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = types.SimpleNamespace(
        **{**_catalog_row()["config"], "use_kda_lora": True})
    assert config_from_hf(hf) == dataclasses.replace(LING_3_FLASH, kda_lora=True)


def _solar_row():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Solar-Open2-250B":
                return row
    pytest.skip("the catalog has no such row")


@pytest.mark.parametrize("change, name", [
    (dict(gqa_layers=[0, 5, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44]), "gqa_layers"),
    (dict(gqa_layers=[3, 7, 11, 15, 19, 23, 27, 31, 35, 39, 43, 47]), "gqa_layers"),
    (dict(gqa_interval=5), "gqa_layers"),
    (dict(use_rope=True), "use_rope"),
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(kda_allow_neg_eigval=False), "kda_allow_neg_eigval"),
    (dict(use_gqa_gate=False), "use_gqa_gate"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 128,
                              "num_heads": 64, "num_kv_heads": 8}), "num_kv_heads"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "yarn"),
])
def test_the_solar_open2_mapping_refuses_by_name(change, name):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = types.SimpleNamespace(**{**_solar_row()["config"], **change})
    with pytest.raises(NotImplementedError, match=name):
        config_from_hf(hf)


def test_the_loader_reads_the_layer_kinds_from_the_group_size():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = types.SimpleNamespace(**{
        **_catalog_row()["config"], "layer_group_size": 4,
        "num_hidden_layers": 8})
    cfg = config_from_hf(hf)
    assert cfg.layer_types == (("linear_attention",) * 3 + ("full_attention",)) * 2
    assert cfg.layer_group_size == 4
