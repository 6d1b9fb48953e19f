"""Generation by diffusion over blocks: what it does not serve refused by
name, the preset and the loader, the denoising parameters through
``POST /v1/completions``, and the migration frame that carries them.
"""

import asyncio
import dataclasses

import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_SDAR_MOE
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig, EngineConfig
from llm_d_kv_cache_manager_tpu.server.engine import Engine
from llm_d_kv_cache_manager_tpu.server.serve import (
    PodServer,
    PodServerConfig,
    _resolve_model,
)
from served_path import prompt_of

CFG = TINY_SDAR_MOE
B = CFG.block_length
PS = 4


# -- refusals, the loader, the API ---------------------------------------------
@pytest.mark.parametrize("what", [
    dict(sp=2), dict(kv_quant_hbm="int8"), dict(spec_decode="prompt_lookup"),
    dict(decode_steps_per_iter=2), dict(decode_steps_per_iter=4),
    dict(block_manager=BlockManagerConfig(total_pages=16, page_size=6)),
])
def test_engine_refuses_by_name(what):
    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, **({"prefill_bucket": 16} if "sp" in what else {}))
    config = dataclasses.replace(config, **what)
    with pytest.raises(ValueError, match="block_length"):
        Engine(config)


def test_presets_and_loader():
    sdar = _resolve_model("JetLM/SDAR-30B-A3B-Chat")
    qwen = _resolve_model("Qwen/Qwen3-30B-A3B")
    assert (sdar.block_length, sdar.mask_token_id) == (4, 151669)
    assert dataclasses.replace(sdar, block_length=0, mask_token_id=0) == qwen
    assert _resolve_model("tiny-sdar-moe") is TINY_SDAR_MOE

    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    class SDARMoeConfig:  # the published config.json's keys
        model_type = "sdar_moe"
        vocab_size, hidden_size, intermediate_size = 151936, 2048, 6144
        num_hidden_layers, num_attention_heads, num_key_value_heads = 48, 32, 4
        head_dim, rope_theta, rope_scaling, rms_norm_eps = 128, 1000000, None, 1e-6
        attention_bias, tie_word_embeddings, hidden_act = False, False, "silu"
        num_experts, num_experts_per_tok, moe_intermediate_size = 128, 8, 768
        norm_topk_prob, decoder_sparse_step, mlp_only_layers = True, 1, []

    assert config_from_hf(SDARMoeConfig()) == sdar
    SDARMoeConfig.model_type = "qwen3_moe"
    assert config_from_hf(SDARMoeConfig()).block_length == 0


@pytest.mark.parametrize("model,body,status", [
    ("tiny-sdar-moe", {"denoising_steps": 2, "confidence_threshold": 0.5,
                       "remasking_strategy": "low_confidence_dynamic"}, 200),
    ("tiny-sdar-moe", {"remasking_strategy": "sequential"}, 400),
    ("tiny-sdar-moe", {"denoising_steps": 0}, 400),
    ("tiny-sdar-moe", {"denoising_steps": B + 1}, 400),
    ("tiny-sdar-moe", {"denoising_steps": "many"}, 400),
    ("tiny-qwen3-moe", {"denoising_steps": 2}, 400),
    ("tiny-qwen3-moe", {"remasking_strategy": "low_confidence_dynamic"}, 400),
    ("tiny-qwen3-moe", {}, 200),
])
def test_completions_api(model, body, status):
    server = PodServer(PodServerConfig(
        model_name=model, pod_identifier="pod-bd", publish_events=False,
        engine=EngineConfig(
            model=_resolve_model(model),
            block_manager=BlockManagerConfig(total_pages=64, page_size=PS),
            max_model_len=64, decode_batch_size=4, prefill_bucket=8,
            interpret=True),
    ))
    server.start()

    async def scenario():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            resp = await client.post("/v1/completions", json={
                "prompt_token_ids": prompt_of(1, 10), "max_tokens": 6, **body})
            return resp.status, await resp.json()
        finally:
            await client.close()

    try:
        got, data = asyncio.run(scenario())
    finally:
        server.shutdown()
    assert got == status, data
    if status == 200:
        assert data["usage"]["completion_tokens"] == 6
        assert len(data["choices"][0]["token_ids"]) == 6 and data["ttft_s"] >= 0
    else:
        assert "error" in data


@pytest.mark.parametrize("steps,threshold", [(None, None), (2, None), (3, 0.5)])
def test_migration_frame_carries_the_denoising_parameters(steps, threshold):
    from llm_d_kv_cache_manager_tpu.kvcache.transfer import protocol

    sent = protocol.MigrationPayload(
        request_id="r", token_ids=[1, 2, 3], user_prompt_len=2, num_generated=1,
        max_new_tokens=9, temperature=0.0, top_k=0, top_p=1.0,
        stop_token_ids=(7,), deadline_remaining_s=None,
        denoising_steps=steps, confidence_threshold=threshold)
    frame = protocol.encode_migrate("m", "pod-a", sent)
    _, _, got = protocol.decode_migrate(frame)
    assert got == sent
    if (steps, threshold) == (None, None):  # the frame it always was
        bare = dataclasses.replace(sent)
        assert frame == protocol.encode_migrate("m", "pod-a", bare)
        assert len(protocol._unpack(frame)) == 10
