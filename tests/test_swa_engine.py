"""A model with sliding layers through ``Engine`` and ``PodServer``: a
prompt of several windows and hits on it in both pools, chunked prefill,
``/stats`` and the gauges: the reference's pick at every step
(``chipbench/references/swa_moe.forward``, float32). A page given back while
lanes decode, and preemption, are in ``tests/test_swa_engine_decode.py``.
"""

import dataclasses

import jax
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_QWEN3_MOE, llama
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.engine import Engine
from served_path import prompt_of

CFG = served_path.ONE_OF_EACH_SWA  # depth is not these cases' point
PS = 4
W = CFG.sliding_window
REF = chip_reference.load("swa_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 43)


def make_engine(params, cfg=CFG, total_pages=96, window_pages=48, **engine):
    return served_path.make_engine(
        cfg, params,
        BlockManagerConfig(
            total_pages=total_pages, page_size=PS, window_pages=window_pages),
        max_model_len=160, **engine)


def run_all(engine, prompts, n=10):
    return served_path.run_all(engine, prompts, n)


def picks(params, ask, generated):
    return served_path.picks(REF, params, CFG, ask, generated)


@pytest.mark.parametrize("prefill_attn", ["xla", "pallas"])
def test_the_served_path_on_a_prompt_of_several_windows(params, prefill_attn):
    """``PodServer.submit`` -> ``Engine.step``: a document of four windows,
    then a second request that hits it in both pools, then one whose hit
    ends mid-document: the reference's pick at every step."""
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    doc = prompt_of(80, 4 * W)
    asks = [doc + prompt_of(81, 5), doc + prompt_of(82, 9),
            doc[: 3 * W] + prompt_of(83, 6)]
    pod = PodServer(
        PodServerConfig(publish_events=False),
        engine=make_engine(params, prefill_attn=prefill_attn))
    pod.engine.obs_step_timing = True
    pod.start()
    try:
        seqs = [pod.submit(ask, SamplingParams(max_new_tokens=11)).result(
            timeout=300) for ask in asks]
    finally:
        pod.shutdown()
    assert [s.num_cached_prompt for s in seqs] == [0, 4 * W, 3 * W]
    for seq, ask in zip(seqs, asks):
        assert seq.generated_tokens == picks(params, ask, seq.generated_tokens)
    engine = pod.engine
    stats = engine.block_manager.window.stats
    assert stats["window_short_hits"] == 0 and stats["window_pages_dropped"] > 0
    # the full layer in the context pool, the sliding one in the window pool
    assert engine.k_pages.shape[0] == 1 and engine.window_pages[0].shape[0] == 1
    row = 2 * CFG.n_kv_heads * CFG.hd * 4
    assert engine.kv_bytes_per_token == row
    assert engine.window_bytes_per_token == row
    assert engine.kv_block_bytes == PS * row
    # a sliding layer read at most a window of each context
    steps = engine.step_stats
    assert 0 < steps["window_ctx_tokens"] < steps["attn_ctx_tokens"]
    assert steps["window_ctx_tokens"] == W * steps["decode_rows"]


def test_chunked_prefill(params):
    ask = prompt_of(100, 70)
    engine = make_engine(
        params, scheduler=SchedulerConfig(
            max_prefill_batch=4, chunked_prefill_tokens=16))
    short = engine.add_request(prompt_of(101, 6), SamplingParams(max_new_tokens=30))
    engine.step()
    long = engine.add_request(ask, SamplingParams(max_new_tokens=8))
    while engine.has_work:
        engine.step()
    assert engine.prefill_stats["dispatches"] >= 1 + 70 // 16
    assert long.generated_tokens == picks(params, ask, long.generated_tokens)
    assert short.generated_tokens == picks(
        params, prompt_of(101, 6), short.generated_tokens)
    # the long prompt's chunks gave back what lay a window behind each
    assert engine.block_manager.window.stats["window_pages_dropped"] >= 70 // PS - 4


def test_stats_and_gauges(params):
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    pod = PodServer(
        PodServerConfig(publish_events=False, obs_metrics=True),
        engine=make_engine(params))
    run_all(pod.engine, [prompt_of(130, 30)], n=12)
    # a hit on it: the pages of its run are kept once the lane has moved on
    run_all(pod.engine, [prompt_of(130, 30) + prompt_of(131, 5)], n=12)

    async def get_stats():
        client = TestClient(TestServer(pod.build_app()))
        await client.start_server()
        try:
            return await (await client.get("/stats")).json()
        finally:
            await client.close()

    stats = asyncio.run(get_stats())
    window = pod.engine.block_manager.window
    assert stats["window_bytes_per_token"] == pod.engine.window_bytes_per_token > 0
    assert stats["window_pages"] == 48
    assert stats["window_pages_held"] == window.num_held > 0
    assert stats["window_pages_dropped"] == window.stats["window_pages_dropped"] > 0
    assert stats["window_short_hits"] == 0 and stats["window_pages_evicted"] == 0
    pod.metrics.set_engine_gauges(0.0, 1, 2, 3, 5, window)
    pod.engine.step_stats["window_ctx_tokens"] = 41
    pod.engine.step_stats["ctx_pages"] = 50
    pod.engine.step_stats["ctx_run_pages"] = 32
    pod.engine.step_stats["full_ctx_pages"] = 70
    pod.engine.step_stats["full_ctx_run_pages"] = 48
    pod.metrics.sync_step_stats(pod.engine.step_stats, None)
    text = pod.metrics.exposition().decode()
    assert 'kvcache_engine_ctx_pages_total{kind="all"} 50.0' in text
    assert 'kvcache_engine_ctx_pages_total{kind="run"} 32.0' in text
    assert 'kvcache_engine_ctx_pages_total{kind="full"} 70.0' in text
    assert 'kvcache_engine_ctx_pages_total{kind="full_run"} 48.0' in text
    assert "kvcache_window_bytes_per_token 5.0" in text  # (set_engine_gauges' 5)
    assert f"kvcache_window_pages_held {float(window.num_held)}" in text
    assert 'kvcache_window_pages_total{event="pages_dropped"}' in text
    assert "kvcache_engine_window_ctx_tokens_total 41.0" in text
    # a model without sliding layers: the keys are there and read nothing
    plain = PodServer(
        PodServerConfig(publish_events=False),
        engine=make_engine(
            llama.init_params(jax.random.PRNGKey(1), TINY_QWEN3_MOE),
            cfg=TINY_QWEN3_MOE))
    assert plain.engine.window_pages is None
    assert plain.engine.block_manager.window is None
    assert plain.engine.block_manager.config.window_pages == 0


def test_the_window_pool_follows_total_pages_unless_stated(params, monkeypatch):
    from llm_d_kv_cache_manager_tpu.server.serve import PodServerConfig

    monkeypatch.setenv("TOTAL_PAGES", "40")
    monkeypatch.delenv("WINDOW_PAGES", raising=False)
    unset = PodServerConfig.from_env().engine
    assert unset.block_manager.window_pages == 0
    engine = Engine(dataclasses.replace(
        unset, model=CFG, interpret=True, prefill_bucket=16,
        block_manager=dataclasses.replace(unset.block_manager, page_size=PS)),
        params=params)
    assert engine.block_manager.config.window_pages == 40
    assert engine.window_pages[0].shape[1] == 40
    monkeypatch.setenv("WINDOW_PAGES", "24")
    assert PodServerConfig.from_env().engine.block_manager.window_pages == 24
