"""A model whose router reads the layer's input through ``Engine`` and
``PodServer``: resident prefixes shorter than the window, of one window and
of three, in one queue (a whole short context lives in both pools and nothing
is given back behind it), the reference's pick at every step
(``chipbench/references/swa_prerouted_moe.forward``, float32), and the
counters the cell's readers read.
"""

import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_SMALLTHINKER
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig, SamplingParams
from served_path import prompt_of

CFG = TINY_SMALLTHINKER
PS = 4
W = CFG.sliding_window
REF = chip_reference.load("swa_prerouted_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 54)


def make_engine(params, total_pages=96, window_pages=64, **engine):
    return served_path.make_engine(
        CFG, params,
        BlockManagerConfig(
            total_pages=total_pages, page_size=PS, window_pages=window_pages),
        max_model_len=160, **engine)


def picks(params, ask, generated):
    return served_path.picks(REF, params, CFG, ask, generated)


@pytest.fixture(scope="module")
def served(params):
    """Three prefixes made resident (half a window, one window, three), then
    a turn after each of them in ONE batch: (the pod, the fills, the turns)."""
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    prefixes = [prompt_of(90 + i, n) for i, n in enumerate((W // 2, W, 3 * W))]
    pod = PodServer(
        PodServerConfig(publish_events=False),
        engine=make_engine(params, prefill_attn="pallas"))
    pod.engine.obs_step_timing = True
    pod.start()
    try:
        fills = [pod.submit(p + prompt_of(70 + i, PS), SamplingParams(
            max_new_tokens=1)).result(timeout=300)
            for i, p in enumerate(prefixes)]
        asks = [p + prompt_of(80 + i, 5 + i) for i, p in enumerate(prefixes)]
        futures = [pod.submit(a, SamplingParams(max_new_tokens=2 * W + 3))
                   for a in asks]
        turns = [f.result(timeout=300) for f in futures]
    finally:
        pod.shutdown()
    return pod, fills, list(zip(asks, turns))


def test_hits_shorter_than_equal_to_and_longer_than_the_window(params, served):
    pod, fills, turns = served
    assert [s.num_cached_prompt for s in fills] == [0, 0, 0]
    # each turn found its whole prefix in both pools
    assert [seq.num_cached_prompt for _, seq in turns] == [W // 2, W, 3 * W]
    for ask, seq in turns:
        assert seq.generated_tokens == picks(params, ask, seq.generated_tokens)
    stats = pod.engine.block_manager.window.stats
    assert stats["window_short_hits"] == 0
    # the lanes moved two windows on: pages were given back behind them
    assert stats["window_pages_dropped"] > 0


def test_the_pools_count_layers_by_kind(served):
    engine = served[0].engine
    assert engine.k_pages.shape[0] == 1 and engine.window_pages[0].shape[0] == 3
    row = 2 * CFG.n_kv_heads * CFG.hd * 4
    assert engine.kv_bytes_per_token == row
    assert engine.window_bytes_per_token == 3 * row


def test_the_tables_slots_are_counted_beside_the_contexts(served):
    """``decode_table_slots``: real lanes x table width x page, a step; the
    contexts are what of them is filled, and a sliding layer read at most a
    window of each."""
    steps = served[0].engine.step_stats
    assert 0 < steps["attn_ctx_tokens"] < steps["decode_table_slots"]
    assert steps["decode_table_slots"] % (PS * steps["decode_rows"]
                                          // steps["decode_dispatches"]) == 0
    assert 0 < steps["window_ctx_tokens"] < steps["attn_ctx_tokens"]
    assert steps["window_ctx_tokens"] <= W * steps["decode_rows"]
    assert steps["experts_touched"] > 0 and steps["decode_forwards"] > 0


def test_the_tables_slots_are_a_prometheus_counter(params):
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    pod = PodServer(
        PodServerConfig(publish_events=False, obs_metrics=True),
        engine=make_engine(params))
    pod.engine.step_stats["decode_table_slots"] = 640
    pod.engine.step_stats["attn_ctx_tokens"] = 123
    pod.metrics.sync_step_stats(pod.engine.step_stats, None)
    text = pod.metrics.exposition().decode()
    assert "kvcache_engine_decode_table_slots_total 640.0" in text
    assert "kvcache_engine_attn_ctx_tokens_total 123.0" in text
