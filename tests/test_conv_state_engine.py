"""Convolution state in the pages, through ``Engine`` and ``PodServer``:
every way the state reaches a token there (a prefix-cache hit, a page
boundary inside a fused burst or under a dispatch ahead, chunked prefill, a
re-prefill after preemption, a page id evicted and reused, ``BlockStored``
after the state) gives the stateless reference's pick at every step
(``chipbench/references/conv_moe.forward``, float32).
"""

import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import BlockStored
from llm_d_kv_cache_manager_tpu.models import TINY_QWEN3_MOE
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    SamplingParams,
    SchedulerConfig,
)
from served_path import prompt_of

CFG = served_path.ONE_OF_EACH_LFM2  # depth is not these cases' point
PS = 4
REF = chip_reference.load("conv_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 34)


#: the pool that runs out (19 pages of 4 tokens to give), on two lanes: the
#: cases that need one (a preemption, an eviction) share its programs and get
#: what they need from the traffic they send
TIGHT = dict(total_pages=20, lanes=2)


def make_engine(params, cfg=CFG, total_pages=96, **engine):
    return served_path.make_engine(
        cfg, params, BlockManagerConfig(total_pages=total_pages, page_size=PS),
        **engine)


def run_all(engine, prompts, n=10):
    return served_path.run_all(engine, prompts, n)


def picks(params, ask, generated):
    return served_path.picks(REF, params, CFG, ask, generated)


def test_a_shared_prefix_is_a_hit_whose_state_comes_from_the_page(params):
    """Two requests sharing a prefix, and a third after both were freed: a
    hit takes the convolution state from the cached page's slot (no restore
    step exists) and generates what a cold run and the reference do."""
    shared = prompt_of(21, 24)
    asks = [shared + prompt_of(22 + i, n) for i, n in enumerate((7, 10, 5))]
    engine = make_engine(params)
    engine.obs_step_timing = True
    first = run_all(engine, asks[:1])[0]
    second = run_all(engine, asks[1:2])[0]
    assert engine.scheduler.running == [] and not engine.has_work
    third = run_all(engine, asks[2:])[0]  # after the others were freed
    assert [s.num_cached_prompt for s in (first, second, third)] == [0, 24, 24]
    for seq, ask in zip((first, second, third), asks):
        alone = run_all(make_engine(params), [ask])[0]
        assert seq.generated_tokens == alone.generated_tokens
        assert seq.generated_tokens == picks(params, ask, seq.generated_tokens)
    # a whole prompt in the cache: its last page is computed again, in a
    # page of its own, from the state of the cached page before it
    again = run_all(engine, [shared])[0]
    assert again.num_cached_prompt == 20
    assert again.generated_tokens == picks(params, shared, again.generated_tokens)
    # the counter the roofline reads: the real lanes' context a dispatch
    assert engine.step_stats["attn_ctx_tokens"] == sum(
        len(ask) + 1 + i for ask in asks + [shared] for i in range(9))
    assert engine.step_stats["latent_ctx_tokens"] == 0


@pytest.mark.parametrize("k, lanes, ahead", [
    (4, 4, False), (1, 2, True), (3, 2, True),
], ids=["burst-of-4", "dispatch-ahead", "bursts-of-3-ahead"])
def test_a_page_boundary_inside_a_burst_or_under_a_dispatch_ahead(
        params, k, lanes, ahead):
    """A lane that crosses a page boundary inside a ``decode_steps`` burst,
    or while a dispatch runs ahead of the host, writes the finished page's
    snapshot on the device: 21 tokens cross five boundaries of 4-token
    pages, and a request that hits those pages afterwards reads them."""
    asks = [prompt_of(60 + i, 9 + 2 * i) for i in range(2)]
    engine = make_engine(params, lanes=lanes, decode_steps_per_iter=k)
    engine.obs_step_timing = True
    seqs = run_all(engine, asks, n=21)
    assert (engine.step_stats["decode_chained_dispatches"] > 0) == ahead
    for seq, ask in zip(seqs, asks):
        assert len(seq.generated_tokens) == 21
        assert seq.generated_tokens == picks(params, ask, seq.generated_tokens)
    # the pages the bursts finished are registered; a later request takes
    # its state from the last of them
    grown = asks[0] + seqs[0].generated_tokens[:15]
    later = run_all(engine, [grown + prompt_of(70, 3)])[0]
    assert later.num_cached_prompt == len(grown) // PS * PS
    assert later.generated_tokens == picks(
        params, grown + prompt_of(70, 3), later.generated_tokens)


def test_chunked_prefill(params):
    """A long prompt ingested in page-aligned chunks beside running decodes:
    each chunk's first token reads the slot its predecessor left."""
    ask = prompt_of(80, 70)
    engine = make_engine(
        params, scheduler=SchedulerConfig(
            max_prefill_batch=4, chunked_prefill_tokens=16))
    short = engine.add_request(prompt_of(81, 6), SamplingParams(max_new_tokens=30))
    engine.step()
    long = engine.add_request(ask, SamplingParams(max_new_tokens=8))
    while engine.has_work:
        engine.step()
    assert engine.prefill_stats["dispatches"] >= 1 + 70 // 16
    assert long.generated_tokens == picks(params, ask, long.generated_tokens)
    assert short.generated_tokens == picks(
        params, prompt_of(81, 6), short.generated_tokens)


def test_preemption_and_resume(params):
    """A pool too small for both lanes' growth: one is preempted, folded and
    prefilled again from its own registered pages (their slots hold its
    state) and goes on as if nothing had happened."""
    asks = [prompt_of(90 + i, 14) for i in range(2)]
    engine = make_engine(params, **TIGHT)
    preempted = []
    on_preempted = engine.scheduler.on_preempted
    engine.scheduler.on_preempted = lambda seq: (
        preempted.append(seq), on_preempted(seq))[1]
    seqs = run_all(engine, asks, n=30)  # 2 x 11 pages, of 19
    assert preempted
    for seq, ask in zip(seqs, asks):
        assert len(seq.all_tokens) - len(ask) == 30
        generated = seq.all_tokens[len(ask):]
        assert generated == picks(params, ask, generated)


def test_a_page_evicted_and_refilled(params):
    """A page id that is evicted and reused takes its state with it: the
    first request's pages are evicted by others, its prompt is computed
    again (no hit) into whatever pages are free, and a third request hits
    the refilled pages."""
    ask = prompt_of(100, 17)
    engine = make_engine(params, **TIGHT)
    first = run_all(engine, [ask], n=5)[0]
    for i in range(4):  # 4 x 8 pages pass through an 19-page pool
        run_all(engine, [prompt_of(110 + i, 29)], n=3)
    again = run_all(engine, [ask], n=5)[0]
    assert again.num_cached_prompt == 0
    hit = run_all(engine, [ask + prompt_of(120, 4)], n=5)[0]
    assert hit.num_cached_prompt == 16
    assert first.generated_tokens == again.generated_tokens
    assert first.generated_tokens == picks(params, ask, first.generated_tokens)
    assert hit.generated_tokens == picks(
        params, ask + prompt_of(120, 4), hit.generated_tokens)


def test_block_stored_never_precedes_the_pages_state(params):
    """``register_full_pages`` runs after the program that wrote a page's
    last token, which is the program that wrote its slot: when a
    ``BlockStored`` is emitted, every convolution layer's slot of that page
    already holds a state, and it is never written again."""
    seen = {}

    def on_events(events):
        bm = engine.block_manager
        for e in events:
            if isinstance(e, BlockStored):
                for h in e.block_hashes:
                    page = bm._cached[h]
                    seen[h] = (page, np.asarray(engine.state_pages[:, page]))

    engine = make_engine(params, on_events=on_events, decode_steps_per_iter=3)
    ask = prompt_of(130, 18)
    run_all(engine, [ask], n=14)
    assert len(seen) == (18 + 14 - 1) // PS
    for page, at_event in seen.values():
        assert np.abs(at_event).max(axis=-1).all()  # every layer's slot
        np.testing.assert_array_equal(
            at_event, np.asarray(engine.state_pages[:, page]))
    # a hit on those pages, and decode past them, rewrites none of them
    run_all(engine, [ask + prompt_of(131, 6)], n=6)
    for page, at_event in seen.values():
        np.testing.assert_array_equal(
            at_event, np.asarray(engine.state_pages[:, page]))


def test_bytes_per_token_from_shapes(params):
    engine = make_engine(params)
    kv = 2 * CFG.n_attn_layers * CFG.n_kv_heads * CFG.hd * 4
    state = CFG.n_conv_layers * 2 * CFG.hidden_size * 4 // PS
    assert engine.kv_bytes_per_token == kv
    assert engine.state_bytes_per_token == state
    assert engine.kv_block_bytes == PS * (kv + state)
    assert engine.k_pages.shape == (CFG.n_attn_layers, 96, PS, 2, 128)
    assert engine.state_pages.shape == (
        CFG.n_conv_layers, 96, 2 * CFG.hidden_size)
    assert (CFG.n_attn_layers, CFG.n_conv_layers) == (1, 2)
    gqa = make_engine(
        served_path.params_of(TINY_QWEN3_MOE, 1), cfg=TINY_QWEN3_MOE)
    assert gqa.state_pages is None and gqa.state_bytes_per_token == 0


def test_stats_and_gauges(params):
    """``/stats`` reads both sizes, always; the gauges beside them."""
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    pod = PodServer(
        PodServerConfig(publish_events=False, obs_metrics=True),
        engine=make_engine(params))

    async def get_stats():
        client = TestClient(TestServer(pod.build_app()))
        await client.start_server()
        try:
            return await (await client.get("/stats")).json()
        finally:
            await client.close()

    stats = asyncio.run(get_stats())
    assert stats["kv_bytes_per_token"] == pod.engine.kv_bytes_per_token
    assert stats["state_bytes_per_token"] == pod.engine.state_bytes_per_token > 0
    pod.metrics.set_engine_gauges(0.0, 1, 2, 3)
    pod.engine.step_stats["attn_ctx_tokens"] = 41
    pod.metrics.sync_step_stats(pod.engine.step_stats, None)
    text = pod.metrics.exposition().decode()
    assert "kvcache_state_bytes_per_token 3.0" in text
    assert "kvcache_engine_attn_ctx_tokens_total 41.0" in text
