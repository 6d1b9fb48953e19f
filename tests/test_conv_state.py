"""Gated short-convolution layers whose state rides in the pages beside the
keys and values of the layers that attend (``LlamaConfig.layer_types``;
LFM2-8B-A1B), on the served path, against the plain reference, at
``TINY_LFM2_MOE`` in float32.

The reference side is ``chipbench/references/conv_moe.forward`` (float32, the
whole sequence at once, the convolution as shifted products, nothing of the
program's model code, no state). The program keeps, a page, the state after
the last token written in it (``llama.init_state_pages``): these tests hold
every way that state reaches a token (a chunk's own tokens, a finished
page's slot, a slot a chunk left inside a page, a prefix-cache hit, a page
boundary inside a fused burst or under a dispatch ahead, a re-prefill after
preemption, a page id evicted and reused) to the stateless reference.
"""

import dataclasses
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference as chip_reference  # noqa: E402
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import BlockStored  # noqa: E402
from llm_d_kv_cache_manager_tpu.models import (  # noqa: E402
    LFM2_8B_A1B,
    TINY_LFM2_MOE,
    TINY_QWEN3_MOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.server import (  # noqa: E402
    BlockManagerConfig,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.engine import Engine  # noqa: E402
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model  # noqa: E402

CFG = TINY_LFM2_MOE
PS = 4
TOL = chip_reference.TOL_F32
REF = chip_reference.load("conv_moe")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(34), CFG)


def prompt_of(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(1, 200, n).tolist()


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return np.asarray(REF.forward(params, cfg, list(tokens))[0], np.float32)


def rel_err(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


def served(params, rows, steps, attn_impl, cfg=CFG):
    """``rows``: [(prompt, tokens resident before the batched call)]: each
    row's first ``resident`` tokens are prefilled cold (a call of their own;
    ``resident`` need not end a page), the rest in ONE batched, right-padded
    call against them; then ``steps`` greedy decode steps of every row in one
    batch. Returns the logits a row, [1 + steps, vocab], the tokens fed and
    the pools."""
    b = len(rows)
    need = [-(-(len(p) + steps) // PS) for p, _ in rows]
    tables = np.zeros((b, max(need)), np.int32)
    nxt = 1
    for i, n in enumerate(need):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    k_pages, v_pages = llama.init_kv_pages(cfg, nxt + 1, PS)
    state = llama.init_state_pages(cfg, nxt + 1)

    def prefill(chunks):
        nonlocal k_pages, v_pages, state
        width = max(hi - lo for _, lo, hi in chunks)
        ctx_w = max(-(-lo // PS) for _, lo, _ in chunks)
        tok = np.zeros((b, width), np.int32)
        pos = np.zeros((b, width), np.int32)
        ok = np.zeros((b, width), bool)
        ctx_bt = np.zeros((b, ctx_w), np.int32)
        ctx_len = np.zeros((b,), np.int32)
        for i, lo, hi in chunks:
            n = hi - lo
            tok[i, :n] = rows[i][0][lo:hi]
            pos[i, :n] = np.arange(lo, hi)
            ok[i, :n] = True
            ctx_bt[i, : -(-lo // PS)] = tables[i, : -(-lo // PS)]
            ctx_len[i] = lo
        page = np.take_along_axis(
            tables, np.minimum(pos // PS, tables.shape[1] - 1), axis=1)
        logits, k_pages, v_pages, state = llama.prefill(
            params, cfg, tok, pos, ok, k_pages, v_pages, page, pos % PS,
            ctx_bt, ctx_len, attn_impl=attn_impl, interpret=True,
            state_pages=state,
        )
        return np.asarray(logits, np.float32)

    for i, (_, resident) in enumerate(rows):
        if resident:
            prefill([(i, 0, resident)])
    last = prefill([(i, r, len(p)) for i, (p, r) in enumerate(rows)])
    out = [[last[i]] for i in range(b)]
    fed = [[] for _ in range(b)]
    lens = np.array([len(p) for p, _ in rows], np.int32)
    for step in range(steps):
        toks = np.array([int(np.argmax(o[-1])) for o in out], np.int32)
        logits, k_pages, v_pages, state = llama.decode_step(
            params, cfg, toks, lens + step, k_pages, v_pages, tables,
            lens + step + 1, page_size=PS, interpret=True, state_pages=state,
        )
        for i in range(b):
            fed[i].append(int(toks[i]))
            out[i].append(np.asarray(logits, np.float32)[i])
    return [np.stack(o) for o in out], fed, (k_pages, v_pages, state, tables)


# -- the served programs against the stateless reference -----------------------
@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("rows", [
    pytest.param([(19, 0)], id="cold-alone"),
    pytest.param([(23, 12)], id="state-from-a-finished-pages-slot"),
    pytest.param([(23, 10)], id="a-chunk-starting-inside-a-page"),
    pytest.param([(17, 16)], id="a-one-token-chunk-at-a-pages-first-slot"),
    pytest.param([(30, 16), (9, 0), (21, 7), (14, 13)],
                 id="right-padded-batch-of-unequal-lengths"),
])
def test_prefill_then_decode_through_the_pools(params, rows, attn_impl):
    rows = [(prompt_of(40 + i, n), r) for i, (n, r) in enumerate(rows)]
    got, fed, _ = served(params, rows, 6, attn_impl)
    for (prompt, _), logits, tokens in zip(rows, got, fed):
        want = reference_logits(params, prompt + tokens)[len(prompt) - 1:]
        assert rel_err(logits, want) < TOL


def test_a_warm_prefill_equals_the_cold_one(params):
    """The same prompt cold in one call, and its tail warm against pages the
    head left (their keys, values and state slots): the same logits, at the
    prompt's end and through six decode steps over page boundaries."""
    prompt = prompt_of(3, 27)
    cold, fed_cold, _ = served(params, [(prompt, 0)], 6, "xla")
    warm, fed_warm, _ = served(params, [(prompt, 16)], 6, "xla")
    assert fed_cold == fed_warm
    assert rel_err(warm[0], cold[0]) < TOL


def test_a_full_pages_slot_is_the_snapshot_at_its_end(params):
    """Slot ``p`` of a convolution layer holds ``z`` of the last two tokens
    written in page ``p``: for a full page, the state at its end, whatever
    chunking wrote it; for the last, partial page, the state at the
    sequence's end. The padding of a right-padded row leaves no state."""
    prompt = prompt_of(5, 22)
    _, _, (_, _, whole, tables) = served(params, [(prompt, 0)], 0, "xla")
    _, _, (_, _, pieces, _) = served(
        params, [(prompt, 10), (prompt_of(6, 30), 0)], 0, "xla")
    used = tables[0, : -(-len(prompt) // PS)]
    assert np.abs(np.asarray(whole[:, used])).min(axis=-1).all()  # written
    np.testing.assert_allclose(
        np.asarray(pieces[:, used]), np.asarray(whole[:, used]),
        rtol=1e-4, atol=1e-5)
    # ... and the state is what the equations say: z = B * x of the last
    # two tokens of each page, read off the first layer (a convolution)
    layer = params["layers"][0]
    h = np.asarray(params["embed"])[prompt]
    u = h / np.sqrt((h * h).mean(-1, keepdims=True) + CFG.rms_norm_eps)
    gate_b, _, x = np.split(
        u * np.asarray(layer["attn_norm"]) @ np.asarray(layer["conv_in"]), 3, -1)
    z = gate_b * x
    for j, page in enumerate(used):
        end = min((j + 1) * PS, len(prompt))
        np.testing.assert_allclose(
            np.asarray(whole[0, page]).reshape(2, -1), z[end - 2: end],
            rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", ["conv", "attention", "conv-dense-ffn"])
def test_one_layer_trees_of_each_kind(params, kind):
    """The harness's layer-alone check runs one-layer trees under
    ``replace(cfg, n_layers=1)``, whose ``layer_types`` still start with
    "conv": operator and FFN are read from the layer's own parameters, and
    the reference's system side makes the pools of the kind it finds."""
    index = {"conv": 3, "attention": 2, "conv-dense-ffn": 0}[kind]
    layer = params["layers"][index]
    assert ("conv_in" in layer) == (kind != "attention")
    assert ("router" in layer) == (kind != "conv-dense-ffn")
    one = {**params, "layers": [layer]}
    cfg1 = dataclasses.replace(CFG, n_layers=1)

    class FakeEngine:
        params, model_cfg, page_size, mesh = one, cfg1, PS, None
        _replicated, prefill_attn = jax.devices()[0], "xla"

    prompt = prompt_of(9, 16)
    got, fed = REF.system(FakeEngine, prompt, 4, True, one, cfg1)
    want = reference_logits(one, prompt + fed, cfg1)[len(prompt) - 1:]
    assert rel_err(got, want) < TOL
    pools = REF.pool_config(one, cfg1)
    assert (pools.n_conv_layers, pools.n_attn_layers) == (
        (0, 1) if kind == "attention" else (1, 0))


def test_the_references_system_side_is_the_harness_check(params):
    """``reference.common_check`` as a benchmark run makes it: whole model
    and every layer alone, through the reference's own ``system`` (cold
    half, warm rest, a one-token chunk inside a page, decode steps)."""

    class FakeEngine:
        model_cfg, page_size, mesh = CFG, PS, None
        _replicated, prefill_attn = jax.devices()[0], "xla"

        class config:
            kv_quant_hbm = None

    FakeEngine.params = params
    out = chip_reference.common_check(
        FakeEngine, REF, seed=7, interpret=True, prompt_tokens=16, steps=4)
    assert out["ok"], out
    assert out["layer_rel_err_p75"] < TOL
    assert REF.pieces(128, 16) == [(0, 64), (64, 127), (127, 128)]


def test_arity_without_state(params):
    """A model without convolution layers never sees the state: three
    results of ``prefill`` and ``decode_step``, called positionally as the
    benchmark's own files call them, and no state pool to make."""
    cfg = TINY_QWEN3_MOE
    p = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert llama.init_state_pages(cfg, 8) is None
    k, v = llama.init_kv_pages(cfg, 8, PS)
    assert k.shape == (cfg.n_layers, 8, PS, cfg.n_kv_heads, cfg.hd)
    pos = np.arange(6)[None, :]
    out = llama.prefill(
        p, cfg, np.ones((1, 6), np.int32), pos, np.ones((1, 6), bool), k, v,
        1 + pos // PS, pos % PS, np.zeros((1, 0), np.int32),
        np.zeros((1,), np.int32), None, "xla", False, None, None, True)
    assert len(out) == 3
    out = llama.decode_step(
        p, cfg, np.ones((1,), np.int32), np.asarray([6]), out[1], out[2],
        np.asarray([[1, 2]]), np.asarray([7]), page_size=PS, interpret=True)
    assert len(out) == 3


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


# -- through the engine ------------------------------------------------------
def make_engine(params, cfg=CFG, on_events=None, total_pages=96, lanes=4,
                **engine):
    engine.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    return Engine(
        EngineConfig(
            model=cfg,
            block_manager=BlockManagerConfig(
                total_pages=total_pages, page_size=PS),
            max_model_len=128, decode_batch_size=lanes, prefill_bucket=16,
            interpret=True, **engine,
        ),
        params=params, on_events=on_events,
    )


def run_all(engine, prompts, n=10):
    seqs = [engine.add_request(p, SamplingParams(max_new_tokens=n))
            for p in prompts]
    while engine.has_work:
        engine.step()
    return seqs


def picks(params, ask, generated):
    """The reference's greedy choice at each generated position, given the
    tokens the engine generated before it."""
    logits = reference_logits(params, ask + generated)
    return logits[len(ask) - 1: -1].argmax(-1).tolist()


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
    engine = make_engine(params, total_pages=13, lanes=2)
    preempted = []
    on_preempted = engine.scheduler.on_preempted
    engine.scheduler.on_preempted = lambda seq: (
        preempted.append(seq), on_preempted(seq))[1]
    seqs = run_all(engine, asks, n=18)
    assert preempted
    for seq, ask in zip(seqs, asks):
        assert len(seq.all_tokens) - len(ask) == 18
        generated = seq.all_tokens[len(ask):]
        assert generated == picks(params, ask, generated)


def test_a_page_evicted_and_refilled(params):
    """A page id that is evicted and reused takes its state with it: the
    first request's pages are evicted by others, its prompt is computed
    again (no hit) into whatever pages are free, and a third request hits
    the refilled pages."""
    ask = prompt_of(100, 17)
    engine = make_engine(params, total_pages=20, lanes=2)
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
    assert engine.k_pages.shape == (2, 96, PS, 2, 128)
    assert engine.state_pages.shape == (6, 96, 2 * CFG.hidden_size)
    gqa = make_engine(
        llama.init_params(jax.random.PRNGKey(1), TINY_QWEN3_MOE),
        cfg=TINY_QWEN3_MOE)
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


def test_a_saved_state_dict_loads_to_the_references_logits(params):
    """The tiny model written out under the checkpoint's names ([out, in]
    matrices, the filter as a depthwise ``Conv1d`` weight ``[d, 1, K]``)
    and read back: the loaded tree is the tree, and the served program on it
    gives the reference's logits."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    names = {
        "attn_norm": "operator_norm.weight", "mlp_norm": "ffn_norm.weight",
        "conv_in": "conv.in_proj.weight", "conv_out": "conv.out_proj.weight",
        "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
        "wv": "self_attn.v_proj.weight", "wo": "self_attn.out_proj.weight",
        "q_norm": "self_attn.q_layernorm.weight",
        "k_norm": "self_attn.k_layernorm.weight",
        "router": "feed_forward.gate.weight",
        "router_bias": "feed_forward.expert_bias",
    }
    ffn = {"gate": "w1", "up": "w3", "down": "w2"}
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.embedding_norm.weight": params["final_norm"]}
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        for ours, theirs in names.items():
            if ours in layer:
                w = np.asarray(layer[ours])
                sd[p + theirs] = w.T if w.ndim == 2 else w
        if "conv_w" in layer:
            sd[p + "conv.conv.weight"] = np.asarray(layer["conv_w"]).T[:, None, :]
        for ours, theirs in ffn.items():
            w = np.asarray(layer[f"w_{ours}"])
            if w.ndim == 2:
                sd[f"{p}feed_forward.{theirs}.weight"] = w.T
                continue
            for j in range(CFG.n_experts):
                sd[f"{p}feed_forward.experts.{j}.{theirs}.weight"] = w[j].T
    loaded = load_hf_state_dict(sd, CFG)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    prompt = prompt_of(77, 14)
    got, fed, _ = served(loaded, [(prompt, 8)], 2, "xla")
    want = reference_logits(params, prompt + fed[0])[len(prompt) - 1:]
    assert rel_err(got[0], want) < TOL
