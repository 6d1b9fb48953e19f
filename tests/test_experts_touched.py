"""``experts_touched`` on the fused decode path.

``llama.decode_steps`` counts, on the device, the distinct experts the rows
of each forward chose in each routed layer, sums them over the layers and the
burst's steps and returns the sum in the first column of the burst's one
array; ``Engine._commit_burst`` adds it to ``step_stats["experts_touched"]``
under ``obs_step_timing``, beside ``decode_forwards``. Held here against a
numpy count of what the router chose (a callback on ``_moe_gates``' indices,
in the test's own trace of the program), for bursts of one and two steps,
with and without the dispatch ahead; the tokens are the same with the host's
half on and off, chained and not; a dense model counts nothing."""

import dataclasses

import jax
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA, TINY_QWEN3_MOE, llama
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)

from run_ahead import never_ahead

PS, LANES = 4, 2

#: a dense first layer, then two routed ones: ``routed_layers`` is not
#: ``n_layers``
ROUTED = dataclasses.replace(
    TINY_QWEN3_MOE, n_layers=3, first_k_dense=1, n_experts=8
)


def _engine(model, k, obs=True):
    eng = Engine(
        EngineConfig(
            model=model,
            block_manager=BlockManagerConfig(total_pages=64, page_size=PS),
            max_model_len=64, decode_batch_size=LANES,
            decode_steps_per_iter=k, interpret=True,
            scheduler=SchedulerConfig(max_prefill_batch=4), prefill_bucket=8,
        ),
    )
    eng.obs_step_timing = obs
    return eng


def _prompt(seed, n):
    return list(map(int, np.random.default_rng(seed).integers(1, 256, n)))


def _run(eng, new=13):
    """Three requests on two lanes: lanes full (dispatches chain), a finish,
    an admission, a tail with one lane."""
    seqs = [
        eng.add_request(_prompt(i, 9 + i), SamplingParams(max_new_tokens=new))
        for i in range(3)
    ]
    eng.run_until_complete()
    assert eng._inflight is None
    return [list(s.output_tokens) for s in seqs]


@pytest.fixture
def chosen(monkeypatch):
    """Every ``[rows, top-k]`` index array ``_moe_gates`` returns in the
    programs traced while the fixture is live, as numpy, in a list."""
    seen = []
    gates = llama._moe_gates

    def recording(layer, cfg, x):
        topv, topi = gates(layer, cfg, x)
        jax.debug.callback(lambda t: seen.append(np.asarray(t)), topi)
        return topv, topi

    monkeypatch.setattr(llama, "_moe_gates", recording)
    return seen


@pytest.mark.parametrize("ahead", [True, False], ids=["chained", "unchained"])
@pytest.mark.parametrize("k", [1, 2])
def test_the_burst_counts_the_experts_the_router_chose(
        k, ahead, chosen, monkeypatch):
    if not ahead:
        never_ahead(monkeypatch)
    # a configuration of this case's own: its programs are traced here, with
    # the callback inside, and no other test's cache holds them
    model = dataclasses.replace(ROUTED, rms_norm_eps=1e-6 + 1e-9 * (2 * k + ahead))
    eng = _engine(model, k)
    assert eng.routed_layers == 2
    _run(eng)
    jax.effects_barrier()
    stats = eng.step_stats
    assert (stats["decode_chained_dispatches"] > 0) == ahead
    assert stats["decode_forwards"] == k * stats["decode_dispatches"] > 0
    # a decode forward routes one row a lane (padded lanes too: the grouped
    # matmuls read their experts as well); a prefill routes rows x chunk
    decode = [t for t in chosen if t.shape[0] == LANES]
    assert len(decode) == eng.routed_layers * stats["decode_forwards"]
    want = sum(len(np.unique(t)) for t in decode)
    assert stats["experts_touched"] == want
    top_k = model.n_experts_per_tok
    assert top_k <= want / len(decode) <= LANES * top_k


@pytest.mark.parametrize("k", [1, 2])
def test_tokens_are_the_same_with_and_without_the_count(k, monkeypatch):
    """The device's half is part of the one program; the host's half (the
    switch) and the dispatch ahead change no token."""
    outs = {}
    for ahead in (True, False):
        if not ahead:
            never_ahead(monkeypatch)
        for obs in (True, False):
            eng = _engine(ROUTED, k, obs=obs)
            outs[ahead, obs] = _run(eng)
            counted = eng.step_stats["experts_touched"]
            assert (counted > 0) == obs
            assert (eng.step_stats["decode_forwards"] > 0) == obs
    assert len({repr(v) for v in outs.values()}) == 1


def test_a_dense_model_counts_no_expert():
    eng = _engine(TINY_LLAMA, 2)
    assert eng.routed_layers == 0
    _run(eng)
    assert eng.step_stats["decode_forwards"] > 0
    assert eng.step_stats["experts_touched"] == 0


@pytest.mark.parametrize("num_steps", [1, 2])
def test_the_count_rides_in_the_first_column_and_the_ids_in_the_last(num_steps):
    """``decode_steps`` alone: ``[count | tokens]``, the count the same in
    every lane; fed its own burst, it starts from the last column."""
    cfg = ROUTED
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    k_pages, v_pages = llama.init_kv_pages(cfg, 16, PS)
    tables = np.arange(1, 1 + LANES * 4, dtype=np.int32).reshape(LANES, 4)
    lens = np.asarray([3, 5], np.int32)

    def packed(offset):
        return llama.pack_decode_inputs(
            lens + offset, tables, lens + offset + 1,
            np.zeros((LANES,), np.float32), np.zeros((LANES,), np.int32),
            np.ones((LANES,), np.float32),
        )

    kw = dict(page_size=PS, num_steps=num_steps, interpret=True)
    key = jax.random.PRNGKey(0)
    first, k_pages, v_pages = llama.decode_steps(
        params, cfg, np.asarray([17, 29], np.int32), packed(0), k_pages,
        v_pages, key, **kw)
    first = np.asarray(first)
    assert first.shape == (LANES, 1 + num_steps)
    assert first[0, 0] == first[1, 0]
    top_k, layers = cfg.n_experts_per_tok, 2
    assert num_steps * layers * top_k <= first[0, 0]
    assert first[0, 0] <= num_steps * layers * min(cfg.n_experts, LANES * top_k)
    # chained: the whole burst as ids == its last column as ids
    pools = [np.asarray(k_pages), np.asarray(v_pages)]
    a, *_ = llama.decode_steps(
        params, cfg, first, packed(num_steps), jax.numpy.asarray(pools[0]),
        jax.numpy.asarray(pools[1]), key, **kw)
    b, *_ = llama.decode_steps(
        params, cfg, first[:, -1], packed(num_steps),
        jax.numpy.asarray(pools[0]), jax.numpy.asarray(pools[1]), key, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
