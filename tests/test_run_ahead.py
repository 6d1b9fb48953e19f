"""One decode dispatch ahead (``Engine._next_schedule_decided``).

The engine leaves a decode burst on the device over the end of a step, and
enqueues its successor before fetching it, exactly where the next schedule
is already decided: nothing could be admitted and no lane reaches its
budget within the burst. Two things are held here:

- the rule itself: with a lane free nothing is ever in flight between two
  steps (no first token waits for a burst), with lanes full and budgets far
  one burst is, a lane about to finish ends the chain, and a successor is
  prefilled in the step after a budget finish;
- parity: greedy outputs under the rule equal, token for token, those of
  the same engine whose rule is patched to "never", over every edge a
  chain meets (``both`` / ``never_ahead``; the parity classes of
  ``test_engine.py``, ``test_decode_fastpath.py`` and friends use them).
"""

import jax
import jax.monitoring
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA, TINY_MLA_MOE, llama
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.sequence import SequenceStatus

PS = 4


def never_ahead(monkeypatch):
    """The engine that waits: the one predicate answers no."""
    monkeypatch.setattr(
        Engine, "_next_schedule_decided", lambda self, active, k: False
    )


def both(make, drive, monkeypatch, chained=True):
    """``drive(make())`` under the rule and under "never": the two
    results, asserted equal. ``chained``: the run under the rule must have
    chained a dispatch (else the case holds nothing), the other none."""
    outs = []
    for never in (False, True):
        if never:
            never_ahead(monkeypatch)
        eng = make()
        eng.obs_step_timing = True
        outs.append(drive(eng))
        assert eng._inflight is None and not eng.has_work
        n = eng.step_stats["decode_chained_dispatches"]
        assert (n > 0) == (chained and not never), (never, n)
    monkeypatch.undo()
    assert outs[0] == outs[1]
    return outs[0]


def _engine(total_pages=64, lanes=2, model=TINY_LLAMA, params=None,
            max_model_len=64, **kw):
    kw.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    kw.setdefault("prefill_bucket", 8)
    return Engine(
        EngineConfig(
            model=model,
            block_manager=BlockManagerConfig(
                total_pages=total_pages, page_size=PS
            ),
            max_model_len=max_model_len,
            decode_batch_size=lanes,
            interpret=True,
            **kw,
        ),
        params=params,
    )


def _prompt(seed, n, vocab=TINY_LLAMA.vocab_size):
    return list(map(int, np.random.default_rng(seed).integers(1, vocab, n)))


def _fill(eng, n, new=20, seed=0, plen=9):
    return [
        eng.add_request(
            _prompt(seed + i, plen + i), SamplingParams(max_new_tokens=new),
            request_id=f"r{seed + i}",
        )
        for i in range(n)
    ]


# -- the rule ------------------------------------------------------------------
@pytest.mark.parametrize("waiting", [0, 3], ids=["nobody-waits", "three-wait"])
@pytest.mark.parametrize("k", [1, 3])
def test_with_a_free_lane_nothing_is_in_flight_between_steps(waiting, k):
    """The first-token guarantee: while a lane is free every ``step()``
    ends with the burst fetched and committed, whatever ``waiting`` holds,
    so an arrival's prefill never finds a burst in its way."""
    eng = _engine(lanes=4, decode_steps_per_iter=k)
    eng.obs_step_timing = True
    _fill(eng, 2, new=14)
    later = [(3, 40), (5, 41), (6, 42)][:waiting]
    for step in range(200):
        for at, seed in later:
            if at == step:
                eng.add_request(
                    _prompt(seed, 7), SamplingParams(max_new_tokens=30)
                )
        if not eng.has_work:
            break
        eng.step()
        if len(eng.scheduler.running) < 4:
            assert eng._inflight is None
    assert not eng.has_work
    if not waiting:
        assert eng.step_stats["decode_chained_dispatches"] == 0


@pytest.mark.parametrize("k", [1, 3])
def test_with_lanes_full_and_budgets_far_one_burst_stays_in_flight(k):
    eng = _engine(lanes=2, decode_steps_per_iter=k)
    eng.obs_step_timing = True
    seqs = _fill(eng, 2, new=30)
    eng.add_request(_prompt(9, 8), SamplingParams(max_new_tokens=4))  # waits
    eng.step()  # the prefill of both
    st = eng.step_stats
    for i in range(5):
        eng.step()
        assert eng._inflight is not None
        assert eng._inflight["active"] == seqs
        # committed state lags the device by the one burst in flight
        assert all(s.num_generated == 1 + k * i for s in seqs)
        assert st["decode_chained_dispatches"] == i
    assert st["decode_dispatches"] == 5
    eng.run_until_complete()
    assert eng._inflight is None
    assert all(s.num_generated == 30 for s in seqs)


@pytest.mark.parametrize(
    "budget", ["max_new_tokens", "max_model_len"],
)
def test_a_lane_within_a_burst_of_its_budget_ends_the_chain(budget):
    """The host knows before a burst returns that a lane finishes in it:
    that burst is fetched in its own step, so the lane leaves at once and
    no surplus burst is computed for it."""
    k = 2
    if budget == "max_new_tokens":
        eng = _engine(lanes=2, decode_steps_per_iter=k)
        short = eng.add_request(_prompt(1, 9), SamplingParams(max_new_tokens=6))
        long = eng.add_request(_prompt(2, 9), SamplingParams(max_new_tokens=40))
        want = 6
    else:
        eng = _engine(lanes=2, decode_steps_per_iter=k, max_model_len=20)
        short = eng.add_request(_prompt(1, 13), SamplingParams(max_new_tokens=40))
        long = eng.add_request(_prompt(2, 5), SamplingParams(max_new_tokens=40))
        want = 20 - 13
    eng.obs_step_timing = True
    dispatched = []
    while short.status is not SequenceStatus.FINISHED:
        before = eng.step_stats["decode_dispatches"]
        eng.step()
        if eng.step_stats["decode_dispatches"] > before:
            dispatched.append(eng._inflight is not None)
    assert short.num_generated == want
    # ran ahead until the burst that reaches the budget, which was not
    # left in flight: the lane finished in the step that dispatched it
    assert dispatched[-1] is False and any(dispatched)
    assert eng._inflight is None
    # exactly the bursts the lane needed: 1 token from the prefill, then
    # ceil((want - 1) / k) dispatches, none for a lane already gone
    assert len(dispatched) == -(-(want - 1) // k)
    eng.run_until_complete()
    assert long.error is None


def test_a_successor_is_prefilled_in_the_step_after_a_budget_finish():
    """A request that arrives while lanes are full waits for a lane, and
    no longer: the step after the one in which a lane finished by its
    budget prefills it."""
    eng = _engine(lanes=2)
    a = eng.add_request(_prompt(1, 9), SamplingParams(max_new_tokens=7))
    b = eng.add_request(_prompt(2, 9), SamplingParams(max_new_tokens=40))
    eng.step()
    eng.step()
    assert eng._inflight is not None
    c = eng.add_request(_prompt(3, 9), SamplingParams(max_new_tokens=5))
    while a.status is not SequenceStatus.FINISHED:
        assert c.status is SequenceStatus.WAITING
        eng.step()
    assert eng._inflight is None and a.num_generated == 7
    eng.step()
    assert c.status is SequenceStatus.RUNNING and c.num_generated == 1
    eng.run_until_complete()
    assert (b.num_generated, c.num_generated) == (40, 5)


def test_the_scheduler_answers_whether_anything_could_be_admitted():
    eng = _engine(lanes=2, total_pages=12)
    sched = eng.scheduler
    assert not sched.admission_closed()  # idle: an arrival would be admitted
    _fill(eng, 1, new=30)
    eng.step()
    assert not sched.admission_closed()  # a lane is free, nobody waits
    big = eng.add_request(
        _prompt(5, 40), SamplingParams(max_new_tokens=2), request_id="big"
    )
    assert not eng.block_manager.can_allocate(big)
    assert sched.admission_closed()  # the head cannot allocate: FCFS holds all
    big.importing = True  # mid-import: skipped, so nobody waits
    assert not sched.admission_closed()
    assert big.import_wanted_time is None  # asking stamps nothing
    big.importing = False
    sched.attach_qos()  # a higher class may pass the head on arrival
    assert not sched.admission_closed()
    sched.qos_enabled = False
    eng.abort(big.request_id)
    _fill(eng, 1, new=30, seed=7)
    eng.step()
    assert len(sched.running) == 2 and sched.admission_closed()  # lanes full


def test_a_chunk_owed_keeps_admission_open():
    eng = _engine(
        lanes=1,
        scheduler=SchedulerConfig(max_prefill_batch=4, chunked_prefill_tokens=8),
    )
    eng.add_request(_prompt(1, 30), SamplingParams(max_new_tokens=4))
    eng.step()
    assert eng.scheduler.prefilling and not eng.scheduler.admission_closed()
    eng.run_until_complete()


def test_chaining_adds_no_program():
    """A chained dispatch runs the program an unchained one compiled: its
    ids are a burst's own ``[lanes, 1 + k]`` output, which every dispatch
    hands over in that shape."""
    def run():
        eng = _engine(lanes=2, decode_steps_per_iter=2)
        eng.obs_step_timing = True
        _fill(eng, 2, new=16)
        eng.run_until_complete()
        return eng.step_stats["decode_chained_dispatches"]

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    with pytest.MonkeyPatch.context() as mp:
        never_ahead(mp)
        assert run() == 0  # compiles what an engine that waits needs
    size = llama.decode_steps._cache_size()
    del compiles[:]
    assert run() > 0
    assert compiles == [] and llama.decode_steps._cache_size() == size


def test_block_diffusion_never_runs_ahead():
    """Its dispatch path is its own (block state lives on the host): full
    lanes and far budgets leave nothing in flight, by construction."""
    from llm_d_kv_cache_manager_tpu.models import TINY_SDAR_MOE

    eng = _engine(lanes=2, model=TINY_SDAR_MOE, prefill_bucket=8)
    eng.obs_step_timing = True
    seqs = [
        eng.add_request(
            _prompt(i, 9, TINY_SDAR_MOE.vocab_size - 8),
            SamplingParams(max_new_tokens=12),
        )
        for i in range(3)
    ]
    while eng.has_work:
        eng.step()
        assert eng._inflight is None
    assert all(s.num_generated == 12 for s in seqs)
    assert eng.step_stats["decode_chained_dispatches"] == 0
    assert eng.step_stats["decode_dispatches"] > 0


# -- parity with the engine that never runs ahead ----------------------------------
def _tokens(seqs):
    assert all(s.error is None for s in seqs)
    return [list(s.generated_tokens) for s in seqs]


def _steady(eng):
    seqs = _fill(eng, 2, new=17) + _fill(eng, 2, new=6, seed=30)
    eng.run_until_complete()
    return _tokens(seqs)


def _budgets(eng):
    # unlike budgets: every finish frees a lane for one who waits, and
    # 13 % 3, 7 % 3 != 0 cut a burst short
    seqs = [
        eng.add_request(_prompt(40 + i, 8 + i), SamplingParams(max_new_tokens=n))
        for i, n in enumerate((13, 7, 22, 5, 9))
    ]
    eng.run_until_complete()
    return _tokens(seqs)


def _stop_mid_chain(eng):
    probe = _engine(lanes=1)
    p = probe.add_request(_prompt(50, 9), SamplingParams(max_new_tokens=8))
    probe.run_until_complete()
    stop = p.generated_tokens[5]
    assert stop not in p.generated_tokens[:5]
    seqs = [
        eng.add_request(
            _prompt(50, 9),
            SamplingParams(max_new_tokens=30, stop_token_ids=(stop,)),
        ),
        eng.add_request(_prompt(51, 9), SamplingParams(max_new_tokens=16)),
        eng.add_request(_prompt(52, 9), SamplingParams(max_new_tokens=6)),
    ]
    eng.run_until_complete()
    assert seqs[0].generated_tokens[-1] == stop
    assert len(seqs[0].generated_tokens) == 6
    return _tokens(seqs)


def _abort_in_flight(eng):
    seqs = _fill(eng, 2, new=24, seed=60)
    waiting = eng.add_request(_prompt(63, 9), SamplingParams(max_new_tokens=6))
    for _ in range(4):
        eng.step()
    free = eng.block_manager.num_free
    gone = eng.abort(seqs[0].request_id)
    assert gone is seqs[0] and eng._inflight is None
    assert eng.block_manager.num_free > free
    eng.run_until_complete()
    assert gone.finish_reason == "abort"
    # an abort lands between two steps: what the lane had by then differs
    # by the burst in flight, which the abort commits first; its
    # batchmates and its successor may not differ at all
    return _tokens([seqs[1], waiting])


def _tight_pool(eng):
    from llm_d_kv_cache_manager_tpu.server.block_manager import AllocationError

    bm = eng.block_manager
    orig, refused = bm.reserve_slots, set()

    def spy(seq, n):
        try:
            return orig(seq, n)
        except AllocationError:
            refused.add(n)
            raise

    bm.reserve_slots = spy
    seqs = _fill(eng, 3, new=12, seed=70, plen=8)
    eng.run_until_complete()
    k = eng.config.decode_steps_per_iter
    # the single reservation was refused (a preemption), and under the
    # rule the double one too: the chain degrades to a step that waits
    assert k in refused, "pool never under pressure"
    if eng.step_stats["decode_chained_dispatches"]:
        assert 2 * k in refused, "the double reservation never degraded"
    return _tokens(seqs)


def _warm_prefix(eng):
    # pages registered while a burst is in flight cover committed tokens
    # only: the same prompt again hits them and gives the same tokens
    p = _prompt(80, 16)
    a = eng.add_request(p, SamplingParams(max_new_tokens=9))
    other = eng.add_request(_prompt(81, 9), SamplingParams(max_new_tokens=9))
    eng.run_until_complete()
    b = eng.add_request(p, SamplingParams(max_new_tokens=9))
    eng.run_until_complete()
    assert b.num_cached_prompt > 0
    return _tokens([a, other, b])


@pytest.fixture(scope="module")
def mla_params():
    return llama.init_params(jax.random.PRNGKey(11), TINY_MLA_MOE)


CASES = {
    "steady-k1": (dict(), _steady),
    "steady-k3": (dict(decode_steps_per_iter=3), _steady),
    "budgets-k1": (dict(), _budgets),
    "budgets-k3": (dict(decode_steps_per_iter=3), _budgets),
    "stop-token-k1": (dict(), _stop_mid_chain),
    "stop-token-k2": (dict(decode_steps_per_iter=2), _stop_mid_chain),
    "abort-k1": (dict(), _abort_in_flight),
    "abort-k3": (dict(decode_steps_per_iter=3), _abort_in_flight),
    "tight-pool-k1": (dict(lanes=3, total_pages=13), _tight_pool),
    "tight-pool-k4": (
        dict(lanes=3, total_pages=12, decode_steps_per_iter=4), _tight_pool,
    ),
    "warm-prefix-k2": (dict(decode_steps_per_iter=2), _warm_prefix),
    "int8-pool-k1": (dict(kv_quant_hbm="int8"), _steady),
    "int8-pool-k2": (
        dict(kv_quant_hbm="int8", decode_steps_per_iter=2), _budgets,
    ),
    "chunked-prefill": (
        dict(
            decode_steps_per_iter=2,
            scheduler=SchedulerConfig(
                max_prefill_batch=4, chunked_prefill_tokens=8
            ),
        ),
        _budgets,
    ),
    "spec-fall-through": (
        dict(spec_decode="prompt_lookup", spec_k=3, spec_ngram=2), _steady,
    ),
    "tp2": (dict(tp=2), _budgets),
}


@pytest.mark.parametrize("case", list(CASES))
def test_greedy_parity_with_the_engine_that_waits(case, monkeypatch):
    kw, drive = CASES[case]
    # speculation drains before it proposes, so its fall-through into the
    # fused path never finds a burst to chain from
    both(
        lambda: _engine(**kw), drive, monkeypatch,
        chained=case != "spec-fall-through",
    )


@pytest.mark.parametrize("k", [1, 2])
def test_greedy_parity_on_a_latent_pool(k, mla_params, monkeypatch):
    def make():
        return _engine(
            lanes=2, total_pages=96, model=TINY_MLA_MOE, params=mla_params,
            max_model_len=128, prefill_bucket=16, decode_steps_per_iter=k,
        )

    def drive(eng):
        seqs = [
            eng.add_request(
                np.random.default_rng(i).integers(1, 200, 9 + i).tolist(),
                SamplingParams(max_new_tokens=n),
            )
            for i, n in enumerate((11, 6, 8))
        ]
        eng.run_until_complete()
        return _tokens(seqs)

    both(make, drive, monkeypatch)


def test_sampled_lanes_keep_their_distribution_and_their_count(monkeypatch):
    """temperature > 0: the streams are not bit-identical to the engine
    that waits (a discarded surplus burst takes a split of the engine's
    key), but every lane ends at its budget and a greedy batchmate's
    tokens do not move."""
    def drive(eng):
        greedy = eng.add_request(_prompt(90, 9), SamplingParams(max_new_tokens=14))
        sampled = eng.add_request(
            _prompt(91, 9),
            SamplingParams(max_new_tokens=9, temperature=0.8, top_k=8),
        )
        eng.run_until_complete()
        assert sampled.num_generated == 9 and sampled.error is None
        return _tokens([greedy])

    both(lambda: _engine(lanes=2), drive, monkeypatch)
