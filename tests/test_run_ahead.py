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
  chain meets: ``tests/test_run_ahead_parity.py`` (one file with this until
  it passed 120 cpu-seconds of a whole run; ``tests/run_ahead.py`` has
  ``both`` / ``never_ahead`` and what else the two share).
"""

import jax
import jax.monitoring
import pytest

from llm_d_kv_cache_manager_tpu.models import llama
from llm_d_kv_cache_manager_tpu.server import SamplingParams, SchedulerConfig
from llm_d_kv_cache_manager_tpu.server.sequence import SequenceStatus
from run_ahead import (
    fill as _fill,
    make_engine as _engine,
    never_ahead,
    prompt as _prompt,
)


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


def test_block_diffusion_runs_ahead_with_lanes_full_and_budgets_far():
    """Its dispatch path is its own, its rule is this one: a lane's block in
    progress stays on the device between two forwards
    (``tests/test_run_ahead_blocks.py``), so full lanes and far budgets
    chain, and with a lane free nothing is in flight between two steps."""
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
    in_flight = 0
    while eng.has_work:
        eng.step()
        in_flight += eng._inflight is not None
        if len(eng.scheduler.running) < 2:
            assert eng._inflight is None
    assert eng._inflight is None and in_flight > 0
    assert all(s.num_generated == 12 for s in seqs)
    assert eng.step_stats["decode_chained_dispatches"] > 0
    assert (eng.step_stats["decode_dispatches"]
            > eng.step_stats["decode_chained_dispatches"])
