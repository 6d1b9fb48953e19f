"""Admission ahead (``Engine._admit_ahead``): where a lane reaches its token
budget inside the burst just enqueued and a request waits, that request is
admitted and its prefill dispatched behind the burst, and the next step
commits it. Held here:

- the rule, case by case: it admits ahead there, and declines for each reason
  its docstring gives (the next step then admits as it always did);
- ``max_running`` holds in ``scheduler.running`` at every step, and an abort
  or a preemption that meets a prefill still on the device leaves no page
  held twice or lost.

Parity with the engine whose admissions wait, pool by pool, is in
``tests/test_admit_ahead_parity.py``; the span and the counts in
``tests/test_admit_spans.py``.
"""

import time

import pytest

from llm_d_kv_cache_manager_tpu.server import SamplingParams, SchedulerConfig
from llm_d_kv_cache_manager_tpu.server.sequence import SequenceStatus
from run_ahead import make_engine, never_admits_ahead, prompt

LANES = 2


def engine(**kw):
    eng = make_engine(lanes=LANES, **kw)
    eng.obs_step_timing = True
    return eng


def ask(eng, seed, n, new, **kw):
    sampling = kw.pop("sampling", None) or SamplingParams(max_new_tokens=new)
    return eng.add_request(prompt(seed, n), sampling, request_id=f"r{seed}", **kw)


def lanes_full(eng, short=7, long=30):
    """Two lanes taken, one ``short`` tokens from its end and one far."""
    a = ask(eng, 1, 9, short)
    b = ask(eng, 2, 9, long)
    while len(eng.scheduler.running) < LANES:  # (chunked: a step a chunk)
        eng.step()
    assert eng.scheduler.running == [a, b]
    return a, b


def drive(eng, until=lambda: False, watch=lambda: None):
    """Steps until ``until()`` or the end; the lanes hold at every step. Returns
    whether a step ever ended with a prefill on the device."""
    ahead = False
    while eng.has_work and not until():
        eng.step()
        assert len(eng.scheduler.running) <= LANES
        assert eng._prefill_ahead is None or eng._inflight is None
        ahead |= eng._prefill_ahead is not None
        watch()
    return ahead


def pool_is_whole(eng, free):
    bm = eng.block_manager
    assert not eng.has_work and eng._prefill_ahead is None
    assert bm.num_free == free
    assert all(info.ref_count == 0 for info in bm._pages.values())


# -- it admits ahead ---------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 3])
def test_a_budget_that_ends_in_the_burst_admits_the_one_who_waits(k):
    eng = engine(decode_steps_per_iter=k)
    free = eng.block_manager.num_free
    a, b = lanes_full(eng)
    c = ask(eng, 3, 9, 5)
    drive(eng, until=a.is_finished)
    # the step that finished ``a`` left ``c``'s prefill on the device: pages
    # taken, out of the queue, not yet a lane; the burst itself was fetched
    job = eng._prefill_ahead
    assert job is not None and job["seqs"] == [c]
    assert eng._inflight is None and a.num_generated == 7
    assert c.block_table and c.num_generated == 0
    assert list(eng.scheduler.waiting) == [] and eng.scheduler.prefilling == [c]
    assert eng.scheduler.running == [b] and eng.has_work
    assert eng.step_stats["admit_ahead"] == 1
    steps = eng.step_stats["steps"]
    eng.step()
    # the next step commits it first and decodes it with the others
    assert eng._prefill_ahead is None and eng.scheduler.prefilling == []
    assert c.status is SequenceStatus.RUNNING and c.num_generated >= 1
    assert eng.scheduler.running == [b, c]
    assert eng.step_stats["decode_dispatches"] and eng.step_stats["steps"] == steps + 1
    assert not drive(eng)  # nobody waits any more
    assert (a.num_generated, b.num_generated, c.num_generated) == (7, 30, 5)
    st = eng.step_stats
    assert (st["admit_attempts"], st["admit_rollbacks"], st["admit_ahead"]) == (3, 0, 1)
    pool_is_whole(eng, free)


def test_two_lanes_that_leave_together_admit_two():
    eng = engine()
    free = eng.block_manager.num_free
    a, b = lanes_full(eng, short=6, long=6)
    later = [ask(eng, 3 + i, 8 + i, 4) for i in range(3)]
    drive(eng, until=a.is_finished)
    assert b.is_finished() and eng._prefill_ahead["seqs"] == later[:2]
    assert list(eng.scheduler.waiting) == later[2:]
    drive(eng)
    assert [s.num_generated for s in later] == [4, 4, 4]
    assert eng.step_stats["admit_ahead"] == 3
    pool_is_whole(eng, free)


def test_a_lane_the_commit_before_found_finished_leaves_the_same_way():
    """A stop token is not foreseen; but a chain fetches a burst when its
    successor is already on the device, and a lane that burst ended is by then
    as certain to leave as one at its budget: the one who waits is admitted
    behind the surplus burst."""
    probe = make_engine(lanes=1)
    p = ask(probe, 1, 9, 8)
    probe.run_until_complete()
    stop = p.generated_tokens[4]
    assert stop not in p.generated_tokens[:4]
    eng = engine()
    free = eng.block_manager.num_free
    a = ask(eng, 1, 9, 0, sampling=SamplingParams(
        max_new_tokens=30, stop_token_ids=(stop,)))
    b = ask(eng, 2, 9, 30)
    eng.step()
    c = ask(eng, 3, 9, 4)
    chained = []
    drive(eng, until=a.is_finished,
          watch=lambda: chained.append(eng._inflight is not None))
    assert chained[-2:] == [True, False] and a.num_generated == 5
    assert eng._prefill_ahead["seqs"] == [c]
    drive(eng)
    assert (b.num_generated, c.num_generated) == (30, 4)
    pool_is_whole(eng, free)


# -- it declines -------------------------------------------------------------------
def _budgets_far(eng):
    """Lanes full and nobody near the end: the chain runs, nobody leaves."""
    a, b = lanes_full(eng, short=12, long=30)
    c = ask(eng, 3, 9, 5)
    for _ in range(9):
        eng.step()
        assert eng._inflight is not None and eng._prefill_ahead is None
    eng._drain_inflight()
    assert eng.step_stats["admit_ahead"] == 0 and a.num_generated == 10
    # (what follows is the last bullet's case: ``c`` is importing by then)
    c.importing = True
    return a, b, c


def _nobody_waits(eng):
    a, b = lanes_full(eng)
    return a, b, None


def _head_importing(eng):
    a, b = lanes_full(eng)
    c = ask(eng, 3, 9, 5)
    c.importing = True
    return a, b, c


def _pages_short(eng):
    # 12 pages to give: a and b hold four each when a ends, c needs five
    a, b = lanes_full(eng, short=6, long=12)
    c = ask(eng, 3, 17, 3)
    return a, b, c


def _qos(eng):
    eng.scheduler.attach_qos()
    a, b = lanes_full(eng)
    return a, b, ask(eng, 3, 9, 5)


def _deadline(eng):
    a, b = lanes_full(eng)
    return a, b, ask(eng, 3, 9, 5, deadline=time.monotonic() + 3600)


def _plain(eng):
    a, b = lanes_full(eng)
    return a, b, ask(eng, 3, 9, 5)


DECLINES = {
    "no-certain-leaver": (dict(), _budgets_far),
    "nobody-waits": (dict(), _nobody_waits),
    "head-importing": (dict(), _head_importing),
    "pages-short-without-the-leavers": (dict(total_pages=13), _pages_short),
    "tenant-qos": (dict(), _qos),
    "deadlines": (dict(), _deadline),
    "chunked-prefill": (
        dict(scheduler=SchedulerConfig(
            max_prefill_batch=4, chunked_prefill_tokens=8)),
        _plain,
    ),
    "speculation": (
        dict(spec_decode="prompt_lookup", spec_k=3, spec_ngram=2), _plain,
    ),
}


@pytest.mark.parametrize("case", list(DECLINES))
def test_it_declines_and_the_next_step_admits(case):
    kw, arrange = DECLINES[case]
    eng = engine(**kw)
    bm = eng.block_manager
    free = bm.num_free
    a, b, c = arrange(eng)
    could = []
    ahead = drive(
        eng, until=a.is_finished,
        watch=lambda: could.append(c is not None and bm.can_allocate(c)),
    )
    assert not ahead and eng._prefill_ahead is None and eng._inflight is None
    assert eng.step_stats["admit_ahead"] == 0
    if c is None:
        assert not drive(eng)
        return pool_is_whole(eng, free)
    # it still waits, untouched, when the lane has gone
    assert c.status is SequenceStatus.WAITING and not c.block_table
    assert list(eng.scheduler.waiting) == [c]
    if case == "pages-short-without-the-leavers":
        # refused by the pages alone: the step before the lane left the head
        # could not allocate, with the lane's pages back it can
        assert could[-2] is False and could[-1] is True
    if c.importing:
        assert c.import_wanted_time is None  # the walk ahead stamps nothing
        eng.step()
        assert c.status is SequenceStatus.WAITING
        c.importing = False
    eng.step()
    assert c.num_prefilled > 0 and c not in eng.scheduler.waiting
    drive(eng)
    assert c.is_finished() and c.error is None
    assert eng.step_stats["admit_ahead"] == 0
    pool_is_whole(eng, free)


def test_a_chunk_owed_declines():
    """No decode step of the one scheduling mode that admits ahead meets a
    chunk owed (a prefill step comes first), so the rule is asked directly."""
    eng = engine()
    a, b = lanes_full(eng)
    c = ask(eng, 3, 9, 5)
    eng.scheduler.prefilling.append(a)
    eng._admit_ahead([a, b], 30)
    assert eng._prefill_ahead is None and not c.block_table
    assert list(eng.scheduler.waiting) == [c]
    eng.scheduler.prefilling.clear()
    eng.run_until_complete()


def test_the_walk_ahead_is_the_schedulers_own_and_stops_at_an_import():
    """``schedule(leaving=)``: the lanes that leave count as gone and nothing
    else does; FCFS holds, so a request still importing ends the walk."""
    eng = engine()
    sched = eng.scheduler
    a, b = lanes_full(eng)
    first, second, third = (ask(eng, 3 + i, 9, 5) for i in range(3))
    second.importing = True
    assert sched.schedule().prefill == []  # lanes full: nothing, as ever
    out = sched.schedule(leaving=2)
    assert out.prefill == [first] and out.chunks is None
    assert list(sched.waiting) == [second, third]
    assert sched.prefilling == [first] and sched.running == [a, b]
    assert second.import_wanted_time is None
    # (the engine asks only with nothing in ``prefilling``: undo that one)
    eng.block_manager.free_sequence(first)
    first.reset_allocation()
    sched.prefilling.clear()
    second.importing = False
    assert sched.schedule(leaving=1).prefill == [second]  # one lane, one taken
    assert list(sched.waiting) == [third]
    eng.abort_all()
    with pytest.raises(AssertionError, match="chunked"):
        engine(scheduler=SchedulerConfig(
            max_prefill_batch=4, chunked_prefill_tokens=8,
        )).scheduler.schedule(leaving=1)


def test_the_budget_of_the_walk_ahead_rolls_back_as_a_steps_does():
    eng = engine(scheduler=SchedulerConfig(
        max_prefill_batch=4, max_prefill_tokens=20))
    free = eng.block_manager.num_free
    a, b = lanes_full(eng, short=6, long=6)
    c, d = ask(eng, 3, 12, 4), ask(eng, 4, 12, 4)
    drive(eng, until=a.is_finished)
    # both were walked; the second is over the batch's budget and went back
    assert eng._prefill_ahead["seqs"] == [c] and list(eng.scheduler.waiting) == [d]
    assert not d.block_table
    st = eng.step_stats
    assert (st["admit_attempts"], st["admit_rollbacks"], st["admit_ahead"]) == (4, 1, 1)
    drive(eng)
    assert (c.num_generated, d.num_generated) == (4, 4)
    assert st["admit_attempts"] - st["admit_rollbacks"] == 4
    pool_is_whole(eng, free)


# -- what meets a prefill still on the device -----------------------------------------
@pytest.mark.parametrize("who", ["the-successor", "a-batchmate", "everyone"])
def test_an_abort_commits_the_prefill_ahead_first(who):
    eng = engine()
    free = eng.block_manager.num_free
    a, b = lanes_full(eng)
    c = ask(eng, 3, 9, 5)
    drive(eng, until=a.is_finished)
    assert eng._prefill_ahead is not None
    if who == "everyone":
        assert eng.abort_all() == [b, c]
    else:
        gone = eng.abort((c if who == "the-successor" else b).request_id)
        assert gone.finish_reason == "abort" and not gone.block_table
    # committed where it stood: the successor had its first token
    assert eng._prefill_ahead is None and c.num_generated == 1
    assert eng.scheduler.prefilling == []
    drive(eng)
    assert (c.num_generated == 5) == (who == "a-batchmate")
    pool_is_whole(eng, free)


def test_a_preemption_after_an_admission_ahead_gives_every_page_back(monkeypatch):
    """A pool in which the successor fits as the pool stands and the lanes'
    growth then does not: someone is preempted with the admission ahead just
    behind, and the run ends with the pool whole and the tokens of the engine
    whose admissions wait."""
    def run(never):
        if never:
            never_admits_ahead(monkeypatch)
        eng = engine(total_pages=12)
        free = eng.block_manager.num_free
        preempted = []
        fold = type(eng.scheduler).on_preempted
        monkeypatch.setattr(
            eng.scheduler, "on_preempted",
            lambda seq: preempted.append(seq.request_id) or fold(eng.scheduler, seq))
        seqs = [ask(eng, 10 + i, 8, n) for i, n in enumerate((5, 18, 18, 6))]
        drive(eng)
        assert all(s.error is None for s in seqs) and preempted
        assert (eng.step_stats["admit_ahead"] > 0) == (not never)
        pool_is_whole(eng, free)
        return [list(s.generated_tokens) for s in seqs]

    assert run(False) == run(True)
