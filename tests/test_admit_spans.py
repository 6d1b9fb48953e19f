"""An admission's child spans and counts (``Engine.part``, ``ADMIT_PARTS``,
``ADMIT_COUNTS``): ``BlockManager.allocate`` and the scheduler's roll-back,
timed from inside under the switch the phases have.

Off (the default) the primitive is the phases' one shared do-nothing object
and ``step_stats`` stays at zero. On, an admission is one span ``admit`` with
its parts inside it, all inside ``engine.schedule``; the phases tile the
loop's time as they did; a roll-back of either kind is counted; a phase that
starts inside a child fails. An admission ahead (``Engine._admit_ahead``) says
so on its span, lies in the step of the burst it follows, and is counted.
"""

import dataclasses

import jax
import pytest
import served_path
from served_path import prompt_of

from llm_d_kv_cache_manager_tpu.models import TINY_LING_HYBRID, TINY_LLAMA
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.block_manager import (
    AllocationError,
    BlockManager,
)
from llm_d_kv_cache_manager_tpu.server.engine import (
    ADMIT_COUNTS,
    ADMIT_PARTS,
    ADMIT_SECONDS,
    NO_PHASE,
    STEP_PHASES,
)
from llm_d_kv_cache_manager_tpu.server.scheduler import Scheduler
from llm_d_kv_cache_manager_tpu.server.sequence import Sequence

PS = 4
PART_KEYS = ADMIT_SECONDS[1:]


def engine_of(total_pages=64, **kw):
    kw.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    kw.setdefault("decode_batch_size", 4)
    return Engine(EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(total_pages=total_pages, page_size=PS),
        max_model_len=64, prefill_bucket=8, interpret=True, **kw,
    ))


def schedule(eng):
    """What ``Engine.step`` does of a step up to the dispatch: the scheduler
    inside the ``schedule`` phase. No program runs, so none compiles."""
    with eng.phase("schedule"):
        return eng.scheduler.schedule()


def admit_stats(eng):
    return {k: v for k, v in eng.step_stats.items() if k.startswith("admit")}


# -- the primitive ------------------------------------------------------------
def test_the_names_are_fixed_in_two_tuples_beside_the_phases():
    assert ADMIT_PARTS == ("hash", "walk", "window", "state", "pages", "rollback")
    assert ADMIT_COUNTS == (
        "admit_attempts", "admit_rollbacks", "admit_tokens",
        "admit_blocks_hit", "admit_pages", "admit_evictions", "admit_ahead")
    assert not set(ADMIT_PARTS) & set(STEP_PHASES)
    eng = engine_of()
    assert ADMIT_SECONDS == ("admit_s", *(f"admit_{p}_s" for p in ADMIT_PARTS))
    assert set(admit_stats(eng)) == {*ADMIT_SECONDS, *ADMIT_COUNTS}


def test_off_a_part_is_the_phases_one_object_and_an_admission_counts_nothing():
    eng = engine_of()
    assert not eng.obs_step_timing
    assert eng.part() is NO_PHASE is eng.phase("schedule")
    assert all(eng.part(name, seq=1) is NO_PHASE for name in ADMIT_PARTS)
    assert eng.block_manager.part == eng.scheduler.part == eng.part
    # alone, a block manager and a scheduler have the same nothing
    alone = BlockManager(BlockManagerConfig(total_pages=8, page_size=PS))
    assert alone.part() is Scheduler(alone).part("rollback") is NO_PHASE
    with NO_PHASE as part:
        assert part.add(admit_pages=3) is None
    eng.add_request(prompt_of(1, 10), SamplingParams(max_new_tokens=2))
    out = schedule(eng)
    assert len(out.prefill) == 1 and out.prefill[0].block_table
    assert all(v == 0 for v in eng.step_stats.values())


def test_no_switch_of_its_own():
    """One switch, the phases': nothing in the three modules reads another."""
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    fields |= {f.name for f in dataclasses.fields(BlockManagerConfig)}
    fields |= {f.name for f in dataclasses.fields(SchedulerConfig)}
    assert not {f for f in fields if "admit" in f or "part" in f}


# -- on: one admission ----------------------------------------------------------
def test_on_an_admission_is_counted_and_its_parts_lie_inside_it():
    eng = engine_of()
    shared = prompt_of(2, 12)
    first = eng.add_request(shared + [7], SamplingParams(max_new_tokens=3))
    eng.run_until_complete()  # compiles; leaves three blocks cached
    assert first.is_finished() and eng.block_manager.num_cached_pages >= 3
    eng.obs_step_timing = True
    eng.add_request(shared + [8, 9], SamplingParams(max_new_tokens=3))
    eng.run_until_complete()
    st = eng.step_stats
    assert st["admit_attempts"] == 1
    assert st["admit_rollbacks"] == st["admit_evictions"] == 0
    assert st["admit_tokens"] == 14 and st["admit_blocks_hit"] == 3
    assert st["admit_pages"] == 1  # four pages of four tokens, three cached
    for key in ("admit_hash_s", "admit_walk_s", "admit_pages_s"):
        assert st[key] > 0
    assert st["admit_window_s"] == st["admit_state_s"] == st["admit_rollback_s"] == 0
    assert 0 < sum(st[key] for key in PART_KEYS) <= st["admit_s"] <= st["schedule_s"]
    # the phases are what they were: a child is in none of their sums
    assert sum(st[f"{p}_s"] for p in STEP_PHASES if p != "loop") == pytest.approx(
        st["schedule_s"] + st["prefill_s"] + st["decode_s"] + st["publish_s"])
    assert eng._open_phase is None and eng._open_parts == 0


def test_a_decode_step_opens_no_child_span(monkeypatch):
    eng = engine_of()
    eng.add_request(prompt_of(3, 9), SamplingParams(max_new_tokens=6))
    eng.step()  # the admission and its prefill
    eng.obs_step_timing = True
    made = []
    real = eng.part
    monkeypatch.setattr(
        eng.block_manager, "part", lambda *a, **kw: made.append(a) or real(*a, **kw))
    monkeypatch.setattr(eng.scheduler, "part", eng.block_manager.part)
    eng.run_until_complete()
    assert eng.step_stats["steps"] >= 3 and made == []
    assert all(v == 0 for v in admit_stats(eng).values())


# -- roll-backs -------------------------------------------------------------------
def test_an_allocation_error_counts_one_rollback(monkeypatch):
    eng = engine_of()
    eng.obs_step_timing = True
    bm = eng.block_manager
    held = prompt_of(4, 8)
    seq = eng.add_request(held + prompt_of(5, 9), SamplingParams(max_new_tokens=2))
    # two cached blocks the attempt takes a reference of and must give back
    donor = Sequence(prompt_tokens=held + [1], sampling=SamplingParams())
    bm.allocate(donor)
    donor.num_computed = len(donor.prompt_tokens)
    bm.register_full_pages(donor)
    bm.free_sequence(donor)
    before = dict(admit_stats(eng))
    free_before, cached_before = bm.num_free, bm.num_cached_pages
    real, calls = bm._pop_free_page, []

    def dry_after_one():
        calls.append(1)
        if len(calls) > 1:
            raise AllocationError("KV page pool exhausted")
        return real()

    monkeypatch.setattr(bm, "_pop_free_page", dry_after_one)
    out = schedule(eng)
    monkeypatch.undo()
    assert out.prefill == [] and list(eng.scheduler.waiting) == [seq]
    assert not seq.block_table
    assert (bm.num_free, bm.num_cached_pages) == (free_before, cached_before)
    st = admit_stats(eng)
    grown = {k: st[k] - before[k] for k in ADMIT_COUNTS}
    assert grown == {
        "admit_attempts": 1, "admit_rollbacks": 1,
        "admit_tokens": 17, "admit_blocks_hit": 0, "admit_pages": 1,
        "admit_evictions": 0, "admit_ahead": 0}
    assert 0 < st["admit_rollback_s"] - before["admit_rollback_s"] < st["admit_s"]
    assert eng._open_parts == 0
    # the next step hashes the prompt again, and admits
    assert schedule(eng).prefill == [seq]
    st = admit_stats(eng)
    assert st["admit_attempts"] - before["admit_attempts"] == 2
    assert st["admit_tokens"] - before["admit_tokens"] == 34
    assert st["admit_blocks_hit"] - before["admit_blocks_hit"] == 2


def test_a_suffix_over_the_budget_counts_one_rollback_after_its_admission():
    eng = engine_of(scheduler=SchedulerConfig(
        max_prefill_batch=4, max_prefill_tokens=20))
    eng.obs_step_timing = True
    a = eng.add_request(prompt_of(6, 12), SamplingParams(max_new_tokens=2))
    b = eng.add_request(prompt_of(7, 12), SamplingParams(max_new_tokens=2))
    free = eng.block_manager.num_free
    out = schedule(eng)
    assert out.prefill == [a] and list(eng.scheduler.waiting) == [b]
    assert not b.block_table and eng.block_manager.num_free == free - 3
    st = eng.step_stats
    # both were hashed, walked and given pages; the second gave them back
    assert st["admit_attempts"] == 2
    assert st["admit_rollbacks"] == 1 and st["admit_pages"] == 6
    assert st["admit_tokens"] == 24 and st["admit_rollback_s"] > 0
    assert st["admit_attempts"] - st["admit_rollbacks"] == len(out.prefill)


def test_a_chunked_step_without_budget_rolls_back_too():
    eng = engine_of(scheduler=SchedulerConfig(
        max_prefill_batch=4, chunked_prefill_tokens=8))
    eng.obs_step_timing = True
    a = eng.add_request(prompt_of(8, 6), SamplingParams(max_new_tokens=2))
    b = eng.add_request(prompt_of(9, 12), SamplingParams(max_new_tokens=2))
    out = schedule(eng)
    assert out.prefill == [a] and list(eng.scheduler.waiting) == [b]
    st = eng.step_stats
    assert (st["admit_attempts"], st["admit_rollbacks"]) == (2, 1)


def test_evictions_are_the_pops_the_free_list_did_not_serve():
    eng = engine_of(total_pages=9)  # eight pages to give
    bm = eng.block_manager
    donor = Sequence(prompt_tokens=prompt_of(10, 21), sampling=SamplingParams())
    bm.allocate(donor)
    donor.num_computed = len(donor.prompt_tokens)
    bm.register_full_pages(donor)
    bm.free_sequence(donor)  # five cached and evictable, one back, two free
    assert (bm.num_cached_pages, len(bm._free)) == (5, 3)
    eng.obs_step_timing = True
    seq = eng.add_request(prompt_of(11, 20), SamplingParams(max_new_tokens=2))
    assert schedule(eng).prefill == [seq]
    st = eng.step_stats
    assert (st["admit_pages"], st["admit_evictions"]) == (5, 2)
    assert bm.num_cached_pages == 3


# -- a phase may not start inside a child -------------------------------------------
def test_a_phase_entered_inside_an_open_child_fails_loudly():
    eng = engine_of()
    eng.obs_step_timing = True
    with eng.phase("schedule"):
        with eng.part(seq=1, tokens=4):
            with pytest.raises(AssertionError, match="decode_fetch.*admit"):
                with eng.phase("decode_fetch"):
                    pass
            with eng.part("hash"):  # children nest
                assert eng._open_parts == 2
        with eng.phase("decode_fetch"):  # outside a child: the drain's case
            pass
    assert eng._open_phase is None and eng._open_parts == 0
    assert eng.step_stats["admit_s"] >= eng.step_stats["admit_hash_s"] > 0


# -- the second pools report their own part -------------------------------------------
def second_pool_engines():
    swa = served_path.ONE_OF_EACH_SWA
    kda = dataclasses.replace(TINY_LING_HYBRID, n_layers=3)
    return {
        "window": lambda: served_path.make_engine(
            swa, served_path.params_of(swa, 43),
            BlockManagerConfig(total_pages=96, page_size=PS, window_pages=48),
            max_model_len=160),
        "state": lambda: served_path.make_engine(
            kda, served_path.params_of(kda, 47),
            BlockManagerConfig(total_pages=128, page_size=PS,
                               state_snapshot_tokens=8,
                               state_snapshot_slots=16)),
    }


@pytest.mark.parametrize("pool", ["window", "state"])
def test_a_model_with_a_second_pool_reports_that_pools_part(pool):
    eng = second_pool_engines()[pool]()
    assert getattr(eng.block_manager, pool) is not None
    eng.obs_step_timing = True
    seq = eng.add_request(prompt_of(12, 21), SamplingParams(max_new_tokens=2))
    assert schedule(eng).prefill == [seq]
    st = eng.step_stats
    other = "state" if pool == "window" else "window"
    assert st[f"admit_{pool}_s"] > 0 and st[f"admit_{other}_s"] == 0
    assert sum(st[key] for key in PART_KEYS) <= st["admit_s"] <= st["schedule_s"]
    assert (st["admit_attempts"], st["admit_rollbacks"], st["admit_pages"]) == (1, 0, 6)


def test_a_state_pool_out_of_slots_rolls_back_inside_its_part():
    kda = dataclasses.replace(TINY_LING_HYBRID, n_layers=3)
    eng = served_path.make_engine(
        kda, served_path.params_of(kda, 47),
        BlockManagerConfig(total_pages=128, page_size=PS,
                           state_snapshot_tokens=8, state_snapshot_slots=16))
    eng.obs_step_timing = True
    seq = eng.add_request(prompt_of(13, 9), SamplingParams(max_new_tokens=2))

    def none_left():
        raise AllocationError("state pool exhausted")

    eng.block_manager.state.pop = none_left
    assert schedule(eng).prefill == [] and not seq.block_table
    st = eng.step_stats
    assert (st["admit_attempts"], st["admit_rollbacks"]) == (1, 1)
    assert st["admit_state_s"] > 0 and st["admit_pages"] == 0
    assert eng._open_parts == 0


# -- the spans on the profiler's clock ---------------------------------------------
def test_a_profiler_capture_holds_the_children_inside_engine_schedule(tmp_path):
    from jax.profiler import ProfileData

    eng = engine_of(scheduler=SchedulerConfig(
        max_prefill_batch=4, max_prefill_tokens=20))
    eng.obs_step_timing = True
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark's traced run
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        a = eng.add_request(prompt_of(14, 12), SamplingParams(max_new_tokens=2))
        b = eng.add_request(prompt_of(15, 12), SamplingParams(max_new_tokens=2))
        schedule(eng)
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(str(pb)).planes
        for line in plane.lines for ev in line.events
        if ev.name.split(".")[0] in ("admit", "engine")
    ]
    (outer,) = [e for e in events if e[0] == "engine.schedule"]
    children = [e for e in events if e[0].startswith("admit")]
    assert sorted({e[0] for e in children}) == [
        "admit", "admit.hash", "admit.pages", "admit.rollback", "admit.walk"]
    for name, start, end, stats in children:
        assert outer[1] <= start <= end <= outer[2]
        assert stats["replica"] == eng.replica and stats["step"] == outer[3]["step"]
    whole = sorted(e for e in children if e[0] == "admit")
    assert [e[3]["seq"] for e in whole] == [a.seq_id, b.seq_id]
    assert [e[3]["tokens"] for e in whole] == [12, 12]
    # the scheduler's roll-back follows the span of the admission it undoes
    (undo,) = [e for e in children if e[0] == "admit.rollback"]
    assert undo[3]["seq"] == b.seq_id and undo[1] >= whole[1][2]
    for name, start, end, _ in children:
        if name not in ("admit", "admit.rollback"):
            assert any(w[1] <= start and end <= w[2] for w in whole)


# -- an admission ahead ----------------------------------------------------------------
def test_an_admission_ahead_lies_in_the_step_of_the_burst_it_follows(tmp_path):
    """Two lanes, both five tokens from their end; two wait, and the batch's
    budget holds one of them: the first is admitted behind the burst that
    ends the lanes (``ahead=1``, inside a second ``engine.schedule`` of that
    step, between the burst's dispatch and its fetch), the second is walked
    there too and rolled back, and admitted by a step of its own
    (``ahead=0``)."""
    from jax.profiler import ProfileData

    eng = engine_of(decode_batch_size=2, scheduler=SchedulerConfig(
        max_prefill_batch=4, max_prefill_tokens=20))
    short = eng.add_request(prompt_of(20, 9), SamplingParams(max_new_tokens=5))
    eng.add_request(prompt_of(21, 9), SamplingParams(max_new_tokens=5))
    eng.step()
    c = eng.add_request(prompt_of(22, 12), SamplingParams(max_new_tokens=2))
    d = eng.add_request(prompt_of(23, 12), SamplingParams(max_new_tokens=2))
    eng.obs_step_timing = True
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark's traced run
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        while not short.is_finished():
            eng.step()
        went_ahead = eng._step_count - 1
        assert eng._prefill_ahead["seqs"] == [c]
        eng.run_until_complete()
    finally:
        jax.profiler.stop_trace()
    st = eng.step_stats
    assert (st["admit_attempts"], st["admit_rollbacks"], st["admit_ahead"]) == (3, 1, 1)
    assert st["admit_attempts"] - st["admit_rollbacks"] == 2  # c and d
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(str(pb)).planes
        for line in plane.lines for ev in line.events
        if ev.name.split(".")[0] in ("admit", "engine")
    )
    whole = [e for e in events if e[2] == "admit"]
    assert [(e[3]["seq"], e[3]["ahead"]) for e in whole] == [
        (c.seq_id, 1), (d.seq_id, 1), (d.seq_id, 0)]
    (undo,) = [e for e in events if e[2] == "admit.rollback"]
    assert undo[3]["seq"] == d.seq_id and undo[3]["step"] == went_ahead
    ahead, rolled, later = whole
    assert ahead[3]["step"] == rolled[3]["step"] == went_ahead < later[3]["step"]
    # inside the second ``engine.schedule`` of its step, which follows the
    # burst's dispatch; the prefill's build, upload and dispatch follow it,
    # and the burst's fetch follows them
    step = [e for e in events
            if e[2].startswith("engine.") and e[3]["step"] == went_ahead]
    names = [e[2][len("engine."):] for e in step]
    assert names[:4] == ["schedule", "decode_build", "decode_put", "decode_dispatch"]
    at = names.index("schedule", 1)
    assert names[at:at + 5] == [
        "schedule", "prefill_build", "prefill_put", "prefill_dispatch",
        "prefill_build"]  # (the last: the sampler's inputs)
    assert names.index("decode_dispatch") < at < names.index("decode_fetch", at)
    outer = step[at]
    assert outer[0] <= ahead[0] and rolled[1] <= undo[1] <= outer[1]
    # the step after commits that prefill before it schedules
    after = [e[2][len("engine."):] for e in events
             if e[2].startswith("engine.") and e[3]["step"] == went_ahead + 1]
    assert after[:3] == ["prefill_fetch", "prefill_commit", "schedule"]
