"""The engine loop's phases (``Engine.phase``, ``STEP_PHASES``) and the
queue wait measured from the door (``queue_s``, ``staged_s``).

Off (the default) the primitive is one shared do-nothing object and
``step_stats`` stays at zero. On, the phases tile the loop's time: they sum
to the step's wall time, the old keys are sums of the new ones, every fetch
of sampled tokens sits inside a ``*_fetch`` phase, and a profiler capture
shows them as host events that never contain one another.
"""

import ast
import inspect
import textwrap
import time

import jax
import numpy as np
import pytest
from test_observability import _GateHolder

from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server import engine as engine_mod
from llm_d_kv_cache_manager_tpu.server.engine import NO_PHASE, STEP_PHASES
from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

PS = 4
IN_STEP = [p for p in STEP_PHASES if p != "loop"]
PARTS = ("build", "put", "dispatch", "fetch", "commit")


def _engine_cfg(total_pages=64, **kw):
    kw.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    kw.setdefault("decode_batch_size", 4)
    return EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(total_pages=total_pages, page_size=PS),
        max_model_len=64,
        prefill_bucket=8,
        interpret=True,
        **kw,
    )


def _prompt(seed, n):
    return list(
        map(int, np.random.default_rng(seed).integers(0, TINY_LLAMA.vocab_size, n))
    )


def _pod(pod_id="phase-pod", **kw):
    return PodServer(PodServerConfig(
        model_name="tiny-llama", pod_identifier=pod_id, publish_events=False,
        engine=_engine_cfg(), **kw,
    ))


def _run(eng, prompts, max_new_tokens=4):
    seqs = [
        eng.add_request(p, SamplingParams(max_new_tokens=max_new_tokens))
        for p in prompts
    ]
    eng.run_until_complete()
    return seqs


# -- the primitive ------------------------------------------------------------
def test_the_names_are_fixed_in_one_tuple():
    assert STEP_PHASES == (
        "schedule",
        "prefill_build", "prefill_put", "prefill_dispatch", "prefill_fetch",
        "prefill_commit",
        "decode_build", "decode_put", "decode_dispatch", "decode_fetch",
        "decode_commit",
        "publish", "loop",
    )
    assert len(set(STEP_PHASES)) == 13


def test_off_hands_out_the_one_shared_object_and_counts_nothing(monkeypatch):
    eng = Engine(_engine_cfg())
    assert not eng.obs_step_timing
    assert all(eng.phase(name) is NO_PHASE for name in STEP_PHASES)
    assert not hasattr(NO_PHASE, "__dict__")  # nothing to allocate into
    # no clock is read for a phase: perf_counter may not be called at all
    # on the default path (the prefill-rate sample aside, which predates
    # the phases and feeds the recompute-vs-restore model)
    calls = []
    real = time.perf_counter
    monkeypatch.setattr(
        engine_mod.time, "perf_counter", lambda: calls.append(1) or real()
    )
    _run(eng, [_prompt(1, 10)], max_new_tokens=5)
    monkeypatch.undo()
    assert len(calls) == 2 * eng.prefill_stats["dispatches"]
    assert all(v == 0 for v in eng.step_stats.values())


@pytest.mark.parametrize("kw", [
    {}, {"decode_batch_size": 3},
    {"decode_batch_size": 2, "total_pages": 10, "decode_steps_per_iter": 2},
    {"spec_decode": "prompt_lookup"},
    {"scheduler": SchedulerConfig(max_prefill_batch=4, chunked_prefill_tokens=8)},
], ids=["plain", "ahead", "ahead-tight-pool", "spec", "chunked"])
def test_on_the_phases_tile_the_step(kw, monkeypatch):
    eng = Engine(_engine_cfg(**kw))
    _run(eng, [_prompt(2, 9)], max_new_tokens=2)  # compile outside the clock
    eng.obs_step_timing = True
    opened = {"entered": 0, "spans": 0}
    for key, method in (("entered", "__enter__"), ("spans", "_start")):
        def counted(self, *, _real=getattr(engine_mod._Phase, method), _key=key):
            opened[_key] += 1
            return _real(self)
        monkeypatch.setattr(engine_mod._Phase, method, counted)
    st = eng.step_stats
    # an echoing prompt, so that prompt lookup has something to propose
    echo = _prompt(3, 6) * 3
    # The phases cover the steps' wall time (what is left is adding the
    # requests, the counters and the glue between two phases) and none is
    # counted twice. The share is of some ten milliseconds on a CPU that
    # five other workers and the "device" itself keep busy: one pass in
    # six read under 0.8 for a thread that was not scheduled between two
    # phases, so the best of three passes is held to it, each to the wall.
    shares = []
    while len(shares) < 3 and max(shares, default=0.0) <= 0.8:
        before = sum(st[f"{p}_s"] for p in IN_STEP)
        t0 = time.perf_counter()
        seqs = _run(
            eng, [_prompt(4, 14), echo, _prompt(5, 7)], max_new_tokens=6
        )
        wall = time.perf_counter() - t0
        assert all(s.num_generated == 6 for s in seqs)
        shares.append((sum(st[f"{p}_s"] for p in IN_STEP) - before) / wall)
        assert shares[-1] <= 1.0
    assert max(shares) > 0.8, shares
    assert st["steps"] > 0 and st["loop_s"] == 0.0  # no serving loop here
    phases = sum(st[f"{p}_s"] for p in IN_STEP)
    old = st["schedule_s"] + st["prefill_s"] + st["decode_s"] + st["publish_s"]
    assert phases == pytest.approx(old, rel=1e-9)
    assert st["prefill_s"] == pytest.approx(
        sum(st[f"prefill_{part}_s"] for part in PARTS)
    )
    assert st["decode_s"] == pytest.approx(
        sum(st[f"decode_{part}_s"] for part in PARTS)
    )
    assert st["sample_s"] == pytest.approx(
        st["prefill_fetch_s"] + st["decode_fetch_s"]
    )
    assert st["sample_s"] > 0 and st["prefill_dispatch_s"] > 0
    assert all(st[f"{half}_put_s"] > 0 for half in ("prefill", "decode"))
    assert eng._open_phase is None
    # one span a phase: only the burst in flight, drained where it stands
    # (a reservation out of pages), ever suspends the phase it lands in
    # and so opens a span more; a burst that is chained from suspends none
    assert opened["spans"] >= opened["entered"] > 0
    if "total_pages" in kw:
        assert st["decode_chained_dispatches"] > 0
        assert opened["spans"] > opened["entered"]
    else:
        assert opened["spans"] == opened["entered"]
        assert (st["decode_chained_dispatches"] > 0) == (
            kw.get("decode_batch_size") == 3
        )


def test_decode_rows_are_the_lanes_that_ran():
    eng = Engine(_engine_cfg())
    _run(eng, [_prompt(6, 9)], max_new_tokens=2)
    eng.obs_step_timing = True
    # three requests prefilled together, then decoded in lockstep: every
    # decode dispatch carries three real lanes of the four
    _run(eng, [_prompt(7, 9), _prompt(8, 9), _prompt(9, 9)], max_new_tokens=5)
    st = eng.step_stats
    assert st["decode_dispatches"] == 4  # the first token comes from prefill
    assert st["decode_rows"] / st["decode_dispatches"] == 3.0
    # and alone: one lane
    before = dict(st)
    _run(eng, [_prompt(10, 9)], max_new_tokens=3)
    assert st["decode_rows"] - before["decode_rows"] == (
        st["decode_dispatches"] - before["decode_dispatches"]
    ) == 2


SAMPLED = dict(temperature=0.8, top_k=8)


@pytest.mark.parametrize(
    "kw",
    [{}, dict(spec_decode="prompt_lookup", spec_k=4, spec_ngram=2)],
    ids=["fused", "spec"],
)
def test_sampled_dispatches_are_those_with_a_sampled_lane(kw):
    """``decode_sampled_dispatches`` counts the dispatches in which the
    sampler's gate runs its vocabulary filter: those that carry a lane with
    ``temperature > 0``, and no dispatch of greedy lanes."""
    eng = Engine(_engine_cfg(**kw))
    eng.obs_step_timing = True
    st = eng.step_stats
    _run(eng, [_prompt(20, 9), _prompt(21, 9)], max_new_tokens=6)
    assert st["decode_dispatches"] > 0
    assert st["decode_sampled_dispatches"] == 0
    # one sampled lane beside a greedy one that outlives it: every dispatch
    # while it runs is counted, those after it has finished are not
    before = dict(st)
    eng.add_request(_prompt(22, 9), SamplingParams(max_new_tokens=12))
    sampled = eng.add_request(
        _prompt(23, 9), SamplingParams(max_new_tokens=4, **SAMPLED)
    )
    eng.run_until_complete()
    assert len(sampled.generated_tokens) == 4
    took = st["decode_dispatches"] - before["decode_dispatches"]
    took_sampled = (
        st["decode_sampled_dispatches"] - before["decode_sampled_dispatches"]
    )
    assert 0 < took_sampled < took
    if not kw:  # one token a dispatch, the first from the prefill
        assert (took, took_sampled) == (11, 3)
    # and alone: every dispatch
    before = dict(st)
    eng.add_request(_prompt(24, 9), SamplingParams(max_new_tokens=5, **SAMPLED))
    eng.run_until_complete()
    assert (
        st["decode_sampled_dispatches"] - before["decode_sampled_dispatches"]
        == st["decode_dispatches"] - before["decode_dispatches"] > 0
    )


def test_sampled_dispatches_are_in_stats_with_the_switch_on():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    def stats_after(server, requests):
        server.start()

        async def scenario():
            async with TestClient(TestServer(server.build_app())) as c:
                for extra in requests:
                    resp = await c.post("/v1/completions", json={
                        "prompt_token_ids": _prompt(25, 10), "max_tokens": 4,
                        **extra,
                    })
                    assert resp.status == 200
                return await (await c.get("/stats")).json()

        try:
            return asyncio.run(scenario())
        finally:
            server.shutdown()

    on = stats_after(
        _pod("sampled-pod", obs_metrics=True), [{}, {"temperature": 0.9}]
    )["obs"]["step_stats"]
    assert on["decode_dispatches"] == 6 and on["decode_sampled_dispatches"] == 3
    # off: the counter is not fed and /stats has no such block
    assert "obs" not in stats_after(_pod("quiet-pod"), [{"temperature": 0.9}])


def test_a_burst_drained_ahead_of_a_prefill_is_decode_time():
    """What the rule cannot foresee, here a deadline that passes, frees a
    lane while a burst is in flight; the prefill of the one who waited
    first commits that burst: its fetch and commit come between
    ``schedule`` and ``prefill_build`` and are booked to ``decode_s`` and
    ``sample_s``, not to ``prefill_s``."""
    eng = Engine(_engine_cfg(decode_batch_size=1))
    _run(eng, [_prompt(19, 9)], max_new_tokens=2)
    eng.obs_step_timing = True
    first = eng.add_request(_prompt(20, 9), SamplingParams(max_new_tokens=24))
    eng.add_request(_prompt(21, 9), SamplingParams(max_new_tokens=2))
    while eng._inflight is None:
        eng.step()
    first.deadline = 0.0  # long past: the lane leaves at the next step
    eng.step()
    assert first.finish_reason == "deadline" and eng._inflight is not None
    names, real = [], eng.phase
    eng.phase = lambda name: names.append(name) or real(name)
    before = dict(eng.step_stats)
    eng.step()
    took = {k: v - before[k] for k, v in eng.step_stats.items()}
    assert names[:5] == [
        "schedule", "decode_fetch", "decode_commit", "prefill_build",
        "prefill_put",
    ]
    assert took["decode_fetch_s"] > 0 and took["prefill_fetch_s"] > 0
    assert took["prefill_s"] == pytest.approx(
        sum(took[f"prefill_{part}_s"] for part in PARTS)
    )
    assert took["decode_s"] == pytest.approx(
        sum(took[f"decode_{part}_s"] for part in PARTS)
    )
    assert took["sample_s"] == pytest.approx(
        took["decode_fetch_s"] + took["prefill_fetch_s"]
    )
    eng.run_until_complete()


def test_a_chained_step_fetches_the_burst_after_the_next_dispatch():
    """With lanes full the phases of a step follow one another in a new
    order and still never nest: ``decode_dispatch`` of burst N+1, then
    ``decode_fetch`` and ``decode_commit`` of burst N."""
    eng = Engine(_engine_cfg(decode_batch_size=1))
    _run(eng, [_prompt(19, 9)], max_new_tokens=2)
    eng.obs_step_timing = True
    eng.add_request(_prompt(22, 9), SamplingParams(max_new_tokens=24))
    while eng._inflight is None:
        eng.step()
    names, real = [], eng.phase
    eng.phase = lambda name: names.append(name) or real(name)
    before = dict(eng.step_stats)
    eng.step()
    assert names == [
        "schedule", "decode_build", "decode_put", "decode_dispatch",
        "decode_fetch", "decode_commit", "publish",
    ]
    assert eng.step_stats["decode_chained_dispatches"] == (
        before["decode_chained_dispatches"] + 1
    )
    eng.run_until_complete()


def test_a_phase_opened_inside_another_suspends_it():
    eng = Engine(_engine_cfg())
    eng.obs_step_timing = True
    with eng.phase("decode_build"):
        time.sleep(0.02)
        with eng.phase("decode_fetch"):
            time.sleep(0.05)
        time.sleep(0.02)
    st = eng.step_stats
    assert 0.04 <= st["decode_build_s"] < 0.05 + 0.03
    assert 0.05 <= st["decode_fetch_s"] < 0.05 + 0.03
    assert st["decode_s"] == pytest.approx(
        st["decode_build_s"] + st["decode_fetch_s"]
    )
    assert st["sample_s"] == st["decode_fetch_s"]
    assert eng._open_phase is None


# -- every fetch of sampled tokens is inside a *_fetch phase --------------------
#: methods of Engine that move KV pages or measure at start-up, not tokens:
#: their ``np.asarray`` of a device array belongs to the page movers
#: (``gather_s``, a slice inside the build phases) or to no step at all
PAGE_MOVERS = {"__init__", "_flush_page_moves", "export_kv_blocks"}


def _fetch_sites():
    """(method, line, inside a ``*_fetch`` phase?) of every one-argument
    ``np.asarray(x)`` in Engine outside the page movers: with no dtype it
    is a device array coming to the host."""
    tree = ast.parse(textwrap.dedent(inspect.getsource(Engine)))
    sites = []

    def is_fetch_phase(item):
        call = item.context_expr
        return (
            isinstance(call, ast.Call)
            and isinstance(call.func, ast.Attribute)
            and call.func.attr == "phase"
            and call.args
            and isinstance(call.args[0], ast.Constant)
            and str(call.args[0].value).endswith("_fetch")
        )

    def walk(node, method, inside):
        if isinstance(node, ast.With):
            inside = inside or any(map(is_fetch_phase, node.items))
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "asarray"
            and len(node.args) == 1
            and not node.keywords
        ):
            sites.append((method, node.lineno, inside))
        for child in ast.iter_child_nodes(node):
            walk(child, method, inside)

    for fn in tree.body[0].body:
        if isinstance(fn, ast.FunctionDef) and fn.name not in PAGE_MOVERS:
            walk(fn, fn.name, False)
    return sites


def test_every_fetch_site_sits_inside_a_fetch_phase():
    sites = _fetch_sites()
    assert {m for m, _, _ in sites} == {
        "_commit_prefill", "_commit_burst", "_run_decode_spec", "_commit_block"
    }
    assert all(inside for _, _, inside in sites), sites


# -- the spans on the profiler's clock ---------------------------------------------
def test_a_profiler_capture_holds_the_phases_and_none_contains_another(tmp_path):
    from jax.profiler import ProfileData

    eng = Engine(_engine_cfg())
    _run(eng, [_prompt(11, 9)], max_new_tokens=2)
    eng.obs_step_timing = True
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark's traced run
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _run(eng, [_prompt(12, 9), _prompt(13, 11)], max_new_tokens=4)
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = [
        ev for plane in ProfileData.from_file(str(pb)).planes
        for line in plane.lines for ev in line.events
        if ev.name.startswith("engine.")
    ]
    names = {ev.name for ev in events}
    assert names == {f"engine.{p}" for p in IN_STEP}
    assert "engine.step" not in names
    steps = set()
    for ev in events:
        stats = dict(ev.stats)
        assert stats["replica"] == eng.replica == "cpu:0"
        steps.add(stats["step"])
    assert len(steps) == eng.step_stats["steps"]  # one identifier a step
    spans = sorted((ev.start_ns, ev.start_ns + ev.duration_ns) for ev in events)
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))


# -- queue wait, from the door -----------------------------------------------------
def test_the_response_carries_queue_wait_with_everything_off():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    server = _pod()
    server.start()

    async def scenario():
        async with TestClient(TestServer(server.build_app())) as c:
            resp = await c.post(
                "/v1/completions",
                json={"prompt_token_ids": _prompt(14, 10), "max_tokens": 3},
            )
            return await resp.json()

    try:
        data = asyncio.run(scenario())
    finally:
        server.shutdown()
    assert not server.engine.obs_step_timing
    assert all(v == 0 for v in server.engine.step_stats.values())
    assert 0.0 <= data["staged_s"] <= data["queue_s"]
    assert data["queue_s"] - data["staged_s"] <= data["ttft_s"]


def test_a_request_held_staged_shows_the_wait_in_both():
    server = _pod("queue-pod", obs_tracing=True)
    holder = _GateHolder(server)  # holds the loop before its next step
    holder.install()
    server.start()
    try:
        holder.gate.clear()
        # the first request takes the loop to the gate; the second arrives
        # while the loop is held there and sits staged until it opens
        first = server.submit(_prompt(15, 8), SamplingParams(max_new_tokens=2))
        time.sleep(0.1)
        fut = server.submit(_prompt(16, 8), SamplingParams(max_new_tokens=2))
        time.sleep(0.25)
        holder.gate.set()
        seq = fut.result(timeout=120)
        first.result(timeout=120)
    finally:
        holder.gate.set()
        server.shutdown()
    assert seq.staged_s >= 0.2
    assert seq.staged_s <= seq.queue_s
    assert seq.queue_s == seq.prefill_start_time - seq.submit_time
    assert seq.staged_s == seq.arrival_time - seq.submit_time
    assert seq.queue_s - seq.staged_s <= seq.ttft  # ttft_s itself is unchanged
    assert seq.ttft == seq.first_token_time - seq.arrival_time
    # the ring and the response cannot disagree: one stamp
    (trace,) = server.tracer.traces(request_id=fut.request_id)
    queue = next(s for s in trace["spans"] if s["name"] == "pod.queue")
    assert queue["duration_s"] == pytest.approx(seq.queue_s, abs=1e-5)


def test_a_sequence_added_to_the_engine_directly_was_never_staged():
    eng = Engine(_engine_cfg())
    (seq,) = _run(eng, [_prompt(17, 9)], max_new_tokens=2)
    assert seq.submit_time == seq.arrival_time and seq.staged_s == 0.0
    assert seq.queue_s == seq.prefill_start_time - seq.arrival_time


def test_the_serving_loop_times_what_lies_between_two_steps():
    server = _pod("loop-pod")
    server.engine.obs_step_timing = True  # the one switch, as run.py sets it
    server.start()
    try:
        t0 = time.perf_counter()
        server.submit(
            _prompt(18, 8), SamplingParams(max_new_tokens=8)
        ).result(timeout=120)
        wall = time.perf_counter() - t0
        time.sleep(0.3)  # parked: an idle wait is not loop time
        st = dict(server.engine.step_stats)
    finally:
        server.shutdown()
    assert st["steps"] >= 8 and 0.0 < st["loop_s"] < wall
    total = sum(st[f"{p}_s"] for p in STEP_PHASES)
    assert total <= wall * 1.02
    assert st["loop_s"] < 0.25  # the 0.3 s of idleness is in no phase


# -- what a prefill dispatch counts --------------------------------------------
@pytest.mark.parametrize("n_seqs, rows, tp", [
    (1, 1, 1), (2, 2, 1), (3, 3, 1), (5, 5, 1), (8, 8, 1), (1, 8, 2),
], ids=["1", "2", "3", "5", "8", "tp2:1->8"])
def test_a_prefill_dispatch_counts_the_slots_of_the_rows_it_computed(n_seqs, rows, tp):
    """``token_slots`` grows by the rows ``llama.prefill_packed`` computed
    (as many as hold an admitted sequence; under a mesh the one body of the
    operand's 8) times the bucketed width; ``tokens_computed`` and
    ``dispatches`` are what they were."""
    eng = Engine(_engine_cfg(
        scheduler=SchedulerConfig(max_prefill_batch=8), decode_batch_size=8,
        tp=tp,
    ))
    prompts = [_prompt(40 + i, 5 + i) for i in range(n_seqs)]
    for p in prompts:
        eng.add_request(p, SamplingParams(max_new_tokens=2))
    eng.step()
    chunk = -(-len(prompts[-1]) // 8) * 8  # the longest, in buckets of 8
    assert eng.prefill_stats == {
        "tokens_computed": sum(map(len, prompts)), "dispatches": 1,
        "token_slots": rows * chunk,
    }
