"""The readers of the admission's spans (``chipbench/admit_times.py``;
``admit_ms.*``, ``admit_tokens_mean``, ``admit_retry_share``,
``admit_step_idle_ms.*``) on planes made by hand and on one capture recorded
here: CPU, no chip, nothing here is a measurement.
"""

import importlib.util
import json
import os
import re

import pytest

from chipbench import admit_times, run, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
FAMILIES = ("admit_ms", "admit_tokens_mean", "admit_retry_share",
            "admit_step_idle_ms")
#: the nine entries, looked up in ``BENCHMARK.json`` by NAME (they waited in
#: ``chipbench/admit_entries.json`` from PR 52 until PR 55 appended them)
NEW = [m for m in BENCH["per_layer"]
       if m["name"].split(".")[0] in FAMILIES]
#: the five cells PR 52 read them in; a ``benchmark`` PR adds a cell whose
#: traced run on the chip prints a number for them
CELLS = ["qwen3-32b.sessions", "qwen3-30b-a3b.reasoning", "sdar-30b-a3b.blockgen",
         "kanana-2-30b-a3b.docqa", "lfm2-8b-a1b.agentloop"]
MS_PARTS = ("hash", "walk", "window", "state", "pages", "rollback", "other")
IDLE_PHASES = ("schedule", "prefill_build", "prefill_put", "rest")


def records(**kw):
    base = dict(
        cell=BENCH["workloads"][0], good=[], failed=[], in_flight=[],
        in_flight_tokens=0, late_s=[], window_s=10.0, stats_before=[],
        stats_after=[], running_samples=[], lanes=16, page=16, pods=[object()],
        step_before=[], step_after=[], compiles_in_window=0,
        memory_peak_bytes=0, model_cfg=None, peaks={}, trace=None,
    )
    base.update(kw)
    return run.RunRecords(**base)


def read(name, rec):
    return run.load_layer_metric(name)(rec)


def traced(planes, monkeypatch):
    """Records of a traced run whose trace directory holds ``planes``."""
    loads = []
    monkeypatch.setattr(admit_times, "load", lambda: loads.append(1) or planes)
    return records(trace=trace_reduce.reduce(planes)), loads


# -- the entries ------------------------------------------------------------------
def test_the_entries():
    names = [m["name"] for m in NEW]
    assert names == (
        [f"admit_ms.{p}" for p in ("hash", "walk", "pages", "other")]
        + ["admit_tokens_mean"]
        + [f"admit_step_idle_ms.{p}" for p in IDLE_PHASES])
    layers = {"admit_ms": "block manager", "admit_tokens_mean": "block manager",
              "admit_step_idle_ms": "device"}
    units = {"admit_tokens_mean": "tokens"}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        section = f.read().split("## 3. Layers")[1].split("\n## 4.")[0]
    perf_layers = set(re.findall(r"^\| ([a-z+/ ]+) \| ", section, re.M))
    for m in NEW:
        family = m["name"].split(".")[0]
        assert {**m, "workloads": CELLS} == {
            "name": m["name"], "unit": units.get(family, "ms"), "better": "lower",
            "source": "device_trace", "layer": layers[family],
            "moves": "out_tokens_per_s", "workloads": CELLS}
        assert set(m["workloads"]) >= set(CELLS)
        assert m["layer"] in perf_layers
        assert callable(run.load_layer_metric(m["name"]))
        assert f"`{m['name']}`" in section or f"`{family}.*`" in section
    # the family files serve what has no entry yet: the parts of a second
    # pool, and the roll-backs, which no cell's traffic reaches (PERF.md 6)
    for name in ("admit_ms.window", "admit_ms.state", "admit_ms.rollback",
                 "admit_retry_share"):
        assert callable(run.load_layer_metric(name))


def test_benchmark_json_reads_the_entries_in_the_cells_they_list():
    """The entries stand in ``BENCHMARK.json`` itself since PR 55: a traced
    run reads all nine in each of the five cells, and in any other cell
    those that list it. The file they waited in stays, read by nothing,
    while ``docs/observability.md`` names it (a ``benchmark`` PR may not
    edit the document, ``PERF.md`` section 7): until it goes, what it holds
    is what ``BENCHMARK.json`` holds, but for a wider ``workloads``."""
    waited = os.path.join(ROOT, "chipbench", "admit_entries.json")
    if os.path.exists(waited):
        assert [{**m, "workloads": CELLS} for m in NEW] == json.load(
            open(waited))["per_layer"]
    bench = run.load_benchmark()
    assert len(NEW) == 9
    ends = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        here = [m["name"] for m in run.metrics_of_cell(bench["per_layer"], cell["name"])
                if m["name"].split(".")[0] in FAMILIES]
        assert here == [m["name"] for m in NEW if cell["name"] in m["workloads"]]
        assert cell["name"] not in CELLS or len(here) == 9
    assert {m["moves"] for m in NEW} <= ends


# What ``test_chipbench.py`` asks of every entry of ``BENCHMARK.json``, asked
# of each of the nine by name.
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
by_name = pytest.mark.parametrize("m", NEW, ids=lambda m: m["name"])


@by_name
def test_an_entrys_name_keeps_the_character_rules(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert "roofline" not in m["name"] and "mfu" not in m["name"]


@by_name
def test_an_entry_is_a_per_layer_entry(m):
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    cells = {w["name"] for w in BENCH["workloads"]}
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved.get("workloads", cells)) <= cells
    assert m["layer"] in {e["layer"] for e in BENCH["per_layer"]}


@by_name
def test_an_entry_has_a_reader(m):
    """By its own file, or by the file of the part before the first dot."""
    assert callable(run.load_layer_metric(m["name"]))
    with pytest.raises(run.BenchFailure, match="no reader"):
        run.load_layer_metric("no_such_metric." + m["name"])


def test_the_parts_are_the_programs():
    from llm_d_kv_cache_manager_tpu.server.engine import ADMIT_PARTS, STEP_PHASES

    assert MS_PARTS == ADMIT_PARTS + (admit_times.OTHER,)
    assert set(admit_times.STEP_PHASES_NAMED) < set(STEP_PHASES)
    assert IDLE_PHASES == admit_times.STEP_PHASES_NAMED + (admit_times.REST,)


# -- planes made by hand ---------------------------------------------------------------
def ev(name, start_us, dur_us, **stats):
    text = " ".join([name] + [f"{k}={v}" for k, v in stats.items()])
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3,
            "text": text}


def phase(name, step, start_us, dur_us, replica="tpu:0"):
    return ev(f"engine.{name}", start_us, dur_us, step=step, replica=replica)


def part(name, step, start_us, dur_us, replica="tpu:0", **stats):
    name = f"admit.{name}" if name else "admit"
    return ev(name, start_us, dur_us, step=step, replica=replica, **stats)


def planes(host_events, other_thread=()):
    """Chip 0 is busy 100-300, 400-600 and 800-900 us of a trace that spans
    0-1000 us: idle 0-100, 300-400, 600-800, 900-1000 = 500 us."""
    device = [ev("%fusion.1 = f32[16]{0} fusion()", 100, 200),
              ev("%fusion.2 = f32[16]{0} fusion()", 400, 200),
              ev("%fusion.3 = f32[16]{0} fusion()", 800, 100)]
    return [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": device}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ev("%fusion.9 = f32[16]{0} fusion()", 0, 1000)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": list(host_events)},
            {"name": "other", "events": list(other_thread)},
        ]},
    ]


def three_steps():
    """Step 7 decodes; step 8 admits two sequences (idle under its schedule
    300-350, its prefill_build 350-380, its prefill_put 380-400, its publish
    600-650) after a loop span of its number (idle 290-300: none, busy);
    step 9 tries one admission and rolls it back, and admits nothing."""
    return [
        ev("start", 0, 1),
        phase("decode_fetch", 7, 10, 250),       # idle 10-100: not step 8's
        phase("loop", 8, 260, 30),               # 260-290: busy
        phase("schedule", 8, 290, 60),           # 290-350: idle 300-350
        part("", 8, 292, 30, seq=41, tokens=20000),
        part("hash", 8, 293, 20),
        part("walk", 8, 313, 4),
        part("pages", 8, 318, 2),                # 4 us of the span under no part
        part("", 8, 324, 20, seq=42, tokens=10000),
        part("hash", 8, 325, 10),
        part("walk", 8, 335, 2),
        part("state", 8, 337, 3),
        part("pages", 8, 341, 1),                # 4 us under no part
        phase("prefill_build", 8, 350, 30),      # idle 350-380
        phase("prefill_put", 8, 380, 40),        # 380-420: idle 380-400
        phase("prefill_dispatch", 8, 420, 180),  # busy
        phase("publish", 8, 600, 50),            # idle 600-650: rest
        phase("schedule", 9, 650, 100),          # idle 650-750: not admitting
        part("", 9, 660, 40, seq=43, tokens=6000),
        part("hash", 9, 661, 10),
        part("walk", 9, 672, 3),
        part("pages", 9, 676, 4),
        part("rollback", 9, 681, 18),            # inside: out of pages; 5 under none
        phase("decode_build", 9, 750, 40),
        ev("end", 999, 1),
    ]


def test_admit_ms_sums_to_the_mean_admit_span(monkeypatch):
    rec, loads = traced(planes(three_steps()), monkeypatch)
    got = {p: read(f"admit_ms.{p}", rec) for p in MS_PARTS}
    assert len(loads) == 1  # one load serves every reader of the run
    want_us = {"hash": 40, "walk": 9, "window": 0, "state": 3, "pages": 7,
               "rollback": 18, "other": 13}
    assert got == {p: pytest.approx(us / 3 / 1e3) for p, us in want_us.items()}
    assert sum(got.values()) == pytest.approx((30 + 20 + 40) / 3 / 1e3)
    assert read("admit_tokens_mean", rec) == pytest.approx(12000.0)
    assert read("admit_retry_share", rec) == pytest.approx(100 / 3)
    assert len(loads) == 1


def test_the_schedulers_rollback_follows_its_admission(monkeypatch):
    """Over the budget: the span lies after the ``admit`` span it undoes, so
    it is in ``admit_ms.rollback`` and in no ``admit`` span's time."""
    host = [
        ev("start", 0, 1), phase("schedule", 3, 290, 100),
        part("", 3, 300, 20, seq=1, tokens=64), part("hash", 3, 301, 10),
        part("", 3, 330, 20, seq=2, tokens=32), part("hash", 3, 331, 6),
        part("rollback", 3, 352, 8, seq=2),
        ev("end", 999, 1),
    ]
    rec, _ = traced(planes(host), monkeypatch)
    got = {p: read(f"admit_ms.{p}", rec) for p in MS_PARTS}
    assert got["rollback"] == pytest.approx(8 / 2 / 1e3)
    assert got["other"] == pytest.approx((40 - 16) / 2 / 1e3)
    assert sum(got.values()) - got["rollback"] == pytest.approx(40 / 2 / 1e3)
    assert read("admit_retry_share", rec) == pytest.approx(50.0)
    # one of the step's two attempts stood: the step admitted
    assert read("admit_step_idle_ms.schedule", rec) == pytest.approx(0.090)


def test_admit_step_idle_ms_sums_to_the_admitting_steps_idle_time(monkeypatch):
    rec, _ = traced(planes(three_steps()), monkeypatch)
    got = {p: read(f"admit_step_idle_ms.{p}", rec) for p in IDLE_PHASES}
    # one admitting step (8); step 9's roll-back leaves it out, step 7 decodes
    assert got == {"schedule": pytest.approx(0.050),
                   "prefill_build": pytest.approx(0.030),
                   "prefill_put": pytest.approx(0.020),
                   "rest": pytest.approx(0.050)}
    found = admit_times.reduce(planes(three_steps()))
    assert found["step_idle"]["steps"] == 1
    idle, admits, phases = admit_times.spans_of(planes(three_steps()))
    assert sum(b - a for a, b in idle) == 500e3
    step_8 = [(s, e) for s, e, _, stats in phases if stats["step"] == "8"]
    under_8 = sum(max(0.0, min(b, e) - max(a, s))
                  for a, b in idle for s, e in step_8)
    assert sum(got.values()) == pytest.approx(under_8 / 1e6)


def test_idle_gap_share_reads_the_same_planes_as_it_did(monkeypatch):
    """The children nest inside ``engine.schedule`` and are not ``engine.*``
    spans: the phases tile the loop's time with or without them."""
    path = os.path.join(ROOT, "chipbench", "layer_metrics", "idle_gap_share.py")
    spec = importlib.util.spec_from_file_location("idle_gap_share_with_admit", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with_children = mod.shares(planes(three_steps()))
    without = mod.shares(planes(
        [e for e in three_steps() if not e["name"].startswith("admit")]))
    assert with_children == without
    assert sum(with_children.values()) == pytest.approx(100.0)
    assert with_children["unattributed"] >= 0
    assert with_children["schedule"] == pytest.approx(100 * 150 / 500)
    assert not [k for k in with_children if k.startswith("admit")]
    # and the reduction still names a gap after a phase, not after a child
    gaps = trace_reduce.reduce(planes(three_steps()))["idle_gaps"]
    assert gaps and all(name.startswith("python3:engine.") for name, _ in gaps)


def test_another_replicas_spans_are_not_read(monkeypatch):
    other = [phase("schedule", 8, 300, 100, replica="tpu:1"),
             part("", 8, 300, 90, replica="tpu:1", seq=9, tokens=99999)]
    rec, _ = traced(planes(three_steps(), other), monkeypatch)
    assert read("admit_tokens_mean", rec) == pytest.approx(12000.0)
    assert read("admit_step_idle_ms.schedule", rec) == pytest.approx(0.050)


def test_nothing_is_read_off_the_chip_or_from_a_program_without_the_spans(monkeypatch):
    names = [m["name"] for m in NEW] + ["admit_ms.window", "admit_ms.rollback"]
    assert all(read(n, records(trace=None)) is None for n in names)
    # the parent's trace: phases, and no child span
    bare = planes([e for e in three_steps() if not e["name"].startswith("admit")])
    assert admit_times.reduce(bare) == {}
    rec, loads = traced(bare, monkeypatch)
    assert all(read(n, rec) is None for n in names) and len(loads) == 1
    # no device plane at all (the CPU rehearsal reduces to no chip)
    host_only = [p for p in planes(three_steps()) if p["name"].startswith("/host")]
    assert admit_times.reduce(host_only) == {}
    rec, loads = traced(host_only, monkeypatch)
    assert all(read(n, rec) is None for n in names) and loads == []
    # admissions that were all rolled back: no admitting step to divide by
    rolled = planes([e for e in three_steps() if "step=9" in e["text"]])
    rec, _ = traced(rolled, monkeypatch)
    assert read("admit_step_idle_ms.schedule", rec) is None
    assert read("admit_retry_share", rec) == pytest.approx(100.0)


# -- a capture recorded here -------------------------------------------------------------
def test_a_recorded_capture_reads_through_load(tmp_path):
    """``Engine.part``'s spans as the profiler writes them: ``load`` keeps the
    integer stats that ``trace_reduce.load`` drops, of these spans alone; with
    a device plane laid beside them the reduction finds the admissions of the
    replica."""
    import jax

    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig, Engine, EngineConfig, SamplingParams, SchedulerConfig)

    eng = Engine(EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(total_pages=64, page_size=4),
        scheduler=SchedulerConfig(max_prefill_batch=4, max_prefill_tokens=20),
        max_model_len=64, prefill_bucket=8, decode_batch_size=4, interpret=True))
    eng.obs_step_timing = True
    eng.replica = "tpu:0"  # as a chip's engine says it
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark's traced run
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for n in (12, 12, 6):  # three events that are no span of the program
            with jax.profiler.TraceAnnotation("np.asarray", size=n):
                eng.add_request(list(range(1, n + 1)),
                                SamplingParams(max_new_tokens=2))
        for _ in range(2):  # the second of the first step's two is rolled back
            with eng.phase("schedule"):
                out = eng.scheduler.schedule()
            eng.scheduler.on_prefill_done(out.prefill)
            eng._step_count += 1
    finally:
        jax.profiler.stop_trace()
    made = admit_times.load(str(tmp_path))
    mine = [e for p in made for line in p["lines"] for e in line["events"]
            if e["name"].startswith(("admit", "engine."))]
    assert {e["name"] for e in mine} >= {"admit", "admit.hash", "admit.walk",
                                         "admit.pages", "admit.rollback",
                                         "engine.schedule"}
    assert all(re.search(r"\bstep=\d+\b", e["text"])
               and "replica=tpu:0" in e["text"].split() for e in mine)
    # every other event is what the benchmark's own load makes of it
    whole = trace_reduce.load(str(tmp_path))
    flat = lambda ps: [e for p in ps for line in p["lines"] for e in line["events"]]
    assert len(flat(made)) == len(flat(whole)) > len(mine)
    for got, want in zip(flat(made), flat(whole)):
        assert {**got, "text": ""} == {**want, "text": ""}
        assert (got["text"] == want["text"]) is (got not in mine)
    assert [e for e in flat(made) if e["name"] == "np.asarray"
            and "size=" not in e["text"]]
    last = max(e["start_ns"] + e["dur_ns"] for e in mine)
    made.append({"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [{
        "name": "%fusion.1 = f32[16]{0} fusion()", "text": "%fusion.1",
        "start_ns": last + 1e3, "dur_ns": 1e3}]}]})
    found = admit_times.reduce(made)
    st = eng.step_stats
    assert found["admits"] == st["admit_attempts"] == 4
    assert found["rollbacks"] == st["admit_rollbacks"] == 1
    assert found["tokens"] == st["admit_tokens"] == 12 + 12 + 12 + 6
    assert found["step_idle"]["steps"] == 2
    # the profiler's clock and the host's agree on what the spans took
    assert found["ns"]["admit"] / 1e9 == pytest.approx(st["admit_s"], rel=0.25)
    assert found["ns"]["other"] >= 0
    assert sum(v for k, v in found["ns"].items() if k != "admit") == pytest.approx(
        found["ns"]["admit"] + sum(
            e["dur_ns"] for e in mine if e["name"] == "admit.rollback"))
    # the chip idles from the trace's first event to its one operation
    idle = found["step_idle"]["ns"]
    assert idle["schedule"] > 0 and idle["prefill_build"] == idle["rest"] == 0
