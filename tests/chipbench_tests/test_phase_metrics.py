"""The readers of the engine's phase spans and counters
(``step_phase_ms.*``, ``idle_gap_share.*``, ``queue_wait_ms_p50``,
``staged_wait_ms_p50``, ``decode_rows_mean``) on hand-made records and
planes: CPU, no chip, nothing here is a measurement.
"""

import importlib.util
import json
import os

import pytest

from chipbench import run, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
FAMILIES = ("step_phase_ms", "idle_gap_share", "queue_wait_ms_p50",
            "staged_wait_ms_p50", "decode_rows_mean")
NEW = [m for m in BENCH["per_layer"] if m["name"].split(".")[0] in FAMILIES]
SESSIONS, REASONING = "qwen3-32b.sessions", "qwen3-30b-a3b.reasoning"


def engine_phases():
    from llm_d_kv_cache_manager_tpu.server.engine import STEP_PHASES

    return STEP_PHASES


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


# -- the entries ------------------------------------------------------------------
def test_the_entries_are_the_phases_of_the_engine_and_each_has_a_reader():
    phases = engine_phases()
    cells = {
        m["name"]: set(m["workloads"]) for m in NEW
    }
    both = {SESSIONS, REASONING}
    for p in phases:  # at least the two cells; a benchmark PR adds cells
        assert cells[f"idle_gap_share.{p}"] >= both
        if p.startswith("prefill_"):  # moves ttft_ms_p50: one cell reports it
            assert cells[f"step_phase_ms.{p}"] == {SESSIONS}
        else:
            assert cells[f"step_phase_ms.{p}"] >= both
    assert cells["idle_gap_share.unattributed"] >= both
    assert REASONING in cells["step_phase_ms.prefill"]
    assert SESSIONS not in cells["step_phase_ms.prefill"]
    assert len(NEW) == 2 * len(phases) + 2 + 3
    for m in NEW:
        assert callable(run.load_layer_metric(m["name"]))
        # nothing off the chip is reported under a device's name
        assert (m["source"] == "device_trace") == m["name"].startswith("idle_gap_share.")


# -- step_phase_ms, decode_rows_mean ------------------------------------------------
def step(steps, **secs):
    return {"steps": steps, **{f"{k}_s": v for k, v in secs.items()}}


def test_step_phase_ms_is_per_step_of_any_kind_over_all_replicas():
    rec = records(
        step_before=[step(100, decode_fetch=1.0, prefill=0.5, loop=0.0),
                     step(0, decode_fetch=0.0, prefill=0.0, loop=0.0)],
        step_after=[step(200, decode_fetch=3.0, prefill=0.9, loop=0.1),
                    step(100, decode_fetch=1.0, prefill=0.2, loop=0.1)],
    )
    assert read("step_phase_ms.decode_fetch", rec) == pytest.approx(15.0)
    assert read("step_phase_ms.prefill", rec) == pytest.approx(3.0)
    assert read("step_phase_ms.loop", rec) == pytest.approx(1.0)


def test_step_phase_ms_reads_nothing_from_a_program_without_the_phase():
    old = {"steps": 10, "schedule_s": 0.1, "prefill_s": 0.4, "decode_s": 1.4,
           "publish_s": 0.1, "sample_s": 1.0}
    rec = records(step_before=[dict(old, steps=0)], step_after=[old])
    assert read("step_phase_ms.decode_build", rec) is None
    assert read("step_phase_ms.loop", rec) is None
    assert read("decode_rows_mean", rec) is None
    assert read("step_phase_ms.schedule", rec) == pytest.approx(0.0)
    # no step inside the window, or an untraced run: nothing to divide by
    assert read("step_phase_ms.schedule", records(step_before=[old], step_after=[old])) is None
    assert read("step_phase_ms.schedule", records()) is None


def test_a_cells_entries_add_up_to_the_loops_period():
    """On the real engine: the phases of ``step_stats`` less the sums that
    were there before add up to ``step_ms_mean`` + ``step_phase_ms.loop``."""
    phases = engine_phases()
    before = {"steps": 0, "prefill_s": 0.0, "decode_s": 0.0,
              **{f"{p}_s": 0.0 for p in phases}}
    after = dict(before, steps=50)
    for i, p in enumerate(phases):
        after[f"{p}_s"] = 0.01 * (i + 1)
    after["prefill_s"] = sum(after[f"{p}_s"] for p in phases if p.startswith("prefill_"))
    after["decode_s"] = sum(after[f"{p}_s"] for p in phases if p.startswith("decode_"))
    rec = records(step_before=[before], step_after=[after])
    total = sum(read(f"step_phase_ms.{p}", rec) for p in phases)
    assert total == pytest.approx(
        read("step_ms_mean", rec) + read("step_phase_ms.loop", rec)
    )
    five = sum(read(f"step_phase_ms.{p}", rec) for p in phases if p.startswith("prefill_"))
    assert read("step_phase_ms.prefill", rec) == pytest.approx(five)


def test_decode_rows_mean():
    rec = records(
        step_before=[{"decode_rows": 100, "decode_dispatches": 10}],
        step_after=[{"decode_rows": 420, "decode_dispatches": 30}],
    )
    assert read("decode_rows_mean", rec) == pytest.approx(16.0)
    assert read("decode_rows_mean", records(
        step_before=[{"decode_rows": 1, "decode_dispatches": 1}],
        step_after=[{"decode_rows": 1, "decode_dispatches": 1}])) is None


# -- queue_wait_ms_p50, staged_wait_ms_p50 ------------------------------------------
def test_queue_and_staged_wait_are_medians_of_the_responses():
    good = [{"body": {"ttft_s": 0.05, "queue_s": q, "staged_s": s}}
            for q, s in ((0.010, 0.004), (0.020, 0.016), (0.040, 0.030))]
    rec = records(good=good)
    assert read("queue_wait_ms_p50", rec) == pytest.approx(20.0)
    assert read("staged_wait_ms_p50", rec) == pytest.approx(16.0)
    # a program that does not report them, and a window without completions
    old = records(good=[{"body": {"ttft_s": 0.05}}])
    assert read("queue_wait_ms_p50", old) is None
    assert read("staged_wait_ms_p50", old) is None
    assert read("queue_wait_ms_p50", records()) is None


# -- idle_gap_share ------------------------------------------------------------------
def idle_gap_share():
    path = os.path.join(ROOT, "chipbench", "layer_metrics", "idle_gap_share.py")
    spec = importlib.util.spec_from_file_location("idle_gap_share_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def ev(name, start_us, dur_us, **stats):
    text = " ".join([name] + [f"{k}={v}" for k, v in stats.items()])
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3,
            "text": text}


def span(phase, start_us, dur_us, replica="tpu:0"):
    return ev(f"engine.{phase}", start_us, dur_us, replica=replica)


def planes(host_events, other_thread=()):
    """Chip 0 is busy 100-300 and 400-600 us of a trace that spans
    0-1000 us: idle 0-100, 300-400, 600-1000 = 600 us."""
    device = [ev("%fusion.1 = f32[16]{0} fusion()", 100, 200),
              ev("%fusion.2 = f32[16]{0} fusion()", 400, 200)]
    return [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": device}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ev("%fusion.9 = f32[16]{0} fusion()", 0, 1000)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": list(host_events)},
            {"name": "other", "events": list(other_thread)},
        ]},
    ]


def test_idle_time_goes_to_the_phase_open_on_the_chips_own_replica():
    mod = idle_gap_share()
    host = [
        ev("start", 0, 1),                    # the trace's first stamp
        span("decode_fetch", 50, 270),        # 50-320: idle 50-100, 300-320
        span("decode_commit", 320, 30),       # 320-350: wholly inside a gap
        span("decode_build", 350, 30),        # 350-380: wholly inside a gap
        # the runtime's own event, nested in a phase: not read
        ev("np.asarray(jax.Array)", 60, 250),
        span("decode_dispatch", 380, 40),     # 380-420: idle 380-400
        span("loop", 700, 100),               # idle 700-800
        ev("end", 999, 1),                    # the trace's last stamp
    ]
    other = [span("schedule", 600, 400, replica="tpu:1")]  # another replica's
    got = mod.shares(planes(host, other))
    want = {"decode_fetch": 70, "decode_commit": 30, "decode_build": 30,
            "decode_dispatch": 20, "loop": 100, "unattributed": 350}
    assert got == {k: pytest.approx(100 * v / 600) for k, v in want.items()}
    assert sum(got.values()) == pytest.approx(100.0)
    assert "schedule" not in got


def test_every_suffix_is_read_from_one_load_and_sums_to_100(monkeypatch):
    mod = idle_gap_share()
    host = [ev("start", 0, 1), span("decode_build", 300, 100),
            span("publish", 600, 200), ev("end", 999, 1)]
    made = planes(host)
    loads = []
    monkeypatch.setattr(trace_reduce, "load",
                        lambda d: loads.append(d) or made)
    rec = records(trace=trace_reduce.reduce(made))
    names = [m["name"] for m in NEW if m["name"].startswith("idle_gap_share.")]
    values = {n: read(n, rec) for n in names}
    assert len(names) == len(engine_phases()) + 1 == 14 and len(loads) == 1
    assert loads[0].endswith(os.path.join("chipbench", "out", "trace"))
    assert sum(values.values()) == pytest.approx(100.0)
    assert values["idle_gap_share.decode_build"] == pytest.approx(100 / 6)
    assert values["idle_gap_share.publish"] == pytest.approx(200 / 6)
    assert values["idle_gap_share.unattributed"] == pytest.approx(300 / 6)
    assert values["idle_gap_share.schedule"] == 0.0
    del mod


def test_nothing_is_read_off_the_chip_or_from_a_program_without_the_spans(monkeypatch):
    mod = idle_gap_share()
    assert read("idle_gap_share.loop", records(trace=None)) is None
    # the parent's trace: the runtime's events only
    host = [ev("np.asarray(jax.Array)", 0, 1000), ev("PjitFunction(step)", 310, 20)]
    made = planes(host)
    assert mod.shares(made) == {}
    monkeypatch.setattr(trace_reduce, "load", lambda d: made)
    rec = records(trace=trace_reduce.reduce(made))
    assert read("idle_gap_share.unattributed", rec) is None
    assert read("idle_gap_share.decode_fetch", rec) is None
    # no device plane at all
    assert mod.shares([p for p in made if p["name"].startswith("/host")]) == {}


def test_the_reduction_names_the_gaps_after_the_phases():
    """``trace_reduce.reduce`` names a gap after the host event that
    overlaps it most: with the phases in the trace, and none enclosing the
    others, that is a phase and not ``np.asarray``."""
    host = [
        span("decode_fetch", 50, 252), ev("np.asarray(jax.Array)", 60, 241),
        span("decode_commit", 302, 30), span("decode_build", 332, 58),
        span("decode_dispatch", 390, 20), span("loop", 650, 300),
    ]
    gaps = trace_reduce.reduce(planes(host))["idle_gaps"]
    assert [name for name, _ in gaps] == ["python3:engine.decode_build"]
