"""The reader of the admissions that went ahead (``admit_ahead_share``:
``Engine.step_stats``' ``admit_ahead`` over the admissions that stood) on
records made by hand and on an engine driven here, and the form of its entry
in ``BENCHMARK.json``, held as ``test_admit_metrics.py`` holds the nine of the
admission's spans: CPU, no chip, nothing here is a measurement.
"""

import json
import os
import re

import pytest

from chipbench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
#: the entry, looked up in ``BENCHMARK.json`` by NAME (it waited in
#: ``chipbench/admit_ahead_entries.json`` from PR 53 until PR 55 appended it)
NEW = [m for m in BENCH["per_layer"] if m["name"] == "admit_ahead_share"]
#: the five cells PR 53 read it in; a ``benchmark`` PR adds a cell whose
#: traced run on the chip prints a number for it
CELLS = ["qwen3-32b.sessions", "qwen3-30b-a3b.reasoning", "sdar-30b-a3b.blockgen",
         "kanana-2-30b-a3b.docqa", "lfm2-8b-a1b.agentloop"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


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


def counts(attempts, rollbacks, ahead=None):
    out = {"admit_attempts": attempts, "admit_rollbacks": rollbacks, "steps": 9}
    if ahead is not None:
        out["admit_ahead"] = ahead
    return out


READ = run.load_layer_metric("admit_ahead_share")


# -- the entry ----------------------------------------------------------------------
def test_the_entry():
    (m,) = NEW
    assert {**m, "workloads": CELLS} == {
        "name": "admit_ahead_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine step",
        "moves": "out_tokens_per_s", "workloads": CELLS}
    assert set(m["workloads"]) >= set(CELLS)
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert "roofline" not in m["name"] and "mfu" not in m["name"]
    cells = {w["name"] for w in BENCH["workloads"]}
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved.get("workloads", cells)) <= cells
    assert m["layer"] in {e["layer"] for e in BENCH["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        section = f.read().split("## 3. Layers")[1].split("\n## 4.")[0]
    row = next(line for line in section.splitlines()
               if line.startswith(f"| {m['layer']} |"))
    assert f"`{m['name']}`" in row


def test_the_entry_has_a_reader_by_its_own_file():
    assert callable(READ)
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "layer_metrics", "admit_ahead_share.py"))
    with pytest.raises(run.BenchFailure, match="no reader"):
        run.load_layer_metric("no_such_metric.admit_ahead_share")


def test_benchmark_json_holds_it_and_reads_it_in_the_cells_it_lists():
    """The entry stands in ``BENCHMARK.json`` itself since PR 55: a traced
    run reads it in each of the five cells, and in any other cell it lists.
    The file it waited in stays, read by nothing, while
    ``docs/architecture.md`` names it (a ``benchmark`` PR may not edit the
    document, ``PERF.md`` section 7): until it goes, what it holds is what
    ``BENCHMARK.json`` holds, but for a wider ``workloads``."""
    waited = os.path.join(ROOT, "chipbench", "admit_ahead_entries.json")
    if os.path.exists(waited):
        assert [{**m, "workloads": CELLS} for m in NEW] == json.load(
            open(waited))["per_layer"]
    bench = run.load_benchmark()
    (entry,) = NEW
    for cell in bench["workloads"]:
        here = [m["name"]
                for m in run.metrics_of_cell(bench["per_layer"], cell["name"])]
        assert ("admit_ahead_share" in here) == (cell["name"] in entry["workloads"])
        assert cell["name"] not in CELLS or "admit_ahead_share" in here


# -- the reader on records made by hand -------------------------------------------
def test_the_share_is_ahead_over_the_admissions_that_stood():
    rec = records(step_before=[counts(10, 1, 2)], step_after=[counts(31, 2, 18)])
    # 21 attempts, one rolled back: 20 stood, 16 of them ahead
    assert READ(rec) == pytest.approx(80.0)


def test_replicas_are_summed():
    rec = records(
        step_before=[counts(0, 0, 0), counts(4, 0, 4)],
        step_after=[counts(10, 0, 10), counts(14, 0, 4)],
    )
    assert READ(rec) == pytest.approx(50.0)


@pytest.mark.parametrize("before,after", [
    (counts(3, 0), counts(9, 0)),        # the parent: no such counter
    (counts(3, 0), counts(9, 0, 4)),     # (only one side: not a growth)
])
def test_a_program_that_does_not_count_them_reads_nothing(before, after):
    assert READ(records(step_before=[before], step_after=[after])) is None


def test_a_window_that_admitted_nothing_reads_nothing():
    same = counts(5, 1, 3)
    assert READ(records(step_before=[same], step_after=[dict(same)])) is None
    assert READ(records()) is None  # an untraced run: no copies of step_stats
    # every attempt of the window rolled back: nothing stood
    rec = records(step_before=[counts(5, 1, 3)], step_after=[counts(7, 3, 3)])
    assert READ(rec) is None


# -- the reader on an engine driven here ------------------------------------------------
def test_the_reader_on_an_engines_own_counts():
    """``run.py`` copies ``step_stats`` before and after the window of a
    traced run; an engine whose lanes are full with two waiting admits them
    ahead, and the reader says so from those copies."""
    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig,
        Engine,
        EngineConfig,
        SamplingParams,
        SchedulerConfig,
    )
    from llm_d_kv_cache_manager_tpu.server.engine import ADMIT_COUNTS

    assert "admit_ahead" in ADMIT_COUNTS
    eng = Engine(EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(total_pages=64, page_size=4),
        scheduler=SchedulerConfig(max_prefill_batch=4),
        max_model_len=64, prefill_bucket=8, decode_batch_size=2, interpret=True,
    ))
    eng.obs_step_timing = True
    for i, n in enumerate((4, 6)):
        eng.add_request(list(range(1 + i, 10 + i)), SamplingParams(max_new_tokens=n))
    eng.step()  # two admissions by a step of their own, before the window
    before = dict(eng.step_stats)
    for i, n in enumerate((3, 3)):
        eng.add_request(list(range(20 + i, 29 + i)), SamplingParams(max_new_tokens=n))
    eng.run_until_complete()
    after = dict(eng.step_stats)
    assert after["admit_ahead"] - before["admit_ahead"] == 2
    assert READ(records(step_before=[before], step_after=[after])) == 100.0
    whole = records(step_before=[dict.fromkeys(after, 0)], step_after=[after])
    assert READ(whole) == pytest.approx(50.0)
