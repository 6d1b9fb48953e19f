"""``chained_dispatch_share``: the reader of the engine's
``decode_chained_dispatches`` counter on hand-made records, and its entry.
CPU, no chip, nothing here is a measurement.
"""

import pytest
from test_phase_metrics import BENCH, REASONING, SESSIONS, read, records

NAME = "chained_dispatch_share"
DOCQA = "kanana-2-30b-a3b.docqa"


def counts(dispatches, chained=None):
    out = {"decode_dispatches": dispatches, "decode_rows": 16 * dispatches,
           "decode_sampled_dispatches": 0}
    if chained is not None:
        out["decode_chained_dispatches"] = chained
    return out


def test_the_entry():
    # looked up by its name: where it stands in the list is not held
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    first = [SESSIONS, REASONING, DOCQA]
    assert {**entry, "workloads": first} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine step",
        "moves": "itl_ms_p50", "workloads": first,
    }
    assert set(first) <= set(entry["workloads"])  # a benchmark PR adds cells
    cells = {w["name"] for w in BENCH["workloads"]}
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= cells
    assert set(entry["workloads"]) <= set(moved.get("workloads", cells))


def test_the_engine_counts_what_it_reads():
    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig, Engine, EngineConfig, SamplingParams,
    )

    eng = Engine(EngineConfig(
        model=TINY_LLAMA, interpret=True, decode_batch_size=1,
        block_manager=BlockManagerConfig(total_pages=16, page_size=4),
    ))
    stats = eng.step_stats
    assert stats["decode_chained_dispatches"] == stats["decode_dispatches"] == 0
    eng.obs_step_timing = True
    eng.add_request(list(range(3, 12)), SamplingParams(max_new_tokens=9))
    eng.run_until_complete()
    # one lane, taken: every dispatch but the first and the last (which
    # reaches the budget, so nothing is enqueued behind it) is chained
    assert stats["decode_dispatches"] == 8
    assert stats["decode_chained_dispatches"] == 7
    assert read(NAME, records(
        step_before=[counts(0, 0)], step_after=[dict(stats)],
    )) == pytest.approx(87.5)


@pytest.mark.parametrize(
    "before, after, want",
    [
        # a program without the counter (the parent): nothing, and no raise
        ([counts(10)], [counts(30)], None),
        ([counts(10, 0)], [counts(30)], None),
        # a lane is always free: the engine never runs ahead
        ([counts(10, 0)], [counts(30, 0)], 0.0),
        # what was counted before the window is not the window's
        ([counts(10, 10)], [counts(30, 10)], 0.0),
        ([counts(10, 4)], [counts(30, 19)], 75.0),
        ([counts(0, 0)], [counts(8, 8)], 100.0),
        # all replicas together
        ([counts(0, 0), counts(5, 5)], [counts(30, 3), counts(15, 12)], 25.0),
        # no dispatch inside the window, or an untraced run
        ([counts(7, 2)], [counts(7, 2)], None),
        ([], [], None),
    ],
)
def test_the_share(before, after, want):
    got = read(NAME, records(step_before=before, step_after=after))
    assert got == (want if want is None else pytest.approx(want))
    assert want is None or isinstance(got, float)
