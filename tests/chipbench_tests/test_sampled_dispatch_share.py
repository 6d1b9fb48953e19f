"""``sampled_dispatch_share``: the reader of the engine's
``decode_sampled_dispatches`` counter on hand-made records. CPU, no chip,
nothing here is a measurement.
"""

import pytest
from test_phase_metrics import BENCH, REASONING, SESSIONS, read, records

NAME = "sampled_dispatch_share"


def counts(dispatches, sampled=None):
    out = {"decode_dispatches": dispatches, "decode_rows": 16 * dispatches}
    if sampled is not None:
        out["decode_sampled_dispatches"] = sampled
    return out


def test_the_entry():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entry["source"] == "program_counter" and entry["unit"] == "%"
    assert entry["layer"] == "engine step" and entry["moves"] == "itl_ms_p50"
    assert {SESSIONS, REASONING} <= set(entry["workloads"])  # by name


def test_the_engine_counts_what_it_reads():
    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig, Engine, EngineConfig,
    )

    stats = Engine(EngineConfig(
        model=TINY_LLAMA, interpret=True,
        block_manager=BlockManagerConfig(total_pages=8, page_size=4),
    )).step_stats
    assert stats["decode_sampled_dispatches"] == stats["decode_dispatches"] == 0


@pytest.mark.parametrize(
    "before, after, want",
    [
        # a program without the counter (the parent): nothing, and no raise
        ([counts(10)], [counts(30)], None),
        ([counts(10, 0)], [counts(30)], None),
        # greedy lanes only: the cell bypasses the filter
        ([counts(10, 0)], [counts(30, 0)], 0.0),
        # what was counted before the window is not the window's
        ([counts(10, 10)], [counts(30, 10)], 0.0),
        ([counts(10, 4)], [counts(30, 9)], 25.0),
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
