"""``page_run_share``: the reader of the engine's ``ctx_pages`` /
``ctx_run_pages`` counters on hand-made records, its entry, and the engines
that count them. CPU, no chip, nothing here is a measurement.
"""

import jax
import numpy as np
import pytest
from test_phase_metrics import BENCH, read, records

NAME = "page_run_share"
DOCQA = "kanana-2-30b-a3b.docqa"


def counts(pages=None, in_runs=None, dispatches=0):
    out = {"decode_dispatches": dispatches}
    if pages is not None:
        out["ctx_pages"] = pages
    if in_runs is not None:
        out["ctx_run_pages"] = in_runs
    return out


def test_the_entry():
    # looked up by its name: where it stands in the list is not held
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert {**entry, "workloads": [DOCQA]} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "itl_ms_p50", "workloads": [DOCQA],
    }
    assert DOCQA in entry["workloads"]  # and the cells a benchmark PR added
    cells = {w["name"] for w in BENCH["workloads"]}
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= cells
    assert set(entry["workloads"]) <= set(moved.get("workloads", cells))
    # its layer is one the benchmark names already
    assert entry["layer"] in {
        m["layer"] for m in BENCH["per_layer"] if m["name"] != NAME
    }


@pytest.mark.parametrize(
    "before, after, want",
    [
        # two hand-made pairs: 1200 of 1280 pages in runs; none of 640
        ([counts(100, 100)], [counts(1380, 1300)], 93.75),
        ([counts(64, 64)], [counts(704, 64)], 0.0),
        # a program without the counters (the parent): nothing, and no raise
        ([counts()], [counts()], None),
        ([counts(0, 0)], [counts(10)], None),
        ([counts(0)], [counts(10, 10)], None),
        # all replicas together
        ([counts(0, 0), counts(10, 10)], [counts(100, 50), counts(110, 35)], 37.5),
        # a model that runs neither kernel counts no page; an untraced run
        ([counts(0, 0)], [counts(0, 0, dispatches=9)], None),
        ([], [], None),
    ],
)
def test_the_share(before, after, want):
    got = read(NAME, records(step_before=before, step_after=after))
    assert got == (want if want is None else pytest.approx(want))


def _engine(model, **block_manager):
    from llm_d_kv_cache_manager_tpu.models import llama
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig, Engine, EngineConfig,
    )

    eng = Engine(
        EngineConfig(
            model=model, interpret=True, decode_batch_size=2,
            block_manager=BlockManagerConfig(page_size=4, **block_manager),
        ),
        params=llama.init_params(jax.random.PRNGKey(3), model),
    )
    eng.obs_step_timing = True
    return eng


def _decode(eng, prompts, new_tokens):
    from llm_d_kv_cache_manager_tpu.server import SamplingParams

    for prompt in prompts:
        eng.add_request(prompt, SamplingParams(max_new_tokens=new_tokens))
    eng.run_until_complete()
    return eng.step_stats


def test_a_latent_engine_counts_its_block_tables():
    from llm_d_kv_cache_manager_tpu.models import TINY_MLA_MOE
    from llm_d_kv_cache_manager_tpu.ops._page_copies import RUN_PAGES

    eng = _engine(TINY_MLA_MOE, total_pages=128)
    assert eng.step_stats["ctx_pages"] == eng.step_stats["ctx_run_pages"] == 0
    rng = np.random.default_rng(0)
    # a fresh free list hands out ascending ids: a prompt of 70 tokens is
    # 17 whole pages, one run; beside it a lane too short for a group
    stats = _decode(eng, [rng.integers(1, 200, 70).tolist(),
                          rng.integers(1, 200, 9).tolist()], 6)
    assert stats["ctx_pages"] > stats["ctx_run_pages"] > 0
    assert stats["ctx_run_pages"] % RUN_PAGES == 0
    share = read(NAME, records(
        step_before=[counts(0, 0)], step_after=[dict(stats)],
    ))
    assert 0 < share < 100


def test_a_window_engine_counts_its_window_tables():
    import dataclasses

    from llm_d_kv_cache_manager_tpu.models import TINY_SWA_MOE
    from llm_d_kv_cache_manager_tpu.ops._page_copies import RUN_PAGES

    eng = _engine(TINY_SWA_MOE, total_pages=96, window_pages=48)
    rng = np.random.default_rng(1)
    stats = _decode(eng, [rng.integers(1, 200, 30).tolist()], 12)
    # a window of 8 positions is at most three pages of 4: no group of a
    # step is whole, so every page is copied alone
    assert stats["ctx_pages"] > 0 and stats["ctx_run_pages"] == 0
    assert stats["ctx_pages"] <= 3 * stats["decode_forwards"]
    assert read(NAME, records(
        step_before=[counts(0, 0)], step_after=[dict(stats)],
    )) == 0.0
    # a window of RUN_PAGES + 2 pages over a fresh window pool: one group
    # of a lane's 11 or 12 visible pages is whole
    wide = dataclasses.replace(TINY_SWA_MOE, sliding_window=4 * RUN_PAGES + 8)
    eng = _engine(wide, total_pages=160, window_pages=8 * RUN_PAGES + 32)
    stats = _decode(eng, [rng.integers(1, 200, 150).tolist()], 6)
    assert stats["ctx_run_pages"] == RUN_PAGES * stats["decode_forwards"]
    assert stats["ctx_pages"] <= (RUN_PAGES + 3) * stats["decode_forwards"]


def test_a_model_that_runs_neither_kernel_counts_nothing():
    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA

    eng = _engine(TINY_LLAMA, total_pages=32)
    stats = _decode(eng, [list(range(3, 40))], 6)
    assert stats["decode_dispatches"] > 0
    assert stats["ctx_pages"] == stats["ctx_run_pages"] == 0
    assert read(NAME, records(
        step_before=[counts(0, 0)], step_after=[dict(stats)],
    )) is None
