"""``full_ctx_page_run_share``: the reader of the engine's ``full_ctx_pages``
/ ``full_ctx_run_pages`` counters (the pages a full layer's ``paged_attention``
copies since it walks a lane's own pages, PR 57) on hand-made records, its
entry, and the engines that count them, against the kernel's own rule. CPU,
no chip, nothing here is a measurement.
"""

import numpy as np
import pytest
from test_page_run_share import _decode, _engine
from test_phase_metrics import BENCH, read, records

NAME = "full_ctx_page_run_share"
CELLS = [
    "qwen3-32b.sessions", "qwen3-30b-a3b.reasoning", "lfm2-8b-a1b.agentloop",
    "trinity-large-preview.longdocs", "smallthinker-21b-a3b.mixedlen",
]


def counts(pages=None, in_runs=None, **more):
    out = dict(more)
    if pages is not None:
        out["full_ctx_pages"] = pages
    if in_runs is not None:
        out["full_ctx_run_pages"] = in_runs
    return out


def test_the_entry():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert {**entry, "workloads": CELLS} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "itl_ms_p50", "workloads": CELLS,
    }
    assert set(CELLS) <= set(entry["workloads"])  # and what a later PR adds
    # the cells whose full layers run ``paged_attention``: no latent pool
    # (``mla_decode``), no block diffusion (``block_attention``)
    configs = {w["name"]: w["config"] for w in BENCH["workloads"]}
    assert set(entry["workloads"]) <= set(configs)
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved.get("workloads", configs))
    assert entry["layer"] in {
        m["layer"] for m in BENCH["per_layer"] if m["name"] != NAME
    }


@pytest.mark.parametrize(
    "before, after, want",
    [
        ([counts(100, 96)], [counts(1380, 1296)], 93.75),
        ([counts(64, 64)], [counts(704, 64)], 0.0),
        # a program without the counters (the parent, whose full call was a
        # program a table page): nothing, and no raise
        ([counts()], [counts()], None),
        ([counts(ctx_pages=5, ctx_run_pages=0)],
         [counts(ctx_pages=90, ctx_run_pages=64)], None),
        ([counts(0, 0)], [counts(10)], None),
        ([counts(0)], [counts(10, 10)], None),
        # all replicas together
        ([counts(0, 0), counts(10, 10)], [counts(100, 50), counts(110, 35)], 37.5),
        # a latent pool's dispatches copy no page of a full layer; an
        # untraced run
        ([counts(0, 0)], [counts(0, 0, decode_dispatches=9)], None),
        ([], [], None),
    ],
)
def test_the_share(before, after, want):
    got = read(NAME, records(step_before=before, step_after=after))
    assert got == (want if want is None else pytest.approx(want))


def _kernels_rule(eng, prompts, new_tokens):
    """What the dispatches' first steps copy by ``count_run_pages`` over the
    tables the engine built, summed beside the engine's own count."""
    from llm_d_kv_cache_manager_tpu.ops._page_copies import count_run_pages
    from llm_d_kv_cache_manager_tpu.ops.paged_attention import walk_step_pages

    seen = []
    counted = eng._count_ctx_pages

    def spy(seq_lens, block_tables, window_tables, steps):
        width, ps = block_tables.shape[1], eng.page_size
        hist = np.clip(seq_lens - 1, 0, width * ps)
        pages, in_runs = count_run_pages(
            block_tables, 0, -(-hist // ps), walk_step_pages(width, ps),
            eng.k_pages.shape[1],
        )
        assert pages == int((-(-hist // ps)).sum())
        seen.append((steps * pages, steps * in_runs))
        counted(seq_lens, block_tables, window_tables, steps)

    eng._count_ctx_pages = spy
    stats = _decode(eng, prompts, new_tokens)
    return stats, np.sum(seen, axis=0).tolist()


def test_a_dense_engine_counts_its_block_tables():
    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
    from llm_d_kv_cache_manager_tpu.ops._page_copies import RUN_PAGES

    eng = _engine(TINY_LLAMA, total_pages=128)
    assert eng.step_stats["full_ctx_pages"] == 0
    rng = np.random.default_rng(0)
    # a fresh free list hands out ascending ids: a prompt of 70 tokens is
    # 17 whole pages of 4, one group of 16 a run; beside it a lane too short
    stats, (pages, in_runs) = _kernels_rule(
        eng, [rng.integers(1, 200, 70).tolist(),
              rng.integers(1, 200, 9).tolist()], 6)
    assert (stats["full_ctx_pages"], stats["full_ctx_run_pages"]) == (pages, in_runs)
    assert pages > in_runs > 0 and in_runs % RUN_PAGES == 0
    # the latent and the window walk's keys stay theirs
    assert stats["ctx_pages"] == stats["ctx_run_pages"] == 0
    share = read(NAME, records(
        step_before=[counts(0, 0)], step_after=[dict(stats)],
    ))
    assert share == pytest.approx(100.0 * in_runs / pages)


def test_a_window_engine_counts_both_walks():
    from llm_d_kv_cache_manager_tpu.models import TINY_SWA_MOE

    eng = _engine(TINY_SWA_MOE, total_pages=96, window_pages=48)
    rng = np.random.default_rng(1)
    stats, (pages, in_runs) = _kernels_rule(
        eng, [rng.integers(1, 200, 30).tolist()], 12)
    # the full layers walk the whole context, the sliding ones a window of
    # 8 positions: at most three pages of 4
    assert (stats["full_ctx_pages"], stats["full_ctx_run_pages"]) == (pages, in_runs)
    assert 0 < stats["ctx_pages"] <= 3 * stats["decode_forwards"] < pages
    assert stats["ctx_run_pages"] == 0


def test_a_latent_engine_counts_no_full_layer():
    from llm_d_kv_cache_manager_tpu.models import TINY_MLA_MOE

    eng = _engine(TINY_MLA_MOE, total_pages=128)
    stats = _decode(eng, [list(range(3, 73))], 6)
    assert stats["ctx_pages"] > 0
    assert stats["full_ctx_pages"] == stats["full_ctx_run_pages"] == 0
    assert read(NAME, records(
        step_before=[counts(0, 0)], step_after=[dict(stats)],
    )) is None
