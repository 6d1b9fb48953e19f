"""A model with linear-attention layers BESIDE per-head K/V layers on the
served path: the engine, the scheduler and the block manager with a state
pool of slots beside the key/value pools (``TINY_SOLAR_HYBRID``). A hit is K/V
pages AND a snapshot: the context-pool hit is cut back to the last boundary
whose snapshot is held, the warm prefill reads the cached K/V pages and the
restored state in one program, and a burst that passes a boundary leaves a
snapshot behind while its lane's K/V pages fill on. Greedy generations are
held to the plain reference's choices (``chipbench/references/kda_gqa_moe``),
so a wrong slot, page, snapshot or restore shows as a wrong token. The state
pool's own cases (pins, eviction order, preemption) are the block manager's
and are held once, in ``tests/test_kda_engine.py``.
"""

import dataclasses

import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_SOLAR_HYBRID
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    SchedulerConfig,
)
from served_path import make_engine, prompt_of, run_all

#: one period of the preset's two (a GQA layer, three linear ones): the block
#: manager, the scheduler and the engine's slots do not see the depth
CFG = dataclasses.replace(TINY_SOLAR_HYBRID, n_layers=4)
PS, STRIDE = 4, 8
REF = chip_reference.load("kda_gqa_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 58)


def engine_of(params, *, snapshots=16, pages=128, **kw):
    kw.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    return make_engine(
        CFG, params,
        BlockManagerConfig(total_pages=pages, page_size=PS,
                           state_snapshot_tokens=STRIDE,
                           state_snapshot_slots=snapshots),
        **kw)


def picks(params, ask, generated):
    return served_path.picks(REF, params, CFG, ask, generated)


@pytest.mark.parametrize("burst", [1, 4], ids=["step", "burst4"])
def test_generation_is_the_references(params, burst):
    engine = engine_of(params, decode_steps_per_iter=burst)
    engine.obs_step_timing = True
    prompts = [prompt_of(i, n) for i, n in enumerate((27, 9, 16, 21))]
    seqs = run_all(engine, prompts, 11)
    for prompt, seq in zip(prompts, seqs):
        assert seq.output_tokens == picks(params, prompt, seq.output_tokens)
    # a snapshot at every boundary each sequence passed, prefill and decode
    want = sum((len(p) + 11 - 1) // STRIDE for p in prompts)
    stats = engine.state_pool_stats()
    assert stats["state_snapshots_taken"] == want
    # ONE model counts the K/V context its GQA layer read, the pages its
    # kernel walked and the lanes whose slots its linear layers read and wrote
    counted = engine.step_stats
    assert counted["attn_ctx_tokens"] > 0 and counted["full_ctx_pages"] > 0
    assert counted["decode_rows"] > 0 and counted["experts_touched"] > 0
    assert counted["latent_ctx_tokens"] == 0 and counted["ctx_pages"] == 0


def test_a_hit_is_kv_pages_and_a_snapshot(params):
    engine = engine_of(params)
    first = prompt_of(3, 27)
    run_all(engine, [first], 2)
    # 22 shared tokens: 5 pages hit in the K/V pools, the last boundary
    # under them is 16: the warm prefill starts there, over 4 cached pages
    ask = first[:22] + prompt_of(4, 7)
    (seq,) = run_all(engine, [ask], 6)
    assert seq.num_cached_prompt == 16
    assert seq.output_tokens == picks(params, ask, seq.output_tokens)
    got = engine.state_pool_stats()
    assert got["state_restores"] == 1 and got["state_cutback_lost"] == 0
    assert got["state_cutback_tokens"] == 20 - 16
    assert got["state_admissions"] == 2


def test_a_hit_whose_snapshot_is_gone_keeps_its_pages_and_starts_earlier(params):
    engine = engine_of(params)
    first = prompt_of(7, 27)
    run_all(engine, [first], 2)
    st = engine.block_manager.state
    hashes = engine.block_manager.token_db.prefix_hashes(first)
    st.evict_hash(hashes[24 // PS - 1])  # the snapshot goes, its K/V page stays
    ask = first[:26] + [9, 9, 9]
    (seq,) = run_all(engine, [ask], 5)
    assert seq.num_cached_prompt == 16
    assert seq.output_tokens == picks(params, ask, seq.output_tokens)
    assert engine.state_pool_stats()["state_cutback_lost"] == 1


@pytest.mark.parametrize("burst", [1, 4], ids=["step", "burst4"])
def test_a_snapshot_taken_in_decode_is_hit_later(params, burst):
    """A lane whose burst passes a boundary leaves the state at it behind
    while the GQA layer's K/V pages of the same tokens fill on: the follow-up
    hits both."""
    engine = engine_of(params, decode_steps_per_iter=burst)
    first = prompt_of(9, 13)
    (seq,) = run_all(engine, [first], 14)
    history = first + seq.output_tokens[:13]
    (turn,) = run_all(engine, [history + [7, 7, 7]], 5)
    assert turn.num_cached_prompt == 24
    assert turn.output_tokens == picks(
        params, history + [7, 7, 7], turn.output_tokens)
    assert engine.state_pool_stats()["state_restores"] == 1


def test_a_lane_is_restored_while_the_others_keep_their_slots(params):
    """Three lanes decode on; a fourth request is admitted from a snapshot
    into the free lane: its restore and their in-place updates are one
    stream of dispatches over one state pool and one pair of K/V pools."""
    engine = engine_of(params, decode_steps_per_iter=4)
    first = prompt_of(11, 26)
    run_all(engine, [first], 2)
    asks = [prompt_of(30 + i, n) for i, n in enumerate((12, 19, 7))]
    seqs = [engine.add_request(p, served_path.SamplingParams(max_new_tokens=20))
            for p in asks]
    for _ in range(3):
        engine.step()
    warm = first[:25] + [5, 6]
    seqs.append(engine.add_request(
        warm, served_path.SamplingParams(max_new_tokens=9)))
    while engine.has_work:
        engine.step()
    assert seqs[-1].num_cached_prompt == 24
    for ask, seq in zip(asks + [warm], seqs):
        assert seq.output_tokens == picks(params, ask, seq.output_tokens)
    st = engine.block_manager.state
    assert len(st._free) + st.num_snapshots == st.n_slots - 1
