"""A model with sliding layers through ``Engine``, where lanes decode: a
page given back inside a burst or under a dispatch ahead, and preemption and
resume in either pool: the reference's pick at every step
(``chipbench/references/swa_moe.forward``, float32). Prefill, hits and
``/stats`` are in ``tests/test_swa_engine.py``.
"""

import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig
from served_path import prompt_of

CFG = served_path.ONE_OF_EACH_SWA  # depth is not these cases' point
PS = 4
W = CFG.sliding_window
REF = chip_reference.load("swa_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 43)


def make_engine(params, total_pages=96, window_pages=48, **engine):
    return served_path.make_engine(
        CFG, params,
        BlockManagerConfig(
            total_pages=total_pages, page_size=PS, window_pages=window_pages),
        max_model_len=160, **engine)


def run_all(engine, prompts, n=10):
    return served_path.run_all(engine, prompts, n)


def picks(params, ask, generated):
    return served_path.picks(REF, params, CFG, ask, generated)


@pytest.mark.parametrize("k, lanes, ahead", [
    (1, 2, True), (3, 2, True), (5, 4, False),
])
def test_a_page_given_back_inside_a_burst_or_under_a_dispatch_ahead(
        params, k, lanes, ahead):
    asks = [prompt_of(90 + i, 9 + 4 * i) for i in range(2)]
    engine = make_engine(params, lanes=lanes, decode_steps_per_iter=k)
    engine.obs_step_timing = True
    seqs = run_all(engine, asks, n=26)
    assert bool(engine.step_stats["decode_chained_dispatches"]) == ahead
    for seq, ask in zip(seqs, asks):
        assert seq.generated_tokens == picks(params, ask, seq.generated_tokens)
    assert engine.block_manager.window.num_held <= 2 * (W // PS + 1)


@pytest.mark.parametrize("sizes, lanes", [
    pytest.param(dict(total_pages=13), 2, id="context-pool"),
    pytest.param(dict(window_pages=11), 4, id="window-pool"),
])
def test_preemption_and_resume(params, sizes, lanes):
    """A pool too small for the lanes' growth, the context pool or the
    window pool (whose lanes each hold a window and a boundary): a lane is
    preempted, folded and prefilled again (a hit where the window pool still
    has its last window) and goes on as an unbroken run."""
    asks = [prompt_of(110 + i, 14 + i) for i in range(lanes)]
    engine = make_engine(params, lanes=lanes, **sizes)
    preempted = []
    on_preempted = engine.scheduler.on_preempted
    engine.scheduler.on_preempted = lambda seq: (
        preempted.append(seq), on_preempted(seq))[1]
    seqs = run_all(engine, asks, n=18)
    assert preempted
    for seq, ask in zip(seqs, asks):
        generated = seq.all_tokens[len(ask):]
        assert len(generated) == 18 and generated == picks(params, ask, generated)
