"""Admission ahead (``Engine._admit_ahead``), parity: a closed loop of two
callers a lane over shared prefixes gives, request by request, the tokens and
the ``num_cached_prompt`` of the engine whose admissions wait for their step
(``never_admits_ahead`` of ``tests/run_ahead.py``), and leaves the pools as
that engine leaves them; on the plain pool, a window pool, a state pool of
snapshots (where a cut-back prompt's first chunk goes ahead and the second is
owed), a pool with convolution state, a latent pool, and generation by
diffusion over blocks. The rule itself is in ``tests/test_admit_ahead.py``.
"""

import dataclasses

import pytest

import served_path
from llm_d_kv_cache_manager_tpu.models import (
    TINY_LING_HYBRID,
    TINY_LLAMA,
    TINY_SDAR_MOE,
)
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig, SamplingParams
from run_ahead import never_admits_ahead, prompt

PS, LANES = 4, 2
#: one period of the preset's two: every kind of layer once
#: (``tests/test_kda_engine.py``)
KDA = dataclasses.replace(TINY_LING_HYBRID, n_layers=3)

#: (configuration, seed of its parameters, what its block manager adds)
POOLS = {
    "plain": (TINY_LLAMA, 3, {}),
    "window": (served_path.ONE_OF_EACH_SWA, 43, dict(window_pages=64)),
    "snapshots": (KDA, 47, dict(state_snapshot_tokens=8, state_snapshot_slots=24)),
    "conv-state": (served_path.ONE_OF_EACH_LFM2, 34, {}),
    "latent": (served_path.ONE_OF_EACH_MLA, 11, {}),
    "blocks": (TINY_SDAR_MOE, 11, {}),
}


def make(pool, k):
    cfg, seed, pages = POOLS[pool]
    eng = served_path.make_engine(
        cfg, served_path.params_of(cfg, seed),
        BlockManagerConfig(total_pages=128, page_size=PS, **pages),
        lanes=LANES, decode_steps_per_iter=k,
    )
    eng.obs_step_timing = True
    return eng


def closed_loop(eng, callers=2 * LANES, turns=3):
    """Every caller sends its next request when the one before is answered;
    two documents of 30 tokens are shared, a request is one of them and a
    few tokens of its own. Budgets differ, so lanes end one at a time, and
    with two callers a lane somebody always waits. Returns, in the order
    the requests were sent, (tokens, cached prompt tokens)."""
    vocab = 200  # under every preset's special ids
    docs = [prompt(70 + d, 30, vocab) for d in range(2)]
    sent, owner = [], {}

    def send(caller, turn):
        n = caller * turns + turn
        seq = eng.add_request(
            docs[n % 2] + prompt(100 + n, 3 + n % 5, vocab),
            SamplingParams(max_new_tokens=(5, 9, 6, 12, 7)[n % 5]),
            request_id=f"c{caller}t{turn}",
        )
        sent.append(seq)
        owner[seq.request_id] = (caller, turn)

    for caller in range(callers):
        send(caller, 0)
    while eng.has_work:
        for seq in eng.step():
            caller, turn = owner[seq.request_id]
            if turn + 1 < turns:
                send(caller, turn + 1)
        assert len(eng.scheduler.running) <= LANES
    assert all(s.error is None and s.is_finished() for s in sent)
    assert [s.num_generated for s in sent] == [
        s.sampling.max_new_tokens for s in sent]
    return [(list(s.generated_tokens), s.num_cached_prompt) for s in sent]


def pools_of(eng):
    """The final accounting of every pool the engine has."""
    bm = eng.block_manager
    assert eng._prefill_ahead is None and eng._inflight is None
    assert all(info.ref_count == 0 for info in bm._pages.values())
    out = {"free": bm.num_free, "cached": bm.num_cached_pages}
    if bm.window is not None:
        out.update(window_free=bm.window.num_free, window_held=bm.window.num_held,
                   **bm.window.stats)
    if bm.state is not None:
        assert not any(bm.state._pins.values())
        out.update(eng.state_pool_stats())
    return out


@pytest.mark.parametrize("pool,k", [
    ("plain", 1), ("plain", 3), ("window", 2), ("snapshots", 2),
    ("conv-state", 2), ("latent", 2), ("blocks", 1),
])
def test_a_closed_loop_gives_what_the_engine_that_waits_gives(pool, k, monkeypatch):
    runs = []
    for never in (False, True):
        if never:
            never_admits_ahead(monkeypatch)
        eng = make(pool, k)
        answers = closed_loop(eng)
        st = eng.step_stats
        stood = st["admit_attempts"] - st["admit_rollbacks"]
        assert stood == len(answers)
        if never:
            assert st["admit_ahead"] == 0
        else:
            # all but the first to take the lanes and a few that found a
            # lane free went ahead
            assert st["admit_ahead"] >= len(answers) // 2
        runs.append((answers, pools_of(eng), stood))
    assert any(cached for _, cached in runs[0][0])  # the documents were hit
    assert runs[0] == runs[1]
