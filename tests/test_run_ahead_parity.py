"""One decode dispatch ahead (``Engine._next_schedule_decided``): greedy
outputs under the rule equal, token for token, those of the same engine whose
rule is patched to "never", over every edge a chain meets (``both`` /
``never_ahead`` of ``tests/run_ahead.py``). The rule itself is in
``tests/test_run_ahead.py``.
"""

import jax
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_MLA_MOE, llama
from llm_d_kv_cache_manager_tpu.server import SamplingParams, SchedulerConfig
from run_ahead import both, fill as _fill, make_engine as _engine, prompt as _prompt



# -- parity with the engine that never runs ahead ----------------------------------
def _tokens(seqs):
    assert all(s.error is None for s in seqs)
    return [list(s.generated_tokens) for s in seqs]


def _steady(eng):
    seqs = _fill(eng, 2, new=17) + _fill(eng, 2, new=6, seed=30)
    eng.run_until_complete()
    return _tokens(seqs)


def _budgets(eng):
    # unlike budgets: every finish frees a lane for one who waits, and
    # 13 % 3, 7 % 3 != 0 cut a burst short
    seqs = [
        eng.add_request(_prompt(40 + i, 8 + i), SamplingParams(max_new_tokens=n))
        for i, n in enumerate((13, 7, 22, 5, 9))
    ]
    eng.run_until_complete()
    return _tokens(seqs)


def _stop_mid_chain(eng):
    probe = _engine(lanes=1)
    p = probe.add_request(_prompt(50, 9), SamplingParams(max_new_tokens=8))
    probe.run_until_complete()
    stop = p.generated_tokens[5]
    assert stop not in p.generated_tokens[:5]
    seqs = [
        eng.add_request(
            _prompt(50, 9),
            SamplingParams(max_new_tokens=30, stop_token_ids=(stop,)),
        ),
        eng.add_request(_prompt(51, 9), SamplingParams(max_new_tokens=16)),
        eng.add_request(_prompt(52, 9), SamplingParams(max_new_tokens=6)),
    ]
    eng.run_until_complete()
    assert seqs[0].generated_tokens[-1] == stop
    assert len(seqs[0].generated_tokens) == 6
    return _tokens(seqs)


def _abort_in_flight(eng):
    seqs = _fill(eng, 2, new=24, seed=60)
    waiting = eng.add_request(_prompt(63, 9), SamplingParams(max_new_tokens=6))
    for _ in range(4):
        eng.step()
    free = eng.block_manager.num_free
    gone = eng.abort(seqs[0].request_id)
    assert gone is seqs[0] and eng._inflight is None
    assert eng.block_manager.num_free > free
    eng.run_until_complete()
    assert gone.finish_reason == "abort"
    # an abort lands between two steps: what the lane had by then differs
    # by the burst in flight, which the abort commits first; its
    # batchmates and its successor may not differ at all
    return _tokens([seqs[1], waiting])


def _tight_pool(eng):
    from llm_d_kv_cache_manager_tpu.server.block_manager import AllocationError

    bm = eng.block_manager
    orig, refused = bm.reserve_slots, set()

    def spy(seq, n):
        try:
            return orig(seq, n)
        except AllocationError:
            refused.add(n)
            raise

    bm.reserve_slots = spy
    seqs = _fill(eng, 3, new=12, seed=70, plen=8)
    eng.run_until_complete()
    k = eng.config.decode_steps_per_iter
    # the single reservation was refused (a preemption), and under the
    # rule the double one too: the chain degrades to a step that waits
    assert k in refused, "pool never under pressure"
    if eng.step_stats["decode_chained_dispatches"]:
        assert 2 * k in refused, "the double reservation never degraded"
    return _tokens(seqs)


def _warm_prefix(eng):
    # pages registered while a burst is in flight cover committed tokens
    # only: the same prompt again hits them and gives the same tokens
    p = _prompt(80, 16)
    a = eng.add_request(p, SamplingParams(max_new_tokens=9))
    other = eng.add_request(_prompt(81, 9), SamplingParams(max_new_tokens=9))
    eng.run_until_complete()
    b = eng.add_request(p, SamplingParams(max_new_tokens=9))
    eng.run_until_complete()
    assert b.num_cached_prompt > 0
    return _tokens([a, other, b])


@pytest.fixture(scope="module")
def mla_params():
    return llama.init_params(jax.random.PRNGKey(11), TINY_MLA_MOE)


CASES = {
    "steady-k1": (dict(), _steady),
    "steady-k3": (dict(decode_steps_per_iter=3), _steady),
    "budgets-k1": (dict(), _budgets),
    "budgets-k3": (dict(decode_steps_per_iter=3), _budgets),
    "stop-token-k1": (dict(), _stop_mid_chain),
    "stop-token-k2": (dict(decode_steps_per_iter=2), _stop_mid_chain),
    "abort-k1": (dict(), _abort_in_flight),
    "abort-k3": (dict(decode_steps_per_iter=3), _abort_in_flight),
    "tight-pool-k1": (dict(lanes=3, total_pages=13), _tight_pool),
    "tight-pool-k4": (
        dict(lanes=3, total_pages=12, decode_steps_per_iter=4), _tight_pool,
    ),
    "warm-prefix-k2": (dict(decode_steps_per_iter=2), _warm_prefix),
    "int8-pool-k1": (dict(kv_quant_hbm="int8"), _steady),
    "int8-pool-k2": (
        dict(kv_quant_hbm="int8", decode_steps_per_iter=2), _budgets,
    ),
    "chunked-prefill": (
        dict(
            decode_steps_per_iter=2,
            scheduler=SchedulerConfig(
                max_prefill_batch=4, chunked_prefill_tokens=8
            ),
        ),
        _budgets,
    ),
    "spec-fall-through": (
        dict(spec_decode="prompt_lookup", spec_k=3, spec_ngram=2), _steady,
    ),
    "tp2": (dict(tp=2), _budgets),
}


@pytest.mark.parametrize("case", list(CASES))
def test_greedy_parity_with_the_engine_that_waits(case, monkeypatch):
    kw, drive = CASES[case]
    # speculation drains before it proposes, so its fall-through into the
    # fused path never finds a burst to chain from
    both(
        lambda: _engine(**kw), drive, monkeypatch,
        chained=case != "spec-fall-through",
    )


@pytest.mark.parametrize("k", [1, 2])
def test_greedy_parity_on_a_latent_pool(k, mla_params, monkeypatch):
    def make():
        return _engine(
            lanes=2, total_pages=96, model=TINY_MLA_MOE, params=mla_params,
            max_model_len=128, prefill_bucket=16, decode_steps_per_iter=k,
        )

    def drive(eng):
        seqs = [
            eng.add_request(
                np.random.default_rng(i).integers(1, 200, 9 + i).tolist(),
                SamplingParams(max_new_tokens=n),
            )
            for i, n in enumerate((11, 6, 8))
        ]
        eng.run_until_complete()
        return _tokens(seqs)

    both(make, drive, monkeypatch)


def test_sampled_lanes_keep_their_distribution_and_their_count(monkeypatch):
    """temperature > 0: the streams are not bit-identical to the engine
    that waits (a discarded surplus burst takes a split of the engine's
    key), but every lane ends at its budget and a greedy batchmate's
    tokens do not move."""
    def drive(eng):
        greedy = eng.add_request(_prompt(90, 9), SamplingParams(max_new_tokens=14))
        sampled = eng.add_request(
            _prompt(91, 9),
            SamplingParams(max_new_tokens=9, temperature=0.8, top_k=8),
        )
        eng.run_until_complete()
        assert sampled.num_generated == 9 and sampled.error is None
        return _tokens([greedy])

    both(lambda: _engine(lanes=2), drive, monkeypatch)
