"""A dispatch stages its inputs in one upload (``Engine._stage``), the
engine's side (the packed programs against the unpacked operands:
``tests/test_packed_inputs.py``):

- the engine's rng: an all-greedy run leaves it where it was, a dispatch
  with a sampled lane splits it once;
- what a dispatch uploads (a patched ``Engine._dev`` counts), chained and
  unchained, and ``step_stats``' two counters of the same;
- the programs a warm-up compiles: one a (chunk, context) bucket pair.
"""

import jax
import numpy as np
import pytest

import served_path
from llm_d_kv_cache_manager_tpu.models import (
    TINY_LLAMA,
    TINY_QWEN3_MOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)

PS = 4
#: (the latent and the hybrid preset at one of each kind of layer: how a
#: dispatch's inputs are packed does not see the depth)
PRESETS = {
    "dense": TINY_LLAMA, "moe": TINY_QWEN3_MOE,
    "latent": served_path.ONE_OF_EACH_MLA,
    "hybrid": served_path.ONE_OF_EACH_LFM2,
}
MODELS = pytest.mark.parametrize("kind", list(PRESETS))


# -- the engine ----------------------------------------------------------------
def _engine(kind="dense", lanes=2, k=1, **kw):
    kw.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    kw.setdefault("prefill_bucket", 8)
    return Engine(
        EngineConfig(
            model=PRESETS[kind],
            block_manager=BlockManagerConfig(total_pages=64, page_size=PS),
            max_model_len=64, decode_batch_size=lanes,
            decode_steps_per_iter=k, interpret=True, **kw,
        ),
    )


def _prompt(seed, n, vocab=256):
    return list(map(int, np.random.default_rng(seed).integers(1, vocab, n)))


def _key_bits(key) -> bytes:
    return np.asarray(jax.random.key_data(key)).tobytes()


@MODELS
def test_an_all_greedy_run_leaves_the_rng_where_it_was(kind):
    eng = _engine(kind, lanes=2)
    before = _key_bits(eng._rng)
    seqs = [
        eng.add_request(_prompt(i, 9 + i), SamplingParams(max_new_tokens=6))
        for i in range(3)
    ]
    eng.run_until_complete()
    assert all(s.num_generated == 6 for s in seqs)
    assert _key_bits(eng._rng) == before


@pytest.mark.parametrize("k", [1, 3])
def test_a_dispatch_with_a_sampled_lane_splits_the_rng_once(k):
    """A greedy lane and a sampled one, a lane free (nothing runs ahead):
    the prefill's sampler and every decode dispatch while the sampled lane
    runs split once each, as each did before; once it has finished, the
    greedy lane's dispatches split nothing."""
    eng = _engine(lanes=4, k=k)
    eng.obs_step_timing = True
    key = eng._rng
    eng.add_request(_prompt(1, 9), SamplingParams(max_new_tokens=20))
    eng.add_request(
        _prompt(2, 9), SamplingParams(max_new_tokens=7, temperature=0.9, top_k=8))
    eng.run_until_complete()
    st = eng.step_stats
    assert 0 < st["decode_sampled_dispatches"] < st["decode_dispatches"]
    for _ in range(1 + st["decode_sampled_dispatches"]):  # 1: the prefill's
        key = jax.random.split(key)[0]
    assert _key_bits(eng._rng) == _key_bits(key)


def _count_uploads(monkeypatch):
    """Patches ``Engine._dev`` to count; returns the list of counts a
    ``_run_prefill`` / ``_run_decode_fused`` call made: (what, n)."""
    calls, n = [], [0]
    dev = Engine._dev

    def counted(self, x, dtype=None):
        n[0] += 1
        return dev(self, x, dtype)

    monkeypatch.setattr(Engine, "_dev", counted)
    for what in ("_run_prefill", "_run_decode_fused", "_run_decode_spec",
                 "_run_decode_block"):
        inner = getattr(Engine, what)

        def around(self, *a, _inner=inner, _what=what, **kw):
            start, chained = n[0], self._inflight is not None
            out = _inner(self, *a, **kw)
            calls.append((_what + ("+chained" if chained else ""), n[0] - start))
            return out

        monkeypatch.setattr(Engine, what, around)
    return calls


@MODELS
def test_what_a_dispatch_uploads(kind, monkeypatch):
    """Lanes full and budgets far: dispatches chain. An unchained decode
    dispatch uploads its ids and the packed array, a chained one the packed
    array alone (its ids lie on the device), a prefill its packed array and
    the sampler's parameters; ``step_stats`` counts the same. The third
    request waits for a lane and is admitted behind the burst that ends one
    (``Engine._admit_ahead``): that call of ``_run_decode_fused`` holds its
    prefill's two uploads too."""
    calls = _count_uploads(monkeypatch)
    eng = _engine(kind, lanes=2)
    eng.obs_step_timing = True
    for i in range(3):
        eng.add_request(_prompt(i, 9 + i), SamplingParams(max_new_tokens=12))
    eng.run_until_complete()
    by = {}
    for what, n in calls:
        by.setdefault(what, set()).add(n)
    assert by["_run_prefill"] == {2}
    assert by["_run_decode_fused"] <= {0, 2}  # 0: every lane had finished
    assert by["_run_decode_fused+chained"] == {1, 1 + 2}
    st = eng.step_stats
    assert st["decode_chained_dispatches"] > 0
    assert st["decode_uploads"] == (
        2 * st["decode_dispatches"] - st["decode_chained_dispatches"])
    assert st["prefill_uploads"] == 2 * eng.prefill_stats["dispatches"]
    assert st["decode_uploads"] + st["prefill_uploads"] == sum(n for _, n in calls)


def test_what_the_other_two_decode_paths_upload(monkeypatch):
    from llm_d_kv_cache_manager_tpu.models import TINY_SDAR_MOE

    calls = _count_uploads(monkeypatch)
    spec = _engine(spec_decode="prompt_lookup", spec_k=2)
    spec.add_request([5, 6, 7, 8] * 4, SamplingParams(max_new_tokens=8))
    spec.run_until_complete()
    block = Engine(EngineConfig(
        model=TINY_SDAR_MOE, interpret=True, max_model_len=64,
        block_manager=BlockManagerConfig(total_pages=64, page_size=PS),
        decode_batch_size=2, prefill_bucket=8,
        scheduler=SchedulerConfig(max_prefill_batch=4)))
    block.add_request(
        _prompt(1, 9, TINY_SDAR_MOE.vocab_size - 8),
        SamplingParams(max_new_tokens=8))
    block.run_until_complete()
    by = {}
    for what, n in calls:
        by.setdefault(what, set()).add(n)
    assert by["_run_decode_spec"] <= {0, 2} and 2 in by["_run_decode_spec"]
    assert by["_run_decode_block"] == {2}
    # a block-diffusion prefill samples nothing: its packed array alone
    assert by["_run_prefill"] == {1, 2}


def test_counters_are_off_with_the_switch():
    eng = _engine()
    eng.add_request(_prompt(1, 9), SamplingParams(max_new_tokens=4))
    eng.run_until_complete()
    assert eng.step_stats["decode_uploads"] == 0
    assert eng.step_stats["prefill_uploads"] == 0


def test_a_warm_up_compiles_one_program_a_bucket_pair():
    """Two chunk widths x two context widths: four ``prefill_packed``
    programs, as ``prefill`` had (the packed width alone would not tell
    (16, 8) from another pair of the same sum: ``chunk`` is static), and
    a second pass over the same shapes compiles nothing."""
    def warm_up():
        eng = _engine(
            lanes=4, prefill_ctx_bucket=2,
            scheduler=SchedulerConfig(max_prefill_batch=1))
        shared = _prompt(50, 16)
        # (chunk 8, ctx 0), (chunk 16, ctx 0): cold, one a step
        for n in (7, 15):
            eng.add_request(_prompt(60 + n, n), SamplingParams(max_new_tokens=1))
            eng.run_until_complete()
        eng.add_request(shared + _prompt(70, 3), SamplingParams(max_new_tokens=1))
        eng.run_until_complete()  # leaves the shared pages cached
        # (chunk 8, ctx 4), (chunk 16, ctx 4): warm behind the shared pages
        for n in (5, 12):
            seq = eng.add_request(
                shared + _prompt(80 + n, n), SamplingParams(max_new_tokens=1))
            eng.run_until_complete()
            assert seq.num_cached_prompt == 16
        return eng

    llama.prefill_packed.clear_cache()
    shapes = set()
    pack = llama.pack_prefill_inputs

    def spy(tokens, positions, valid, page_ids, slot_ids, block_tables, ctx_lens):
        shapes.add((tokens.shape[1], block_tables.shape[1]))
        return pack(tokens, positions, valid, page_ids, slot_ids,
                    block_tables, ctx_lens)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(llama, "pack_prefill_inputs", spy)
        warm_up()
    assert shapes == {(8, 0), (16, 0), (24, 0), (8, 4), (16, 4)}
    assert llama.prefill_packed._cache_size() == len(shapes)
    warm_up()
    assert llama.prefill_packed._cache_size() == len(shapes)
