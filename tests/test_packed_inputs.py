"""A dispatch stages its inputs in one upload (``Engine._stage``).

``llama.decode_steps`` takes every per-lane input but the ids as ONE int32
array (``pack_decode_inputs``: block table, position, length, and the
sampling parameters with the two floats as their bits) and
``llama.prefill_packed`` its seven arrays as one (``pack_prefill_inputs``);
both slice it apart inside the program. Held here:

- the packed programs return the tokens and pools the unpacked operands
  give (``llama.decode_step`` + ``sample_tokens``, bit for bit;
  ``llama.prefill``, to the rounding of a row computed alone), for a dense,
  a sparse, a latent and a hybrid model, greedy and with a sampled lane,
  ids as a vector and as a burst's output;
- the engine's rng: an all-greedy run leaves it where it was, a dispatch
  with a sampled lane splits it once;
- what a dispatch uploads (a patched ``Engine._dev`` counts), chained and
  unchained, and ``step_stats``' two counters of the same;
- the programs a warm-up compiles: one a (chunk, context) bucket pair.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    TINY_LFM2_MOE,
    TINY_LLAMA,
    TINY_MLA_MOE,
    TINY_QWEN3_MOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.ops.sampling import (
    pack_sampling_params,
    sample_tokens,
    sample_tokens_packed,
    unpack_sampling_params,
)
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)

PS = 4
PRESETS = {
    "dense": TINY_LLAMA, "moe": TINY_QWEN3_MOE, "latent": TINY_MLA_MOE,
    "hybrid": TINY_LFM2_MOE,
}
MODELS = pytest.mark.parametrize("kind", list(PRESETS))


@pytest.fixture(scope="module")
def weights():
    made = {}

    def of(kind):
        if kind not in made:
            made[kind] = llama.init_params(jax.random.PRNGKey(3), PRESETS[kind])
        return made[kind]

    return of


# -- the layouts ---------------------------------------------------------------
def test_sampling_params_come_back_bit_for_bit():
    temperature = np.asarray([0.0, 0.7, 1.0, -0.0, 1e-30], np.float32)
    top_k = np.asarray([0, 5, 40, 1, 2**31 - 1], np.int32)
    top_p = np.asarray([1.0, 0.95, 0.1, np.nextafter(1, 0), 0.0], np.float32)
    packed = pack_sampling_params(temperature, top_k, top_p)
    assert packed.shape == (5, 3) and packed.dtype == np.int32
    got = jax.jit(unpack_sampling_params)(packed)
    for want, have in zip((temperature, top_k, top_p), got):
        assert have.dtype == want.dtype
        assert np.asarray(have).tobytes() == want.tobytes()


def test_decode_inputs_come_back_bit_for_bit():
    rng = np.random.default_rng(0)
    tables = rng.integers(0, 99, (4, 6)).astype(np.int32)
    positions, seq_lens = np.asarray([2, 0, 9, 7]), np.asarray([3, 0, 10, 8])
    temperature = np.asarray([0.0, 0.0, 0.8, 1.3], np.float32)
    top_k, top_p = np.asarray([0, 0, 7, 1]), np.asarray([1, 1, 0.9, 0.5], np.float32)
    packed = llama.pack_decode_inputs(
        positions, tables, seq_lens, temperature, top_k, top_p)
    assert packed.shape == (4, 6 + llama.DECODE_PACKED_TAIL) and packed.dtype == np.int32
    got = jax.jit(llama._unpack_decode_inputs)(packed)
    want = (tables, positions, seq_lens, temperature, top_k, top_p)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), w)
    assert got[3].dtype == jnp.float32 and got[5].dtype == jnp.float32


# -- the programs against the unpacked operands --------------------------------
def _pools(cfg, pages=16):
    k_pages, v_pages = llama.init_kv_pages(cfg, pages, PS)
    state = llama.init_state_pages(cfg, pages)
    return k_pages, v_pages, ({} if state is None else {"state_pages": state})


def _prefill_operands(cfg, warm: bool):
    """Two rows of a chunk of 8 (7 and 5 valid tokens); ``warm``: behind
    one resident page each (pages 1 and 5), else cold and no context."""
    rng = np.random.default_rng(11)
    n_valid, start = np.asarray([7, 5]), (PS if warm else 0)
    tokens = rng.integers(1, cfg.vocab_size, (2, 8)).astype(np.int32)
    positions = (start + np.arange(8))[None, :].repeat(2, 0).astype(np.int32)
    valid = np.arange(8)[None, :] < n_valid[:, None]
    tables = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    page_ids = np.take_along_axis(tables, positions // PS, axis=1)
    ctx_bt = tables[:, : (1 if warm else 0)]
    ctx_lens = np.full((2,), start, np.int32)
    return (tokens, positions, valid, page_ids, positions % PS, ctx_bt,
            ctx_lens), tables, start + n_valid


def _prefilled(cfg, params, packed: bool, warm=False):
    """Pools after a prefill (the resident page first where ``warm``)
    through ``prefill`` or ``prefill_packed``; returns (logits, pools)."""
    k_pages, v_pages, state = _pools(cfg)
    kw = dict(attn_impl="xla", interpret=True)
    outs = []
    for ops in ([_resident_page(cfg)] if warm else []) + [
        _prefill_operands(cfg, warm)[0]
    ]:
        t, p, ok, pg, sl, bt, cl = ops
        if packed:
            out = llama.prefill_packed(
                params, cfg, llama.pack_prefill_inputs(*ops), k_pages, v_pages,
                chunk=t.shape[1], **kw, **state)
        else:
            out = llama.prefill(
                params, cfg, t, p, ok, k_pages, v_pages, pg, sl, bt, cl,
                **kw, **state)
        logits, k_pages, v_pages, *rest = out
        if state:
            state = {"state_pages": rest[0]}
        outs.append(logits)
    return outs[-1], (k_pages, v_pages, state)


def _resident_page(cfg):
    """The page of context the warm case attends: 4 tokens a row."""
    rng = np.random.default_rng(12)
    tokens = np.zeros((2, 8), np.int32)
    tokens[:, :PS] = rng.integers(1, cfg.vocab_size, (2, PS))
    positions = np.arange(8)[None, :].repeat(2, 0).astype(np.int32)
    valid = positions < PS
    page_ids = np.where(valid, np.asarray([[1], [5]]), 0).astype(np.int32)
    return (tokens, positions, valid, page_ids, positions % PS,
            np.zeros((2, 0), np.int32), np.zeros((2,), np.int32))


#: ``prefill_packed`` computes its rows one at a time (PR 42): a float32
#: matmul over one row rounds otherwise than over two, 1.2e-6 at most here
ROW_TOL = dict(rtol=1e-5, atol=1e-5)


def _same_pools(a, b):
    (ka, va, sa), (kb, vb, sb) = a, b
    np.testing.assert_allclose(np.asarray(ka), np.asarray(kb), **ROW_TOL)
    np.testing.assert_allclose(np.asarray(va), np.asarray(vb), **ROW_TOL)
    assert sa.keys() == sb.keys()
    for name in sa:
        np.testing.assert_allclose(
            np.asarray(sa[name]), np.asarray(sb[name]), **ROW_TOL)


@MODELS
@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
def test_prefill_packed_is_prefill(kind, warm, weights):
    cfg, params = PRESETS[kind], weights(kind)
    want, want_pools = _prefilled(cfg, params, packed=False, warm=warm)
    got, got_pools = _prefilled(cfg, params, packed=True, warm=warm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **ROW_TOL)
    assert np.abs(np.asarray(want)).max() > 0
    _same_pools(got_pools, want_pools)


@MODELS
@pytest.mark.parametrize("ids", ["vector", "burst"])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "one-sampled"])
def test_decode_steps_is_decode_step_and_the_sampler(kind, ids, sampled, weights):
    """One fused step over the packed operand against the logits API over
    the unpacked ones and ``sample_tokens`` with the burst's first key.
    ``burst``: the ids arrive as a burst's ``[lanes, 1 + k]`` output on the
    device (a chained dispatch), whose last column counts."""
    cfg, params = PRESETS[kind], weights(kind)
    _, tables, lens = _prefill_operands(cfg, warm=False)
    tokens = np.asarray([17, 29], np.int32)
    temperature = np.asarray([0.0, 0.9 if sampled else 0.0], np.float32)
    top_k, top_p = np.asarray([0, 12], np.int32), np.asarray([1.0, 0.9], np.float32)
    key = jax.random.PRNGKey(5)

    _, (k_pages, v_pages, state) = _prefilled(cfg, params, packed=False)
    logits, *want_pools = llama.decode_step(
        params, cfg, tokens, lens, k_pages, v_pages, tables, lens + 1,
        page_size=PS, interpret=True, **state)
    want = sample_tokens(
        logits.astype(jnp.float32), temperature, top_k, top_p,
        jax.random.split(key, 1)[0])

    _, (k_pages, v_pages, state) = _prefilled(cfg, params, packed=False)
    fed = tokens if ids == "vector" else jnp.stack(
        [jnp.zeros_like(tokens), jnp.asarray(tokens)], axis=1)
    got, *got_pools = llama.decode_steps(
        params, cfg, fed,
        llama.pack_decode_inputs(lens, tables, lens + 1, temperature, top_k, top_p),
        k_pages, v_pages, key, page_size=PS, num_steps=1, interpret=True,
        **state)
    np.testing.assert_array_equal(np.asarray(got)[:, 1], np.asarray(want))
    for have, ref in zip(got_pools, want_pools):
        np.testing.assert_array_equal(np.asarray(have), np.asarray(ref))
    assert len(got_pools) == len(want_pools) == 2 + bool(state)


def test_first_tokens_sampler_takes_the_packed_parameters():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(4, 64)), jnp.bfloat16)
    temperature = np.asarray([0.0, 0.8, 1.0, 0.0], np.float32)
    top_k, top_p = np.asarray([0, 5, 0, 3], np.int32), np.asarray([1, 0.9, 0.7, 1], np.float32)
    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        np.testing.assert_array_equal(
            np.asarray(sample_tokens_packed(
                logits, pack_sampling_params(temperature, top_k, top_p), key)),
            np.asarray(sample_tokens(
                logits.astype(jnp.float32), temperature, top_k, top_p, key)),
        )


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
    the sampler's parameters; ``step_stats`` counts the same."""
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
    assert by["_run_decode_fused+chained"] == {1}
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
