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
  ids as a vector and as a burst's output.

The engine's side (its rng, what a dispatch uploads, the programs a warm-up
compiles) is ``tests/test_packed_inputs_engine.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from llm_d_kv_cache_manager_tpu.models import (
    TINY_LLAMA,
    TINY_QWEN3_MOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.ops.sampling import (
    pack_sampling_params,
    sample_tokens,
    sample_tokens_packed,
    unpack_sampling_params,
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
