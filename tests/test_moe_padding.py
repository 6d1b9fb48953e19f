"""A row that holds no token chooses no expert.

``llama._moe_mlp_routed`` takes the mask a prefill body already has: a slot
that is not valid is in no expert's group, so the group sizes sum to ``k``
times the real tokens and the grouped matmuls visit the real rows' tiles
alone. Held here on the CPU in float32, with ``ragged_dot`` and with both
kernels in interpret mode: the real rows of a padded ``[8, w]`` dispatch read
what they read alone and what they read with no mask; what the kernels leave
past the last group (undefined: poisoned here with NaN) reaches nothing; the
count of experts is the real rows'; an idle lane of ``denoise_steps`` moves
no active lane's tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    TINY_LFM2_MOE,
    TINY_MLA_MOE,
    TINY_QWEN3_MOE,
    TINY_SDAR_MOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.ops import gmm as gmm_ops

ROWS, WIDTH, PS = 8, 12, 4

#: softmax routing; sigmoid routing with a bias that chooses and a shared
#: expert; the hybrid one (sigmoid, convolution layers beside attention)
PRESETS = {
    "softmax": TINY_QWEN3_MOE,
    "sigmoid-shared": TINY_MLA_MOE,
    "hybrid": TINY_LFM2_MOE,
}
#: real tokens of each row, for 1, 3 and 8 real rows of the 8
LENGTHS = {
    1: [7, 0, 0, 0, 0, 0, 0, 0],
    3: [12, 5, 1, 0, 0, 0, 0, 0],
    8: [12, 12, 12, 12, 12, 12, 12, 12],
}
TOL = dict(atol=2e-5, rtol=2e-4)


def _cfg(preset: str, gmm: str):
    return dataclasses.replace(PRESETS[preset], moe_gmm=gmm)


def _routed_layer(cfg, seed=0, **init):
    params = llama.init_params(jax.random.PRNGKey(seed), cfg, **init)
    layer = next(lay for lay in params["layers"] if "router" in lay)
    if "router_bias" in layer:  # a bias that really chooses
        layer = dict(layer, router_bias=jnp.asarray(
            np.random.default_rng(seed).normal(size=cfg.n_experts) * 0.3,
            jnp.float32,
        ))
    return layer


def _inputs(cfg, real_rows: int, seed=1):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(
        rng.normal(size=(ROWS, WIDTH, cfg.hidden_size)), jnp.float32
    )
    lengths = np.asarray(LENGTHS[real_rows])
    valid = np.arange(WIDTH)[None, :] < lengths[:, None]
    return x, lengths, valid


@pytest.fixture
def grouped(monkeypatch):
    """Every ``group_sizes`` the routed FFN hands the grouped matmul while
    the fixture is live, and the rows past the last group poisoned with NaN
    in what it returns: what a kernel may leave there."""
    seen = []
    real = gmm_ops.grouped_matmul

    def poisoning(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        if not isinstance(group_sizes, jax.core.Tracer):
            seen.append(np.asarray(group_sizes))
        past = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(gmm_ops, "grouped_matmul", poisoning)
    return seen


@pytest.mark.parametrize("real_rows", [1, 3, 8])
@pytest.mark.parametrize("gmm", ["xla", "kernel"])
@pytest.mark.parametrize("preset", list(PRESETS))
def test_real_rows_read_what_they_read_alone(preset, gmm, real_rows, grouped):
    cfg = _cfg(preset, gmm)
    layer = _routed_layer(cfg)
    x, lengths, valid = _inputs(cfg, real_rows)
    k = cfg.n_experts_per_tok

    got = np.asarray(llama._mlp(layer, cfg, x, interpret=True, valid=valid))
    assert np.isfinite(got).all()
    # every grouped matmul of the layer saw the real tokens' rows alone
    assert len(grouped) == 3
    for sizes in grouped:
        assert sizes.shape == (cfg.n_experts,)
        assert sizes.sum() == k * valid.sum()

    unmasked = np.asarray(llama._mlp(layer, cfg, x, interpret=True))
    np.testing.assert_allclose(got[valid], unmasked[valid], **TOL)
    for row, n in enumerate(lengths):
        if n:
            alone = llama._mlp(layer, cfg, x[row:row + 1, :n], interpret=True)
            np.testing.assert_allclose(got[row, :n], np.asarray(alone)[0], **TOL)

    # the routed part of a slot that holds no token is exactly nothing
    routed = np.asarray(
        llama._moe_mlp_routed(layer, cfg, x, interpret=True, valid=valid)
    )
    assert np.isfinite(routed).all()
    assert not routed[~valid].any()
    if real_rows < ROWS:
        assert np.abs(routed[valid]).max() > 0


@pytest.mark.parametrize("real_rows", [1, 3, 8])
def test_int8_expert_stacks_agree(real_rows, grouped):
    cfg_k = dataclasses.replace(
        TINY_QWEN3_MOE, hidden_size=128, moe_intermediate_size=128,
        moe_gmm="kernel",
    )
    cfg_x = dataclasses.replace(cfg_k, moe_gmm="xla")
    layer = _routed_layer(cfg_k, quantize="int8", quantize_experts=True)
    x, _, valid = _inputs(cfg_k, real_rows)
    out_k = np.asarray(
        llama._moe_mlp_routed(layer, cfg_k, x, interpret=True, valid=valid)
    )
    out_x = np.asarray(
        llama._moe_mlp_routed(layer, cfg_x, x, interpret=True, valid=valid)
    )
    unmasked = np.asarray(llama._moe_mlp_routed(layer, cfg_k, x, interpret=True))
    assert np.isfinite(out_k).all() and np.isfinite(out_x).all()
    np.testing.assert_allclose(out_k[valid], out_x[valid], atol=5e-3, rtol=5e-2)
    np.testing.assert_allclose(out_k[valid], unmasked[valid], **TOL)
    assert not out_k[~valid].any() and not out_x[~valid].any()


@pytest.mark.parametrize("real_rows", [1, 3, 8])
@pytest.mark.parametrize("preset", list(PRESETS))
def test_touched_counts_the_real_rows_experts(preset, real_rows):
    cfg = _cfg(preset, "xla")
    layer = _routed_layer(cfg)
    x, _, valid = _inputs(cfg, real_rows)
    _, topi = llama._moe_gates(layer, cfg, x)
    want = len(np.unique(np.asarray(topi)[valid]))
    touched = []
    llama._moe_mlp_routed(
        layer, cfg, x, interpret=True, touched=touched, valid=valid
    )
    assert [int(t) for t in touched] == [want]
    every = []
    llama._moe_mlp_routed(layer, cfg, x, interpret=True, touched=every)
    assert int(every[0]) == len(np.unique(np.asarray(topi))) >= want


def _prefill(cfg, params, tokens, lengths, gmm):
    cfg = dataclasses.replace(cfg, moe_gmm=gmm)
    rows, width = tokens.shape
    pages_a_row = -(-width // PS)
    k_pages, v_pages = llama.init_kv_pages(cfg, 1 + rows * pages_a_row, PS)
    state = llama.init_state_pages(cfg, 1 + rows * pages_a_row)
    pos = np.broadcast_to(np.arange(width, dtype=np.int32), (rows, width))
    valid = pos < np.asarray(lengths)[:, None]
    first = 1 + pages_a_row * np.arange(rows, dtype=np.int32)[:, None]
    out = llama.prefill(
        params, cfg, tokens, pos, valid, k_pages, v_pages,
        np.where(valid, first + pos // PS, 0), pos % PS,
        np.zeros((rows, 0), np.int32), np.zeros((rows,), np.int32),
        interpret=True, **({} if state is None else {"state_pages": state}),
    )
    return np.asarray(out[0], np.float32)


@pytest.mark.parametrize("gmm", ["xla", "kernel"])
@pytest.mark.parametrize("preset", list(PRESETS))
def test_a_padded_prefill_reads_its_real_rows_logits(preset, gmm, grouped):
    """The whole program: the last-token logits of three real rows among
    eight are what each row reads in a dispatch of its own, with the rows
    past the groups poisoned in every layer."""
    # a configuration of this test's own: its programs are traced here, with
    # the poison inside, and no other test's cache holds them
    cfg = dataclasses.replace(
        PRESETS[preset], rms_norm_eps=PRESETS[preset].rms_norm_eps + 3e-9
    )
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(4)
    tokens = rng.integers(1, 200, (ROWS, WIDTH)).astype(np.int32)
    lengths = LENGTHS[3]
    got = _prefill(cfg, params, tokens, lengths, gmm)
    assert np.isfinite(got[:3]).all()
    for row in range(3):
        n = lengths[row]
        alone = _prefill(cfg, params, tokens[row:row + 1, :n], [n], gmm)
        np.testing.assert_allclose(got[row], alone[0], atol=1e-4, rtol=1e-3)


def test_an_idle_lane_moves_no_active_lanes_tokens():
    cfg = TINY_SDAR_MOE
    width, lanes, table_w = cfg.block_length, 3, 4
    params = llama.init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(6)
    k_pages, v_pages = llama.init_kv_pages(cfg, 1 + lanes * table_w, PS)
    k_pages = jnp.asarray(rng.normal(size=k_pages.shape), k_pages.dtype)
    v_pages = jnp.asarray(rng.normal(size=v_pages.shape), v_pages.dtype)
    tables = 1 + np.arange(lanes * table_w, dtype=np.int32).reshape(lanes, -1)
    seq_lens = np.asarray([8, 4, 12], np.int32)

    def run(active):
        packed = np.concatenate([
            np.full((lanes, width), cfg.mask_token_id, np.int32),
            np.ones((lanes, width), np.int32), tables, seq_lens[:, None],
            np.zeros((lanes, 1), np.int32),  # step
            np.full((lanes, 1), 2, np.int32),  # denoising steps
            np.zeros((lanes, 1), np.int32),  # top_k
            np.asarray(active, np.int32)[:, None],
        ], axis=1)
        fparams = np.tile(np.asarray([[0.9, 0.0, 1.0]], np.float32), (lanes, 1))
        out, _, _ = llama.denoise_steps(
            params, cfg, packed, fparams, jnp.copy(k_pages), jnp.copy(v_pages),
            jax.random.PRNGKey(0), page_size=PS, table_w=table_w,
            attn_impl="xla", interpret=True,
        )
        return np.asarray(out)

    every, two = run([1, 1, 1]), run([1, 0, 1])
    np.testing.assert_array_equal(two[[0, 2], :-1], every[[0, 2], :-1])
    # the idle lane fixes nothing and its rows choose no expert
    np.testing.assert_array_equal(two[1, :width], cfg.mask_token_id)
    assert 0 < two[0, -1] <= every[0, -1]
