"""A row that holds no token chooses no expert.

``llama._moe_mlp_routed`` takes the mask a prefill body already has: a slot
that is not valid is in no expert's group, so the group sizes sum to ``k``
times the real tokens and the grouped matmuls visit the real rows' tiles
alone. Held here on the CPU in float32, with ``ragged_dot`` and with both
kernels in interpret mode: the real rows of a padded ``[8, w]`` dispatch read
what they read alone and what they read with no mask; what the kernels leave
past the last group (undefined: poisoned here with NaN) reaches nothing; the
count of experts is the real rows'. The whole programs (a padded prefill, an
idle lane of ``denoise_steps``) are in ``tests/test_moe_padding_programs.py``,
the presets, the lengths and the poisoning both files use in
``tests/moe_padding.py``."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_QWEN3_MOE, llama
from moe_padding import (
    LENGTHS,
    PRESETS,
    ROWS,
    TOL,
    WIDTH,
    poisoned_grouped_matmul,
)


def _cfg(preset: str, gmm: str):
    return dataclasses.replace(PRESETS[preset], moe_gmm=gmm)


@functools.lru_cache(maxsize=None)
def _routed_layer(cfg, seed=0, **init):
    """(kept a process: the eighteen cases of a preset read one tree)"""
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


grouped = pytest.fixture(poisoned_grouped_matmul)


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
