"""The FFN side of the latent-attention model: a sigmoid router whose bias
chooses and does not weigh, a shared expert, a leading dense layer, each read
from the layer's own parameters, against
``chipbench/references/mla_moe.forward`` at ``TINY_MLA_MOE`` in float32. The
attention side is in ``tests/test_mla.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_MLA_MOE, llama
from served_path import prompt_of, rel_err

CFG = TINY_MLA_MOE
PS = 4
TOL = 1e-4
REF = chip_reference.load("mla_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 11)


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return served_path.reference_logits(REF, params, cfg, tokens)


def served(params, rows, steps, attn_impl, cfg=CFG):
    got, fed, _ = served_path.served(
        params, cfg, rows, steps, attn_impl, page_size=PS)
    return got, fed


# -- the router, the shared expert, the dense layer ----------------------------
def _gates(layer, x, **changes):
    return llama._moe_gates(layer, dataclasses.replace(CFG, **changes), x)


def test_the_bias_chooses_and_does_not_weigh(params):
    layer = dict(params["layers"][1])
    x = jnp.asarray(np.random.default_rng(1).normal(size=(7, CFG.hidden_size)),
                    jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ layer["router"]))
    # a bias that lifts experts 6 and 7 over every score chooses them
    layer["router_bias"] = jnp.zeros(8).at[jnp.asarray([6, 7])].set(5.0)
    topv, topi = _gates(layer, x)
    assert (np.sort(np.asarray(topi), axis=1) == [6, 7]).all()
    picked = np.take_along_axis(scores, np.asarray(topi), axis=1)
    want = picked / picked.sum(1, keepdims=True) * CFG.routed_scaling_factor
    np.testing.assert_allclose(topv, want, rtol=1e-6)
    # the scaling factor and the renormalisation are the configuration's
    plain, _ = _gates(layer, x, routed_scaling_factor=1.0)
    np.testing.assert_allclose(np.asarray(plain).sum(1), 1.0, rtol=1e-6)
    raw, _ = _gates(layer, x, routed_scaling_factor=1.0, norm_topk_prob=False)
    np.testing.assert_allclose(raw, picked, rtol=1e-6)
    # two groups of four, the better one kept: experts 6 and 7 lie in the
    # second and are still the choice; with the bias on 1 and 6 instead, the
    # group of 6 wins (at least 8 against at most 5 + 1 + 1) and 1 is left out
    _, grouped = _gates(layer, x, n_group=2, topk_group=1)
    assert (np.sort(np.asarray(grouped), axis=1) == [6, 7]).all()
    layer["router_bias"] = jnp.zeros(8).at[1].set(5.0).at[6].set(8.0)
    _, split = _gates(layer, x)
    assert (np.sort(np.asarray(split), axis=1) == [1, 6]).all()
    _, kept = _gates(layer, x, n_group=2, topk_group=1)
    assert (np.asarray(kept) >= 4).all() and (np.asarray(kept) == 6).any(1).all()
    with pytest.raises(ValueError, match="n_group=3"):
        _gates(layer, x, n_group=3)


def test_a_layers_ffn_is_read_from_its_parameters(params):
    dense, routed = params["layers"][0], params["layers"][1]
    assert "router" not in dense and dense["w_gate"].ndim == 2
    assert routed["w_gate"].ndim == 3 and routed["ws_gate"].shape == (64, 48)
    assert routed["router_bias"].dtype == jnp.float32
    # a routed layer run alone, as the first of a one-layer model (the
    # benchmark's layer-alone comparison), is still routed and shared
    cfg1 = dataclasses.replace(CFG, n_layers=1)
    prompt = prompt_of(5, 11)
    for layer in (dense, routed):
        alone = {**params, "layers": [layer]}
        got, _ = served(alone, [(prompt, 4)], 2, "xla", cfg=cfg1)
        fed = [int(np.argmax(row)) for row in got[0][:-1]]
        want = reference_logits(alone, prompt + fed, cfg1)[len(prompt) - 1:]
        assert rel_err(got[0], want) < TOL
    # ... and without its shared expert it is another model
    bare = {k: v for k, v in routed.items() if not k.startswith("ws_")}
    got, _ = served({**params, "layers": [bare]}, [(prompt, 4)], 0, "xla", cfg=cfg1)
    want = reference_logits({**params, "layers": [routed]}, prompt, cfg1)[-1:]
    assert rel_err(got[0], want) > 0.05
