"""A model with sliding layers as the benchmark checks it:
``reference.common_check`` through ``chipbench/references/swa_moe.system``
(the whole model, then every layer alone with the pools of its kind), and the
parts that the ranges of the experts give against the reference's FFN, at
``TINY_SWA_MOE`` in float32. The served programs row by row are in
``tests/test_swa.py``.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_SWA_MOE, llama
from served_path import prompt_of

CFG = TINY_SWA_MOE
#: every routed expert held: the uncut layer
UNCUT = dataclasses.replace(CFG, expert_first=0, expert_count=None)
PS = 4
W = CFG.sliding_window
TOL = chip_reference.TOL_F32
REF = chip_reference.load("swa_moe")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(43), CFG)


def engine_like(params, cfg=CFG, attn_impl="xla"):
    """What ``reference.common_check`` and a reference's ``system`` read of
    an engine."""
    return types.SimpleNamespace(
        params=params, model_cfg=cfg, page_size=PS, mesh=None,
        _replicated=jax.devices()[0], prefill_attn=attn_impl)


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_the_references_system_side_is_the_harness_check(params, attn_impl):
    """``reference.common_check`` through ``swa_moe.system``: a sequence grown
    in warm chunks past the window with pages reused, every position from the
    prompt's end on compared, then decode steps over a page's end and a
    window page's; then every layer alone, each with the pools of its kind."""
    line = chip_reference.common_check(
        engine_like(params, attn_impl=attn_impl), REF, seed=3, interpret=True,
        prompt_tokens=16, steps=4)
    assert line["ok"] and line["rel_err"] < TOL
    assert line["layer_rel_err_p75"] < TOL
    got, fed = REF.system(
        engine_like(params), prompt_of(7, 16), 4, interpret=True)
    assert 16 + len(fed) - 4 >= W + 2 * PS and got.shape[0] == len(fed) + 1


# -- the share -----------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """The parts that the ranges of the experts give, the shared expert
    counted once, add up to the layer with every expert held."""
    uncut = llama.init_params(jax.random.PRNGKey(7), UNCUT)["layers"][2]
    x = jnp.asarray(
        np.random.default_rng(3).normal(size=(2, 9, CFG.hidden_size)),
        jnp.float32)
    flat = x.reshape(-1, CFG.hidden_size)
    with jax.default_matmul_precision("highest"):
        whole, _ = REF._ffn(uncut, UNCUT, flat)
        shared = REF.common._swiglu(
            flat, uncut["ws_gate"], uncut["ws_up"], uncut["ws_down"])
    total = np.zeros_like(np.asarray(shared))
    for first in range(0, CFG.n_experts, 2):
        cfg = dataclasses.replace(CFG, expert_first=first, expert_count=2)
        mine = {k: v[first:first + 2] if k in ("w_gate", "w_up", "w_down")
                else v for k, v in uncut.items()}
        part = np.asarray(llama._mlp(mine, cfg, x, interpret=True)).reshape(
            total.shape)
        with jax.default_matmul_precision("highest"):
            want, _ = REF._ffn(mine, cfg, flat)
        np.testing.assert_allclose(part, want, atol=2e-5, rtol=2e-4)
        total += part - np.asarray(shared)  # this range's routed part
    np.testing.assert_allclose(total + shared, whole, atol=5e-5, rtol=2e-4)
    np.testing.assert_allclose(
        np.asarray(llama._mlp(uncut, UNCUT, x, interpret=True)).reshape(
            total.shape), whole, atol=5e-5, rtol=2e-4)
