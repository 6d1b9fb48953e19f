"""Delta-rule linear-attention layers beside gated GQA layers that take no
position (``TINY_SOLAR_HYBRID``: a state pool of slots beside per-head K/V
pools in ONE prefill and ONE decode program) over sparse experts of which a
share is held: the served programs against the plain reference, in float32.

The reference side is ``chipbench/references/kda_gqa_moe.forward`` (float32,
the recurrence token by token, nothing of the program's model code). Every
call here reads a row's state from one slot and writes it to ANOTHER (a
prefill that goes on from resident tokens is the engine's warm prefill from
a K/V hit and a restored snapshot; a decode step whose switch is its own
position is a lane passing a snapshot boundary), but for the case that keeps
some lanes' slots. Three controls that must fail the same tolerance: ``beta``
not doubled, q and k rotated on the GQA layers, the GQA gate left out. Then
a layer's parts: a low-rank pair against the full projection of its product,
and the eight shares of a routed layer against the uncut one. The kernels at
``beta`` in (1, 2) are in ``tests/test_kda_gqa_kernels.py``, the engine in
``tests/test_kda_gqa_engine.py``, presets, loader, sharding and quantisation
in ``tests/test_kda_gqa_config.py``; the helpers are ``tests/served_path.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_SOLAR_HYBRID, llama
from served_path import prompt_of, rel_err

CFG = TINY_SOLAR_HYBRID
#: one period of the two (a GQA layer, three linear ones): every kind of layer
#: and half the program to compile, for the cases that do not need the second
#: period (where a layer's place among its kind is not its place in the model)
PERIOD = dataclasses.replace(CFG, n_layers=4)
PS = 4
TOL = 1e-4
REF = chip_reference.load("kda_gqa_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 58)


def one_period(tree):
    return {**tree, "layers": tree["layers"][:PERIOD.n_layers]}


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return served_path.reference_logits(REF, params, cfg, tokens)


def served(params, rows, steps, attn_impl, cfg=CFG, second=None):
    got, fed, _ = served_path.served(
        params, REF.pool_config(params, cfg), rows, steps, attn_impl,
        page_size=PS, second=second or served_path.StateSlots())
    return got, fed


class EvenRowsKeepTheirSlot(served_path.StateSlots):
    """Rows 0, 2, .. read and write ONE slot in every call (a lane that keeps
    its slot); rows 1, 3, .. go on in another slot than they read (a lane
    restored from a snapshot, or passing a boundary), in the same dispatch."""

    def _swap(self, i):
        if i % 2 == 0:
            return [self.slots[i][0]] * 2
        return super()._swap(i)


# -- the served programs against the token-by-token reference ------------------
BATCH = [(30, 16), (9, 0), (21, 8)]


@pytest.mark.parametrize("attn_impl, cfg, rows, second", [
    # both periods; a cold row beside two that go on from resident K/V pages
    # and a state read from another slot than the one written
    pytest.param("xla", CFG, BATCH, None, id="xla-batch-of-unequal-lengths"),
    pytest.param("xla", PERIOD, [(70, 64)], None,
                 id="xla-over-a-chunk-of-the-recurrence"),
    pytest.param("xla", PERIOD, BATCH + [(17, 12)], EvenRowsKeepTheirSlot,
                 id="xla-a-lane-restored-beside-one-that-keeps-its-slot"),
    # (the GQA layers' kernels, interpreted: the linear layers' prefill is
    # the same program under both)
    pytest.param("pallas", PERIOD, BATCH, None,
                 id="pallas-batch-of-unequal-lengths"),
])
def test_prefill_then_decode_through_both_pools(
        params, rows, cfg, attn_impl, second):
    if cfg is PERIOD:
        params = one_period(params)
    rows = [(prompt_of(40 + i, n), r) for i, (n, r) in enumerate(rows)]
    got, fed = served(params, rows, 5, attn_impl, cfg=cfg,
                      second=second and second())
    for (prompt, _), logits, tokens in zip(rows, got, fed):
        want = reference_logits(params, prompt + tokens, cfg)[len(prompt) - 1:]
        assert rel_err(logits, want) < TOL


def _without_gate(tree):
    return {**tree, "layers": [
        {k: v for k, v in layer.items() if k != "wg"}
        for layer in tree["layers"]]}


@pytest.mark.parametrize("control", [
    "beta_not_doubled", "gqa_layer_rotates", "gqa_gate_left_out"])
def test_a_control_fails_the_same_tolerance(params, control):
    """The program steered to another model (the reference and the weights
    it reads stay what they are) is far outside what the sound program
    holds: each of the marks this configuration adds is read."""
    params = one_period(params)
    steered_cfg = {
        "beta_not_doubled": dataclasses.replace(PERIOD, kda_neg_eigval=False),
        "gqa_layer_rotates": dataclasses.replace(PERIOD, no_rope=False),
    }.get(control, PERIOD)
    steered = _without_gate(params) if control == "gqa_gate_left_out" else params
    prompt = prompt_of(41, 30)
    got, fed = served(steered, [(prompt, 16)], 3, "xla", cfg=steered_cfg)
    want = reference_logits(params, prompt + fed[0], PERIOD)[len(prompt) - 1:]
    assert rel_err(got[0], want) > 100 * TOL


# -- a layer's parts -------------------------------------------------------------
def _x(seed, n=9):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(1, n, CFG.hidden_size)),
        jnp.float32)


def test_a_low_rank_pair_is_the_full_projection_of_its_product(params):
    """``(x W_down) W_up = x (W_down W_up)``: a linear layer whose decay goes
    through the pair gives the operands of one whose ``kda_wf`` is the pair's
    product; the channel-wise output gate is ``sigmoid`` of the same product
    form, a value a channel."""
    layer, x = params["layers"][1], _x(1)
    rows = jnp.zeros((1, CFG.kda_conv_kernel - 1, 3 * 4 * 16), jnp.float32)
    full = {k: v for k, v in layer.items() if not k.startswith("kda_wf")}
    full["kda_wf"] = layer["kda_wf_down"] @ layer["kda_wf_up"]
    assert full["kda_wf"].shape == (64, 64) and layer["kda_wf_down"].shape == (64, 16)
    got = llama._kda_inputs(layer, CFG, x, rows)
    want = llama._kda_inputs(full, CFG, x, rows)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-6)
    # beta is doubled: in (0, 2), and above 1 somewhere
    beta = np.asarray(got[4])
    assert 0 < beta.min() and 1 < beta.max() < 2
    o = jnp.asarray(np.random.default_rng(2).normal(size=(1, 9, 4, 16)),
                    jnp.float32)
    gate = jax.nn.sigmoid(x @ (layer["kda_wg_down"] @ layer["kda_wg_up"]))
    normed = o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + CFG.rms_norm_eps)
    want_out = (normed * layer["kda_o_norm"]
                * gate.reshape(1, 9, 4, 16)).reshape(1, 9, -1) @ layer["wo"]
    np.testing.assert_allclose(
        llama._kda_output(layer, CFG, x, o), want_out, atol=2e-5)


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer(params):
    """The deployment's cut at the tiny size: each of eight chips holds one
    of the 8 experts (the cell's 40 of 320), every chip routes over all of
    them and adds the places that fall on its own; the shared expert is
    counted once."""
    layer, x = params["layers"][2], _x(3)
    whole = llama._mlp(layer, CFG, x, interpret=True)[0]
    want_whole, _ = REF._kda._ffn(layer, CFG, x[0])
    assert rel_err(np.asarray(whole), np.asarray(want_whole)) < TOL
    shared = llama._swiglu(
        CFG, x, layer["ws_gate"], layer["ws_up"], layer["ws_down"])[0]
    parts = []
    for chip in range(8):
        cut = dataclasses.replace(CFG, expert_first=chip, expert_count=1)
        held = {**layer, **{
            name: layer[name][chip: chip + 1]
            for name in ("w_gate", "w_up", "w_down")}}
        part = llama._mlp(held, cut, x, interpret=True)[0]
        want, _ = REF._kda._ffn(held, cut, x[0])
        assert rel_err(np.asarray(part), np.asarray(want)) < TOL
        parts.append(part - shared)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
