"""The parts of the double layer alone, program against program and against
the layer's own equations: the routed sum added after the second FFN, a token
on zero experts alone, every expert held adds no operation, the query through
its latent and both scales, a prefill's places a block at a time. Against the
reference (``chipbench/references/scmoe_mla.forward``): ``tests/test_scmoe.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    TINY_MLA_MOE,
    TINY_QWEN3_MOE,
    TINY_SCMOE,
    llama,
)

CFG = TINY_SCMOE


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(7), CFG)


def test_the_routed_sum_is_added_after_the_second_ffn(params):
    """Not this model: ``s`` added where it is computed (step 2)."""
    layer = params["layers"][0]
    x = jnp.asarray(
        np.random.default_rng(2).normal(size=(1, 5, CFG.hidden_size)),
        jnp.float32)
    aside = []
    after_first = llama._ffn(layer, CFG, x, interpret=True, aside=aside)
    assert len(aside) == 1  # held aside, not added
    norm = llama.rms_norm(x, layer["mlp_norm"], CFG.rms_norm_eps, 0.0)
    dense = llama._swiglu(
        CFG, norm, layer["w_gate"], layer["w_up"], layer["w_down"])
    np.testing.assert_allclose(after_first, x + dense, atol=1e-5)
    s = aside[0]
    after_second = llama._ffn(
        layer["second"], CFG, after_first, interpret=True, aside=aside)
    assert not aside  # taken by the second half
    plain = llama._ffn(layer["second"], CFG, after_first, interpret=True)
    np.testing.assert_allclose(after_second, plain + s, atol=1e-5)
    assert float(jnp.abs(s).max()) > 1e-3


# -- a token all of whose places fall on zero experts --------------------------
def test_a_token_on_zero_experts_alone_is_in_no_group(params, monkeypatch):
    from llm_d_kv_cache_manager_tpu.ops import gmm as gmm_ops

    layer = dict(params["layers"][0]["moe"])
    # a bias that lifts the first four zero experts over every probability
    layer["router_bias"] = jnp.zeros(CFG.router_outputs).at[
        jnp.arange(CFG.n_experts, CFG.n_experts + 4)].set(5.0)
    x = jnp.asarray(
        np.random.default_rng(4).normal(size=(1, 6, CFG.hidden_size)),
        jnp.float32)
    seen = []
    real = gmm_ops.grouped_matmul
    monkeypatch.setattr(
        gmm_ops, "grouped_matmul",
        lambda lhs, rhs, sizes, **kw: (seen.append(np.asarray(sizes)),
                                       real(lhs, rhs, sizes, **kw))[1])
    touched = []
    with jax.disable_jit():
        out = llama._moe_mlp_routed(
            layer, CFG, x, interpret=True, touched=touched)
    p = jax.nn.softmax((x[0] @ layer["router"]).astype(jnp.float32), axis=-1)
    gates = CFG.routed_scaling_factor * p[:, CFG.n_experts:CFG.n_experts + 4]
    np.testing.assert_allclose(
        out[0], jnp.sum(gates, -1, keepdims=True) * x[0], rtol=1e-5, atol=1e-6)
    assert seen and all(int(sizes.sum()) == 0 for sizes in seen)
    # [experts read, places on zero experts, places on held experts]
    assert np.asarray(touched[0]).tolist() == [0, 6 * CFG.n_experts_per_tok, 0]


# -- a model that holds every expert adds no operation -------------------------
@pytest.mark.parametrize("preset", [TINY_QWEN3_MOE, TINY_MLA_MOE],
                         ids=["softmax", "sigmoid-shared"])
@pytest.mark.parametrize("masked", [False, True], ids=["decode", "prefill"])
def test_every_expert_held_and_no_zero_expert_adds_no_operation(preset, masked):
    """The routed layer traced with the new fields at their defaults and
    with the whole range stated: one jaxpr, bit-equal outputs. And that one
    program has no operation of the held path: no ``moe_zero`` scope, one
    ``select_n`` less than the masked form."""
    params = llama.init_params(jax.random.PRNGKey(3), preset)
    layer = next(lay for lay in params["layers"] if "router" in lay)
    stated = dataclasses.replace(
        preset, expert_first=0, expert_count=preset.n_experts)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 5, preset.hidden_size)), jnp.float32)
    valid = jnp.asarray(np.arange(5)[None] < np.array([[5], [2]])) if masked else None

    def routed(cfg):
        def fn(layer, x):
            touched = []
            out = llama._moe_mlp_routed(
                layer, cfg, x, interpret=True, touched=touched, valid=valid)
            return out, touched[0]
        return fn

    default, whole = (jax.make_jaxpr(routed(c))(layer, x) for c in (preset, stated))
    assert str(default) == str(whole)
    assert "moe_zero" not in str(default)
    for a, b in zip(jax.jit(routed(preset))(layer, x),
                    jax.jit(routed(stated))(layer, x)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert llama.burst_counts(preset) == llama.burst_counts(stated) == 1
    assert llama.burst_counts(CFG) == len(llama.BURST_COUNTS_HELD)


# -- the low-rank query ---------------------------------------------------------
def test_the_query_goes_through_its_latent_and_both_scales(params):
    layer = params["layers"][0]
    x = jnp.asarray(
        np.random.default_rng(6).normal(size=(1, 3, CFG.hidden_size)),
        jnp.float32)
    pos = jnp.arange(3)[None]
    inv = jnp.asarray(llama.rope_frequencies(CFG.qk_rope_head_dim, CFG.rope_theta))
    q_n, _, row = llama._mla_project(layer, CFG, x, pos, inv)
    unscaled = dataclasses.replace(
        CFG, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    q_n0, _, row0 = llama._mla_project(layer, unscaled, x, pos, inv)
    dc = CFG.kv_lora_rank
    np.testing.assert_allclose(
        q_n, q_n0 * (CFG.hidden_size / CFG.q_lora_rank) ** 0.5, rtol=1e-5)
    np.testing.assert_allclose(
        row[..., :dc], row0[..., :dc] * (CFG.hidden_size / dc) ** 0.5, rtol=1e-5)
    np.testing.assert_allclose(row[..., dc:], row0[..., dc:])  # the rope key
    # a layer with ``wq`` and no pair is served by what it holds
    full = {k: v for k, v in layer.items() if k not in ("wq_a", "wq_b", "q_a_norm")}
    full["wq"] = layer["wq_a"] @ layer["wq_b"]
    assert llama._mla_project(full, CFG, x, pos, inv)[0].shape == q_n.shape


# -- a prefill's places a block at a time --------------------------------------
@pytest.mark.parametrize("masked", [False, True], ids=["every-row", "padded"])
def test_blocks_of_sorted_rows_give_what_one_pass_gives(params, masked, monkeypatch):
    """A dispatch of more places than ``ROUTED_ROW_BLOCK`` runs the grouped
    matmuls over blocks of sorted rows, as many as hold a row of a group:
    the same sum, no place dropped, whatever the router chose."""
    moe = dict(params["layers"][1]["moe"])
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(3, 7, CFG.hidden_size)), jnp.float32)
    valid = jnp.asarray(np.arange(7)[None] < np.array([[7], [3], [0]])) if masked else None

    def run(layer):
        touched = []
        out = llama._moe_mlp_routed(
            layer, CFG, x, interpret=True, touched=touched, valid=valid)
        return np.asarray(out), np.asarray(touched[0])

    for lift in (None, [4, 5, 6, 7]):  # a random router; every place held
        if lift:
            moe["router_bias"] = jnp.zeros(CFG.router_outputs).at[
                jnp.asarray(lift)].set(5.0)
        monkeypatch.setattr(llama, "ROUTED_ROW_BLOCK", 4096)
        whole, counts = run(moe)
        monkeypatch.setattr(llama, "ROUTED_ROW_BLOCK", 8)
        blocked, counts_blocked = run(moe)
        np.testing.assert_allclose(blocked, whole, atol=1e-5, rtol=1e-5)
        assert counts.tolist() == counts_blocked.tolist()
        if lift:  # all 84 (or 40) places in groups: eleven (five) blocks
            assert counts[2] == (10 if masked else 21) * 4
