"""Delta-rule linear-attention layers beside latent ones (``TINY_LING_HYBRID``),
a layer at a time: both forms of the gate and each kind of layer run alone
against the plain reference (``chipbench/references/kda_mla_moe``, float32),
the carried rows' pool of whole tiles against the flat row it replaced, and
group-limited routing in both dispatches and under a held range. The whole
preset through the pools is in ``tests/test_kda.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_LING_HYBRID, llama
from served_path import prompt_of, rel_err

CFG = TINY_LING_HYBRID
PS = 4
TOL = 1e-4
REF = chip_reference.load("kda_mla_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 47)


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return served_path.reference_logits(REF, params, cfg, tokens)


def served(params, rows, steps, attn_impl, cfg=CFG):
    got, fed, _ = served_path.served(
        params, REF.pool_config(params, cfg), rows, steps, attn_impl,
        page_size=PS, second=served_path.StateSlots())
    return got, fed


# -- a layer alone ---------------------------------------------------------------
@pytest.mark.parametrize("safe", [True, False], ids=["safe_gate", "softplus"])
def test_both_forms_of_the_gate(safe):
    # (the gate is the linear layers' alone: one of them, over the dense FFN)
    cfg = dataclasses.replace(CFG, n_layers=1, kda_safe_gate=safe)
    params = llama.init_params(jax.random.PRNGKey(5), cfg)
    prompt = prompt_of(7, 21)
    got, fed = served(params, [(prompt, 8)], 3, "xla", cfg=cfg)
    want = reference_logits(params, prompt + fed[0], cfg)[len(prompt) - 1:]
    assert rel_err(got[0], want) < TOL
    # ... and they are two models: the other form's reference is not this one
    other = dataclasses.replace(cfg, kda_safe_gate=not safe)
    wrong = reference_logits(params, prompt + fed[0], other)[len(prompt) - 1:]
    assert rel_err(got[0], wrong) > 100 * TOL


@pytest.mark.parametrize("index", [0, 1, 2], ids=[
    "linear-dense", "linear-routed", "latent-routed"])
def test_a_layer_run_alone_is_what_its_parameters_say(params, index):
    """The benchmark's layer-alone comparison: a one-layer tree under
    ``replace(cfg, n_layers=1)``, whose ``layer_types`` still speak of the
    whole model; the mixer and the FFN are read from the layer."""
    layer = params["layers"][index]
    assert ("kda_qkv" in layer) == (CFG.layer_kind(index) == "linear")
    assert ("router" in layer) == (index >= CFG.first_k_dense)
    cfg1 = dataclasses.replace(CFG, n_layers=1)
    alone = {**params, "layers": [layer]}
    prompt = prompt_of(60 + index, 13)
    got, fed = served(alone, [(prompt, 4)], 2, "xla", cfg=cfg1)
    want = reference_logits(alone, prompt + fed[0], cfg1)[len(prompt) - 1:]
    assert rel_err(got[0], want) < TOL


# -- the carried rows' pool ------------------------------------------------------
ROWS_SLOTS, ROWS_LANES = 7, 4
ROWS_CASES = {
    # (the slot a lane read, the slot it writes)
    "keeps_its_slot": ([1, 2, 3, 4], [1, 2, 3, 4]),
    # lanes 1 and 2 write another slot than they read: a snapshot is left
    "another_slot": ([1, 2, 3, 4], [1, 5, 6, 4]),
    # three padded lanes, all on the reserved slot
    "padded": ([1, 0, 0, 0], [1, 0, 0, 0]),
}


@pytest.mark.parametrize("case", list(ROWS_CASES))
@pytest.mark.parametrize("head_dim", [16, 32], ids=["row_of_576", "row_of_1152"])
def test_a_slot_of_whole_tiles_holds_what_the_flat_row_held(case, head_dim):
    """A decode step's write of the carried rows (``_scatter_slots`` over
    the pool ``[layers, slots, *kda_conv_tile]``) against the flat
    ``.at[].set`` over ``[layers * slots, row]`` it was until PR 48, bit for
    bit, in every layer of a pool of three: a row that is whole 128-lane
    tiles (9 x 128) and one that is not (the tiny preset's 576 values, 9 x
    64). What a lane reads back is what it wrote, a tap a row, oldest
    first."""
    cfg = dataclasses.replace(
        CFG, n_layers=3, layer_types=("linear_attention",) * 3,
        kda_head_dim=head_dim)
    row, taps = cfg.kda_conv_row, cfg.kda_conv_kernel
    assert cfg.kda_conv_tile == (9, 64 if head_dim == 16 else 128)
    empty = llama.init_kda_state(cfg, ROWS_SLOTS)[1]
    assert empty.shape == (3, ROWS_SLOTS, *cfg.kda_conv_tile)
    rng = np.random.default_rng(len(case) + head_dim)
    pool = jnp.asarray(rng.standard_normal(empty.shape), empty.dtype)
    fresh = jnp.asarray(rng.standard_normal((3, ROWS_LANES, row)), pool.dtype)
    read, write = (np.asarray(x, np.int32) for x in ROWS_CASES[case])
    got = np.asarray(llama._scatter_slots(
        pool, fresh, jnp.asarray(write), jnp.ones(ROWS_LANES, bool)))
    flat = pool.reshape(3 * ROWS_SLOTS, row)
    idx = np.arange(3)[:, None] * ROWS_SLOTS + write[None, :]
    want = np.asarray(
        flat.at[idx.reshape(-1)].set(fresh.reshape(-1, row))
    ).reshape(3, ROWS_SLOTS, row)
    assert got.shape == pool.shape
    # (slot 0 is written by every padded lane and read by nobody who cares)
    assert np.array_equal(got.reshape(want.shape)[:, 1:], want[:, 1:])
    # a slot nobody writes is as it was: what a lane read and left behind
    untouched = np.setdiff1d(np.arange(ROWS_SLOTS), write)
    assert set(read) - set(write) <= set(untouched)
    assert np.array_equal(got[:, untouched], np.asarray(pool)[:, untouched])
    back = np.asarray(llama._slot_rows(jnp.asarray(got), jnp.asarray(write)))
    real = write > 0
    assert np.array_equal(
        back.reshape(3, ROWS_LANES, taps - 1, -1)[:, real],
        np.asarray(fresh).reshape(3, ROWS_LANES, taps - 1, -1)[:, real])


# -- group-limited routing -------------------------------------------------------
def _x(seed, n=9):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(n, CFG.hidden_size)),
        jnp.float32)


@pytest.mark.parametrize("n_group, topk_group", [(4, 2), (4, 1), (2, 1), (8, 3)])
def test_the_router_chooses_within_the_best_groups(params, n_group, topk_group):
    cfg = dataclasses.replace(CFG, n_group=n_group, topk_group=topk_group,
                              n_experts_per_tok=1 if n_group == 8 else 2)
    layer, x = params["layers"][1], _x(n_group)
    topv, topi = llama._moe_gates(layer, cfg, x)
    scores = np.asarray(jax.nn.sigmoid(x @ layer["router"]))
    want_i, _ = REF.choose(
        jnp.asarray(scores), layer["router_bias"], cfg)
    assert np.array_equal(np.sort(topi, axis=1), np.sort(want_i, axis=1))
    # by hand: a group's score is the sum of its two largest choice scores
    c = scores + np.asarray(layer["router_bias"])
    size = c.shape[1] // n_group
    grouped = c.reshape(len(c), n_group, size)
    best = np.sort(grouped, axis=-1)[..., -min(2, size):].sum(-1)
    kept = np.argsort(-best, axis=1)[:, :topk_group]
    for row, groups in zip(np.asarray(topi), kept):
        assert set(row // size) <= set(groups.tolist())
    # the gates weigh with the scores alone, renormalised, times the factor
    picked = np.take_along_axis(scores, np.asarray(topi), axis=1)
    np.testing.assert_allclose(
        topv, picked / picked.sum(1, keepdims=True) * cfg.routed_scaling_factor,
        rtol=1e-5)


def test_one_group_is_the_routing_it_always_was(params):
    layer, x = params["layers"][1], _x(1)
    one = dataclasses.replace(CFG, n_group=1, topk_group=1)
    topv, topi = llama._moe_gates(layer, one, x)
    scores = np.asarray(jax.nn.sigmoid(x @ layer["router"]))
    want = np.argsort(-(scores + np.asarray(layer["router_bias"])), axis=1)[:, :2]
    assert np.array_equal(np.sort(topi, axis=1), np.sort(want, axis=1))
    # ... and the groups do leave experts out: some row's choice differs
    _, limited = llama._moe_gates(
        layer, dataclasses.replace(CFG, n_group=4, topk_group=1), x)
    assert not np.array_equal(np.sort(limited, axis=1), np.sort(topi, axis=1))


@pytest.mark.parametrize("dispatch", ["routed", "dense"])
def test_both_dispatches_route_within_the_groups(params, dispatch):
    cfg = dataclasses.replace(CFG, moe_dispatch=dispatch)
    layer, x = params["layers"][1], _x(2)[None]
    got = llama._mlp(layer, cfg, x, interpret=True)[0]
    want, _ = REF._ffn(layer, cfg, x[0])
    assert rel_err(np.asarray(got), np.asarray(want)) < TOL


def test_a_routing_group_a_chip_adds_up_to_the_uncut_layer(params):
    """The deployment's cut: each of four chips holds one group's experts
    (``expert_first`` / ``expert_count``), every chip routes over all of them
    and adds the places that fall in its own group; the shared expert is
    counted once."""
    layer, x = params["layers"][2], _x(3)[None]
    whole = llama._mlp(layer, CFG, x, interpret=True)[0]
    shared = llama._swiglu(
        CFG, x, layer["ws_gate"], layer["ws_up"], layer["ws_down"])[0]
    size = CFG.n_experts // CFG.n_group
    parts = []
    for group in range(CFG.n_group):
        cut = dataclasses.replace(
            CFG, expert_first=group * size, expert_count=size)
        held = {**layer, **{
            name: layer[name][group * size: (group + 1) * size]
            for name in ("w_gate", "w_up", "w_down")}}
        part = llama._mlp(held, cut, x, interpret=True)[0]
        want, _ = REF._ffn(held, cut, x[0])
        assert rel_err(np.asarray(part), np.asarray(want)) < TOL
        parts.append(part - shared)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
