"""The shortcut-connected double layer (``LlamaConfig.double_layer``), zero-
compute experts among the router's outputs, a routed layer that is told what
it holds, a softmax router whose bias chooses, and the low-rank query path
into the latent pool, on the served path at ``TINY_SCMOE`` in float32 against
``chipbench/references/scmoe_mla.forward`` (float32, expanded attention,
nothing of the program's model code, given the same share)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference as chip_reference  # noqa: E402
from llm_d_kv_cache_manager_tpu.models import (  # noqa: E402
    LONGCAT_FLASH_OMNI,
    TINY_MLA_MOE,
    TINY_QWEN3_MOE,
    TINY_SCMOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.server import (  # noqa: E402
    BlockManagerConfig,
    EngineConfig,
)
from llm_d_kv_cache_manager_tpu.server.engine import Engine  # noqa: E402
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model  # noqa: E402

from test_mla import make_engine, prompt_of, rel_err, run_all, served  # noqa: E402

CFG = TINY_SCMOE
#: every routed expert held: the uncut layer
UNCUT = dataclasses.replace(CFG, expert_first=0, expert_count=None)
PS = 4
TOL = chip_reference.TOL_F32
REF = chip_reference.load("scmoe_mla")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(scope="module")
def uncut_params():
    return llama.init_params(jax.random.PRNGKey(7), UNCUT)


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return np.asarray(REF.forward(params, cfg, list(tokens))[0], np.float32)


def share_of(params, first, count):
    """The tree of the rank that holds experts ``first .. first + count - 1``
    of ``params``' (every one held there)."""
    def cut(layer):
        moe = {k: v[first:first + count] if k.startswith("w_") else v
               for k, v in layer["moe"].items()}
        return {**layer, "moe": moe}

    return {**params, "layers": [cut(layer) for layer in params["layers"]]}


# -- (1) the served programs and the engine against the reference --------------
@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("rows", [
    pytest.param([(19, 0)], id="cold-alone"),
    pytest.param([(23, 12)], id="paged-context-alone"),
    pytest.param([(30, 16), (9, 0), (21, 8)], id="batch-of-unequal-lengths"),
])
def test_prefill_then_decode_through_the_pool(params, rows, attn_impl):
    rows = [(prompt_of(40 + i, n), r) for i, (n, r) in enumerate(rows)]
    # 6 steps from 19 / 23 / 30 / 9 / 21 tokens: every row crosses a page
    got, fed = served(params, rows, 6, attn_impl, cfg=CFG)
    for (prompt, _), logits, tokens in zip(rows, got, fed):
        want = reference_logits(params, prompt + tokens)[len(prompt) - 1:]
        assert rel_err(logits, want) < TOL


@pytest.mark.parametrize("prefill_attn", ["xla", "pallas"])
def test_the_engine_agrees_with_the_reference(params, prefill_attn):
    """Cold prefill, a warm prefill against the cached session, decode
    across page boundaries, through ``Engine``: the greedy tokens are the
    reference's picks and the counters of a layer that is told what it
    holds add up."""
    session = prompt_of(21, 32)
    asks = [session + prompt_of(22, 7), session + prompt_of(23, 10)]
    engine = make_engine(params, cfg=CFG, prefill_attn=prefill_attn)
    engine.obs_step_timing = True
    first = run_all(engine, asks[:1])[0]
    second = run_all(engine, asks[1:])[0]
    assert first.num_cached_prompt == 0 and second.num_cached_prompt == 32
    for seq, ask in zip((first, second), asks):
        logits = reference_logits(params, ask + seq.generated_tokens)
        picks = logits[len(ask) - 1: -1].argmax(-1).tolist()
        assert seq.generated_tokens == picks
    # the pool's layer axis counts attentions: two a published layer
    assert engine.k_pages.shape[0] == 2 * CFG.n_layers == CFG.n_attn_layers
    assert engine.kv_bytes_per_token == 2 * CFG.n_layers * CFG.kv_row_shape[0] * 4
    assert engine.routed_layers == CFG.n_layers
    stats = engine.step_stats
    assert stats["routed_places"] == (
        stats["decode_forwards"] * 4 * CFG.n_experts_per_tok * CFG.n_layers
    )  # 4 lanes a dispatch, padded ones included
    assert 0 < stats["zero_places"] < stats["routed_places"]
    assert 0 < stats["held_places"] < stats["routed_places"]
    assert 0 < stats["experts_touched"] <= (
        stats["decode_forwards"] * CFG.n_layers * CFG.experts_held
    )


def test_a_double_layer_run_alone_is_served_by_what_it_holds(params):
    """The benchmark's layer-alone comparison: one entry of ``layers`` as a
    one-layer model is a whole published layer, two attentions in the pool."""
    cfg1 = dataclasses.replace(CFG, n_layers=1)
    prompt = prompt_of(5, 11)
    for layer in params["layers"]:
        alone = {**params, "layers": [layer]}
        got, fed = served(alone, [(prompt, 4)], 2, "xla", cfg=cfg1)
        want = reference_logits(alone, prompt + fed[0], cfg1)[len(prompt) - 1:]
        assert rel_err(got[0], want) < TOL
    k_pages, _ = jax.eval_shape(lambda: llama.init_kv_pages(cfg1, 8, PS))
    assert k_pages.shape[0] == 2


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


# -- (2) the shares add up -----------------------------------------------------
def _routed(layer, cfg, x, **kw):
    return np.asarray(llama._moe_mlp_routed(
        layer["moe"], cfg, x, interpret=True, **kw), np.float32)


def test_the_shares_of_a_four_way_split_add_up_to_the_uncut_layer(uncut_params):
    layer = uncut_params["layers"][1]
    x = jnp.asarray(
        np.random.default_rng(3).normal(size=(2, 9, CFG.hidden_size)),
        jnp.float32)
    flat = x.reshape(-1, CFG.hidden_size)
    with jax.default_matmul_precision("highest"):
        uncut_ref, _ = REF._moe(layer["moe"], UNCUT, flat)
        # the zero experts' part alone: a rank that holds no routed expert
        # the router chose... is what every rank's part shares
        topv, topi = llama._moe_gates(layer["moe"], UNCUT, flat)
    zero = np.asarray(
        jnp.sum(jnp.where(topi >= CFG.n_experts, topv, 0.0), -1)[:, None] * flat)
    total = np.zeros_like(zero)
    for rank in range(4):
        cfg = dataclasses.replace(CFG, expert_first=4 * rank, expert_count=4)
        mine = share_of(uncut_params, 4 * rank, 4)["layers"][1]
        part = _routed(mine, cfg, x).reshape(zero.shape)
        with jax.default_matmul_precision("highest"):
            want, _ = REF._moe(mine["moe"], cfg, flat)
        np.testing.assert_allclose(part, want, atol=2e-5, rtol=2e-4)
        total += part - zero  # the routed part of this rank
    np.testing.assert_allclose(total + zero, uncut_ref, atol=5e-5, rtol=2e-4)
    # ... and the program with every expert held is the uncut layer too
    np.testing.assert_allclose(
        _routed(layer, UNCUT, x).reshape(zero.shape), uncut_ref,
        atol=5e-5, rtol=2e-4)


# -- (3) a token all of whose places fall on zero experts ----------------------
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


# -- (4) chosen by p + bias, weighed by p --------------------------------------
def test_the_bias_chooses_and_does_not_weigh(params):
    layer = dict(params["layers"][0]["moe"])
    x = jnp.asarray(
        np.random.default_rng(1).normal(size=(7, CFG.hidden_size)), jnp.float32)
    p = np.asarray(jax.nn.softmax(x @ layer["router"], axis=-1))
    lifted = [2, 5, 17, 23]  # two routed experts, two zero experts
    layer["router_bias"] = jnp.zeros(CFG.router_outputs).at[
        jnp.asarray(lifted)].set(5.0)
    topv, topi = llama._moe_gates(layer, CFG, x)
    assert (np.sort(np.asarray(topi), axis=1) == lifted).all()
    picked = np.take_along_axis(p, np.asarray(topi), axis=1)
    # times the scaling factor, not renormalised
    np.testing.assert_allclose(topv, 6.0 * picked, rtol=1e-6)
    # without a bias in the layer the largest probabilities are chosen
    del layer["router_bias"]
    _, plain = llama._moe_gates(layer, CFG, x)
    assert (np.sort(np.asarray(plain), 1)
            == np.sort(np.argsort(-p, 1)[:, :4], 1)).all()


# -- (5) what is refused, by name ----------------------------------------------
def _engine_config(**what):
    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, prefill_bucket=16)
    return dataclasses.replace(config, **what)


@pytest.mark.parametrize("what, name", [
    (dict(kv_quant_hbm="int8"), "kv_lora_rank.*kv_quant_hbm"),
    (dict(block_manager=BlockManagerConfig(
        total_pages=16, page_size=PS, host_pages=8)), "kv_lora_rank.*host_pages"),
    (dict(remote_tier=True), "kv_lora_rank.*remote_tier"),
    (dict(sp=2), "kv_lora_rank.*sp > 1"),
    (dict(tp=2), "kv_lora_rank.*tp > 1"),
    (dict(spec_decode="prompt_lookup"), "kv_lora_rank.*spec_decode"),
    (dict(model=dataclasses.replace(CFG, block_length=4)),
     "kv_lora_rank.*block_length"),
    (dict(model=dataclasses.replace(CFG, moe_dispatch="dense")),
     "experts 4..7 held.*moe_dispatch"),
    (dict(model=dataclasses.replace(CFG, expert_first=14)),
     "experts 14..17 held.*past"),
    (dict(model=dataclasses.replace(
        TINY_QWEN3_MOE, expert_count=2), tp=2), "experts 0..1 held.*tp > 1"),
])
def test_engine_refuses_by_name(what, name):
    with pytest.raises(ValueError, match=name):
        Engine(_engine_config(**what))


def test_page_export_and_import_are_refused_by_name(params):
    engine = Engine(_engine_config(), params=params)
    with pytest.raises(ValueError, match="kv_lora_rank.*export_kv_blocks"):
        engine.export_kv_blocks([1, 2])
    with pytest.raises(ValueError, match="kv_lora_rank.*import_kv_blocks"):
        engine.import_kv_blocks([])


def test_the_dispatches_refuse_what_they_do_not_run(params):
    layer = params["layers"][0]["moe"]
    x = jnp.zeros((1, 3, CFG.hidden_size), jnp.float32)
    dense = dataclasses.replace(CFG, moe_dispatch="dense")
    with pytest.raises(ValueError, match="dense.*held range"):
        llama._moe_mlp(layer, dense, x, interpret=True)
    with pytest.raises(ValueError, match="holds 4 experts.*told 16"):
        llama._moe_mlp(layer, UNCUT, x, interpret=True)


# -- (6) a model that holds every expert adds no operation ----------------------
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


# -- the low-rank query, the presets, the pool ---------------------------------
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


def test_presets():
    assert _resolve_model("tiny-scmoe") is TINY_SCMOE
    cfg = _resolve_model("meituan-longcat/LongCat-Flash-Omni")
    assert cfg is LONGCAT_FLASH_OMNI
    assert (cfg.router_outputs, cfg.experts_held, cfg.n_attn_layers) == (768, 512, 56)
    cut = dataclasses.replace(
        cfg, n_layers=1, vocab_size=16384, expert_first=0, expert_count=16)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cut))
    (layer,) = shapes["layers"]
    for half in (layer, layer["second"]):
        assert half["wq_a"].shape == (6144, 1536)
        assert half["wq_b"].shape == (1536, 64 * 192)
        assert half["wkv_a"].shape == (6144, 576)
        assert half["wkv_b"].shape == (512, 64 * 256)
        assert half["wo"].shape == (64 * 128, 6144)
        assert half["w_gate"].shape == (6144, 12288)
    assert layer["moe"]["router"].shape == (6144, 768)
    assert layer["moe"]["router_bias"].shape == (768,)
    assert layer["moe"]["w_gate"].shape == (16, 6144, 2048)
    assert shapes["embed"].shape == (16384, 6144)
    k_pages, v_pages = jax.eval_shape(lambda: llama.init_kv_pages(cut, 8, 16))
    assert k_pages.shape == (2, 8, 16, 640) and v_pages.size == 0


# -- the loader: a longcat_flash config and a synthetic state dict -------------
class _LongcatConfig:  # the catalog row's keys (the language model's config)
    model_type = "longcat_flash"
    attention_bias, vocab_size, hidden_size = False, 131072, 6144
    ffn_hidden_size, expert_ffn_hidden_size, num_layers = 12288, 2048, 28
    num_attention_heads, kv_lora_rank, q_lora_rank = 64, 512, 1536
    qk_rope_head_dim, v_head_dim, qk_nope_head_dim = 64, 128, 128
    mla_scale_q_lora, mla_scale_kv_lora, routed_scaling_factor = True, True, 6
    n_routed_experts, max_position_embeddings, rms_norm_eps = 512, 131072, 1e-5
    rope_theta, attention_method, zero_expert_num = 10000000, "MLA", 256
    zero_expert_type, moe_topk = "identity", 12


def test_the_loader_reads_the_published_config():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    assert config_from_hf(_LongcatConfig()) == LONGCAT_FLASH_OMNI


@pytest.mark.parametrize("change, name", [
    (dict(zero_expert_type="copy"), "zero_expert_type"),
    (dict(attention_method="GQA"), "attention_method"),
    (dict(attention_bias=True), "attention_bias"),
])
def test_the_loader_refuses_by_name(change, name):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = _LongcatConfig()
    for key, value in change.items():
        setattr(hf, key, value)
    with pytest.raises(NotImplementedError, match=name):
        config_from_hf(hf)


def _state_dict(params, n_experts_written):
    """``params`` (every expert held) under the checkpoint's names."""
    attn = {
        "wq_a": "q_a_proj.weight", "q_a_norm": "q_a_layernorm.weight",
        "wq_b": "q_b_proj.weight", "wkv_a": "kv_a_proj_with_mqa.weight",
        "kv_norm": "kv_a_layernorm.weight", "wkv_b": "kv_b_proj.weight",
        "wo": "o_proj.weight",
    }
    ffn = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}

    def put(sd, name, w):
        w = np.asarray(w)
        sd[name] = w.T if w.ndim == 2 else w

    sd = {}
    put(sd, "model.embed_tokens.weight", params["embed"].T)
    put(sd, "model.norm.weight", params["final_norm"])
    put(sd, "lm_head.weight", params["lm_head"])
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        for j, half in enumerate((layer, layer["second"])):
            put(sd, f"{p}input_layernorm.{j}.weight", half["attn_norm"])
            put(sd, f"{p}post_attention_layernorm.{j}.weight", half["mlp_norm"])
            for ours, theirs in attn.items():
                put(sd, f"{p}self_attn.{j}.{theirs}", half[ours])
            for ours, theirs in ffn.items():
                put(sd, f"{p}mlps.{j}.{theirs}.weight", half[ours])
        put(sd, p + "mlp.router.classifier.weight", layer["moe"]["router"])
        put(sd, p + "mlp.router.e_score_correction_bias",
            layer["moe"]["router_bias"])
        for e in range(n_experts_written):
            for ours, theirs in ffn.items():
                put(sd, f"{p}mlp.experts.{e}.{theirs}.weight",
                    layer["moe"][ours][e])
    return sd


def test_a_held_range_loads_its_own_experts(uncut_params):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    sd = _state_dict(uncut_params, UNCUT.n_experts)
    for cfg, want in ((UNCUT, uncut_params), (CFG, share_of(uncut_params, 4, 4))):
        loaded = load_hf_state_dict(sd, cfg)
        assert jax.tree.structure(loaded) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(want)):
            assert a.dtype == b.dtype and np.array_equal(a, b)
    prompt = prompt_of(77, 14)
    got, fed = served(loaded, [(prompt, 8)], 2, "xla", cfg=CFG)
    want = reference_logits(loaded, prompt + fed[0])[len(prompt) - 1:]
    assert rel_err(got[0], want) < TOL


@pytest.mark.parametrize("key", [
    "audio_tower.layers.0.self_attn.q_proj.weight",
    "visual.blocks.0.attn.qkv.weight",
    "model.layers.0.mlp.experts.16.gate_proj.weight",  # past the 16 scored
    "model.mtp.layers.0.eh_proj.weight",
])
def test_a_key_that_is_not_mapped_is_refused_by_name(uncut_params, key):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    sd = _state_dict(uncut_params, UNCUT.n_experts)
    sd[key] = np.zeros((2, 2), np.float32)
    with pytest.raises(NotImplementedError, match=key.split(".")[0]):
        load_hf_state_dict(sd, CFG)


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
