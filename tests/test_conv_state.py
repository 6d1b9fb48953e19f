"""Gated short-convolution layers whose state rides in the pages beside the
keys and values of the layers that attend (``LlamaConfig.layer_types``;
LFM2-8B-A1B): the served programs against the plain reference, at
``TINY_LFM2_MOE`` in float32.

The reference side is ``chipbench/references/conv_moe.forward`` (float32, the
whole sequence at once, the convolution as shifted products, nothing of the
program's model code, no state). The program keeps, a page, the state after
the last token written in it (``llama.init_state_pages``): these tests hold
the ways that state reaches a token in ``llama.prefill`` and
``llama.decode_step`` (a chunk's own tokens, a finished page's slot, a slot a
chunk left inside a page, a tree read back from a checkpoint) to the
stateless reference. The ways it reaches one through the engine (a
prefix-cache hit, a page boundary inside a fused burst or under a dispatch
ahead, a re-prefill after preemption, a page id evicted and reused) are in
``tests/test_conv_state_engine.py``, refusals, presets and the loader in
``tests/test_conv_state_config.py``; the helpers they share with the other
architectures are ``tests/served_path.py``.
"""

import dataclasses

import jax
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import (
    TINY_LFM2_MOE,
    TINY_QWEN3_MOE,
    llama,
)
from served_path import prompt_of, rel_err

CFG = TINY_LFM2_MOE
PS = 4
TOL = chip_reference.TOL_F32
REF = chip_reference.load("conv_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 34)


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return served_path.reference_logits(REF, params, cfg, tokens)


class StatePages:
    """The second pool of ``served_path.served``: a state slot a page."""

    def make(self, cfg, rows, pages, table_pages):
        self.pool = llama.init_state_pages(cfg, pages)

    def prefill(self, chunks, positions, ctx_pages):
        return dict(state_pages=self.pool)

    def decode(self, positions):
        return dict(state_pages=self.pool)

    def keep(self, results):
        (self.pool,) = results


def served(params, rows, steps, attn_impl, cfg=CFG):
    """``served_path.served`` with the state pool: the logits a row,
    [1 + steps, vocab], the tokens fed and the pools."""
    state = StatePages()
    got, fed, (k_pages, v_pages, tables) = served_path.served(
        params, cfg, rows, steps, attn_impl, page_size=PS, second=state)
    return got, fed, (k_pages, v_pages, state.pool, tables)


# -- the served programs against the stateless reference -----------------------
@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("rows", [
    pytest.param([(19, 0)], id="cold-alone"),
    pytest.param([(23, 12)], id="state-from-a-finished-pages-slot"),
    pytest.param([(23, 10)], id="a-chunk-starting-inside-a-page"),
    pytest.param([(17, 16)], id="a-one-token-chunk-at-a-pages-first-slot"),
    pytest.param([(30, 16), (9, 0), (21, 7), (14, 13)],
                 id="right-padded-batch-of-unequal-lengths"),
])
def test_prefill_then_decode_through_the_pools(params, rows, attn_impl):
    rows = [(prompt_of(40 + i, n), r) for i, (n, r) in enumerate(rows)]
    got, fed, _ = served(params, rows, 6, attn_impl)
    for (prompt, _), logits, tokens in zip(rows, got, fed):
        want = reference_logits(params, prompt + tokens)[len(prompt) - 1:]
        assert rel_err(logits, want) < TOL


def test_a_warm_prefill_equals_the_cold_one(params):
    """The same prompt cold in one call, and its tail warm against pages the
    head left (their keys, values and state slots): the same logits, at the
    prompt's end and through six decode steps over page boundaries."""
    prompt = prompt_of(3, 27)
    cold, fed_cold, _ = served(params, [(prompt, 0)], 6, "xla")
    warm, fed_warm, _ = served(params, [(prompt, 16)], 6, "xla")
    assert fed_cold == fed_warm
    assert rel_err(warm[0], cold[0]) < TOL


def test_a_full_pages_slot_is_the_snapshot_at_its_end(params):
    """Slot ``p`` of a convolution layer holds ``z`` of the last two tokens
    written in page ``p``: for a full page, the state at its end, whatever
    chunking wrote it; for the last, partial page, the state at the
    sequence's end. The padding of a right-padded row leaves no state."""
    prompt = prompt_of(5, 22)
    _, _, (_, _, whole, tables) = served(params, [(prompt, 0)], 0, "xla")
    _, _, (_, _, pieces, _) = served(
        params, [(prompt, 10), (prompt_of(6, 30), 0)], 0, "xla")
    used = tables[0, : -(-len(prompt) // PS)]
    assert np.abs(np.asarray(whole[:, used])).min(axis=-1).all()  # written
    np.testing.assert_allclose(
        np.asarray(pieces[:, used]), np.asarray(whole[:, used]),
        rtol=1e-4, atol=1e-5)
    # ... and the state is what the equations say: z = B * x of the last
    # two tokens of each page, read off the first layer (a convolution)
    layer = params["layers"][0]
    h = np.asarray(params["embed"])[prompt]
    u = h / np.sqrt((h * h).mean(-1, keepdims=True) + CFG.rms_norm_eps)
    gate_b, _, x = np.split(
        u * np.asarray(layer["attn_norm"]) @ np.asarray(layer["conv_in"]), 3, -1)
    z = gate_b * x
    for j, page in enumerate(used):
        end = min((j + 1) * PS, len(prompt))
        np.testing.assert_allclose(
            np.asarray(whole[0, page]).reshape(2, -1), z[end - 2: end],
            rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", ["conv", "attention", "conv-dense-ffn"])
def test_one_layer_trees_of_each_kind(params, kind):
    """The harness's layer-alone check runs one-layer trees under
    ``replace(cfg, n_layers=1)``, whose ``layer_types`` still start with
    "conv": operator and FFN are read from the layer's own parameters, and
    the reference's system side makes the pools of the kind it finds."""
    index = {"conv": 3, "attention": 2, "conv-dense-ffn": 0}[kind]
    layer = params["layers"][index]
    assert ("conv_in" in layer) == (kind != "attention")
    assert ("router" in layer) == (kind != "conv-dense-ffn")
    one = {**params, "layers": [layer]}
    cfg1 = dataclasses.replace(CFG, n_layers=1)

    class FakeEngine:
        params, model_cfg, page_size, mesh = one, cfg1, PS, None
        _replicated, prefill_attn = jax.devices()[0], "xla"

    prompt = prompt_of(9, 16)
    got, fed = REF.system(FakeEngine, prompt, 4, True, one, cfg1)
    want = reference_logits(one, prompt + fed, cfg1)[len(prompt) - 1:]
    assert rel_err(got, want) < TOL
    pools = REF.pool_config(one, cfg1)
    assert (pools.n_conv_layers, pools.n_attn_layers) == (
        (0, 1) if kind == "attention" else (1, 0))


def test_the_references_system_side_is_the_harness_check(params):
    """``reference.common_check`` as a benchmark run makes it: whole model
    and every layer alone, through the reference's own ``system`` (cold
    half, warm rest, a one-token chunk inside a page, decode steps)."""

    class FakeEngine:
        model_cfg, page_size, mesh = CFG, PS, None
        _replicated, prefill_attn = jax.devices()[0], "xla"

        class config:
            kv_quant_hbm = None

    FakeEngine.params = params
    out = chip_reference.common_check(
        FakeEngine, REF, seed=7, interpret=True, prompt_tokens=16, steps=4)
    assert out["ok"], out
    assert out["layer_rel_err_p75"] < TOL
    assert REF.pieces(128, 16) == [(0, 64), (64, 127), (127, 128)]


def test_arity_without_state(params):
    """A model without convolution layers never sees the state: three
    results of ``prefill`` and ``decode_step``, called positionally as the
    benchmark's own files call them, and no state pool to make."""
    cfg = TINY_QWEN3_MOE
    p = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert llama.init_state_pages(cfg, 8) is None
    k, v = llama.init_kv_pages(cfg, 8, PS)
    assert k.shape == (cfg.n_layers, 8, PS, cfg.n_kv_heads, cfg.hd)
    pos = np.arange(6)[None, :]
    out = llama.prefill(
        p, cfg, np.ones((1, 6), np.int32), pos, np.ones((1, 6), bool), k, v,
        1 + pos // PS, pos % PS, np.zeros((1, 0), np.int32),
        np.zeros((1,), np.int32), None, "xla", False, None, None, True)
    assert len(out) == 3
    out = llama.decode_step(
        p, cfg, np.ones((1,), np.int32), np.asarray([6]), out[1], out[2],
        np.asarray([[1, 2]]), np.asarray([7]), page_size=PS, interpret=True)
    assert len(out) == 3


def test_a_saved_state_dict_loads_to_the_references_logits(params):
    """The tiny model written out under the checkpoint's names ([out, in]
    matrices, the filter as a depthwise ``Conv1d`` weight ``[d, 1, K]``)
    and read back: the loaded tree is the tree, and the served program on it
    gives the reference's logits."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    names = {
        "attn_norm": "operator_norm.weight", "mlp_norm": "ffn_norm.weight",
        "conv_in": "conv.in_proj.weight", "conv_out": "conv.out_proj.weight",
        "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
        "wv": "self_attn.v_proj.weight", "wo": "self_attn.out_proj.weight",
        "q_norm": "self_attn.q_layernorm.weight",
        "k_norm": "self_attn.k_layernorm.weight",
        "router": "feed_forward.gate.weight",
        "router_bias": "feed_forward.expert_bias",
    }
    ffn = {"gate": "w1", "up": "w3", "down": "w2"}
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.embedding_norm.weight": params["final_norm"]}
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        for ours, theirs in names.items():
            if ours in layer:
                w = np.asarray(layer[ours])
                sd[p + theirs] = w.T if w.ndim == 2 else w
        if "conv_w" in layer:
            sd[p + "conv.conv.weight"] = np.asarray(layer["conv_w"]).T[:, None, :]
        for ours, theirs in ffn.items():
            w = np.asarray(layer[f"w_{ours}"])
            if w.ndim == 2:
                sd[f"{p}feed_forward.{theirs}.weight"] = w.T
                continue
            for j in range(CFG.n_experts):
                sd[f"{p}feed_forward.experts.{j}.{theirs}.weight"] = w[j].T
    loaded = load_hf_state_dict(sd, CFG)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    prompt = prompt_of(77, 14)
    got, fed, _ = served(loaded, [(prompt, 8)], 2, "xla")
    want = reference_logits(params, prompt + fed[0])[len(prompt) - 1:]
    assert rel_err(got[0], want) < TOL
