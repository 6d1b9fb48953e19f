"""Latent attention (``LlamaConfig.kv_lora_rank`` > 0), shared experts, a
sigmoid router with a correction bias and a leading dense layer: the served
programs against the plain reference, at ``TINY_MLA_MOE`` in float32.

The reference side is ``chipbench/references/mla_moe.forward`` (float32, the
EXPANDED form, pairwise rotation, nothing of the program's model code). The
program is absorbed everywhere: its prefill is the kernel (interpreted) or
the kernel's ``jax.numpy`` oracle (``attn_impl="xla"``), its decode always
the kernel over the pool. These tests, not the chip's bf16 check, hold the
absorbed form to the expanded one, and the interpreted kernel to its oracle.
The kernel over tables of runs is in ``tests/test_mla_kernels.py``, the
router, the shared expert and the dense layer in ``tests/test_mla_layers.py``,
the engine in ``tests/test_mla_engine.py``, refusals, presets and the loader
in ``tests/test_mla_config.py``; the helpers they share with the other
architectures are ``tests/served_path.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_MLA_MOE, llama
from llm_d_kv_cache_manager_tpu.ops.mla_attention import (
    mla_paged_attention,
    mla_paged_attention_reference,
)
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
    """``served_path.served`` through the one pool of latent rows: the
    logits a row, [1 + steps, vocab], and the tokens fed."""
    got, fed, (_, v_pages, _) = served_path.served(
        params, cfg, rows, steps, attn_impl, page_size=PS)
    assert v_pages.nbytes == 0
    return got, fed


# -- the served programs against the expanded reference ------------------------
@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("rows", [
    pytest.param([(19, 0)], id="cold-alone"),
    pytest.param([(23, 12)], id="paged-context-alone"),
    pytest.param([(30, 16), (9, 0), (21, 8)], id="batch-of-unequal-lengths"),
])
def test_prefill_then_decode_through_the_pool(params, rows, attn_impl):
    rows = [(prompt_of(40 + i, n), r) for i, (n, r) in enumerate(rows)]
    got, fed = served(params, rows, 5, attn_impl)
    for (prompt, _), logits, tokens in zip(rows, got, fed):
        want = reference_logits(params, prompt + tokens)[len(prompt) - 1:]
        assert rel_err(logits, want) < TOL


def expanded_attention(layer, cfg, q_n, q_r, row, pool_l, block_tables,
                       ctx_lens, positions, valid):
    """One layer's attention in the EXPANDED form, plain ``jax.numpy``:
    every table page gathered, ``[k_n | v] = c W_kvb`` made for every
    context and chunk token and every head, one masked softmax. What the
    program never computes, and its absorbed form is held to."""
    b, s = positions.shape
    dc, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    t = block_tables.shape[1] * pool_l.shape[1]
    ctx = pool_l[block_tables].reshape(b, t, row.shape[-1])
    rows = jnp.concatenate([ctx, row], axis=1)  # [b, t + s, width]
    w_kb, w_vb = llama._mla_kvb(layer, cfg, row.dtype)
    k_n = jnp.einsum("bkc,chn->bkhn", rows[..., :dc], w_kb)
    v = jnp.einsum("bkc,chv->bkhv", rows[..., :dc], w_vb)
    scores = (
        jnp.einsum("bqhn,bkhn->bhqk", q_n, k_n)
        + jnp.einsum("bqhr,bkr->bhqk", q_r, rows[..., dc : dc + dr])
    ) * llama._mla_scale(cfg)
    ctx_ok = jnp.arange(t)[None, None, :] < ctx_lens[:, None, None]  # [b, 1, t]
    chunk_ok = (positions[:, None, :] <= positions[:, :, None]) & valid[:, None, :]
    mask = jnp.concatenate(
        [jnp.broadcast_to(ctx_ok, (b, s, t)), chunk_ok], axis=2
    ) & valid[:, :, None]
    probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # padded rows
    live = jnp.concatenate([ctx_ok[:, 0], jnp.ones((b, s), bool)], 1)
    v = jnp.where(live[..., None, None], v, 0)  # a dead table entry
    return jnp.einsum("bhqk,bkhv->bqhv", probs, v)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "oracle"])
def test_absorbed_against_expanded(params, kernel):
    """The two forms on the same inputs, one layer's attention: a paged
    context of unequal lengths, a chunk with a padded tail. The program's
    absorbed form by both of its paths."""
    rng = np.random.default_rng(3)
    layer = params["layers"][1]
    b, s, t_pages = 2, 6, 5
    x = jnp.asarray(rng.normal(size=(b, s, CFG.hidden_size)), jnp.float32)
    ctx_lens = jnp.asarray([17, 8], jnp.int32)
    positions = ctx_lens[:, None] + jnp.arange(s)[None, :]
    valid = jnp.asarray([[True] * 6, [True] * 4 + [False] * 2])
    pool = jnp.asarray(
        rng.normal(size=(2, 16, PS, *CFG.kv_row_shape)), jnp.float32
    ).at[..., CFG.latent_width:].set(0.0)
    tables = jnp.asarray(rng.permutation(16)[: b * t_pages].reshape(b, t_pages))
    inv_freq = jnp.asarray(
        llama.rope_frequencies(CFG.qk_rope_head_dim, CFG.rope_theta)
    )
    q_n, q_r, row = llama._mla_project(layer, CFG, x, positions, inv_freq)
    absorbed = llama._mla_absorbed(
        layer, CFG, q_n, q_r, row, pool, tables, ctx_lens,
        jnp.sum(valid, axis=1), layer_index=1, interpret=True, kernel=kernel,
    )
    expanded = expanded_attention(
        layer, CFG, q_n, q_r, row, pool[1], tables, ctx_lens, positions, valid
    )
    assert absorbed.shape == (b, s, CFG.n_heads, CFG.v_head_dim)
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5, rtol=1e-5)
    assert not np.asarray(absorbed[1, 4:]).any()  # padded rows give zeros


# -- the kernel against its oracle ----------------------------------------------
@pytest.mark.parametrize("s, ctxs, n_valid, key_block", [
    pytest.param(1, [0, 5, 150], [1, 1, 1], 32, id="decode-five-key-steps"),
    pytest.param(1, [9, 130, 37], [1, 0, 1], 32, id="decode-a-dead-lane"),
    pytest.param(20, [0, 45], [20, 7], 32, id="chunk-padded-tail"),
    pytest.param(150, [64, 200], [150, 33], 128, id="chunk-two-key-blocks"),
])
def test_kernel_against_its_oracle(s, ctxs, n_valid, key_block):
    rng = np.random.default_rng(0)
    b, heads, dk, dv, layers, pages, layer = len(ctxs), 4, 40, 32, 3, 96, 1
    q = jnp.asarray(rng.normal(size=(b, s, heads, dk)), jnp.float32)
    fresh = jnp.asarray(rng.normal(size=(b, s, dk)), jnp.float32)
    pool = rng.normal(size=(layers, pages, PS, dk)).astype(np.float32)
    pool[:, 0] = np.nan  # the dead tail of every table points here
    pool[[0, 2]] *= 1e3  # another layer's rows would be seen
    width = -(-max(ctxs) // PS) + 3
    tables = np.zeros((b, width), np.int32)
    order = rng.permutation(np.arange(1, pages))
    at = 0
    for i, ctx in enumerate(ctxs):
        n = -(-ctx // PS)
        tables[i, :n] = order[at:at + n]
        at += n
    args = (jnp.asarray(tables), jnp.asarray(ctxs, jnp.int32),
            jnp.asarray(n_valid, jnp.int32))
    got = mla_paged_attention(
        q, fresh, jnp.asarray(pool), *args, dv=dv, scale=0.3, interpret=True,
        key_block=key_block, layer=layer,
    )
    want = mla_paged_attention_reference(
        q, fresh, jnp.asarray(pool[layer]), *args, dv=dv, scale=0.3
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


def test_a_saved_state_dict_loads_to_the_references_logits(params):
    """The tiny model written out under the checkpoint's names ([out, in]
    matrices) and read back: the loaded tree is the tree, and the served
    program on it gives the reference's logits."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    names = {
        "attn_norm": "input_layernorm.weight", "mlp_norm": "post_attention_layernorm.weight",
        "wq": "self_attn.q_proj.weight", "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
        "kv_norm": "self_attn.kv_a_layernorm.weight", "wkv_b": "self_attn.kv_b_proj.weight",
        "wo": "self_attn.o_proj.weight", "router": "mlp.gate.weight",
        "router_bias": "mlp.gate.e_score_correction_bias",
    }
    ffn = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["final_norm"],
          "lm_head.weight": params["lm_head"].T}
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        for ours, theirs in names.items():
            if ours in layer:
                w = np.asarray(layer[ours])
                sd[p + theirs] = w.T if w.ndim == 2 else w
        for ours, theirs in ffn.items():
            w = np.asarray(layer[f"w_{ours}"])
            if w.ndim == 2:
                sd[f"{p}mlp.{theirs}.weight"] = w.T
                continue
            for j in range(CFG.n_experts):
                sd[f"{p}mlp.experts.{j}.{theirs}.weight"] = w[j].T
            sd[f"{p}mlp.shared_experts.{theirs}.weight"] = np.asarray(layer[f"ws_{ours}"]).T
    loaded = load_hf_state_dict(sd, CFG)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    prompt = prompt_of(77, 14)
    got, fed = served(loaded, [(prompt, 8)], 2, "xla")
    want = reference_logits(params, prompt + fed[0])[len(prompt) - 1:]
    assert rel_err(got[0], want) < TOL
