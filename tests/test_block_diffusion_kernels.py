"""``block_length`` 0 and 1 are the programs that were there, and both
attention paths (the flash kernel, interpreted, and the XLA scan) under the
block mask against a dense softmax. The served path under the mask is held
to the reference in ``tests/test_block_diffusion.py``.
"""

import dataclasses

import jax
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_SDAR_MOE, llama
from llm_d_kv_cache_manager_tpu.ops.attention import prefill_with_paged_context
from llm_d_kv_cache_manager_tpu.ops.flash_prefill import flash_prefill_paged
from served_path import prompt_of

CFG = TINY_SDAR_MOE
B = CFG.block_length
PS = 4


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(11), CFG)


# -- block_length 0 and 1 are the programs that were there ---------------------
def _attention_inputs():
    rng = np.random.default_rng(0)
    b, s, n_q, n_kv, d = 2, 12, 4, 2, 24
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    return dict(
        q=f(b, s, n_q, d), k=f(b, s, n_kv, d), v=f(b, s, n_kv, d),
        k_pages=f(6, PS, n_kv, d), v_pages=f(6, PS, n_kv, d),
        block_tables=np.asarray([[1, 2], [3, 4]], np.int32),
        ctx_lens=np.asarray([8, 5], np.int32),
    )


def _run_program(name: str, params, block_length: int):
    cfg = dataclasses.replace(CFG, block_length=block_length)
    a = _attention_inputs()
    if name == "prefill_with_paged_context":
        pos = a["ctx_lens"][:, None] + np.arange(12)[None, :]
        return prefill_with_paged_context(
            a["q"], a["k"], a["v"], a["k_pages"], a["v_pages"],
            a["block_tables"], a["ctx_lens"], positions=pos,
            valid=np.arange(12)[None, :] < np.asarray([[12], [9]]),
            block_length=block_length)
    if name == "flash_prefill_paged":
        return flash_prefill_paged(
            a["q"], a["k"], a["v"], a["k_pages"], a["v_pages"],
            a["block_tables"], a["ctx_lens"], np.asarray([12, 9], np.int32),
            interpret=True, block_length=block_length)
    tokens = np.asarray([prompt_of(8, 12)], np.int32)
    pos = np.arange(12, dtype=np.int32)[None, :]
    k_pages, v_pages = llama.init_kv_pages(cfg, 8, PS)
    logits, k_pages, v_pages = llama.prefill(
        params, cfg, tokens, pos, np.ones((1, 12), bool), k_pages, v_pages,
        1 + pos // PS, pos % PS, np.zeros((1, 0), np.int32),
        np.zeros((1,), np.int32), interpret=True)
    if name == "prefill":
        return logits, k_pages
    toks, k_pages, _ = llama.decode_steps(
        params, cfg, np.asarray([7], np.int32),
        llama.pack_decode_inputs(
            np.asarray([12]), np.asarray([[1, 2, 3, 4]]), np.asarray([13]),
            np.zeros((1,), np.float32), np.zeros((1,), np.int32),
            np.ones((1,), np.float32)),
        k_pages, v_pages, jax.random.PRNGKey(0), page_size=PS, num_steps=3,
        interpret=True)
    return toks[:, 1:], k_pages


@pytest.mark.parametrize("name", ["prefill", "decode_steps", "flash_prefill_paged",
                                  "prefill_with_paged_context"])
def test_block_length_one_is_the_causal_program(params, name):
    zero = jax.tree.leaves(_run_program(name, params, 0))
    one = jax.tree.leaves(_run_program(name, params, 1))
    assert len(zero) == len(one)
    for x, y in zip(zero, one):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("name", ["flash_prefill_paged", "prefill_with_paged_context"])
def test_block_mask_in_the_attention_paths(name):
    """Both attention paths against a dense softmax under the block mask,
    with context, a ragged batch and a chunk that starts on a block
    boundary."""
    a = _attention_inputs()
    a["ctx_lens"] = np.asarray([8, 4], np.int32)  # whole blocks
    n_valid = np.asarray([12, 9])
    if name == "flash_prefill_paged":
        got = flash_prefill_paged(**a, n_valid=n_valid.astype(np.int32),
                                  interpret=True, block_length=B)
    else:
        got = prefill_with_paged_context(
            **a, positions=a["ctx_lens"][:, None] + np.arange(12)[None, :],
            valid=np.arange(12)[None, :] < n_valid[:, None], block_length=B)
    got = np.asarray(got)
    for i in range(2):
        c, n = int(a["ctx_lens"][i]), int(n_valid[i])
        ctx_k = a["k_pages"][a["block_tables"][i]].reshape(-1, 2, 24)[:c]
        ctx_v = a["v_pages"][a["block_tables"][i]].reshape(-1, 2, 24)[:c]
        keys = np.concatenate([ctx_k, a["k"][i, :n]]).repeat(2, axis=1)
        vals = np.concatenate([ctx_v, a["v"][i, :n]]).repeat(2, axis=1)
        pos = np.arange(c + n)
        sees = pos[None, :] // B <= pos[c:, None] // B
        scores = np.einsum("qhd,khd->hqk", a["q"][i, :n], keys) / np.sqrt(24)
        scores = np.where(sees[None], scores, -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        want = np.einsum("hqk,khd->qhd", probs, vals)
        np.testing.assert_allclose(got[i, :n], want, atol=2e-5, rtol=2e-5)
