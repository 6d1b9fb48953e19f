"""Generation by diffusion over blocks (``LlamaConfig.block_length`` > 0)
on the served path, against the plain reference, at ``TINY_SDAR_MOE`` in
float32. These tests, not the chip's bf16 check, hold the block mask to 1e-4.

The reference side is ``chipbench/references/moe_block_diffusion.forward``
(float32, nothing of the program's model code) and, around it, the published
generation procedure written out plainly (``served_path.reference_generate``). The
engine's stored keys and values are read back as logits: after a request
the next block, all masks, is forwarded against the pages the engine wrote
(``probe_logits``), which the prefill's mask, the in-block mask and the
commit all have to be right for. The tokens through the engine (alone and in
a batch, lengths, chunked prefill, events, preemption, abort, stop tokens,
sampled lanes) are in ``tests/test_block_diffusion_engine.py``, the attention
paths alone
under the block mask in ``tests/test_block_diffusion_kernels.py``, refusals,
the preset,
the loader, the API and the migration frame in
``tests/test_block_diffusion_config.py``; the helpers shared with the other
architectures are ``tests/served_path.py``.
"""

import dataclasses

import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import BlockStored
from llm_d_kv_cache_manager_tpu.models import TINY_SDAR_MOE, llama
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig
from served_path import prompt_of, rel_err, run_one

CFG = TINY_SDAR_MOE
B = CFG.block_length
MASK = CFG.mask_token_id
PS = 4
TOL = 1e-4
REF = chip_reference.load("moe_block_diffusion")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 11)


def make_engine(params, cfg=CFG, prefill_attn="xla", on_events=None):
    return served_path.make_engine(
        cfg, params, BlockManagerConfig(total_pages=96, page_size=PS),
        prefill_attn=prefill_attn, on_events=on_events)


def reference_logits(params, tokens) -> np.ndarray:
    return served_path.reference_logits(REF, params, CFG, tokens)


def reference_generate(params, prompt, max_tokens):
    return served_path.reference_generate(
        REF, params, CFG, prompt, max_tokens, steps=B, threshold=0.9)


def probe_logits(engine, table, n_final: int) -> np.ndarray:
    """Logits [B, vocab] of the block after ``n_final`` final tokens, all
    masks, forwarded against the pages the engine wrote for them."""
    free = engine.config.block_manager.total_pages - 1  # never handed out here
    pages = -(-(n_final + B) // PS)
    bt = (table + [free] * pages)[:pages]
    logits, engine.k_pages, engine.v_pages = llama.denoise_step(
        engine.params, CFG, np.full((1, B), MASK, np.int32),
        np.asarray([n_final], np.int32), engine.k_pages, engine.v_pages,
        np.asarray([bt], np.int32), page_size=PS,
        attn_impl=engine.prefill_attn, interpret=True,
    )
    return np.asarray(logits, np.float32)[0]


# -- the engine against the reference ------------------------------------------
@pytest.mark.parametrize("prefill_attn", ["xla", "pallas"])
@pytest.mark.parametrize("tail", [0, 1, 3])
def test_engine_agrees_with_reference(params, tail, prefill_attn):
    """Prefill + two generated blocks: the tokens are the reference
    procedure's, and the keys and values the engine stored give the
    reference's logits for the next block."""
    prompt = prompt_of(tail, 16 + tail)
    max_tokens = 2 * B - tail  # the first block holds the prompt's tail
    engine = make_engine(params, prefill_attn=prefill_attn)
    seq, table = run_one(engine, prompt, max_new_tokens=max_tokens)
    want_tokens, final = reference_generate(params, prompt, max_tokens)
    assert seq.generated_tokens == want_tokens
    assert seq.num_generated == max_tokens and len(final) == 16 + 2 * B
    got = probe_logits(engine, table, len(final))
    want = reference_logits(params, final + [MASK] * B)[-B:]
    assert rel_err(got, want) < TOL


@pytest.mark.parametrize("prefill_attn", ["xla", "pallas"])
def test_causal_engine_fails_the_reference(params, prefill_attn):
    """The same comparison with ``block_length`` 0: the keys and values
    of an autoregressive run of the same weights are not the reference's."""
    prompt = prompt_of(0, 16)
    causal = dataclasses.replace(CFG, block_length=0)
    engine = make_engine(params, cfg=causal, prefill_attn=prefill_attn)
    seq, table = run_one(engine, prompt, max_new_tokens=2 * B + 1)
    final = (prompt + seq.generated_tokens)[: 16 + 2 * B]
    got = probe_logits(engine, table, len(final))
    want = reference_logits(params, final + [MASK] * B)[-B:]
    assert rel_err(got, want) > 100 * TOL


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("block_length,holds", [(B, True), (0, False), (1, False)])
def test_prefill_logits_hold_the_mask(params, attn_impl, block_length, holds):
    """Every row of a prefill against the reference: to 1e-4 with the
    model's block length, far off with the causal program."""
    tokens = prompt_of(5, 24)
    cfg = dataclasses.replace(CFG, block_length=block_length)
    k_pages, v_pages = llama.init_kv_pages(cfg, 8, PS)
    pos = np.arange(24)[None, :]
    logits, _, _ = llama.prefill(
        params, cfg, np.asarray([tokens], np.int32), pos.astype(np.int32),
        np.ones((1, 24), bool), k_pages, v_pages,
        (1 + pos // PS).astype(np.int32), (pos % PS).astype(np.int32),
        np.zeros((1, 0), np.int32), np.zeros((1,), np.int32),
        attn_impl=attn_impl, return_all_logits=True, interpret=True,
    )
    err = rel_err(np.asarray(logits, np.float32)[0], reference_logits(params, tokens))
    assert (err < TOL) if holds else (err > 100 * TOL)


# -- the prefix cache and the events -------------------------------------------
def test_second_turn_hits_the_cache_and_reads_cold_logits(params):
    events = []
    warm = make_engine(params, on_events=events.extend)
    first, _ = run_one(warm, prompt_of(7, 17), max_new_tokens=11)
    turn2 = first.prompt_tokens + first.generated_tokens  # 28 tokens
    second, table = run_one(warm, turn2, max_new_tokens=B)
    assert second.num_cached_prompt == 24  # never the whole prompt
    cold_engine = make_engine(params)
    cold, cold_table = run_one(cold_engine, turn2, max_new_tokens=B)
    assert cold.num_cached_prompt == 0
    assert second.generated_tokens == cold.generated_tokens
    n_final = len(turn2) + B
    got = probe_logits(warm, table, n_final)
    want = probe_logits(cold_engine, cold_table, n_final)
    assert rel_err(got, want) < 1e-5
    # every page the events name holds final blocks only: its hash is one of
    # the chain over the final tokens, and no mask id is in it
    final = second.prompt_tokens + second.generated_tokens
    chain = set(warm.block_manager.token_db.prefix_hashes(final))
    stored = [e for e in events if isinstance(e, BlockStored)]
    assert len(stored) == len(final) // PS
    for ev in stored:
        assert set(ev.block_hashes) <= chain
        assert MASK not in ev.token_ids and len(ev.token_ids) == PS
