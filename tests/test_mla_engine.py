"""Latent attention through ``Engine``: a shared document hits the prefix
cache and the greedy tokens are the reference's picks
(``chipbench/references/mla_moe.forward``, float32), the counters of a latent
pool, the events of a GQA model for the same requests, and a low-rank query
path.
"""

import dataclasses

import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import BlockStored
from llm_d_kv_cache_manager_tpu.models import TINY_QWEN3_MOE
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig
from served_path import prompt_of

CFG = served_path.ONE_OF_EACH_MLA  # depth is not these cases' point
PS = 4
REF = chip_reference.load("mla_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 11)


def reference_logits(params, tokens, cfg=CFG):
    return served_path.reference_logits(REF, params, cfg, tokens)


def make_engine(params, cfg=CFG, **engine):
    return served_path.make_engine(
        cfg, params, BlockManagerConfig(total_pages=96, page_size=PS), **engine)


def run_all(engine, prompts, n=6):
    return served_path.run_all(engine, prompts, n)


def stored_hashes(events):
    return [h for e in events if isinstance(e, BlockStored) for h in e.block_hashes]


@pytest.mark.parametrize("prefill_attn", ["xla", "pallas"])
def test_a_shared_document_hits_the_prefix_cache(params, prefill_attn):
    document = prompt_of(21, 32)
    asks = [document + prompt_of(22, 7), document + prompt_of(23, 10)]
    events = []
    engine = make_engine(params, on_events=events.extend, prefill_attn=prefill_attn)
    engine.obs_step_timing = True
    first = run_all(engine, asks[:1])[0]
    second = run_all(engine, asks[1:])[0]
    assert first.num_cached_prompt == 0 and second.num_cached_prompt == 32
    for seq, ask in zip((first, second), asks):
        # greedy tokens of an unshared run, and the reference's
        alone = run_all(make_engine(params), [ask])[0]
        assert seq.generated_tokens == alone.generated_tokens
        logits = reference_logits(params, ask + seq.generated_tokens)
        picks = logits[len(ask) - 1: -1].argmax(-1).tolist()
        assert seq.generated_tokens == picks
    # the counters of a latent pool: the context rows the decode dispatches
    # read, and what a token holds (one row a layer, no second pool)
    assert engine.step_stats["latent_ctx_tokens"] == sum(
        len(ask) + 1 + i for ask in asks for i in range(5)
    )
    row_bytes = CFG.kv_row_shape[0] * 4
    assert engine.kv_bytes_per_token == CFG.n_layers * row_bytes
    assert engine.kv_block_bytes == PS * CFG.n_layers * row_bytes
    assert engine.v_pages.nbytes == 0
    # pages, hashes and BlockStored know tokens, not heads: a GQA model of
    # the same tokenizer emits the same events for the same requests
    gqa_events = []
    gqa = make_engine(
        served_path.params_of(TINY_QWEN3_MOE, 1),
        cfg=TINY_QWEN3_MOE, on_events=gqa_events.extend,
    )
    for ask in asks:
        run_all(gqa, [ask], n=1)
    document_hashes = engine.block_manager.token_db.prefix_hashes(document)
    assert stored_hashes(events)[: len(document_hashes)] == document_hashes
    assert stored_hashes(gqa_events)[: len(document_hashes)] == document_hashes


def test_a_low_rank_query_path_runs():
    """What the engine refused until PR 41 (``q_lora_rank``): the same
    model with the query through a latent of 16 is served, and is another
    model than the full-rank one (``tests/test_scmoe.py`` holds the path to
    its reference)."""
    cfg = dataclasses.replace(CFG, q_lora_rank=16)
    params = served_path.params_of(cfg, 11)
    layer = params["layers"][1]
    assert "wq" not in layer and layer["wq_a"].shape == (64, 16)
    assert layer["wq_b"].shape == (16, 4 * 24) and layer["q_a_norm"].shape == (16,)
    ask = prompt_of(31, 21)
    seq = run_all(make_engine(params, cfg=cfg), [ask])[0]
    alone, _, _ = served_path.served(
        params, cfg, [(ask, 8)], 5, "xla", page_size=PS)
    assert seq.generated_tokens == alone[0].argmax(-1).tolist()
