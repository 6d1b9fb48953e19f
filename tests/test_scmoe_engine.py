"""The double layer through ``Engine``: the greedy tokens are the picks of
``chipbench/references/scmoe_mla.forward`` (float32) and the counters of a
layer that is told what it holds add up."""

import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_SCMOE
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig
from served_path import prompt_of

CFG = TINY_SCMOE
PS = 4
REF = chip_reference.load("scmoe_mla")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 7)


def reference_logits(params, tokens, cfg=CFG):
    return served_path.reference_logits(REF, params, cfg, tokens)


def make_engine(params, cfg=CFG, **engine):
    return served_path.make_engine(
        cfg, params, BlockManagerConfig(total_pages=96, page_size=PS), **engine)


def run_all(engine, prompts, n=6):
    return served_path.run_all(engine, prompts, n)


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
