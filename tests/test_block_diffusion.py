"""Generation by diffusion over blocks (``LlamaConfig.block_length`` > 0)
on the served path, against the plain reference, at ``TINY_SDAR_MOE`` in
float32. These tests, not the chip's bf16 check, hold the block mask to 1e-4.

The reference side is ``chipbench/references/moe_block_diffusion.forward``
(float32, nothing of the program's model code) and, around it, the published
generation procedure written out plainly (``reference_generate``). The
engine's stored keys and values are read back as logits: after a request
the next block, all masks, is forwarded against the pages the engine wrote
(``probe_logits``), which the prefill's mask, the in-block mask and the
commit all have to be right for.
"""

import asyncio
import dataclasses
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference as chip_reference  # noqa: E402
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import BlockStored  # noqa: E402
from llm_d_kv_cache_manager_tpu.models import TINY_SDAR_MOE, llama  # noqa: E402
from llm_d_kv_cache_manager_tpu.ops.attention import (  # noqa: E402
    prefill_with_paged_context,
)
from llm_d_kv_cache_manager_tpu.ops.flash_prefill import (  # noqa: E402
    flash_prefill_paged,
)
from llm_d_kv_cache_manager_tpu.server import (  # noqa: E402
    BlockManagerConfig,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.engine import Engine  # noqa: E402
from llm_d_kv_cache_manager_tpu.server.serve import (  # noqa: E402
    PodServer,
    PodServerConfig,
    _resolve_model,
)

CFG = TINY_SDAR_MOE
B = CFG.block_length
MASK = CFG.mask_token_id
PS = 4
TOL = 1e-4
REF = chip_reference.load("moe_block_diffusion")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(11), CFG)


def prompt_of(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(1, 200, n).tolist()


def make_engine(params, cfg=CFG, prefill_attn="xla", total_pages=96, lanes=4,
                on_events=None, **scheduler):
    return Engine(
        EngineConfig(
            model=cfg,
            block_manager=BlockManagerConfig(total_pages=total_pages, page_size=PS),
            scheduler=SchedulerConfig(max_prefill_batch=4, **scheduler),
            max_model_len=128, decode_batch_size=lanes, prefill_bucket=16,
            prefill_attn=prefill_attn, interpret=True,
        ),
        params=params, on_events=on_events,
    )


def reference_logits(params, tokens) -> np.ndarray:
    return np.asarray(REF.forward(params, CFG, list(tokens))[0], np.float32)


def reference_generate(params, prompt, max_tokens, steps=B, threshold=0.9):
    """The published procedure on the reference's logits, greedy: the
    completion, and every token of the final blocks (prompt included)."""
    n_final = len(prompt) // B * B
    final, tail = list(prompt[:n_final]), list(prompt[n_final:])
    while len(final) - len(prompt) < max_tokens:
        cur = tail + [MASK] * (B - len(tail))
        masked = np.array([False] * len(tail) + [True] * (B - len(tail)))
        step = 0
        while masked.any():
            logits = reference_logits(params, final + cur)[-B:]
            probs = np.exp(logits - logits.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)
            x0, p = probs.argmax(-1), probs.max(-1)
            owed = B // steps + (step < B % steps)
            high = masked & (p > threshold)
            if high.sum() >= owed:
                fix = high
            else:
                order = np.argsort(-np.where(masked, p, -np.inf), kind="stable")
                fix = np.zeros(B, bool)
                fix[order[:owed]] = True
                fix &= masked
            cur = [int(x0[i]) if fix[i] else cur[i] for i in range(B)]
            masked &= ~fix
            step += 1
        final, tail = final + cur, []
    return final[len(prompt):len(prompt) + max_tokens], final


def run_one(engine, prompt, **sampling):
    """(the finished sequence, its block table while it ran)."""
    seq = engine.add_request(prompt, SamplingParams(**sampling))
    table = []
    while engine.has_work:
        engine.step()
        table = list(seq.block_table) or table
    return seq, table


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


def rel_err(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


# -- (a), (b): the engine against the reference --------------------------------
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
    """(b) The same comparison with ``block_length`` 0: the keys and values
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


# -- (c): alone and in a batch -------------------------------------------------
SETTINGS = [(steps, thr) for steps in (1, 2, 4) for thr in (0.0, 0.9)]


def _request(i: int):
    steps, thr = SETTINGS[i]
    prompt = prompt_of(20 + i, 14 + i)  # tails 2, 3, 0, 1, 2, 3
    return prompt, dict(max_new_tokens=9 + i, denoising_steps=steps,
                        confidence_threshold=thr)


@pytest.fixture(scope="module")
def batched(params):
    """All six requests through one engine of four lanes, admitted two
    steps apart, so lanes sit at other steps of other blocks."""
    engine = make_engine(params)
    seqs = []
    for i in range(len(SETTINGS)):
        prompt, sampling = _request(i)
        seqs.append(engine.add_request(prompt, SamplingParams(**sampling)))
        engine.step()
        engine.step()
    engine.run_until_complete()
    return [s.generated_tokens for s in seqs]


@pytest.mark.parametrize("i", range(len(SETTINGS)), ids=lambda i: "steps%d-thr%s" % SETTINGS[i])
def test_tokens_alone_and_in_a_batch(params, batched, i):
    prompt, sampling = _request(i)
    alone, _ = run_one(make_engine(params), prompt, **sampling)
    steps, thr = SETTINGS[i]
    want, _ = reference_generate(params, prompt, sampling["max_new_tokens"],
                                 steps=steps, threshold=thr)
    assert alone.generated_tokens == want
    assert batched[i] == want
    assert alone.num_generated == sampling["max_new_tokens"]


def test_threshold_zero_fixes_a_block_in_one_forward(params):
    engine = make_engine(params)
    engine.obs_step_timing = True
    run_one(engine, prompt_of(1, 16), max_new_tokens=2 * B,
            confidence_threshold=0.0)
    stats = engine.step_stats
    # a block: one denoising forward, one committing forward
    assert stats["denoise_lane_forwards"] == stats["commit_lane_forwards"] == 2
    assert stats["blocks_final"] == 2 and stats["block_tokens_fixed"] == 2 * B
    assert stats["decode_dispatches"] == stats["decode_rows"] == 4
    # distinct experts a dispatch's rows chose, summed over layers and dispatches
    cfg = engine.model_cfg
    assert (4 * cfg.n_layers * cfg.n_experts_per_tok <= stats["experts_touched"]
            <= 4 * cfg.n_layers * cfg.n_experts)


# -- (d): the prefix cache and the events --------------------------------------
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


def test_no_page_is_registered_before_its_blocks_are_final(params):
    """Step by step: what is registered never passes the final tokens, and
    a block in progress (its rows lie past ``num_computed``) is in no
    event."""
    events = []
    engine = make_engine(params, on_events=events.extend)
    seq = engine.add_request(prompt_of(9, 18), SamplingParams(max_new_tokens=14))
    mid_block = 0
    while engine.has_work:
        engine.step()
        if seq.block_table:
            assert seq.num_computed % B == 0
            assert seq.num_registered_pages * PS <= seq.num_computed
            mid_block += seq.block_tokens is not None
        stored = sum(len(e.block_hashes) for e in events
                     if isinstance(e, BlockStored))
        assert stored * PS <= max(seq.num_computed, 16)
    assert mid_block > 0 and seq.num_generated == 14


# -- (e): preemption, abort, lengths -------------------------------------------
def _step_into_block(engine, seq):
    """Step until ``seq`` stands in the middle of a block (some rows fixed,
    some masked) after at least one final block of output."""
    for _ in range(200):
        engine.step()
        if (seq.num_generated and seq.block_masked
                and 0 < sum(seq.block_masked) < B):
            return
    raise AssertionError("never stood in the middle of a block")


def test_preemption_in_the_middle_of_a_block(params):
    prompt = prompt_of(13, 18)
    want, _ = run_one(make_engine(params), prompt, max_new_tokens=17)
    engine = make_engine(params)
    seq = engine.add_request(prompt, SamplingParams(max_new_tokens=17))
    _step_into_block(engine, seq)
    generated = seq.num_generated
    # what ``_grow_or_preempt`` does to its victim
    engine.scheduler.on_preempted(seq)
    engine.block_manager.free_sequence(seq)
    seq.fold_for_preemption()
    engine.scheduler.waiting.appendleft(seq)
    assert seq.block_tokens is None and seq.num_generated == generated
    assert len(seq.prompt_tokens) == len(prompt) + generated  # final tokens only
    engine.run_until_complete()
    assert seq.generated_tokens == want.generated_tokens
    assert seq.num_generated == 17 and seq.num_cached_prompt > 0


def test_pool_pressure_preempts_and_finishes(params):
    """A pool too small for four growing lanes: some lane is preempted where
    it stands, and every request still gets its tokens."""
    prompts = [prompt_of(30 + i, 15 + i) for i in range(4)]
    want = [run_one(make_engine(params), p, max_new_tokens=21)[0].generated_tokens
            for p in prompts]
    engine = make_engine(params, total_pages=30)
    seqs = [engine.add_request(p, SamplingParams(max_new_tokens=21)) for p in prompts]
    engine.run_until_complete()
    assert [s.generated_tokens for s in seqs] == want
    assert any(len(s.prompt_tokens) > s.user_prompt_len for s in seqs)  # folded


def test_abort_in_the_middle_of_a_block(params):
    other_prompt = prompt_of(41, 17)
    want, _ = run_one(make_engine(params), other_prompt, max_new_tokens=13)
    engine = make_engine(params)
    free = engine.block_manager.num_free
    victim = engine.add_request(prompt_of(40, 18), SamplingParams(max_new_tokens=40),
                                request_id="victim")
    other = engine.add_request(other_prompt, SamplingParams(max_new_tokens=13))
    _step_into_block(engine, victim)
    assert engine.abort("victim") is victim
    assert victim.finish_reason == "abort" and not victim.block_table
    assert 0 < victim.num_generated < 40 and victim.num_generated % B == 2
    engine.run_until_complete()
    assert other.generated_tokens == want.generated_tokens
    assert engine.block_manager.num_free == free


@pytest.mark.parametrize("max_tokens", [1, 2, 5, 7, 8])
def test_completion_is_max_tokens_long(params, max_tokens):
    seq, _ = run_one(make_engine(params), prompt_of(3, 19), max_new_tokens=max_tokens)
    want, _ = reference_generate(params, prompt_of(3, 19), max_tokens)
    assert seq.num_generated == len(seq.generated_tokens) == max_tokens
    assert seq.generated_tokens == want
    assert seq.first_token_time is not None and seq.ttft >= 0


def test_stop_token_inside_a_final_block(params):
    full, _ = run_one(make_engine(params), prompt_of(3, 19), max_new_tokens=12)
    stop = full.generated_tokens[5]
    cut = full.generated_tokens[: full.generated_tokens.index(stop) + 1]
    seq, _ = run_one(make_engine(params), prompt_of(3, 19), max_new_tokens=12,
                     stop_token_ids=(stop,))
    assert seq.generated_tokens == cut


def test_short_prompt_and_chunked_prefill(params):
    """A prompt shorter than a block has no prefill at all; chunked
    scheduling cuts a long one at block boundaries; both give the
    reference's tokens."""
    for prompt, kw in ((prompt_of(2, 3), {}),
                       (prompt_of(4, 45), {"chunked_prefill_tokens": 16})):
        engine = make_engine(params, **kw)
        seq, _ = run_one(engine, prompt, max_new_tokens=6)
        assert seq.generated_tokens == reference_generate(params, prompt, 6)[0]


def test_sampled_lanes_fix_rows_and_count(params):
    engine = make_engine(params)
    engine.obs_step_timing = True
    seq, _ = run_one(engine, prompt_of(6, 16), max_new_tokens=8, temperature=0.8,
                     top_k=20, top_p=0.9)
    assert seq.num_generated == 8 and MASK not in seq.generated_tokens
    stats = engine.step_stats
    assert stats["decode_sampled_dispatches"] == stats["decode_dispatches"] > 0


# -- (f): block_length 0 and 1 are the programs that were there ---------------
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


# -- refusals, the loader, the API ---------------------------------------------
@pytest.mark.parametrize("what", [
    dict(sp=2), dict(kv_quant_hbm="int8"), dict(spec_decode="prompt_lookup"),
    dict(decode_steps_per_iter=2), dict(decode_steps_per_iter=4),
    dict(block_manager=BlockManagerConfig(total_pages=16, page_size=6)),
])
def test_engine_refuses_by_name(what):
    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, **({"prefill_bucket": 16} if "sp" in what else {}))
    config = dataclasses.replace(config, **what)
    with pytest.raises(ValueError, match="block_length"):
        Engine(config)


def test_presets_and_loader():
    sdar = _resolve_model("JetLM/SDAR-30B-A3B-Chat")
    qwen = _resolve_model("Qwen/Qwen3-30B-A3B")
    assert (sdar.block_length, sdar.mask_token_id) == (4, 151669)
    assert dataclasses.replace(sdar, block_length=0, mask_token_id=0) == qwen
    assert _resolve_model("tiny-sdar-moe") is TINY_SDAR_MOE

    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    class SDARMoeConfig:  # the published config.json's keys
        model_type = "sdar_moe"
        vocab_size, hidden_size, intermediate_size = 151936, 2048, 6144
        num_hidden_layers, num_attention_heads, num_key_value_heads = 48, 32, 4
        head_dim, rope_theta, rope_scaling, rms_norm_eps = 128, 1000000, None, 1e-6
        attention_bias, tie_word_embeddings, hidden_act = False, False, "silu"
        num_experts, num_experts_per_tok, moe_intermediate_size = 128, 8, 768
        norm_topk_prob, decoder_sparse_step, mlp_only_layers = True, 1, []

    assert config_from_hf(SDARMoeConfig()) == sdar
    SDARMoeConfig.model_type = "qwen3_moe"
    assert config_from_hf(SDARMoeConfig()).block_length == 0


@pytest.mark.parametrize("model,body,status", [
    ("tiny-sdar-moe", {"denoising_steps": 2, "confidence_threshold": 0.5,
                       "remasking_strategy": "low_confidence_dynamic"}, 200),
    ("tiny-sdar-moe", {"remasking_strategy": "sequential"}, 400),
    ("tiny-sdar-moe", {"denoising_steps": 0}, 400),
    ("tiny-sdar-moe", {"denoising_steps": B + 1}, 400),
    ("tiny-sdar-moe", {"denoising_steps": "many"}, 400),
    ("tiny-qwen3-moe", {"denoising_steps": 2}, 400),
    ("tiny-qwen3-moe", {"remasking_strategy": "low_confidence_dynamic"}, 400),
    ("tiny-qwen3-moe", {}, 200),
])
def test_completions_api(model, body, status):
    server = PodServer(PodServerConfig(
        model_name=model, pod_identifier="pod-bd", publish_events=False,
        engine=EngineConfig(
            model=_resolve_model(model),
            block_manager=BlockManagerConfig(total_pages=64, page_size=PS),
            max_model_len=64, decode_batch_size=4, prefill_bucket=8,
            interpret=True),
    ))
    server.start()

    async def scenario():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            resp = await client.post("/v1/completions", json={
                "prompt_token_ids": prompt_of(1, 10), "max_tokens": 6, **body})
            return resp.status, await resp.json()
        finally:
            await client.close()

    try:
        got, data = asyncio.run(scenario())
    finally:
        server.shutdown()
    assert got == status, data
    if status == 200:
        assert data["usage"]["completion_tokens"] == 6
        assert len(data["choices"][0]["token_ids"]) == 6 and data["ttft_s"] >= 0
    else:
        assert "error" in data


@pytest.mark.parametrize("steps,threshold", [(None, None), (2, None), (3, 0.5)])
def test_migration_frame_carries_the_denoising_parameters(steps, threshold):
    from llm_d_kv_cache_manager_tpu.kvcache.transfer import protocol

    sent = protocol.MigrationPayload(
        request_id="r", token_ids=[1, 2, 3], user_prompt_len=2, num_generated=1,
        max_new_tokens=9, temperature=0.0, top_k=0, top_p=1.0,
        stop_token_ids=(7,), deadline_remaining_s=None,
        denoising_steps=steps, confidence_threshold=threshold)
    frame = protocol.encode_migrate("m", "pod-a", sent)
    _, _, got = protocol.decode_migrate(frame)
    assert got == sent
    if (steps, threshold) == (None, None):  # the frame it always was
        bare = dataclasses.replace(sent)
        assert frame == protocol.encode_migrate("m", "pod-a", bare)
        assert len(protocol._unpack(frame)) == 10
