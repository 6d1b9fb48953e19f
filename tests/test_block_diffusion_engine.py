"""Generation by diffusion over blocks through ``Engine``, token by token:
the tokens of the published procedure on the reference's logits
(``served_path.reference_generate`` over
``chipbench/references/moe_block_diffusion.forward``) alone and in a batch, at
every length, with a short prompt and with chunked prefill; a block fixed in
one forward, no page registered before its blocks are final, preemption and
abort in the middle of a block, pool pressure, a stop token inside a final
block, sampled lanes. The stored keys and values read back as logits:
``tests/test_block_diffusion.py``.
"""

import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import BlockStored
from llm_d_kv_cache_manager_tpu.models import TINY_SDAR_MOE
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    SamplingParams,
    SchedulerConfig,
)
from served_path import prompt_of, run_one

CFG = TINY_SDAR_MOE
B = CFG.block_length
MASK = CFG.mask_token_id
PS = 4
REF = chip_reference.load("moe_block_diffusion")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 11)


def make_engine(params, total_pages=96, on_events=None, **scheduler):
    return served_path.make_engine(
        CFG, params, BlockManagerConfig(total_pages=total_pages, page_size=PS),
        prefill_attn="xla", on_events=on_events,
        scheduler=SchedulerConfig(max_prefill_batch=4, **scheduler))


def reference_generate(params, prompt, max_tokens, steps=B, threshold=0.9):
    return served_path.reference_generate(
        REF, params, CFG, prompt, max_tokens, steps, threshold)


# -- alone and in a batch ------------------------------------------------------
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


# -- preemption, abort, lengths ------------------------------------------------
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
