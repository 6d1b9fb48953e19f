"""One dispatch ahead in generation by diffusion over blocks
(``Engine._run_decode_block``): a lane's block in progress stays on the device
between two forwards (``llama.denoise_steps``' ``carried``), under the rule of
the fused path (``Engine._next_schedule_decided``; ``tests/test_run_ahead.py``).

Greedy outputs under the rule equal, token for token, those of the same engine
whose rule is patched to "never" (``both`` of ``tests/run_ahead.py``) over
every edge a chain of forwards meets; chaining compiles nothing; a pool too
tight for the block after the next degrades to the engine that waits; nothing
is registered or published before its block is final; the program is callable
as the benchmark's reference calls it. Every engine here but one has two lanes,
all one table width and one set of parameters: the programs compile once.
"""

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import BlockStored
from llm_d_kv_cache_manager_tpu.models import TINY_SDAR_MOE, llama
from llm_d_kv_cache_manager_tpu.server import SamplingParams
from llm_d_kv_cache_manager_tpu.server.block_manager import AllocationError
from run_ahead import PS, both, make_engine, never_ahead, prompt

CFG = TINY_SDAR_MOE
B = CFG.block_length
VOCAB = CFG.vocab_size - 8  # below the mask token's id


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 11)


def _engine(params, total_pages=64, lanes=2, **kw):
    return make_engine(
        total_pages=total_pages, lanes=lanes, model=CFG, params=params, **kw
    )


def _add(eng, seed, plen, **sampling):
    return eng.add_request(
        prompt(seed, plen, VOCAB), SamplingParams(**sampling),
        request_id=f"r{seed}",
    )


def _tokens(seqs):
    assert all(s.error is None for s in seqs)
    return [list(s.generated_tokens) for s in seqs]


def _run(eng, requests):
    seqs = [_add(eng, *r[:2], **r[2]) for r in requests]
    eng.run_until_complete()
    return seqs


# -- parity with the engine that never runs ahead ------------------------------
def _mixed_steps(eng):
    # lanes at other steps of other blocks: 2 and 4 forwards a block, prompt
    # tails 1, 2 and 3, a third request that takes the first lane freed
    seqs = _run(eng, [
        (100, 9, dict(max_new_tokens=24, denoising_steps=2)),
        (101, 10, dict(max_new_tokens=20, denoising_steps=4)),
        (102, 11, dict(max_new_tokens=12, denoising_steps=2)),
    ])
    return _tokens(seqs)


def _one_forward_a_block(eng):
    # threshold 0: the device finds the next forward to be the committing
    # one before the host has seen the block
    seqs = _run(eng, [
        (110, 9, dict(max_new_tokens=24, confidence_threshold=0.0)),
        (111, 12, dict(max_new_tokens=16, confidence_threshold=0.0,
                       denoising_steps=2)),
        (112, 10, dict(max_new_tokens=8, confidence_threshold=0.0)),
    ])
    stats = eng.step_stats
    assert stats["denoise_lane_forwards"] == stats["commit_lane_forwards"]
    return _tokens(seqs)


def _stop_inside_a_final_block(eng):
    probe = _engine(eng.params)
    full = _add(probe, 120, 9, max_new_tokens=16)
    probe.run_until_complete()
    stop = full.generated_tokens[9]  # the second row of its third block
    cut = full.generated_tokens[: full.generated_tokens.index(stop) + 1]
    seqs = _run(eng, [
        (120, 9, dict(max_new_tokens=30, stop_token_ids=(stop,))),
        (121, 9, dict(max_new_tokens=24)),
        (122, 9, dict(max_new_tokens=8)),
    ])
    assert seqs[0].generated_tokens == cut
    # the forward enqueued behind the one that held the stop token opened a
    # block for a lane that had ended: discarded, and counted in no lane's
    stats = eng.step_stats
    surplus = stats["decode_rows"] - (
        stats["denoise_lane_forwards"] + stats["commit_lane_forwards"]
    )
    assert surplus == (stats["decode_chained_dispatches"] > 0)
    return _tokens(seqs)


def _budgets_inside_a_block(eng):
    # no budget ends with its block: every finish cuts a final block short
    # and frees a lane for one who waits
    seqs = _run(eng, [
        (130 + i, 8 + i, dict(max_new_tokens=n, denoising_steps=2 + i % 3))
        for i, n in enumerate((13, 7, 22, 5, 9))
    ])
    assert [s.num_generated for s in seqs] == [13, 7, 22, 5, 9]
    return _tokens(seqs)


def _sampled(eng):
    # threshold 1: a forward fixes what the schedule owes and no more,
    # whatever the sampler draws; no dispatch is discarded here, so the
    # engine's key is split as often under the rule as without it
    seqs = _run(eng, [
        (140, 8, dict(max_new_tokens=16, temperature=0.8, top_k=20,
                      confidence_threshold=1.0, denoising_steps=2)),
        (141, 12, dict(max_new_tokens=16, confidence_threshold=1.0,
                       denoising_steps=2)),
    ])
    stats = eng.step_stats
    assert stats["block_tokens_fixed"] == 2 * stats["denoise_lane_forwards"]
    assert stats["decode_sampled_dispatches"] == stats["decode_dispatches"]
    assert CFG.mask_token_id not in seqs[0].generated_tokens
    return _tokens(seqs)


def _spy_refusals(eng):
    """Whether a forward was in flight at each reservation the pool refused,
    and the victims of preemption in order."""
    bm, sched = eng.block_manager, eng.scheduler
    reserve, preempted = bm.reserve_slots, sched.on_preempted
    refused, victims = [], []

    def reserve_spy(seq, n):
        try:
            return reserve(seq, n)
        except AllocationError:
            refused.append(eng._inflight is not None)
            raise

    def preempted_spy(seq):
        mid = seq.block_masked is not None and 0 < sum(seq.block_masked) < B
        victims.append((seq.request_id, mid))
        return preempted(seq)

    bm.reserve_slots, sched.on_preempted = reserve_spy, preempted_spy
    return refused, victims


def _preemption_inside_a_block(eng):
    refused, victims = _spy_refusals(eng)
    # (four lanes: two that cannot both fit take each other's block in turn
    # for ever, under either engine)
    seqs = _run(eng, [
        (150 + i, 9 + i, dict(max_new_tokens=17 + i, denoising_steps=steps))
        for i, steps in enumerate((2, 3, 4, 2))
    ])
    assert False in refused, "the pool never refused a block"
    assert any(mid for _, mid in victims), "no victim stood inside a block"
    assert any(len(s.prompt_tokens) > s.user_prompt_len for s in seqs)  # folded
    # the same victims in the same order as the engine that waits picks
    return _tokens(seqs), [rid for rid, _ in victims]


def _abort_inside_a_block(eng):
    victim = _add(eng, 160, 10, max_new_tokens=40, denoising_steps=4)
    other = _add(eng, 161, 9, max_new_tokens=18, denoising_steps=3)
    waiting = _add(eng, 162, 9, max_new_tokens=6)
    for _ in range(200):
        eng.step()
        if (victim.num_generated and victim.block_masked
                and 0 < sum(victim.block_masked) < B):
            break
    chained = eng.step_stats["decode_chained_dispatches"] > 0
    # under the rule the abort finds a forward in flight, and commits it
    assert (eng._inflight is not None) == chained
    free = eng.block_manager.num_free
    assert eng.abort(victim.request_id) is victim and eng._inflight is None
    assert eng.block_manager.num_free > free
    eng.run_until_complete()
    assert victim.finish_reason == "abort" and 0 < victim.num_generated < 40
    # what the victim had by then differs by the forward in flight; its
    # batchmate and its successor may not differ at all
    return _tokens([other, waiting])


CASES = {
    "mixed-steps": (dict(), _mixed_steps),
    "one-forward-a-block": (dict(), _one_forward_a_block),
    "stop-token": (dict(), _stop_inside_a_final_block),
    "budgets": (dict(), _budgets_inside_a_block),
    "sampled": (dict(), _sampled),
    "preemption": (dict(total_pages=24, lanes=4), _preemption_inside_a_block),
    "abort": (dict(), _abort_inside_a_block),
}


@pytest.mark.parametrize("case", list(CASES))
def test_greedy_parity_with_the_engine_that_waits(case, params, monkeypatch):
    kw, drive = CASES[case]
    both(lambda: _engine(params, **kw), drive, monkeypatch)


def test_a_pool_too_tight_for_two_blocks_ahead_degrades(params, monkeypatch):
    """The block after the next is reserved where the pool has it and is
    not worth a lane: refused, the forward in flight is committed and the
    step goes on as the engine that waits would, preempting whom it would."""
    seen = []

    def drive(eng):
        refused, victims = _spy_refusals(eng)
        seqs = _run(eng, [
            (170, 9, dict(max_new_tokens=26, denoising_steps=2)),
            (171, 9, dict(max_new_tokens=26, denoising_steps=2)),
        ])
        seen.append(refused)
        return _tokens(seqs), [rid for rid, _ in victims]

    both(lambda: _engine(params, total_pages=15), drive, monkeypatch)
    ahead, waits = seen
    assert True in ahead, "the reservation two blocks ahead never degraded"
    assert True not in waits
    # a refusal with nothing in flight is a preemption, under either engine
    assert ahead.count(False) == waits.count(False)


# -- the rule ------------------------------------------------------------------
def test_nothing_is_registered_or_published_before_its_block_is_final(params):
    """Step by step with a forward in flight: what is registered never
    passes the final tokens, which lag the device by that forward, and a
    block in progress is in no event."""
    events = []
    eng = _engine(params, on_events=events.extend)
    eng.obs_step_timing = True
    seqs = [_add(eng, 180 + i, 10 + i, max_new_tokens=22) for i in range(2)]
    in_flight = 0
    while eng.has_work:
        eng.step()
        in_flight += eng._inflight is not None
        live = [s for s in seqs if s.block_table]
        for seq in live:
            assert seq.num_computed % B == 0
            assert seq.num_registered_pages * PS <= seq.num_computed
        if len(live) == 2:
            stored = sum(len(e.block_hashes) for e in events
                         if isinstance(e, BlockStored))
            assert stored * PS <= sum(s.num_computed for s in seqs)
    assert in_flight > 0 and eng.step_stats["decode_chained_dispatches"] > 0
    assert [s.num_generated for s in seqs] == [22, 22]


def test_chaining_adds_no_program(params):
    """A chained forward runs the program an unchained one compiled: every
    dispatch hands over a ``[lanes, 2 * B + 1]`` array as the forward before
    it, and uploads the same two arrays."""
    def run():
        eng = _engine(params)
        eng.obs_step_timing = True
        _run(eng, [(190 + i, 9 + i, dict(max_new_tokens=16)) for i in range(2)])
        stats = eng.step_stats
        assert stats["decode_uploads"] == 2 * stats["decode_dispatches"]
        return stats["decode_chained_dispatches"]

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    with pytest.MonkeyPatch.context() as mp:
        never_ahead(mp)
        assert run() == 0  # compiles what an engine that waits needs
    size = llama.denoise_steps._cache_size()
    del compiles[:]
    assert run() > 0
    assert compiles == [] and llama.denoise_steps._cache_size() == size


# -- the program -----------------------------------------------------------------
def test_the_program_is_callable_as_the_reference_calls_it(params):
    """``chipbench/references/moe_block_diffusion.py`` calls
    ``denoise_steps`` with seven positional operands and no ``carried``: that
    call returns what a dispatch of the engine returns for the same blocks,
    whether a lane's block rides in ``packed_i32`` or in ``carried``."""
    lanes, table_w = 3, 4
    rng = np.random.default_rng(3)
    pools = llama.init_kv_pages(CFG, 1 + lanes * table_w, PS)
    k_pages, v_pages = (
        jnp.asarray(rng.normal(size=p.shape), p.dtype) for p in pools
    )
    tables = 1 + np.arange(lanes * table_w, dtype=np.int32).reshape(lanes, -1)
    tokens = rng.integers(1, VOCAB, (lanes, B)).astype(np.int32)
    masked = np.asarray([[0, 1, 1, 1], [0, 0, 1, 0], [1, 1, 1, 1]], np.int32)
    tokens[masked != 0] = CFG.mask_token_id
    fparams = np.tile(np.asarray([[0.9, 0.0, 1.0]], np.float32), (lanes, 1))

    def pack(tokens, masked, active):
        return np.concatenate([
            tokens, masked, tables,
            np.asarray([[8, 0, 2, 0], [4, 1, 2, 0], [12, 0, 4, 0]], np.int32),
            np.asarray(active, np.int32)[:, None],
        ], axis=1)

    def run(packed_i32, **carried):
        out, _, _ = llama.denoise_steps(
            params, CFG, packed_i32, fparams, jnp.copy(k_pages),
            jnp.copy(v_pages), jax.random.PRNGKey(0), page_size=PS,
            table_w=table_w, attn_impl="xla", interpret=True, **carried,
        )
        return np.asarray(out)

    want = run(pack(tokens, masked, [1, 1, 1]))
    assert want.shape == (lanes, 2 * B + 1)
    fixed = masked.sum(1) - want[:, B : 2 * B].sum(1)
    assert (fixed >= [1, 1, 1]).all() and (want[:, :B][masked == 0]
                                           == tokens[masked == 0]).all()
    # the engine's dispatch, unchained: an operand that no lane's word names
    junk = np.full((lanes, 2 * B + 1), 7, np.int32)
    np.testing.assert_array_equal(
        run(pack(tokens, masked, [1, 1, 1]), carried=junk), want
    )
    # chained: lanes 0 and 2 take their blocks from the forward before, and
    # what ``packed_i32`` holds in their place is not read
    theirs = np.asarray([1, 0, 1])[:, None]
    carried = np.where(
        theirs, np.concatenate([tokens, masked, junk[:, :1]], axis=1), 7
    ).astype(np.int32)
    chained = pack(
        np.where(theirs, 7, tokens).astype(np.int32),
        np.where(theirs, 1 - masked, masked).astype(np.int32),
        [llama.BLOCK_CARRIED, 1, llama.BLOCK_CARRIED],
    )
    np.testing.assert_array_equal(run(chained, carried=carried), want)
