"""A model with linear-attention layers on the served path: the engine, the
scheduler and the block manager's state pool of slots (``StatePool``): a
live slot a sequence, snapshots every ``state_snapshot_tokens`` keyed by the
block that ends there, a context-pool hit cut back to the last boundary whose
snapshot is held. Greedy generations are held to the plain reference's
choices (``chipbench/references/kda_mla_moe``), so a wrong slot, snapshot or
restore shows as a wrong token.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_LING_HYBRID
from llm_d_kv_cache_manager_tpu.ops import kda
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.block_manager import (
    AllocationError,
    StatePool,
)
from served_path import (
    kept_layer_programs,
    make_engine,
    prompt_of,
    rel_err,
    run_all,
)

#: one period of the preset's two (linear and dense, linear and routed, latent
#: and routed): every kind of layer once and half the programs to compile. The
#: block manager, the scheduler and the engine's slots do not see the depth;
#: ``test_kda.py`` runs both periods through the pools.
CFG = dataclasses.replace(TINY_LING_HYBRID, n_layers=3)
PS, STRIDE = 4, 8
REF = chip_reference.load("kda_mla_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 47)


def engine_of(params, *, snapshots=16, pages=128, **kw):
    return make_engine(
        CFG, params,
        BlockManagerConfig(total_pages=pages, page_size=PS,
                           state_snapshot_tokens=STRIDE,
                           state_snapshot_slots=snapshots),
        **kw)


#: the pool that runs out (23 pages of 4 tokens to give), on two lanes: the
#: cases that need one share its programs
SMALL = dict(pages=24, lanes=2)


def picks(params, ask, generated):
    return served_path.picks(REF, params, CFG, ask, generated)


def stats(engine):
    return engine.state_pool_stats()


def pool_is_whole(engine):
    """Every slot is free, a snapshot or held by a live sequence; no pin is
    left when nothing runs."""
    st = engine.block_manager.state
    assert len(st._free) + st.num_snapshots == st.n_slots - 1
    assert not any(st._pins.values())
    assert set(st._idle) | set(st._kept) == set(st._hash)


# -- generation against the reference -----------------------------------------
@pytest.mark.parametrize("burst", [1, 4], ids=["step", "burst4"])
def test_generation_is_the_references(params, burst):
    engine = engine_of(
        params, decode_steps_per_iter=burst,
        scheduler=SchedulerConfig(max_prefill_batch=4))
    prompts = [prompt_of(i, n) for i, n in enumerate((27, 9, 16, 21))]
    seqs = run_all(engine, prompts, 11)
    for prompt, seq in zip(prompts, seqs):
        assert seq.output_tokens == picks(params, prompt, seq.output_tokens)
    # a snapshot at every boundary each sequence passed, prefill and decode
    want = sum((len(p) + 11 - 1) // STRIDE for p in prompts)
    assert stats(engine)["state_snapshots_taken"] == want
    assert stats(engine)["state_snapshots_held"] == want
    pool_is_whole(engine)


def test_lanes_all_taken_run_a_dispatch_ahead(params):
    """Every lane taken: a burst stays in flight over the step's end and the
    next is planned from positions the host has not seen committed; the slots
    a lane leaves behind are registered when their tokens are."""
    engine = engine_of(params, decode_steps_per_iter=4)
    engine.obs_step_timing = True
    prompts = [prompt_of(20 + i, n) for i, n in enumerate((13, 6, 10, 7))]
    seqs = run_all(engine, prompts, 14)
    assert engine.step_stats["decode_chained_dispatches"] > 0
    for prompt, seq in zip(prompts, seqs):
        assert seq.output_tokens == picks(params, prompt, seq.output_tokens)
    pool_is_whole(engine)


# -- hits ------------------------------------------------------------------------
def test_a_hit_is_cut_back_to_a_snapshot(params):
    engine = engine_of(params)
    first = prompt_of(3, 27)
    run_all(engine, [first], 2)
    # 22 shared tokens: 5 pages hit in the latent pool, the last boundary
    # under them is 16
    ask = first[:22] + prompt_of(4, 7)
    (seq,) = run_all(engine, [ask], 6)
    assert seq.num_cached_prompt == 16
    assert seq.output_tokens == picks(params, ask, seq.output_tokens)
    got = stats(engine)
    assert got["state_restores"] == 1 and got["state_cutback_lost"] == 0
    assert got["state_cutback_tokens"] == 20 - 16
    assert got["state_admissions"] == 2
    pool_is_whole(engine)


def test_a_hit_on_a_boundary_is_not_cut(params):
    engine = engine_of(params)
    first = prompt_of(5, 30)
    run_all(engine, [first], 2)
    ask = first[:24] + prompt_of(6, 5)
    (seq,) = run_all(engine, [ask], 4)
    assert seq.num_cached_prompt == 24
    assert stats(engine)["state_cutback_tokens"] == 0
    assert seq.output_tokens == picks(params, ask, seq.output_tokens)


def test_a_hit_whose_snapshot_is_gone_is_cut_back_further(params):
    engine = engine_of(params)
    first = prompt_of(7, 27)
    run_all(engine, [first], 2)
    st = engine.block_manager.state
    hashes = engine.block_manager.token_db.prefix_hashes(first)
    st.evict_hash(hashes[24 // PS - 1])  # the snapshot at 24 goes, its page stays
    st.evict_hash(hashes[16 // PS - 1])
    (seq,) = run_all(engine, [first[:26] + [9, 9, 9]], 5)
    assert seq.num_cached_prompt == 8
    assert seq.output_tokens == picks(
        params, first[:26] + [9, 9, 9], seq.output_tokens)
    got = stats(engine)
    assert got["state_cutback_lost"] == 1
    assert got["state_cutback_tokens"] == 24 - 8
    # the sequence passed 16 and 24 again and left their snapshots behind
    assert st.lookup(hashes[16 // PS - 1]) is not None
    assert st.lookup(hashes[24 // PS - 1]) is not None
    pool_is_whole(engine)


def test_no_snapshot_at_all_starts_from_zero_state(params):
    engine = engine_of(params)
    first = prompt_of(8, 20)
    run_all(engine, [first], 1)
    st = engine.block_manager.state
    for h in list(st._snap):
        st.evict_hash(h)
    (seq,) = run_all(engine, [first + [1, 2]], 4)
    assert seq.num_cached_prompt == 0
    assert seq.output_tokens == picks(params, first + [1, 2], seq.output_tokens)
    assert stats(engine)["state_cutback_lost"] == 1


@pytest.mark.parametrize("burst", [1, 4], ids=["step", "burst4"])
def test_a_snapshot_taken_in_decode_is_hit_later(params, burst):
    engine = engine_of(params, decode_steps_per_iter=burst)
    first = prompt_of(9, 13)
    (seq,) = run_all(engine, [first], 14)
    # the thread so far: its boundaries 16 and 24 lie in the generated part
    thread = first + seq.output_tokens[:13]
    (turn,) = run_all(engine, [thread + [7, 7, 7]], 5)
    assert turn.num_cached_prompt == 24
    assert turn.output_tokens == picks(
        params, thread + [7, 7, 7], turn.output_tokens)
    assert stats(engine)["state_restores"] == 1


def reference_state(params, tokens):
    """Every linear layer's state after ``tokens`` by the plain reference
    (float32, nothing of the program's model code but the recurrence token by
    token, ``kda_recurrent``): (the heads' matrices ``[H, K, K]``, the carried
    rows ``[taps - 1, 3 H K]``: the newest inputs of the plain convolution)
    a layer."""
    f32, s = jnp.float32, len(tokens)
    H, K = CFG.n_heads, CFG.kda_head_dim
    with kept_layer_programs(chip_reference):
        layer_forward = chip_reference._layer_fn(CFG, REF._ffn, REF._mixer)
    states = []
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        for layer in params["layers"]:
            if "kda_qkv" in layer:
                x = chip_reference._rms(
                    h, layer["attn_norm"].astype(f32), CFG.rms_norm_eps)
                z = x @ layer["kda_qkv"].astype(f32)
                taps = layer["kda_conv_w"].astype(f32)
                n = taps.shape[0]
                zp = jnp.concatenate([jnp.zeros((n - 1, z.shape[1]), f32), z])
                conv = jax.nn.silu(sum(taps[j] * zp[j: j + s] for j in range(n)))
                q, k, v = (t.reshape(1, s, H, K) for t in jnp.split(conv, 3, -1))
                beta = jax.nn.sigmoid(x @ layer["kda_wb"].astype(f32))
                _, S = kda.kda_recurrent(
                    REF._l2(q) / np.sqrt(K), REF._l2(k), v,
                    REF.gate(layer, CFG, x)[None], beta[None],
                    jnp.zeros((1, H, K, K), f32))
                states.append((np.asarray(S[0]), np.asarray(zp[s:])))
            h, _ = layer_forward(layer, h)
    return states


def test_a_burst_that_passes_a_boundary_leaves_the_state_at_it(params):
    """A burst of four decodes positions 13 to 16 and so passes the boundary
    at 16: the token AT it reads slot a and writes slot b, the matrices by
    ``kda_decode`` and every layer's carried rows by the one scatter after
    the layer loop. Slot a is the snapshot: it holds the token-by-token
    reference's state after 16 tokens, a later turn restores from it and
    generates what an engine that never saw the thread generates, and the
    restore leaves it as it was."""
    engine = engine_of(params, decode_steps_per_iter=4)
    first = prompt_of(21, 13)
    (seq,) = run_all(engine, [first], 14)
    thread = first + seq.output_tokens
    bm, st = engine.block_manager, engine.block_manager.state
    slot = st.lookup(bm.token_db.prefix_hashes(thread)[16 // PS - 1])
    assert slot is not None

    def snapshot():
        return [np.asarray(pool[:, slot]) for pool in engine.state_pages]

    matrices, rows = before = snapshot()
    want = reference_state(params, thread[:16])
    assert len(want) == CFG.n_kda_layers == 2
    for layer, (want_S, want_rows) in enumerate(want):
        assert rel_err(matrices[layer], want_S) < 1e-4
        assert rel_err(rows[layer].reshape(want_rows.shape), want_rows) < 1e-4
    # a turn on the thread so far: four pages hit, the state restored at 16
    ask = thread[:19] + [7, 7, 7]
    (turn,) = run_all(engine, [ask], 9)
    assert turn.num_cached_prompt == 16
    assert stats(engine)["state_restores"] == 1
    (alone,) = run_all(engine_of(params, decode_steps_per_iter=4), [ask], 9)
    assert alone.num_cached_prompt == 0
    assert turn.output_tokens == alone.output_tokens
    assert turn.output_tokens == picks(params, ask, turn.output_tokens)
    assert all(np.array_equal(a, b) for a, b in zip(before, snapshot()))
    pool_is_whole(engine)


def test_a_page_takes_its_snapshot_with_it(params):
    """A context page that is evicted takes its block's snapshot along; a
    snapshot that is reused leaves its page where it is."""
    engine = engine_of(params, **SMALL)
    first = prompt_of(10, 33)
    run_all(engine, [first], 1)
    bm, st = engine.block_manager, engine.block_manager.state
    held = st.num_snapshots
    assert held == 4
    # fill the pool with another thread: the first one's pages are recycled
    run_all(engine, [prompt_of(11, 72)], 1)
    hashes = bm.token_db.prefix_hashes(first)
    gone = [h for h in hashes if h not in bm._cached]
    assert gone and all(st.lookup(h) is None for h in gone)
    assert stats(engine)["state_snapshots_evicted"] >= 1
    pool_is_whole(engine)


def test_events_keep_speaking_of_pages(params):
    """A cut-back admission prefills tokens of blocks that are cached again,
    into pages that stay unhashed: no block is stored twice, none removed."""
    events = []
    engine = engine_of(params, on_events=events.extend)
    first = prompt_of(12, 27)
    run_all(engine, [first], 1)
    stored = [h for ev in events for h in getattr(ev, "block_hashes", [])]
    run_all(engine, [first[:22] + [5, 5, 5, 5, 5, 5]], 1)
    again = [h for ev in events for h in getattr(ev, "block_hashes", [])]
    assert len(set(again)) == len(again)
    assert again[: len(stored)] == stored
    assert all(type(ev).__name__ == "BlockStored" for ev in events)


# -- slots given back --------------------------------------------------------------
def test_an_abort_gives_the_slots_back(params):
    engine = engine_of(params)
    seq = engine.add_request(
        prompt_of(13, 19), SamplingParams(max_new_tokens=40),
        request_id="gone")
    for _ in range(6):
        engine.step()
    st = engine.block_manager.state
    assert seq.state_slot and len(st._free) + st.num_snapshots < st.n_slots - 1
    engine.abort("gone")
    while engine.has_work:
        engine.step()
    assert not seq.state_slot and not seq.state_due
    pool_is_whole(engine)


def test_a_preemption_gives_the_slots_back_and_the_rerun_is_the_same(params):
    """Too few pages for two long generations: one is preempted, gives its
    slots back, and is prefilled again from what the cache still holds."""
    engine = engine_of(params, **SMALL)
    prompts = [prompt_of(14, 17), prompt_of(15, 19)]
    seqs = run_all(engine, prompts, 30)
    # (a preempted sequence's tokens so far were folded into its prompt)
    assert any(len(seq.prompt_tokens) > len(prompt)
               for prompt, seq in zip(prompts, seqs))
    for prompt, seq in zip(prompts, seqs):
        generated = seq.all_tokens[len(prompt):]
        assert generated == picks(params, prompt, generated)
    pool_is_whole(engine)


def test_a_rolled_back_admission_gives_its_slot_back(params):
    """The scheduler admits a batch under a token budget and rolls back the
    sequence that does not fit: its slot and its claim on a snapshot go."""
    engine = engine_of(
        params, scheduler=SchedulerConfig(
            max_prefill_batch=4, max_prefill_tokens=24))
    prompts = [prompt_of(16 + i, 20) for i in range(3)]
    seqs = run_all(engine, prompts, 3)
    for prompt, seq in zip(prompts, seqs):
        assert seq.output_tokens == picks(params, prompt, seq.output_tokens)
    pool_is_whole(engine)


# -- the scheduler cuts chunks at boundaries ---------------------------------------
def test_a_prefill_is_cut_where_a_snapshot_is_due(params):
    engine = engine_of(params)
    seq = engine.add_request(prompt_of(17, 29), SamplingParams(max_new_tokens=2))
    seen = []
    while engine.has_work:
        engine.step()
        seen.append(seq.num_prefilled)
    assert seen[:4] == [8, 16, 24, 29]
    assert engine.prefill_stats["dispatches"] == 4
    # a batch's rows are cut each where its own boundary lies
    engine = engine_of(params)
    first = prompt_of(18, 20)
    run_all(engine, [first], 1)
    a = engine.add_request(first[:14] + [3] * 9, SamplingParams(max_new_tokens=1))
    b = engine.add_request(prompt_of(19, 11), SamplingParams(max_new_tokens=1))
    engine.step()
    assert (a.num_cached_prompt, a.num_prefilled) == (8, 16)
    assert (b.num_cached_prompt, b.num_prefilled) == (0, 8)


# -- the pool alone ------------------------------------------------------------------
def test_snapshots_that_served_a_hit_are_reused_last():
    st = StatePool(6, 8, 4)  # slots 1..5
    taken = [st.pop() for _ in range(5)]
    for slot, h in zip(taken, "abcde"):
        assert st.register(slot, h)
    assert not st.register(taken[0], "a")  # a block has one snapshot
    st.pin(st.lookup("a"), hit=True)  # the oldest serves a hit
    st.unpin(st.lookup("a"))
    st.pin(st.lookup("c"))  # its own sequence goes on from it: no hit
    st.unpin(st.lookup("c"))
    order = []
    for _ in range(5):
        slot = st.pop()
        order.append(slot)
    # never hit first, oldest first ("c" was touched last); the one hit last
    assert order == [taken[1], taken[3], taken[4], taken[2], taken[0]]
    assert st.stats["state_snapshots_evicted"] == 5
    with pytest.raises(AllocationError, match="state slot pool exhausted"):
        st.pop()


def test_a_pinned_snapshot_is_not_reused_and_goes_when_read():
    st = StatePool(3, 8, 4)
    a, b = st.pop(), st.pop()
    st.register(a, "x")
    st.pin(a, hit=True)
    st.free(b)
    assert st.pop() == b  # the pinned one is passed over
    with pytest.raises(AllocationError):
        st.pop()
    st.evict_hash("x")  # its page goes while a reader is still to come
    assert st.lookup("x") is None and st.num_available == 0
    st.unpin(a)
    assert st.pop() == a


def test_a_pool_needs_a_stride_of_whole_pages():
    with pytest.raises(ValueError, match="stride of whole pages"):
        StatePool(8, 6, 4)
    with pytest.raises(ValueError, match="slot 0 is reserved"):
        StatePool(1, 8, 4)
