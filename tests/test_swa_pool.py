"""The block manager of a model with sliding layers, alone: a window pool
beside the context pool, and its three rules by position (GIVING BACK: a
sequence drops the window pages a window behind its query; A HIT NEEDS BOTH:
a prefix hit is cut back to what the window pool still holds; A CONTEXT PAGE
TAKES ITS WINDOW PAGE: eviction of one evicts the other). No program runs
here; the engine on these rules is ``tests/test_swa_engine.py``.
"""

import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_SWA_MOE
from llm_d_kv_cache_manager_tpu.server import BlockManagerConfig
from llm_d_kv_cache_manager_tpu.server.block_manager import (
    AllocationError,
    BlockManager,
)
from llm_d_kv_cache_manager_tpu.server.sequence import Sequence
from served_path import prompt_of

PS = 4
W = TINY_SWA_MOE.sliding_window


# -- the block manager ---------------------------------------------------------
def manager(total=64, window_pages=32, on_events=None, window=W):
    return BlockManager(
        BlockManagerConfig(total_pages=total, page_size=PS,
                           window_pages=window_pages, sliding_window=window),
        on_events=on_events)


def bm_prefill(bm, tokens):
    """Admit ``tokens`` and prefill them in one chunk, as the engine does."""
    seq = Sequence(prompt_tokens=list(tokens))
    cached = bm.allocate(seq)
    bm.reserve_window(seq, cached, len(tokens), chunk=True)
    seq.num_prefilled = seq.num_computed = len(tokens)
    bm.register_full_pages(seq)
    seq.output_tokens.append(1)
    bm.append_slot(seq)
    return seq


def bm_decode(bm, seq, steps, token=2):
    for _ in range(steps):
        bm.reserve_slots(seq, 1)
        seq.num_computed = seq.num_tokens
        seq.output_tokens.append(token)
        bm.register_full_pages(seq)


def first_block(pos):
    return max(pos - W + 1, 0) // PS


@pytest.mark.parametrize("prompt, steps", [(3, 2), (6, 9), (6, 30), (21, 14)])
def test_pages_are_given_back_as_a_sequence_moves_on(prompt, steps):
    bm = manager()
    seq = bm_prefill(bm, prompt_of(60, prompt))
    bm_decode(bm, seq, steps)
    # the last dispatch's query stood at num_tokens - 2 and wrote its slot:
    # what that query saw is held, no more
    query = seq.num_tokens - 2
    assert seq.window_first == first_block(query)
    assert len(seq.window_table) == -(-(query + 1) // PS) - seq.window_first
    assert bm.window.stats["window_pages_dropped"] == seq.window_first
    assert bm.window.num_held == len(seq.window_table)
    # the context pool holds the whole sequence, as it always did
    assert len(seq.block_table) == -(-(query + 1) // PS)
    bm.free_sequence(seq)
    assert not seq.window_table and bm.window.num_held <= W // PS + 1


def test_a_page_given_back_is_reused_before_a_finished_sequences():
    bm = manager(window_pages=12)  # 11 pages
    done = bm_prefill(bm, prompt_of(61, 7))
    bm.free_sequence(done)  # leaves a full page in its last window
    left = list(bm.window._left)
    assert len(left) == 1
    seq = bm_prefill(bm, prompt_of(62, 6))
    bm_decode(bm, seq, 14)  # moves on: gives full pages back
    passed = list(bm.window._passed)
    assert passed and bm.window.stats["window_pages_dropped"] == len(passed)
    while bm.window._free:
        bm.window.pop()
    assert bm.window.pop() == passed[0]  # the oldest given back, first
    assert [bm.window.pop() for _ in passed[1:]] == passed[1:]
    assert bm.window.pop() == left[0]  # then what the finished one left
    assert bm.window.stats["window_pages_evicted"] == len(passed) + 1


def test_a_hit_needs_both_pools():
    """A hit with its run whole; a hit cut back to the last whole run; a hit
    cut to nothing; and the page that served a hit is kept when the sequence
    that took it moves on."""
    bm = manager(window_pages=14)  # 13 pages
    doc = prompt_of(63, 12)
    bm.free_sequence(bm_prefill(bm, doc))
    # its run whole: blocks first_block(12) = 1 .. 2
    longer = doc + prompt_of(64, 8)
    second = bm_prefill(bm, longer + [9])
    assert second.num_cached_prompt == 12
    assert bm.window.stats["window_short_hits"] == 0
    bm_decode(bm, second, 3)
    bm.free_sequence(second)  # ends at 24: blocks 1, 2 given back, SERVED
    kept = list(bm.window._kept)
    assert len(kept) == 2 and not set(kept) & set(bm.window._passed)
    # pressure: everything free, given back or left is reused, the kept stay
    other = bm_prefill(bm, prompt_of(65, 5))
    while len(bm.window._free) + len(bm.window._passed) + len(bm.window._left):
        other.window_table.append(bm.window.pop())
    assert list(bm.window._kept) == kept
    # cut back: the context pool has 20 tokens, the window pool the run that
    # ends at 12
    third = Sequence(prompt_tokens=longer + [9, 9])
    assert bm.allocate(third) == 12
    assert bm.window.stats["window_short_hits"] == 1
    assert bm.window.stats["window_short_hit_tokens"] == 8
    assert third.window_first == 1 and third.window_table == kept
    bm.free_sequence(third)
    # cut to nothing: the kept pages go last
    other.window_table.extend(bm.window.pop() for _ in kept)
    with pytest.raises(AllocationError):
        bm.window.pop()
    fourth = Sequence(prompt_tokens=longer + [9, 9])
    assert bm.allocate(fourth) == 0 and fourth.window_table == []
    assert bm.window.stats["window_short_hit_tokens"] == 8 + 20
    assert len(fourth.block_table) == -(-len(longer + [9, 9]) // PS)


def test_a_hit_passes_over_what_lies_before_its_run():
    """The fill's case: a document grown piece by piece by requests that
    finish. Each hit takes the last window and passes the pages before it
    over, so they are reused before any last window."""
    bm = manager(window_pages=40)
    doc = prompt_of(66, 40)
    for k in range(1, 11):
        seq = bm_prefill(bm, doc[: 4 * k] + [7, 7, 7, 7])
        assert seq.num_cached_prompt == 4 * (k - 1)
        bm.free_sequence(seq)
    assert bm.window.stats["window_short_hits"] == 0
    # what a hit at 40 needs is kept or left, everything before is given back
    needed = {bm.window._cached[h]
              for h in bm.token_db.prefix_hashes(doc)[first_block(40):]}
    assert needed <= set(bm.window._kept) | set(bm.window._left)
    assert len(bm.window._passed) >= first_block(32)


def test_eviction_of_a_context_page_takes_its_window_page():
    events = []
    bm = manager(total=8, window_pages=16, on_events=events.extend)  # 7 pages
    first = bm_prefill(bm, prompt_of(67, 11))
    bm.free_sequence(first)
    hashes = bm.token_db.prefix_hashes(first.prompt_tokens)
    assert [h in bm.window._cached for h in hashes] == [True, True]
    other = bm_prefill(bm, prompt_of(68, 26))  # 7 pages: evicts both
    bm.flush_events()
    assert not any(h in bm._cached or h in bm.window._cached for h in hashes)
    assert bm.window.stats["window_pages_evicted"] == 2
    removed = [e for e in events if type(e).__name__ == "BlockRemoved"]
    assert sorted(h for e in removed for h in e.block_hashes) == sorted(hashes)
    bm.free_sequence(other)


def test_the_events_are_those_of_a_model_without_a_window():
    """An event speaks of the pages that live as long as the prefix: the
    same requests publish the same events with a window pool and without."""
    def run(window_pages):
        events = []
        bm = BlockManager(
            BlockManagerConfig(total_pages=12, page_size=PS,
                               window_pages=window_pages,
                               sliding_window=W if window_pages else 0),
            on_events=events.extend)
        doc = prompt_of(69, 16)
        for i, tail in enumerate((3, 9, 5)):
            seq = bm_prefill(bm, doc + prompt_of(70 + i, tail))
            bm_decode(bm, seq, 6)
            bm.free_sequence(seq)
            bm.flush_events()
        return [(type(e).__name__, tuple(e.block_hashes)) for e in events]

    with_window = run(window_pages=32)
    assert with_window == run(window_pages=0)
    assert {"BlockStored", "BlockRemoved"} <= {name for name, _ in with_window}


def test_a_sequence_that_cannot_get_a_window_page_raises_like_a_context_page():
    bm = manager(window_pages=6)  # 5 pages
    seq = bm_prefill(bm, prompt_of(71, 6))
    hog = bm_prefill(bm, prompt_of(72, 10))
    with pytest.raises(AllocationError):
        bm_decode(bm, seq, 12)
    waiting = Sequence(prompt_tokens=prompt_of(73, 9))
    assert not bm.can_allocate(waiting)  # the window pool says no
    bm.free_sequence(hog)
    bm.free_sequence(seq)
    assert bm.can_allocate(waiting)
