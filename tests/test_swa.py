"""Window and full attention layers in one model (``LlamaConfig.
sliding_window``; Trinity-Large-Preview, ``afmoe``) on the served path: a
window pool beside the context pool whose pages a sequence gives back as it
moves on, a gate on the attention's output, no positions on the full layers,
four norms a layer, the embedding's factor and one rank's share of the
experts, against the plain reference, at ``TINY_SWA_MOE`` in float32 (window
8, pages of 4: sliding, sliding, sliding, full, sliding).

The reference side is ``chipbench/references/swa_moe.forward`` (float32, the
whole sequence at once, nothing of the program's model code, no cache). These
tests hold every way a key reaches a query through the two pools (a chunk's
own keys, a window table that starts mid-context, a page reused after it was
given back, a prefix hit that needs both pools, a hit cut back, a page
boundary inside a burst or under a dispatch ahead, a re-prefill after
preemption) to it, and the block manager's three rules (giving back, a hit
needs both, a context page takes its window page) by position.
"""

import dataclasses
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference as chip_reference  # noqa: E402
from llm_d_kv_cache_manager_tpu.models import (  # noqa: E402
    TINY_MOE,
    TINY_QWEN3_MOE,
    TINY_SWA_MOE,
    TRINITY_LARGE_PREVIEW,
    llama,
)
from llm_d_kv_cache_manager_tpu.ops.attention import (  # noqa: E402
    prefill_with_paged_context,
)
from llm_d_kv_cache_manager_tpu.ops.flash_prefill import (  # noqa: E402
    flash_prefill_paged,
)
from llm_d_kv_cache_manager_tpu.ops._page_copies import (  # noqa: E402
    RUN_PAGES,
    count_run_pages,
)
from llm_d_kv_cache_manager_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention,
    paged_attention_reference,
    paged_window_attention,
    window_step_pages,
)
from llm_d_kv_cache_manager_tpu.server import (  # noqa: E402
    BlockManagerConfig,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.block_manager import (  # noqa: E402
    AllocationError,
    BlockManager,
)
from llm_d_kv_cache_manager_tpu.server.engine import Engine  # noqa: E402
from llm_d_kv_cache_manager_tpu.server.sequence import Sequence  # noqa: E402
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model  # noqa: E402

CFG = TINY_SWA_MOE
#: every routed expert held: the uncut layer
UNCUT = dataclasses.replace(CFG, expert_first=0, expert_count=None)
PS = 4
W = CFG.sliding_window
TOL = chip_reference.TOL_F32
REF = chip_reference.load("swa_moe")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(43), CFG)


def prompt_of(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(1, 200, n).tolist()


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return np.asarray(REF.forward(params, cfg, list(tokens))[0], np.float32)


def rel_err(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


def engine_like(params, cfg=CFG, attn_impl="xla"):
    """What ``reference.common_check`` and a reference's ``system`` read of
    an engine."""
    return types.SimpleNamespace(
        params=params, model_cfg=cfg, page_size=PS, mesh=None,
        _replicated=jax.devices()[0], prefill_attn=attn_impl)


def served(params, rows, steps, attn_impl, cfg=CFG):
    """``rows``: [(prompt, tokens resident before the batched call)]: each
    row's first ``resident`` tokens are prefilled cold (a call of their
    own), the rest in ONE batched, right-padded call against them through
    both pools; then ``steps`` greedy decode steps of every row in one batch.
    A row's window pages come from its own free list and are given back as
    the engine's block manager gives them back. Returns the logits a row,
    [1 + steps, vocab], and the tokens fed."""
    b, w = len(rows), cfg.sliding_window
    need = [-(-(len(p) + steps) // PS) for p, _ in rows]
    tables = np.zeros((b, max(need)), np.int32)
    nxt = 1
    for i, n in enumerate(need):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    k_pages, v_pages = llama.init_kv_pages(cfg, nxt + 1, PS)
    w_width = max(need) + 2
    window_pages = llama.init_window_pages(cfg, b * w_width + 1, PS)
    wts = []
    for i in range(b):  # a row's window pages: its own range of the pool
        wt = REF.WindowTable(w_width + 1, w, PS)
        wt.free = [i * w_width + p for p in wt.free]
        wts.append(wt)

    def prefill(chunks):
        nonlocal k_pages, v_pages, window_pages
        width = max(hi - lo for _, lo, hi in chunks)
        ctx_w = max(-(-lo // PS) for _, lo, _ in chunks)
        tok = np.zeros((b, width), np.int32)
        pos = np.zeros((b, width), np.int32)
        ok = np.zeros((b, width), bool)
        ctx_bt = np.zeros((b, ctx_w), np.int32)
        ctx_len = np.zeros((b,), np.int32)
        w_ids = np.zeros((b, width), np.int32)
        w_tab = np.zeros((b, ctx_w), np.int32)
        w_start = np.zeros((b,), np.int32)
        for i, lo, hi in chunks:
            n = hi - lo
            tok[i, :n] = rows[i][0][lo:hi]
            pos[i, :n] = np.arange(lo, hi)
            ok[i, :n] = True
            ctx_bt[i, : -(-lo // PS)] = tables[i, : -(-lo // PS)]
            ctx_len[i] = lo
            wts[i].move_to(lo, hi)
            w_ids[i, :n] = wts[i].page_of(pos[i, :n])
            w_tab[i] = wts[i].row(ctx_w)[0]
            w_start[i] = wts[i].first * PS
        page = np.take_along_axis(
            tables, np.minimum(pos // PS, tables.shape[1] - 1), axis=1)
        logits, k_pages, v_pages, window_pages = llama.prefill(
            params, cfg, tok, pos, ok, k_pages, v_pages, page, pos % PS,
            ctx_bt, ctx_len, attn_impl=attn_impl, interpret=True,
            window_pages=window_pages, window_rows=(w_ids, w_tab, w_start),
        )
        return np.asarray(logits, np.float32)

    for i, (_, resident) in enumerate(rows):
        if resident:
            prefill([(i, 0, resident)])
    last = prefill([(i, r, len(p)) for i, (p, r) in enumerate(rows)])
    out = [[last[i]] for i in range(b)]
    fed = [[] for _ in range(b)]
    lens = np.array([len(p) for p, _ in rows], np.int32)
    for step in range(steps):
        toks = np.array([int(np.argmax(o[-1])) for o in out], np.int32)
        for i in range(b):
            wts[i].move_to(lens[i] + step, lens[i] + step + 1)
        logits, k_pages, v_pages, window_pages = llama.decode_step(
            params, cfg, toks, lens + step, k_pages, v_pages, tables,
            lens + step + 1, page_size=PS, interpret=True,
            window_pages=window_pages,
            window_tables=np.concatenate([wt.row(w_width) for wt in wts]),
            window_start=np.array([wt.first * PS for wt in wts], np.int32),
        )
        for i in range(b):
            fed[i].append(int(toks[i]))
            out[i].append(np.asarray(logits, np.float32)[i])
    return [np.stack(o) for o in out], fed


# -- (1) the served programs through both pools against the reference ----------
@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("rows", [
    pytest.param([(W // 2, 0)], id="half-a-window-whole"),
    pytest.param([(W, 0)], id="one-window-whole"),
    pytest.param([(5 * W // 2, 0)], id="two-and-a-half-windows-whole"),
    pytest.param([(4 * W, 0)], id="four-windows-whole"),
    pytest.param([(5 * W // 2, W)], id="two-and-a-half-windows-chunked"),
    pytest.param([(4 * W, 3 * W - PS)], id="four-windows-chunked"),
    pytest.param([(4 * W, 2 * W), (W // 2, 0), (5 * W // 2, W), (W + 1, 0)],
                 id="batch-of-unequal-lengths"),
])
def test_prefill_then_decode_through_both_pools(params, rows, attn_impl):
    rows = [(prompt_of(40 + i, n), r) for i, (n, r) in enumerate(rows)]
    # 6 steps: every row crosses a page and gives a window page back
    got, fed = served(params, rows, 6, attn_impl)
    for (prompt, _), logits, tokens in zip(rows, got, fed):
        want = reference_logits(params, prompt + tokens)[len(prompt) - 1:]
        assert rel_err(logits, want) < TOL


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_the_references_system_side_is_the_harness_check(params, attn_impl):
    """``reference.common_check`` through ``swa_moe.system``: a sequence grown
    in warm chunks past the window with pages reused, every position from the
    prompt's end on compared, then decode steps over a page's end and a
    window page's; then every layer alone, each with the pools of its kind."""
    line = chip_reference.common_check(
        engine_like(params, attn_impl=attn_impl), REF, seed=3, interpret=True,
        prompt_tokens=16, steps=4)
    assert line["ok"] and line["rel_err"] < TOL
    assert line["layer_rel_err_p75"] < TOL
    got, fed = REF.system(
        engine_like(params), prompt_of(7, 16), 4, interpret=True)
    assert 16 + len(fed) - 4 >= W + 2 * PS and got.shape[0] == len(fed) + 1


# -- (2) the window's edge ------------------------------------------------------
def test_the_windows_edge(params):
    """Windows of W - 1, W and W + 1 positions each agree with the reference
    of that window, and differ from each other by far more than the
    tolerance: a window off by one is seen in float32."""
    prompt = prompt_of(50, 3 * W)
    seen = {}
    for w in (W - 1, W, W + 1):
        cfg = dataclasses.replace(CFG, sliding_window=w)
        (got,), (fed,) = served(params, [(prompt, W)], 5, "xla", cfg=cfg)
        want = reference_logits(params, prompt + fed, cfg)[len(prompt) - 1:]
        assert rel_err(got, want) < TOL
        seen[w] = reference_logits(params, prompt, cfg)[-1]
    assert rel_err(seen[W - 1], seen[W]) > 100 * TOL
    assert rel_err(seen[W + 1], seen[W]) > 100 * TOL


# -- (3) the kernels against their oracles, a table that starts mid-context ----
def _pool(rng, pages, n_kv=2, hd=16):
    return jnp.asarray(rng.normal(size=(pages, PS, n_kv, hd)), jnp.float32)


def test_decode_kernel_with_a_window_table_that_starts_mid_context():
    rng = np.random.default_rng(5)
    k_pool, v_pool = _pool(rng, 12), _pool(rng, 12)
    q = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    # contexts of 21, 9 and 3 tokens: tables start at positions 12, 0 and 0
    tables = jnp.asarray([[3, 7, 1, 9], [5, 2, 8, 0], [4, 0, 0, 0]], jnp.int32)
    starts = jnp.asarray([12, 0, 0], jnp.int32)
    lens = jnp.asarray([21, 9, 3], jnp.int32)
    for window in (W, W - 1, 3):
        got = paged_attention(
            q, k_pool, v_pool, tables, lens, interpret=True, window=window,
            table_start=starts)
        want = paged_attention_reference(
            q, k_pool, v_pool, tables, lens, window=window, table_start=starts)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # ... and the window is what is seen: the same call over the whole table
    whole = paged_attention_reference(q, k_pool, v_pool, tables, lens - starts)
    assert not np.allclose(got[1], whole[1], atol=1e-3)


def test_prefill_kernel_with_a_window_table_that_starts_mid_context():
    rng = np.random.default_rng(6)
    k_pool, v_pool = _pool(rng, 12), _pool(rng, 12)
    s = 11
    q = jnp.asarray(rng.normal(size=(2, s, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, s, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, s, 2, 16)), jnp.float32)
    tables = jnp.asarray([[3, 7, 1], [5, 0, 0]], jnp.int32)
    starts = jnp.asarray([8, 0], jnp.int32)
    ctx = jnp.asarray([18, 2], jnp.int32)
    n_valid = jnp.asarray([s, 7], jnp.int32)
    positions = ctx[:, None] + jnp.arange(s)[None, :]
    valid = jnp.arange(s)[None, :] < n_valid[:, None]
    for window in (W, 5):
        got = flash_prefill_paged(
            q, k, v, k_pool, v_pool, tables, ctx, n_valid, interpret=True,
            window=window, table_start=starts)
        want = prefill_with_paged_context(
            q, k, v, k_pool, v_pool, tables, ctx, positions=positions,
            valid=valid, window=window, table_start=starts)
        for row in range(2):
            n = int(n_valid[row])
            np.testing.assert_allclose(
                got[row, :n], want[row, :n], atol=2e-5, rtol=2e-5)


# -- (3b) a run of window pages is one copy (ops/_page_copies.py) ---------------
#: (window, lengths from the table's first slot): pages of 4, so a window of
#: 64 is one group of 16 pages (and a page more where it starts inside one)
#: and a step of 32 pages holds it; the first visible page is slot ``(length
#: - window) // 4`` of the table
_RUN_WINDOWS = {
    "first-page-on-a-group-boundary": (64, [64, 96, 128]),
    "first-page-inside-a-group": (64, [76, 101, 139]),
    "short-histories": (64, [0, 1, 4, 32, 33, 36]),
    "two-steps": (160, [170, 301, 164]),
}
_RUN_WINDOW_POOL = 256


def _window_run_tables(kind, lens, width, window):
    rng = np.random.default_rng(9)
    tables = np.zeros((len(lens), width), np.int32)
    at = 1
    if kind == "the-dead-tail-goes-on-past-the-pool":
        # the last lane's pages end with the pool's last page
        at = _RUN_WINDOW_POOL - sum(-(-n // PS) + 1 for n in lens) + 1
    for row, n in zip(tables, lens):
        pages = -(-n // PS)
        ids = np.arange(at, at + pages)
        at += pages + 1
        if kind == "shuffled":
            ids = rng.permutation(ids)
        elif kind == "descending":
            ids = ids[::-1]
        elif kind == "broken-in-the-middle-of-a-group":
            # ... of the first group from the lane's first visible page
            ids = ids + (np.arange(pages) >= max(n - window, 0) // PS + 3)
            at += 1
        row[:pages] = ids
        if kind == "the-dead-tail-goes-on-past-the-pool":
            row[pages:] = (ids[-1] if pages else 0) + 1 + np.arange(width - pages)
    return tables


@pytest.mark.parametrize("walk", list(_RUN_WINDOWS))
@pytest.mark.parametrize("kind, fresh", [
    # the served call takes the current token as an operand (fresh); a
    # table of runs and one of none also with every token resident
    ("one-run", True), ("one-run", False), ("shuffled", True),
    ("shuffled", False), ("descending", True),
    ("broken-in-the-middle-of-a-group", True),
    ("the-dead-tail-goes-on-past-the-pool", True),
])
def test_window_kernel_over_tables_of_runs(kind, walk, fresh):
    window, lens = _RUN_WINDOWS[walk]
    rng = np.random.default_rng(3)
    b, n_kv, hd, layer = len(lens), 2, 16, 1
    width = -(-max(lens) // PS) + 3
    tables = _window_run_tables(kind, lens, width, window)
    pools = rng.normal(size=(2, 2, _RUN_WINDOW_POOL, PS, n_kv, hd)).astype(np.float32)
    live = np.zeros(_RUN_WINDOW_POOL, bool)
    for row, n in zip(tables, lens):
        live[row[: -(-n // PS)]] = True
    pools[:, :, ~live] = np.nan  # whatever no lane holds must not be read
    pools[:, 0] *= 1e3  # another layer's pages would be seen
    q = jnp.asarray(rng.normal(size=(b, 4, hd)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(2, b, n_kv, hd)), jnp.float32)
    clean = np.where(live[:, None, None, None], pools[:, layer], 0.0)
    if fresh:  # the oracle reads the current token from its slot
        for i, n in enumerate(lens):
            if n:
                clean[:, tables[i, (n - 1) // PS], (n - 1) % PS] = new[:, i]
    got = paged_window_attention(
        q, jnp.asarray(pools[0]), jnp.asarray(pools[1]), jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32), *(new if fresh else ()), window=window,
        scale=0.25, interpret=True, layer=jnp.int32(layer),
    )
    held = np.where(
        np.arange(width)[None, :] < -(-np.asarray(lens)[:, None] // PS), tables, 0
    )
    want = paged_attention_reference(
        q, jnp.asarray(clean[0]), jnp.asarray(clean[1]), jnp.asarray(held),
        jnp.asarray(lens, jnp.int32), window=window, scale=0.25,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # the case is what its name says: groups count from the first visible page
    hist = np.asarray(lens) - fresh
    first = np.maximum(np.asarray(lens) - window, 0) // PS
    pages, in_runs = count_run_pages(
        tables, first, np.maximum(-(-hist // PS) - first, 0),
        window_step_pages(width, PS), _RUN_WINDOW_POOL,
    )
    assert pages == sum(
        max(-(-h // PS) - f, 0) for h, f in zip(hist.tolist(), first.tolist())
    )
    # every whole group of a step of 32 pages (``KEY_BLOCK`` 256 / 4 would
    # be 64: the table is narrower)
    step = window_step_pages(width, PS)
    whole = sum(
        min(step, n - at) // RUN_PAGES * RUN_PAGES
        for n in np.maximum(-(-hist // PS) - first, 0).tolist()
        for at in range(0, n, step)
    )
    if kind in ("shuffled", "descending"):
        assert in_runs == 0
    elif kind == "broken-in-the-middle-of-a-group":
        assert in_runs < whole or not whole
    else:
        assert in_runs == whole

# -- (4) the block manager ------------------------------------------------------
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


# -- (5) through the engine ------------------------------------------------------
def make_engine(params, cfg=CFG, on_events=None, total_pages=96,
                window_pages=48, lanes=4, **engine):
    engine.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    return Engine(
        EngineConfig(
            model=cfg,
            block_manager=BlockManagerConfig(
                total_pages=total_pages, page_size=PS,
                window_pages=window_pages),
            max_model_len=160, decode_batch_size=lanes, prefill_bucket=16,
            interpret=True, **engine,
        ),
        params=params, on_events=on_events,
    )


def run_all(engine, prompts, n=10):
    seqs = [engine.add_request(p, SamplingParams(max_new_tokens=n))
            for p in prompts]
    while engine.has_work:
        engine.step()
    return seqs


def picks(params, ask, generated):
    """The reference's greedy choice at each generated position, given the
    tokens the engine generated before it."""
    logits = reference_logits(params, ask + generated)
    return logits[len(ask) - 1: -1].argmax(-1).tolist()


@pytest.mark.parametrize("prefill_attn", ["xla", "pallas"])
def test_the_served_path_on_a_prompt_of_several_windows(params, prefill_attn):
    """``PodServer.submit`` -> ``Engine.step``: a document of four windows,
    then a second request that hits it in both pools, then one whose hit
    ends mid-document: the reference's pick at every step."""
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    doc = prompt_of(80, 4 * W)
    asks = [doc + prompt_of(81, 5), doc + prompt_of(82, 9),
            doc[: 3 * W] + prompt_of(83, 6)]
    pod = PodServer(
        PodServerConfig(publish_events=False),
        engine=make_engine(params, prefill_attn=prefill_attn))
    pod.engine.obs_step_timing = True
    pod.start()
    try:
        seqs = [pod.submit(ask, SamplingParams(max_new_tokens=11)).result(
            timeout=300) for ask in asks]
    finally:
        pod.shutdown()
    assert [s.num_cached_prompt for s in seqs] == [0, 4 * W, 3 * W]
    for seq, ask in zip(seqs, asks):
        assert seq.generated_tokens == picks(params, ask, seq.generated_tokens)
    engine = pod.engine
    stats = engine.block_manager.window.stats
    assert stats["window_short_hits"] == 0 and stats["window_pages_dropped"] > 0
    # one full layer in the context pool, four sliding ones in the window pool
    assert engine.k_pages.shape[0] == 1 and engine.window_pages[0].shape[0] == 4
    row = 2 * CFG.n_kv_heads * CFG.hd * 4
    assert engine.kv_bytes_per_token == row
    assert engine.window_bytes_per_token == 4 * row
    assert engine.kv_block_bytes == PS * row
    # a sliding layer read at most a window of each context
    steps = engine.step_stats
    assert 0 < steps["window_ctx_tokens"] < steps["attn_ctx_tokens"]
    assert steps["window_ctx_tokens"] == W * steps["decode_rows"]


@pytest.mark.parametrize("k, lanes, ahead", [
    (1, 2, True), (3, 2, True), (5, 4, False),
])
def test_a_page_given_back_inside_a_burst_or_under_a_dispatch_ahead(
        params, k, lanes, ahead):
    asks = [prompt_of(90 + i, 9 + 4 * i) for i in range(2)]
    engine = make_engine(params, lanes=lanes, decode_steps_per_iter=k)
    engine.obs_step_timing = True
    seqs = run_all(engine, asks, n=26)
    assert bool(engine.step_stats["decode_chained_dispatches"]) == ahead
    for seq, ask in zip(seqs, asks):
        assert seq.generated_tokens == picks(params, ask, seq.generated_tokens)
    assert engine.block_manager.window.num_held <= 2 * (W // PS + 1)


def test_chunked_prefill(params):
    ask = prompt_of(100, 70)
    engine = make_engine(
        params, scheduler=SchedulerConfig(
            max_prefill_batch=4, chunked_prefill_tokens=16))
    short = engine.add_request(prompt_of(101, 6), SamplingParams(max_new_tokens=30))
    engine.step()
    long = engine.add_request(ask, SamplingParams(max_new_tokens=8))
    while engine.has_work:
        engine.step()
    assert engine.prefill_stats["dispatches"] >= 1 + 70 // 16
    assert long.generated_tokens == picks(params, ask, long.generated_tokens)
    assert short.generated_tokens == picks(
        params, prompt_of(101, 6), short.generated_tokens)
    # the long prompt's chunks gave back what lay a window behind each
    assert engine.block_manager.window.stats["window_pages_dropped"] >= 70 // PS - 4


@pytest.mark.parametrize("sizes, lanes", [
    pytest.param(dict(total_pages=13), 2, id="context-pool"),
    pytest.param(dict(window_pages=11), 4, id="window-pool"),
])
def test_preemption_and_resume(params, sizes, lanes):
    """A pool too small for the lanes' growth, the context pool or the
    window pool (whose lanes each hold a window and a boundary): a lane is
    preempted, folded and prefilled again (a hit where the window pool still
    has its last window) and goes on as an unbroken run."""
    asks = [prompt_of(110 + i, 14 + i) for i in range(lanes)]
    engine = make_engine(params, lanes=lanes, **sizes)
    preempted = []
    on_preempted = engine.scheduler.on_preempted
    engine.scheduler.on_preempted = lambda seq: (
        preempted.append(seq), on_preempted(seq))[1]
    seqs = run_all(engine, asks, n=18)
    assert preempted
    for seq, ask in zip(seqs, asks):
        generated = seq.all_tokens[len(ask):]
        assert len(generated) == 18 and generated == picks(params, ask, generated)


def test_stats_and_gauges(params):
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    pod = PodServer(
        PodServerConfig(publish_events=False, obs_metrics=True),
        engine=make_engine(params))
    run_all(pod.engine, [prompt_of(130, 30)], n=12)
    # a hit on it: the pages of its run are kept once the lane has moved on
    run_all(pod.engine, [prompt_of(130, 30) + prompt_of(131, 5)], n=12)

    async def get_stats():
        client = TestClient(TestServer(pod.build_app()))
        await client.start_server()
        try:
            return await (await client.get("/stats")).json()
        finally:
            await client.close()

    stats = asyncio.run(get_stats())
    window = pod.engine.block_manager.window
    assert stats["window_bytes_per_token"] == pod.engine.window_bytes_per_token > 0
    assert stats["window_pages"] == 48
    assert stats["window_pages_held"] == window.num_held > 0
    assert stats["window_pages_dropped"] == window.stats["window_pages_dropped"] > 0
    assert stats["window_short_hits"] == 0 and stats["window_pages_evicted"] == 0
    pod.metrics.set_engine_gauges(0.0, 1, 2, 3, 5, window)
    pod.engine.step_stats["window_ctx_tokens"] = 41
    pod.engine.step_stats["ctx_pages"] = 50
    pod.engine.step_stats["ctx_run_pages"] = 32
    pod.metrics.sync_step_stats(pod.engine.step_stats, None)
    text = pod.metrics.exposition().decode()
    assert 'kvcache_engine_ctx_pages_total{kind="all"} 50.0' in text
    assert 'kvcache_engine_ctx_pages_total{kind="run"} 32.0' in text
    assert "kvcache_window_bytes_per_token 5.0" in text
    assert f"kvcache_window_pages_held {float(window.num_held)}" in text
    assert 'kvcache_window_pages_total{event="pages_dropped"}' in text
    assert "kvcache_engine_window_ctx_tokens_total 41.0" in text
    # a model without sliding layers: the keys are there and read nothing
    plain = PodServer(
        PodServerConfig(publish_events=False),
        engine=make_engine(
            llama.init_params(jax.random.PRNGKey(1), TINY_QWEN3_MOE),
            cfg=TINY_QWEN3_MOE))
    assert plain.engine.window_pages is None
    assert plain.engine.block_manager.window is None
    assert plain.engine.block_manager.config.window_pages == 0


def test_the_window_pool_follows_total_pages_unless_stated(params, monkeypatch):
    from llm_d_kv_cache_manager_tpu.server.serve import PodServerConfig

    monkeypatch.setenv("TOTAL_PAGES", "40")
    monkeypatch.delenv("WINDOW_PAGES", raising=False)
    unset = PodServerConfig.from_env().engine
    assert unset.block_manager.window_pages == 0
    engine = Engine(dataclasses.replace(
        unset, model=CFG, interpret=True, prefill_bucket=16,
        block_manager=dataclasses.replace(unset.block_manager, page_size=PS)),
        params=params)
    assert engine.block_manager.config.window_pages == 40
    assert engine.window_pages[0].shape[1] == 40
    monkeypatch.setenv("WINDOW_PAGES", "24")
    assert PodServerConfig.from_env().engine.block_manager.window_pages == 24


# -- (6) the share ---------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """The parts that the ranges of the experts give, the shared expert
    counted once, add up to the layer with every expert held."""
    uncut = llama.init_params(jax.random.PRNGKey(7), UNCUT)["layers"][2]
    x = jnp.asarray(
        np.random.default_rng(3).normal(size=(2, 9, CFG.hidden_size)),
        jnp.float32)
    flat = x.reshape(-1, CFG.hidden_size)
    with jax.default_matmul_precision("highest"):
        whole, _ = REF._ffn(uncut, UNCUT, flat)
        shared = REF.common._swiglu(
            flat, uncut["ws_gate"], uncut["ws_up"], uncut["ws_down"])
    total = np.zeros_like(np.asarray(shared))
    for first in range(0, CFG.n_experts, 2):
        cfg = dataclasses.replace(CFG, expert_first=first, expert_count=2)
        mine = {k: v[first:first + 2] if k in ("w_gate", "w_up", "w_down")
                else v for k, v in uncut.items()}
        part = np.asarray(llama._mlp(mine, cfg, x, interpret=True)).reshape(
            total.shape)
        with jax.default_matmul_precision("highest"):
            want, _ = REF._ffn(mine, cfg, flat)
        np.testing.assert_allclose(part, want, atol=2e-5, rtol=2e-4)
        total += part - np.asarray(shared)  # this range's routed part
    np.testing.assert_allclose(total + shared, whole, atol=5e-5, rtol=2e-4)
    np.testing.assert_allclose(
        np.asarray(llama._mlp(uncut, UNCUT, x, interpret=True)).reshape(
            total.shape), whole, atol=5e-5, rtol=2e-4)


# -- (7) what the window pool does not serve is refused by name ----------------
@pytest.mark.parametrize("what, name", [
    (dict(block_manager=BlockManagerConfig(
        total_pages=32, page_size=PS, host_pages=8)), "host_pages"),
    (dict(remote_tier=True), "remote_tier"),
    (dict(kv_quant_hbm="int8"), "kv_quant_hbm"),
    (dict(tp=2), "tp > 1"),
    (dict(sp=2), "sp > 1"),
    (dict(spec_decode="prompt_lookup"), "spec_decode"),
    (dict(model=dataclasses.replace(CFG, block_length=4)), "block_length"),
    (dict(model=dataclasses.replace(CFG, sliding_window=6)), "sliding_window=6"),
    (dict(block_manager=BlockManagerConfig(
        total_pages=32, page_size=PS, window_pages=8)), "window_pages=8"),
])
def test_engine_refuses_by_name(what, name):
    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=32, page_size=PS),
        interpret=True, prefill_bucket=16)
    config = dataclasses.replace(config, **what)
    with pytest.raises(ValueError, match=name):
        Engine(config)


@pytest.mark.parametrize("entry", [
    "transfer_endpoint", "transfer_endpoint-injected", "export_kv_blocks",
    "import_kv_blocks", "freeze_for_migration",
])
def test_page_moves_are_refused_by_name(params, entry):
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=32, page_size=PS),
        interpret=True, prefill_bucket=16)
    pod = PodServerConfig(
        engine=config, transfer_endpoint="tcp://127.0.0.1:0", publish_events=False)
    calls = {
        "transfer_endpoint": lambda: PodServer(pod),
        "transfer_endpoint-injected":
            lambda: PodServer(pod, engine=Engine(config, params=params)),
        "export_kv_blocks":
            lambda: Engine(config, params=params).export_kv_blocks([1, 2]),
        "import_kv_blocks":
            lambda: Engine(config, params=params).import_kv_blocks([]),
        "freeze_for_migration":
            lambda: Engine(config, params=params).freeze_for_migration("r"),
    }
    with pytest.raises(ValueError, match="sliding layers.*" + entry.split("-")[0]):
        calls[entry]()


def test_the_model_programs_refuse_what_carries_no_window(params):
    ids = jnp.zeros((1, 4), jnp.int32)
    k_pages, v_pages = llama.init_kv_pages(CFG, 4, PS)
    with pytest.raises(ValueError, match="sliding layers: the window pools"):
        llama.prefill(
            params, CFG, ids, ids, ids > -1, k_pages, v_pages, ids + 1, ids,
            jnp.zeros((1, 0), jnp.int32), jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="sliding layers: the window pools"):
        llama.decode_step(
            params, CFG, ids[0, :1], ids[0, :1], k_pages, v_pages, ids + 1,
            ids[0, :1] + 1, page_size=PS, interpret=True)


# -- (8) a model without sliding layers has the programs it had ----------------
@pytest.mark.parametrize("cfg", [TINY_MOE, TINY_QWEN3_MOE], ids=["moe", "qwen3"])
def test_no_window_operand_reaches_a_model_without_sliding_layers(cfg):
    """Lowered, ``decode_steps`` and a prefill program of a model without
    sliding layers take their parameters and the operands they always took,
    and nothing in them is named for a window. (Their text was compared with
    the parent commit's, byte for byte, when the window came: PERF.md
    section 6, PR 43.)"""
    params = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    k, v = jax.eval_shape(lambda: llama.init_kv_pages(cfg, 32, PS))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    leaves = len(jax.tree.leaves(params))
    programs = {
        "decode_steps": (llama.decode_steps.lower(
            params, cfg, ints(4, llama.burst_counts(cfg) + 2),
            ints(4, 8 + llama.DECODE_PACKED_TAIL), k, v,
            jax.ShapeDtypeStruct((2,), jnp.uint32), page_size=PS, num_steps=2,
            interpret=True), 5),
        "prefill_packed": (llama.prefill_packed.lower(
            params, cfg, ints(4, 5 * 16 + 4 + 1), k, v, chunk=16,
            attn_impl="pallas", interpret=True), 3),
    }
    for name, (lowered, operands) in programs.items():
        text = lowered.as_text(debug_info=True)
        assert "paged_attention_window" not in text, name
        assert "attn_window" not in text, name
        assert len(jax.tree.leaves(lowered.args_info)) == leaves + operands, name


def test_the_sliding_layers_calls_are_named(params):
    """The trace tells the two decode kernels apart, and the sliding layers'
    attention has a scope of its own."""
    k, v = jax.eval_shape(lambda: llama.init_kv_pages(CFG, 32, PS))
    wp = jax.eval_shape(lambda: llama.init_window_pages(CFG, 16, PS))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    text = llama.decode_steps.lower(
        jax.eval_shape(lambda: params), CFG, ints(4, llama.burst_counts(CFG) + 2),
        ints(4, 8 + llama.DECODE_PACKED_TAIL), k, v,
        jax.ShapeDtypeStruct((2,), jnp.uint32), page_size=PS, num_steps=2,
        interpret=True, window_pages=wp, window_packed=ints(4, 5),
    ).as_text(debug_info=True)
    assert "paged_attention_window" in text
    assert "model.attn_window" in text and "model.attn/" in text
    assert "attn_window" in llama.MODEL_SCOPES


# -- presets and the loader ------------------------------------------------------
def test_presets():
    big = TRINITY_LARGE_PREVIEW
    assert _resolve_model("arcee-ai/Trinity-Large-Preview") is big
    assert _resolve_model("tiny-swa-moe") is CFG
    kinds = big.layer_types
    assert len(kinds) == 60 and kinds.count("sliding_attention") == 45
    assert all(k == "full_attention" for k in kinds[3::4])
    assert big.n_window_layers == 45 and big.n_attn_layers == 15
    cut = dataclasses.replace(
        big, n_layers=5, first_k_dense=1, vocab_size=25024, expert_first=0,
        expert_count=32)
    assert cut.layer_types_published == list(kinds)  # the published list, whole
    assert (cut.n_window_layers, cut.n_attn_layers, cut.experts_held) == (4, 1, 32)
    assert hash(cut) != hash(big)
    assert CFG.layer_types[:5] == (
        "sliding_attention",) * 3 + ("full_attention", "sliding_attention")
    assert not TINY_QWEN3_MOE.n_window_layers and not TINY_QWEN3_MOE.sliding_window
    # the cut's tree: the leaves a layer's kind and place give it
    tree = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), CFG))["layers"]
    assert ["window" in layer for layer in tree] == [True] * 3 + [False, True]
    assert ["router" in layer for layer in tree] == [False] + [True] * 4
    assert all({"wg", "attn_post_norm", "mlp_post_norm"} <= set(layer)
               for layer in tree)
    assert tree[1]["w_gate"].shape == (4, 64, 48)  # the held experts
    assert tree[1]["router"].shape == (64, 8)  # every expert scored


class _TrinityConfig:  # the published config.json's keys (the catalog's row)
    model_type = "afmoe"
    global_attn_every_n_layers, head_dim, hidden_act = 4, 128, "silu"
    hidden_size, intermediate_size = 3072, 12288
    layer_types = (["sliding_attention"] * 3 + ["full_attention"]) * 15
    load_balance_coeff, max_position_embeddings = 5e-05, 262144
    moe_intermediate_size, mup_enabled, n_group = 3072, True, 1
    num_attention_heads, num_dense_layers, num_expert_groups = 48, 6, 1
    num_experts, num_experts_per_tok, num_hidden_layers = 256, 4, 60
    num_key_value_heads, num_limited_groups, num_shared_experts = 8, 1, 1
    rms_norm_eps, rope_scaling, rope_theta = 1e-05, None, 10000
    route_norm, route_scale, score_func = True, 2.448, "sigmoid"
    sliding_window, tie_word_embeddings, topk_group = 4096, False, 1
    use_grouped_mm, vocab_size = True, 200192


def test_the_loader_reads_the_published_config():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    assert config_from_hf(_TrinityConfig()) == TRINITY_LARGE_PREVIEW


@pytest.mark.parametrize("change, name", [
    (dict(layer_types=["conv", "sliding_attention"] * 30), "layer_types"),
    (dict(score_func="softmax"), "score_func"),
    (dict(n_group=2), "n_group"),
    (dict(mup_enabled=False), "mup_enabled"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "yarn"),
])
def test_the_loader_refuses_by_name(change, name):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = _TrinityConfig()
    for key, value in change.items():
        setattr(hf, key, value)
    with pytest.raises(NotImplementedError, match=name):
        config_from_hf(hf)


def test_a_saved_state_dict_loads_to_the_references_logits():
    """The tiny model with every expert written out under the checkpoint's
    names ([out, in] matrices) and read back by a rank that holds a range
    of them: the loaded tree is that rank's tree, and the served program on
    it gives the reference's logits."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    whole = llama.init_params(jax.random.PRNGKey(9), UNCUT)
    names = {
        "attn_norm": "input_layernorm.weight",
        "attn_post_norm": "post_attention_layernorm.weight",
        "mlp_norm": "pre_mlp_layernorm.weight",
        "mlp_post_norm": "post_mlp_layernorm.weight",
        "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
        "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
        "wg": "self_attn.gate_proj.weight",
        "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
        "router": "mlp.router.gate.weight", "router_bias": "mlp.expert_bias",
    }
    ffn = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}
    sd = {"model.embed_tokens.weight": whole["embed"],
          "model.norm.weight": whole["final_norm"],
          "lm_head.weight": np.asarray(whole["lm_head"]).T}
    for i, layer in enumerate(whole["layers"]):
        p = f"model.layers.{i}."
        for ours, theirs in names.items():
            if ours in layer:
                w = np.asarray(layer[ours])
                sd[p + theirs] = w.T if w.ndim == 2 else w
        for ours, theirs in ffn.items():
            w = np.asarray(layer["w_" + ours])
            if "router" in layer:
                for j in range(CFG.n_experts):
                    sd[f"{p}mlp.experts.{j}.{theirs}.weight"] = w[j].T
                sd[f"{p}mlp.shared_experts.{theirs}.weight"] = np.asarray(
                    layer["ws_" + ours]).T
            else:
                sd[f"{p}mlp.{theirs}.weight"] = w.T
    loaded = load_hf_state_dict(sd, CFG)
    first, count = CFG.expert_first, CFG.expert_count
    for got, layer in zip(loaded["layers"], whole["layers"]):
        assert set(got) == set(layer)
        for key, want in layer.items():
            if key in ("w_gate", "w_up", "w_down") and "router" in layer:
                want = want[first:first + count]
            np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want))
    prompt = prompt_of(140, 3 * W)
    (got,), (fed,) = served(loaded, [(prompt, W)], 3, "xla")
    want = reference_logits(loaded, prompt + fed)[len(prompt) - 1:]
    assert rel_err(got, want) < TOL
