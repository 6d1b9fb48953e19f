"""Latent attention (``LlamaConfig.kv_lora_rank`` > 0), shared experts, a
sigmoid router with a correction bias and a leading dense layer, on the
served path, against the plain reference, at ``TINY_MLA_MOE`` in float32.

The reference side is ``chipbench/references/mla_moe.forward`` (float32, the
EXPANDED form, pairwise rotation, nothing of the program's model code). The
program is absorbed everywhere: its prefill is the kernel (interpreted) or
the kernel's ``jax.numpy`` oracle (``attn_impl="xla"``), its decode always
the kernel over the pool. These tests, not the chip's bf16 check, hold the
absorbed form to the expanded one.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference as chip_reference  # noqa: E402
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import BlockStored  # noqa: E402
from llm_d_kv_cache_manager_tpu.models import (  # noqa: E402
    KANANA_2_30B_A3B,
    TINY_MLA_MOE,
    TINY_QWEN3_MOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.ops._page_copies import (  # noqa: E402
    RUN_PAGES,
    count_run_pages,
)
from llm_d_kv_cache_manager_tpu.ops.mla_attention import (  # noqa: E402
    mla_paged_attention,
    mla_paged_attention_reference,
)
from llm_d_kv_cache_manager_tpu.server import (  # noqa: E402
    BlockManagerConfig,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.engine import Engine  # noqa: E402
from llm_d_kv_cache_manager_tpu.server.serve import _resolve_model  # noqa: E402

CFG = TINY_MLA_MOE
PS = 4
TOL = 1e-4
REF = chip_reference.load("mla_moe")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(11), CFG)


def prompt_of(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(1, 200, n).tolist()


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return np.asarray(REF.forward(params, cfg, list(tokens))[0], np.float32)


def rel_err(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


def served(params, rows, steps, attn_impl, cfg=CFG):
    """``rows``: [(prompt, tokens resident before this call)]: each row's
    first ``resident`` tokens are prefilled cold (a call of their own), the
    rest in ONE batched call against them; then ``steps`` greedy decode
    steps of every row in one batch. Returns the logits a row, [1 + steps,
    vocab], and the tokens fed."""
    b = len(rows)
    need = [-(-(len(p) + steps) // PS) for p, _ in rows]
    tables = np.zeros((b, max(need)), np.int32)
    nxt = 1
    for i, n in enumerate(need):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    k_pages, v_pages = llama.init_kv_pages(cfg, nxt + 1, PS)

    def prefill(chunks):
        nonlocal k_pages, v_pages
        width = max(hi - lo for _, lo, hi in chunks)
        ctx_w = max(lo for _, lo, _ in chunks) // PS
        tok = np.zeros((b, width), np.int32)
        pos = np.zeros((b, width), np.int32)
        ok = np.zeros((b, width), bool)
        ctx_bt = np.zeros((b, ctx_w), np.int32)
        ctx_len = np.zeros((b,), np.int32)
        for i, lo, hi in chunks:
            n = hi - lo
            tok[i, :n] = rows[i][0][lo:hi]
            pos[i, :n] = np.arange(lo, hi)
            ok[i, :n] = True
            ctx_bt[i, : lo // PS] = tables[i, : lo // PS]
            ctx_len[i] = lo
        page = np.take_along_axis(tables, pos // PS, axis=1)
        logits, k_pages, v_pages = llama.prefill(
            params, cfg, tok, pos, ok, k_pages, v_pages, page, pos % PS,
            ctx_bt, ctx_len, attn_impl=attn_impl, interpret=True,
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
        logits, k_pages, v_pages = llama.decode_step(
            params, cfg, toks, lens + step, k_pages, v_pages, tables,
            lens + step + 1, page_size=PS, interpret=True,
        )
        for i in range(b):
            fed[i].append(int(toks[i]))
            out[i].append(np.asarray(logits, np.float32)[i])
    assert v_pages.nbytes == 0
    return [np.stack(o) for o in out], fed


# -- the served programs against the expanded reference ------------------------
@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("rows", [
    pytest.param([(19, 0)], id="cold-alone"),
    pytest.param([(23, 12)], id="paged-context-alone"),
    pytest.param([(30, 16), (9, 0), (21, 8)], id="batch-of-unequal-lengths"),
])
def test_prefill_then_decode_through_the_pool(params, rows, attn_impl):
    rows = [(prompt_of(40 + i, n), r) for i, (n, r) in enumerate(rows)]
    got, fed = served(params, rows, 5, attn_impl)
    for (prompt, _), logits, tokens in zip(rows, got, fed):
        want = reference_logits(params, prompt + tokens)[len(prompt) - 1:]
        assert rel_err(logits, want) < TOL


def expanded_attention(layer, cfg, q_n, q_r, row, pool_l, block_tables,
                       ctx_lens, positions, valid):
    """One layer's attention in the EXPANDED form, plain ``jax.numpy``:
    every table page gathered, ``[k_n | v] = c W_kvb`` made for every
    context and chunk token and every head, one masked softmax. What the
    program never computes, and its absorbed form is held to."""
    b, s = positions.shape
    dc, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    t = block_tables.shape[1] * pool_l.shape[1]
    ctx = pool_l[block_tables].reshape(b, t, row.shape[-1])
    rows = jnp.concatenate([ctx, row], axis=1)  # [b, t + s, width]
    w_kb, w_vb = llama._mla_kvb(layer, cfg, row.dtype)
    k_n = jnp.einsum("bkc,chn->bkhn", rows[..., :dc], w_kb)
    v = jnp.einsum("bkc,chv->bkhv", rows[..., :dc], w_vb)
    scores = (
        jnp.einsum("bqhn,bkhn->bhqk", q_n, k_n)
        + jnp.einsum("bqhr,bkr->bhqk", q_r, rows[..., dc : dc + dr])
    ) * llama._mla_scale(cfg)
    ctx_ok = jnp.arange(t)[None, None, :] < ctx_lens[:, None, None]  # [b, 1, t]
    chunk_ok = (positions[:, None, :] <= positions[:, :, None]) & valid[:, None, :]
    mask = jnp.concatenate(
        [jnp.broadcast_to(ctx_ok, (b, s, t)), chunk_ok], axis=2
    ) & valid[:, :, None]
    probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # padded rows
    live = jnp.concatenate([ctx_ok[:, 0], jnp.ones((b, s), bool)], 1)
    v = jnp.where(live[..., None, None], v, 0)  # a dead table entry
    return jnp.einsum("bhqk,bkhv->bqhv", probs, v)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "oracle"])
def test_absorbed_against_expanded(params, kernel):
    """The two forms on the same inputs, one layer's attention: a paged
    context of unequal lengths, a chunk with a padded tail. The program's
    absorbed form by both of its paths."""
    rng = np.random.default_rng(3)
    layer = params["layers"][1]
    b, s, t_pages = 2, 6, 5
    x = jnp.asarray(rng.normal(size=(b, s, CFG.hidden_size)), jnp.float32)
    ctx_lens = jnp.asarray([17, 8], jnp.int32)
    positions = ctx_lens[:, None] + jnp.arange(s)[None, :]
    valid = jnp.asarray([[True] * 6, [True] * 4 + [False] * 2])
    pool = jnp.asarray(
        rng.normal(size=(2, 16, PS, *CFG.kv_row_shape)), jnp.float32
    ).at[..., CFG.latent_width:].set(0.0)
    tables = jnp.asarray(rng.permutation(16)[: b * t_pages].reshape(b, t_pages))
    inv_freq = jnp.asarray(
        llama.rope_frequencies(CFG.qk_rope_head_dim, CFG.rope_theta)
    )
    q_n, q_r, row = llama._mla_project(layer, CFG, x, positions, inv_freq)
    absorbed = llama._mla_absorbed(
        layer, CFG, q_n, q_r, row, pool, tables, ctx_lens,
        jnp.sum(valid, axis=1), layer_index=1, interpret=True, kernel=kernel,
    )
    expanded = expanded_attention(
        layer, CFG, q_n, q_r, row, pool[1], tables, ctx_lens, positions, valid
    )
    assert absorbed.shape == (b, s, CFG.n_heads, CFG.v_head_dim)
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5, rtol=1e-5)
    assert not np.asarray(absorbed[1, 4:]).any()  # padded rows give zeros


# -- the kernel against its oracle ----------------------------------------------
@pytest.mark.parametrize("s, ctxs, n_valid, key_block", [
    pytest.param(1, [0, 5, 150], [1, 1, 1], 32, id="decode-five-key-steps"),
    pytest.param(1, [9, 130, 37], [1, 0, 1], 32, id="decode-a-dead-lane"),
    pytest.param(20, [0, 45], [20, 7], 32, id="chunk-padded-tail"),
    pytest.param(150, [64, 200], [150, 33], 128, id="chunk-two-key-blocks"),
])
def test_kernel_against_its_oracle(s, ctxs, n_valid, key_block):
    rng = np.random.default_rng(0)
    b, heads, dk, dv, layers, pages, layer = len(ctxs), 4, 40, 32, 3, 96, 1
    q = jnp.asarray(rng.normal(size=(b, s, heads, dk)), jnp.float32)
    fresh = jnp.asarray(rng.normal(size=(b, s, dk)), jnp.float32)
    pool = rng.normal(size=(layers, pages, PS, dk)).astype(np.float32)
    pool[:, 0] = np.nan  # the dead tail of every table points here
    pool[[0, 2]] *= 1e3  # another layer's rows would be seen
    width = -(-max(ctxs) // PS) + 3
    tables = np.zeros((b, width), np.int32)
    order = rng.permutation(np.arange(1, pages))
    at = 0
    for i, ctx in enumerate(ctxs):
        n = -(-ctx // PS)
        tables[i, :n] = order[at:at + n]
        at += n
    args = (jnp.asarray(tables), jnp.asarray(ctxs, jnp.int32),
            jnp.asarray(n_valid, jnp.int32))
    got = mla_paged_attention(
        q, fresh, jnp.asarray(pool), *args, dv=dv, scale=0.3, interpret=True,
        key_block=key_block, layer=layer,
    )
    want = mla_paged_attention_reference(
        q, fresh, jnp.asarray(pool[layer]), *args, dv=dv, scale=0.3
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


# -- a run of pages is one copy (ops/_page_copies.py) ---------------------------
#: pages a lane: none, one, a group, a group and one, a step and some, two
#: steps and some (``key_block`` 32 is a step of 32 pages of 4 rows)
_RUN_LANES = [0, 1, RUN_PAGES, RUN_PAGES + 1, 38, 75]
_RUN_POOL = 256


def _run_tables(kind, width):
    """A table a lane of ``_RUN_LANES`` pages, laid out as ``kind`` says, and
    rows of ``ctx`` tokens (a lane's last page half full where it has one).
    The dead tail of every row holds page 0, which is NaN, unless ``kind``
    says what it holds."""
    rng = np.random.default_rng(7)
    tables = np.zeros((len(_RUN_LANES), width), np.int32)
    at = 1
    for row, n in zip(tables, _RUN_LANES):
        ids = np.arange(at, at + n)
        at += n + 2
        if kind == "shuffled":
            ids = rng.permutation(ids)
        elif kind == "descending":
            ids = ids[::-1]
        elif kind == "broken-in-the-middle-of-a-group":
            ids = ids + (np.arange(n) >= 3) + (np.arange(n) >= RUN_PAGES + 5)
            at += 2
        elif kind in ("ends-at-the-pools-last-page",
                      "the-dead-tail-goes-on-as-a-run"):
            ids = ids - ids[-1:] + _RUN_POOL - 1
        row[:n] = ids
        if kind == "the-dead-tail-goes-on-as-a-run":
            # ... past the pool: a copy that took it for a run would start
            # where no page is (the interpreter then reads other pages)
            row[n:] = (ids[-1] if n else 0) + 1 + np.arange(width - n)
        elif kind == "the-dead-tail-holds-garbage":
            row[n:] = rng.integers(-5, 2 * _RUN_POOL, width - n)
    return tables


@pytest.mark.parametrize("s", [1, 20], ids=["decode", "chunk"])
@pytest.mark.parametrize("kind", [
    "one-run", "shuffled", "descending", "broken-in-the-middle-of-a-group",
    "ends-at-the-pools-last-page", "the-dead-tail-goes-on-as-a-run",
    "the-dead-tail-holds-garbage",
])
def test_kernel_over_tables_of_runs(kind, s):
    rng = np.random.default_rng(1)
    b, heads, dk, dv, layers, layer = len(_RUN_LANES), 4, 40, 32, 2, 1
    width = max(_RUN_LANES) + 5
    tables = _run_tables(kind, width)
    q = jnp.asarray(rng.normal(size=(b, s, heads, dk)), jnp.float32)
    fresh = jnp.asarray(rng.normal(size=(b, s, dk)), jnp.float32)
    pool = rng.normal(size=(layers, _RUN_POOL, PS, dk)).astype(np.float32)
    live = np.zeros(_RUN_POOL, bool)
    for row, n in zip(tables, _RUN_LANES):
        live[row[:n]] = True
    pool[:, ~live] = np.nan  # whatever no lane holds must not be read
    pool[0] *= 1e3  # another layer's rows would be seen
    ctxs = [max(n * PS - 2, 0) for n in _RUN_LANES]
    args = (jnp.asarray(tables), jnp.asarray(ctxs, jnp.int32),
            jnp.full((b,), s, jnp.int32))
    got = mla_paged_attention(
        q, fresh, jnp.asarray(pool), *args, dv=dv, scale=0.3, interpret=True,
        key_block=32, layer=layer,
    )
    # the oracle gathers a table's every entry: give it the live ones alone
    clean = np.where(
        np.arange(width)[None, :] < np.asarray(_RUN_LANES)[:, None], tables, 0
    )
    clean_pool = np.where(live[:, None, None], pool[layer], 0.0)
    want = mla_paged_attention_reference(
        q, fresh, jnp.asarray(clean_pool), jnp.asarray(clean), *args[1:],
        dv=dv, scale=0.3,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    # the case is what its name says
    pages, in_runs = count_run_pages(
        tables, 0, -(-np.asarray(ctxs) // PS), 32, _RUN_POOL
    )
    assert pages == sum(_RUN_LANES)
    if kind in ("shuffled", "descending"):
        assert in_runs == 0
    elif kind == "broken-in-the-middle-of-a-group":
        assert 0 < in_runs < pages
    else:  # every whole group of a step of 32 pages
        assert in_runs == sum(
            min(32, n - at) // RUN_PAGES * RUN_PAGES
            for n in _RUN_LANES for at in range(0, n, 32)
        ) > 0


# -- the router, the shared expert, the dense layer ----------------------------
def _gates(layer, x, **changes):
    return llama._moe_gates(layer, dataclasses.replace(CFG, **changes), x)


def test_the_bias_chooses_and_does_not_weigh(params):
    layer = dict(params["layers"][1])
    x = jnp.asarray(np.random.default_rng(1).normal(size=(7, CFG.hidden_size)),
                    jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ layer["router"]))
    # a bias that lifts experts 6 and 7 over every score chooses them
    layer["router_bias"] = jnp.zeros(8).at[jnp.asarray([6, 7])].set(5.0)
    topv, topi = _gates(layer, x)
    assert (np.sort(np.asarray(topi), axis=1) == [6, 7]).all()
    picked = np.take_along_axis(scores, np.asarray(topi), axis=1)
    want = picked / picked.sum(1, keepdims=True) * CFG.routed_scaling_factor
    np.testing.assert_allclose(topv, want, rtol=1e-6)
    # the scaling factor and the renormalisation are the configuration's
    plain, _ = _gates(layer, x, routed_scaling_factor=1.0)
    np.testing.assert_allclose(np.asarray(plain).sum(1), 1.0, rtol=1e-6)
    raw, _ = _gates(layer, x, routed_scaling_factor=1.0, norm_topk_prob=False)
    np.testing.assert_allclose(raw, picked, rtol=1e-6)
    with pytest.raises(ValueError, match="group-limited"):
        _gates(layer, x, n_group=2)


def test_a_layers_ffn_is_read_from_its_parameters(params):
    dense, routed = params["layers"][0], params["layers"][1]
    assert "router" not in dense and dense["w_gate"].ndim == 2
    assert routed["w_gate"].ndim == 3 and routed["ws_gate"].shape == (64, 48)
    assert routed["router_bias"].dtype == jnp.float32
    # a routed layer run alone, as the first of a one-layer model (the
    # benchmark's layer-alone comparison), is still routed and shared
    cfg1 = dataclasses.replace(CFG, n_layers=1)
    prompt = prompt_of(5, 11)
    for layer in (dense, routed):
        alone = {**params, "layers": [layer]}
        got, _ = served(alone, [(prompt, 4)], 2, "xla", cfg=cfg1)
        fed = [int(np.argmax(row)) for row in got[0][:-1]]
        want = reference_logits(alone, prompt + fed, cfg1)[len(prompt) - 1:]
        assert rel_err(got[0], want) < TOL
    # ... and without its shared expert it is another model
    bare = {k: v for k, v in routed.items() if not k.startswith("ws_")}
    got, _ = served({**params, "layers": [bare]}, [(prompt, 4)], 0, "xla", cfg=cfg1)
    want = reference_logits({**params, "layers": [routed]}, prompt, cfg1)[-1:]
    assert rel_err(got[0], want) > 0.05


def test_the_pool_is_one_row_a_token_and_nothing_else():
    for cfg, values in ((KANANA_2_30B_A3B, 576), (CFG, 40)):
        k_pages, v_pages = jax.eval_shape(
            lambda cfg=cfg: llama.init_kv_pages(cfg, 8, 16)
        )
        assert cfg.latent_width == values
        # held in whole tiles of 128 lanes (the compiler pads 576 to 640 in
        # HBM whatever the array says, and Mosaic cuts no tile out of that)
        row = -(-values // 128) * 128
        assert k_pages.shape == (cfg.n_layers, 8, 16, row)
        assert v_pages.size == 0
        per_token = (k_pages.size + v_pages.size) // (8 * 16)
        assert per_token == cfg.n_layers * row
    # nothing is sized from n_kv_heads x hd (64 x 32 here, as published)
    assert KANANA_2_30B_A3B.kv_row_shape == (640,)
    assert TINY_QWEN3_MOE.kv_row_shape == (2, 24)


# -- the engine: prefix cache, events, refusals --------------------------------
def make_engine(params, cfg=CFG, on_events=None, **engine):
    return Engine(
        EngineConfig(
            model=cfg,
            block_manager=BlockManagerConfig(total_pages=96, page_size=PS),
            scheduler=SchedulerConfig(max_prefill_batch=4),
            max_model_len=128, decode_batch_size=4, prefill_bucket=16,
            interpret=True, **engine,
        ),
        params=params, on_events=on_events,
    )


def run_all(engine, prompts, n=6):
    seqs = [engine.add_request(p, SamplingParams(max_new_tokens=n))
            for p in prompts]
    while engine.has_work:
        engine.step()
    return seqs


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
        llama.init_params(jax.random.PRNGKey(1), TINY_QWEN3_MOE),
        cfg=TINY_QWEN3_MOE, on_events=gqa_events.extend,
    )
    for ask in asks:
        run_all(gqa, [ask], n=1)
    document_hashes = engine.block_manager.token_db.prefix_hashes(document)
    assert stored_hashes(events)[: len(document_hashes)] == document_hashes
    assert stored_hashes(gqa_events)[: len(document_hashes)] == document_hashes


@pytest.mark.parametrize("what, name", [
    (dict(kv_quant_hbm="int8"), "kv_quant_hbm"),
    (dict(block_manager=BlockManagerConfig(
        total_pages=16, page_size=PS, host_pages=8)), "host_pages"),
    (dict(remote_tier=True), "remote_tier"),
    (dict(sp=2), "sp > 1"),
    (dict(tp=2), "tp > 1"),
    (dict(spec_decode="prompt_lookup"), "spec_decode"),
    (dict(model=dataclasses.replace(CFG, block_length=4)), "block_length"),
    (dict(model=dataclasses.replace(CFG, n_group=2, topk_group=2)), "n_group"),
])
def test_engine_refuses_by_name(what, name):
    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, prefill_bucket=16)
    config = dataclasses.replace(config, **what)
    with pytest.raises(ValueError, match="kv_lora_rank.*" + name):
        Engine(config)


def test_a_low_rank_query_path_runs():
    """What the engine refused until PR 41 (``q_lora_rank``): the same
    model with the query through a latent of 16 is served, and is another
    model than the full-rank one (``tests/test_scmoe.py`` holds the path to
    its reference)."""
    cfg = dataclasses.replace(CFG, q_lora_rank=16)
    params = llama.init_params(jax.random.PRNGKey(11), cfg)
    layer = params["layers"][1]
    assert "wq" not in layer and layer["wq_a"].shape == (64, 16)
    assert layer["wq_b"].shape == (16, 4 * 24) and layer["q_a_norm"].shape == (16,)
    ask = prompt_of(31, 21)
    seq = run_all(make_engine(params, cfg=cfg), [ask])[0]
    alone, _ = served(params, [(ask, 8)], 5, "xla", cfg=cfg)
    assert seq.generated_tokens == alone[0].argmax(-1).tolist()


def test_page_export_and_import_are_refused_by_name(params):
    """``TRANSFER_ENDPOINT`` serves nothing for a latent pool: the pod
    refuses it at construction, before anything is built, and the engine's
    two entry points refuse any other caller."""
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    config = EngineConfig(
        model=CFG, block_manager=BlockManagerConfig(total_pages=16, page_size=PS),
        interpret=True, prefill_bucket=16)
    pod = PodServerConfig(
        engine=config, transfer_endpoint="tcp://127.0.0.1:0", publish_events=False)
    with pytest.raises(ValueError, match="kv_lora_rank.*transfer_endpoint"):
        PodServer(pod)
    engine = Engine(config, params=params)
    with pytest.raises(ValueError, match="kv_lora_rank.*export_kv_blocks"):
        engine.export_kv_blocks([1, 2])
    with pytest.raises(ValueError, match="kv_lora_rank.*import_kv_blocks"):
        engine.import_kv_blocks([])
    with pytest.raises(ValueError, match="kv_lora_rank.*transfer_endpoint"):
        PodServer(pod, engine=engine)  # an injected engine's model counts too


def test_presets():
    assert _resolve_model("tiny-mla-moe") is TINY_MLA_MOE
    cfg = _resolve_model("kakaocorp/kanana-2-30b-a3b-instruct-2601")
    assert cfg is KANANA_2_30B_A3B
    # what ``chipbench/run.py``'s built-in list reads off the preset
    assert (cfg.hd, cfg.n_kv_heads, cfg.n_experts, cfg.moe_inter) == (64, 32, 128, 768)
    shapes = jax.eval_shape(
        lambda: llama.init_params(
            jax.random.PRNGKey(0), dataclasses.replace(cfg, n_layers=2))
    )
    dense, routed = shapes["layers"]
    assert dense["w_gate"].shape == (2048, 6144)
    assert routed["wq"].shape == (2048, 32 * 192)
    assert routed["wkv_a"].shape == (2048, 576)
    assert routed["wkv_b"].shape == (512, 32 * 256)
    assert routed["wo"].shape == (32 * 128, 2048)
    assert routed["ws_gate"].shape == (2048, 1536)
    assert routed["w_gate"].shape == (128, 2048, 768)


# -- the loader: a deepseek_v3 config and state dict ---------------------------
class _KananaConfig:  # the published config.json's keys
    model_type = "deepseek_v3"
    vocab_size, hidden_size, intermediate_size = 128256, 2048, 6144
    num_hidden_layers, num_attention_heads, num_key_value_heads = 48, 32, 32
    head_dim, rope_theta, rope_scaling, rms_norm_eps = 64, 1000000, None, 1e-6
    attention_bias, tie_word_embeddings, hidden_act = False, False, "silu"
    n_routed_experts, num_experts_per_tok, moe_intermediate_size = 128, 6, 768
    n_shared_experts, norm_topk_prob, first_k_dense_replace = 2, True, 1
    kv_lora_rank, q_lora_rank, qk_nope_head_dim, qk_rope_head_dim = 512, None, 128, 64
    v_head_dim, rope_interleave, routed_scaling_factor = 128, True, 2.448
    scoring_func, topk_method, n_group, topk_group = "sigmoid", "noaux_tc", 1, 1
    moe_layer_freq = 1


def test_the_loader_reads_the_published_config():
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    assert config_from_hf(_KananaConfig()) == KANANA_2_30B_A3B


def test_the_loader_reads_a_low_rank_query_path():
    """What the loader refused until PR 41: ``q_lora_rank`` is read, and a
    state dict with ``q_a_proj`` / ``q_a_layernorm`` / ``q_b_proj`` loads to
    the tree the program runs."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import (
        config_from_hf,
        load_hf_state_dict,
    )

    hf = _KananaConfig()
    hf.q_lora_rank = 1536
    assert config_from_hf(hf) == dataclasses.replace(
        KANANA_2_30B_A3B, q_lora_rank=1536)
    cfg = dataclasses.replace(
        CFG, q_lora_rank=16, n_layers=1, first_k_dense=1)
    params = llama.init_params(jax.random.PRNGKey(2), cfg)
    (layer,) = params["layers"]
    names = {
        "attn_norm": "input_layernorm.weight",
        "mlp_norm": "post_attention_layernorm.weight",
        "wq_a": "self_attn.q_a_proj.weight",
        "q_a_norm": "self_attn.q_a_layernorm.weight",
        "wq_b": "self_attn.q_b_proj.weight",
        "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
        "kv_norm": "self_attn.kv_a_layernorm.weight",
        "wkv_b": "self_attn.kv_b_proj.weight", "wo": "self_attn.o_proj.weight",
        "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
        "w_down": "mlp.down_proj.weight",
    }
    assert set(names) == set(layer)
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["final_norm"],
          "lm_head.weight": params["lm_head"].T}
    for ours, theirs in names.items():
        w = np.asarray(layer[ours])
        sd["model.layers.0." + theirs] = w.T if w.ndim == 2 else w
    loaded = load_hf_state_dict(sd, cfg)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("change, name", [
    (dict(n_group=8, topk_group=4), "group-limited"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(rope_scaling={"type": "yarn", "factor": 40}), "yarn"),
])
def test_the_loader_refuses_by_name(change, name):
    from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

    hf = _KananaConfig()
    for key, value in change.items():
        setattr(hf, key, value)
    with pytest.raises(NotImplementedError, match=name):
        config_from_hf(hf)


def test_a_saved_state_dict_loads_to_the_references_logits(params):
    """The tiny model written out under the checkpoint's names ([out, in]
    matrices) and read back: the loaded tree is the tree, and the served
    program on it gives the reference's logits."""
    from llm_d_kv_cache_manager_tpu.models.hf_loader import load_hf_state_dict

    names = {
        "attn_norm": "input_layernorm.weight", "mlp_norm": "post_attention_layernorm.weight",
        "wq": "self_attn.q_proj.weight", "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
        "kv_norm": "self_attn.kv_a_layernorm.weight", "wkv_b": "self_attn.kv_b_proj.weight",
        "wo": "self_attn.o_proj.weight", "router": "mlp.gate.weight",
        "router_bias": "mlp.gate.e_score_correction_bias",
    }
    ffn = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["final_norm"],
          "lm_head.weight": params["lm_head"].T}
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        for ours, theirs in names.items():
            if ours in layer:
                w = np.asarray(layer[ours])
                sd[p + theirs] = w.T if w.ndim == 2 else w
        for ours, theirs in ffn.items():
            w = np.asarray(layer[f"w_{ours}"])
            if w.ndim == 2:
                sd[f"{p}mlp.{theirs}.weight"] = w.T
                continue
            for j in range(CFG.n_experts):
                sd[f"{p}mlp.experts.{j}.{theirs}.weight"] = w[j].T
            sd[f"{p}mlp.shared_experts.{theirs}.weight"] = np.asarray(layer[f"ws_{ours}"]).T
    loaded = load_hf_state_dict(sd, CFG)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    prompt = prompt_of(77, 14)
    got, fed = served(loaded, [(prompt, 8)], 2, "xla")
    want = reference_logits(params, prompt + fed[0])[len(prompt) - 1:]
    assert rel_err(got[0], want) < TOL
