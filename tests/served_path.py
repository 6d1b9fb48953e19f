"""What the architectures' test files share (``test_mla*``, ``test_scmoe*``,
``test_conv_state*``, ``test_swa*``, ``test_block_diffusion*``): prompts, the
error that is compared, the reference's logits and picks, an engine at the
tests' sizes, and the served programs driven as the engine drives them.
Not collected (no ``test_`` in its name); the files import from here, not
from each other.

What is an architecture's own (its preset, its reference, its tolerance, the
cases that are its mechanism's) stays in its files; so does the second pool it
lays beside the keys and values where one file drives it (the state pool of
``tests/test_conv_state.py``); the window pool and the state pool of slots
are here because two files drive each.

**One set of compiled programs a process.** A test here costs what it is the
first in its worker to compile (a tiny model's ``decode_steps`` is 5 s, a
prefill 2-4 s, a reference's layer 0.4 s a length), so everything that decides
a program is asked for in as few forms as the cases allow, and whatever was
built is kept for the process:

- the served programs are ``llama``'s module-level jitted functions, so JAX
  keeps one program a (configuration, shapes) whoever calls, for the life of
  the process: ``served`` pads rows, chunk, context, tables and pool to the
  buckets below, and an engine file asks ``make_engine`` for as few (pool,
  lanes, burst) shapes as its cases allow (a roomy pool on four lanes, a
  tight one on two): a case gets a preemption, an eviction or a full set of
  lanes from the traffic it sends, and says why where it keeps a size of its
  own. Where depth is not the point the file runs the fewest layers that keep
  every kind of layer: a program's cost is its layers';
- the parameters of a (configuration, seed) are built once (``params_of``);
- a reference's jitted layer is kept a (configuration, operators), where
  ``chipbench/references`` build it anew in every ``forward``, and a sequence
  is padded to whole ``REFERENCE_BUCKET`` tokens (``reference_logits``).
"""

import contextlib
import dataclasses
import functools

import jax
import numpy as np

from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import (
    TINY_LFM2_MOE,
    TINY_MLA_MOE,
    TINY_SWA_MOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.server import (
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.engine import Engine

#: ``served`` pads a call's chunk to whole ``CHUNK_BUCKET`` tokens and its
#: context table to whole ``CTX_BUCKET`` pages (none stays none: a cold call
#: is a program of its own, as it is in the engine), the block tables to
#: whole ``TABLE_BUCKET`` pages and the pools to whole ``POOL_BUCKET`` pages,
#: as the engine does with ``prefill_bucket`` and ``prefill_ctx_bucket``: the
#: cases of one contract then share a handful of compiled programs. What lies
#: past a row's end is masked by ``valid`` and by the lengths, so the logits
#: compared are what they were.
CHUNK_BUCKET = 16
CTX_BUCKET = 4
TABLE_BUCKET = 16
POOL_BUCKET = 32
#: ... and the rows of a call to whole ``ROW_BUCKET`` rows, as the engine pads a
#: dispatch to its lanes: a row alone and a batch of three are ONE prefill and
#: ONE decode program a (chunk, context) shape. A row that pads is a sequence
#: of one token, cold, in pages of its own: every second pool treats it as it
#: treats any row, and nothing of it is returned.
ROW_BUCKET = 4


#: The deeper tiny presets at ONE OF EACH KIND OF LAYER, for the files whose
#: cases are not about depth (an engine's scheduling, pools and counters, how a
#: dispatch packs its inputs, a window's edge): the block manager, the
#: scheduler and the engine's pools do not see the depth, and a program costs
#: what its layers cost to compile. The files against the reference
#: (``tests/test_swa.py`` and its like) run the whole presets.
#: a sliding layer over the dense FFN and a full one over the routed (of five)
ONE_OF_EACH_SWA = dataclasses.replace(
    TINY_SWA_MOE, n_layers=2,
    layer_types=("sliding_attention", "full_attention"))
#: a convolution over the dense FFN, an attention and a convolution over the
#: routed (of eight)
ONE_OF_EACH_LFM2 = dataclasses.replace(
    TINY_LFM2_MOE, n_layers=3, first_k_dense=1,
    layer_types=("conv", "full_attention", "conv"))
#: the leading dense layer and one routed layer (of four)
ONE_OF_EACH_MLA = dataclasses.replace(TINY_MLA_MOE, n_layers=2)


def round_up(n: int, bucket: int) -> int:
    return -(-n // bucket) * bucket


def prompt_of(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(1, 200, n).tolist()


def rel_err(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


@functools.lru_cache(maxsize=None)
def params_of(cfg, seed: int):
    """``llama.init_params`` of a (configuration, seed), once a process:
    files of one architecture that land on one worker share the tree."""
    return llama.init_params(jax.random.PRNGKey(seed), cfg)


#: ``reference_logits`` pads a sequence to whole ``REFERENCE_BUCKET`` tokens:
#: the references are causal (a token sees nothing behind it), so the rows
#: compared are what they were, and the lengths of a file's cases are a
#: handful of compiled programs where they were one each.
REFERENCE_BUCKET = 32


_KEPT_LAYER_FNS = {}


@contextlib.contextmanager
def kept_layer_programs(*modules):
    """``_layer_fn`` of a reference module returns a NEW jitted function
    every call, and ``forward`` calls it once a sequence: every sequence
    compiled every layer again, whatever had been compiled before (4.5 s of
    a four-prompt engine case, 30 of ``tests/test_kda_engine.py``'s 100).
    Inside this block the modules' ``_layer_fn`` is one kept a
    (configuration, operators) for the process, so the function is the same
    one and its programs are found again; on the way out the modules have
    their own back. ``chipbench/`` is the benchmark's and stays as it is,
    and so does what ``tests/chipbench_tests/`` runs against, whichever
    file its worker ran before."""
    own = {m: m._layer_fn for m in modules if hasattr(m, "_layer_fn")}
    for module, build in own.items():
        if module not in _KEPT_LAYER_FNS:
            _KEPT_LAYER_FNS[module] = functools.lru_cache(maxsize=None)(build)
        module._layer_fn = _KEPT_LAYER_FNS[module]
    try:
        yield
    finally:
        for module, build in own.items():
            module._layer_fn = build


def reference_logits(ref, params, cfg, tokens) -> np.ndarray:
    """``ref``: a module of ``chipbench/references`` (float32, the whole
    sequence at once, nothing of the program's model code)."""
    tokens = list(tokens)
    # (under a block mask a block sees all of itself: no pad inside one)
    whole_blocks = len(tokens) % (cfg.block_length or 1) == 0
    pad = -len(tokens) % REFERENCE_BUCKET if whole_blocks else 0
    with kept_layer_programs(chip_reference, ref):
        logits = ref.forward(params, cfg, tokens + [0] * pad)[0]
    return np.asarray(logits, np.float32)[: len(tokens)]


def picks(ref, params, cfg, ask, generated) -> list[int]:
    """The reference's greedy choice at each generated position, given the
    tokens the engine generated before it."""
    logits = reference_logits(ref, params, cfg, ask + generated)
    return logits[len(ask) - 1: -1].argmax(-1).tolist()


def make_engine(cfg, params, pages, *, lanes=4, on_events=None, **engine):
    """An interpreted engine at the tests' sizes. ``pages``: its
    ``BlockManagerConfig``; ``engine``: any other field of ``EngineConfig``."""
    engine.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    engine.setdefault("max_model_len", 128)
    return Engine(
        EngineConfig(
            model=cfg, block_manager=pages, decode_batch_size=lanes,
            prefill_bucket=16, interpret=True, **engine,
        ),
        params=params, on_events=on_events,
    )


def run_all(engine, prompts, n):
    seqs = [engine.add_request(p, SamplingParams(max_new_tokens=n))
            for p in prompts]
    while engine.has_work:
        engine.step()
    return seqs


def run_one(engine, prompt, **sampling):
    """(the finished sequence, its block table while it ran)."""
    seq = engine.add_request(prompt, SamplingParams(**sampling))
    table = []
    while engine.has_work:
        engine.step()
        table = list(seq.block_table) or table
    return seq, table


def reference_generate(ref, params, cfg, prompt, max_tokens, steps, threshold):
    """Generation by diffusion over blocks (``cfg.block_length`` > 0): the
    published procedure on the reference's logits, greedy: the completion,
    and every token of the final blocks (prompt included)."""
    block, mask = cfg.block_length, cfg.mask_token_id
    n_final = len(prompt) // block * block
    final, tail = list(prompt[:n_final]), list(prompt[n_final:])
    while len(final) - len(prompt) < max_tokens:
        cur = tail + [mask] * (block - len(tail))
        masked = np.array([False] * len(tail) + [True] * (block - len(tail)))
        step = 0
        while masked.any():
            logits = reference_logits(ref, params, cfg, final + cur)[-block:]
            probs = np.exp(logits - logits.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)
            x0, p = probs.argmax(-1), probs.max(-1)
            owed = block // steps + (step < block % steps)
            high = masked & (p > threshold)
            if high.sum() >= owed:
                fix = high
            else:
                order = np.argsort(-np.where(masked, p, -np.inf), kind="stable")
                fix = np.zeros(block, bool)
                fix[order[:owed]] = True
                fix &= masked
            cur = [int(x0[i]) if fix[i] else cur[i] for i in range(block)]
            masked &= ~fix
            step += 1
        final, tail = final + cur, []
    return final[len(prompt):len(prompt) + max_tokens], final


class NoSecondPool:
    """What ``served`` asks of the pool an architecture lays beside the keys
    and values; this one is none (a latent pool, a plain GQA pool)."""

    def make(self, cfg, rows: int, pages: int, table_pages: int) -> None:
        """Before the first call: ``rows`` sequences, ``pages`` pages in the
        key/value pools, block tables of ``table_pages`` pages."""

    def prefill(self, chunks, positions, ctx_pages: int) -> dict:
        """``llama.prefill``'s keywords for a call of ``chunks`` [(row,
        first position, end)] at ``positions`` [rows, width] with a context
        table of ``ctx_pages`` pages."""
        return {}

    def decode(self, positions) -> dict:
        """``llama.decode_step``'s keywords for a step of every row at
        ``positions`` [rows]."""
        return {}

    def keep(self, results) -> None:
        """What the call returned after the logits, keys and values."""
        assert not results


class WindowPages(NoSecondPool):
    """The second pool of a model with sliding layers: a row's window pages
    come from its own free list and are given back as the engine's block
    manager gives them back. ``window_table``: the reference's own table of
    one sequence's window pages (``chipbench/references/swa_moe.WindowTable``)."""

    def __init__(self, window_table, page_size: int):
        self.window_table, self.page_size = window_table, page_size

    def make(self, cfg, rows, pages, table_pages):
        self.width = table_pages + 2
        self.pool = llama.init_window_pages(
            cfg, rows * self.width + 1, self.page_size)
        self.tables = []
        for i in range(rows):  # a row's window pages: its own range of the pool
            wt = self.window_table(
                self.width + 1, cfg.sliding_window, self.page_size)
            wt.free = [i * self.width + p for p in wt.free]
            self.tables.append(wt)

    def prefill(self, chunks, positions, ctx_pages):
        b, width = positions.shape
        w_ids = np.zeros((b, width), np.int32)
        w_tab = np.zeros((b, ctx_pages), np.int32)
        w_start = np.zeros((b,), np.int32)
        for i, lo, hi in chunks:
            wt = self.tables[i]
            wt.move_to(lo, hi)
            w_ids[i, : hi - lo] = wt.page_of(positions[i, : hi - lo])
            w_tab[i] = wt.row(ctx_pages)[0]
            w_start[i] = wt.first * self.page_size
        return dict(window_pages=self.pool, window_rows=(w_ids, w_tab, w_start))

    def decode(self, positions):
        for wt, at in zip(self.tables, positions):
            wt.move_to(at, at + 1)
        return dict(
            window_pages=self.pool,
            window_tables=np.concatenate(
                [wt.row(self.width) for wt in self.tables]),
            window_start=np.array(
                [wt.first * self.page_size for wt in self.tables], np.int32),
        )

    def keep(self, results):
        (self.pool,) = results


class StateSlots(NoSecondPool):
    """The second pool of a model with linear layers: two slots a row, and
    every call reads the row's state from the one and writes it to the other
    (a prefill as ``[read, write]``, a decode step as ``[a, b, switch]`` with
    the switch at the step's own position)."""

    def make(self, cfg, rows, pages, table_pages):
        self.pool = llama.init_kda_state(cfg, 2 * rows + 1)
        self.slots = [[1 + 2 * i, 2 + 2 * i] for i in range(rows)]

    def _swap(self, i):
        self.slots[i].reverse()
        return list(reversed(self.slots[i]))  # [the one read, the one written]

    def prefill(self, chunks, positions, ctx_pages):
        if self.pool is None:
            return {}
        slots = np.zeros((positions.shape[0], 2), np.int32)
        for i, _, _ in chunks:
            slots[i] = self._swap(i)
        return dict(state_pages=self.pool, state_slots=slots)

    def decode(self, positions):
        if self.pool is None:
            return {}
        slots = np.array([[*self._swap(i), at]
                          for i, at in enumerate(positions)], np.int32)
        return dict(state_pages=self.pool, state_slots=slots)

    def keep(self, results):
        if self.pool is not None:
            (self.pool,) = results


def served(params, cfg, rows, steps, attn_impl, *, page_size, second=None):
    """``rows``: [(prompt, tokens resident before the batched call)]: each
    row's first ``resident`` tokens are prefilled cold (a call of their own;
    ``resident`` need not end a page), the rest in ONE batched, right-padded
    call against them; then ``steps`` greedy decode steps of every row in one
    batch. Returns the logits a row, [1 + steps, vocab], the tokens fed, and
    (the key pool, the value pool, the block tables), of ``rows`` alone (the
    call's rows are padded to whole ``ROW_BUCKET``)."""
    ps, real = page_size, len(rows)
    rows = list(rows) + [([1], 0)] * (-real % ROW_BUCKET)
    b = len(rows)
    second = second or NoSecondPool()
    need = [-(-(len(p) + steps) // ps) for p, _ in rows]
    tables = np.zeros((b, round_up(max(need), TABLE_BUCKET)), np.int32)
    nxt = 1
    for i, n in enumerate(need):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    pages = round_up(nxt + 1, POOL_BUCKET)
    k_pages, v_pages = llama.init_kv_pages(cfg, pages, ps)
    second.make(cfg, b, pages, tables.shape[1])

    def prefill(chunks):
        nonlocal k_pages, v_pages
        width = round_up(max(hi - lo for _, lo, hi in chunks), CHUNK_BUCKET)
        ctx_w = round_up(max(-(-lo // ps) for _, lo, _ in chunks), CTX_BUCKET)
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
            ctx_bt[i, : -(-lo // ps)] = tables[i, : -(-lo // ps)]
            ctx_len[i] = lo
        page = np.take_along_axis(tables, pos // ps, axis=1)
        logits, k_pages, v_pages, *rest = llama.prefill(
            params, cfg, tok, pos, ok, k_pages, v_pages, page, pos % ps,
            ctx_bt, ctx_len, attn_impl=attn_impl, interpret=True,
            **second.prefill(chunks, pos, ctx_w),
        )
        second.keep(rest)
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
        logits, k_pages, v_pages, *rest = llama.decode_step(
            params, cfg, toks, lens + step, k_pages, v_pages, tables,
            lens + step + 1, page_size=ps, interpret=True,
            **second.decode(lens + step),
        )
        second.keep(rest)
        for i in range(b):
            fed[i].append(int(toks[i]))
            out[i].append(np.asarray(logits, np.float32)[i])
    return ([np.stack(o) for o in out[:real]], fed[:real],
            (k_pages, v_pages, tables[:real]))
