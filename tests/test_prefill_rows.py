"""A prefill dispatch computes the rows that hold a sequence.

``llama.prefill_packed`` holds its forward for one row of the bucketed width,
in a loop that runs as far as the last row with a valid slot; the pools are
written once, after the loop. Held here, on the CPU in float32, for one small preset of each kind the
cells run and for 1, 2, 3, 5 and 8 real rows of unequal lengths behind a
resident page each:

- the real rows' logits and both pools (the state pool too) are what the
  single eight-row body (``llama.prefill``) leaves, every slot of them, to
  ``TOL`` (``tests/test_packed_inputs.py`` holds an operand of ONE shape bit
  for bit; a float32 matmul over one row rounds otherwise than over eight,
  2e-6 here), and the rows no turn computed read zero;
- no slot outside the real rows' pages changes;
- a row that holds nothing before one that does is computed with it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    TINY_LFM2_MOE,
    TINY_LLAMA,
    TINY_MLA_MOE,
    TINY_QWEN3_MOE,
    TINY_SCMOE,
    TINY_SDAR_MOE,
    llama,
)

PS, ROWS, CHUNK, PAGES = 4, 8, 8, 32
#: valid tokens of each row's chunk: unequal, one row full, one of one token
LENGTHS = np.asarray([7, 3, 8, 5, 1, 6, 2, 4])
PRESETS = {
    "dense-gqa": TINY_LLAMA,
    "routed": TINY_QWEN3_MOE,  # its padding leaves the expert groups (PR 40)
    "latent-shared": TINY_MLA_MOE,
    "conv-state": TINY_LFM2_MOE,  # a row's state comes from its page's slot
    "block-mask": TINY_SDAR_MOE,
    "held-zero": TINY_SCMOE,
}
KW = dict(attn_impl="xla", interpret=True)
TOL = 1e-5


def _cfg(kind):
    return dataclasses.replace(PRESETS[kind], dtype=jnp.float32)


@pytest.fixture(scope="module")
def warm():
    """(params, pools after one resident page a row) a kind, made once."""
    made = {}

    def of(kind):
        if kind not in made:
            cfg = _cfg(kind)
            params = llama.init_params(jax.random.PRNGKey(7), cfg)
            made[kind] = params, _run(
                llama.prefill, cfg, params, _pools(cfg), _resident(cfg)
            )[1]
        return made[kind]

    return of


def _tables():
    # row i: its resident page, then the chunk's two; page 0 is nobody's
    return 1 + 3 * np.arange(ROWS)[:, None] + np.arange(3)[None, :]


def _pools(cfg):
    k_pages, v_pages = llama.init_kv_pages(cfg, PAGES, PS)
    state = llama.init_state_pages(cfg, PAGES)
    return k_pages, v_pages, state


def _resident(cfg):
    """A page of context in every row, cold."""
    rng = np.random.default_rng(21)
    positions = np.arange(CHUNK)[None, :].repeat(ROWS, 0).astype(np.int32)
    valid = positions < PS
    tokens = np.where(valid, rng.integers(1, cfg.vocab_size, (ROWS, CHUNK)), 0)
    page_ids = np.where(valid, _tables()[:, :1], 0)
    return (tokens, positions, valid, page_ids, positions % PS,
            np.zeros((ROWS, 0), np.int32), np.zeros((ROWS,), np.int32))


def _chunk(cfg, n_rows):
    """The first ``n_rows`` rows hold ``LENGTHS`` tokens behind their
    resident page; the others hold nothing, as the engine leaves them."""
    rng = np.random.default_rng(22)
    real = (np.arange(ROWS) < n_rows)[:, None]
    positions = (PS + np.arange(CHUNK))[None, :].repeat(ROWS, 0)
    valid = real & (np.arange(CHUNK)[None, :] < LENGTHS[:, None])
    tokens = np.where(valid, rng.integers(1, cfg.vocab_size, (ROWS, CHUNK)), 0)
    page_ids = np.where(
        valid, np.take_along_axis(_tables(), positions // PS, axis=1), 0)
    return (tokens, np.where(valid, positions, 0), valid, page_ids,
            np.where(valid, positions % PS, 0), np.where(real, _tables()[:, :1], 0),
            np.where(real[:, 0], PS, 0))


def _run(fn, cfg, params, pools, operands):
    """(logits, pools) of one dispatch through ``prefill`` or
    ``prefill_packed``; the pools are donated, so copies go in."""
    k_pages, v_pages, state = (None if p is None else jnp.copy(p) for p in pools)
    stateful = {} if state is None else {"state_pages": state}
    operands = [np.asarray(x, np.int32) for x in operands]
    operands[2] = operands[2].astype(bool)
    if fn is llama.prefill_packed:
        out = fn(params, cfg, llama.pack_prefill_inputs(*operands), k_pages,
                 v_pages, chunk=CHUNK, **KW, **stateful)
    else:
        t, p, ok, pg, sl, bt, cl = operands
        out = fn(params, cfg, t, p, ok, k_pages, v_pages, pg, sl, bt, cl,
                 **KW, **stateful)
    logits, k_pages, v_pages, *rest = out
    return np.asarray(logits), (k_pages, v_pages, rest[0] if rest else None)


@pytest.mark.parametrize("n_rows", [1, 2, 3, 5, 8])
@pytest.mark.parametrize("kind", list(PRESETS))
def test_the_rows_that_hold_a_sequence_are_what_eight_rows_give(
        kind, n_rows, warm):
    cfg = _cfg(kind)
    params, before = warm(kind)
    operands = _chunk(cfg, n_rows)
    want, want_pools = _run(llama.prefill, cfg, params, before, operands)
    got, got_pools = _run(llama.prefill_packed, cfg, params, before, operands)

    assert got.shape == want.shape == (ROWS, cfg.vocab_size)
    assert np.abs(want[:n_rows]).max() > 0
    np.testing.assert_allclose(got[:n_rows], want[:n_rows], rtol=TOL, atol=TOL)
    assert not got[n_rows:].any()  # the rows no turn computed

    mine = np.zeros(PAGES, bool)
    mine[_tables()[:n_rows, 1:].ravel()] = True
    for have, ref, was in zip(got_pools, want_pools, before):
        if ref is None or not ref.size:  # no state pool; a latent model's
            assert have is None or not have.size  # second pool has no page
            continue
        have, ref, was = np.asarray(have), np.asarray(ref), np.asarray(was)
        np.testing.assert_allclose(have, ref, rtol=TOL, atol=TOL)
        # pages lie on the second axis of every pool
        np.testing.assert_array_equal(have[:, ~mine], was[:, ~mine])
        assert (have[:, mine] != was[:, mine]).any()


def test_a_conv_rows_state_came_from_its_pages_slot(warm):
    """The case above reads a warm row's state from the resident page's
    slot: with that slot zeroed the row's logits are others."""
    cfg = _cfg("conv-state")
    params, (k_pages, v_pages, state) = warm("conv-state")
    operands = _chunk(cfg, 1)
    want, _ = _run(llama.prefill_packed, cfg, params,
                   (k_pages, v_pages, state), operands)
    wiped = state.at[:, _tables()[0, 0]].set(0)
    got, _ = _run(llama.prefill_packed, cfg, params,
                  (k_pages, v_pages, wiped), operands)
    assert np.abs(got[0] - want[0]).max() > 1e-3


def test_a_gap_among_the_rows_is_covered():
    """The loop runs as far as the LAST row with a valid slot: a row that
    holds nothing before one that does is computed with it."""
    cfg = _cfg("dense-gqa")
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    tokens, positions, valid, page_ids, slot_ids, bt, cl = _chunk(cfg, 3)
    valid = valid & (np.arange(ROWS) != 1)[:, None]
    operands = (tokens, positions, valid, np.where(valid, page_ids, 0),
                slot_ids, bt, cl)
    pools = _run(llama.prefill, cfg, params, _pools(cfg), _resident(cfg))[1]
    want, want_pools = _run(llama.prefill, cfg, params, pools, operands)
    got, got_pools = _run(llama.prefill_packed, cfg, params, pools, operands)
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], rtol=TOL, atol=TOL)
    for have, ref in zip(got_pools[:2], want_pools[:2]):
        np.testing.assert_allclose(
            np.asarray(have), np.asarray(ref), rtol=TOL, atol=TOL)


def test_the_program_traces_its_forward_once(monkeypatch):
    """What a warm set-up pays a program is its trace, its lowering and its
    load: the rows' loop needs the forward's result shapes before it has a
    body, and gets them from the same trace (``llama._prefill_rows`` is a
    jit of its own), not from a second one."""
    cfg = _cfg("conv-state")
    traced = []
    real = llama._prefill_forward
    monkeypatch.setattr(
        llama, "_prefill_forward",
        lambda *a, **kw: traced.append(1) or real(*a, **kw))
    shapes = jax.eval_shape(lambda: (
        llama.init_params(jax.random.PRNGKey(0), cfg), _pools(cfg)))
    params, (k_pages, v_pages, state) = shapes
    llama.prefill_packed.lower(
        params, cfg, jnp.zeros((ROWS, 5 * 24 + 3 + 1), jnp.int32), k_pages,
        v_pages, chunk=24, state_pages=state, **KW)
    assert len(traced) == 1
