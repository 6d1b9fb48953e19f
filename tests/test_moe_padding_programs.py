"""A row that holds no token chooses no expert, in the whole programs: the
real rows of a padded prefill dispatch read the logits they read in a dispatch
of their own, with what the grouped matmuls leave past the last group
poisoned in every routed layer, and an idle lane of ``denoise_steps`` moves
no active lane's tokens. One routed layer alone is in
``tests/test_moe_padding.py`` (one file until it passed 120 cpu-seconds of a
whole run); ``tests/moe_padding.py`` has what the two share."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_SDAR_MOE, llama
from moe_padding import (
    LENGTHS,
    PRESETS,
    PS,
    ROWS,
    WIDTH,
    poisoned_grouped_matmul,
)

grouped = pytest.fixture(poisoned_grouped_matmul)


def _prefill(cfg, params, tokens, lengths, gmm):
    cfg = dataclasses.replace(cfg, moe_gmm=gmm)
    rows, width = tokens.shape
    pages_a_row = -(-width // PS)
    k_pages, v_pages = llama.init_kv_pages(cfg, 1 + rows * pages_a_row, PS)
    state = llama.init_state_pages(cfg, 1 + rows * pages_a_row)
    pos = np.broadcast_to(np.arange(width, dtype=np.int32), (rows, width))
    valid = pos < np.asarray(lengths)[:, None]
    first = 1 + pages_a_row * np.arange(rows, dtype=np.int32)[:, None]
    out = llama.prefill(
        params, cfg, tokens, pos, valid, k_pages, v_pages,
        np.where(valid, first + pos // PS, 0), pos % PS,
        np.zeros((rows, 0), np.int32), np.zeros((rows,), np.int32),
        interpret=True, **({} if state is None else {"state_pages": state}),
    )
    return np.asarray(out[0], np.float32)


@pytest.mark.parametrize("gmm", ["xla", "kernel"])
@pytest.mark.parametrize("preset", list(PRESETS))
def test_a_padded_prefill_reads_its_real_rows_logits(preset, gmm, grouped):
    """The whole program: the last-token logits of three real rows among
    eight are what each row reads in a dispatch of its own, with the rows
    past the groups poisoned in every layer."""
    # a configuration of this test's own: its programs are traced here, with
    # the poison inside, and no other test's cache holds them
    cfg = dataclasses.replace(
        PRESETS[preset], rms_norm_eps=PRESETS[preset].rms_norm_eps + 3e-9
    )
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(4)
    tokens = rng.integers(1, 200, (ROWS, WIDTH)).astype(np.int32)
    lengths = LENGTHS[3]
    got = _prefill(cfg, params, tokens, lengths, gmm)
    assert np.isfinite(got[:3]).all()
    for row in range(3):
        # (alone AT ITS OWN LENGTH, a program a row: an oracle padded to the
        # dispatch's width would pass through the mask under test)
        n = lengths[row]
        alone = _prefill(cfg, params, tokens[row:row + 1, :n], [n], gmm)
        np.testing.assert_allclose(got[row], alone[0], atol=1e-4, rtol=1e-3)


def test_an_idle_lane_moves_no_active_lanes_tokens():
    cfg = TINY_SDAR_MOE
    width, lanes, table_w = cfg.block_length, 3, 4
    params = llama.init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(6)
    k_pages, v_pages = llama.init_kv_pages(cfg, 1 + lanes * table_w, PS)
    k_pages = jnp.asarray(rng.normal(size=k_pages.shape), k_pages.dtype)
    v_pages = jnp.asarray(rng.normal(size=v_pages.shape), v_pages.dtype)
    tables = 1 + np.arange(lanes * table_w, dtype=np.int32).reshape(lanes, -1)
    seq_lens = np.asarray([8, 4, 12], np.int32)

    def run(active):
        packed = np.concatenate([
            np.full((lanes, width), cfg.mask_token_id, np.int32),
            np.ones((lanes, width), np.int32), tables, seq_lens[:, None],
            np.zeros((lanes, 1), np.int32),  # step
            np.full((lanes, 1), 2, np.int32),  # denoising steps
            np.zeros((lanes, 1), np.int32),  # top_k
            np.asarray(active, np.int32)[:, None],
        ], axis=1)
        fparams = np.tile(np.asarray([[0.9, 0.0, 1.0]], np.float32), (lanes, 1))
        out, _, _ = llama.denoise_steps(
            params, cfg, packed, fparams, jnp.copy(k_pages), jnp.copy(v_pages),
            jax.random.PRNGKey(0), page_size=PS, table_w=table_w,
            attn_impl="xla", interpret=True,
        )
        return np.asarray(out)

    every, two = run([1, 1, 1]), run([1, 0, 1])
    np.testing.assert_array_equal(two[[0, 2], :-1], every[[0, 2], :-1])
    # the idle lane fixes nothing and its rows choose no expert
    np.testing.assert_array_equal(two[1, :width], cfg.mask_token_id)
    assert 0 < two[0, -1] <= every[0, -1]
