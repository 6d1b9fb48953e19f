"""Delta-rule linear-attention layers beside latent ones (``layer_types``
"linear_attention", ``TINY_LING_HYBRID``) over group-limited sparse experts:
the served programs against the plain reference, in float32.

The reference side is ``chipbench/references/kda_mla_moe.forward`` (float32,
the recurrence token by token, nothing of the program's model code); its
recurrence is itself held to ``transformers``' gated delta rule where the
gate is one value a head. The program's prefill is the chunked form, its
decode the kernel over a pool of slots (interpreted): every call here reads a
row's state from one slot and writes it to ANOTHER, which is how the engine
takes and restores snapshots. The kernels alone, and the reference's
recurrence against ``transformers``', are in ``tests/test_kda_kernels.py``
(the import of ``transformers`` alone is 20-80 s of a loaded worker, and this
file is the architecture's dearest without it), the engine in
``tests/test_kda_engine.py``,
a layer alone, the carried rows' pool and group-limited routing in
``tests/test_kda_layers.py`` (one file with this until it passed 120
cpu-seconds of a whole run), refusals, presets and the loader in
``tests/test_kda_config.py``; the
helpers they share with the other architectures are ``tests/served_path.py``.
"""

import dataclasses

import jax
import numpy as np
import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_LING_HYBRID, llama
from served_path import prompt_of, rel_err

CFG = TINY_LING_HYBRID
#: one period of the two (linear and dense, linear and routed, latent and
#: routed): every kind of layer once, half the program to compile. The cases
#: that do not need the second period (where a layer's place among its kind
#: is not its place in the model) run on it.
PERIOD = dataclasses.replace(CFG, n_layers=3)
PS = 4
TOL = 1e-4
REF = chip_reference.load("kda_mla_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 47)


def one_period(tree):
    return {**tree, "layers": tree["layers"][:PERIOD.n_layers]}


def reference_logits(params, tokens, cfg=CFG) -> np.ndarray:
    return served_path.reference_logits(REF, params, cfg, tokens)


def served(params, rows, steps, attn_impl, cfg=CFG):
    got, fed, _ = served_path.served(
        params, REF.pool_config(params, cfg), rows, steps, attn_impl,
        page_size=PS, second=served_path.StateSlots())
    return got, fed


# -- the served programs against the token-by-token reference ------------------
@pytest.mark.parametrize("attn_impl, cfg, rows", [
    # both periods; a cold row beside two that go on from a state
    pytest.param("xla", CFG, [(30, 16), (9, 0), (21, 8)],
                 id="xla-batch-of-unequal-lengths"),
    pytest.param("xla", PERIOD, [(70, 64)],
                 id="xla-over-a-chunk-of-the-recurrence"),
    # (the latent layers' kernel, interpreted: the linear layers' prefill is
    # the same program under both)
    pytest.param("pallas", PERIOD, [(30, 16), (9, 0), (21, 8)],
                 id="pallas-batch-of-unequal-lengths"),
])
def test_prefill_then_decode_through_the_pools(params, rows, cfg, attn_impl):
    if cfg is PERIOD:
        params = one_period(params)
    rows = [(prompt_of(40 + i, n), r) for i, (n, r) in enumerate(rows)]
    got, fed = served(params, rows, 5, attn_impl, cfg=cfg)
    for (prompt, _), logits, tokens in zip(rows, got, fed):
        want = reference_logits(params, prompt + tokens, cfg)[len(prompt) - 1:]
        assert rel_err(logits, want) < TOL


def test_a_snapshot_is_left_as_it_was(params):
    """A prefill that reads slot 1 and writes slot 2 leaves slot 1 bit for
    bit; a second one from slot 1 gives the first's logits bit for bit."""
    cfg, params = PERIOD, one_period(params)
    prompt = prompt_of(3, 24)
    k_pages, v_pages = llama.init_kv_pages(cfg, 16, PS)
    state = llama.init_kda_state(cfg, 4)

    def piece(lo, hi, read, write):
        nonlocal k_pages, v_pages, state
        pos = np.arange(lo, hi)[None, :]
        logits, k_pages, v_pages, state = llama.prefill(
            params, cfg, np.asarray([prompt[lo:hi]], np.int32), pos,
            np.ones((1, hi - lo), bool), k_pages, v_pages, 1 + pos // PS,
            pos % PS, 1 + np.arange(lo // PS)[None, :], np.array([lo]),
            interpret=True, state_pages=state,
            state_slots=np.array([[read, write]], np.int32))
        return np.asarray(logits)

    piece(0, 12, 1, 1)
    before = jax.tree.map(lambda x: np.asarray(x[:, 1]), state)
    first = piece(12, 24, 1, 2)
    again = piece(12, 24, 1, 3)
    after = jax.tree.map(lambda x: np.asarray(x[:, 1]), state)
    assert np.array_equal(first, again)
    assert all(np.array_equal(a, b) for a, b in zip(before, after))
    assert all(np.array_equal(x[:, 2], x[:, 3]) for x in state)
    assert not np.array_equal(state[0][:, 1], state[0][:, 2])
