"""The delta-rule recurrence of ``ops/kda.py``: the decode kernel in
interpret mode against its ``jax.numpy`` oracle over a pool of slots (a slot
read and written in place, a slot left behind, a fresh lane, the padded
lanes' slot), the chunked prefill against the token-by-token recurrence
from a non-zero initial state, and the plain reference's own recurrence
(``chipbench/references/kda_mla_moe``) against ``transformers``' gated delta
rule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.ops import kda

H, K = 3, 16
REF = chip_reference.load("kda_mla_moe")


def _rng(seed):
    return np.random.default_rng(seed)


def _operands(rng, *lead, safe=True):
    """(q, k, v, g, beta) of shapes ``[*lead, H, K]`` / ``[*lead, H]`` as a
    linear layer makes them: k of length one, q over sqrt(K), g <= 0."""
    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q, k, v = r(*lead, H, K), r(*lead, H, K), r(*lead, H, K)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(K)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    a = r(*lead, H, K)
    g = -5.0 * jax.nn.sigmoid(a) if safe else -jax.nn.softplus(a)
    return q, k, v, g, jax.nn.sigmoid(r(*lead, H))


@pytest.mark.parametrize("s, chunk", [(1, 64), (63, 64), (64, 64), (150, 64),
                                      (40, 8), (33, 16)])
@pytest.mark.parametrize("safe", [True, False], ids=["safe_gate", "softplus"])
def test_the_chunked_prefill_is_the_recurrence(s, chunk, safe):
    rng = _rng(s + chunk)
    ops = _operands(rng, 2, s, safe=safe)
    S0 = jnp.asarray(rng.standard_normal((2, H, K, K)), jnp.float32)
    want_o, want_S = kda.kda_recurrent(*ops, S0)
    got_o, got_S = kda.kda_chunked(*ops, S0, chunk=chunk)
    scale = float(jnp.abs(want_o).max())
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5 * max(scale, 1.0)
    assert float(jnp.abs(got_S - want_S).max()) < 2e-5


def test_a_strong_decay_does_not_overflow_a_chunk():
    """``g`` at the safe gate's bound on every channel of every token: the
    running sum reaches -320 inside a chunk and no exponent is positive."""
    rng = _rng(3)
    q, k, v, _, beta = _operands(rng, 1, 64)
    g = jnp.full((1, 64, H, K), -5.0)
    S0 = jnp.asarray(rng.standard_normal((1, H, K, K)), jnp.float32)
    want_o, want_S = kda.kda_recurrent(q, k, v, g, beta, S0)
    got_o, got_S = kda.kda_chunked(q, k, v, g, beta, S0)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_S, want_S, atol=1e-5)


def test_padding_leaves_the_state_as_it_was():
    """A token with ``g = 0`` and ``beta = 0`` (what ``llama`` makes of a
    slot that holds no token) changes nothing: the state after 20 tokens and
    12 of padding is the state after the 20."""
    rng = _rng(4)
    q, k, v, g, beta = _operands(rng, 1, 32)
    real = jnp.arange(32)[None, :, None] < 20
    g = jnp.where(real[..., None], g, 0.0)
    beta = jnp.where(real, beta, 0.0)
    S0 = jnp.asarray(rng.standard_normal((1, H, K, K)), jnp.float32)
    _, padded = kda.kda_chunked(q, k, v, g, beta, S0, chunk=16)
    _, cut = kda.kda_recurrent(
        q[:, :20], k[:, :20], v[:, :20], g[:, :20], beta[:, :20], S0)
    np.testing.assert_allclose(padded, cut, atol=1e-5)


# -- the decode kernel over a pool of slots ------------------------------------
LAYERS, SLOTS, LANES = 2, 7, 4

CASES = {
    # every lane keeps its slot
    "in_place": ([1, 2, 3, 4], [1, 2, 3, 4], [0, 0, 0, 0]),
    # lanes 1 and 2 leave their slot behind and go on in another
    "left_behind": ([1, 2, 3, 4], [1, 5, 6, 4], [0, 0, 0, 0]),
    # lane 0 starts from zeros whatever its slot holds
    "fresh": ([1, 2, 3, 4], [1, 2, 3, 4], [1, 0, 0, 0]),
    # two padded lanes on the reserved slot
    "padded": ([1, 2, 0, 0], [1, 2, 0, 0], [0, 0, 0, 0]),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("layer", [0, 1])
def test_the_decode_kernel_is_its_oracle(case, layer):
    read, write, fresh = (jnp.asarray(x, jnp.int32) for x in CASES[case])
    rng = _rng(layer)
    pool = jnp.asarray(
        rng.standard_normal((LAYERS, SLOTS, H, K, K)), jnp.float32)
    ops = _operands(rng, LANES)
    want_o, want_pool = kda.kda_decode_reference(
        pool, *ops, read, write, fresh, layer)
    got_o, got_pool = kda.kda_decode(
        pool, *ops, read, write, fresh, layer, interpret=True)
    real = np.asarray(write) > 0
    np.testing.assert_allclose(
        np.asarray(got_o)[real], np.asarray(want_o)[real], atol=1e-5)
    # (slot 0 is written by every padded lane: whatever it holds is unread)
    np.testing.assert_allclose(
        np.asarray(got_pool)[:, 1:], np.asarray(want_pool)[:, 1:], atol=1e-5)
    # a slot nobody wrote is as it was: the other layer's, and what a lane
    # left behind
    untouched = np.ones((LAYERS, SLOTS), bool)
    untouched[layer, np.asarray(write)] = False
    untouched[:, 0] = False
    assert np.array_equal(
        np.asarray(got_pool)[untouched], np.asarray(pool)[untouched])


def test_the_oracle_is_one_step_of_the_recurrence():
    rng = _rng(9)
    pool = jnp.asarray(
        rng.standard_normal((LAYERS, SLOTS, H, K, K)), jnp.float32)
    q, k, v, g, beta = _operands(rng, LANES)
    read = jnp.asarray([1, 2, 3, 4], jnp.int32)
    o, new = kda.kda_decode_reference(
        pool, q, k, v, g, beta, read, read, jnp.zeros(4, jnp.int32), 1)
    want_o, want_S = kda.kda_recurrent(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
        pool[1, 1:5])
    np.testing.assert_allclose(o, want_o[:, 0], atol=1e-6)
    np.testing.assert_allclose(new[1, 1:5], want_S, atol=1e-6)


def test_a_compiled_kernel_is_refused_off_the_chip():
    pool = jnp.zeros((1, 2, H, K, K), jnp.float32)
    ops = _operands(_rng(0), 1)
    one = jnp.ones(1, jnp.int32)
    with pytest.raises(RuntimeError, match="kda_decode.*interpret"):
        kda.kda_decode(pool, *ops, one, one, one * 0, 0)


# -- the reference's recurrence -------------------------------------------------
def test_the_reference_recurrence_is_the_gated_delta_rule():
    """With ``g`` equal over a head's channels the recurrence is the gated
    delta rule: ``transformers``' token-by-token form (torch, CPU)."""
    torch = pytest.importorskip("torch")
    from transformers.models.qwen3_next.modeling_qwen3_next import (
        torch_recurrent_gated_delta_rule,
    )

    rng = np.random.default_rng(0)
    s, H, K = 37, 3, 16
    q, k, v = (rng.standard_normal((s, H, K)).astype(np.float32)
               for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.abs(rng.standard_normal((s, H))).astype(np.float32)
    beta = 1 / (1 + np.exp(-rng.standard_normal((s, H)).astype(np.float32)))
    S0 = rng.standard_normal((H, K, K)).astype(np.float32)
    # (theirs scales q by 1 / sqrt(K) inside; ours takes q as the layer
    # scaled it)
    got_o, got_S = REF.recurrence(
        jnp.asarray(q / np.sqrt(K)), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(np.repeat(g[..., None], K, axis=-1)), jnp.asarray(beta),
        jnp.asarray(S0))
    want_o, want_S = torch_recurrent_gated_delta_rule(
        *(torch.tensor(x)[None] for x in (q, k, v, g, beta)),
        initial_state=torch.tensor(S0)[None], output_final_state=True)
    np.testing.assert_allclose(got_o, want_o[0].numpy(), atol=2e-5)
    np.testing.assert_allclose(got_S, want_S[0].numpy(), atol=2e-5)
