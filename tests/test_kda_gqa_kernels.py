"""The delta-rule kernels where ``beta`` lies in (1, 2) (``kda_neg_eigval``:
``I - beta k k^T`` has a NEGATIVE eigenvalue, the state can change sign
along ``k``) and the operands have the shape 64 heads give them (the decode
kernel's operand block ``[lanes, K, 4 H]`` is 256 lanes wide, two 128-lane
tiles, for the first time): ``kda_chunked`` = ``kda_recurrent`` =
``kda_decode`` (interpreted). What Mosaic and the chip make of the same
shapes is ``tools/aot_pool_copies --config solar-open2-250b`` and the cell's
reference check; the kernels at ``beta`` in (0, 1) are
``tests/test_kda_kernels.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops import kda

#: 64 heads of a small state: the operand block is 4 x 64 = 256 columns
H, K = 64, 8


def _operands(rng, *lead):
    """(q, k, v, g, beta) as a Solar linear layer makes them: k of length
    one, q over sqrt(K), the paper's gate, ``beta`` in (1, 2)."""
    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q, k, v = r(*lead, H, K), r(*lead, H, K), r(*lead, H, K)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(K)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jax.nn.softplus(r(*lead, H, K))
    return q, k, v, g, 1.0 + jax.nn.sigmoid(r(*lead, H))


@pytest.mark.parametrize("s, chunk", [(1, 64), (64, 64), (150, 64), (33, 16)])
def test_the_chunked_prefill_is_the_recurrence_past_beta_one(s, chunk):
    rng = np.random.default_rng(s + chunk)
    ops = _operands(rng, 2, s)
    assert 1 < float(ops[4].min()) and float(ops[4].max()) < 2
    S0 = jnp.asarray(rng.standard_normal((2, H, K, K)), jnp.float32)
    want_o, want_S = kda.kda_recurrent(*ops, S0)
    got_o, got_S = kda.kda_chunked(*ops, S0, chunk=chunk)
    scale = float(jnp.abs(want_o).max())
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5 * max(scale, 1.0)
    assert float(jnp.abs(got_S - want_S).max()) < 2e-5 * max(
        float(jnp.abs(want_S).max()), 1.0)


def test_beta_past_one_flips_the_state_along_the_key():
    """One token from a state ``S`` with no decay: the component of ``S``
    along ``k`` is scaled by ``1 - beta``, negative for ``beta`` in (1, 2)."""
    rng = np.random.default_rng(5)
    q, k, v, _, beta = _operands(rng, 1, 1)
    S0 = jnp.asarray(rng.standard_normal((1, H, K, K)), jnp.float32)
    zero = jnp.zeros_like(v)
    _, S = kda.kda_recurrent(q, k, zero, jnp.zeros_like(k), beta, S0)
    along = jnp.einsum("bhkv,bhk->bhv", S, k[:, 0])
    before = jnp.einsum("bhkv,bhk->bhv", S0, k[:, 0])
    np.testing.assert_allclose(
        along, (1.0 - beta[:, 0])[..., None] * before, atol=1e-5)
    assert float((1.0 - beta).max()) < 0


@pytest.mark.parametrize("layer", [0, 1])
def test_the_decode_kernel_is_a_step_of_the_recurrence_at_64_heads(layer):
    lanes, slots = 4, 7
    rng = np.random.default_rng(layer)
    pool = jnp.asarray(rng.standard_normal((2, slots, H, K, K)), jnp.float32)
    q, k, v, g, beta = _operands(rng, lanes)
    # lane 0 keeps its slot, lanes 1 and 2 leave theirs behind, lane 3 pads
    read = jnp.asarray([1, 2, 3, 0], jnp.int32)
    write = jnp.asarray([1, 5, 6, 0], jnp.int32)
    fresh = jnp.zeros(lanes, jnp.int32)
    got_o, got_pool = kda.kda_decode(
        pool, q, k, v, g, beta, read, write, fresh, layer, interpret=True)
    want_o, want_S = kda.kda_recurrent(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
        pool[layer, read])
    np.testing.assert_allclose(got_o[:3], want_o[:3, 0], atol=1e-5)
    np.testing.assert_allclose(got_pool[layer, write[:3]], want_S[:3], atol=1e-5)
    # what a lane left behind, and the other layer, are as they were
    assert np.array_equal(got_pool[layer, 2:5], pool[layer, 2:5])
    assert np.array_equal(got_pool[1 - layer], pool[1 - layer])
