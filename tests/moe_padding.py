"""What ``tests/test_moe_padding.py`` (a routed layer alone) and
``tests/test_moe_padding_programs.py`` (the whole programs) share: the
presets, the real lengths of a padded ``[8, 12]`` dispatch, and the grouped
matmul that poisons what it leaves past the last group. Not collected."""

import jax
import jax.numpy as jnp
import numpy as np

from llm_d_kv_cache_manager_tpu.models import (
    TINY_LFM2_MOE,
    TINY_MLA_MOE,
    TINY_QWEN3_MOE,
)
from llm_d_kv_cache_manager_tpu.ops import gmm as gmm_ops

ROWS, WIDTH, PS = 8, 12, 4

#: softmax routing; sigmoid routing with a bias that chooses and a shared
#: expert; the hybrid one (sigmoid, convolution layers beside attention)
PRESETS = {
    "softmax": TINY_QWEN3_MOE,
    "sigmoid-shared": TINY_MLA_MOE,
    "hybrid": TINY_LFM2_MOE,
}
#: real tokens of each row, for 1, 3 and 8 real rows of the 8
LENGTHS = {
    1: [7, 0, 0, 0, 0, 0, 0, 0],
    3: [12, 5, 1, 0, 0, 0, 0, 0],
    8: [12, 12, 12, 12, 12, 12, 12, 12],
}
TOL = dict(atol=2e-5, rtol=2e-4)


def poisoned_grouped_matmul(monkeypatch):
    """Every ``group_sizes`` the routed FFN hands the grouped matmul while
    the fixture is live, and the rows past the last group poisoned with NaN
    in what it returns: what a kernel may leave there."""
    seen = []
    real = gmm_ops.grouped_matmul

    def poisoning(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        if not isinstance(group_sizes, jax.core.Tracer):
            seen.append(np.asarray(group_sizes))
        past = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(gmm_ops, "grouped_matmul", poisoning)
    return seen
