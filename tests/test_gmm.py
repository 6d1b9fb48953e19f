"""Grouped-matmul (gmm) kernel parity: the MoE routed dispatch's MXU path.

Oracle = ``jax.lax.ragged_dot`` (the XLA path the kernels replace,
``ops/gmm.py use_kernel=False``). Kernels run in Pallas interpret mode on
CPU; on-chip numerics are re-checked, compiled, by ``chip_smoke.py``'s
kernel phase per the repo's Mosaic lesson — interpret mode does not catch
Mosaic miscompiles.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_d_kv_cache_manager_tpu.models import TINY_MOE, init_params
from llm_d_kv_cache_manager_tpu.models import llama
from llm_d_kv_cache_manager_tpu.models.quant import quantize_tensor
from llm_d_kv_cache_manager_tpu.ops.gmm import grouped_matmul


def _problem(rng, E, d, f, sizes, dtype=jnp.bfloat16):
    sizes = np.asarray(sizes)
    rows = int(sizes.sum())
    lhs = jnp.asarray(rng.normal(size=(rows, d)), dtype)
    w = jnp.asarray(rng.normal(size=(E, d, f)) * 0.1, dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    rgi = jnp.asarray(np.repeat(np.arange(E), sizes), jnp.int32)
    return lhs, w, gs, rgi


class TestGroupedMatmul:
    @pytest.mark.parametrize(
        "sizes",
        [
            [40, 0, 25, 60, 10, 30, 20, 15],  # uneven + an empty group
            [0, 0, 128, 0, 0, 0, 0, 128],  # mostly empty
            [32] * 8,  # uniform
            [1, 2, 3, 4, 5, 6, 7, 8],  # tiny groups, rows % 8 != 0
        ],
    )
    def test_bf16_kernel_matches_ragged_dot(self, sizes):
        rng = np.random.default_rng(1)
        lhs, w, gs, _ = _problem(rng, 8, 256, 384, sizes)
        oracle = jax.lax.ragged_dot(lhs, w, gs).astype(jnp.float32)
        out = grouped_matmul(lhs, w, gs, interpret=True).astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), atol=2e-2)

    def test_int8_kernel_matches_dequant_oracle(self):
        rng = np.random.default_rng(2)
        sizes = [40, 0, 25, 60, 10, 30, 20, 15]
        lhs, w, gs, rgi = _problem(rng, 8, 256, 384, sizes)
        qw = quantize_tensor(w)
        oracle = grouped_matmul(
            lhs, qw, gs, row_group_ids=rgi, use_kernel=False
        ).astype(jnp.float32)
        out = grouped_matmul(
            lhs, qw, gs, row_group_ids=rgi, interpret=True
        ).astype(jnp.float32)
        # The kernel is MORE precise than the oracle (exact int8 dot in
        # f32, scale applied once) — bound the difference, not equality.
        scale = float(jnp.max(jnp.abs(oracle))) + 1e-9
        err = float(jnp.max(jnp.abs(out - oracle))) / scale
        assert err < 2e-2, err

    @pytest.mark.parametrize("kernel", ["megablox", "int8"])
    @pytest.mark.parametrize(
        "sizes",
        [
            [40, 0, 25, 60, 10, 30, 20, 15],  # 200 of 600 rows, tiles unvisited
            [0, 0, 3, 0, 0, 0, 0, 0],  # three real rows
            [0] * 8,  # no real row: no tile is visited
        ],
    )
    def test_group_sizes_may_sum_to_fewer_than_the_rows(self, kernel, sizes):
        """The rows past the last group are in no group (a prefill
        dispatch's padding): the rows inside the groups read what
        ``ragged_dot`` reads; the rest is undefined and not compared."""
        rng = np.random.default_rng(7)
        rows, real = 600, int(np.sum(sizes))
        lhs = jnp.asarray(rng.normal(size=(rows, 256)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(8, 256, 384)) * 0.1, jnp.bfloat16)
        gs = jnp.asarray(sizes, jnp.int32)
        rgi = jnp.asarray(
            np.concatenate([np.repeat(np.arange(8), sizes),
                            np.full(rows - real, 7)]), jnp.int32)
        rhs = quantize_tensor(w) if kernel == "int8" else w
        oracle = grouped_matmul(
            lhs, rhs, gs, row_group_ids=rgi, use_kernel=False
        ).astype(jnp.float32)
        out = grouped_matmul(
            lhs, rhs, gs, row_group_ids=rgi, interpret=True
        ).astype(jnp.float32)
        assert out.shape == (rows, 384)
        assert not np.asarray(oracle)[real:].any()  # ragged_dot zeroes them
        if real:  # with no real row the call runs and nothing is defined
            scale = float(jnp.max(jnp.abs(oracle))) + 1e-9
            err = float(jnp.max(jnp.abs(out[:real] - oracle[:real]))) / scale
            assert err < 2e-2, err

    def test_int8_requires_row_group_ids(self):
        rng = np.random.default_rng(3)
        lhs, w, gs, _ = _problem(rng, 8, 256, 384, [32] * 8)
        with pytest.raises(ValueError, match="row_group_ids"):
            grouped_matmul(lhs, quantize_tensor(w), gs, interpret=True)

    def test_non_tile_multiple_rows_padding_sliced(self):
        rng = np.random.default_rng(4)
        sizes = [13, 7, 29, 3, 0, 11, 5, 132]  # 200 rows
        lhs, w, gs, rgi = _problem(rng, 8, 256, 128, sizes)
        qw = quantize_tensor(w)
        out = grouped_matmul(lhs, qw, gs, row_group_ids=rgi, interpret=True)
        assert out.shape == (200, 128)
        assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


class TestRoutedDispatchWithKernel:
    """Model-level parity: moe_gmm='kernel' vs 'xla' on the routed paths."""

    def _cfg(self, **kw):
        from dataclasses import replace

        # Kernel-friendly geometry (lane-aligned dims); f32 for tight
        # comparison in interpret mode.
        return replace(
            TINY_MOE,
            hidden_size=128,
            intermediate_size=256,
            n_heads=4,
            n_kv_heads=2,
            **kw,
        )

    def test_routed_kernel_matches_xla(self):
        cfg_x = self._cfg(moe_gmm="xla")
        cfg_k = self._cfg(moe_gmm="kernel")
        params = init_params(jax.random.PRNGKey(0), cfg_x)
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(2, 16, 128)), jnp.float32)
        layer = params["layers"][0]
        out_x = llama._moe_mlp_routed(layer, cfg_x, x, interpret=True)
        out_k = llama._moe_mlp_routed(layer, cfg_k, x, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_x), atol=2e-5, rtol=2e-4
        )

    def test_routed_kernel_int8_close_to_bf16_path(self):
        cfg_k = self._cfg(moe_gmm="kernel")
        params = init_params(
            jax.random.PRNGKey(0), cfg_k, quantize="int8", quantize_experts=True
        )
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.normal(size=(1, 16, 128)), jnp.float32)
        layer = params["layers"][0]
        out_k = llama._moe_mlp_routed(layer, cfg_k, x, interpret=True)
        cfg_x = self._cfg(moe_gmm="xla")
        out_x = llama._moe_mlp_routed(layer, cfg_x, x, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_x), atol=5e-3, rtol=5e-2
        )

    def test_unknown_moe_gmm_rejected(self):
        cfg = self._cfg(moe_gmm="cuda")
        params = init_params(jax.random.PRNGKey(0), cfg)
        x = jnp.zeros((1, 4, 128), jnp.float32)
        with pytest.raises(ValueError, match="moe_gmm"):
            llama._moe_mlp_routed(params["layers"][0], cfg, x)


class TestExpertParallelWithKernel:
    def test_ep_kernel_matches_xla_on_virtual_mesh(self):
        from dataclasses import replace

        from llm_d_kv_cache_manager_tpu.parallel import MeshConfig, make_mesh
        from llm_d_kv_cache_manager_tpu.parallel.sharding import shard_params

        base = replace(
            TINY_MOE,
            hidden_size=128,
            intermediate_size=256,
            n_heads=4,
            n_kv_heads=2,
            n_experts=4,
            n_experts_per_tok=1,  # k*tp < E at tp=2 → routed-EP selected
        )
        mesh = make_mesh(MeshConfig(dp=1, tp=2))
        params = init_params(jax.random.PRNGKey(1), base)
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.float32)
        layer = params["layers"][0]

        outs = {}
        for impl in ("xla", "kernel"):
            cfg = replace(base, moe_gmm=impl)
            sharded = shard_params(params, mesh, cfg)
            outs[impl] = llama._moe_mlp_routed_ep(
                sharded["layers"][0], cfg, x, mesh, interpret=True
            )
        np.testing.assert_allclose(
            np.asarray(outs["kernel"]), np.asarray(outs["xla"]),
            atol=2e-5, rtol=2e-4,
        )
