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
from llm_d_kv_cache_manager_tpu.ops import gmm
from llm_d_kv_cache_manager_tpu.ops.gmm import gmm_tiling, grouped_matmul
from tools.aot_pool_copies import routed_decode_calls

#: sizes whose groups lie across the borders of the row tiles the rule picks
#: for their 320 rows (three of 112) and for 600 rows (five of 128)
STRADDLING = [100, 60, 0, 90, 50, 0, 10, 10]


class TestTheTileRule:
    @pytest.mark.parametrize("rhs_itemsize", [2, 1], ids=["bf16", "int8"])
    @pytest.mark.parametrize(
        "rows, d, f",
        [  # the cells' decode calls, from chipbench/configs
            pytest.param(rows, d, f, id=name)
            for name, rows, _, d, f in routed_decode_calls()
        ]
        + [  # a prefill dispatch's calls: 1 024 to ROUTED_ROW_BLOCK rows
            pytest.param(1024, 2048, 768, id="prefill-1024"),
            pytest.param(llama.ROUTED_ROW_BLOCK, 6144, 2048, id="prefill-block"),
        ],
    )
    def test_the_served_shapes_tiles(self, rows, d, f, rhs_itemsize):
        tm, tk, tn = gmm_tiling(rows, d, f, 2, rhs_itemsize)
        assert d % tk == 0 and f % tn == 0
        assert tk % 128 == 0 and tn % 128 == 0
        assert tm % 16 == 0 and tm <= gmm.MXU_ROWS  # bf16 packs 16 rows a tile
        held = (
            2 * tk * tn * rhs_itemsize  # the weight tile, double buffered
            + 2 * tm * tk * 2  # the lhs tile
            + 2 * tm * tn * 4  # the float32 output tile
            + tm * tn * 4  # the accumulator
        )
        assert held == gmm.tile_bytes(tm, tk, tn, 2, rhs_itemsize)
        assert held <= gmm.VMEM_TILE_BUDGET < 16 * 2**20
        # the whole contraction, and columns as wide as the budget takes
        assert tk == d
        if f % (2 * tn) == 0:
            assert (
                gmm.tile_bytes(tm, tk, 2 * tn, 2, rhs_itemsize)
                > gmm.VMEM_TILE_BUDGET
            )

    def test_the_cells_are_all_read(self):
        assert len(routed_decode_calls()) >= 14  # seven sparse configurations

    def test_a_small_unaligned_shape_is_one_tile(self):
        assert gmm_tiling(36, 200, 72, 4, 4) == (48, 200, 72)

    @pytest.mark.parametrize("rows, tm", [
        (8, 16), (128, 128), (192, 96), (512, 128), (600, 128), (768, 128),
        (130, 80), (4096, 128),
    ])
    def test_the_rows_are_cut_evenly(self, rows, tm):
        assert gmm_tiling(rows, 256, 384, 2, 2)[0] == tm

    @pytest.mark.parametrize("d, f", [(2000, 768), (768, 1100)])
    def test_a_large_unaligned_dimension_is_refused(self, d, f):
        with pytest.raises(ValueError, match="not 128-aligned"):
            gmm_tiling(128, d, f, 2, 2)

    def test_the_parity_cases_cross_a_row_tile(self):
        """What the cases below rely on: at their small shape the rule's
        row tile is shorter than the rows, and a group lies on each border
        (of the 320 rows alone, and of the 600 of which they are the real)."""
        ends = np.cumsum(STRADDLING)
        starts = ends - STRADDLING
        for rows in (sum(STRADDLING), 600):
            tm, tk, tn = gmm_tiling(rows, 256, 384, 2, 2)
            assert (tk, tn) == (256, 384) and 2 * tm < sum(STRADDLING)
            for border in (tm, 2 * tm):
                assert any(s < border < e for s, e in zip(starts, ends))


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
            STRADDLING,  # three row tiles, a group across each border
        ],
    )
    def test_bf16_kernel_matches_ragged_dot(self, sizes):
        rng = np.random.default_rng(1)
        lhs, w, gs, _ = _problem(rng, 8, 256, 384, sizes)
        oracle = jax.lax.ragged_dot(lhs, w, gs).astype(jnp.float32)
        out = grouped_matmul(lhs, w, gs, interpret=True).astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), atol=2e-2)

    @pytest.mark.parametrize(
        "sizes", [[40, 0, 25, 60, 10, 30, 20, 15], STRADDLING]
    )
    def test_int8_kernel_matches_dequant_oracle(self, sizes):
        rng = np.random.default_rng(2)
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
            STRADDLING,  # 320 of 600 rows: the third tile part real
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
