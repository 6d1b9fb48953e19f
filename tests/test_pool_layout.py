"""The KV pool is written in the layout it is kept in, and read where it lies.

Three parts:

- **What the chip's compiler makes of the write** (``TestCompiledForTheChip``):
  ``_scatter_kv_pages_all_layers`` is compiled for a DESCRIBED v5e chip (the
  TPU compiler is installed here and needs no chip: on-chip-measurement guide,
  section 2) and the optimised HLO is read: besides the scatter's own fusion
  no instruction that moves bytes may have the pool's shape. Until PR 29 the scatter's window
  held the layer axis, and with 4 KV heads the compiler copied both whole
  pools into a layer-inward layout and back in every MoE decode step,
  denoising forward and prefill dispatch: 6.5-6.7 ms a step on the chip
  (PERF_LEDGER.jsonl, PR 28: ``copy_bf16_8_4096_16_4_128_``), which no CPU
  test and no docstring could see. Every case loads libtpu, which one process
  at a time may hold: they all stay in this one file (one xdist worker) and
  the topology is described in a fixture, never at import.
- **What it makes of the read** (``TestTheContextIsReadInPlace``): the prefill
  and block-attention kernel's call, as ``_prefill_body`` makes it for one
  layer of the five-dimensional pool, at both cells' shapes and under ``tp``.
  Until PR 31 the caller sliced the pool by layer, gathered every table page
  and copied the gather head-major (``fusion_bf16_1_4096_16_4_128_``,
  ``fusion_bf16_2048_16_4_128_``, ``copy_bitcast_fusion_bf16_16_4_2048_128_``
  and two more: 1.33 s of a 4.26 s window in ``blockgen``, PERF_LEDGER.jsonl,
  PR 30); the kernel now lowers for the chip (Mosaic included) with nothing
  of those shapes beside it.
- **What the write writes** (``TestValues``, CPU): the flat-row scatter
  against the five-dimensional expression it replaced.
"""

import dataclasses
import faulthandler
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from llm_d_kv_cache_manager_tpu.models import llama
from llm_d_kv_cache_manager_tpu.models.llama import _scatter_kv_pages_all_layers
from llm_d_kv_cache_manager_tpu.parallel.sharding import kv_pages_sharding
from tools import aot_pool_copies

#: This file's own limit: a case compiles in under a second, the first pays
#: the compiler's start (a few seconds), a whole served program (the slow
#: cases) takes 20-60 s. What can go wrong is a compile
#: that hangs in native code, where no Python signal handler runs: the
#: watchdog dumps every thread's stack and ends the process (an xdist worker
#: is replaced and the case reported as crashed) instead of letting one case
#: eat tier-1's budget.
_CASE_LIMIT_S = 240


@pytest.fixture(autouse=True)
def _case_limit():
    faulthandler.dump_traceback_later(_CASE_LIMIT_S, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


def _old_scatter(pages, fresh, page_ids, slot_ids, valid):
    """The write as it was until PR 29 (the oracle; layer axis in the
    scatter's window)."""
    L, total_pages, _, n_kv, hd = pages.shape
    pidx = jnp.where(valid.reshape(-1), page_ids.reshape(-1), total_pages)
    return pages.at[:, pidx, slot_ids.reshape(-1)].set(
        fresh.reshape(L, -1, n_kv, hd), mode="drop"
    )


def _whole_pool_moves(hlo, pool_shape):
    """Instructions that produce an array of the pool's shape and are
    neither free nor a fusion around the scatter."""
    inside = aot_pool_copies.fusion_opcodes(hlo)
    return [
        (i.opcode, i.name, i.result)
        for i in aot_pool_copies.pool_instructions(hlo, pool_shape)
        if i.moves_bytes and "scatter" not in inside.get(i.name, ())
    ]


# (pool shape, (b, s) of the write): the cells' pools at the row counts the
# served programs write — 16 (a decode step), 64 (a denoising forward: 16
# lanes x a block of 4), 1024 (a prefill dispatch: 8 x 128).
_MOE_POOL = (8, 4096, 16, 4, 128)  # qwen3-30b-a3b, sdar-30b-a3b
_DENSE_POOL = (5, 8192, 16, 8, 128)  # qwen3-32b
# lfm2-8b-a1b: 3 attention layers of 14; 8 KV heads of 64, two a 128-lane row
_HYBRID_POOL = (3, 16384, 16, 4, 128)
_STATE_POOL = (11, 16384, 2 * 2048)  # its 11 convolution layers' slots
_CASES = [
    pytest.param(_MOE_POOL, (16, 1), 1, id="kv4-decode16"),
    pytest.param(_MOE_POOL, (16, 4), 1, id="kv4-block64"),
    pytest.param(_MOE_POOL, (8, 128), 1, id="kv4-prefill1024"),
    pytest.param(_DENSE_POOL, (16, 1), 1, id="kv8-decode16"),
    pytest.param(_DENSE_POOL, (8, 128), 1, id="kv8-prefill1024"),
    pytest.param(_HYBRID_POOL, (32, 1), 1, id="kv8x64-decode32"),
    pytest.param(_HYBRID_POOL, (8, 128), 1, id="kv8x64-prefill1024"),
    # A tp=4 slice: the pool is sharded on the KV-head axis, which the flat
    # view leaves alone (it merges the three replicated leading axes).
    pytest.param(_MOE_POOL, (16, 1), 4, id="kv4-tp4-decode16"),
    pytest.param(_DENSE_POOL, (8, 128), 4, id="kv8-tp4-prefill1024"),
]


@pytest.fixture(scope="module")
def topo():
    try:
        return aot_pool_copies.describe_v5e()
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


class TestCompiledForTheChip:
    @pytest.mark.parametrize("pool_shape, write, tp", _CASES)
    def test_only_the_scatter_touches_the_pool(self, topo, pool_shape, write, tp):
        mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, tp), ("dp", "tp"))
        pool_sharding = kv_pages_sharding(mesh)
        replicated = NamedSharding(mesh, P())
        L, _, _, n_kv, hd = pool_shape
        b, s = write

        def shaped(shape, dtype, sharding=replicated):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        hlo = aot_pool_copies.compile_text(
            jax.jit(
                _scatter_kv_pages_all_layers,
                donate_argnums=0,
                out_shardings=pool_sharding,
            ),
            shaped(pool_shape, jnp.bfloat16, pool_sharding),
            shaped((L, b, s, n_kv, hd), jnp.bfloat16, pool_sharding),
            shaped(write, jnp.int32),
            shaped(write, jnp.int32),
            shaped(write, jnp.bool_),
        )
        per_device = (*pool_shape[:3], n_kv // tp, hd)
        found = aot_pool_copies.pool_instructions(hlo, per_device)
        assert found, "the pool is nowhere in the module: the reader is blind"
        # Nothing but the scatter's own fusion (in place: its operand is the
        # donated pool) may produce a pool; where the compiler keeps the flat
        # view inside that fusion, nothing at all does.
        assert _whole_pool_moves(hlo, per_device) == [], (
            "the compiled write moves the whole pool besides scattering into it"
        )
        inside = aot_pool_copies.fusion_opcodes(hlo)
        everything = list(aot_pool_copies.instructions(hlo))
        scatters = [
            name for _, name, op, _ in everything
            if op == "scatter" or "scatter" in inside.get(name, ())
        ]
        assert len(scatters) == 1, scatters
        collectives = ("all-gather", "all-reduce", "all-to-all", "collective-permute")
        assert not [name for _, name, op, _ in everything if op.startswith(collectives)]


def _elements(result):
    """The most elements any array of a printed result type holds (a tuple's
    members one by one)."""
    return max(
        (int(np.prod([int(n) for n in dims.split(",")]))
         for dims in re.findall(r"\w+\[([\d,]+)\]", result)),
        default=0,
    )


# (pool shape, (lanes, query rows), table pages, block length, tp): the two
# cells' calls — `blockgen`'s forward, 16 lanes x one block of 4 rows at 4 KV
# heads over a table of 128 pages, and `sessions`' prefill dispatch, 8 rows x
# a 128-token chunk at 8 KV heads over a table of 256 pages — and each under
# a `tp` mesh that leaves a shard two KV heads.
_READS = [
    pytest.param(_MOE_POOL, (16, 4), 128, 4, 1, id="kv4-block4x16"),
    pytest.param(_DENSE_POOL, (8, 128), 256, 0, 1, id="kv8-chunk128x8"),
    pytest.param(_MOE_POOL, (16, 4), 128, 4, 2, id="kv4-tp2-block4x16"),
    pytest.param(_DENSE_POOL, (8, 128), 256, 0, 4, id="kv8-tp4-chunk128x8"),
]


class TestTheContextIsReadInPlace:
    def _compile(self, topo, pool_shape, rows, table_pages, block_length, tp):
        mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, tp), ("dp", "tp"))
        replicated = NamedSharding(mesh, P())
        heads = NamedSharding(mesh, P(None, None, "tp"))
        L, _, _, n_kv, hd = pool_shape
        b, s = rows

        def shaped(shape, dtype, sharding=replicated):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        def one_layer(q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid):
            # the call `_prefill_body` makes for its last layer
            return llama._flash_prefill_tp(
                q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid,
                layer=L - 1, interpret=False, mesh=mesh if tp > 1 else None,
                block_length=block_length,
            )

        pool = shaped(pool_shape, jnp.bfloat16, kv_pages_sharding(mesh))
        return aot_pool_copies.compile_text(
            jax.jit(one_layer),
            shaped((b, s, 8 * n_kv, hd), jnp.bfloat16, heads),
            shaped((b, s, n_kv, hd), jnp.bfloat16, heads),
            shaped((b, s, n_kv, hd), jnp.bfloat16, heads),
            pool, pool,
            shaped((b, table_pages), jnp.int32),
            shaped((b,), jnp.int32), shaped((b,), jnp.int32),
        )

    @pytest.mark.parametrize("pool_shape, rows, table_pages, block_length, tp", _READS)
    def test_no_slice_of_the_pool_and_no_gathered_context(
        self, topo, pool_shape, rows, table_pages, block_length, tp
    ):
        hlo = self._compile(topo, pool_shape, rows, table_pages, block_length, tp)
        _, total_pages, page, n_kv, hd = pool_shape
        per_device = (*pool_shape[:3], n_kv // tp, hd)
        found = aot_pool_copies.pool_instructions(hlo, per_device, layer_slices=True)
        assert found, "the pool is nowhere in the module: the reader is blind"
        # The pool comes in as a parameter and goes to the kernel as it is:
        # nothing shaped like it, or like one layer of it, moves bytes.
        assert [(i.opcode, i.name, i.result) for i in found if i.moves_bytes] == []
        # A context gathered for the kernel (all of a row's table pages, in
        # whatever order of axes) is the largest array such a program could
        # make, and the smallest that is too large: the queries, the fresh
        # keys and values and the output are 4 to 64 times smaller.
        gathered = rows[0] * table_pages * page * (n_kv // tp) * hd
        everything = list(aot_pool_copies.instructions(hlo))
        large = [
            (op, name, result) for _, name, op, result in everything
            if op not in aot_pool_copies.FREE and _elements(result) >= gathered
        ]
        assert large == [], "something of a gathered context's size is made"
        kernels = [name for _, name, op, _ in everything if op == "custom-call"]
        assert len(kernels) == 1, kernels
        collectives = ("all-gather", "all-reduce", "all-to-all", "collective-permute")
        assert not [name for _, name, op, _ in everything if op.startswith(collectives)]

    def test_one_kv_head_a_shard_is_refused_by_name(self, topo):
        # tp = n_kv_heads: a 16-bit pool is tiled two rows deep over an axis
        # of one and Mosaic cuts no page tile out of it. The wrapper says so
        # instead of Mosaic, and the engine's rule takes the XLA prefill.
        with pytest.raises(NotImplementedError, match="one KV head a shard"):
            self._compile(topo, _MOE_POOL, (16, 4), 128, 4, 4)


_LATENT_POOL = (8, 16384, 16, 640)  # kanana-2-30b-a3b: one row a token


class TestTheLatentPool:
    """A latent model's one pool ``[L, P, page, row]`` (PR 32): the write
    through the same flat-row scatter, the ``mla_decode`` / ``mla_prefill``
    kernel's call as ``_mla_absorbed`` makes it, at the cell's shapes (32
    lanes over a table of 2048 pages; 8 rows x a 128-token question over
    1792), and the layout the compiler gives the pool."""

    @pytest.mark.parametrize("write", [(32, 1), (8, 128)], ids=["decode32", "prefill1024"])
    def test_only_the_scatter_touches_the_pool(self, topo, write):
        one_chip = SingleDeviceSharding(topo.devices[0])
        L, row = _LATENT_POOL[0], _LATENT_POOL[3]

        def shaped(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        hlo = aot_pool_copies.compile_text(
            jax.jit(_scatter_kv_pages_all_layers, donate_argnums=0),
            shaped(_LATENT_POOL, jnp.bfloat16),
            shaped((L, *write, row), jnp.bfloat16),
            shaped(write, jnp.int32), shaped(write, jnp.int32),
            shaped(write, jnp.bool_),
        )
        assert aot_pool_copies.pool_instructions(hlo, _LATENT_POOL)
        assert _whole_pool_moves(hlo, _LATENT_POOL) == []
        # 576 values are held in 640: the array says so itself, so that
        # ``nbytes`` is what the device holds and Mosaic can cut page tiles
        layout = aot_pool_copies.pool_layout(hlo, _LATENT_POOL)
        assert layout.startswith("bf16[8,16384,16,640]{3,2,1,0:T(8,128)(2,1)"), layout

    @pytest.mark.parametrize("rows, table_pages, kernel", [
        ((32, 1), 2048, "mla_decode"), ((8, 128), 1792, "mla_prefill"),
    ], ids=["decode", "question"])
    def test_the_kernel_reads_the_pool_in_place(self, topo, rows, table_pages, kernel):
        one_chip = SingleDeviceSharding(topo.devices[0])
        cfg = llama.KANANA_2_30B_A3B
        b, s = rows

        def shaped(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def one_layer(wkv_b, q_n, q_r, row, pool, block_tables, ctx_lens, n_valid):
            return llama._mla_absorbed(
                {"wkv_b": wkv_b}, cfg, q_n, q_r, row, pool, block_tables,
                ctx_lens, n_valid, layer_index=7, interpret=False,
            )

        hlo = aot_pool_copies.compile_text(
            jax.jit(one_layer),
            shaped((512, 32 * 256)), shaped((b, s, 32, 128)),
            shaped((b, s, 32, 64)), shaped((b, s, 640)), shaped(_LATENT_POOL),
            shaped((b, table_pages), jnp.int32), shaped((b,), jnp.int32),
            shaped((b,), jnp.int32),
        )
        found = aot_pool_copies.pool_instructions(hlo, _LATENT_POOL, layer_slices=True)
        assert found
        assert [(i.opcode, i.name, i.result) for i in found if i.moves_bytes] == []
        everything = list(aot_pool_copies.instructions(hlo))
        # nothing the size of a gathered context (rows x table x page x row)
        gathered = b * table_pages * 16 * 640
        assert [
            (op, name) for _, name, op, result in everything
            if op not in aot_pool_copies.FREE and _elements(result) >= gathered
        ] == []
        calls = [name for _, name, op, _ in everything if op == "custom-call"]
        assert len([name for name in calls if kernel in name]) == 1, calls

    def test_a_row_of_576_values_has_no_page_tile(self, topo):
        # what the pool's width of 640 is for: the compiler holds 576 as 640
        # and Mosaic cuts no page tile out of the padded minor dimension
        from llm_d_kv_cache_manager_tpu.ops.mla_attention import mla_paged_attention

        one_chip = SingleDeviceSharding(topo.devices[0])

        def shaped(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def call(q, fresh, pool, block_tables, ctx_lens, n_valid):
            return mla_paged_attention(
                q, fresh, pool, block_tables, ctx_lens, n_valid, dv=512,
                scale=0.07, layer=jnp.int32(0),
            )

        with pytest.raises(Exception, match="aligned to tiling"):
            aot_pool_copies.compile_text(
                jax.jit(call), shaped((32, 1, 32, 576)), shaped((32, 1, 576)),
                shaped((8, 1024, 16, 576)), shaped((32, 64), jnp.int32),
                shaped((32,), jnp.int32), shaped((32,), jnp.int32),
            )


class TestTheStatePoolAndTheRowOfTwoHeads:
    """A model with convolution layers (PR 34): heads of 64 lie two a
    128-lane row in the key/value pools, so that the compiler pads nothing
    (``LlamaConfig.kv_row_shape``), and the convolution layers' state lies a
    page a row in a pool of its own, read and written as flat slots."""

    def test_a_row_of_two_heads_is_held_unpadded(self, topo):
        one_chip = SingleDeviceSharding(topo.devices[0])
        cfg = llama.LFM2_8B_A1B
        assert cfg.kv_row_shape == _HYBRID_POOL[3:]

        def shaped(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        write = (32, 1)
        hlo = aot_pool_copies.compile_text(
            jax.jit(_scatter_kv_pages_all_layers, donate_argnums=0),
            shaped(_HYBRID_POOL, jnp.bfloat16),
            shaped((3, *write, *cfg.kv_row_shape), jnp.bfloat16),
            shaped(write, jnp.int32), shaped(write, jnp.int32),
            shaped(write, jnp.bool_),
        )
        layout = aot_pool_copies.pool_layout(hlo, _HYBRID_POOL)
        assert layout.startswith("bf16[3,16384,16,4,128]{4,3,2,1,0:T(4,128)(2,1)"), layout
        # the same heads a row each would be held in twice the bytes: the
        # minor dimension of 64 is laid out on 128 lanes
        padded = (3, 16384, 16, 8, 64)
        hlo = aot_pool_copies.compile_text(
            jax.jit(_scatter_kv_pages_all_layers, donate_argnums=0),
            shaped(padded, jnp.bfloat16), shaped((3, *write, 8, 64), jnp.bfloat16),
            shaped(write, jnp.int32), shaped(write, jnp.int32),
            shaped(write, jnp.bool_),
        )
        assert "T(8,128)" in aot_pool_copies.pool_layout(hlo, padded)

    @pytest.mark.parametrize("touched", [(32, 1), (8, 9)], ids=["decode32", "prefill8x9pages"])
    def test_the_state_is_read_and_written_as_flat_slots(self, topo, touched):
        one_chip = SingleDeviceSharding(topo.devices[0])
        cfg = dataclasses.replace(llama.LFM2_8B_A1B, n_layers=14)
        assert jax.eval_shape(
            lambda: llama.init_state_pages(cfg, 16384)).shape == _STATE_POOL

        def shaped(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def step(state, prev_page, has_prev, fresh, page, ok):
            before = llama._conv_prev_state(state, cfg, prev_page, has_prev)
            return before, llama._scatter_state_pages(state, fresh, page, ok)

        b = touched[0]
        hlo = aot_pool_copies.compile_text(
            jax.jit(step, donate_argnums=0),
            shaped(_STATE_POOL, jnp.bfloat16), shaped((b,), jnp.int32),
            shaped((b,), jnp.bool_),
            shaped((11, *touched, _STATE_POOL[2]), jnp.bfloat16),
            shaped(touched, jnp.int32), shaped(touched, jnp.bool_),
        )
        assert aot_pool_copies.pool_instructions(hlo, _STATE_POOL)
        # (with the layer axis in the gather's window the compiler copied
        # the whole pool into a layer-inward layout before every read)
        assert _whole_pool_moves(hlo, _STATE_POOL) == []
        layout = aot_pool_copies.pool_layout(hlo, _STATE_POOL)
        assert layout.startswith("bf16[11,16384,4096]{2,1,0:T(8,128)(2,1)"), layout


_WINDOW_POOL = (4, 8192, 16, 8, 128)  # trinity-large-preview: four sliding layers


class TestTheWindowIsWalkedInPlace:
    """A sliding layer's decode call (PR 44) as ``_paged_attention_tp`` makes
    it at `longdocs`' shape: 32 lanes, a group of 6 over 8 KV heads, a
    259-page window table, the pools five-dimensional. One kernel, named for
    the window, that takes the pools as they are."""

    def test_no_slice_of_the_pool_and_no_gathered_window(self, topo):
        one_chip = SingleDeviceSharding(topo.devices[0])
        L, _, page, n_kv, hd = _WINDOW_POOL
        lanes, table_pages, window = 32, 259, 4096

        def shaped(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def one_layer(q, k_pages, v_pages, tables, lens, starts, k, v):
            return llama._paged_attention_tp(
                q, k_pages, v_pages, tables, lens, k, v, interpret=False,
                mesh=None, layer=L - 1, scale=hd**-0.5, window=window,
                table_start=starts,
            )

        hlo = aot_pool_copies.compile_text(
            jax.jit(one_layer),
            shaped((lanes, 6 * n_kv, hd)),
            shaped(_WINDOW_POOL), shaped(_WINDOW_POOL),
            shaped((lanes, table_pages), jnp.int32),
            shaped((lanes,), jnp.int32), shaped((lanes,), jnp.int32),
            shaped((lanes, n_kv, hd)), shaped((lanes, n_kv, hd)),
        )
        found = aot_pool_copies.pool_instructions(hlo, _WINDOW_POOL, layer_slices=True)
        assert found, "the pool is nowhere in the module: the reader is blind"
        assert [(i.opcode, i.name, i.result) for i in found if i.moves_bytes] == []
        gathered = lanes * window * n_kv * hd  # a lane's window, gathered
        everything = list(aot_pool_copies.instructions(hlo))
        assert [
            (op, name, result) for _, name, op, result in everything
            if op not in aot_pool_copies.FREE and _elements(result) >= gathered
        ] == []
        kernels = [name for _, name, op, _ in everything if op == "custom-call"]
        assert len(kernels) == 1, kernels
        assert "paged_attention_window" in hlo


_SERVED = [
    ("kanana-2-30b-a3b", "decode_steps"),
    ("kanana-2-30b-a3b", "prefill"),
    ("lfm2-8b-a1b", "decode_steps"),
    ("lfm2-8b-a1b", "prefill"),
    ("longcat-flash-omni", "decode_steps"),
    ("longcat-flash-omni", "prefill"),
    ("qwen3-30b-a3b", "decode_steps"),
    ("qwen3-30b-a3b", "prefill"),
    ("qwen3-32b", "decode_steps"),
    ("qwen3-32b", "prefill"),
    ("ling-3.0-flash", "decode_steps"),
    ("ling-3.0-flash", "prefill"),
    ("sdar-30b-a3b", "denoise_steps"),
    ("sdar-30b-a3b", "prefill"),
    ("trinity-large-preview", "decode_steps"),
    ("trinity-large-preview", "prefill"),
]


class TestServedPrograms:
    """The whole served programs at the cells' shapes, as
    ``python -m tools.aot_pool_copies`` compiles them: inside a whole
    program another consumer can ask for another layout than a helper or a
    kernel alone gets. Nothing shaped like the pool, or like one layer of
    it, may move bytes (until PR 31 ``prefill`` and ``denoise_steps`` sliced
    both pools by layer for the prefill kernel)."""

    @pytest.mark.parametrize("config, program", _SERVED)
    def test_no_copy_of_the_pool_or_of_a_layer_of_it(self, topo, config, program):
        one_chip = SingleDeviceSharding(topo.devices[0])
        fn, args, kwargs, pool_shape = aot_pool_copies.served_program(
            config, program, one_chip
        )
        hlo = aot_pool_copies.compile_text(fn, *args, **kwargs)
        found = aot_pool_copies.pool_instructions(hlo, pool_shape, layer_slices=True)
        assert found
        inside = aot_pool_copies.fusion_opcodes(hlo)
        assert [
            (i.opcode, i.name, i.result) for i in found
            if i.moves_bytes and "scatter" not in inside.get(i.name, ())
        ] == []
        state_shape = aot_pool_copies.state_pool_shape(kwargs)
        assert (state_shape is not None) == (
            config in ("lfm2-8b-a1b", "ling-3.0-flash"))
        if state_shape:  # the convolution layers' state pool beside them
            assert aot_pool_copies.pool_instructions(hlo, state_shape)
            assert _whole_pool_moves(hlo, state_shape) == []
        rows_shape = aot_pool_copies.state_rows_shape(kwargs)
        assert (rows_shape is not None) == (config == "ling-3.0-flash")
        if rows_shape:  # the linear layers' carried rows beside the matrices
            assert aot_pool_copies.pool_instructions(hlo, rows_shape)
            assert _whole_pool_moves(hlo, rows_shape) == []
        window_shape = aot_pool_copies.window_pool_shape(kwargs)
        assert (window_shape is not None) == (config == "trinity-large-preview")
        if window_shape:  # the sliding layers' window pools beside them
            assert aot_pool_copies.pool_instructions(hlo, window_shape)
            assert _whole_pool_moves(hlo, window_shape) == []
        # the kernel that walks a window is in a model with sliding layers'
        # decode program and in no other
        assert ("paged_attention_window" in hlo) == (
            (config, program) == ("trinity-large-preview", "decode_steps"))

    def test_a_program_the_configuration_does_not_serve(self, topo):
        one_chip = SingleDeviceSharding(topo.devices[0])
        assert aot_pool_copies.served_program("qwen3-32b", "denoise_steps", one_chip) is None
        assert aot_pool_copies.served_program("sdar-30b-a3b", "decode_steps", one_chip) is None


class TestTheGroupedMatmulsTiles:
    """``ops/gmm.py::gmm_tiling`` sizes a visit's tiles against the 16 MiB of
    scoped VMEM a Pallas call has on a v5e by its own arithmetic; the
    compiler's count is the one that refuses a program. Both kernels at the
    decode shapes of the seven sparse cells, under a second each."""

    #: {(rows, experts, d, f, int8): the compiled text}: two cells whose
    #: calls have one shape (a square ``[3072, 3072]`` up and down) are one
    #: lowering
    compiled = {}

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("rows, experts, d, f", [
        pytest.param(*call[1:], id=call[0])
        for call in aot_pool_copies.routed_decode_calls()
    ])
    def test_the_rules_tiles_compile_for_the_chip(
        self, topo, rows, experts, d, f, int8
    ):
        from llm_d_kv_cache_manager_tpu.models.quant import QuantizedTensor
        from llm_d_kv_cache_manager_tpu.ops.gmm import grouped_matmul

        one_chip = SingleDeviceSharding(topo.devices[0])

        def S(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def call(lhs, w, scale, sizes, ids):
            rhs = QuantizedTensor(w, scale) if int8 else w
            return grouped_matmul(lhs, rhs, sizes, row_group_ids=ids)

        shape = (rows, experts, d, f, int8)
        if shape not in self.compiled:
            self.compiled[shape] = aot_pool_copies.compile_text(
                jax.jit(call),
                S((rows, d), jnp.bfloat16),
                S((experts, d, f), jnp.int8 if int8 else jnp.bfloat16),
                S((experts, 1, f), jnp.float32),
                S((experts,), jnp.int32),
                S((rows,), jnp.int32),
            )
        hlo = self.compiled[shape]
        assert "tpu_custom_call" in hlo
        if not int8:  # the name the benchmark's readers find the kernel by
            assert re.search(r"%gmm[.\d]* = f32\[", hlo)


class TestThePrefillLoopReadsThePools:
    """``prefill_packed`` holds its forward for one row, in a loop over the
    rows that hold a sequence; the loop reads the pools and the one write
    comes after it (branches of a conditional that RETURNED a pool copied
    it whole on the way in and on the way out: the TPU compiler, PR 42).
    The slow cases above hold that for the cells' programs at their depth;
    these are four of them cut to the fewest layers that keep every kind of
    POOL (a K/V pool; a state pool beside one: a convolution over the dense
    FFN and an attention over the routed; a latent pool, twice a double
    layer; a pair of window pools beside one: a sliding layer and a full
    one), 7-28 s each. The assertions are about a kind of layer's pool, not
    about depth."""

    @pytest.mark.parametrize("config, replace", [
        ("qwen3-32b", dict(n_layers=1)),
        ("lfm2-8b-a1b", dict(
            n_layers=2, first_k_dense=1,
            layer_types=("conv", "full_attention"))),
        ("longcat-flash-omni", dict(n_layers=1)),
        ("trinity-large-preview", dict(
            n_layers=2, layer_types=("sliding_attention", "full_attention"))),
    ], ids=["qwen3-32b", "lfm2-8b-a1b", "longcat-flash-omni",
            "trinity-large-preview"])
    def test_the_loop_copies_no_pool(self, topo, config, replace):
        one_chip = SingleDeviceSharding(topo.devices[0])
        fn, args, kwargs, pool_shape = aot_pool_copies.served_program(
            config, "prefill", one_chip, **replace
        )
        hlo = aot_pool_copies.compile_text(fn, *args, **kwargs)
        assert " while(" in hlo  # the rows' loop is there to be read
        assert aot_pool_copies.pool_instructions(hlo, pool_shape)
        assert _whole_pool_moves(hlo, pool_shape) == []
        state_shape = aot_pool_copies.state_pool_shape(kwargs)
        assert (state_shape is not None) == (config == "lfm2-8b-a1b")
        if state_shape:
            assert _whole_pool_moves(hlo, state_shape) == []
        window_shape = aot_pool_copies.window_pool_shape(kwargs)
        assert (window_shape is not None) == (config == "trinity-large-preview")
        if window_shape:
            assert aot_pool_copies.pool_instructions(hlo, window_shape)
            assert _whole_pool_moves(hlo, window_shape) == []


def test_the_rows_of_a_dispatch_add_no_program():
    """Dispatches of 1, 2 and 5 sequences of one (chunk, context) shape run
    ONE compiled ``prefill_packed``: how many rows it computes is decided
    inside it, on the device, so the set of programs a warm-up loads is
    what it was."""
    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig, Engine, EngineConfig, SamplingParams,
        SchedulerConfig,
    )

    eng = Engine(EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(total_pages=64, page_size=4),
        scheduler=SchedulerConfig(max_prefill_batch=8), max_model_len=64,
        decode_batch_size=8, prefill_bucket=8, interpret=True,
    ))
    llama.prefill_packed.clear_cache()
    rng = np.random.default_rng(9)
    sizes = []
    # the first two settle the jit's own entries (fresh pools, then a
    # program's results: two entries of ONE compiled program, as before)
    for n_seqs, rows in ((1, 1), (1, 1), (2, 2), (5, 5), (1, 1)):
        before = dict(eng.prefill_stats)
        for _ in range(n_seqs):
            eng.add_request(
                list(map(int, rng.integers(1, 256, 7))),
                SamplingParams(max_new_tokens=1),
            )
        eng.run_until_complete()
        assert eng.prefill_stats["dispatches"] == before["dispatches"] + 1
        assert eng.prefill_stats["token_slots"] == before["token_slots"] + 8 * rows
        sizes.append(llama.prefill_packed._cache_size())
    assert sizes[0] == 1 and sizes[1:] == [sizes[1]] * 4, sizes


_RECORDED = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[2,8,4,2,128], param_1: s32[6]) -> bf16[64,2,128] {
  %param_0 = bf16[2,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)} parameter(0)
  %bitcast.1 = bf16[64,2,128]{2,1,0:T(2,128)(2,1)} bitcast(%param_0)
  ROOT %scatter.1 = bf16[64,2,128]{2,1,0:T(2,128)(2,1)} scatter(%bitcast.1, %param_1), to_apply=%assign
}

%fused_computation.2 (param_0.1: bf16[2,8,4,2,128]) -> (bf16[1,8,4,2,128], bf16[1,8,4,2,128]) {
  %param_0.1 = bf16[2,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)} parameter(0)
  %slice.1 = bf16[1,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)} slice(%param_0.1), slice={[0:1], [0:8], [0:4], [0:2], [0:128]}
  %slice.2 = bf16[1,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)} slice(%param_0.1), slice={[1:2], [0:8], [0:4], [0:2], [0:128]}
  ROOT %tuple.1 = (bf16[1,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)}, bf16[1,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)}) tuple(%slice.1, %slice.2)
}

ENTRY %main.9 (k_pages.1: bf16[2,8,4,2,128], rows.1: s32[6]) -> (f32[3], bf16[2,8,4,2,128]) {
  %k_pages.1 = bf16[2,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)} parameter(0)
  %rows.1 = s32[6]{0:T(128)} parameter(1)
  %copy.7 = bf16[2,8,4,2,128]{4,0,3,2,1:T(8,128)(2,1)} copy(bf16[2,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)} %k_pages.1)
  %fusion.2 = (bf16[1,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)}, /*index=1*/bf16[1,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)S(1)}) fusion(%k_pages.1), kind=kLoop, calls=%fused_computation.2
  %fusion.1 = bf16[64,2,128]{2,1,0:T(2,128)(2,1)} fusion(%k_pages.1, %rows.1), kind=kInput, calls=%fused_computation.1
  %bitcast.4 = bf16[2,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)} bitcast(%fusion.1)
  %logits.1 = f32[3]{0:T(128)} constant({0, 0, 0})
  ROOT %tuple.9 = (f32[3]{0:T(128)}, bf16[2,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)}) tuple(%logits.1, %bitcast.4)
}
"""


class TestTheStatePoolOfSlots:
    """A model with linear-attention layers (``ling-3.0-flash`` cut to a
    linear and a latent layer): the decode program's kernel updates the
    lanes' matrices where they lie in the pool (``kda_decode``,
    ``input_output_aliases``: its custom call's result is the pool and the
    one thing that may produce it), the carried rows and a prefill's final
    states go in by ONE scatter fusion each, and nothing copies either pool
    or the latent pool beside them. A slot of the rows' pool is whole tiles
    that lie together (``[.., 288, 128]``, ``LlamaConfig.kda_conv_tile``):
    until PR 48 the pool was ``[.., slots, 36864]``, a slot one sublane of
    288 tiles, and the compiler wrote a step's rows in a ``while`` of one
    ``dynamic-update-slice`` a (layer, lane), a fifth of the decode step
    (PERF_LEDGER.jsonl, PR 47: ``dynamic-update-slice_bf16_2736_36864_``;
    PERF.md section 6, PR 48)."""

    @pytest.mark.parametrize("program", ["decode_steps", "prefill"])
    def test_the_pools_are_updated_where_they_lie(self, topo, program):
        one_chip = SingleDeviceSharding(topo.devices[0])
        fn, args, kwargs, pool_shape = aot_pool_copies.served_program(
            "ling-3.0-flash", program, one_chip, n_layers=2,
            layer_types=("linear_attention", "full_attention"),
        )
        hlo = aot_pool_copies.compile_text(fn, *args, **kwargs)
        matrices = aot_pool_copies.state_pool_shape(kwargs)
        rows = aot_pool_copies.state_rows_shape(kwargs)
        assert matrices == (1, 456, 32, 128, 128) and rows == (1, 456, 288, 128)
        for shape in (pool_shape, matrices, rows):
            assert aot_pool_copies.pool_instructions(hlo, shape)
            assert _whole_pool_moves(hlo, shape) == []
        kernels = [
            i for i in aot_pool_copies.pool_instructions(hlo, matrices)
            if i.opcode == "custom-call"
        ]
        # one call a linear layer in a decode step; a prefill has none (its
        # recurrence is plain matrix products, its write one scatter)
        assert len(kernels) == (program == "decode_steps")
        assert all(i.name.startswith("kda_decode") and not i.moves_bytes
                   for i in kernels)
        # what writes the rows' pool, or a layer of it: one fusion around a
        # scatter, in no loop's body, and no single-row update anywhere
        inside = aot_pool_copies.fusion_opcodes(hlo)
        writes = [
            i for i in
            aot_pool_copies.pool_instructions(hlo, rows, layer_slices=True)
            if {"scatter", "dynamic-update-slice"}
            & ({i.opcode} | inside.get(i.name, set()))
        ]
        assert [i.opcode for i in writes] == ["fusion"], writes
        assert "scatter" in inside[writes[0].name]
        assert writes[0].computation not in re.findall(
            r"\bbody=%?([\w.\-]+)", hlo)


class TestTheReader:
    """``tools/aot_pool_copies``' reading of optimised HLO text, on a small
    recorded module (no compiler): fusion bodies are skipped, tuple results
    are searched member by member, free opcodes are told from those that
    move bytes."""

    POOL = (2, 8, 4, 2, 128)

    def test_whole_pools(self):
        found = aot_pool_copies.pool_instructions(_RECORDED, self.POOL)
        assert [(i.name, i.opcode, i.moves_bytes) for i in found] == [
            ("k_pages.1", "parameter", False),
            ("copy.7", "copy", True),
            ("bitcast.4", "bitcast", False),
            ("tuple.9", "tuple", False),
        ]
        assert all(i.computation == "main.9" for i in found)
        assert found[1].result == "bf16[2,8,4,2,128]{4,0,3,2,1:T(8,128)(2,1)}"

    def test_layer_slices_inside_a_tuple_result(self):
        found = aot_pool_copies.pool_instructions(
            _RECORDED, self.POOL, layer_slices=True
        )
        sliced = [i for i in found if i.name == "fusion.2"]
        assert len(sliced) == 1 and sliced[0].moves_bytes
        assert aot_pool_copies.tuple_members(sliced[0].result) == (
            "1 x bf16[1,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)}, "
            "1 x bf16[1,8,4,2,128]{4,3,2,1,0:T(2,128)(2,1)S(1)}"
        )

    def test_what_a_fusion_holds(self):
        inside = aot_pool_copies.fusion_opcodes(_RECORDED)
        assert inside == {
            "fusion.2": {"parameter", "slice", "tuple"},
            "fusion.1": {"parameter", "bitcast", "scatter"},
        }
        assert _whole_pool_moves(_RECORDED, self.POOL) == [
            ("copy", "copy.7", "bf16[2,8,4,2,128]{4,0,3,2,1:T(8,128)(2,1)}")
        ]


def _random_write(rng, pool_shape, b, s, dtype=jnp.float32):
    L, total_pages, page_size, n_kv, hd = pool_shape
    pages = jnp.asarray(rng.standard_normal(pool_shape), dtype)
    fresh = jnp.asarray(rng.standard_normal((L, b, s, n_kv, hd)), dtype)
    # Distinct (page, slot) per token: a scatter with duplicate rows may keep
    # either, in both forms.
    token_rows = rng.choice(total_pages * page_size, size=b * s, replace=False)
    page_ids = (token_rows // page_size).reshape(b, s).astype(np.int32)
    slot_ids = (token_rows % page_size).reshape(b, s).astype(np.int32)
    return pages, fresh, page_ids, slot_ids


class TestValues:
    POOL = (3, 12, 4, 2, 8)

    @pytest.mark.parametrize(
        "b, s, n_valid",
        [
            pytest.param(5, 1, [1, 1, 0, 1, 0], id="decode"),
            pytest.param(3, 8, [8, 5, 0], id="prefill-right-padded"),
            pytest.param(4, 4, [4, 0, 4, 0], id="block-inactive-lanes"),
        ],
    )
    def test_matches_the_five_dimensional_scatter(self, b, s, n_valid):
        rng = np.random.default_rng(b * 100 + s)
        pages, fresh, page_ids, slot_ids = _random_write(rng, self.POOL, b, s)
        valid = np.arange(s)[None, :] < np.asarray(n_valid)[:, None]
        # Pad rows point at real places (page 0 is the engine's padding
        # page): only ``valid`` may keep them out.
        got = _scatter_kv_pages_all_layers(pages, fresh, page_ids, slot_ids, valid)
        want = _old_scatter(pages, fresh, page_ids, slot_ids, valid)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert got.dtype == pages.dtype and got.shape == pages.shape
        # And it wrote what it should, where it should, in every layer.
        bi, si = np.nonzero(valid)
        np.testing.assert_array_equal(
            np.asarray(got)[:, page_ids[bi, si], slot_ids[bi, si]],
            np.asarray(fresh)[:, bi, si],
        )

    def test_a_page_is_written_in_every_layer_with_that_layers_rows(self):
        # The same page and slot ids serve all layers; a row index that lost
        # its layer would write every layer's rows into one.
        L, total_pages, page_size, n_kv, hd = self.POOL
        pages = jnp.zeros(self.POOL, jnp.float32)
        fresh = jnp.broadcast_to(
            jnp.arange(1, L + 1, dtype=jnp.float32)[:, None, None, None, None],
            (L, 2, 1, n_kv, hd),
        )
        page_ids = np.array([[7], [7]], np.int32)
        slot_ids = np.array([[0], [3]], np.int32)
        got = np.asarray(_scatter_kv_pages_all_layers(
            pages, fresh, page_ids, slot_ids, np.ones((2, 1), bool)))
        for layer in range(L):
            assert (got[layer, 7, [0, 3]] == layer + 1).all()
            assert got[layer].sum() == (layer + 1) * 2 * n_kv * hd

    def test_the_last_row_of_the_pool_and_the_sentinel_past_it(self):
        L, total_pages, page_size, n_kv, hd = self.POOL
        rng = np.random.default_rng(7)
        pages, fresh, _, _ = _random_write(rng, self.POOL, 2, 1)
        last = np.array([[total_pages - 1], [total_pages - 1]], np.int32)
        slots = np.array([[page_size - 1], [page_size - 2]], np.int32)
        # Lane 0 writes the pool's very last row; lane 1 is invalid, so it
        # goes to the sentinel: it may land nowhere, least of all there.
        valid = np.array([[True], [False]])
        got = np.asarray(_scatter_kv_pages_all_layers(pages, fresh, last, slots, valid))
        want = np.asarray(pages).copy()
        want[:, -1, -1] = np.asarray(fresh)[:, 0, 0]
        np.testing.assert_array_equal(got, want)

    def test_nothing_valid_writes_nothing(self):
        rng = np.random.default_rng(11)
        pages, fresh, page_ids, slot_ids = _random_write(rng, self.POOL, 3, 4)
        got = _scatter_kv_pages_all_layers(
            pages, fresh, page_ids, slot_ids, np.zeros((3, 4), bool))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(pages))

    @pytest.mark.parametrize(
        "page, slot", [pytest.param(12, 0, id="page"), pytest.param(3, 4, id="slot")]
    )
    def test_a_place_past_the_pool_is_dropped_not_another_layers(self, page, slot):
        # The five-dimensional scatter dropped an out-of-range page or slot;
        # as a flat row it would be a real row of the next page or layer.
        rng = np.random.default_rng(13)
        pages, fresh, page_ids, slot_ids = _random_write(rng, self.POOL, 2, 1)
        page_ids[1, 0], slot_ids[1, 0] = page, slot
        valid = np.ones((2, 1), bool)
        got = _scatter_kv_pages_all_layers(pages, fresh, page_ids, slot_ids, valid)
        want = _old_scatter(pages, fresh, page_ids, slot_ids, valid)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_a_pool_sharded_on_kv_heads_stays_so(self):
        # tp=4 on the virtual CPU devices: the flat view merges the three
        # leading (replicated) axes only, so the head sharding carries
        # through the write and the values are the unsharded ones.
        pool_shape = (2, 6, 4, 4, 8)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("dp", "tp"))
        sharding = kv_pages_sharding(mesh)
        rng = np.random.default_rng(17)
        pages, fresh, page_ids, slot_ids = _random_write(rng, pool_shape, 3, 2)
        valid = np.array([[True, True], [True, False], [False, False]])
        want = _old_scatter(pages, fresh, page_ids, slot_ids, valid)
        got = jax.jit(_scatter_kv_pages_all_layers)(
            jax.device_put(pages, sharding), jax.device_put(fresh, sharding),
            page_ids, slot_ids, valid,
        )
        assert got.sharding.is_equivalent_to(sharding, got.ndim)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
