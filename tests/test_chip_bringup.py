"""Bring-up invariants (ISSUE 21): nothing on the main path hides the
device, replicas land on their own device, the compile cache can be placed
from outside, and ``chip_smoke.py`` without its flag and without a chip is an
error (its explicit dry run: ``tests/test_chip_smoke_dry_run.py``, the
serving phases, and ``tests/test_chip_smoke_dry_run_kernels.py``).

Everything here runs on the CPU's virtual devices; the compiled path is
proven on the chip by ``chip_smoke.py`` itself.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
from llm_d_kv_cache_manager_tpu.parallel import MeshConfig, make_mesh
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
)
from llm_d_kv_cache_manager_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, **env):
    return subprocess.run(
        [sys.executable, *cmd], cwd=REPO, capture_output=True, text=True,
        timeout=600, env={**os.environ, **env},
    )


class TestChipSmokeCommand:
    def test_without_the_flag_no_chip_is_an_error(self):
        """Never a fallback: no accelerator and no --dry-run exits non-zero
        before a kernel or a pod exists, and prints no result."""
        r = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
        assert r.returncode != 0
        assert '"ok": true' not in r.stdout
        assert "== kernels" not in r.stdout and "== serve" not in r.stdout
        assert "no accelerator" in r.stderr


def _tiny_engine(device):
    return Engine(
        EngineConfig(
            model=TINY_LLAMA,
            block_manager=BlockManagerConfig(total_pages=32, page_size=4),
            max_model_len=64, decode_batch_size=2, prefill_bucket=8,
            interpret=True,
        ),
        mesh=make_mesh(MeshConfig(), devices=[device]),
    )


def _devices_of(tree):
    return {d for x in jax.tree.leaves(tree) for d in x.devices()}


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 virtual devices")
class TestReplicaOwnsItsDevice:
    def test_tp1_engine_lives_on_the_device_it_was_given(self):
        dev = jax.devices()[5]
        eng = _tiny_engine(dev)
        assert eng.devices == [dev] and eng.mesh is None
        assert _devices_of(eng.params) == {dev}
        assert _devices_of((eng.k_pages, eng.v_pages)) == {dev}
        seq = eng.add_request(list(range(10)), SamplingParams(max_new_tokens=5))
        eng.run_until_complete()
        assert len(seq.output_tokens) == 5
        # pools and rng are outputs of the prefill/decode steps just run
        assert _devices_of((eng.k_pages, eng.v_pages, eng._rng)) == {dev}
        assert eng._dev(np.zeros(3, np.int32)).devices() == {dev}

    def test_two_replicas_do_not_share_a_device(self):
        d3, d6 = jax.devices()[3], jax.devices()[6]
        before = {
            d: sum(a.nbytes for a in jax.live_arrays() if d in a.devices())
            for d in jax.devices()
        }
        a, b = _tiny_engine(d3), _tiny_engine(d6)
        outs = []
        for eng in (a, b):
            seq = eng.add_request(
                list(range(12)), SamplingParams(max_new_tokens=4)
            )
            eng.run_until_complete()
            outs.append(seq.output_tokens)
        assert outs[0] == outs[1]  # same seed, same weights, own copies
        assert _devices_of((a.params, a.k_pages)) == {d3}
        assert _devices_of((b.params, b.k_pages)) == {d6}
        after = {
            d: sum(x.nbytes for x in jax.live_arrays() if d in x.devices())
            for d in jax.devices()
        }
        grew = {d for d in jax.devices() if after[d] > before[d]}
        # nothing was staged through the default device (or any other)
        assert grew == {d3, d6}

    def test_mesh_must_match_the_config(self):
        with pytest.raises(ValueError, match="does not match"):
            Engine(
                EngineConfig(model=TINY_LLAMA, interpret=True),
                mesh=make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2]),
            )


class TestNothingHidesTheDevice:
    def test_engine_refuses_compiled_kernels_off_tpu(self):
        with pytest.raises(ValueError, match="interpret=False on 'cpu'"):
            Engine(EngineConfig(model=TINY_LLAMA))

    def test_interpret_takes_the_xla_prefill_and_nothing_else_does(self):
        """The one stated rule; the backend is never consulted."""
        eng = _tiny_engine(jax.devices()[0])
        assert eng.prefill_attn == "xla"
        pinned = Engine(
            EngineConfig(model=TINY_LLAMA, interpret=True, prefill_attn="pallas")
        )
        assert pinned.prefill_attn == "pallas"

    def test_kernel_wrappers_raise_instead_of_interpreting(self):
        from llm_d_kv_cache_manager_tpu.ops.flash_prefill import (
            flash_prefill_paged,
        )
        from llm_d_kv_cache_manager_tpu.ops.gmm import grouped_matmul
        from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
            paged_attention,
        )

        q = jnp.zeros((1, 4, 8), jnp.float32)
        pages = jnp.zeros((4, 4, 2, 8), jnp.float32)
        bt = jnp.zeros((1, 2), jnp.int32)
        sl = jnp.asarray([3], jnp.int32)
        with pytest.raises(RuntimeError, match="Mosaic needs a TPU"):
            paged_attention(q, pages, pages, bt, sl)
        paged_attention(q, pages, pages, bt, sl, interpret=True)  # asked for

        qs = jnp.zeros((1, 8, 4, 8), jnp.float32)
        ks = jnp.zeros((1, 8, 2, 8), jnp.float32)
        with pytest.raises(RuntimeError, match="Mosaic needs a TPU"):
            flash_prefill_paged(
                qs, ks, ks, pages, pages, bt,
                jnp.zeros((1,), jnp.int32), jnp.asarray([8], jnp.int32),
            )

        lhs = jnp.zeros((8, 128), jnp.float32)
        rhs = jnp.zeros((2, 128, 128), jnp.float32)
        gs = jnp.asarray([5, 3], jnp.int32)
        with pytest.raises(RuntimeError, match="Mosaic needs a TPU"):
            grouped_matmul(lhs, rhs, gs)
        grouped_matmul(lhs, rhs, gs, use_kernel=False)  # XLA, any backend


class TestCompileCachePlacement:
    @pytest.fixture(autouse=True)
    def _restore(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_placement_is_left_to_jax(self, monkeypatch, tmp_path):
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # the program set no path in code
        assert jax.config.jax_compilation_cache_dir is None

    def test_default_is_one_fixed_directory_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert first == os.path.join(REPO, ".jax_compile_cache")
        assert jax.config.jax_compilation_cache_dir == first
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_compile_cache/" in f.read().split()

    def test_entry_count(self, tmp_path):
        assert compile_cache.cache_entries(str(tmp_path / "absent")) == 0
        (tmp_path / "a").write_text("x")
        assert compile_cache.cache_entries(str(tmp_path)) == 1
