"""Weight-only int8 quantization (models/quant.py).

Covers: per-tensor quantization error bounds, pytree mechanics, whole-model
logits fidelity (dense + MoE, routed and dense dispatch), the engine's
quantize="int8" serving path, byte accounting, and tp-sharded quantized
params (q partitioned, scale's size-1 contraction axis replicated).
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_d_kv_cache_manager_tpu.models import (
    TINY_LLAMA,
    TINY_MOE,
    TINY_QWEN3_MOE,
    init_params,
    param_bytes,
    quantize_params,
    quantize_tensor,
    materialize,
    QuantizedTensor,
)


class TestQuantizeTensor:
    def test_roundtrip_error_bounded_by_half_scale(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
        qt = quantize_tensor(w)
        assert qt.q.dtype == jnp.int8
        assert qt.scale.shape == (1, 48)
        deq = materialize(qt, jnp.float32)
        err = np.abs(np.asarray(deq - w))
        bound = np.asarray(qt.scale) / 2 + 1e-7
        assert (err <= bound).all(), err.max()

    def test_moe_weight_scale_per_expert_and_channel(self):
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.standard_normal((4, 32, 16)), jnp.float32)
        qt = quantize_tensor(w)
        assert qt.scale.shape == (4, 1, 16)

    def test_extreme_channel_does_not_poison_others(self):
        w = jnp.ones((8, 4), jnp.float32)
        w = w.at[:, 0].multiply(1e4)  # one huge output channel
        deq = materialize(quantize_tensor(w), jnp.float32)
        # channels 1..3 keep full relative precision despite channel 0
        np.testing.assert_allclose(np.asarray(deq[:, 1:]), 1.0, rtol=1e-2)

    def test_zero_weight_does_not_divide_by_zero(self):
        qt = quantize_tensor(jnp.zeros((4, 4), jnp.float32))
        assert np.isfinite(np.asarray(qt.scale)).all()
        assert (np.asarray(materialize(qt, jnp.float32)) == 0).all()

    def test_pytree_roundtrip(self):
        qt = quantize_tensor(jnp.ones((4, 4), jnp.float32))
        leaves, treedef = jax.tree.flatten(qt)
        assert len(leaves) == 2
        back = jax.tree.unflatten(treedef, leaves)
        assert isinstance(back, QuantizedTensor)


class TestQuantizedModel:
    def _logits(self, cfg, params, tokens):
        from llm_d_kv_cache_manager_tpu.parallel.train import _forward_logits

        return np.asarray(_forward_logits(params, cfg, tokens, interpret=True))

    def _fidelity(self, cfg, seed=0):
        rng = np.random.default_rng(seed)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
        params = init_params(jax.random.PRNGKey(seed), cfg)
        ref = self._logits(cfg, params, tokens)
        got = self._logits(cfg, quantize_params(params), tokens)
        # int8 weight-only: logits stay highly correlated with bf16/f32
        ref_f, got_f = ref.reshape(-1), got.reshape(-1)
        cos = np.dot(ref_f, got_f) / (
            np.linalg.norm(ref_f) * np.linalg.norm(got_f) + 1e-9
        )
        assert cos > 0.99, cos
        # greedy next-token choice agrees at most positions
        agree = (ref.argmax(-1) == got.argmax(-1)).mean()
        assert agree > 0.8, agree

    def test_dense_model_fidelity(self):
        self._fidelity(TINY_LLAMA)

    def test_moe_routed_fidelity(self):
        self._fidelity(TINY_QWEN3_MOE)

    def test_moe_experts_opt_in_fidelity(self):
        """quantize_experts=True (capacity-forced deployments) must still
        be numerically sound even though it is not the perf default."""
        cfg = TINY_QWEN3_MOE
        rng = np.random.default_rng(3)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
        params = init_params(jax.random.PRNGKey(3), cfg)
        ref = self._logits(cfg, params, tokens)
        qparams = quantize_params(params, quantize_experts=True)
        assert isinstance(qparams["layers"][0]["w_gate"], QuantizedTensor)
        got = self._logits(cfg, qparams, tokens)
        ref_f, got_f = ref.reshape(-1), got.reshape(-1)
        cos = np.dot(ref_f, got_f) / (
            np.linalg.norm(ref_f) * np.linalg.norm(got_f) + 1e-9
        )
        assert cos > 0.99, cos

    def test_moe_dense_dispatch_fidelity(self):
        self._fidelity(dataclasses.replace(TINY_MOE, moe_dispatch="dense"))

    def test_init_params_quantize_inline(self):
        params = init_params(jax.random.PRNGKey(0), TINY_LLAMA, quantize="int8")
        layer = params["layers"][0]
        assert isinstance(layer["wq"], QuantizedTensor)
        assert isinstance(layer["w_down"], QuantizedTensor)
        assert not isinstance(layer["attn_norm"], QuantizedTensor)
        assert not isinstance(params["embed"], QuantizedTensor)

    def test_router_and_experts_stay_full_precision(self):
        """MoE: router (precision-sensitive) AND expert stacks (int8
        dequant does not fuse into ragged_dot — measured slower, see
        round-3 measurement) stay in model dtype; the attention
        weights still quantize."""
        params = init_params(jax.random.PRNGKey(0), TINY_MOE, quantize="int8")
        layer = params["layers"][0]
        assert not isinstance(layer["router"], QuantizedTensor)
        assert not isinstance(layer["w_gate"], QuantizedTensor)
        assert not isinstance(layer["w_down"], QuantizedTensor)
        assert isinstance(layer["wq"], QuantizedTensor)

    def test_quantize_params_skips_experts_by_default(self):
        params = init_params(jax.random.PRNGKey(1), TINY_MOE)
        qparams = quantize_params(params)
        layer = qparams["layers"][0]
        assert not isinstance(layer["w_gate"], QuantizedTensor)
        assert isinstance(layer["wq"], QuantizedTensor)

    def test_param_bytes_roughly_halved(self):
        cfg = dataclasses.replace(TINY_LLAMA, dtype=jnp.bfloat16)
        params = init_params(jax.random.PRNGKey(0), cfg)
        qparams = quantize_params(params)
        # Embedding (unquantized) dominates tiny configs, so compare the
        # quantized subset directly: int8 + f32-scale ≈ 0.5x of bf16.
        w = params["layers"][0]["w_gate"]
        qw = qparams["layers"][0]["w_gate"]
        orig = w.size * w.dtype.itemsize
        quant = qw.q.size + qw.scale.size * 4
        assert quant < 0.6 * orig
        assert param_bytes(qparams) < param_bytes(params)

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="quantize"):
            init_params(jax.random.PRNGKey(0), TINY_LLAMA, quantize="int4")


class TestQuantizedEngine:
    def test_engine_serves_quantized(self):
        from llm_d_kv_cache_manager_tpu.server import (
            BlockManagerConfig,
            Engine,
            EngineConfig,
            SamplingParams,
        )

        eng = Engine(
            EngineConfig(
                model=TINY_LLAMA,
                block_manager=BlockManagerConfig(total_pages=32, page_size=4),
                max_model_len=32,
                decode_batch_size=2,
                prefill_bucket=8,
                interpret=True,
                quantize="int8",
            )
        )
        assert isinstance(eng.params["layers"][0]["wq"], QuantizedTensor)
        rng = np.random.default_rng(0)
        seq = eng.add_request(
            rng.integers(0, TINY_LLAMA.vocab_size, 10).tolist(),
            SamplingParams(max_new_tokens=4),
        )
        eng.run_until_complete()
        assert len(seq.output_tokens) == 4
        # warm path: prefix hit served from the quantized engine
        seq2 = eng.add_request(
            seq.prompt_tokens + rng.integers(0, TINY_LLAMA.vocab_size, 3).tolist(),
            SamplingParams(max_new_tokens=2),
        )
        eng.run_until_complete()
        assert len(seq2.output_tokens) == 2
        assert seq2.num_cached_prompt > 0

    def test_engine_rejects_unknown_mode(self):
        from llm_d_kv_cache_manager_tpu.server import Engine, EngineConfig

        params = init_params(jax.random.PRNGKey(0), TINY_LLAMA)
        with pytest.raises(ValueError, match="quantize"):
            Engine(
                EngineConfig(model=TINY_LLAMA, quantize="fp4", interpret=True),
                params=params,
            )


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 virtual devices")
class TestQuantizedSharding:
    def test_quantized_moe_with_expert_parallel_dispatch(self):
        """int8 attention weights + bf16 experts under an expert-parallel
        mesh: the QuantizedTensor sharding and the shard_map routed-EP
        dispatch must compose (forward == unsharded)."""
        from llm_d_kv_cache_manager_tpu.parallel import (
            MeshConfig,
            batch_sharding,
            make_mesh,
            shard_params,
        )
        from llm_d_kv_cache_manager_tpu.parallel.train import _forward_logits

        cfg = dataclasses.replace(
            TINY_QWEN3_MOE, n_experts=16, n_experts_per_tok=2
        )
        params = quantize_params(init_params(jax.random.PRNGKey(7), cfg))
        assert isinstance(params["layers"][0]["wq"], QuantizedTensor)
        assert not isinstance(params["layers"][0]["w_gate"], QuantizedTensor)
        rng = np.random.default_rng(17)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
        ref = np.asarray(_forward_logits(params, cfg, tokens, interpret=True))

        mesh = make_mesh(MeshConfig(dp=2, tp=4))  # k*tp = 8 < 16 → routed-EP
        sharded = shard_params(params, mesh, cfg)
        out = np.asarray(
            jax.jit(
                _forward_logits, static_argnames=("cfg", "mesh", "interpret")
            )(
                sharded, cfg, jax.device_put(tokens, batch_sharding(mesh)),
                mesh=mesh, interpret=True,
            )
        )
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    def test_sharded_quantized_forward_matches_unsharded(self):
        from llm_d_kv_cache_manager_tpu.parallel import (
            MeshConfig,
            batch_sharding,
            make_mesh,
            shard_params,
        )
        from llm_d_kv_cache_manager_tpu.parallel.train import _forward_logits

        params = quantize_params(init_params(jax.random.PRNGKey(2), TINY_LLAMA))
        rng = np.random.default_rng(3)
        tokens = jnp.asarray(
            rng.integers(0, TINY_LLAMA.vocab_size, (4, 16)), jnp.int32
        )
        ref = np.asarray(_forward_logits(params, TINY_LLAMA, tokens))

        mesh = make_mesh(MeshConfig(dp=2, tp=2))
        sharded = shard_params(params, mesh, TINY_LLAMA)
        # int8 payload is partitioned on tp; its scale is not torn along
        # the size-1 contraction axis.
        wq = sharded["layers"][0]["wq"]
        out_dim = TINY_LLAMA.n_heads * TINY_LLAMA.hd
        assert {s.data.shape for s in wq.q.addressable_shards} == {
            (TINY_LLAMA.hidden_size, out_dim // 2)
        }
        assert {s.data.shape for s in wq.scale.addressable_shards} == {
            (1, out_dim // 2)
        }
        wo = sharded["layers"][0]["wo"]
        assert {s.data.shape for s in wo.q.addressable_shards} == {
            (out_dim // 2, TINY_LLAMA.hidden_size)
        }  # row-parallel: input dim split
        assert {s.data.shape for s in wo.scale.addressable_shards} == {
            (1, TINY_LLAMA.hidden_size)
        }  # scale replicated (size-1 axis unpartitionable)

        tok_sharded = jax.device_put(tokens, batch_sharding(mesh))
        out = np.asarray(
            jax.jit(_forward_logits, static_argnames=("cfg",))(
                sharded, TINY_LLAMA, tok_sharded
            )
        )
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
