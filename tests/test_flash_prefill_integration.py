"""The Pallas flash-prefill kernel (``ops/flash_prefill.py``) through
``llama.prefill`` and the engine end to end, and the mask contract of
``attn_impl="pallas"``. The kernel alone against its oracle is in
``tests/test_flash_prefill.py``, which was one file with this until it passed
120 cpu-seconds of a whole run.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp


class TestPrefillIntegration:
    def test_llama_prefill_pallas_matches_xla(self):
        """Whole-model prefill with attn_impl='pallas' vs 'xla'."""
        from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA, llama

        cfg = TINY_LLAMA
        rng = np.random.default_rng(5)
        b, s, page = 2, 16, 4
        total_pages = 32
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        valid = jnp.arange(s)[None, :] < jnp.asarray([[s], [s - 2]])[:, 0, None]
        page_ids = jnp.asarray(
            rng.permutation(total_pages - 1)[: b * (s // page)].reshape(b, -1),
            jnp.int32,
        ).repeat(page, axis=1)
        slot_ids = jnp.broadcast_to(jnp.arange(s)[None, :] % page, (b, s))
        bt = jnp.zeros((b, 2), jnp.int32)
        cl = jnp.zeros((b,), jnp.int32)

        def run(impl):
            kp, vp = llama.init_kv_pages(cfg, total_pages, page)
            return llama.prefill(
                params, cfg, tokens, positions, valid, kp, vp,
                page_ids, slot_ids, bt, cl, attn_impl=impl, interpret=True,
            )

        logits_x, kpx, vpx = run("xla")
        logits_p, kpp, vpp = run("pallas")
        np.testing.assert_allclose(
            np.asarray(logits_p), np.asarray(logits_x), atol=1e-4, rtol=1e-4
        )
        # Layer>0 K/V inherit ~1e-6 noise from the differing attention
        # summation order; the written pages must agree to that tolerance.
        np.testing.assert_allclose(
            np.asarray(kpp), np.asarray(kpx), atol=1e-5, rtol=1e-4
        )

    def test_engine_pallas_prefill_end_to_end(self):
        """Engine with prefill_attn='pallas' (interpret on CPU): cold and
        warm prefix requests complete, the warm hit fires, and the engine
        is deterministic run-to-run. (Token-exact equality with the XLA
        engine is NOT asserted: on a flat random-init model the two
        implementations' ~1e-6 summation-order noise flips greedy argmax —
        logits parity is covered at op and model level above.)"""
        from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
        from llm_d_kv_cache_manager_tpu.server import (
            BlockManagerConfig,
            Engine,
            EngineConfig,
            SamplingParams,
        )

        def run_once():
            eng = Engine(
                EngineConfig(
                    model=TINY_LLAMA,
                    block_manager=BlockManagerConfig(total_pages=64, page_size=4),
                    max_model_len=64,
                    decode_batch_size=2,
                    prefill_bucket=8,
                    interpret=True,
                    prefill_attn="pallas",
                )
            )
            assert eng.prefill_attn == "pallas"
            rng = np.random.default_rng(6)
            prompt = rng.integers(0, TINY_LLAMA.vocab_size, 18).tolist()
            s1 = eng.add_request(prompt, SamplingParams(max_new_tokens=4))
            eng.run_until_complete()
            s2 = eng.add_request(
                prompt + rng.integers(0, TINY_LLAMA.vocab_size, 3).tolist(),
                SamplingParams(max_new_tokens=3),
            )
            eng.run_until_complete()
            assert len(s1.output_tokens) == 4
            assert len(s2.output_tokens) == 3
            assert s2.num_cached_prompt > 0
            return s1.output_tokens, s2.output_tokens

        assert run_once() == run_once()  # deterministic

    def test_unknown_impl_rejected(self):
        from llm_d_kv_cache_manager_tpu.server import Engine, EngineConfig

        with pytest.raises(ValueError, match="prefill_attn"):
            Engine(EngineConfig(prefill_attn="cuda", interpret=True))


class TestMaskContract:
    """prefill(attn_impl='pallas') requires a right-padded prefix mask; the
    opt-in LLMD_CHECK_PREFILL_MASK host-callback assert catches violations
    (the xla path honors arbitrary masks, so a holey mask would otherwise
    silently diverge between the two implementations)."""

    def _run(self, valid):
        from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA, llama

        cfg = TINY_LLAMA
        rng = np.random.default_rng(6)
        b, s, page, total_pages = 2, 8, 4, 16
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        page_ids = jnp.asarray(
            rng.permutation(total_pages - 1)[: b * (s // page)].reshape(b, -1),
            jnp.int32,
        ).repeat(page, axis=1)
        slot_ids = jnp.broadcast_to(jnp.arange(s)[None, :] % page, (b, s))
        bt = jnp.zeros((b, 2), jnp.int32)
        cl = jnp.zeros((b,), jnp.int32)
        kp, vp = llama.init_kv_pages(cfg, total_pages, page)
        out = llama.prefill(
            params, cfg, tokens, positions, jnp.asarray(valid), kp, vp,
            page_ids, slot_ids, bt, cl, attn_impl="pallas", interpret=True,
        )
        jax.block_until_ready(out)

    def test_check_passes_right_padded(self, monkeypatch):
        from llm_d_kv_cache_manager_tpu.models import llama

        monkeypatch.setenv("LLMD_CHECK_PREFILL_MASK", "1")
        llama.prefill.clear_cache()  # env is read at trace time
        valid = np.arange(8)[None, :] < np.asarray([8, 5])[:, None]
        self._run(valid)  # must not raise
        llama.prefill.clear_cache()

    def test_check_rejects_interior_holes(self, monkeypatch):
        from llm_d_kv_cache_manager_tpu.models import llama

        monkeypatch.setenv("LLMD_CHECK_PREFILL_MASK", "1")
        llama.prefill.clear_cache()
        valid = np.arange(8)[None, :] < np.asarray([8, 5])[:, None]
        valid = valid.copy()
        valid[1, 2] = False  # hole inside the valid prefix
        with pytest.raises(Exception, match="right-padded"):
            self._run(valid)
        llama.prefill.clear_cache()
