"""Model numerics tests.

- Cross-check against transformers' torch Llama (random-init, no network):
  the strongest validation of RMSNorm/RoPE/GQA/SwiGLU wiring.
- Prefill↔decode consistency on the paged KV cache: prefilling n tokens
  must give the same next-token logits as prefilling n-1 and decoding one.
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_d_kv_cache_manager_tpu.models import (
    TINY_LLAMA,
    LlamaConfig,
    decode_step,
    init_kv_pages,
    init_params,
)
from llm_d_kv_cache_manager_tpu.models import prefill as _prefill

PAGE_SIZE = 4

# These tests run on the CPU and say so: without ``interpret`` the model
# asks for compiled Pallas kernels (MoE ``moe_gmm="auto"``) and raises.
prefill = functools.partial(_prefill, interpret=True)


def _alloc(cfg, batch, max_tokens):
    """Trivial sequential page allocation for tests."""
    pages_per_seq = max_tokens // PAGE_SIZE
    total = batch * pages_per_seq + 1
    k_pages, v_pages = init_kv_pages(cfg, total, PAGE_SIZE)
    block_tables = np.arange(batch * pages_per_seq).reshape(batch, pages_per_seq) + 1
    return k_pages, v_pages, jnp.asarray(block_tables, jnp.int32)


def _prefill_args(block_tables, batch, seq):
    pos = np.tile(np.arange(seq), (batch, 1))
    page_ids = np.take_along_axis(
        np.asarray(block_tables), pos // PAGE_SIZE, axis=1
    )
    slot_ids = pos % PAGE_SIZE
    valid = np.ones((batch, seq), bool)
    return (
        jnp.asarray(pos, jnp.int32),
        jnp.asarray(valid),
        jnp.asarray(page_ids, jnp.int32),
        jnp.asarray(slot_ids, jnp.int32),
    )


def _zero_ctx(batch):
    return jnp.zeros((batch, 1), jnp.int32), jnp.zeros((batch,), jnp.int32)


class TestHFNumericsParity:
    def test_logits_match_transformers(self):
        torch = pytest.importorskip("torch")
        from transformers import LlamaConfig as HFLlamaConfig
        from transformers import LlamaForCausalLM

        from llm_d_kv_cache_manager_tpu.models.hf_loader import (
            config_from_hf,
            load_hf_state_dict,
        )

        hf_cfg = HFLlamaConfig(
            vocab_size=128,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            rope_theta=10000.0,
            rms_norm_eps=1e-5,
            tie_word_embeddings=False,
        )
        torch.manual_seed(0)
        hf_model = LlamaForCausalLM(hf_cfg).eval()

        cfg = config_from_hf(hf_cfg)
        cfg = LlamaConfig(**{**cfg.__dict__, "dtype": jnp.float32})
        params = load_hf_state_dict(hf_model.state_dict(), cfg)

        batch, seq = 2, 12
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 128, (batch, seq))

        with torch.no_grad():
            hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()  # [b, s, vocab]

        k_pages, v_pages, block_tables = _alloc(cfg, batch, seq)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        logits, _, _ = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )
        np.testing.assert_allclose(
            np.asarray(logits), hf_logits[:, -1], rtol=2e-4, atol=2e-4
        )

    def test_qwen_style_bias_loads(self):
        torch = pytest.importorskip("torch")
        from transformers import Qwen2Config, Qwen2ForCausalLM

        from llm_d_kv_cache_manager_tpu.models.hf_loader import (
            config_from_hf,
            load_hf_state_dict,
        )

        hf_cfg = Qwen2Config(
            vocab_size=128,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            rope_theta=10000.0,
            rms_norm_eps=1e-5,
            tie_word_embeddings=False,
        )
        torch.manual_seed(1)
        hf_model = Qwen2ForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg)
        assert cfg.qkv_bias
        cfg = LlamaConfig(**{**cfg.__dict__, "dtype": jnp.float32})
        params = load_hf_state_dict(hf_model.state_dict(), cfg)

        batch, seq = 1, 8
        rng = np.random.default_rng(2)
        tokens = rng.integers(0, 128, (batch, seq))
        with torch.no_grad():
            hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()

        k_pages, v_pages, block_tables = _alloc(cfg, batch, seq)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        logits, _, _ = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )
        np.testing.assert_allclose(
            np.asarray(logits), hf_logits[:, -1], rtol=2e-4, atol=2e-4
        )

    def test_gemma_matches_transformers(self):
        """Gemma family: gated-GELU FFN, (1+w) RMSNorm, sqrt(d)-scaled tied
        embeddings, decoupled head_dim — prefill AND decode logits must match
        HF GemmaForCausalLM."""
        torch = pytest.importorskip("torch")
        from transformers import GemmaConfig, GemmaForCausalLM

        from llm_d_kv_cache_manager_tpu.models.hf_loader import (
            config_from_hf,
            load_hf_state_dict,
        )

        hf_cfg = GemmaConfig(
            vocab_size=128,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=4,
            head_dim=24,
            rope_theta=10000.0,
            rms_norm_eps=1e-6,
            tie_word_embeddings=True,
            hidden_activation="gelu_pytorch_tanh",
        )
        torch.manual_seed(5)
        hf_model = GemmaForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg)
        assert cfg.norm_offset == 1.0 and cfg.scale_embeddings
        assert cfg.hidden_act == "gelu_tanh" and cfg.tie_word_embeddings
        cfg = LlamaConfig(**{**cfg.__dict__, "dtype": jnp.float32})
        params = load_hf_state_dict(hf_model.state_dict(), cfg)

        batch, seq = 2, 12
        rng = np.random.default_rng(6)
        tokens = rng.integers(0, 128, (batch, seq))
        with torch.no_grad():
            hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()

        k_pages, v_pages, block_tables = _alloc(cfg, batch, seq + PAGE_SIZE)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        logits, k_pages, v_pages = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )
        np.testing.assert_allclose(
            np.asarray(logits), hf_logits[:, -1], rtol=2e-4, atol=2e-4
        )

        nxt = rng.integers(0, 128, (batch, 1))
        with torch.no_grad():
            hf_logits2 = hf_model(
                torch.tensor(np.concatenate([tokens, nxt], axis=1))
            ).logits.numpy()
        dec_logits, _, _ = decode_step(
            params, cfg,
            jnp.asarray(nxt[:, 0], jnp.int32),
            jnp.full((batch,), seq, jnp.int32),
            k_pages, v_pages, block_tables,
            jnp.full((batch,), seq + 1, jnp.int32),
            page_size=PAGE_SIZE, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(dec_logits), hf_logits2[:, -1], rtol=2e-4, atol=2e-4
        )

    def test_qwen3_moe_matches_transformers(self):
        """Qwen3-MoE: qk-norm + 128-expert-style routed FFN with decoupled
        expert width and norm_topk_prob gating — prefill logits must match
        HF Qwen3MoeForCausalLM (tiny random model, both gating modes)."""
        torch = pytest.importorskip("torch")
        try:
            from transformers import Qwen3MoeConfig, Qwen3MoeForCausalLM
        except ImportError:
            pytest.skip("transformers has no Qwen3Moe")
        from llm_d_kv_cache_manager_tpu.models.hf_loader import (
            config_from_hf,
            load_hf_state_dict,
        )

        for norm_topk in (True, False):
            hf_cfg = Qwen3MoeConfig(
                vocab_size=128,
                hidden_size=64,
                intermediate_size=128,
                moe_intermediate_size=48,
                num_hidden_layers=2,
                num_attention_heads=4,
                num_key_value_heads=2,
                head_dim=24,
                num_experts=4,
                num_experts_per_tok=2,
                norm_topk_prob=norm_topk,
                rope_theta=10000.0,
                rms_norm_eps=1e-6,
                tie_word_embeddings=False,
            )
            torch.manual_seed(7)
            hf_model = Qwen3MoeForCausalLM(hf_cfg).eval()
            cfg = config_from_hf(hf_cfg)
            assert cfg.qk_norm and cfg.n_experts == 4
            assert cfg.moe_inter == 48 and cfg.norm_topk_prob is norm_topk
            cfg = LlamaConfig(**{**cfg.__dict__, "dtype": jnp.float32})
            params = load_hf_state_dict(hf_model.state_dict(), cfg)

            batch, seq = 2, 12
            rng = np.random.default_rng(8)
            tokens = rng.integers(0, 128, (batch, seq))
            with torch.no_grad():
                hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()
            k_pages, v_pages, block_tables = _alloc(cfg, batch, seq)
            pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
            logits, _, _ = prefill(
                params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
                k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
            )
            np.testing.assert_allclose(
                np.asarray(logits), hf_logits[:, -1], rtol=3e-4, atol=3e-4
            )

    def test_qwen3_moe_mixed_dense_rejected(self):
        pytest.importorskip("torch")
        try:
            from transformers import Qwen3MoeConfig
        except ImportError:
            pytest.skip("transformers has no Qwen3Moe")
        from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

        cfg = Qwen3MoeConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
            num_experts=2, mlp_only_layers=[0],
        )
        with pytest.raises(NotImplementedError, match="dense/sparse"):
            config_from_hf(cfg)

    def test_gemma2_rejected_loudly(self):
        """Gemma2/3 layer schemas differ; loading them as Gemma-1 must raise
        instead of silently producing wrong logits."""
        pytest.importorskip("torch")
        try:
            from transformers import Gemma2Config
        except ImportError:
            pytest.skip("transformers has no Gemma2Config")
        from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

        with pytest.raises(NotImplementedError, match="Gemma2"):
            config_from_hf(Gemma2Config(vocab_size=64, hidden_size=32,
                                        intermediate_size=64,
                                        num_hidden_layers=1,
                                        num_attention_heads=2,
                                        num_key_value_heads=2))

    def test_qwen3_qk_norm_matches_transformers(self):
        torch = pytest.importorskip("torch")
        from transformers import Qwen3Config, Qwen3ForCausalLM

        from llm_d_kv_cache_manager_tpu.models.hf_loader import (
            config_from_hf,
            load_hf_state_dict,
        )

        hf_cfg = Qwen3Config(
            vocab_size=128,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            head_dim=32,  # decoupled from hidden_size // n_heads, like Qwen3-32B
            rope_theta=10000.0,
            rms_norm_eps=1e-6,
            tie_word_embeddings=False,
        )
        torch.manual_seed(3)
        hf_model = Qwen3ForCausalLM(hf_cfg).eval()
        cfg = config_from_hf(hf_cfg)
        assert cfg.qk_norm and not cfg.qkv_bias and cfg.hd == 32
        cfg = LlamaConfig(**{**cfg.__dict__, "dtype": jnp.float32})
        params = load_hf_state_dict(hf_model.state_dict(), cfg)

        batch, seq = 2, 12
        rng = np.random.default_rng(4)
        tokens = rng.integers(0, 128, (batch, seq))
        with torch.no_grad():
            hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()

        # One spare page per sequence for the decode step below.
        k_pages, v_pages, block_tables = _alloc(cfg, batch, seq + PAGE_SIZE)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        logits, k_pages, v_pages = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )
        np.testing.assert_allclose(
            np.asarray(logits), hf_logits[:, -1], rtol=2e-4, atol=2e-4
        )

        # Decode path applies qk-norm identically: next-token logits after a
        # decode step must match HF's logits with one more token appended.
        nxt = rng.integers(0, 128, (batch, 1))
        with torch.no_grad():
            hf_logits2 = hf_model(
                torch.tensor(np.concatenate([tokens, nxt], axis=1))
            ).logits.numpy()
        dec_logits, _, _ = decode_step(
            params, cfg,
            jnp.asarray(nxt[:, 0], jnp.int32),
            jnp.full((batch,), seq, jnp.int32),
            k_pages, v_pages, block_tables,
            jnp.full((batch,), seq + 1, jnp.int32),
            page_size=PAGE_SIZE, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(dec_logits), hf_logits2[:, -1], rtol=2e-4, atol=2e-4
        )


class TestPrefillDecodeConsistency:
    def test_decode_matches_prefill(self):
        cfg = TINY_LLAMA
        params = init_params(jax.random.PRNGKey(0), cfg)
        batch, seq = 2, 12
        rng = np.random.default_rng(1)
        tokens = rng.integers(0, cfg.vocab_size, (batch, seq))

        # Full prefill of all `seq` tokens.
        k_pages, v_pages, block_tables = _alloc(cfg, batch, seq)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        full_logits, _, _ = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )

        # Prefill seq-1, then decode token seq-1.
        k_pages, v_pages, block_tables = _alloc(cfg, batch, seq)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        valid = valid.at[:, -1].set(False)
        _, k_pages, v_pages = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )
        dec_logits, _, _ = decode_step(
            params, cfg,
            jnp.asarray(tokens[:, -1], jnp.int32),
            jnp.full((batch,), seq - 1, jnp.int32),
            k_pages, v_pages, block_tables,
            jnp.full((batch,), seq, jnp.int32),
            page_size=PAGE_SIZE, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(dec_logits), np.asarray(full_logits), rtol=2e-4, atol=2e-4
        )

    def test_decode_two_steps(self):
        cfg = TINY_LLAMA
        params = init_params(jax.random.PRNGKey(0), cfg)
        batch, seq = 1, 8
        rng = np.random.default_rng(3)
        tokens = rng.integers(0, cfg.vocab_size, (batch, seq))

        full_k, full_v, block_tables = _alloc(cfg, batch, seq)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        full_logits, _, _ = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            full_k, full_v, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )

        # Prefill first 6, decode tokens 6 and 7.
        k_pages, v_pages, block_tables = _alloc(cfg, batch, seq)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        valid = valid.at[:, 6:].set(False)
        _, k_pages, v_pages = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )
        for step in (6, 7):
            logits, k_pages, v_pages = decode_step(
                params, cfg,
                jnp.asarray(tokens[:, step], jnp.int32),
                jnp.full((batch,), step, jnp.int32),
                k_pages, v_pages, block_tables,
                jnp.full((batch,), step + 1, jnp.int32),
                page_size=PAGE_SIZE, interpret=True,
            )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits), rtol=2e-4, atol=2e-4
        )

    def test_prefix_cached_suffix_prefill_matches_full(self):
        """The prefix-cache compute-skip: prefill tokens[0:8] (request A),
        then prefill only tokens[8:12] with A's pages as context (request B
        sharing the prefix) — logits must match a full 12-token prefill."""
        cfg = TINY_LLAMA
        params = init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(6)
        tokens = rng.integers(0, cfg.vocab_size, (1, 12))

        # Oracle: full prefill.
        k_pages, v_pages, bt = _alloc(cfg, 1, 12)
        pos, valid, page_ids, slot_ids = _prefill_args(bt, 1, 12)
        ref_logits, _, _ = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(1),
        )

        # Request A: prefill the 8-token shared prefix (2 pages).
        k_pages, v_pages, bt = _alloc(cfg, 1, 12)
        pos8, valid8, page_ids8, slot_ids8 = _prefill_args(bt[:, :2], 1, 8)
        _, k_pages, v_pages = prefill(
            params, cfg, jnp.asarray(tokens[:, :8], jnp.int32), pos8, valid8,
            k_pages, v_pages, page_ids8, slot_ids8, *_zero_ctx(1),
        )

        # Request B: suffix-only prefill attending to A's cached pages.
        suffix = jnp.asarray(tokens[:, 8:], jnp.int32)
        pos_s = jnp.arange(8, 12, dtype=jnp.int32)[None, :]
        valid_s = jnp.ones((1, 4), bool)
        page_ids_s = jnp.full((1, 4), int(bt[0, 2]), jnp.int32)
        slot_ids_s = pos_s % PAGE_SIZE
        ctx_bt = bt[:, :2]
        ctx_lens = jnp.asarray([8], jnp.int32)
        logits, _, _ = prefill(
            params, cfg, suffix, pos_s, valid_s,
            k_pages, v_pages, page_ids_s, slot_ids_s, ctx_bt, ctx_lens,
        )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
        )

    def test_pad_position_value_is_irrelevant(self):
        # Invalid positions are fully masked: whatever position value padding
        # carries (incl. 0, which passes the causal check) must not affect
        # valid tokens' logits.
        cfg = TINY_LLAMA
        params = init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(5)
        tokens = rng.integers(0, cfg.vocab_size, (1, 8))

        k_pages, v_pages, bt = _alloc(cfg, 1, 8)
        pos, valid, page_ids, slot_ids = _prefill_args(bt, 1, 8)
        ref_logits, _, _ = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )

        padded = np.concatenate([tokens, rng.integers(0, cfg.vocab_size, (1, 4))], axis=1)
        k_pages, v_pages, bt = _alloc(cfg, 1, 12)
        pos12, valid12, page_ids12, slot_ids12 = _prefill_args(bt, 1, 12)
        pos12 = pos12.at[:, 8:].set(0)  # pad positions = 0, the nasty case
        valid12 = valid12.at[:, 8:].set(False)
        pad_logits, _, _ = prefill(
            params, cfg, jnp.asarray(padded, jnp.int32), pos12, valid12,
            k_pages, v_pages, page_ids12, slot_ids12, *_zero_ctx(1),
        )
        np.testing.assert_allclose(
            np.asarray(pad_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
        )

    def test_llama31_rope_scaling_config_is_jittable(self):
        from llm_d_kv_cache_manager_tpu.ops.rope import RopeScalingConfig

        cfg = LlamaConfig(**{**TINY_LLAMA.__dict__, "rope_scaling": RopeScalingConfig()})
        params = init_params(jax.random.PRNGKey(0), cfg)
        k_pages, v_pages, bt = _alloc(cfg, 1, 8)
        pos, valid, page_ids, slot_ids = _prefill_args(bt, 1, 8)
        logits, _, _ = prefill(
            params, cfg, jnp.zeros((1, 8), jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )
        assert logits.shape == (1, cfg.vocab_size)

    def test_padded_prefill_matches_unpadded(self):
        cfg = TINY_LLAMA
        params = init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(4)
        tokens = rng.integers(0, cfg.vocab_size, (1, 8))

        k_pages, v_pages, bt = _alloc(cfg, 1, 8)
        pos, valid, page_ids, slot_ids = _prefill_args(bt, 1, 8)
        ref_logits, _, _ = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )

        # Same 8 tokens followed by 4 padding slots marked invalid.
        padded = np.concatenate([tokens, np.zeros((1, 4), int)], axis=1)
        k_pages, v_pages, bt = _alloc(cfg, 1, 12)
        pos, valid, page_ids, slot_ids = _prefill_args(bt, 1, 12)
        valid = valid.at[:, 8:].set(False)
        pad_logits, _, _ = prefill(
            params, cfg, jnp.asarray(padded, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )
        np.testing.assert_allclose(
            np.asarray(pad_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
        )


class TestMixtralMoE:
    def test_logits_match_transformers_mixtral(self):
        torch = pytest.importorskip("torch")
        from transformers import MixtralConfig, MixtralForCausalLM

        from llm_d_kv_cache_manager_tpu.models.hf_loader import (
            config_from_hf,
            load_hf_state_dict,
        )

        hf_cfg = MixtralConfig(
            vocab_size=128,
            hidden_size=64,
            intermediate_size=96,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            num_local_experts=4,
            num_experts_per_tok=2,
            rope_theta=10000.0,
            rms_norm_eps=1e-5,
            tie_word_embeddings=False,
        )
        torch.manual_seed(7)
        hf_model = MixtralForCausalLM(hf_cfg).eval()

        cfg = config_from_hf(hf_cfg)
        assert cfg.n_experts == 4 and cfg.n_experts_per_tok == 2
        cfg = LlamaConfig(**{**cfg.__dict__, "dtype": jnp.float32})
        params = load_hf_state_dict(hf_model.state_dict(), cfg)

        batch, seq = 2, 12
        rng = np.random.default_rng(8)
        tokens = rng.integers(0, 128, (batch, seq))
        with torch.no_grad():
            hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()

        # One spare page per sequence for the decode step below.
        k_pages, v_pages, block_tables = _alloc(cfg, batch, seq + PAGE_SIZE)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        logits, k_pages, v_pages = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )
        np.testing.assert_allclose(
            np.asarray(logits), hf_logits[:, -1], rtol=2e-4, atol=2e-4
        )

        # Decode path routes through the same MoE: one more token must match
        # HF on the extended sequence.
        nxt = rng.integers(0, 128, (batch, 1))
        with torch.no_grad():
            hf_logits2 = hf_model(
                torch.tensor(np.concatenate([tokens, nxt], axis=1))
            ).logits.numpy()
        dec_logits, _, _ = decode_step(
            params, cfg,
            jnp.asarray(nxt[:, 0], jnp.int32),
            jnp.full((batch,), seq, jnp.int32),
            k_pages, v_pages, block_tables,
            jnp.full((batch,), seq + 1, jnp.int32),
            page_size=PAGE_SIZE, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(dec_logits), hf_logits2[:, -1], rtol=2e-4, atol=2e-4
        )

    def test_moe_decode_matches_prefill(self):
        from llm_d_kv_cache_manager_tpu.models import TINY_MOE

        cfg = TINY_MOE
        params = init_params(jax.random.PRNGKey(0), cfg)
        batch, seq = 2, 12
        rng = np.random.default_rng(9)
        tokens = rng.integers(0, cfg.vocab_size, (batch, seq))

        k_pages, v_pages, block_tables = _alloc(cfg, batch, seq)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        full_logits, _, _ = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )

        k_pages, v_pages, block_tables = _alloc(cfg, batch, seq)
        pos, valid, page_ids, slot_ids = _prefill_args(block_tables, batch, seq)
        valid = valid.at[:, -1].set(False)
        _, k_pages, v_pages = prefill(
            params, cfg, jnp.asarray(tokens, jnp.int32), pos, valid,
            k_pages, v_pages, page_ids, slot_ids, *_zero_ctx(page_ids.shape[0]),
        )
        dec_logits, _, _ = decode_step(
            params, cfg,
            jnp.asarray(tokens[:, -1], jnp.int32),
            jnp.full((batch,), seq - 1, jnp.int32),
            k_pages, v_pages, block_tables,
            jnp.full((batch,), seq, jnp.int32),
            page_size=PAGE_SIZE, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(dec_logits), np.asarray(full_logits), rtol=2e-4, atol=2e-4
        )

    def test_top_k_routing_is_sparse(self):
        """Zeroing a non-selected expert's weights must not change outputs:
        proves only the top-k experts contribute, despite the masked-dense
        compute."""
        from llm_d_kv_cache_manager_tpu.models import TINY_MOE
        from llm_d_kv_cache_manager_tpu.models.llama import _moe_mlp

        cfg = TINY_MOE
        params = init_params(jax.random.PRNGKey(3), cfg)
        layer = params["layers"][0]
        rng = np.random.default_rng(10)
        x = jnp.asarray(rng.standard_normal((1, 5, cfg.hidden_size)), jnp.float32)

        router_logits = np.asarray(x @ layer["router"])  # [1, 5, E]
        ref = np.asarray(_moe_mlp(layer, cfg, x, interpret=True))

        # For each expert, zero its weights; if it was never in any token's
        # top-2, the output must be identical.
        topk = np.argsort(-router_logits, axis=-1)[..., : cfg.n_experts_per_tok]
        for e in range(cfg.n_experts):
            mutated = dict(layer)
            for w in ("w_gate", "w_up", "w_down"):
                mutated[w] = layer[w].at[e].set(0.0)
            got = np.asarray(_moe_mlp(mutated, cfg, x, interpret=True))
            if e not in topk:
                np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
            else:
                assert not np.allclose(got, ref)


class TestRoutedDispatch:
    """Grouped top-k gather dispatch vs the masked-dense oracle."""

    @pytest.mark.parametrize("tiny", ["TINY_MOE", "TINY_QWEN3_MOE"])
    @pytest.mark.parametrize("shape", [(1, 1), (2, 1), (3, 17)])
    def test_routed_matches_dense_oracle(self, tiny, shape):
        import dataclasses

        from llm_d_kv_cache_manager_tpu.models import llama
        from llm_d_kv_cache_manager_tpu.models.llama import _moe_mlp

        cfg = getattr(llama, tiny)
        assert cfg.moe_dispatch == "routed"  # the default under test
        dense_cfg = dataclasses.replace(cfg, moe_dispatch="dense")
        params = init_params(jax.random.PRNGKey(5), cfg)
        layer = params["layers"][0]
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.standard_normal((*shape, cfg.hidden_size)), jnp.float32)
        routed = np.asarray(_moe_mlp(layer, cfg, x, interpret=True))
        dense = np.asarray(_moe_mlp(layer, dense_cfg, x, interpret=True))
        np.testing.assert_allclose(routed, dense, rtol=1e-5, atol=1e-5)

    def test_unknown_dispatch_rejected(self):
        import dataclasses

        from llm_d_kv_cache_manager_tpu.models import TINY_MOE
        from llm_d_kv_cache_manager_tpu.models.llama import _moe_mlp

        cfg = dataclasses.replace(TINY_MOE, moe_dispatch="nope")
        params = init_params(jax.random.PRNGKey(0), cfg)
        x = jnp.zeros((1, 2, cfg.hidden_size), jnp.float32)
        with pytest.raises(ValueError, match="moe_dispatch"):
            _moe_mlp(params["layers"][0], cfg, x, interpret=True)

    def _a3b_shaped(self):
        """Qwen3-30B-A3B expert geometry (128 experts, top-8) at reduced
        hidden width — the E/k ratio is what's under test."""
        import dataclasses

        from llm_d_kv_cache_manager_tpu.models import llama

        return dataclasses.replace(
            llama.TINY_QWEN3_MOE,
            hidden_size=128,
            n_experts=128,
            n_experts_per_tok=8,
            moe_intermediate_size=64,
        )

    def test_routed_never_materializes_all_expert_activations(self):
        """Structural complexity check (backend-independent): the dense
        oracle materializes an [E, n, f] activation; the routed dispatch's
        largest intermediate must be [n*k, f] — E/k times smaller. The
        FLOPs ratio itself (E/k = 16 at 128/8) is not asserted here: the
        CPU lowering of ragged_dot is loop-dense, so it cannot be read
        from a CPU compile."""
        import dataclasses

        cfg = self._a3b_shaped()
        from llm_d_kv_cache_manager_tpu.models.llama import _moe_mlp

        params = init_params(jax.random.PRNGKey(0), cfg)
        layer = params["layers"][0]
        n, k, f = 64, cfg.n_experts_per_tok, cfg.moe_inter
        x = jnp.zeros((1, n, cfg.hidden_size), jnp.float32)

        jaxpr = jax.make_jaxpr(lambda p, v: _moe_mlp(p, cfg, v, interpret=True))(layer, x)
        prims = {e.primitive.name for e in jaxpr.eqns}
        assert "ragged_dot" in prims or "ragged_dot_general" in prims, prims
        dense_inter = cfg.n_experts * n * f
        biggest = max(
            int(np.prod(v.aval.shape))
            for e in jaxpr.eqns
            for v in e.outvars
            if v.aval.shape
        )
        # The routed design goal: nothing bigger than the [n*k, max(d, f)]
        # gather/activation ever materializes (E/k times below dense scale;
        # the bound is inclusive because the gather is exactly that size).
        routed_scale = n * k * max(cfg.hidden_size, f)
        assert biggest <= routed_scale, (
            f"routed path materializes a {biggest}-element intermediate; "
            f"design bound is {routed_scale}, dense-oracle scale is {dense_inter}"
        )
        assert dense_inter / routed_scale >= cfg.n_experts / k / 2, (
            "reduced config no longer separates routed from dense scale"
        )

        dense_jaxpr = jax.make_jaxpr(
            lambda p, v: _moe_mlp(p, dataclasses.replace(cfg, moe_dispatch="dense"), v)
        )(layer, x)
        dense_biggest = max(
            int(np.prod(v.aval.shape))
            for e in dense_jaxpr.eqns
            for v in e.outvars
            if v.aval.shape
        )
        assert dense_biggest >= dense_inter  # the oracle really is dense

    @pytest.mark.skipif(
        jax.default_backend() != "tpu", reason="needs the TPU ragged_dot kernel"
    )
    def test_routed_flops_scale_with_top_k_not_n_experts(self):
        """XLA TPU cost model: dense/routed FLOPs ratio ~E/k at 128/8."""
        import dataclasses

        from llm_d_kv_cache_manager_tpu.models.llama import _moe_mlp

        cfg = self._a3b_shaped()
        dense_cfg = dataclasses.replace(cfg, moe_dispatch="dense")
        params = init_params(jax.random.PRNGKey(0), cfg)
        layer = params["layers"][0]
        x = jnp.zeros((1, 64, cfg.hidden_size), jnp.float32)

        def flops(c):
            fn = jax.jit(lambda p, v: _moe_mlp(p, c, v))
            an = fn.lower(layer, x).compile().cost_analysis()
            an = an[0] if isinstance(an, list) else an
            return an["flops"]

        ratio = flops(dense_cfg) / flops(cfg)
        assert ratio > 8, f"dense/routed flops ratio only {ratio:.1f}"


class TestQwen2MoeRejection:
    def test_shared_expert_moe_rejected(self):
        pytest.importorskip("torch")
        try:
            from transformers import Qwen2MoeConfig
        except ImportError:
            pytest.skip("transformers has no Qwen2Moe")
        from llm_d_kv_cache_manager_tpu.models.hf_loader import config_from_hf

        cfg = Qwen2MoeConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
            num_experts=4, shared_expert_intermediate_size=64,
        )
        with pytest.raises(NotImplementedError, match="shared-expert"):
            config_from_hf(cfg)
