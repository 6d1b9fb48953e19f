"""Quantized KV in HBM suite (ISSUE 16 acceptance).

``KV_QUANT_HBM=int8``: the device KV pool itself holds int8 codes with
per-page-per-(layer, kv_head) f32 scales, and the decode kernel dequantizes
in-register — double the on-chip cache capacity for the same HBM bytes.

- **Kernel parity**: the quantized ``paged_attention`` variant (scales as
  pipelined operands, in-kernel dequant) matches
  ``paged_attention_reference`` run on the dequantized pool *exactly* —
  including GQA, the 5-D multi-layer operand, and the ``has_fresh``
  current-token merge. Quantization error lives in the codes, never in
  the kernel.
- **HBM layout round-trip**: ``kv_hbm_scale_shape`` geometry and the
  write-time quantization error bound (<= scale/2 per element) for pages
  produced by the engine's prefill scatter and decode carry-page path.
- **Engine parity**: greedy outputs with the knob on match the fp
  baseline on the pinned workload; spill→bring-back through the (forced
  int8) host tier copies codes directly — no dequant→requant — so a
  round trip reproduces the no-spill quantized outputs bit-for-bit;
  preemption/refold completes and reports stably under the knob.
- **Mixed-fleet transfer**: quantized-HBM pods interoperate with legacy
  peers in BOTH directions (stored codes ride the existing ``quant``
  wire triple; imports land without widening), and with int8-wire pods.
- **Knob-off pins**: pool dtype, wire quant fields, ``kv_block_bytes``,
  and the ``/stats`` surface are bit-identical to the legacy engine.
- **Scope**: fp8 is a declared-but-stubbed mode; sp>1, spec_decode and
  the pallas prefill kernel are rejected at init, never silently widened.
"""

import asyncio
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_d_kv_cache_manager_tpu.kvcache.transfer import protocol
from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA, quant
from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
)
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

PS = 4
MODEL = "tiny-llama"


def _engine_config(total_pages=64, host_pages=0, decode_batch=4, **kw):
    return EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(
            total_pages=total_pages, page_size=PS, host_pages=host_pages
        ),
        scheduler=SchedulerConfig(max_prefill_batch=4),
        max_model_len=64,
        decode_batch_size=decode_batch,
        prefill_bucket=8,
        interpret=True,
        **kw,
    )


def _engine(**kw):
    return Engine(_engine_config(**kw))


def _prompt(seed, n):
    return list(
        map(int, np.random.default_rng(seed).integers(0, TINY_LLAMA.vocab_size, n))
    )


def _quantized_pool(rng, n_layers, total_pages, n_kv, hd):
    """Random int8 pool + scales and its exact full-width f32 view."""
    codes = rng.integers(-127, 128, (n_layers, total_pages, PS, n_kv, hd))
    codes = codes.astype(np.int8)
    scales = rng.uniform(0.01, 0.2, (n_layers, total_pages, n_kv)).astype(
        np.float32
    )
    wide = quant.dequantize_kv_pool(codes, scales, np.float32)
    return jnp.asarray(codes), jnp.asarray(scales), jnp.asarray(wide)


class TestQuantizedDecodeKernel:
    """Interpret-mode parity: quantized kernel vs reference on the
    dequantized pool. Tolerances are float roundoff, NOT quantization
    noise — both sides see the same (dequantized) values."""

    def _check(self, out, ref):
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_parity_gqa_single_layer(self):
        rng = np.random.default_rng(0)
        n_kv, hd, group, batch, total_pages, max_pages = 2, 8, 4, 3, 16, 4
        codes, scales, wide = _quantized_pool(rng, 1, total_pages, n_kv, hd)
        q = jnp.asarray(
            rng.standard_normal((batch, n_kv * group, hd)), jnp.float32
        )
        bt = jnp.asarray(
            rng.integers(1, total_pages, (batch, max_pages)), jnp.int32
        )
        sl = jnp.asarray([5, 16, 9], jnp.int32)
        out = paged_attention(
            q, codes[0], codes[0], bt, sl,
            k_scale=scales[0], v_scale=scales[0], interpret=True,
        )
        ref = paged_attention_reference(q, wide[0], wide[0], bt, sl)
        self._check(out, ref)

    def test_parity_multi_layer_operand(self):
        # 5-D pool with `layer` selecting inside the index map — the
        # serving path's shape (no per-layer pool copies).
        rng = np.random.default_rng(1)
        n_kv, hd, batch, total_pages, max_pages = 2, 8, 2, 12, 3
        codes, scales, wide = _quantized_pool(rng, 3, total_pages, n_kv, hd)
        q = jnp.asarray(rng.standard_normal((batch, 4, hd)), jnp.float32)
        bt = jnp.asarray(
            rng.integers(1, total_pages, (batch, max_pages)), jnp.int32
        )
        sl = jnp.asarray([7, 12], jnp.int32)
        for layer in (0, 2):
            out = paged_attention(
                q, codes, codes, bt, sl,
                k_scale=scales, v_scale=scales, interpret=True, layer=layer,
            )
            ref = paged_attention_reference(
                q, wide[layer], wide[layer], bt, sl
            )
            self._check(out, ref)

    def test_parity_has_fresh_current_token(self):
        # Fresh K/V stay full-precision (never quantized): the kernel
        # merges them after dequantizing the page history.
        rng = np.random.default_rng(2)
        n_kv, hd, batch, total_pages, max_pages = 2, 8, 3, 16, 4
        codes, scales, wide = _quantized_pool(rng, 1, total_pages, n_kv, hd)
        q = jnp.asarray(rng.standard_normal((batch, 4, hd)), jnp.float32)
        fk = jnp.asarray(rng.standard_normal((batch, n_kv, hd)), jnp.float32)
        fv = jnp.asarray(rng.standard_normal((batch, n_kv, hd)), jnp.float32)
        # Pages globally unique (the allocator's no-aliasing contract):
        # the reference below writes each row's fresh token in place, so
        # a page shared between rows would leak one row's current token
        # into another row's history.
        bt = jnp.asarray(
            rng.permutation(np.arange(1, total_pages))[
                : batch * max_pages
            ].reshape(batch, max_pages),
            jnp.int32,
        )
        sl = jnp.asarray([6, 11, 16], jnp.int32)
        out = paged_attention(
            q, codes[0], codes[0], bt, sl, fk, fv,
            k_scale=scales[0], v_scale=scales[0], interpret=True,
        )
        # Reference: write the fresh token into its slot full-width.
        kw = np.asarray(wide[0]).copy()
        vw = np.asarray(wide[0]).copy()
        for b in range(batch):
            pos = int(sl[b]) - 1
            page = int(bt[b, pos // PS])
            kw[page, pos % PS] = np.asarray(fk[b])
            vw[page, pos % PS] = np.asarray(fv[b])
        ref = paged_attention_reference(
            q, jnp.asarray(kw), jnp.asarray(vw), bt, sl
        )
        self._check(out, ref)

    def test_scales_must_come_in_pairs(self):
        rng = np.random.default_rng(3)
        codes, scales, _ = _quantized_pool(rng, 1, 8, 2, 8)
        q = jnp.zeros((1, 4, 8), jnp.float32)
        bt = jnp.ones((1, 2), jnp.int32)
        sl = jnp.asarray([4], jnp.int32)
        with pytest.raises(ValueError, match="together"):
            paged_attention(
                q, codes[0], codes[0], bt, sl,
                k_scale=scales[0], interpret=True,
            )


class TestHBMQuantLayout:
    def test_scale_pool_geometry(self):
        assert quant.kv_hbm_scale_shape((3, 64, PS, 2, 8)) == (3, 64, 2)
        # Same per-page-per-(layer, head) granularity as the host tier's
        # kv_scale_shape — a page's scales copy between tiers by reshape.
        assert quant.kv_scale_shape((3, PS, 2, 8)) == (3, 1, 2, 1)

    def test_dequantize_pool_broadcast(self):
        codes = np.arange(-8, 8, dtype=np.int8).reshape(1, 1, 4, 2, 2)
        scales = np.asarray([[[0.5, 2.0]]], np.float32)
        wide = quant.dequantize_kv_pool(codes, scales, np.float32)
        assert wide.shape == codes.shape
        # Head 0 scaled by 0.5, head 1 by 2.0, every slot and lane.
        assert (wide[0, 0, :, 0, :] == codes[0, 0, :, 0, :] * 0.5).all()
        assert (wide[0, 0, :, 1, :] == codes[0, 0, :, 1, :] * 2.0).all()

    def test_write_time_quantization_error_bounded(self):
        # Same workload into an fp engine and a quantized engine: the
        # allocators make identical decisions, so pages correspond 1:1.
        # Every written element must satisfy |deq - fp| <= scale/2 (+
        # a small slack for the decode carry-page double rounding).
        prompts = [_prompt(10 + i, 16) for i in range(2)]
        fp, q8 = _engine(), _engine(kv_quant_hbm="int8")
        for eng in (fp, q8):
            for p in prompts:
                eng.add_request(p, SamplingParams(max_new_tokens=5))
                eng.run_until_complete()
        assert q8.k_pages.dtype == jnp.int8
        wide = quant.dequantize_kv_pool(
            np.asarray(q8.k_pages), np.asarray(q8.k_scales), np.float32
        )
        full = np.asarray(fp.k_pages, np.float32)
        scales = np.asarray(q8.k_scales)[:, :, None, :, None]
        # Pages that survive in the prefix cache — identical page ids in
        # both engines (same allocator, same workload).
        used = sorted(
            idx
            for p in prompts
            for _, _, tier, idx in q8.block_manager.lookup_chain(
                q8.block_manager.token_db.prefix_hashes(p)
            )
            if tier == "tpu_hbm"
        )
        assert used
        for page in used:
            err = np.abs(wide[:, page] - full[:, page])
            assert (err <= scales[:, page] + 1e-6).all()


class TestEngineGreedyParity:
    def _run(self, prompts, **kw):
        eng = _engine(**kw)
        outs = []
        for p in prompts:
            s = eng.add_request(p, SamplingParams(max_new_tokens=5))
            eng.run_until_complete()
            outs.append(s.output_tokens)
        return eng, outs

    def test_quantized_logits_within_quantization_bound(self):
        """What the int8 pool promises: a warm prefill's first-token
        logits — the ones that READ quantized context pages — stay within
        the quantization bound of the full-width engine's, and a cold
        prefill (which reads no pool) is bit-identical. Greedy-token
        equality is NOT promised: on random tiny weights an argmax flips
        on noise this small (the exactness pin is the
        kernel-vs-dequantized-oracle suite above).

        The bound: symmetric int8 with per-page-per-(layer, head) scales
        puts each stored K/V element within scale/2 = 1/254 of its page's
        largest magnitude (~0.4%); through two layers of attention that
        measured 0.8-1.5% of the logit range on this workload. 5% of the
        full-width logit range leaves ~3x margin and still fails on a
        wrong scale row, a skipped dequant or a page mix-up (those move
        logits by the range itself)."""

        def first_token_logits(**kw):
            eng = _engine(**kw)
            seen = []
            sample = eng._sample

            def spy(logits, seqs):
                seen.append(np.asarray(logits, np.float32)[: len(seqs)])
                return sample(logits, seqs)

            eng._sample = spy
            base = _prompt(70, 32)
            cached = []
            for p in (
                base,  # cold
                base + _prompt(71, 8),  # warm: whole prompt 0 from cache
                base[:16] + _prompt(72, 16),  # warm: half of it
            ):
                s = eng.add_request(p, SamplingParams(max_new_tokens=5))
                eng.run_until_complete()
                assert len(s.output_tokens) == 5
                cached.append(s.num_cached_prompt)
            return eng, seen, cached

        _, ref, ref_cached = first_token_logits()
        eng, qt, qt_cached = first_token_logits(kv_quant_hbm="int8")
        assert qt_cached == ref_cached == [0, 32, 16]
        np.testing.assert_array_equal(qt[0], ref[0])  # cold: no pool read
        for got, want in zip(qt[1:], ref[1:]):
            assert np.isfinite(got).all()
            bound = 0.05 * np.abs(want).max()
            assert 0 < np.abs(got - want).max() <= bound
        assert eng.k_pages.dtype == jnp.int8
        assert eng.k_scales.shape == (
            TINY_LLAMA.n_layers, 64, TINY_LLAMA.n_kv_heads
        )

    def test_spill_bring_back_is_code_exact(self):
        # Satellite 2: under KV_QUANT_HBM the host tier stores the SAME
        # int8 codes as HBM — spill and bring-back copy codes + scales
        # directly (no dequant→requant), so a round trip through host
        # DRAM reproduces the no-spill quantized outputs exactly.
        prompts = [_prompt(70 + i, 16) for i in range(3)]
        prompts.append(prompts[0])
        _, base = self._run(prompts, kv_quant_hbm="int8")
        eng, spilled = self._run(
            prompts, total_pages=12, host_pages=32, kv_quant_hbm="int8"
        )
        assert spilled == base
        assert eng._host_k.dtype == np.int8  # ladder is all-int8
        assert eng.block_manager.host_stats["spilled"] > 0
        assert eng.block_manager.host_stats["restored"] > 0

    def test_preemption_refold_completes_under_knob(self):
        # Pool sized so concurrent decode growth preempts: the refold
        # (prompt-folding re-prefill) rewrites pages through the
        # quantized scatter and everything still finishes with stable
        # output accounting.
        eng = _engine(total_pages=9, decode_batch=2, kv_quant_hbm="int8")
        pa = _prompt(50, 10)
        a = eng.add_request(list(pa), SamplingParams(max_new_tokens=12))
        b = eng.add_request(_prompt(51, 10), SamplingParams(max_new_tokens=12))
        done = eng.run_until_complete()
        assert len(done) == 2
        assert len(a.generated_tokens) == 12
        assert len(b.generated_tokens) == 12
        assert a.all_tokens[: a.user_prompt_len] == pa


class TestMixedFleetTransfer:
    def _warm(self, prompt, **kw):
        eng = _engine(**kw)
        eng.add_request(prompt, SamplingParams(max_new_tokens=4))
        eng.run_until_complete()
        return eng

    def _roundtrip(self, blocks):
        dec, complete, err = protocol.decode_response(
            protocol.encode_response(blocks, True)
        )
        assert err is None and complete
        return dec

    def _cold_ref(self, prompt):
        cold = _engine()
        s = cold.add_request(prompt, SamplingParams(max_new_tokens=4))
        cold.run_until_complete()
        return s.output_tokens

    def test_quantized_pod_exports_stored_codes(self):
        prompt = _prompt(200, 24)
        src = self._warm(prompt, kv_quant_hbm="int8")
        hashes = src.block_manager.token_db.prefix_hashes(prompt)
        blocks = src.export_kv_blocks(hashes)
        assert blocks and all(b.quant == "int8" for b in blocks)
        # Wire payload is the stored codes: one byte per element, scales
        # in the host-tier layout — no widening on the export path.
        assert len(blocks[0].k_data) == int(np.prod(blocks[0].shape))
        assert len(blocks[0].k_scale) == (
            int(np.prod(quant.kv_scale_shape(tuple(blocks[0].shape)))) * 4
        )

    def test_quantized_to_legacy_peer(self):
        prompt = _prompt(200, 24)
        src = self._warm(prompt, kv_quant_hbm="int8")
        hashes = src.block_manager.token_db.prefix_hashes(prompt)
        wire = self._roundtrip(src.export_kv_blocks(hashes))
        tgt = _engine()  # legacy: dequantizes into its full-width pool
        assert tgt.import_kv_blocks(wire) == len(wire)
        s = tgt.add_request(prompt, SamplingParams(max_new_tokens=4))
        tgt.run_until_complete()
        assert s.num_cached_prompt > 0
        assert s.output_tokens == self._cold_ref(prompt)

    def test_legacy_peer_to_quantized_pod(self):
        prompt = _prompt(201, 24)
        src = self._warm(prompt)  # full-width wire payload
        hashes = src.block_manager.token_db.prefix_hashes(prompt)
        wire = self._roundtrip(src.export_kv_blocks(hashes))
        assert all(b.quant is None for b in wire)
        tgt = _engine(kv_quant_hbm="int8")  # quantizes at page commit
        assert tgt.import_kv_blocks(wire) == len(wire)
        s = tgt.add_request(prompt, SamplingParams(max_new_tokens=4))
        tgt.run_until_complete()
        assert s.num_cached_prompt > 0
        assert s.output_tokens == self._cold_ref(prompt)

    def test_int8_wire_peer_to_quantized_pod(self):
        # kv_quant=int8 pod (bf16 HBM, int8 wire) → quantized-HBM pod:
        # codes land in the pool directly, never widened in between.
        prompt = _prompt(202, 24)
        src = self._warm(prompt, kv_quant="int8")
        hashes = src.block_manager.token_db.prefix_hashes(prompt)
        wire = self._roundtrip(src.export_kv_blocks(hashes))
        assert all(b.quant == "int8" for b in wire)
        tgt = _engine(kv_quant_hbm="int8")
        assert tgt.import_kv_blocks(wire) == len(wire)
        s = tgt.add_request(prompt, SamplingParams(max_new_tokens=4))
        tgt.run_until_complete()
        assert s.num_cached_prompt > 0
        assert s.output_tokens == self._cold_ref(prompt)


class TestKnobOffPins:
    """KV_QUANT_HBM unset must be bit-identical legacy — the PR 1-14
    knob convention (kvlint: knob-default)."""

    def test_pool_dtype_and_scales(self):
        eng = _engine()
        assert eng.k_pages.dtype == TINY_LLAMA.dtype
        assert eng.k_scales is None and eng.v_scales is None

    def test_wire_unchanged(self):
        prompt = _prompt(210, 24)
        eng = _engine()
        eng.add_request(prompt, SamplingParams(max_new_tokens=4))
        eng.run_until_complete()
        hashes = eng.block_manager.token_db.prefix_hashes(prompt)
        blocks = eng.export_kv_blocks(hashes)
        assert blocks and all(b.quant is None for b in blocks)

    def test_kv_block_bytes(self):
        cfg = TINY_LLAMA
        elems = cfg.n_layers * PS * cfg.n_kv_heads * cfg.hd
        off, on = _engine(), _engine(kv_quant_hbm="int8")
        # Knob off: full-width wire bytes, unchanged by this PR.
        assert off.kv_block_bytes == 2 * elems * jnp.dtype(cfg.dtype).itemsize
        # Knob on: int8 payload + per-(layer, head) f32 scales — the
        # router's cost model must see the real (halved) wire bytes.
        scale_bytes = int(
            np.prod(quant.kv_scale_shape((cfg.n_layers, PS, cfg.n_kv_heads, cfg.hd)))
        ) * 4
        assert on.kv_block_bytes == 2 * (elems + scale_bytes)

    def _stats(self, server):
        server.start()
        out = {}

        async def runner():
            ts = TestServer(server.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                resp = await client.get("/stats")
                out["stats"] = await resp.json()
            finally:
                await client.close()

        try:
            asyncio.run(runner())
        finally:
            server.shutdown()
        return out["stats"]

    def test_stats_block_gated_on_knob(self):
        stats = self._stats(
            PodServer(
                PodServerConfig(
                    model_name=MODEL,
                    pod_identifier="hbmq-pod",
                    publish_events=False,
                    engine=_engine_config(kv_quant_hbm="int8"),
                )
            )
        )
        assert stats["kv_quant_hbm"] == {
            "mode": "int8",
            "total_pages": 64,
            "pool_dtype": "int8",
        }
        off = self._stats(
            PodServer(
                PodServerConfig(
                    model_name=MODEL,
                    pod_identifier="hbmq-pod-off",
                    publish_events=False,
                    engine=_engine_config(),
                )
            )
        )
        assert "kv_quant_hbm" not in off


class TestScopeRejections:
    def test_fp8_is_declared_but_stubbed(self):
        assert "float8_e4m3" in quant.KV_QUANT_HBM_MODES
        with pytest.raises(NotImplementedError, match="float8_e4m3"):
            _engine(kv_quant_hbm="float8_e4m3")

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="kv_quant_hbm"):
            _engine(kv_quant_hbm="fp4")

    def test_sp_rejected(self):
        with pytest.raises(ValueError, match="sp"):
            _engine(kv_quant_hbm="int8", sp=2)

    def test_spec_decode_rejected(self):
        with pytest.raises(ValueError, match="spec_decode"):
            _engine(kv_quant_hbm="int8", spec_decode="prompt_lookup")

    def test_pallas_prefill_rejected_auto_resolves_xla(self):
        with pytest.raises(ValueError, match="xla"):
            _engine(kv_quant_hbm="int8", prefill_attn="pallas")
        eng = _engine(kv_quant_hbm="int8", prefill_attn="auto")
        assert eng.prefill_attn == "xla"
