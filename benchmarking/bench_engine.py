"""Single-chip engine throughput: prefill tok/s and steady-state decode tok/s.

Complements bench.py (routing TTFT) with the absolute serving numbers the
reference reports for its pods (output throughput, `benchmarking/*-capacity`).
Runs the same 1.4B Llama-family bf16 config as bench.py's full mode on one
chip. ``BENCH_MODEL=smoke`` asks for the tiny CPU config (interpreter — a
functional smoke, not a measurement); without it a machine with no chip
fails at engine construction.

Run: ``python benchmarking/bench_engine.py``; one JSON line per measurement.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    import jax
    import jax.numpy as jnp

    from llm_d_kv_cache_manager_tpu.models import llama
    from llm_d_kv_cache_manager_tpu.models.llama import LlamaConfig
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig,
        Engine,
        EngineConfig,
        SamplingParams,
        SchedulerConfig,
    )

    mode = os.environ.get("BENCH_MODEL", "1p4b")
    quantize = None
    if mode == "8b-int8":
        # The real Llama-3-8B architecture, unscaled, weight-only int8
        # (models/quant.py): ~8.3 GB of weights on one v5e chip, leaving
        # room for a 2048-page KV pool (32k tokens at 128 KiB/token).
        model_cfg = llama.LLAMA_3_8B
        quantize = "int8"
        prefill_len, decode_batch, max_new, n_reqs = 2048, 16, 128, 8
        total_pages, page = 2048, 16
        burst = 32
        interpret = False
    elif mode == "1p4b":
        model_cfg = LlamaConfig(
            vocab_size=32_000,
            hidden_size=3072,
            intermediate_size=8192,
            n_layers=12,
            n_heads=24,
            n_kv_heads=8,
            rope_scaling=llama.LLAMA_3_8B.rope_scaling,
            dtype=jnp.bfloat16,
        )
        prefill_len, decode_batch, max_new, n_reqs = 2048, 16, 128, 16
        total_pages, page = 4096, 16
        # Large fused burst amortizes per-dispatch overhead.
        burst = 32
        interpret = False
    else:
        model_cfg = llama.TINY_LLAMA
        prefill_len, decode_batch, max_new, n_reqs = 64, 4, 8, 4
        total_pages, page = 256, 16
        burst = 2
        interpret = True

    decode_batch = int(os.environ.get("BENCH_DECODE_BATCH", decode_batch))
    # BENCH_QUANTIZE=int8: weight-only int8 for ANY mode (decode is
    # weights-bandwidth-bound, so halving weight bytes is the decode lever).
    quantize = os.environ.get("BENCH_QUANTIZE", quantize) or None
    if mode == "8b-int8" and quantize is None:
        raise SystemExit(
            "8b-int8 requires int8 weights: bf16 8B weights + the KV pool "
            "exceed a 16 GB chip (unset BENCH_QUANTIZE or drop the override)"
        )
    if quantize and not mode.endswith("int8"):
        mode = f"{mode}+int8"  # label tracks the weights actually served
    max_len = prefill_len + max_new + page
    # Chunked prefill + mixed steps (BENCH_CHUNKED_PREFILL_TOKENS=N;
    # 0/unset = legacy). Prefill throughput then pays one dispatch per
    # chunk — the cost side of the ITL win bench_chunked_interference.py
    # measures.
    chunked = int(os.environ.get("BENCH_CHUNKED_PREFILL_TOKENS", 0))
    cfg = EngineConfig(
        model=model_cfg,
        block_manager=BlockManagerConfig(total_pages=total_pages, page_size=page),
        scheduler=SchedulerConfig(
            max_prefill_batch=4,
            max_prefill_tokens=8192,
            chunked_prefill_tokens=chunked if chunked > 0 else None,
        ),
        max_model_len=max_len,
        decode_batch_size=decode_batch,
        decode_steps_per_iter=burst,
        prefill_bucket=64,
        prefill_ctx_bucket=-(-max_len // page),
        prefill_attn=os.environ.get("BENCH_PREFILL_ATTN", "auto"),
        interpret=interpret,
    )
    params = llama.init_params(jax.random.PRNGKey(0), model_cfg, quantize=quantize)
    jax.block_until_ready(params)
    rng = np.random.default_rng(0)

    def reqs():
        return [
            rng.integers(0, model_cfg.vocab_size, prefill_len).tolist()
            for _ in range(n_reqs)
        ]

    # Section selection (BENCH_SECTIONS=prefill,decode,spec): re-run one
    # measurement without paying the others' warm/compile/measure time.
    sections = set(
        os.environ.get("BENCH_SECTIONS", "prefill,decode,spec").split(",")
    )

    # Warmup: compile prefill + decode shapes.
    eng = Engine(cfg, params=params)
    for r in reqs()[:2]:
        eng.add_request(r, SamplingParams(max_new_tokens=max_new))
    eng.run_until_complete()
    del eng

    # Prefill throughput: cold engine, time prompt processing only
    # (max_new_tokens=1 → ~pure prefill).
    if "prefill" in sections:
        eng = Engine(cfg, params=params)
        batch = reqs()
        t0 = time.perf_counter()
        for r in batch:
            eng.add_request(r, SamplingParams(max_new_tokens=1))
        eng.run_until_complete()
        dt = time.perf_counter() - t0
        prefill_tps = n_reqs * prefill_len / dt
        print(
            json.dumps(
                {
                    "metric": "prefill_throughput",
                    "value": round(prefill_tps, 1),
                    "unit": "tok/s",
                    "model": mode,
                    "prefill_len": prefill_len,
                    "n_requests": n_reqs,
                    "backend": jax.default_backend(),
                }
            )
        )
        del eng

    # Decode throughput: saturate the decode lanes, measure generated tok/s
    # once prefill is done (prompts short so decode dominates). A throwaway
    # identical round runs first so the timed region never includes XLA
    # compilation of the decode shapes.
    def decode_round(cfg=cfg) -> float:
        eng = Engine(cfg, params=params)
        seqs = [
            eng.add_request(
                rng.integers(0, model_cfg.vocab_size, 64).tolist(),
                SamplingParams(max_new_tokens=max_new),
            )
            for _ in range(decode_batch)
        ]
        while eng.has_work and any(s.num_generated == 0 for s in seqs):
            eng.step()
        # Tokens actually produced inside the timed region, counted over the
        # same sequence set (finished/aborted sequences included).
        gen0 = sum(s.num_generated for s in seqs)
        t0 = time.perf_counter()
        eng.run_until_complete()
        dt = time.perf_counter() - t0
        return (sum(s.num_generated for s in seqs) - gen0) / dt

    from dataclasses import replace

    if "decode" in sections:
        decode_round()  # identical throwaway: compiles every decode shape
        decode_tps = decode_round()
        print(
            json.dumps(
                {
                    "metric": "decode_throughput",
                    "value": round(decode_tps, 1),
                    "unit": "tok/s",
                    "model": mode,
                    "decode_batch": decode_batch,
                    "decode_steps_per_iter": burst,
                    "backend": jax.default_backend(),
                }
            )
        )

    # Pipelined decode: burst N+1 dispatched before burst N commits, hiding
    # per-iteration host work under device execution. Same shapes → no
    # extra compiles.
    if "decode" in sections:
        cfg_pipe = replace(cfg, decode_pipeline=True)
        decode_round(cfg_pipe)  # throwaway (warm page-pool state path)
        decode_pipe_tps = decode_round(cfg_pipe)
        print(
            json.dumps(
                {
                    "metric": "decode_throughput_pipelined",
                    "value": round(decode_pipe_tps, 1),
                    "unit": "tok/s",
                    "model": mode,
                    "decode_batch": decode_batch,
                    "decode_steps_per_iter": burst,
                    "vs_unpipelined": round(
                        decode_pipe_tps / max(decode_tps, 1e-9), 3
                    ),
                    "backend": jax.default_backend(),
                }
            )
        )

    # Speculative decoding (prompt-lookup): only pays off when greedy
    # output echoes the context, so measure on a repetition-heavy workload
    # (prompt = repeated pattern; greedy then tends to continue the cycle)
    # against plain decode on the SAME workload, small batch (the regime
    # where per-dispatch overhead dominates and spec's multi-token commits
    # matter most). BENCH_SPEC=0 skips.
    if "spec" in sections and os.environ.get("BENCH_SPEC", "1") != "0":
        spec_batch = int(os.environ.get("BENCH_SPEC_BATCH", 4))
        # Dedicated rng: the spec workload must be identical whether or
        # not the earlier sections (which consume `rng`) ran.
        spec_rng = np.random.default_rng(1729)
        pattern = spec_rng.integers(0, model_cfg.vocab_size, 12).tolist()

        def spec_round(c) -> tuple[float, dict]:
            eng = Engine(replace(c, decode_batch_size=spec_batch), params=params)
            seqs = [
                eng.add_request(
                    pattern * 5 + pattern[: 2 + i],
                    SamplingParams(max_new_tokens=max_new),
                )
                for i in range(spec_batch)
            ]
            while eng.has_work and any(s.num_generated == 0 for s in seqs):
                eng.step()
            gen0 = sum(s.num_generated for s in seqs)
            t0 = time.perf_counter()
            eng.run_until_complete()
            dt = time.perf_counter() - t0
            return (sum(s.num_generated for s in seqs) - gen0) / dt, dict(
                eng.spec_stats
            )

        cfg_base = replace(cfg, decode_steps_per_iter=1)
        spec_round(cfg_base)  # compile
        base_tps, _ = spec_round(cfg_base)
        # spec_rounds sweep: 1 = the classic one-verify-per-dispatch loop;
        # >1 = fused rounds chained on device (llama.spec_decode_steps),
        # paying one host sync per N verifies.
        rounds_list = [
            int(r)
            for r in os.environ.get("BENCH_SPEC_ROUNDS", "1,4").split(",")
        ]
        for rounds in rounds_list:
            cfg_spec = replace(
                cfg, decode_steps_per_iter=1, spec_decode="prompt_lookup",
                spec_k=4, spec_ngram=3, spec_rounds=rounds,
            )
            spec_round(cfg_spec)  # compile verify shapes
            spec_tps, stats = spec_round(cfg_spec)
            acc = stats["accepted"] / max(stats["proposed"], 1)
            print(
                json.dumps(
                    {
                        "metric": "decode_throughput_spec",
                        "value": round(spec_tps, 1),
                        "unit": "tok/s",
                        "model": mode,
                        "decode_batch": spec_batch,
                        "workload": "repetitive",
                        "spec_rounds": rounds,
                        "plain_same_workload": round(base_tps, 1),
                        "vs_plain": round(spec_tps / max(base_tps, 1e-9), 3),
                        "acceptance_rate": round(acc, 3),
                        "verify_steps": stats["verify_steps"],
                        "bursts": stats["bursts"],
                        "backend": jax.default_backend(),
                    }
                )
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
