"""Long-prompt interference microbenchmark: decode ITL during prompt ingest.

The stall this measures: with legacy either-or scheduling, one long prompt's
prefill occupies a whole engine step, so every running decode lane's
inter-token latency (ITL) spikes by the full prefill wall time — exactly
when the fleet is busiest. Chunked prefill (`chunked_prefill_tokens`)
splits the ingest into budget-sized chunks and carries the decode lanes in
the same (mixed) step, bounding the spike at one chunk's compute.

Method: start a batch of decode lanes, reach steady state, inject one
long prompt, and record every lane's inter-token wall times from injection
until the long prompt finishes. Reported per arm (unchunked vs chunked):

- ``p90_itl_ms`` — p90 of decode ITL samples in the interference window
  (the stall tail the ROADMAP north-star cares about);
- ``ttft_s`` — the long prompt's time to first token (the trade-off side:
  chunking defers the long prompt's completion);
- ``total_tok_s`` — all tokens committed in the window / window wall time
  (chunking must not buy ITL with meaningful total-throughput loss).

One JSON line per arm plus a ``comparison`` line with the headline ratios.

Env knobs: BENCH_MODEL (smoke|1p4b), BENCH_LONG_LEN, BENCH_CHUNK_BUDGET,
BENCH_LANES, BENCH_DECODE_STEPS (fused burst size; 1 = cleanest ITL).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_arm(
    chunked, model_cfg, *, long_len, lanes, page, total_pages, budget,
    decode_steps, interpret, params,
):
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig,
        Engine,
        EngineConfig,
        SamplingParams,
        SchedulerConfig,
    )

    max_len = long_len + 256
    cfg = EngineConfig(
        model=model_cfg,
        block_manager=BlockManagerConfig(total_pages=total_pages, page_size=page),
        scheduler=SchedulerConfig(
            max_prefill_batch=4,
            max_prefill_tokens=8192,
            chunked_prefill_tokens=budget if chunked else None,
        ),
        max_model_len=max_len,
        decode_batch_size=lanes + 1,
        decode_steps_per_iter=decode_steps,
        prefill_bucket=64,
        prefill_ctx_bucket=-(-max_len // page),
        decode_pages_bucket=-(-max_len // page),
        interpret=interpret,
    )
    rng = np.random.default_rng(7)
    eng = Engine(cfg, params=params)

    vocab = model_cfg.vocab_size
    lane_seqs = [
        eng.add_request(
            rng.integers(0, vocab, 48).tolist(),
            SamplingParams(max_new_tokens=10_000),
        )
        for _ in range(lanes)
    ]
    # Steady state: every lane decoding, shapes warm. The warm long prompt
    # has the SAME length as the measured one so every executable the
    # interference window hits (whole-prompt prefill, every chunk/ctx
    # width, mixed-step decode) is compiled before timing starts.
    while any(s.num_generated == 0 for s in lane_seqs):
        eng.step()
    warm = eng.add_request(
        rng.integers(0, vocab, long_len).tolist(),
        SamplingParams(max_new_tokens=1),
    )
    while not warm.is_finished():
        eng.step()
    for _ in range(4):
        eng.step()

    # Interference window: inject the long prompt, sample lane ITLs until
    # it finishes generating.
    long_seq = eng.add_request(
        rng.integers(0, vocab, long_len).tolist(),
        SamplingParams(max_new_tokens=8),
    )
    t0 = time.perf_counter()
    last_commit = {s.seq_id: t0 for s in lane_seqs}
    gen_at = {s.seq_id: s.num_generated for s in lane_seqs}
    itl = []
    tok0 = sum(s.num_generated for s in lane_seqs)
    while not long_seq.is_finished() and eng.has_work:
        eng.step()
        now = time.perf_counter()
        for s in lane_seqs:
            d = s.num_generated - gen_at[s.seq_id]
            if d > 0:
                # Fused bursts commit d tokens at once; attribute the
                # inter-commit wall evenly.
                dt = (now - last_commit[s.seq_id]) / d
                itl.extend([dt] * d)
                last_commit[s.seq_id] = now
                gen_at[s.seq_id] = s.num_generated
    wall = time.perf_counter() - t0
    total_tok = (
        sum(s.num_generated for s in lane_seqs) - tok0 + long_seq.num_generated
    )
    return {
        "p90_itl_ms": float(np.percentile(itl, 90) * 1e3) if itl else None,
        "mean_itl_ms": float(np.mean(itl) * 1e3) if itl else None,
        "itl_samples": len(itl),
        "ttft_s": round(long_seq.ttft, 4) if long_seq.ttft else None,
        "total_tok_s": round(total_tok / wall, 2),
        "window_s": round(wall, 3),
    }


def main() -> int:
    import jax

    from llm_d_kv_cache_manager_tpu.models import llama

    # "smoke" only when asked for; the chip config on a machine with no
    # chip fails at engine construction.
    mode = os.environ.get("BENCH_MODEL", "1p4b")
    if mode == "1p4b":
        import jax.numpy as jnp

        from llm_d_kv_cache_manager_tpu.models.llama import LlamaConfig

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
        long_len, lanes, page, total_pages = 2048, 6, 16, 2048
        budget, decode_steps, interpret = 256, 1, False
    else:
        model_cfg = llama.TINY_LLAMA
        # 2k ingest even in smoke: the stall under test IS the long
        # prompt; results/chunked_prefill.md records this config.
        long_len, lanes, page, total_pages = 2048, 3, 16, 256
        budget, decode_steps, interpret = 128, 1, True

    long_len = int(os.environ.get("BENCH_LONG_LEN", long_len))
    budget = int(os.environ.get("BENCH_CHUNK_BUDGET", budget))
    lanes = int(os.environ.get("BENCH_LANES", lanes))
    decode_steps = int(os.environ.get("BENCH_DECODE_STEPS", decode_steps))

    params = llama.init_params(jax.random.PRNGKey(0), model_cfg)
    jax.block_until_ready(params)

    kw = dict(
        long_len=long_len, lanes=lanes, page=page, total_pages=total_pages,
        budget=budget, decode_steps=decode_steps, interpret=interpret,
        params=params,
    )
    arms = {}
    for chunked in (False, True):
        arms[chunked] = run_arm(chunked, model_cfg, **kw)
        print(
            json.dumps(
                {
                    "metric": "long_prompt_interference",
                    "arm": "chunked" if chunked else "unchunked",
                    "chunked_prefill_tokens": budget if chunked else None,
                    "long_len": long_len,
                    "lanes": lanes,
                    "model": mode,
                    "backend": jax.default_backend(),
                    **arms[chunked],
                }
            )
        )
    un, ch = arms[False], arms[True]
    if un["p90_itl_ms"] and ch["p90_itl_ms"]:
        print(
            json.dumps(
                {
                    "metric": "long_prompt_interference_comparison",
                    "p90_itl_improvement_x": round(
                        un["p90_itl_ms"] / ch["p90_itl_ms"], 2
                    ),
                    "throughput_ratio_chunked_over_unchunked": round(
                        ch["total_tok_s"] / max(un["total_tok_s"], 1e-9), 3
                    ),
                    "ttft_ratio_chunked_over_unchunked": (
                        round(ch["ttft_s"] / un["ttft_s"], 2)
                        if un.get("ttft_s") and ch.get("ttft_s")
                        else None
                    ),
                }
            )
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
