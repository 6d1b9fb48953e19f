"""KV-transfer microbenchmark: the transfer-vs-recompute crossover.

The router's pull-then-compute decision only pays when shipping a prefix's
KV pages beats recomputing them. This benchmark measures both sides on the
real stack, per prefix length:

- **recompute arm** — a cold engine prefills the whole prompt (the
  engine's measured prefill dispatch wall time);
- **pull arm** — a warm engine exports the prefix chain, the payload rides
  the real msgpack wire encoding, a cold engine imports it and prefills
  only the suffix (export + encode/decode + import + suffix prefill wall
  time). In-process transport measures the serialization/commit overhead
  floor; for a network link, add ``wire_bytes / link_bandwidth`` — the
  reported ``wire_mb`` makes that arithmetic one division.

The **crossover** is the smallest prefix (in blocks) where the pull arm
wins. Below it, routing should queue or recompute; above it, pulling is
the better use of the fleet (results/kv_transfer.md for recorded numbers).

One JSON line per prefix length plus a ``crossover`` summary line.

Env knobs: BENCH_MODEL (smoke|1p4b), BENCH_TRANSFER_PREFIX_BLOCKS
(comma-separated block counts), BENCH_TRANSFER_LINK_GBPS (report modeled
network pull time at this link rate; default 0 = in-process only).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _make_engine(engine_cfg, params):
    from llm_d_kv_cache_manager_tpu.server import Engine

    return Engine(engine_cfg, params=params)


def measure_point(
    n_blocks, *, engine_cfg, params, page, suffix_len, vocab, link_bytes_s=0.0
):
    """One crossover point: returns the timing dict for ``n_blocks`` of
    warm prefix."""
    from llm_d_kv_cache_manager_tpu.kvcache.transfer.protocol import (
        decode_response,
        encode_response,
    )
    from llm_d_kv_cache_manager_tpu.server.sequence import SamplingParams

    rng = np.random.default_rng(1000 + n_blocks)
    prefix = rng.integers(0, vocab, n_blocks * page).tolist()
    suffix = rng.integers(0, vocab, suffix_len).tolist()
    prompt = prefix + suffix

    # Warm the source pod with the prefix.
    warm = _make_engine(engine_cfg, params)
    warm.add_request(prefix, SamplingParams(max_new_tokens=1))
    warm.run_until_complete()
    hashes = warm.block_manager.token_db.prefix_hashes(prompt)

    # Recompute arm: cold prefill of the full prompt.
    cold_a = _make_engine(engine_cfg, params)
    t0 = time.perf_counter()
    cold_a.add_request(prompt, SamplingParams(max_new_tokens=1))
    cold_a.run_until_complete()
    t_recompute = time.perf_counter() - t0

    # Pull arm: export -> wire round-trip -> import -> suffix prefill.
    cold_b = _make_engine(engine_cfg, params)
    t0 = time.perf_counter()
    blocks = warm.export_kv_blocks(hashes)
    payload = encode_response(blocks, True)
    blocks_rt, _, _ = decode_response(payload)
    imported = cold_b.import_kv_blocks(blocks_rt)
    cold_b.add_request(prompt, SamplingParams(max_new_tokens=1))
    cold_b.run_until_complete()
    t_pull = time.perf_counter() - t0
    assert imported == n_blocks, (imported, n_blocks)

    wire_bytes = sum(b.wire_bytes for b in blocks)
    t_link = wire_bytes / link_bytes_s if link_bytes_s else 0.0
    return {
        "prefix_blocks": n_blocks,
        "prefix_tokens": len(prefix),
        "wire_mb": round(wire_bytes / 1e6, 3),
        "t_recompute_s": round(t_recompute, 4),
        "t_pull_s": round(t_pull, 4),
        "t_pull_plus_link_s": round(t_pull + t_link, 4),
        "pull_speedup": round(t_recompute / max(t_pull + t_link, 1e-9), 3),
    }


def measure_crossover(engine_cfg, params, *, page, vocab, prefix_blocks, link_gbps=0.0):
    """Sweep prefix lengths; returns (points, crossover_blocks)."""
    link_bytes_s = link_gbps * 1e9 / 8 if link_gbps else 0.0
    points = []
    for n_blocks in prefix_blocks:
        points.append(
            measure_point(
                n_blocks,
                engine_cfg=engine_cfg,
                params=params,
                page=page,
                suffix_len=page,
                vocab=vocab,
                link_bytes_s=link_bytes_s,
            )
        )
    crossover = next(
        (p["prefix_blocks"] for p in points if p["pull_speedup"] > 1.0), None
    )
    return points, crossover


def main() -> int:
    import jax
    import jax.numpy as jnp

    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA, llama
    from llm_d_kv_cache_manager_tpu.models.llama import LlamaConfig
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig,
        EngineConfig,
        SchedulerConfig,
    )

    # Smoke only when asked for; the chip config on a machine with no
    # chip fails at engine construction.
    smoke = os.environ.get("BENCH_SMOKE", "") == "1"
    if smoke:
        model_cfg, page, total_pages = TINY_LLAMA, 4, 512
        prefix_blocks = [1, 2, 4, 8, 16]
        interpret = True
    else:
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
        page, total_pages = 16, 2048
        prefix_blocks = [4, 16, 64, 128, 256]
        interpret = False
    env_blocks = os.environ.get("BENCH_TRANSFER_PREFIX_BLOCKS", "")
    if env_blocks:
        prefix_blocks = [int(b) for b in env_blocks.split(",")]
    link_gbps = float(os.environ.get("BENCH_TRANSFER_LINK_GBPS", "0"))

    max_blocks = max(prefix_blocks)
    engine_cfg = EngineConfig(
        model=model_cfg,
        block_manager=BlockManagerConfig(total_pages=total_pages, page_size=page),
        scheduler=SchedulerConfig(max_prefill_batch=2),
        max_model_len=(max_blocks + 4) * page,
        decode_batch_size=2,
        prefill_bucket=8 if smoke else 64,
        interpret=interpret,
    )
    params = llama.init_params(jax.random.PRNGKey(0), model_cfg)
    jax.block_until_ready(params)
    # Warmup sweep: every prefix length hits its own bucketed prefill
    # shapes — compile them all outside the timed sweep, or each point's
    # first arm eats an XLA compile and the crossover is meaningless.
    for n_blocks in prefix_blocks:
        measure_point(
            n_blocks,
            engine_cfg=engine_cfg,
            params=params,
            page=page,
            suffix_len=page,
            vocab=model_cfg.vocab_size,
        )

    points, crossover = measure_crossover(
        engine_cfg,
        params,
        page=page,
        vocab=model_cfg.vocab_size,
        prefix_blocks=prefix_blocks,
        link_gbps=link_gbps,
    )
    for p in points:
        print(json.dumps(p))
    print(
        json.dumps(
            {
                "metric": "kv_transfer_crossover_blocks",
                "value": crossover,
                "backend": jax.default_backend(),
                "smoke": smoke,
                "page_size": page,
                "link_gbps": link_gbps,
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
