"""Decode fast path microbenchmark (ISSUE 7): where does step time go?

Three measurements on the real stack, one JSON line each:

- **decode arm** — a single engine decodes a fixed token budget with the
  fast path off vs on (``decode_fused_sampling`` + ``decode_pipeline``),
  reporting tok/s and the step-phase decomposition
  (schedule/prefill/decode/sample/gather/publish). The fusion evidence is
  the ``sample`` phase: the blocking share of the sampled-token
  device_get, which the fast path's async D2H + device-resident chaining
  collapses to ~0.
- **spec arm** — the same engine with ``spec_decode="prompt_lookup"`` on
  an EXTRACTIVE workload (the prompt repeats an n-gram pattern, the
  regime prompt lookup exists for), reporting acceptance rate and tok/s.
- **pull arm** — a 2-pod ZMQ fleet: the cold pod is mid-decode on an
  unrelated request when a pull-routed request arrives (``ASYNC_PULL``);
  the reported ``hidden_s``/``exposed_s`` split (from the pull-overlap
  decomposition) shows how much of the transfer the decode work hid.

Env knobs: BENCH_FASTPATH_TOKENS (decode budget per sequence, default
48), BENCH_FASTPATH_LANES (decode lanes, default 4).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _engine_cfg(**kw):
    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig,
        EngineConfig,
        SchedulerConfig,
    )

    kw.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    # Tiny model on the CPU interpreter by construction: a host-path
    # decomposition, never a device measurement.
    return EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(total_pages=256, page_size=4),
        max_model_len=128,
        decode_batch_size=4,
        prefill_bucket=8,
        interpret=True,
        **kw,
    )


def decode_arm(max_new: int, lanes: int) -> dict:
    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
    from llm_d_kv_cache_manager_tpu.server import Engine, SamplingParams

    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(0, TINY_LLAMA.vocab_size, 12).tolist() for _ in range(lanes)
    ]
    out = {}
    outputs = {}
    for label, kw in (
        ("legacy", {}),
        ("fastpath", dict(decode_fused_sampling=True, decode_pipeline=True)),
    ):
        eng = Engine(_engine_cfg(**kw))
        # Warm the jit caches so the measured pass is steady-state — TWO
        # rounds, because the measured pass prefills warm (cached-prefix)
        # shapes: a single cold round would leave the warm-prefill
        # executable to compile inside whichever arm runs first and
        # poison the A/B.
        for _ in range(2):
            for p in prompts:
                eng.add_request(p, SamplingParams(max_new_tokens=4))
            eng.run_until_complete()
        eng.obs_step_timing = True
        seqs = [
            eng.add_request(p, SamplingParams(max_new_tokens=max_new))
            for p in prompts
        ]
        t0 = time.perf_counter()
        eng.run_until_complete()
        wall = time.perf_counter() - t0
        toks = sum(s.num_generated for s in seqs)
        outputs[label] = [s.generated_tokens for s in seqs]
        out[label] = {
            "tok_s": round(toks / wall, 2),
            "wall_s": round(wall, 3),
            "phases": {
                k: round(v, 4) if isinstance(v, float) else v
                for k, v in eng.step_stats.items()
            },
        }
    assert outputs["legacy"] == outputs["fastpath"], "greedy parity violated"
    out["speedup"] = round(out["fastpath"]["tok_s"] / out["legacy"]["tok_s"], 3)
    out["sample_s_legacy"] = out["legacy"]["phases"]["sample_s"]
    out["sample_s_fastpath"] = out["fastpath"]["phases"]["sample_s"]
    return out


def spec_arm(max_new: int) -> dict:
    """Prompt-lookup speculation on an extractive prompt: the context
    repeats a short token pattern, so proposals echo the prompt and
    acceptance is non-trivial (random-token workloads would pin it at 0)."""
    from llm_d_kv_cache_manager_tpu.server import Engine, SamplingParams

    pattern = [11, 23, 42, 7, 99, 5, 64, 31]
    prompt = (pattern * 6)[:44]  # repeated n-grams: lookup's home turf
    out = {}
    for label, kw in (
        ("plain", {}),
        ("spec", dict(spec_decode="prompt_lookup", spec_k=4)),
    ):
        eng = Engine(_engine_cfg(**kw))
        eng.add_request(list(prompt), SamplingParams(max_new_tokens=4))
        eng.run_until_complete()  # warm jit caches
        seq = eng.add_request(list(prompt), SamplingParams(max_new_tokens=max_new))
        t0 = time.perf_counter()
        eng.run_until_complete()
        wall = time.perf_counter() - t0
        out[label] = {
            "tok_s": round(seq.num_generated / wall, 2),
            "tokens": seq.generated_tokens,
        }
        if label == "spec":
            st = eng.spec_stats
            out["acceptance_rate"] = (
                round(st["accepted"] / st["proposed"], 4)
                if st["proposed"]
                else None
            )
            out["proposed"] = st["proposed"]
            out["accepted"] = st["accepted"]
            out["bursts"] = st["bursts"]
    assert out["plain"]["tokens"] == out["spec"]["tokens"], "spec parity violated"
    for label in ("plain", "spec"):
        del out[label]["tokens"]
    return out


def pull_arm() -> dict:
    """Async-pull overlap on a live 2-pod fleet: the cold pod is decoding
    an unrelated request when the pull-routed one arrives, so the fetch
    rides under real decode compute."""
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests")
    )
    from conftest import free_tcp_port

    from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
    from llm_d_kv_cache_manager_tpu.server import SamplingParams
    from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

    def pod(pod_id, **kw):
        return PodServer(
            PodServerConfig(
                model_name="tiny-llama",
                pod_identifier=pod_id,
                publish_events=False,
                engine=_engine_cfg(),
                **kw,
            )
        )

    rng = np.random.default_rng(11)
    endpoint = f"tcp://127.0.0.1:{free_tcp_port()}"
    warm = pod("fp-warm", transfer_endpoint=endpoint)
    cold = pod("fp-cold", async_pull=True, obs_metrics=True)
    warm.start(), cold.start()
    try:
        prefix = rng.integers(0, TINY_LLAMA.vocab_size, 32).tolist()
        warm.generate(prefix, SamplingParams(max_new_tokens=2), timeout=300)
        # A full prefill batch queued AHEAD of the pull-routed request:
        # in the blocking world the pull would run before submission and
        # the request would then STILL wait behind these — the async
        # import instead rides under exactly that queue wait (the hidden
        # share below).
        fillers = [
            cold.submit(
                rng.integers(0, TINY_LLAMA.vocab_size, 24).tolist(),
                SamplingParams(max_new_tokens=2),
            )
            for _ in range(4)
        ]
        t0 = time.perf_counter()
        pulled = cold.submit(
            prefix + rng.integers(0, TINY_LLAMA.vocab_size, 4).tolist(),
            SamplingParams(max_new_tokens=4),
            pull_source=endpoint,
        )
        s = pulled.result(timeout=300)
        pull_to_done = time.perf_counter() - t0
        for f in fillers:
            f.result(timeout=300)
        text = (cold.metrics.exposition() or b"").decode()
        hidden = exposed = None
        for line in text.splitlines():
            if line.startswith("kvcache_transfer_pull_overlap_seconds_sum"):
                val = round(float(line.rsplit(" ", 1)[1]), 4)
                if 'kind="hidden"' in line:
                    hidden = val
                elif 'kind="exposed"' in line:
                    exposed = val
        return {
            "imported_blocks": s.num_cached_prompt // 4,
            "cached_prompt_tokens": s.num_cached_prompt,
            "request_wall_s": round(pull_to_done, 3),
            "hidden_s": hidden,
            "exposed_s": exposed,
        }
    finally:
        warm.shutdown(), cold.shutdown()


def main() -> int:
    max_new = int(os.environ.get("BENCH_FASTPATH_TOKENS", "48"))
    lanes = int(os.environ.get("BENCH_FASTPATH_LANES", "4"))
    import jax

    print(
        json.dumps({"arm": "decode", "backend": jax.default_backend(),
                    **decode_arm(max_new, lanes)})
    )
    print(json.dumps({"arm": "spec", **spec_arm(max_new)}))
    print(json.dumps({"arm": "pull", **pull_arm()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
