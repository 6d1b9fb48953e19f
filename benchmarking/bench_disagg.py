"""Disaggregation interference microbenchmark: decode ITL during 2k ingest.

The ISSUE 1/9 trajectory on one number — p90 decode ITL while a 2k-token
prompt ingests:

- **unchunked** (legacy either-or scheduling): every lane stalls for the
  whole prefill — the baseline stall;
- **chunked** (PR 1, ``chunked_prefill_tokens``): the stall is bounded at
  one chunk's compute — the measured 3.78x win this repo's records carry;
- **disagg** (ISSUE 9): the ingest runs on a DEDICATED prefill engine and
  only the finished chain (import install + a one-page continuation
  prefill) ever touches the decode engine — the interference is removed,
  not amortized. Decode lanes are perturbed only inside the handoff
  window, which is what this arm measures.

Method: the mixed/chunked arms reuse ``bench_chunked_interference.run_arm``
verbatim (same lanes, same 2k prompt, same window). The disagg arm runs
the same decode-engine steady state, executes the ingest on a separate
prefill engine (separate hardware in a real fleet — its wall time is
reported as ``prefill_s``/``ttft_s``, not charged to the lanes), then
measures lane ITLs from the chain import until the continuation
(prompt + first token, ``max_new - 1``) finishes on the decode engine.

One JSON line per arm plus a ``comparison`` line with the headline ratios
(disagg vs unchunked, disagg vs chunked). Env knobs: BENCH_MODEL
(smoke|1p4b), BENCH_LONG_LEN, BENCH_CHUNK_BUDGET, BENCH_LANES.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from bench_chunked_interference import run_arm  # noqa: E402  (shared arms)


def run_disagg_arm(
    model_cfg, *, long_len, lanes, page, total_pages, decode_steps,
    interpret, params, max_new=8,
):
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig,
        Engine,
        EngineConfig,
        SamplingParams,
        SchedulerConfig,
    )

    max_len = long_len + 256

    def cfg():
        return EngineConfig(
            model=model_cfg,
            block_manager=BlockManagerConfig(
                total_pages=total_pages, page_size=page
            ),
            scheduler=SchedulerConfig(
                max_prefill_batch=4, max_prefill_tokens=8192
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
    vocab = model_cfg.vocab_size
    dec = Engine(cfg(), params=params)
    pre = Engine(cfg(), params=params)

    lane_seqs = [
        dec.add_request(
            rng.integers(0, vocab, 48).tolist(),
            SamplingParams(max_new_tokens=10_000),
        )
        for _ in range(lanes)
    ]
    while any(s.num_generated == 0 for s in lane_seqs):
        dec.step()
    # Warm both engines' shapes with a same-length throwaway ingest +
    # handoff so the measured window never hits an XLA compile.
    warm_prompt = rng.integers(0, vocab, long_len).tolist()
    warm = pre.add_request(warm_prompt, SamplingParams(max_new_tokens=1))
    while not warm.is_finished():
        pre.step()
    hashes = pre.block_manager.token_db.prefix_hashes(warm_prompt)
    dec.import_kv_blocks(pre.export_kv_blocks(hashes))
    warm_cont = dec.add_request(
        warm_prompt + warm.generated_tokens,
        SamplingParams(max_new_tokens=max_new - 1),
    )
    while not warm_cont.is_finished():
        dec.step()
    for _ in range(4):
        dec.step()

    # Ingest on the DEDICATED prefill engine (separate hardware in a real
    # fleet): its wall time is the request's TTFT side, not lane stall.
    long_prompt = rng.integers(0, vocab, long_len).tolist()
    t_pre0 = time.perf_counter()
    long_seq = pre.add_request(long_prompt, SamplingParams(max_new_tokens=1))
    while not long_seq.is_finished():
        pre.step()
    prefill_s = time.perf_counter() - t_pre0

    # The handoff window: chain export/import + continuation — the ONLY
    # part of the ingest a decode lane can feel.
    t0 = time.perf_counter()
    last_commit = {s.seq_id: t0 for s in lane_seqs}
    gen_at = {s.seq_id: s.num_generated for s in lane_seqs}
    tok0 = sum(s.num_generated for s in lane_seqs)
    hashes = pre.block_manager.token_db.prefix_hashes(long_prompt)
    blocks = pre.export_kv_blocks(hashes)
    imported = dec.import_kv_blocks(blocks)
    handoff_s = time.perf_counter() - t0
    cont = dec.add_request(
        long_prompt + long_seq.generated_tokens,
        SamplingParams(max_new_tokens=max_new - 1),
    )
    itl = []
    while not cont.is_finished() and dec.has_work:
        dec.step()
        now = time.perf_counter()
        for s in lane_seqs:
            d = s.num_generated - gen_at[s.seq_id]
            if d > 0:
                dt = (now - last_commit[s.seq_id]) / d
                itl.extend([dt] * d)
                last_commit[s.seq_id] = now
                gen_at[s.seq_id] = s.num_generated
    wall = time.perf_counter() - t0
    total_tok = (
        sum(s.num_generated for s in lane_seqs) - tok0
        + cont.num_generated
        + long_seq.num_generated
    )
    return {
        "p90_itl_ms": float(np.percentile(itl, 90) * 1e3) if itl else None,
        "mean_itl_ms": float(np.mean(itl) * 1e3) if itl else None,
        "itl_samples": len(itl),
        # User-visible first token comes from the prefill engine.
        "ttft_s": round(long_seq.ttft, 4) if long_seq.ttft else None,
        "prefill_s": round(prefill_s, 3),
        "handoff_s": round(handoff_s, 4),
        "handoff_blocks": imported,
        "decode_cached_tokens": cont.num_cached_prompt,
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
        # prompt; results/disagg.md records this config. The pool holds
        # TWO 128-page chains plus lanes (every arm gets the same pool):
        # imports never evict, so a pool sized below warmup-chain +
        # measured-chain would silently truncate the handoff and charge
        # the decode engine a suffix prefill no real deployment pays.
        long_len, lanes, page, total_pages = 2048, 3, 16, 512
        budget, decode_steps, interpret = 128, 1, True

    long_len = int(os.environ.get("BENCH_LONG_LEN", long_len))
    budget = int(os.environ.get("BENCH_CHUNK_BUDGET", budget))
    lanes = int(os.environ.get("BENCH_LANES", lanes))

    params = llama.init_params(jax.random.PRNGKey(0), model_cfg)
    jax.block_until_ready(params)

    kw = dict(
        long_len=long_len, lanes=lanes, page=page, total_pages=total_pages,
        budget=budget, decode_steps=decode_steps, interpret=interpret,
        params=params,
    )
    arms = {
        "unchunked": run_arm(False, model_cfg, **kw),
        "chunked": run_arm(True, model_cfg, **kw),
        "disagg": run_disagg_arm(
            model_cfg, long_len=long_len, lanes=lanes, page=page,
            total_pages=total_pages, decode_steps=decode_steps,
            interpret=interpret, params=params,
        ),
    }
    for arm, res in arms.items():
        print(
            json.dumps(
                {
                    "metric": "disagg_interference",
                    "arm": arm,
                    "chunked_prefill_tokens": budget if arm == "chunked" else None,
                    "long_len": long_len,
                    "lanes": lanes,
                    "model": mode,
                    "backend": jax.default_backend(),
                    **res,
                }
            )
        )
    un, ch, dg = arms["unchunked"], arms["chunked"], arms["disagg"]
    if un["p90_itl_ms"] and ch["p90_itl_ms"] and dg["p90_itl_ms"]:
        print(
            json.dumps(
                {
                    "metric": "disagg_interference_comparison",
                    "p90_itl_unchunked_over_disagg_x": round(
                        un["p90_itl_ms"] / dg["p90_itl_ms"], 2
                    ),
                    "p90_itl_chunked_over_disagg_x": round(
                        ch["p90_itl_ms"] / dg["p90_itl_ms"], 2
                    ),
                    "p90_itl_unchunked_over_chunked_x": round(
                        un["p90_itl_ms"] / ch["p90_itl_ms"], 2
                    ),
                    "disagg_ttft_over_unchunked": (
                        round(dg["ttft_s"] / un["ttft_s"], 2)
                        if un.get("ttft_s") and dg.get("ttft_s")
                        else None
                    ),
                }
            )
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
