"""MoE dispatch benchmark: routed (grouped ragged matmuls) vs masked-dense.

Measures, at the real Qwen3-30B-A3B expert geometry (128 experts, top-8,
hidden 2048, expert width 768, bf16), one MoE FFN layer:

- XLA cost-model FLOPs for both dispatches (the complexity-class claim:
  routed ~E/k lower), asserted >8x on TPU;
- wall time per call at prefill-shaped (batched tokens) and decode-shaped
  (few tokens) inputs, compile excluded.

Run on the chip: ``python benchmarking/bench_moe.py``; JSON line output.
``BENCH_SMOKE=1`` asks for the CPU geometry check (tiny config,
interpreter) explicitly; without it a machine with no chip fails when the
gmm kernel is requested.
"""

from __future__ import annotations

import dataclasses
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
    from llm_d_kv_cache_manager_tpu.models.llama import _moe_mlp, init_params

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    if not smoke:
        cfg = dataclasses.replace(
            llama.QWEN3_30B_A3B, n_layers=1, vocab_size=1024
        )
        shapes = {"prefill": (1, 2048), "decode": (16, 1)}
        reps = 20
    else:  # CPU smoke: geometry only (ragged_dot lowers loop-dense on CPU)
        cfg = dataclasses.replace(
            llama.TINY_QWEN3_MOE, n_experts=16, n_experts_per_tok=4
        )
        shapes = {"prefill": (1, 64), "decode": (4, 1)}
        reps = 3

    dense_cfg = dataclasses.replace(cfg, moe_dispatch="dense")
    xla_cfg = dataclasses.replace(cfg, moe_gmm="xla")
    gmm_cfg = dataclasses.replace(cfg, moe_gmm="kernel")
    # BENCH_QUANT=int8: int8 EXPERT stacks (the opt-in path — the default
    # skips experts because this very benchmark showed the dequant doesn't
    # fuse into ragged_dot).
    quant = os.environ.get("BENCH_QUANT") or None
    params = init_params(
        jax.random.PRNGKey(0), cfg, quantize=quant, quantize_experts=bool(quant)
    )
    layer = params["layers"][0]
    rng = np.random.default_rng(0)

    for shape_name, (b, s) in shapes.items():
        x = jnp.asarray(
            rng.standard_normal((b, s, cfg.hidden_size)), cfg.dtype
        )
        row = {
            "metric": f"moe_dispatch_{shape_name}",
            "unit": "ms/call",
            "tokens": b * s,
            "n_experts": cfg.n_experts,
            "top_k": cfg.n_experts_per_tok,
            "quantize": quant,
            "backend": jax.default_backend(),
        }
        variants = (
            ("routed", xla_cfg),  # ragged_dot (rounds 1-3 baseline)
            ("gmm", gmm_cfg),  # Pallas grouped-matmul kernel (round 4)
            ("dense", dense_cfg),
        )
        outs = {}
        for name, c in variants:
            fn = jax.jit(
                lambda p, v, c=c: _moe_mlp(p, c, v, interpret=smoke)
            )
            compiled = fn.lower(layer, x).compile()
            an = compiled.cost_analysis()
            an = an[0] if isinstance(an, list) else an
            outs[name] = np.asarray(fn(layer, x))  # warm + full fetch
            # Chain each call's output into the next input AND fence with a
            # device->host fetch: repeated identical dispatches can be
            # elided/overlapped by the runtime. MIN of several timing
            # rounds rejects sporadic host stalls.
            best = float("inf")
            for _ in range(3):
                y = x
                t0 = time.perf_counter()
                for _ in range(reps):
                    y = fn(layer, y)
                np.asarray(y[0, 0, :1])
                best = min(best, (time.perf_counter() - t0) / reps * 1e3)
            row[name + "_ms"] = round(best, 3)
            row[name + "_gflops"] = round(an.get("flops", 0) / 1e9, 3)
        row["value"] = row["gmm_ms"]
        row["gmm_speedup_vs_routed"] = round(row["routed_ms"] / row["gmm_ms"], 2)
        row["speedup_vs_dense"] = round(row["dense_ms"] / row["gmm_ms"], 2)
        # Effective grouped-matmul throughput (the 3 FFN matmuls' useful
        # FLOPs over the kernel's wall time).
        if row["routed_gflops"]:
            row["gmm_effective_tflops"] = round(
                row["routed_gflops"] / row["gmm_ms"], 1
            )
            row["flops_ratio_dense_over_routed"] = round(
                row["dense_gflops"] / row["routed_gflops"], 1
            )
        print(json.dumps(row))
        # On-chip numerics: the kernel must match the ragged_dot oracle
        # (interpret-mode tests can't catch Mosaic miscompiles — the
        # repo's own round-1 lesson).
        scale = np.abs(outs["routed"].astype(np.float32)).max() + 1e-9
        err = (
            np.abs(
                outs["gmm"].astype(np.float32) - outs["routed"].astype(np.float32)
            ).max()
            / scale
        )
        tol = 5e-2 if quant else 2e-2
        assert err < tol, f"gmm-vs-ragged mismatch: rel err {err:.4f} ({shape_name})"
        if not smoke and shape_name == "prefill":
            assert row["flops_ratio_dense_over_routed"] > 8, row
    return 0


if __name__ == "__main__":
    sys.exit(main())
