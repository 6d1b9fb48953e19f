"""Flash-prefill kernel block-size sweep (op level, fenced timings).

Times `flash_prefill_paged` directly at serving shapes across
(q_block, key_block) configurations, against the XLA-scan oracle's time.
Timing discipline: chain outputs into the next call's query and fence
with a device→host fetch.

Chip only: ``python benchmarking/bench_flash_prefill_blocks.py`` (exits
non-zero where JAX finds no TPU).
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

    from llm_d_kv_cache_manager_tpu.ops.attention import prefill_with_paged_context
    from llm_d_kv_cache_manager_tpu.ops.flash_prefill import flash_prefill_paged

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # The sweep's per-config try/except would otherwise swallow the
        # kernel wrapper's refusal and exit 0 with no measurement.
        raise SystemExit(
            f"bench_flash_prefill_blocks: no TPU (platform={platform!r})"
        )
    # 1.4B-bench attention geometry; one layer's attention op.
    b, s, n_q, n_kv, d, ps = 4, 2048, 24, 8, 128, 16
    max_ctx_pages = 128  # 2048 tokens of warm context
    reps = 8

    rng = np.random.default_rng(0)
    total_pages = b * max_ctx_pages + 1
    dtype = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((b, s, n_q, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), dtype)
    k_pages = jnp.asarray(rng.standard_normal((total_pages, ps, n_kv, d)), dtype)
    v_pages = jnp.asarray(rng.standard_normal((total_pages, ps, n_kv, d)), dtype)
    bt = jnp.asarray(
        (rng.permutation(total_pages - 1)[: b * max_ctx_pages] + 1).reshape(
            b, max_ctx_pages
        ),
        jnp.int32,
    )
    cl = jnp.asarray([2048, 2048, 1024, 0], jnp.int32)
    nv = jnp.full((b,), s, jnp.int32)
    positions = cl[:, None] + jnp.arange(s)[None, :]
    valid = jnp.ones((b, s), bool)

    def time_fn(fn):
        y = fn(q)
        np.asarray(y[0, 0, 0, :1])  # compile + fence
        qq = q
        t0 = time.perf_counter()
        for _ in range(reps):
            y = fn(qq)
            # chain: perturb the query with the output (same shape)
            qq = (qq + y.astype(qq.dtype) * 1e-3).astype(qq.dtype)
        np.asarray(y[0, 0, 0, :1])
        return (time.perf_counter() - t0) / reps * 1e3

    # jit the oracle with every array as a traced ARGUMENT (un-jitted it
    # dispatches eagerly op-by-op; closing over the arrays would bake them
    # in as constants and let XLA fold the q-independent gather/concat out
    # of the timed region — asymmetric vs the Pallas path's jit).
    xla_jit = jax.jit(
        lambda qq, k, v, kp, vp, bt, cl, pos, val: prefill_with_paged_context(
            qq, k, v, kp, vp, bt, cl, positions=pos, valid=val
        )
    )
    xla_ms = time_fn(
        lambda qq: xla_jit(qq, k, v, k_pages, v_pages, bt, cl, positions, valid)
    )
    print(json.dumps({"impl": "xla_scan", "ms": round(xla_ms, 2)}), flush=True)

    for qb in (128, 256, 512):
        for kb in (256, 512, 1024):
            try:
                ms = time_fn(
                    lambda qq, qb=qb, kb=kb: flash_prefill_paged(
                        qq, k, v, k_pages, v_pages, bt, cl, nv,
                        q_block=qb, key_block=kb,
                    )
                )
            except Exception as e:  # VMEM overflow etc.
                print(json.dumps({"q_block": qb, "key_block": kb,
                                  "error": type(e).__name__}), flush=True)
                continue
            print(
                json.dumps(
                    {
                        "impl": "pallas",
                        "q_block": qb,
                        "key_block": kb,
                        "ms": round(ms, 2),
                        "speedup_vs_xla": round(xla_ms / ms, 2),
                    }
                ),
                flush=True,
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
