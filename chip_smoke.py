#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the classes a deployment
uses, at the full width of ``models.QWEN3_32B`` (hidden 5120, 64/8 heads,
head_dim 128, FFN 25600, vocabulary 151936, qk-norm, bf16) cut by DEPTH
only, with seeded random weights:

0. before JAX: build the native hash/index libraries from the committed
   sources; build a ``ScoringService`` (real ZMQ SUB bound on TCP, its HTTP
   app, an offline tokenizer), ask it once (score -> 0) and check that it
   did not initialise a JAX backend — a scorer that touches a backend on a
   TPU host takes the chip from the pod;
1. device: versions, platform, device_kind, count. Platform other than
   ``tpu`` -> non-zero exit before any kernel or pod exists;
2. kernels: every Pallas kernel compiled (``interpret=False``) against its
   own reference at the served shapes;
3. serving: one ``PodServer`` per visible chip, configured the way
   ``serve.main()`` configures it (``PodServerConfig.from_env()``), behind
   the scorer and the event plane, answering cold / warm / repeated /
   concurrent completions over HTTP; with several replicas, placement is
   asserted per chip and scorer-routed traffic must beat round-robin on
   prefix-cache hit rate;
4. tp (four chips): one ``tp=4`` engine whose first-step logits agree with
   a ``tp=1`` engine's.

Any failed phase exits non-zero. Wall times printed here are set-up/wall
times of a smoke, NOT performance results. On success the second-to-last
stdout line is the summary (ending ``"claim": null``) and the last is one
JSON object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.

``--dry-run`` is the explicit CPU rehearsal (tiny preset, Pallas
interpreter, virtual devices) for debugging this command before chip time
is spent; it is never a fallback and its summary says ``platform: cpu``.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import gc
import json
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
PHASES = ("kernels", "serve", "tp")

#: Tolerances, with their reasons.
#:
#: Kernel vs reference, bf16: both sides read the same bf16 inputs and
#: accumulate in f32; they differ by accumulation order, by the p@v dot
#: running on bf16 probabilities in the flash kernel, and by one final
#: bf16 rounding (2^-8 relative). Outputs are O(1), so 3e-2 absolute is a
#: few bf16 ulps — the figure the round-1 on-chip parity script used.
TOL_ATTN_BF16 = 3e-2
#: f32 inputs on a TPU still run through the MXU's bf16 passes with
#: different accumulation orders on the two sides (~1e-3 cross-impl).
TOL_ATTN_F32_ON_CHIP = 5e-3
#: int8-pool decode: kernel and oracle see the SAME dequantized values, so
#: this is float roundoff (bf16 query/output), not quantization noise.
TOL_ATTN_INT8_POOL = 3e-2
#: Grouped matmul: max|kernel - ragged_dot| / max|ragged_dot|. bf16 output
#: rounding over a 2048-long contraction; the int8 side adds the f32-vs-
#: bf16 dequantization order (bounds first set on a rig that is gone).
TOL_GMM_BF16 = 2e-2
TOL_GMM_INT8 = 5e-2
#: tp=4 vs tp=1 first-step logits, max|Δ| / max|logit|: row-parallel
#: matmuls sum four partial products in another order, each rounded to
#: bf16 (2^-8) and compounded over the layers; random weights keep logits
#: O(1). Logits, not tokens: the argmax moves on rounding.
TOL_TP_LOGITS = 5e-2
#: The dry run's tiny preset is float32 end to end.
TOL_DRY = 2e-4


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    """A check did not hold. Never caught: it ends the run non-zero."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    say(f"  ok: {what}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@contextlib.contextmanager
def environ(**values: str):
    """``from_env()`` reads the process environment — set it for one call."""
    saved = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def http(method: str, url: str, body=None, timeout: float = 900.0):
    """(status, parsed JSON body) — error statuses are returned, not
    raised: the smoke reads responses, it does not infer health from a
    process being alive."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            return e.code, json.loads(raw)
        except ValueError:
            return e.code, {"raw": raw.decode(errors="replace")}


# --------------------------------------------------------------------------
# phase 0 — before any JAX backend
# --------------------------------------------------------------------------
def build_native() -> dict:
    """``*.so`` is git-ignored, so a checkout has none; without them the
    hash chain and the index fall back to pure Python without a word."""
    from llm_d_kv_cache_manager_tpu.native import build, hashcore, lruindex

    outs = build.build(verbose=False)  # raises CalledProcessError on failure
    check(hashcore.available(), "libhashcore.so built from source and loads")
    check(lruindex.available(), "liblruindex.so built from source and loads")
    return {"built": [os.path.basename(p) for p in outs]}


class Fleet:
    """The HTTP side: one asyncio loop thread serving every aiohttp app."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self.loop.run_forever, name="smoke-http", daemon=True
        )
        self._thread.start()
        self._runners = []

    def serve(self, app, port: int) -> str:
        from aiohttp import web

        async def _up():
            runner = web.AppRunner(app)
            await runner.setup()
            await web.TCPSite(runner, "127.0.0.1", port).start()
            return runner

        fut = asyncio.run_coroutine_threadsafe(_up(), self.loop)
        self._runners.append(fut.result(timeout=60))
        return f"http://127.0.0.1:{port}"

    def close(self) -> None:
        async def _down():
            for r in self._runners:
                await r.cleanup()

        asyncio.run_coroutine_threadsafe(_down(), self.loop).result(timeout=60)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=10)


def make_tokenizer():
    from llm_d_kv_cache_manager_tpu.tokenization import Tokenizer

    class CharTokenizer(Tokenizer):
        """Offline (there is no network): token id = code point, so scorer
        and pod hash the same ids for the same text."""

        def encode(self, prompt, model_name):
            return (
                [ord(c) for c in prompt],
                [(i, i + 1) for i in range(len(prompt))],
            )

        def decode(self, token_ids, model_name):
            return "".join(chr(t) if 32 <= t < 127 else "?" for t in token_ids)

    return CharTokenizer()


def start_scorer(fleet: Fleet, model_name: str, zmq_port: int):
    """ScoringService the way ``server.api``'s main builds it, then one
    question: nothing has been stored, so every pod scores 0."""
    from jax._src.xla_bridge import backends_are_initialized

    from llm_d_kv_cache_manager_tpu.server.api import (
        ScoringService,
        ServiceConfig,
    )

    with environ(ZMQ_ENDPOINT=f"tcp://*:{zmq_port}", BLOCK_SIZE="16"):
        cfg = ServiceConfig.from_env()
    svc = ScoringService(cfg, tokenizer=make_tokenizer())
    svc.start()
    url = fleet.serve(svc.build_app(), free_port())
    status, body = http(
        "POST", f"{url}/score_completions",
        {"prompt": "x" * 64, "model": model_name}, timeout=60,
    )
    check(status == 200 and not body.get("scores"),
          f"scorer answers and scores nothing yet ({status} {body})")
    index = type(svc.indexer.kv_block_index).__name__
    inner = getattr(svc.indexer.kv_block_index, "_inner", None)
    if inner is not None:
        index = type(inner).__name__
    hashing = (
        "native"
        if svc.indexer.token_processor._native is not None
        else "python"
    )
    check(not backends_are_initialized(),
          "the scorer was built and answered without initialising a JAX "
          "backend")
    return svc, url, {"index": index, "hashing": hashing}


# --------------------------------------------------------------------------
# phase 1 — device
# --------------------------------------------------------------------------
def device_report(dry_run: bool, want_chips) -> dict:
    import jax
    import jaxlib

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    devs = jax.devices()
    report = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    say(f"device: {json.dumps(report)}")
    if dry_run:
        if report["platform"] != "cpu":
            raise SmokeFailure("--dry-run is the CPU rehearsal; got "
                               f"platform={report['platform']!r}")
    elif report["platform"] != "tpu":
        # With JAX_PLATFORMS unset JAX itself falls back to the CPU with a
        # warning when the TPU fails to initialise. Nothing runs on that.
        raise SmokeFailure(
            f"JAX found no accelerator (platform={report['platform']!r}); "
            "use --dry-run for the explicit CPU rehearsal"
        )
    if want_chips is not None and len(devs) < want_chips:
        raise SmokeFailure(
            f"--chips {want_chips} but JAX sees {len(devs)} device(s)"
        )
    return report


# --------------------------------------------------------------------------
# phase 2 — kernels, compiled, against their own references
# --------------------------------------------------------------------------
def _max_err(got, ref, mask=None) -> float:
    import numpy as np

    d = np.abs(np.asarray(got, np.float32) - np.asarray(ref, np.float32))
    if mask is not None:
        d = d * mask
    if not np.isfinite(np.asarray(got, np.float32)).all():
        return float("inf")
    return float(d.max())


def kernel_phase(dry: bool) -> dict:
    """Every kernel case runs (one failure must not hide the next); the
    phase fails if any case failed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_kv_cache_manager_tpu.models import quant
    from llm_d_kv_cache_manager_tpu.ops.attention import (
        prefill_with_paged_context,
    )
    from llm_d_kv_cache_manager_tpu.ops.flash_prefill import (
        flash_prefill_paged,
    )
    from llm_d_kv_cache_manager_tpu.ops.gmm import grouped_matmul
    from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_reference,
    )

    interpret = dry
    cases: dict[str, dict] = {}

    def run(name: str, fn, tol: float, main_path: bool) -> None:
        t0 = time.perf_counter()
        try:
            err = fn()
            ok = err <= tol
            cases[name] = {"ok": ok, "err": float(f"{err:.3g}"), "tol": tol}
        except Exception as e:  # recorded, and the phase then fails
            cases[name] = {
                "ok": False, "error": f"{type(e).__name__}: {e}"[:2000],
            }
        cases[name]["main_path"] = main_path
        cases[name]["wall_s"] = round(time.perf_counter() - t0, 2)
        say(f"  kernel {name}: {json.dumps(cases[name])[:600]}")

    # -- decode: paged_attention, 5-D pool + layer index + has_fresh ------
    if dry:
        geo = dict(b=4, n_q=8, n_kv=2, hd=32, ps=16, L=2, width=4)
        seq_lens = [50, 1, 0, 64]
        dtype, tol_dec = jnp.float32, TOL_DRY
    else:  # Qwen3-32B: 64/8 heads, head_dim 128, page 16, 8 lanes,
        # table width 144 pages = the served 2k-context bucket
        geo = dict(b=8, n_q=64, n_kv=8, hd=128, ps=16, L=2, width=144)
        seq_lens = [2092, 2061, 17, 1, 0, 300, 2304, 16]
        dtype, tol_dec = jnp.bfloat16, TOL_ATTN_BF16

    def decode_inputs(seed, geo=geo):
        rng = np.random.default_rng(seed)
        b, width, ps = geo["b"], geo["width"], geo["ps"]
        total = b * width + 1
        shape = (geo["L"], total, ps, geo["n_kv"], geo["hd"])
        q = jnp.asarray(rng.standard_normal((b, geo["n_q"], geo["hd"])), dtype)
        fk = jnp.asarray(
            rng.standard_normal((b, geo["n_kv"], geo["hd"])), dtype
        )
        fv = jnp.asarray(
            rng.standard_normal((b, geo["n_kv"], geo["hd"])), dtype
        )
        # Pages globally unique (the allocator's no-aliasing contract).
        bt = (rng.permutation(total - 1)[: b * width] + 1).reshape(b, width)
        return rng, shape, q, fk, fv, bt.astype(np.int32)

    def reference_with_fresh(q, k_l, v_l, fk, fv, bt, sl, **seen):
        """Oracle: write each lane's current token into its slot
        full-width, then plain gather-softmax over ``seq_len`` tokens
        (``seen``: a sliding layer's ``window``)."""
        k_l, v_l = np.array(k_l), np.array(v_l)
        for i, n in enumerate(sl):
            if n > 0:
                page, slot = bt[i, (n - 1) // geo["ps"]], (n - 1) % geo["ps"]
                k_l[page, slot] = np.asarray(fk[i])
                v_l[page, slot] = np.asarray(fv[i])
        return paged_attention_reference(
            q, jnp.asarray(k_l), jnp.asarray(v_l), jnp.asarray(bt),
            jnp.asarray(sl, jnp.int32), **seen,
        )

    def decode_case():
        rng, shape, q, fk, fv, bt = decode_inputs(1)
        kp = jnp.asarray(rng.standard_normal(shape), dtype)
        vp = jnp.asarray(rng.standard_normal(shape), dtype)
        layer = geo["L"] - 1
        got = paged_attention(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(seq_lens, jnp.int32),
            fk, fv, interpret=interpret, layer=layer,
        )
        ref = reference_with_fresh(
            q, kp[layer], vp[layer], fk, fv, bt, seq_lens
        )
        return _max_err(got, ref)

    def decode_int8_pool_case():
        rng, shape, q, fk, fv, bt = decode_inputs(2)
        codes_k = rng.integers(-127, 128, shape).astype(np.int8)
        codes_v = rng.integers(-127, 128, shape).astype(np.int8)
        sc_shape = (shape[0], shape[1], shape[3])
        # ~N(0,1)-sized values after dequantization
        sk = rng.uniform(0.005, 0.03, sc_shape).astype(np.float32)
        sv = rng.uniform(0.005, 0.03, sc_shape).astype(np.float32)
        layer = geo["L"] - 1
        got = paged_attention(
            q, jnp.asarray(codes_k), jnp.asarray(codes_v), jnp.asarray(bt),
            jnp.asarray(seq_lens, jnp.int32), fk, fv,
            k_scale=jnp.asarray(sk), v_scale=jnp.asarray(sv),
            interpret=interpret, layer=layer,
        )
        wide_k = quant.dequantize_kv_pool(
            codes_k[layer : layer + 1], sk[layer : layer + 1], np.float32
        )[0]
        wide_v = quant.dequantize_kv_pool(
            codes_v[layer : layer + 1], sv[layer : layer + 1], np.float32
        )[0]
        ref = reference_with_fresh(
            q.astype(jnp.float32), wide_k, wide_v,
            np.asarray(fk, np.float32), np.asarray(fv, np.float32),
            bt, seq_lens,
        )
        return _max_err(got, ref)

    def decode_window_case():
        # A sliding layer's call (Trinity-Large-Preview: 48/8 heads, window
        # 4096 through a 259-page window table whose first slot stands for
        # ``starts``): lanes at every offset of the window in its first
        # page, one that fills its table, short ones and an idle one.
        if dry:
            window, width, lens = 24, 6, [24 + 5, 0, 23, 96]
        else:
            window, width, lens = 4096, 259, [4101, 4097, 4096, 300, 1, 0, 4111, 4144]
        rng, shape, q, fk, fv, bt = decode_inputs(
            3, dict(geo, width=width, n_q=6 * geo["n_kv"]))
        # a stretch of the pool a lane: as it lies on odd lanes (runs of
        # pages, which the kernel copies a group at once), shuffled on even
        bt = 1 + np.arange(bt.size, dtype=np.int32).reshape(bt.shape)
        bt[::2] = rng.permuted(bt[::2], axis=1)
        kp = jnp.asarray(rng.standard_normal(shape), dtype)
        vp = jnp.asarray(rng.standard_normal(shape), dtype)
        starts = (np.arange(len(lens)) * 3 * geo["ps"]).astype(np.int32)
        layer = geo["L"] - 1
        got = paged_attention(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(lens, jnp.int32) + starts,
            fk, fv, interpret=interpret, layer=layer, window=window,
            table_start=jnp.asarray(starts),
        )
        ref = reference_with_fresh(
            q, kp[layer], vp[layer], fk, fv, bt, lens, window=window
        )
        return _max_err(got, ref)

    run("paged_attention[bf16 pool, 5-D, layer, has_fresh]",
        decode_case, tol_dec, main_path=True)
    run("paged_attention_window[bf16 pools, 5-D, layer operand, has_fresh]",
        decode_window_case, tol_dec, main_path=True)
    run("paged_attention[int8 pool, in-kernel dequant]",
        decode_int8_pool_case, TOL_DRY if dry else TOL_ATTN_INT8_POOL,
        main_path=False)

    # -- prefill: flash_prefill_paged vs the XLA scan ---------------------
    # The kernel reads its context from the five-dimensional pool in place
    # (layer 1 of 2 here; every table entry past a row's context names page
    # 0, which like the other layer holds large values that would show).
    def flash_case(*, b, s, n_q, n_kv, d, ps, max_ctx_pages, ctx_lens,
                   n_valid, dt, seed, block_length=0):
        def fn():
            rng = np.random.default_rng(seed)
            total = max(b * max_ctx_pages + 1, 2)
            q = jnp.asarray(rng.standard_normal((b, s, n_q, d)), dt)
            k = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), dt)
            v = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), dt)
            kp = jnp.asarray(rng.standard_normal((total, ps, n_kv, d)), dt)
            vp = jnp.asarray(rng.standard_normal((total, ps, n_kv, d)), dt)
            perm = rng.permutation(total - 1)[: b * max_ctx_pages] + 1
            bt = perm.reshape(b, max_ctx_pages).astype(np.int32)
            cl = jnp.asarray(ctx_lens, jnp.int32)
            nv = jnp.asarray(n_valid, jnp.int32)
            positions = cl[:, None] + jnp.arange(s)[None, :]
            valid = jnp.arange(s)[None, :] < nv[:, None]
            ref = prefill_with_paged_context(
                q, k, v, kp, vp, jnp.asarray(bt), cl, positions=positions,
                valid=valid, block_length=block_length,
            )
            live = (
                np.arange(max_ctx_pages)[None, :]
                < -(-np.asarray(ctx_lens) // ps)[:, None]
            )
            k5 = jnp.stack([jnp.full_like(kp, 1e4), kp.at[0].set(1e4)])
            v5 = jnp.stack([jnp.full_like(vp, 1e4), vp.at[0].set(1e4)])
            got = flash_prefill_paged(
                q, k, v, k5, v5, jnp.asarray(np.where(live, bt, 0)), cl, nv,
                interpret=interpret, block_length=block_length, layer=1,
            )
            return _max_err(got, ref, np.asarray(valid)[:, :, None, None])

        return fn

    if dry:
        flash = {
            "gqa-warm": dict(b=2, s=16, n_q=4, n_kv=2, d=16, ps=4,
                             max_ctx_pages=5, ctx_lens=[20, 7],
                             n_valid=[16, 11], dt=jnp.float32, seed=1),
            "gqa-cold": dict(b=2, s=32, n_q=4, n_kv=2, d=16, ps=4,
                             max_ctx_pages=1, ctx_lens=[0, 0],
                             n_valid=[32, 20], dt=jnp.float32, seed=2),
            "block-warm": dict(b=3, s=4, n_q=8, n_kv=4, d=16, ps=4,
                               max_ctx_pages=6, ctx_lens=[20, 8, 0],
                               n_valid=[4, 4, 0], dt=jnp.float32, seed=3,
                               block_length=4),
        }
        tols = {name: TOL_DRY for name in flash}
    else:
        bf = jnp.bfloat16
        flash = {
            # Qwen3-32B GQA 64/8, `sessions`' prefill dispatch: 8 rows of a
            # 128-token chunk over 1k-3k tokens of paged context in a table
            # of 256 pages, most rows padding
            "qwen3-32b-sessions": dict(b=8, s=128, n_q=64, n_kv=8, d=128,
                                       ps=16, max_ctx_pages=256,
                                       ctx_lens=[3072, 2048, 1024, 0,
                                                 1040, 16, 4096, 0],
                                       n_valid=[128, 97, 33, 128, 1, 64, 12, 0],
                                       dt=bf, seed=1),
            # SDAR-30B-A3B 32/4, `blockgen`'s forward: 16 lanes of one
            # block of 4 rows over 128-1536 tokens of context in a table of
            # 128 pages, block-causal
            "sdar-30b-a3b-blockgen": dict(b=16, s=4, n_q=32, n_kv=4, d=128,
                                          ps=16, max_ctx_pages=128,
                                          ctx_lens=[128, 1536, 516, 0, 1028,
                                                    2048, 132, 640, 1532, 256,
                                                    4, 772, 1280, 900, 384, 8],
                                          n_valid=[4, 4, 4, 4, 4, 4, 0, 4,
                                                   4, 4, 4, 0, 4, 4, 4, 4],
                                          dt=bf, seed=7, block_length=4),
            "qwen3-32b-cold": dict(b=2, s=2112, n_q=64, n_kv=8, d=128,
                                   ps=16, max_ctx_pages=1, ctx_lens=[0, 0],
                                   n_valid=[2060, 1536], dt=bf, seed=2),
            # the superseded round-1 parity script's other geometries
            "8b-gqa-warm": dict(b=4, s=64, n_q=32, n_kv=8, d=128, ps=16,
                                max_ctx_pages=257,
                                ctx_lens=[4096, 4096, 1234, 0],
                                n_valid=[64, 64, 64, 48], dt=bf, seed=3),
            "mha": dict(b=2, s=512, n_q=16, n_kv=16, d=128, ps=16,
                        max_ctx_pages=16, ctx_lens=[256, 9],
                        n_valid=[512, 500], dt=bf, seed=4),
            "f32": dict(b=2, s=256, n_q=8, n_kv=2, d=128, ps=16,
                        max_ctx_pages=8, ctx_lens=[128, 77],
                        n_valid=[256, 200], dt=jnp.float32, seed=6),
        }
        tols = {name: TOL_ATTN_BF16 for name in flash}
        tols["f32"] = TOL_ATTN_F32_ON_CHIP
    for name, kw in flash.items():
        run(f"flash_prefill_paged[{name}]", flash_case(**kw), tols[name],
            main_path=True)

    # -- latent (MLA) attention: mla_decode / mla_prefill over the pool in
    # place, against the jax.numpy oracle, at the docqa cell's shapes -------
    from llm_d_kv_cache_manager_tpu.ops.mla_attention import (
        mla_paged_attention,
        mla_paged_attention_reference,
    )

    def mla_case(*, b, s, heads, dk, dv, ps, width, ctx_lens, n_valid, dt, seed):
        def fn():
            rng = np.random.default_rng(seed)
            pages = sum(-(-c // ps) for c in ctx_lens) + 1
            pool = rng.standard_normal((2, pages, ps, dk)).astype(np.float32)
            pool[..., dv + (dk - dv) // 2:] = 0.0  # a row's padding
            pool[:, 0] = 1e4  # the dead tail of every table points here
            pool[0] *= 1e3  # another layer's rows would be seen
            bt = np.zeros((b, width), np.int32)
            # a stretch of the pool a lane: as it lies on odd lanes (runs of
            # pages, which the kernel copies a group at once), shuffled on
            # even ones
            at = 1
            for i, c in enumerate(ctx_lens):
                n = -(-c // ps)
                ids = np.arange(at, at + n)
                bt[i, :n] = ids if i % 2 else rng.permutation(ids)
                at += n
            q = jnp.asarray(rng.standard_normal((b, s, heads, dk)), dt)
            fresh = jnp.asarray(rng.standard_normal((b, s, dk)), dt)
            pool = jnp.asarray(pool, dt)
            args = (jnp.asarray(bt), jnp.asarray(ctx_lens, jnp.int32),
                    jnp.asarray(n_valid, jnp.int32))
            scale = (dk * 0.3) ** -0.5
            got = mla_paged_attention(
                q, fresh, pool, *args, dv=dv, scale=scale,
                interpret=interpret, layer=jnp.int32(1),
            )
            with jax.default_matmul_precision("highest"):
                ref = mla_paged_attention_reference(
                    q, fresh, pool[1], *args, dv=dv, scale=scale)
            return _max_err(got, ref)

        return fn

    if dry:
        mla = {
            "decode": dict(b=4, s=1, heads=4, dk=128, dv=32, ps=4, width=24,
                           ctx_lens=[9, 90, 0, 37], n_valid=[1, 1, 0, 1],
                           dt=jnp.float32, seed=7),
            "question": dict(b=2, s=20, heads=4, dk=128, dv=32, ps=4, width=16,
                             ctx_lens=[40, 0], n_valid=[20, 7],
                             dt=jnp.float32, seed=8),
        }
    else:  # kanana-2-30b-a3b: 32 heads over rows of 576 values held in 640
        mla = {
            "decode": dict(b=32, s=1, heads=32, dk=640, dv=512, ps=16,
                           width=2048,
                           ctx_lens=[128, 12288, 28700, 0] * 8,
                           n_valid=[1, 1, 1, 0] * 8, dt=jnp.bfloat16, seed=7),
            "question": dict(b=2, s=128, heads=32, dk=640, dv=512, ps=16,
                             width=1792, ctx_lens=[28672, 12288],
                             n_valid=[128, 77], dt=jnp.bfloat16, seed=8),
        }
    for name, kw in mla.items():
        run(f"mla_paged_attention[{name}]", mla_case(**kw),
            TOL_DRY if dry else TOL_ATTN_BF16, main_path=True)

    # -- MoE: grouped_matmul vs ragged_dot at one Qwen3-30B-A3B layer -----
    if dry:
        e, d_model, f, shapes = 4, 128, 256, {"decode": 16, "prefill": 64}
        gdt = jnp.float32
    else:  # 128 experts, hidden 2048, expert width 768, top-8
        e, d_model, f = 128, 2048, 768
        shapes = {"decode": 16 * 8, "prefill": 2048 * 8}
        gdt = jnp.bfloat16

    def gmm_case(rows: int, int8: bool):
        def fn():
            rng = np.random.default_rng(rows + int8)
            ids = np.sort(rng.integers(0, e, rows)).astype(np.int32)
            gs = jnp.asarray(np.bincount(ids, minlength=e), jnp.int32)
            worst = 0.0
            for din, dout in ((d_model, f), (f, d_model)):  # up, down
                lhs = jnp.asarray(rng.standard_normal((rows, din)), gdt)
                w = jnp.asarray(
                    rng.standard_normal((e, din, dout)) * din**-0.5, gdt
                )
                rhs = quant.quantize_tensor(w) if int8 else w
                kw = dict(row_group_ids=jnp.asarray(ids))
                ref = grouped_matmul(lhs, rhs, gs, use_kernel=False, **kw)
                got = grouped_matmul(
                    lhs, rhs, gs, interpret=interpret, **kw
                )
                scale = float(np.abs(np.asarray(ref, np.float32)).max())
                worst = max(worst, _max_err(got, ref) / (scale + 1e-9))
            return worst

        return fn

    for shape_name, rows in shapes.items():
        run(f"grouped_matmul[bf16, {shape_name}]", gmm_case(rows, False),
            TOL_DRY if dry else TOL_GMM_BF16, main_path=False)
        run(f"grouped_matmul[int8, {shape_name}]", gmm_case(rows, True),
            TOL_DRY if dry else TOL_GMM_INT8, main_path=False)

    failed = [n for n, c in cases.items() if not c["ok"]]
    if failed:
        raise SmokeFailure(f"kernel cases failed: {failed}")
    return {"cases": cases}


# --------------------------------------------------------------------------
# phase 3 — serving
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Sizing:
    model_name: str
    n_layers: int
    total_pages: int
    max_model_len: int
    prefix_len: int  # shared prefix, whole pages
    suffix_len: int  # < one page, so a repeat hits the same cached pages
    max_new: int
    decode_batch: int
    why: str


def size_for_chip(dry: bool) -> Sizing:
    """Depth and pool from what the chip reports. Widths are never cut."""
    import jax

    from llm_d_kv_cache_manager_tpu import models

    if dry:
        return Sizing(
            model_name="tiny-llama", n_layers=models.TINY_LLAMA.n_layers,
            total_pages=256, max_model_len=256, prefix_len=64,
            suffix_len=12, max_new=8, decode_batch=2,
            why="dry run: tiny preset, CPU",
        )
    cfg = models.QWEN3_32B
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    itemsize = 2  # bf16
    hd = cfg.hd
    per_layer = itemsize * (
        cfg.hidden_size * cfg.n_heads * hd  # wq
        + 2 * cfg.hidden_size * cfg.n_kv_heads * hd  # wk, wv
        + cfg.n_heads * hd * cfg.hidden_size  # wo
        + 3 * cfg.hidden_size * cfg.intermediate_size  # gate, up, down
    )
    embed_and_head = 2 * itemsize * cfg.vocab_size * cfg.hidden_size
    total_pages, page = 4096, 16
    pool_per_layer = 2 * total_pages * page * cfg.n_kv_heads * hd * itemsize
    # Room for one padded prefill dispatch (8 rows x 2112 tokens): the
    # [tokens, 25600] FFN intermediates in f32 are ~1.7 GB apiece.
    reserve = int(6.5e9)
    n = int((limit - reserve - embed_and_head) // (per_layer + pool_per_layer))
    if n < 1:
        raise SmokeFailure(f"bytes_limit={limit} holds no layer at all")
    n = min(n, cfg.n_layers)
    static = embed_and_head + n * (per_layer + pool_per_layer)
    why = (
        f"bytes_limit={limit / 2**30:.2f} GiB; embedding+head "
        f"{embed_and_head / 2**30:.2f} GiB + {n} layers x "
        f"({per_layer / 2**30:.2f} GiB weights + "
        f"{pool_per_layer / 2**30:.2f} GiB of a {total_pages}-page pool) = "
        f"{static / 2**30:.2f} GiB resident, {reserve / 2**30:.1f} GiB left "
        "for a padded 8x2112-token prefill"
    )
    return Sizing(
        model_name="Qwen/Qwen3-32B", n_layers=n, total_pages=total_pages,
        max_model_len=4096, prefix_len=2048, suffix_len=12, max_new=32,
        decode_batch=8, why=why,
    )


def make_pod(i: int, sizing: Sizing, zmq_port: int, dry: bool, device):
    """One replica, configured the way ``serve.main()`` configures it:
    ``PodServerConfig.from_env()`` with every feature switch at its
    default — only sizing, identity and addresses are set."""
    from llm_d_kv_cache_manager_tpu.parallel import MeshConfig, make_mesh
    from llm_d_kv_cache_manager_tpu.server.serve import (
        PodServer,
        PodServerConfig,
        _resolve_model,
    )

    env = dict(
        MODEL_NAME=sizing.model_name,
        POD_IDENTIFIER=f"chip-pod-{i}",
        ZMQ_ENDPOINT=f"tcp://localhost:{zmq_port}",
        BLOCK_SIZE="16",
        TOTAL_PAGES=str(sizing.total_pages),
        MAX_MODEL_LEN=str(sizing.max_model_len),
        DECODE_BATCH_SIZE=str(sizing.decode_batch),
    )
    if dry:
        env["INTERPRET"] = "1"
    with environ(**env):
        cfg = PodServerConfig.from_env()
    cfg.engine.model = dataclasses.replace(
        _resolve_model(cfg.model_name), n_layers=sizing.n_layers
    )
    server = PodServer(
        cfg, tokenizer=make_tokenizer(),
        mesh=make_mesh(MeshConfig(), devices=[device]),
    )
    server.start()
    return server


def ascii_text(rng, n: int) -> str:
    return "".join(chr(c) for c in rng.integers(33, 127, n))


def complete(url: str, prompt: str, max_new: int, expect_len: int) -> dict:
    """One completion over HTTP, every field checked."""
    status, body = http(
        "POST", f"{url}/v1/completions",
        {"prompt": prompt, "max_tokens": max_new, "temperature": 0.0},
    )
    if status != 200:
        raise SmokeFailure(f"completion -> {status} {json.dumps(body)[:2000]}")
    choice = body["choices"][0]
    toks = choice["token_ids"]
    if len(toks) != max_new or choice["finish_reason"] != "length":
        raise SmokeFailure(
            f"completion returned {len(toks)} tokens, finish_reason="
            f"{choice['finish_reason']!r}; wanted {max_new}, 'length'"
        )
    if body["usage"]["prompt_tokens"] != expect_len:
        raise SmokeFailure(f"prompt_tokens {body['usage']} != {expect_len}")
    return {"tokens": toks, "cached": body["usage"]["cached_prompt_tokens"]}


def scores_for(scorer_url: str, prompt: str, model: str) -> dict:
    status, body = http(
        "POST", f"{scorer_url}/score_completions",
        {"prompt": prompt, "model": model}, timeout=60,
    )
    if status != 200:
        raise SmokeFailure(f"score -> {status} {body}")
    return body.get("scores") or {}


def wait_visible(scorer_url, prompt, model, pod_name, timeout=60.0) -> int:
    """Poll the score until ``pod_name``'s blocks crossed ZMQ."""
    deadline = time.monotonic() + timeout
    while True:
        score = scores_for(scorer_url, prompt, model).get(pod_name, 0)
        if score > 0:
            return score
        if time.monotonic() > deadline:
            raise SmokeFailure(
                f"{pod_name}'s blocks never became visible to the scorer"
            )
        time.sleep(0.05)


def prove_device_not_hidden(server, sizing: Sizing) -> dict:
    """interpret off, Pallas prefill on, and a Mosaic custom call in the
    compiled text of the engine's own decode and prefill steps."""
    import jax
    import numpy as np

    from llm_d_kv_cache_manager_tpu.models import llama

    eng = server.engine
    check(eng.config.interpret is False, "engine.config.interpret is False")
    check(eng.prefill_attn == "pallas", 'engine.prefill_attn == "pallas"')

    def like(x):  # the pools are donated every step: lower on abstractions
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=eng._replicated)

    lanes = eng.config.decode_batch_size
    # the served decode shape: the whole request, in pages, bucketed
    bucket = eng.config.decode_pages_bucket
    pages = -(-(sizing.prefix_len + sizing.suffix_len + sizing.max_new)
              // eng.page_size)
    width = -(-pages // bucket) * bucket
    decode = llama.decode_steps.lower(
        eng.params, eng.model_cfg, arr((lanes,), np.int32),
        arr((lanes, width + llama.DECODE_PACKED_TAIL), np.int32),
        like(eng.k_pages), like(eng.v_pages), like(eng._rng),
        page_size=eng.page_size, num_steps=eng.config.decode_steps_per_iter,
        interpret=False, mesh=eng.mesh,
    ).compile().as_text()
    b = eng.config.scheduler.max_prefill_batch
    chunk = eng.config.prefill_bucket
    ctx_pages = sizing.prefix_len // eng.page_size
    prefill = llama.prefill_packed.lower(
        eng.params, eng.model_cfg,
        arr((b, 5 * chunk + ctx_pages + 1), np.int32),
        like(eng.k_pages), like(eng.v_pages), chunk=chunk, mesh=eng.mesh,
        attn_impl=eng.prefill_attn, interpret=False,
    ).compile().as_text()
    check("tpu_custom_call" in decode,
          "compiled decode step contains a Mosaic custom call")
    check("tpu_custom_call" in prefill,
          "compiled warm-prefill step contains a Mosaic custom call")
    return {"decode_custom_calls": decode.count("tpu_custom_call"),
            "prefill_custom_calls": prefill.count("tpu_custom_call")}


def serve_phase(scorer_url, zmq_port, devices, dry: bool) -> dict:
    import jax
    import numpy as np

    # The pods' HTTP side is this phase's own: closing it drops the apps'
    # references to the servers, so their arrays leave the chips.
    fleet = Fleet()
    sizing = size_for_chip(dry)
    say(f"  sizing: {sizing.why}")
    model = sizing.model_name
    n = len(devices)
    servers, urls, names = [], [], []
    out: dict = {
        "model": model, "n_layers": sizing.n_layers,
        "total_pages": sizing.total_pages, "replicas": n,
        "sizing": sizing.why,
    }
    try:
        t0 = time.perf_counter()
        for i, dev in enumerate(devices):
            servers.append(make_pod(i, sizing, zmq_port, dry, dev))
            urls.append(fleet.serve(servers[-1].build_app(), free_port()))
            names.append(servers[-1].config.pod_identifier)
        out["build_wall_s"] = round(time.perf_counter() - t0, 1)
        time.sleep(0.5)  # PUB sockets finish connecting to the SUB

        rng = np.random.default_rng(21)
        plen = sizing.prefix_len + sizing.suffix_len
        prefixes = [ascii_text(rng, sizing.prefix_len) for _ in range(n)]

        def prompt(prefix):
            return prefix + ascii_text(rng, sizing.suffix_len)

        # -- cold, visible, warm, repeat: on every replica ---------------
        t0 = time.perf_counter()
        per_pod = []
        for i in range(n):
            cold_p = prompt(prefixes[i])
            check(not scores_for(scorer_url, cold_p, model),
                  f"pod {i}: nobody holds the prefix yet (score 0)")
            cold = complete(urls[i], cold_p, sizing.max_new, plen)
            check(cold["cached"] == 0, f"pod {i}: cold completion, "
                  f"{plen}-token prompt, {sizing.max_new} new tokens")
            score = wait_visible(scorer_url, cold_p, model, names[i])
            warm_p = prompt(prefixes[i])
            board = scores_for(scorer_url, warm_p, model)
            check(max(board, key=board.get) == names[i],
                  f"pod {i}: its events crossed ZMQ, scorer ranks it first "
                  f"for the shared prefix ({board})")
            _, before = http("GET", f"{urls[i]}/stats")
            warm = complete(urls[i], warm_p, sizing.max_new, plen)
            _, after = http("GET", f"{urls[i]}/stats")
            computed = (after["prefill"]["tokens_computed"]
                        - before["prefill"]["tokens_computed"])
            check(warm["cached"] >= sizing.prefix_len
                  and computed == plen - warm["cached"],
                  f"pod {i}: warm completion reused {warm['cached']} cached "
                  f"prompt tokens; /stats says prefill computed {computed}")
            again = complete(urls[i], warm_p, sizing.max_new, plen)
            check(again["tokens"] == warm["tokens"],
                  f"pod {i}: the same warm request twice gives identical "
                  "greedy tokens")
            per_pod.append({"score_after_cold": score,
                            "warm_cached_tokens": warm["cached"]})
        out["cold_warm_wall_s"] = round(time.perf_counter() - t0, 1)
        out["per_pod"] = per_pod

        if not dry:
            out["device_not_hidden"] = prove_device_not_hidden(
                servers[0], sizing
            )
        else:
            eng = servers[0].engine
            check(eng.config.interpret and eng.prefill_attn == "xla",
                  "dry run: interpret=True takes the XLA prefill (the "
                  "engine's one stated rule)")

        # -- concurrent: >= 2 x decode lanes per replica, scorer-routed --
        t0 = time.perf_counter()
        burst = [prompt(prefixes[j % n])
                 for j in range(2 * sizing.decode_batch * n)]

        def routed(p):
            board = scores_for(scorer_url, p, model)
            best = names.index(max(board, key=lambda k: (board[k], k)))
            return best, complete(urls[best], p, sizing.max_new, plen)

        with ThreadPoolExecutor(len(burst)) as pool:
            results = list(pool.map(routed, burst))
        for j, (best, r) in enumerate(results):
            if best != j % n or r["cached"] < sizing.prefix_len:
                raise SmokeFailure(
                    f"burst request {j}: routed to pod {best}, cached "
                    f"{r['cached']} (wanted pod {j % n}, a warm prefix)"
                )
        check(True, f"{len(burst)} concurrent completions "
              f"(2 x {sizing.decode_batch} lanes x {n} replicas), each "
              "routed by the scorer to the pod holding its prefix")
        out["concurrent_wall_s"] = round(time.perf_counter() - t0, 1)

        # -- several replicas: routing must beat round-robin --------------
        if n > 1:
            out["routing"] = routing_check(
                scorer_url, urls, names, sizing, rng
            )

        # -- placement and memory, per replica ----------------------------
        placement = []
        for i, (srv, dev) in enumerate(zip(servers, devices)):
            eng = srv.engine
            on = {d for x in jax.tree.leaves(eng.params) for d in x.devices()}
            # the pools are the outputs of the last prefill/decode step
            stepped = eng.k_pages.devices() | eng.v_pages.devices()
            check(on == {dev} and stepped == {dev}
                  and eng._rng.devices() == {dev},
                  f"pod {i}: params, KV pools and step outputs sit on "
                  f"{dev} and nowhere else")
            stats = dev.memory_stats() or {}
            placement.append({
                "device": str(dev),
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            })
        out["placement"] = placement
        if not dry:
            used = [p["bytes_in_use"] for p in placement]
            check(max(used) < 2 * min(used),
                  f"bytes_in_use is of the same order on every chip ({used})")
            peak, limit = (placement[0]["peak_bytes_in_use"],
                           placement[0]["bytes_limit"])
            check(peak > limit / 2,
                  f"peak device memory {peak / 2**30:.2f} GiB is above half "
                  f"of the {limit / 2**30:.2f} GiB the chip reports")
            out["peak_gib"] = round(peak / 2**30, 2)

        for url in urls:
            status, body = http("GET", f"{url}/healthz", timeout=60)
            if status != 200:
                raise SmokeFailure(f"/healthz -> {status} {body}")
        check(True, "/healthz is 200 on every pod at the end")
        return out
    finally:
        fleet.close()
        for srv in servers:
            srv.shutdown()


def routing_check(scorer_url, urls, names, sizing: Sizing, rng) -> dict:
    """Shared-prefix groups on fresh prefixes, once round-robin and once
    routed by the scorer's answer (cold placements spread) — the check the
    multichip dryrun makes on virtual devices. Disjoint prefix sets keep
    the two passes from warming each other. Arrivals rotate the groups
    each round, so round-robin sends a group's requests to different pods
    by construction (no lucky shuffle can make it tie)."""
    n = len(urls)
    plen = sizing.prefix_len + sizing.suffix_len
    groups, rounds = n, 3
    order = [(i + r) % groups for r in range(rounds) for i in range(n)]

    def run(use_scorer: bool) -> float:
        prefixes = [ascii_text(rng, sizing.prefix_len) for _ in range(groups)]
        cached = prompts = spread = 0
        for j, g in enumerate(order):
            p = prefixes[g] + ascii_text(rng, sizing.suffix_len)
            board = scores_for(scorer_url, p, sizing.model_name)
            if use_scorer and board:
                best = names.index(max(board, key=lambda k: (board[k], k)))
            elif use_scorer:
                best, spread = spread % n, spread + 1
            else:
                best = j % n
            r = complete(urls[best], p, sizing.max_new, plen)
            cached += r["cached"]
            prompts += plen
            # the next arrival of this group must see this pod's blocks
            wait_visible(scorer_url, p, sizing.model_name, names[best])
        return cached / prompts

    rr = run(use_scorer=False)
    routed = run(use_scorer=True)
    check(routed > rr, f"routed hit rate {routed:.3f} beats round-robin's "
          f"{rr:.3f} over {len(order)} requests in {groups} prefix groups")
    return {"routed_hit_rate": round(routed, 3),
            "round_robin_hit_rate": round(rr, 3)}


# --------------------------------------------------------------------------
# phase 4 — tp=4 against tp=1
# --------------------------------------------------------------------------
def tp_phase(devices, dry: bool) -> dict:
    """One tp=4 engine (shard_map around both Pallas kernels) whose
    first-step logits agree with a tp=1 engine's. Depth 2: the comparison
    needs both engines' weights to pass through the first chip."""
    import jax
    import numpy as np

    from llm_d_kv_cache_manager_tpu import models
    from llm_d_kv_cache_manager_tpu.models import llama
    from llm_d_kv_cache_manager_tpu.parallel import MeshConfig, make_mesh
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig,
        Engine,
        EngineConfig,
        SamplingParams,
    )

    tp = 4
    if dry:
        cfg = dataclasses.replace(models.TINY_LLAMA, n_kv_heads=4)
        s, ps, pages = 16, 4, 64
    else:
        cfg = dataclasses.replace(models.QWEN3_32B, n_layers=2)
        s, ps, pages = 256, 16, 256
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, min(cfg.vocab_size, 50_000), (1, s))

    def first_step_logits(degree: int):
        mesh = make_mesh(MeshConfig(tp=degree), devices=devices[:degree])
        eng = Engine(
            EngineConfig(
                model=cfg,
                block_manager=BlockManagerConfig(
                    total_pages=pages, page_size=ps
                ),
                max_model_len=4 * s, decode_batch_size=2, interpret=dry,
                prefill_bucket=s, tp=degree,
            ),
            mesh=mesh,
        )
        if not dry:
            check(eng.prefill_attn == "pallas" and not eng.config.interpret,
                  f"tp={degree} engine: compiled kernels, Pallas prefill")
        n_pages = s // ps
        positions = np.arange(s)[None, :]
        page_ids = 1 + positions // ps
        # prefill: the flash kernel (under shard_map at tp>1)
        out = llama.prefill(
            eng.params, cfg, eng._dev(tokens, np.int32),
            eng._dev(positions, np.int32), eng._dev(np.ones((1, s), bool)),
            eng.k_pages, eng.v_pages, eng._dev(page_ids, np.int32),
            eng._dev(positions % ps, np.int32),
            eng._dev(np.zeros((1, 0), np.int32)),
            eng._dev(np.zeros((1,), np.int32)), mesh=eng.mesh,
            attn_impl=eng.prefill_attn, interpret=dry,
        )
        prefill_logits, eng.k_pages, eng.v_pages = out
        nxt = int(np.argmax(np.asarray(prefill_logits)[0]))
        # decode: the paged-attention kernel over the pages just written
        bt = np.zeros((1, n_pages + 1), np.int32)
        bt[0] = 1 + np.arange(n_pages + 1)
        decode_logits, eng.k_pages, eng.v_pages = llama.decode_step(
            eng.params, cfg, eng._dev([nxt], np.int32),
            eng._dev([s], np.int32), eng.k_pages, eng.v_pages,
            eng._dev(bt), eng._dev([s + 1], np.int32), page_size=ps,
            interpret=dry, mesh=eng.mesh,
        )
        # and the engine's own loop serves a request on the same devices
        seq = eng.add_request(
            tokens[0].tolist(), SamplingParams(max_new_tokens=4)
        )
        eng.run_until_complete()
        check(seq.error is None and len(seq.generated_tokens) == 4,
              f"tp={degree} engine serves a request end to end")
        return (np.asarray(prefill_logits, np.float32),
                np.asarray(decode_logits, np.float32), nxt)

    p1, d1, nxt1 = first_step_logits(1)
    gc.collect()  # the tp=1 engine's arrays leave the first chip
    p4, d4, nxt4 = first_step_logits(tp)
    out = {}
    tol = TOL_DRY if dry else TOL_TP_LOGITS
    for name, a, b in (("prefill", p1, p4), ("decode", d1, d4)):
        if name == "decode" and nxt1 != nxt4:
            # the fed token differs because the argmax moved on rounding;
            # the prefill comparison above already covers both kernels'
            # inputs — say so instead of comparing different questions
            out["decode_rel_err"] = "argmax moved on rounding; not compared"
            continue
        rel = float(np.abs(a - b).max() / (np.abs(a).max() + 1e-9))
        check(np.isfinite(b).all() and rel <= tol,
              f"tp={tp} {name} logits agree with tp=1: max|Δ|/max|logit| = "
              f"{rel:.2e} (tolerance {tol:g})")
        out[f"{name}_rel_err"] = float(f"{rel:.3g}")
    return out


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="explicit CPU rehearsal: tiny preset, interpreter, "
                    "virtual devices; never a device result")
    ap.add_argument("--chips", type=int, default=None,
                    help="replicas = this many chips; fails if fewer are "
                    "visible (default: every visible chip)")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma list from {PHASES} (default: all)")
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    if set(phases) - set(PHASES):
        ap.error(f"unknown phase in {phases}")
    dry = args.dry_run
    if dry:
        # Before JAX is imported: the rehearsal never reaches for a chip.
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()

    t_start = time.perf_counter()
    summary: dict = {"dry_run": dry, "phases": {}}

    def timed(name, fn, *a):
        say(f"== {name}")
        t0 = time.perf_counter()
        result = fn(*a)
        result = dict(result or {})
        # set-up/wall time of a smoke — not a performance result
        result["wall_s_not_a_perf_result"] = round(
            time.perf_counter() - t0, 1
        )
        summary["phases"][name] = result
        return result

    timed("native", build_native)

    from llm_d_kv_cache_manager_tpu.utils.compile_cache import (
        cache_entries,
        enable_compile_cache,
    )

    fleet = Fleet()
    svc = None
    try:
        zmq_port = free_port()
        model_name = "tiny-llama" if dry else "Qwen/Qwen3-32B"
        say("== scorer (before any JAX backend)")
        svc, scorer_url, scorer_impl = start_scorer(fleet, model_name, zmq_port)
        summary["scorer"] = scorer_impl
        say(f"  scorer served by: {scorer_impl}")
        if not dry:
            check(scorer_impl == {"index": "NativeMemoryIndex",
                                  "hashing": "native"},
                  "the scorer runs on the native libraries, not the "
                  "pure-Python fallback")

        device = timed("device", device_report, dry, args.chips)
        cache_dir = enable_compile_cache()
        entries_before = cache_entries(cache_dir)
        say(f"compile cache: {cache_dir} ({entries_before} entries)")

        import jax

        n_chips = args.chips or (4 if dry else len(jax.devices()))
        devices = jax.devices()[:n_chips]
        if "kernels" in phases:
            timed("kernels", kernel_phase, dry)
        if "serve" in phases:
            timed("serve", serve_phase, scorer_url, zmq_port, devices, dry)
        if "tp" in phases and n_chips >= 4:
            gc.collect()  # the replicas' arrays leave their chips
            stats = devices[0].memory_stats()
            if stats is not None:
                check(stats["bytes_in_use"] < stats["bytes_limit"] / 8,
                      "the replicas' arrays left the first chip "
                      f"({stats['bytes_in_use'] / 2**30:.2f} GiB in use)")
            timed("tp", tp_phase, devices, dry)
        entries_after = cache_entries(cache_dir)
        summary["compile_cache"] = {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": entries_after,
        }
        say(f"compile cache: {cache_dir} ({entries_after} entries)")
    finally:
        if svc is not None:
            svc.shutdown()
        fleet.close()

    summary["wall_s_not_a_perf_result"] = round(
        time.perf_counter() - t_start, 1
    )
    summary["claim"] = None
    say("summary: " + json.dumps(summary))
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke_summary.json"),
              "w") as f:
        json.dump(summary, f, indent=1)
    # the contract's last line: the device exactly as JAX reports it
    print(json.dumps({
        "ok": True,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
