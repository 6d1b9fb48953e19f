#!/usr/bin/env python3
"""The latent configuration's reference check AT THE TIMED SIZES, and the
controls that must read not correct: the runs behind ``references/
mla_moe.py``'s tolerances. Not part of a benchmark run.

    python3 chipbench/probe_mla.py --seeds 1,2 [--controls all|none|a,b]
        [--document 12288] [--question 128] [--steps 8] [--rehearse]

Builds ``kanana-2-30b-a3b``'s weights from each seed the way a run does (no
engine, no server; a pool just large enough), then, through the served
programs: the document filled in 1024-token pieces (``llama.prefill``, each
piece a warm prefill against the pieces before it), the question as one warm
prefill against the whole document, ``--steps`` greedy decode steps through
``llama.decode_step``. The logits after the question and after each step are
compared with the reference's (``mla_moe.forward`` over the whole sequence,
attention in query blocks, the head at those rows alone), as
``reference.common_check`` compares: ``rel_err`` (worst position),
``rel_err_p50``, and ``layer_rel_err_p75`` over every layer run alone
through the same three programs.

The controls steer the PROGRAM (the reference and the weights stay what they
are); the steering is here, in the probe: the program has no such option.

- ``scale``: the softmax scale ``1 / sqrt(d_n)`` (128, not 192);
- ``no_shared``: the shared experts left out of every expert layer;
- ``bias_weighs``: gates taken from ``s + b`` (the bias weighs);
- ``one_sided_rope``: the queries' rope part de-interleaved, the key's not;
- ``int8_rows``: the latent rows rounded through int8 (a scale a token a
  layer) before the write (the nearest precision below the stated one; no
  int8 latent pool exists in the program).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = ("scale", "no_shared", "bias_weighs", "one_sided_rope", "int8_rows")
PIECE = 1024


def steer(llama, control):
    """Patch the program's model code for one control; returns the undo."""
    import jax
    import jax.numpy as jnp

    kept = {}

    def patch(name, value):
        kept[name] = getattr(llama, name)
        setattr(llama, name, value)

    if control == "scale":
        patch("_mla_scale", lambda cfg: cfg.qk_nope_head_dim ** -0.5)
    elif control == "bias_weighs":
        def gates(layer, cfg, x):
            logits = (x @ layer["router"]).astype(jnp.float32)
            choice = jax.nn.sigmoid(logits) + layer["router_bias"]
            topv, topi = jax.lax.top_k(choice, cfg.n_experts_per_tok)
            topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
            return topv * cfg.routed_scaling_factor, topi
        patch("_moe_gates", gates)
    elif control == "one_sided_rope":
        orig = llama._deinterleave
        patch("_deinterleave", lambda x: x if x.shape[-2] == 1 else orig(x))
    elif control == "int8_rows":
        orig_scatter = llama._scatter_kv_pages_all_layers

        def scatter(pages, fresh, *rest):
            f = fresh.astype(jnp.float32)
            scale = jnp.maximum(jnp.max(jnp.abs(f), -1, keepdims=True), 1e-8) / 127
            f = jnp.clip(jnp.round(f / scale), -127, 127) * scale
            return orig_scatter(pages, f.astype(fresh.dtype), *rest)
        patch("_scatter_kv_pages_all_layers", scatter)

    def undo():
        for name, value in kept.items():
            setattr(llama, name, value)

    return undo


def system(llama, params, cfg, tokens, n_doc, steps, page, attn_impl, interpret):
    """The served programs over one sequence: (logits [1 + steps, vocab],
    the tokens fed)."""
    import jax.numpy as jnp
    import numpy as np

    s = len(tokens)
    n_pages = -(-(s + steps) // page)
    k_pages, v_pages = llama.init_kv_pages(cfg, n_pages + 1, page)
    table = 1 + np.arange(n_pages)
    doc_w = n_doc // page  # one table width for every piece: one program
    run = dict(attn_impl=attn_impl, interpret=interpret)
    bounds = list(range(0, n_doc, PIECE)) + [n_doc, s]
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        positions = np.arange(lo, hi)[None, :]
        ctx = np.zeros((1, doc_w), np.int32)
        ctx[0, : lo // page] = table[: lo // page]
        logits, k_pages, v_pages = llama.prefill(
            params, cfg, jnp.asarray([tokens[lo:hi]], jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.ones((1, hi - lo), bool),
            k_pages, v_pages, jnp.asarray(1 + positions // page, jnp.int32),
            jnp.asarray(positions % page, jnp.int32), jnp.asarray(ctx),
            jnp.asarray([lo], jnp.int32), **run,
        )
    out = [np.asarray(logits, np.float32)[0]]
    fed = []
    bt = jnp.asarray(table[None, :], jnp.int32)
    for i in range(steps):
        nxt = int(np.argmax(out[-1]))
        fed.append(nxt)
        logits, k_pages, v_pages = llama.decode_step(
            params, cfg, jnp.asarray([nxt], jnp.int32),
            jnp.asarray([s + i], jnp.int32), k_pages, v_pages, bt,
            jnp.asarray([s + i + 1], jnp.int32), page_size=page,
            interpret=interpret,
        )
        out.append(np.asarray(logits, np.float32)[0])
    return np.stack(out), fed


def strip_shared(params):
    return {**params, "layers": [
        {k: v for k, v in layer.items() if not k.startswith("ws_")}
        for layer in params["layers"]
    ]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--controls", default="all")
    ap.add_argument("--document", type=int, default=12288)
    ap.add_argument("--question", type=int, default=128)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--layers-alone", type=int, default=1,
                    help="0 skips the layers run alone")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import numpy as np

    from chipbench import reference, run as bench_run
    from chipbench.fleet import make_params
    from llm_d_kv_cache_manager_tpu.models import llama
    from llm_d_kv_cache_manager_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    config = bench_run.load_config("kanana-2-30b-a3b", args.rehearse)
    cfg = bench_run.model_config(config, args.rehearse)
    page = int(config["env"]["BLOCK_SIZE"])
    ref = reference.load(config["reference"])
    if args.rehearse:
        global PIECE
        PIECE = 16
        args.document, args.question = min(args.document, 48), min(args.question, 8)
    else:
        enable_compile_cache()
    interpret = args.rehearse
    attn_impl = "xla" if interpret else "pallas"
    bf16 = cfg.dtype == jax.numpy.bfloat16
    tol = ref.TOL_BF16 if bf16 else dict.fromkeys(ref.TOL_BF16, reference.TOL_F32)
    controls = {"all": CONTROLS, "none": ()}.get(
        args.controls, tuple(c for c in args.controls.split(",") if c))
    programs = (llama.prefill, llama.decode_step)

    for seed in (int(x) for x in args.seeds.split(",")):
        params = make_params(cfg, seed, jax.devices()[0])
        rng = np.random.default_rng([seed, 5])
        tokens = rng.integers(33, 127, args.document + args.question).tolist()
        rows = np.arange(len(tokens) - 1, len(tokens) + args.steps)
        cfg1 = dataclasses.replace(cfg, n_layers=1)

        def compare(system_params, truth, model_cfg):
            got, fed = system(llama, system_params, model_cfg, tokens,
                              args.document, args.steps, page, attn_impl,
                              interpret)
            want, gaps = ref.forward(truth, model_cfg, tokens + fed, rows=rows)
            want = np.asarray(want, np.float32)
            if not np.isfinite(got).all():
                return np.full(len(rows), np.inf), np.asarray(gaps)
            err = np.abs(got - want).max(axis=1) / (np.abs(want).max() + 1e-9)
            return err, np.asarray(gaps)

        mine = None
        for control in (None, *controls):
            for jitted in programs:
                jitted.clear_cache()
            undo = steer(llama, control) if control else (lambda: None)
            try:
                mine = strip_shared(params) if control == "no_shared" else params
                err, _ = compare(mine, params, cfg)
                line = {"seed": seed, "control": control or "sound",
                        "tokens": len(tokens), "steps": args.steps,
                        "rel_err": float(err.max()),
                        "rel_err_p50": float(np.median(err))}
                ok = line["rel_err"] <= tol["max"] and line["rel_err_p50"] <= tol["p50"]
                if args.layers_alone:
                    alone, gap = [], []
                    for mine_l, true_l in zip(mine["layers"], params["layers"]):
                        e, gaps = compare({**mine, "layers": [mine_l]},
                                          {**params, "layers": [true_l]}, cfg1)
                        alone += e.tolist()
                        gap += gaps.tolist()
                    alone, gap = np.asarray(alone), np.asarray(gap)
                    # compared: every position (no gap sets one aside)
                    line["layer_rel_err_p75"] = float(np.quantile(alone, 0.75))
                    line["layer_rel_err_max"] = float(alone.max())
                    line["layer_tied_positions"] = int(
                        (gap < ref.ROUTER_GAP_MIN).sum())
                    # told: [tie gap, positions within it, the worst outside
                    # it] at the reference's gap, a half and a quarter of it
                    line["by_gap"] = [
                        [g, int((gap < g).sum()),
                         float(alone[gap >= g].max(initial=0.0))]
                        for g in (ref.ROUTER_GAP_MIN, ref.ROUTER_GAP_MIN / 2,
                                  ref.ROUTER_GAP_MIN / 4)]
                    ok = ok and line["layer_rel_err_p75"] <= tol["layer_p75"]
                line["tol"], line["ok"] = tol, bool(ok)
                print(json.dumps(line), flush=True)
            finally:
                undo()
                for jitted in programs:
                    jitted.clear_cache()
        del params, mine  # the next seed's tree does not fit beside this one
    return 0


if __name__ == "__main__":
    sys.exit(main())
