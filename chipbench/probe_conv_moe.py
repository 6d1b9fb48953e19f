#!/usr/bin/env python3
"""The hybrid (convolution + attention) configuration's reference check AT
THE TIMED SIZES, and the controls that must read not correct: the runs
behind ``references/conv_moe.py``'s tolerances. Not part of a benchmark run.

    python3 chipbench/probe_conv_moe.py --seeds 1,2 [--controls all|none|a,b]
        [--prefix 8192] [--turn 128] [--steps 8] [--rehearse]

Builds ``lfm2-8b-a1b``'s weights from each seed the way a run does (no
engine, no server; pools just large enough), then, through the served
programs: the prefix filled in 1024-token pieces (``llama.prefill``, each
piece a warm prefill whose first token takes its convolution state from the
slot of the page the piece before finished), the turn as a warm prefill
against the whole prefix and a one-token warm prefill of its last token (a
chunk boundary inside a page, as the reference's ``system`` makes one: the
compared position's whole state comes from a slot), ``--steps`` greedy decode
steps through ``llama.decode_step`` (at the default sizes the first lies at a page's first
slot: a page boundary is crossed). The logits after the turn and after each
step are compared with the reference's (``conv_moe.forward`` over the whole
sequence, the head at those rows alone), as ``reference.common_check``
compares: ``rel_err`` (worst position), ``rel_err_p50``, and
``layer_rel_err_p75`` over every layer run alone through the same programs.

The controls steer the PROGRAM (the reference and its weights stay what they
are); the steering is here, in the probe: the program has no such option.

- ``state_zeroed``: every state slot zeroed before each of the turn's two
  warm prefills (a hit that found no state in its pages);
- ``taps_reversed``: the filter's taps in the opposite order;
- ``b_c_swapped``: the gates ``B`` and ``C`` of ``conv_in`` exchanged;
- ``bias_weighs``: gates taken from ``s + b`` (the expert bias weighs);
- ``int8_weights``: the matmul weights and the experts rounded through int8
  (the nearest precision below the stated one).
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

CONTROLS = ("state_zeroed", "taps_reversed", "b_c_swapped", "bias_weighs",
            "int8_weights")
PIECE = 1024


def steer_params(params, control):
    """The tree the PROGRAM reads under a control that is one of weights."""
    import jax.numpy as jnp

    from llm_d_kv_cache_manager_tpu.models import quant

    def conv_layers(change):
        return {**params, "layers": [
            {**layer, **change(layer)} if "conv_in" in layer else layer
            for layer in params["layers"]]}

    if control == "taps_reversed":
        return conv_layers(lambda la: {"conv_w": la["conv_w"][::-1]})
    if control == "b_c_swapped":
        def swap(la):
            b, c, x = jnp.split(la["conv_in"], 3, axis=1)
            return {"conv_in": jnp.concatenate([c, b, x], axis=1)}
        return conv_layers(swap)
    if control == "int8_weights":
        return quant.quantize_params(params, quantize_experts=True)
    return params


def steer_program(llama, control):
    """Patch the program's model code for one control; returns the undo."""
    import jax
    import jax.numpy as jnp

    if control != "bias_weighs":
        return lambda: None
    kept = llama._moe_gates

    def gates(layer, cfg, x):
        logits = (x @ layer["router"]).astype(jnp.float32)
        choice = jax.nn.sigmoid(logits) + layer["router_bias"]
        topv, topi = jax.lax.top_k(choice, cfg.n_experts_per_tok)
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-6)
        return topv * cfg.routed_scaling_factor, topi

    llama._moe_gates = gates
    return lambda: setattr(llama, "_moe_gates", kept)


def system(llama, params, cfg, tokens, n_prefix, steps, page, attn_impl,
           interpret, zero_state=False):
    """The served programs over one sequence: (logits [1 + steps, vocab],
    the tokens fed)."""
    import jax.numpy as jnp
    import numpy as np

    s = len(tokens)
    n_pages = -(-(s + steps) // page)
    k_pages, v_pages = llama.init_kv_pages(cfg, n_pages + 1, page)
    state = llama.init_state_pages(cfg, n_pages + 1)
    table = 1 + np.arange(n_pages)
    # one table width for every piece and the turn: one program a chunk width
    ctx_w = -(-s // page)
    run = dict(attn_impl=attn_impl, interpret=interpret)

    def keep(out):
        nonlocal k_pages, v_pages, state
        logits, k_pages, v_pages, *rest = out
        if rest:
            (state,) = rest
        return np.asarray(logits, np.float32)[0]

    def stateful():
        return {} if state is None else {"state_pages": state}

    bounds = list(range(0, n_prefix, PIECE)) + [n_prefix, s - 1, s]
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if zero_state and lo >= n_prefix and state is not None:
            state = jnp.zeros_like(state)
        positions = np.arange(lo, hi)[None, :]
        ctx = np.zeros((1, ctx_w), np.int32)
        ctx[0, : -(-lo // page)] = table[: -(-lo // page)]
        logits = keep(llama.prefill(
            params, cfg, jnp.asarray([tokens[lo:hi]], jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.ones((1, hi - lo), bool),
            k_pages, v_pages, jnp.asarray(1 + positions // page, jnp.int32),
            jnp.asarray(positions % page, jnp.int32), jnp.asarray(ctx),
            jnp.asarray([lo], jnp.int32), **run, **stateful(),
        ))
    out = [logits]
    fed = []
    bt = jnp.asarray(table[None, :], jnp.int32)
    for i in range(steps):
        nxt = int(np.argmax(out[-1]))
        fed.append(nxt)
        out.append(keep(llama.decode_step(
            params, cfg, jnp.asarray([nxt], jnp.int32),
            jnp.asarray([s + i], jnp.int32), k_pages, v_pages, bt,
            jnp.asarray([s + i + 1], jnp.int32), page_size=page,
            interpret=interpret, **stateful(),
        )))
    return np.stack(out), fed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--controls", default="all")
    ap.add_argument("--prefix", type=int, default=8192)
    ap.add_argument("--turn", type=int, default=128)
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

    config = bench_run.load_config("lfm2-8b-a1b", args.rehearse)
    cfg = bench_run.model_config(config, args.rehearse)
    page = int(config["env"]["BLOCK_SIZE"])
    ref = reference.load(config["reference"])
    if args.rehearse:
        global PIECE
        PIECE = 16
        args.prefix, args.turn = min(args.prefix, 48), min(args.turn, 8)
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
        tokens = rng.integers(33, 127, args.prefix + args.turn).tolist()
        rows = np.arange(len(tokens) - 1, len(tokens) + args.steps)

        def compare(system_params, truth, zero_state):
            # the pools follow the kinds of layer the tree has (a layer
            # alone is one of either kind)
            model_cfg = ref.pool_config(truth, cfg)
            got, fed = system(llama, system_params, model_cfg, tokens,
                              args.prefix, args.steps, page, attn_impl,
                              interpret, zero_state)
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
            undo = steer_program(llama, control)
            zero = control == "state_zeroed"
            try:
                mine = steer_params(params, control)
                err, _ = compare(mine, params, zero)
                line = {"seed": seed, "control": control or "sound",
                        "tokens": len(tokens), "steps": args.steps,
                        "rel_err": float(err.max()),
                        "rel_err_p50": float(np.median(err))}
                ok = line["rel_err"] <= tol["max"] and line["rel_err_p50"] <= tol["p50"]
                if args.layers_alone:
                    alone, gap = [], []
                    for mine_l, true_l in zip(mine["layers"], params["layers"]):
                        e, gaps = compare({**mine, "layers": [mine_l]},
                                          {**params, "layers": [true_l]}, zero)
                        alone += e.tolist()
                        gap += gaps.tolist()
                    alone, gap = np.asarray(alone), np.asarray(gap)
                    # compared: every position (no gap sets one aside)
                    line["layer_rel_err_p75"] = float(np.quantile(alone, 0.75))
                    line["layer_rel_err_max"] = float(alone.max())
                    line["layer_tied_positions"] = int(
                        (gap < ref.ROUTER_GAP_MIN).sum())
                    # told: the third quartile by kind of layer (operator,
                    # FFN), so that a fault in one kind is seen as such
                    kinds = [("conv" if "conv_in" in la else "attn") + "+"
                             + ("moe" if "router" in la else "dense")
                             for la in params["layers"]]
                    per = len(rows)
                    line["p75_by_kind"] = {
                        kind: float(np.quantile(np.concatenate([
                            alone[i * per: (i + 1) * per]
                            for i, k in enumerate(kinds) if k == kind]), 0.75))
                        for kind in sorted(set(kinds))}
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
