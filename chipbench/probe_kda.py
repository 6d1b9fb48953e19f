#!/usr/bin/env python3
"""The linear-and-latent configuration's reference check as a run makes it,
and the controls that must read not correct: the runs behind ``references/
kda_mla_moe.py``'s tolerances. Not part of a benchmark run.

    python3 chipbench/probe_kda.py --seeds 1,2 [--controls all|none|a,b]
        [--prompt 128] [--steps 8] [--layers 7] [--rehearse]

Builds ``ling-3.0-flash``'s weights from each seed the way a run does (no
engine, no server; pools just large enough) and makes the harness's own
comparison (``reference.common_check``: two prompts through the reference's
``system`` side — a prefill into a slot, a second from that slot as a
snapshot into another, decode steps that leave a slot behind and go on in a
new one — then every layer alone), once sound and once under each control.
One line of JSON a run.

The controls steer the PROGRAM (the reference and the weights it reads stay
what they are); the steering is here, in the probe: the program has no such
option.

- ``bf16_state``: the heads' matrices rounded through bf16 at every write of
  the pool (a precision below the stated float32 state): not correct in
  float32 (the rehearsal), and ON THE CHIP INSIDE THE SOUND RUNS' RANGE: 8
  decode steps of rounding stay under bf16's own noise (the reference's
  docstring, "what no limit holds");
- ``gate_after_update``: the decay applied after the rank-one update (``S <-
  diag(exp(g)) (S + beta k u^T)``, ``u`` from the undecayed state);
- ``no_l2_norm``: q and k not normed a head;
- ``no_group_mask``: the top-8 taken over all 512 scores, no group left out;
- ``int8_weights``: the matmul weights and the experts rounded through int8
  (the nearest precision below the stated bf16).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = ("bf16_state", "gate_after_update", "no_l2_norm", "no_group_mask",
            "int8_weights")
CONFIG = "ling-3.0-flash"


def steer_params(params, control):
    """The tree the PROGRAM reads under a control that is one of weights."""
    if control == "int8_weights":
        from llm_d_kv_cache_manager_tpu.models import quant

        return quant.quantize_params(params, quantize_experts=True)
    return params


def steer(llama, kda, control):
    """Patch the program's model code for one control; returns the undo."""
    import jax.numpy as jnp

    kept = []

    def patch(module, name, value):
        kept.append((module, name, getattr(module, name)))
        setattr(module, name, value)

    def plain_recurrence(round_state):
        """The recurrence token by token through ``kda._step`` (whatever it
        is patched to), the state rounded as ``round_state`` says at every
        write of the pool."""
        def chunked(q, k, v, g, beta, S0, chunk=None):
            o, S = kda.kda_recurrent(q, k, v, g, beta, S0)
            return o, round_state(S)

        def decode(pool, q, k, v, g, beta, rd, wr, fresh, layer, *,
                   interpret=False):
            L, slots = pool.shape[:2]
            flat = pool.reshape(L * slots, *pool.shape[2:])
            S, o = kda._step(flat[layer * slots + rd], q, k, v, g, beta)
            flat = flat.at[layer * slots + wr].set(round_state(S))
            return o, flat.reshape(pool.shape)

        patch(kda, "kda_chunked", chunked)
        patch(kda, "kda_decode", decode)

    if control == "bf16_state":
        plain_recurrence(
            lambda S: S.astype(jnp.bfloat16).astype(jnp.float32))
    elif control == "gate_after_update":
        def step(S, q, k, v, g, beta):
            u = v - jnp.sum(S * k[..., :, None], axis=-2)
            S = S + (beta[..., None] * k)[..., :, None] * u[..., None, :]
            S = S * jnp.exp(g)[..., :, None]
            return S, jnp.sum(S * q[..., :, None], axis=-2)

        patch(kda, "_step", step)
        plain_recurrence(lambda S: S)
    elif control == "no_l2_norm":
        patch(llama, "_l2norm", lambda t: t)
    elif control == "no_group_mask":
        patch(llama, "_group_limited", lambda choice, cfg: choice)
    elif control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")

    def undo():
        for module, name, value in reversed(kept):
            setattr(module, name, value)

    return undo


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--controls", default="all")
    ap.add_argument("--prompt", type=int, default=None,
                    help="prompt tokens (default: the harness's own)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None,
                    help="the first so many layers (default: the cell's)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from chipbench import reference, run as bench_run
    from chipbench.fleet import make_params
    from llm_d_kv_cache_manager_tpu.models import llama
    from llm_d_kv_cache_manager_tpu.ops import kda
    from llm_d_kv_cache_manager_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    config = bench_run.load_config(CONFIG, args.rehearse)
    cfg = bench_run.model_config(config, args.rehearse)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    ref = reference.load(config["reference"])
    sizes = {"prompt_tokens": 16, "steps": 4} if args.rehearse else {}
    if args.prompt:
        sizes["prompt_tokens"] = args.prompt
    if args.steps:
        sizes["steps"] = args.steps
    if not args.rehearse:
        enable_compile_cache()
    controls = {"all": CONTROLS, "none": ()}.get(
        args.controls, tuple(c for c in args.controls.split(",") if c))
    programs = (llama.prefill, llama.decode_step)
    device = jax.devices()[0]

    for seed in (int(x) for x in args.seeds.split(",")):
        truth = make_params(cfg, seed, device)
        for control in (None, *controls):
            for jitted in programs:
                jitted.clear_cache()
            undo = steer(llama, kda, control)
            # what ``common_check`` and the reference's ``system`` read of an
            # engine: its parameters, configuration, page and placement
            engine = types.SimpleNamespace(
                params=steer_params(truth, control), model_cfg=cfg,
                page_size=int(config["env"]["BLOCK_SIZE"]), _replicated=device,
                mesh=None, prefill_attn="xla" if args.rehearse else "pallas",
            )
            try:
                line = reference.common_check(
                    engine, ref, seed, interpret=args.rehearse, truth=truth,
                    **sizes)
            finally:
                undo()
                for jitted in programs:
                    jitted.clear_cache()
            print(json.dumps({"seed": seed, "control": control or "sound",
                              **line}), flush=True)
            del engine
        del truth  # the next seed's tree does not fit beside this one
    return 0


if __name__ == "__main__":
    sys.exit(main())
