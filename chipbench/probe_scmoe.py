#!/usr/bin/env python3
"""The double-layer configuration's reference check as a run makes it, and
the controls that must read not correct: the runs behind ``references/
scmoe_mla.py``'s tolerances. Not part of a benchmark run.

    python3 chipbench/probe_scmoe.py --seeds 1,2 [--controls all|none|a,b]
        [--prompt 128] [--steps 8] [--rehearse]

Builds ``longcat-flash-omni``'s weights from each seed the way a run does (no
engine, no server; a pool just large enough) and makes the harness's own
comparison (``reference.common_check``: two prompts through the reference's
``system`` side — a cold half, the warm rest against the latent pool, greedy
decode steps across a page boundary — then every double layer alone), once
sound and once under each control. One line of JSON a run.

The controls steer the PROGRAM (the reference and the weights stay what they
are); the steering is here, in the probe: the program has no such option.

- ``no_zero``: the zero experts' term dropped (their gates taken as 0);
- ``renorm``: the gates renormalised over the chosen places before the
  scaling factor;
- ``no_q_scale`` / ``no_kv_scale``: ``mla_scale_q_lora`` /
  ``mla_scale_kv_lora`` left out;
- ``early_s``: the routed sum added after the first FFN (step 2), where it
  is computed, instead of after the second (step 4);
- ``bias_weighs``: gates taken from ``p + bias`` (the bias weighs);
- ``int8_rows``: the latent rows rounded through int8 (a scale a token an
  attention) before the write: the nearest precision below the stated one
  (no int8 latent pool exists in the program).
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

CONTROLS = ("no_zero", "renorm", "no_q_scale", "no_kv_scale", "early_s",
            "bias_weighs", "int8_rows")
CONFIG = "longcat-flash-omni"


def steer(llama, control):
    """Patch the program's model code for one control; returns the undo."""
    import jax
    import jax.numpy as jnp

    kept = {}

    def patch(name, value):
        kept[name] = getattr(llama, name)
        setattr(llama, name, value)

    def gates_with(change):
        orig = llama._moe_gates

        def gates(layer, cfg, x):
            topv, topi = orig(layer, cfg, x)
            return change(layer, cfg, x, topv, topi), topi
        patch("_moe_gates", gates)

    if control == "no_zero":
        gates_with(lambda layer, cfg, x, topv, topi:
                   jnp.where(topi >= cfg.n_experts, 0.0, topv))
    elif control == "renorm":
        gates_with(lambda layer, cfg, x, topv, topi:
                   topv / jnp.sum(topv, axis=-1, keepdims=True)
                   * cfg.routed_scaling_factor)
    elif control == "bias_weighs":
        def with_bias(layer, cfg, x, topv, topi):
            p = jax.nn.softmax((x @ layer["router"]).astype(jnp.float32), -1)
            choice = p + layer["router_bias"]
            return (jnp.take_along_axis(choice, topi, axis=-1)
                    * cfg.routed_scaling_factor)
        gates_with(with_bias)
    elif control in ("no_q_scale", "no_kv_scale"):
        orig_project = llama._mla_project
        off = {"no_q_scale": "mla_scale_q_lora",
               "no_kv_scale": "mla_scale_kv_lora"}[control]
        patch("_mla_project", lambda layer, cfg, *a: orig_project(
            layer, dataclasses.replace(cfg, **{off: False}), *a))
    elif control == "early_s":
        orig_ffn = llama._ffn

        def ffn(layer, cfg, h, *a, aside=None, **kw):
            if "moe" not in layer:
                return orig_ffn(layer, cfg, h, *a, aside=None, **kw)
            mine = []
            out = orig_ffn(layer, cfg, h, *a, aside=mine, **kw)
            return out + mine.pop()
        patch("_ffn", ffn)
    elif control == "int8_rows":
        from chipbench import probe_mla

        return probe_mla.steer(llama, control)  # the same pool, the same write
    elif control is not None:
        raise ValueError(f"unknown control {control!r}")

    def undo():
        for name, value in kept.items():
            setattr(llama, name, value)

    return undo


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--controls", default="all")
    ap.add_argument("--prompt", type=int, default=None,
                    help="prompt tokens (default: the harness's own)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from chipbench import reference, run as bench_run
    from chipbench.fleet import make_params
    from llm_d_kv_cache_manager_tpu.models import llama
    from llm_d_kv_cache_manager_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    config = bench_run.load_config(CONFIG, args.rehearse)
    cfg = bench_run.model_config(config, args.rehearse)
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
        # what ``common_check`` and the reference's ``system`` read of an
        # engine: its parameters, configuration, page and placement
        engine = types.SimpleNamespace(
            params=make_params(cfg, seed, device), model_cfg=cfg,
            page_size=int(config["env"]["BLOCK_SIZE"]), _replicated=device,
            mesh=None, prefill_attn="xla" if args.rehearse else "pallas",
        )
        for control in (None, *controls):
            for jitted in programs:
                jitted.clear_cache()
            undo = steer(llama, control)
            try:
                line = reference.common_check(
                    engine, ref, seed, interpret=args.rehearse, **sizes)
            finally:
                undo()
                for jitted in programs:
                    jitted.clear_cache()
            print(json.dumps({"seed": seed, "control": control or "sound",
                              **line}), flush=True)
        del engine  # the next seed's tree does not fit beside this one
    return 0


if __name__ == "__main__":
    sys.exit(main())
