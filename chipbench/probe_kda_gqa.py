#!/usr/bin/env python3
"""The linear-and-GQA configuration's reference check as a run makes it, and
the controls that must read not correct: the runs behind ``references/
kda_gqa_moe.py``'s tolerances. Not part of a benchmark run.

    python3 chipbench/probe_kda_gqa.py --seeds 1,2 [--controls all|none|a,b]
        [--prompt 128] [--steps 8] [--layers 4] [--rehearse]

Builds ``solar-open2-250b``'s weights from each seed the way a run does (no
engine, no server; pools just large enough) and makes the harness's own
comparison (``reference.common_check``: two prompts through the reference's
``system`` side — a cold prefill into K/V pages and a slot, a warm one over
those pages from that slot as a snapshot into another, decode steps that
leave a slot behind and go on in a new one — then every layer alone), once
sound and once under each control. One line of JSON a run.

The controls steer the PROGRAM (the reference and the weights it reads stay
what they are); the steering is here, in the probe: the program has no such
option.

- ``bf16_state``: the heads' matrices rounded through bf16 at every write of
  the pool (``probe_kda``'s: a precision below the stated float32 state);
- ``int8_weights``: the matmul weights and the experts rounded through int8
  (the nearest precision below the stated bf16);
- ``beta_not_doubled``: ``beta = sigmoid(.)`` in (0, 1);
- ``gqa_layer_rotates``: q and k of the GQA layer rotated by position;
- ``gqa_gate_left_out``: the heads' output to ``W_o`` ungated.
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

CONTROLS = ("bf16_state", "int8_weights", "beta_not_doubled",
            "gqa_layer_rotates", "gqa_gate_left_out")
CONFIG = "solar-open2-250b"


def steer(llama, kda, control):
    """Patch the program's model code for one control; returns the undo."""
    from chipbench import probe_kda

    if control in (None, "bf16_state", "int8_weights"):
        return probe_kda.steer(llama, kda, control)
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    name, sound = {
        "beta_not_doubled": ("_kda_inputs", llama._kda_inputs),
        "gqa_layer_rotates": ("_rotates", llama._rotates),
        "gqa_gate_left_out": ("_attn_gate", llama._attn_gate),
    }[control]

    def halved(layer, cfg, x, rows):
        q, k, v, g, beta, z = sound(layer, cfg, x, rows)
        return q, k, v, g, 0.5 * beta, z

    setattr(llama, name, {
        "beta_not_doubled": halved,
        "gqa_layer_rotates": lambda layer, cfg: True,
        "gqa_gate_left_out": lambda layer, x, heads: heads,
    }[control])
    return lambda: setattr(llama, name, sound)


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

    from chipbench import probe_kda, reference, run as bench_run
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
                params=probe_kda.steer_params(truth, control), model_cfg=cfg,
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
