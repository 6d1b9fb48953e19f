#!/usr/bin/env python3
"""The pre-routed configuration's reference check as a run makes it, and the
controls that must read not correct: the runs behind ``references/
swa_prerouted_moe.py``'s tolerances. Not part of a benchmark run.

    python3 chipbench/probe_prerouted.py --seeds 1,2 [--controls all|none|a,b]
        [--rehearse]

Builds ``smallthinker-21b-a3b``'s weights from each seed the way a run does
(no engine, no server; pools just large enough) and makes the harness's own
comparison (``reference.common_check``: two prompts through the reference's
``system`` side, then every layer alone), once sound and once under each
control. One line of JSON a run.

The controls steer the PROGRAM (the reference and the weights it reads stay
what they are); the steering is here, in the probe: the program has no such
option.

- ``router_after_attention``: the router fed ``RMSNorm_2`` of the stream
  after the attention, as every other model's is (the layers lose their mark);
- ``silu``: SiLU for ReLU in the experts' gate;
- ``int8_weights``: the matmul weights and the experts rounded through int8
  (the nearest precision below the stated one).
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

CONTROLS = ("router_after_attention", "silu", "int8_weights")
CONFIG = "smallthinker-21b-a3b"


def steer(params, cfg, control):
    """(the tree, the configuration) the PROGRAM runs under a control."""
    from llm_d_kv_cache_manager_tpu.models import quant

    if control == "router_after_attention":
        return {**params, "layers": [
            {k: v for k, v in layer.items() if k != "preroute"}
            for layer in params["layers"]]}, cfg
    if control == "silu":
        return params, dataclasses.replace(cfg, hidden_act="silu")
    if control == "int8_weights":
        return quant.quantize_params(params, quantize_experts=True), cfg
    if control is not None:
        raise ValueError(f"unknown control {control!r}")
    return params, cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--controls", default="all")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from chipbench import reference, run as bench_run
    from chipbench.fleet import make_params
    from llm_d_kv_cache_manager_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    config = bench_run.load_config(CONFIG, args.rehearse)
    cfg = bench_run.model_config(config, args.rehearse)
    ref = reference.load(config["reference"])
    sizes = {"prompt_tokens": 16, "steps": 4} if args.rehearse else {}
    if not args.rehearse:
        enable_compile_cache()
    controls = {"all": CONTROLS, "none": ()}.get(
        args.controls, tuple(c for c in args.controls.split(",") if c))
    device = jax.devices()[0]

    for seed in (int(x) for x in args.seeds.split(",")):
        truth = make_params(cfg, seed, device)
        for control in (None, *controls):
            program_params, program_cfg = steer(truth, cfg, control)
            # what ``common_check`` and the reference's ``system`` read of an
            # engine: its parameters, configuration, page and placement
            engine = types.SimpleNamespace(
                params=program_params, model_cfg=cfg,
                page_size=int(config["env"]["BLOCK_SIZE"]), _replicated=device,
                mesh=None, prefill_attn="xla" if args.rehearse else "pallas",
            )
            steered_ref = ref
            if program_cfg is not cfg:
                # the reference reads the model's own configuration; the
                # program is handed the steered one, depth as asked
                def system(engine, tokens, steps, interpret, params=None,
                           cfg=None, _to=program_cfg):
                    cfg = dataclasses.replace(
                        _to, n_layers=(cfg or _to).n_layers)
                    return ref.system(
                        engine, tokens, steps, interpret, params, cfg)

                steered_ref = types.SimpleNamespace(
                    forward=ref.forward, TOL_BF16=ref.TOL_BF16,
                    ROUTER_GAP_MIN=ref.ROUTER_GAP_MIN, system=system)
            line = reference.common_check(
                engine, steered_ref, seed, interpret=args.rehearse,
                truth=truth, **sizes)
            print(json.dumps({"seed": seed, "control": control or "sound",
                              **line}), flush=True)
            del engine, program_params
        del truth  # the next seed's tree does not fit beside this one
    return 0


if __name__ == "__main__":
    sys.exit(main())
