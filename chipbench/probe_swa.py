#!/usr/bin/env python3
"""The window-and-full configuration's reference check as a run makes it, and
the controls that must read not correct: the runs behind ``references/
swa_moe.py``'s tolerances. Not part of a benchmark run.

    python3 chipbench/probe_swa.py --seeds 1,2 [--controls all|none|a,b]
        [--prompt 128] [--steps 8] [--rehearse]

Builds ``trinity-large-preview``'s weights from each seed the way a run does
(no engine, no server; pools just large enough) and makes the harness's own
comparison (``reference.common_check``: two prompts through the reference's
``system`` side — a cold prefill, warm chunks through both pools until the
sequence stands three quarters of a window past the window, window pages given
back and reused on the way, greedy decode steps over a page's end — then
every layer alone), once sound and once under each control. One line of JSON
a run.

The controls steer the PROGRAM (the reference and the weights it reads stay
what they are); the steering is here, in the probe: the program has no such
option.

- ``no_window``: the sliding layers see their whole context (they keep
  their keys and values in the context pool, as a model without a window
  would, and still rotate);
- ``rope_on_full``: q and k rotated on the full layers too;
- ``no_gate``: the gate on the attention's output left out;
- ``no_attn_post_norm`` / ``no_mlp_post_norm``: either sandwich norm left out;
- ``no_embed_scale``: the embedding's factor sqrt(hidden) left out;
- ``no_renorm``: the gates not renormalised over the chosen experts;
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

CONTROLS = ("no_window", "rope_on_full", "no_gate", "no_attn_post_norm",
            "no_mlp_post_norm", "no_embed_scale", "no_renorm", "int8_weights")
CONFIG = "trinity-large-preview"
#: leaves a control takes out of every layer's tree
DROPPED = {"no_gate": "wg", "no_attn_post_norm": "attn_post_norm",
           "no_mlp_post_norm": "mlp_post_norm"}


def steer_params(params, control):
    """The tree the PROGRAM reads under a control that is one of weights."""
    from llm_d_kv_cache_manager_tpu.models import quant

    def layers(change):
        return {**params, "layers": [change(dict(la)) for la in params["layers"]]}

    if control in DROPPED:
        def drop(layer):
            layer.pop(DROPPED[control])
            return layer
        return layers(drop)
    if control == "no_window":
        def widen(layer):  # a full layer that still rotates (``steer``)
            if "window" in layer:
                layer["rotates"] = layer.pop("window")
            return layer
        return layers(widen)
    if control == "int8_weights":
        return quant.quantize_params(params, quantize_experts=True)
    return params


def steer(llama, control):
    """Patch the program's model code for one control; returns the undo."""
    kept = {}

    def patch(name, value):
        kept[name] = getattr(llama, name)
        setattr(llama, name, value)

    def with_cfg(name, **change):
        orig = getattr(llama, name)
        patch(name, lambda first, cfg, *a, **kw: orig(
            first, dataclasses.replace(cfg, **change), *a, **kw))

    if control == "rope_on_full":
        patch("_rotates", lambda layer, cfg: True)
    elif control == "no_window":
        patch("_rotates", lambda layer, cfg: "rotates" in layer)
    elif control == "no_embed_scale":
        with_cfg("_embed", scale_embeddings=False)
    elif control == "no_renorm":
        with_cfg("_moe_gates", norm_topk_prob=False)
    elif control is not None and control not in CONTROLS:
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
        truth = make_params(cfg, seed, device)
        for control in (None, *controls):
            for jitted in programs:
                jitted.clear_cache()
            undo = steer(llama, control)
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
