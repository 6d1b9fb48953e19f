#!/usr/bin/env python3
"""How far the reference check moves under a change of precision: the runs
behind the tolerances each ``references/<name>.py`` states. Not part of a
benchmark run.

    python3 chipbench/probe_reference.py --config qwen3-30b-a3b --seeds 1,2,3
        [--layers N] [--moe-gmm xla] [--pool int8] [--weights int8]
        [--order 1,0,2,...]

Builds the configuration's weights from each seed the way a run does (no
engine, no pool, no server), runs the check of the reference the
configuration names (``reference.check`` finds ``references/<name>.py``: its
``forward``, its tolerances, its system side where it has one) on them and
prints one JSON line per seed. ``--pool int8`` sends prefill and decode
through an int8 scratch pool (``KV_QUANT_HBM=int8``: the XLA prefill, as the
engine chooses then); ``--weights int8`` gives the system int8 matmul weights
and experts made from the same seed while the reference reads the bf16 ones
(both trees are resident, so take fewer ``--layers``); ``--moe-gmm xla``
takes ``ragged_dot`` instead of the megablox kernel; ``--order`` gives the
system the seed's layers in another order or one of them twice (a program
that is not the model: what the whole-model bounds are there for).
``--rehearse`` is the CPU rehearsal of the same path at the tiny preset.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--moe-gmm", default=None)
    ap.add_argument("--pool", choices=("int8",), default=None)
    ap.add_argument("--weights", choices=("int8",), default=None)
    ap.add_argument("--order", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from chipbench import reference, run
    from llm_d_kv_cache_manager_tpu.models import llama

    config = run.load_config(args.config, args.rehearse)
    cfg = run.model_config(config, args.rehearse)
    replace = {}
    if args.layers:
        replace["n_layers"] = args.layers
    if args.moe_gmm:
        replace["moe_gmm"] = args.moe_gmm
    cfg = dataclasses.replace(cfg, **replace)
    device = run.check_device(1, args.rehearse)[0][0]
    if not args.rehearse:
        from llm_d_kv_cache_manager_tpu.utils.compile_cache import (
            enable_compile_cache,
        )

        enable_compile_cache()

    def weights(seed, **quantize):
        make = jax.jit(functools.partial(llama.init_params, cfg=cfg, **quantize))
        with jax.default_device(device):
            return jax.block_until_ready(make(jax.random.PRNGKey(seed)))

    for seed in (int(x) % (2**31 - 1) for x in args.seeds.split(",")):
        truth = weights(seed)
        params = truth
        if args.weights:
            params = weights(seed, quantize=args.weights, quantize_experts=True)
        if args.order:
            params = {**params, "layers": [
                params["layers"][int(i)] for i in args.order.split(",")]}
        view = types.SimpleNamespace(
            params=params, model_cfg=cfg,
            page_size=int(config["env"]["BLOCK_SIZE"]), mesh=None,
            _replicated=device,
            config=types.SimpleNamespace(kv_quant_hbm=args.pool),
            prefill_attn="xla" if args.pool or args.rehearse else "pallas",
        )
        small = {"prompt_tokens": 16, "steps": 4} if args.rehearse else {}
        out = reference.check(view, config["reference"], seed,
                              interpret=args.rehearse, truth=truth, **small)
        print(json.dumps({"config": args.config, "seed": seed,
                          "layers": cfg.n_layers, "moe_gmm": args.moe_gmm,
                          "pool": args.pool, "weights": args.weights,
                          "order": args.order, **out}),
              flush=True)
        del truth, params, view
    return 0


if __name__ == "__main__":
    sys.exit(main())
