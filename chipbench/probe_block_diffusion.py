#!/usr/bin/env python3
"""``probe_reference.py`` with one more control, for a configuration that
generates by diffusion over blocks: ``--mask causal`` gives the system the
causal program (every attention call of the model's forward made with
``block_length`` 0, as for an autoregressive model) while the blocks, the
denoising program and the reference stay what they are. A reference check
that passes it does not hold the mask. Not part of a benchmark run.

    python3 chipbench/probe_block_diffusion.py --mask causal \\
        --config sdar-30b-a3b --seeds 1,2,3 [probe_reference.py's options]
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mask", choices=("block", "causal"), default="block")
    args, rest = ap.parse_known_args(argv)
    if "--rehearse" in rest and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from chipbench import probe_reference
    from llm_d_kv_cache_manager_tpu.models import llama

    if args.mask == "block":
        return probe_reference.main(rest)

    # the steering is here, in the probe: the program has no such option
    def causal(fn):
        @functools.wraps(fn)
        def call(*a, block_length=0, **kw):
            return fn(*a, block_length=0, **kw)
        return call

    programs = (llama.prefill, llama.denoise_step, llama.denoise_steps)
    kept = (llama._flash_prefill_tp, llama.prefill_with_paged_context)
    try:
        for jitted in programs:  # a program traced before would not be steered
            jitted.clear_cache()
        llama._flash_prefill_tp = causal(kept[0])
        llama.prefill_with_paged_context = causal(kept[1])
        return probe_reference.main(rest)
    finally:
        llama._flash_prefill_tp, llama.prefill_with_paged_context = kept
        for jitted in programs:  # nor may a steered one be met again
            jitted.clear_cache()


if __name__ == "__main__":
    sys.exit(main())
