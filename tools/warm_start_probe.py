"""What a program that holds the full-context ``paged_attention`` costs to
bring up, cold and from a warm compile cache (ROADMAP S6, PERF.md section 6,
PR 57). On the chip, from the repo's root; a process holds the chip, so every
phase is a process of its own and the caller stays off JAX:

    python -m tools.warm_start_probe                      # both shapes, three phases each
    python -m tools.warm_start_probe --tree _chip_tree/parent   # another tree's kernel
    python -m tools.warm_start_probe --straight           # the group of single copies unrolled
    python -m tools.warm_start_probe --events -- chipbench/run.py --workload qwen3-32b.sessions \
        --seed 1 --seconds 5 --trace 0                    # a whole set-up, a line a program

The bare call is every full layer's call of one decode step at a cell's shape
(``SHAPES``), in one jitted function. ``cold``: the persistent cache off,
seconds of ``.trace()`` / ``.lower()`` / ``.compile()`` and of the first and
the second call. ``fill`` writes the cache; ``warm``, a new process, reads it:
the same five numbers, the compile now a retrieval, split into reading the
entry and loading it (``deserialize_executable``), with the entry's bytes.
``--events`` runs a script under the listener ``chipbench/run.py::CompileCounter``
registers and prints, a program, what JAX reports of its trace, lowering,
retrieval and backend compile (which holds the retrieval).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import runpy
import subprocess
import sys
import time

#: (lanes, KV heads, group, head size, table pages, pool layers, pool pages)
SHAPES = {
    "sessions": (16, 8, 8, 128, 256, 5, 8192),
    "longdocs": (32, 8, 6, 128, 2176, 1, 40960),
}
PAGE = 16
EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval",
    "/jax/core/compile/backend_compile_duration": "compile",
}


class Programs:
    """JAX's compile events, gathered a program: a retrieval carries no
    name and belongs to the backend compile that reports after it."""

    def __init__(self):
        import jax.monitoring
        from jax._src import compilation_cache as cc

        self.rows, self.pending = [], {}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        decompress = cc.decompress_executable

        def sized(blob):
            out = decompress(blob)
            self.pending["entry_bytes"] = len(blob)
            self.pending["executable_bytes"] = len(out)
            self.pending["_read_at"] = time.monotonic()
            return out

        cc.decompress_executable = sized

    def _on(self, event, duration, fun_name=None, **_):
        what = EVENTS.get(event)
        if what is None:
            return
        if what == "retrieval":
            self.pending[what] = self.pending.get(what, 0.0) + duration
        else:  # a nested jit reports before the one that holds it: keep the outermost
            self.pending[what] = duration
        if what == "retrieval" and "_read_at" in self.pending:
            # the entry read and unpacked, then loaded onto the device
            self.pending["load"] = time.monotonic() - self.pending.pop("_read_at")
        if what == "compile":
            row = {"program": fun_name, **self.pending}
            self.rows.append(row)
            self.pending = {}
            print("[program]", json.dumps(row), flush=True)


def bare_call(shape: str, straight: bool):
    """The jitted call and its arguments: tables that are runs, contexts a
    quarter to all of the table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # (the module: ``ops`` exports the function under the same name)
    module = importlib.import_module("llm_d_kv_cache_manager_tpu.ops.paged_attention")

    if straight:  # PR 56's form: a group that is no run unrolled on the chip
        walk = module.for_step_pages
        module.for_step_pages = lambda *a, **kw: walk(*a, **{**kw, "rolled": False})
    lanes, n_kv, group, d, width, layers, pool = SHAPES[shape]
    rng = np.random.default_rng(57)
    lens = rng.integers(width * PAGE // 4, width * PAGE, lanes)
    tables = (1 + np.arange(lanes)[:, None] * width + np.arange(width)) % pool
    key = jax.random.key(57)
    q = jax.random.normal(key, (lanes, n_kv * group, d), jnp.bfloat16)
    k = jax.random.normal(key, (layers, pool, PAGE, n_kv, d), jnp.bfloat16)
    fresh = jax.random.normal(key, (lanes, n_kv, d), jnp.bfloat16)

    def step(q, k, v, tables, lens, fk, fv):
        out = q.astype(jnp.float32)
        for li in range(layers):
            out += module.paged_attention(q, k, v, tables, lens, fk, fv, layer=li)
        return out

    return jax.jit(step), (
        q, k, k + 1, jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32),
        fresh, fresh)


def phase(name: str, shape: str, straight: bool) -> dict:
    import jax

    from llm_d_kv_cache_manager_tpu.utils.compile_cache import enable_compile_cache

    if name == "cold":
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    programs = Programs()
    fn, args = bare_call(shape, straight)
    jax.block_until_ready(args)
    row, at = {"phase": name, "shape": shape}, time.monotonic()

    def lap(key, value=None):
        nonlocal at
        jax.block_until_ready(value)
        row[key], at = time.monotonic() - at, time.monotonic()
        return value

    traced = lap("trace_s", fn.trace(*args))
    lowered = lap("lower_s", traced.lower())
    compiled = lap("compile_s", lowered.compile())
    lap("first_call_s", compiled(*args))
    lap("second_call_s", compiled(*args))
    for key in ("retrieval", "load", "entry_bytes", "executable_bytes"):
        if programs.rows and key in programs.rows[-1]:
            row[key] = programs.rows[-1][key]
    row["custom_call_chars"] = sum(
        len(line) for line in lowered.as_text().splitlines()
        if "tpu_custom_call" in line)
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=".", help="the checkout whose kernel is probed")
    ap.add_argument("--straight", action="store_true")
    ap.add_argument("--phase", choices=("cold", "fill", "warm"))
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--events", action="store_true")
    ap.add_argument("script", nargs="*")
    args = ap.parse_args()
    if args.events:
        Programs()
        sys.argv = args.script
        runpy.run_path(args.script[0], run_name="__main__")
    elif args.phase:
        print("[probe]", json.dumps(phase(args.phase, args.shape, args.straight)),
              flush=True)
    else:  # a process a phase, none of them this one
        env = dict(os.environ, PYTHONPATH=os.path.abspath(args.tree))
        for shape in SHAPES:
            for name in ("cold", "fill", "warm"):
                cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
                       "--shape", shape] + ["--straight"] * args.straight
                subprocess.run(cmd, env=env, cwd=args.tree, check=True)


if __name__ == "__main__":
    main()
