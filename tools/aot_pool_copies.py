"""What the compiled programs do to the KV pool, read without a chip.

The TPU's compiler is installed beside JAX and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). This tool
compiles the served programs of the benchmark's configurations — the fused
``decode_steps`` or ``denoise_steps`` and one ``prefill`` dispatch, at the
shapes ``chipbench/configs/<name>.json`` pins — for one v5e chip and lists
every instruction outside a fusion's body whose result has the shape of a
whole K or V pool ``[L, P, page, n_kv, hd]`` (a latent model's one pool:
``[L, P, page, row]``) or of one layer's slice of it, and the layout the
compiler gives the pool. A model with convolution layers has a state pool
beside them (``[conv layers, P, row]``, ``state_pool_shape``), a model with
sliding-window layers a pair of window pools (``[sliding layers, window
pages, page, n_kv, hd]``, ``window_pool_shape``), a model with
linear-attention layers a state pool of slots (``[linear layers, slots,
heads, K, V]`` float32 and the carried rows ``[linear layers, slots, R, C]``,
a slot whole tiles that lie together: ``state_pool_shape`` /
``state_rows_shape``): they are listed the same way.

A pool is hundreds of MiB: any such instruction that is not free (a
``bitcast``, a ``parameter``, tuple plumbing) reads and writes that much
HBM each time the program runs. PR 29 found four ``copy`` of the whole
pool in every MoE program this way (the scatter's window held the layer
axis), and PR 31 the per-layer slices ``_prefill_body`` handed the prefill
kernel (it now takes the five-dimensional pool): no served program of the
cells lists an instruction that moves bytes, and
``tests/test_pool_layout.py`` holds that.

    python -m tools.aot_pool_copies                      # every program
    python -m tools.aot_pool_copies --config qwen3-32b --program prefill

Nothing runs: no time comes out of this, only the compiler's plan. A
compile takes 20-60 s a program on a CPU host.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import re
import sys
from pathlib import Path
from typing import Iterator, NamedTuple

CONFIGS = Path(__file__).resolve().parent.parent / "chipbench" / "configs"
PROGRAMS = ("decode_steps", "denoise_steps", "prefill")
PREFILL_ROWS, PREFILL_CHUNK = 8, 128  # a dispatch of the cells: 1024 rows

#: Opcodes that move no bytes. A ``while`` hands its state to its body and
#: back in one buffer (its operand, the body's parameter and root and its
#: result are assigned the same; where that cannot be, the compiler inserts
#: a ``copy``, which is listed), so a pool that rides in a loop's state
#: (``prefill_packed``'s loop over rows reads the pools) costs what the
#: body's own instructions cost, and those are listed like any other.
FREE = frozenset({"parameter", "bitcast", "get-tuple-element", "tuple", "while"})

#: Pallas kernels that update a pool where it lies (``input_output_aliases``):
#: their custom call's result IS the pool, the same buffer, and what they
#: move is the slots they were given, not the pool (``ops/kda.py``).
IN_PLACE_KERNELS = ("kda_decode",)


class PoolInstruction(NamedTuple):
    computation: str
    name: str
    opcode: str
    result: str  # the result type as printed, layouts included
    moves_bytes: bool


def _split_type(rest: str) -> tuple[str, str]:
    """``rest`` is an instruction after ``" = "``: (result type, remainder).
    A tuple type is parenthesised and layouts carry parentheses of their
    own (``T(8,128)(2,1)``), so a tuple is closed by depth, a plain type by
    its first blank."""
    if not rest.startswith("("):
        head, _, tail = rest.partition(" ")
        return head, tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[: i + 1], rest[i + 1 :].lstrip()
    raise ValueError(f"unbalanced result type: {rest[:80]!r}")


_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def _parse(hlo: str) -> tuple[dict[str, list[tuple[str, str, str]]], dict[str, str]]:
    """An optimised HLO module's text as ({computation: [(name, opcode,
    result type)]}, {fusion instruction: the computation that is its body})."""
    computations: dict[str, list[tuple[str, str, str]]] = {}
    bodies: dict[str, str] = {}
    current: list[tuple[str, str, str]] = []
    for line in hlo.splitlines():
        header = _HEADER.match(line)
        if header:
            current = computations.setdefault(header.group(1), [])
            continue
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        result, tail = _split_type(found.group(2))
        opcode = tail.partition("(")[0]
        current.append((found.group(1), opcode, result))
        if opcode == "fusion":
            bodies[found.group(1)] = _CALLS.search(tail).group(1)
    return computations, bodies


def instructions(hlo: str) -> Iterator[tuple[str, str, str, str]]:
    """(computation, name, opcode, result type) of every instruction that is
    not inside a fusion's body."""
    computations, bodies = _parse(hlo)
    fused = set(bodies.values())
    for computation, members in computations.items():
        if computation not in fused:
            for name, opcode, result in members:
                yield computation, name, opcode, result


def fusion_opcodes(hlo: str) -> dict[str, set[str]]:
    """The opcodes inside each fusion's body, by the fusion instruction's
    name: what tells a scatter's fusion from a relayout's."""
    computations, bodies = _parse(hlo)
    return {
        fusion: {opcode for _, opcode, _ in computations.get(body, ())}
        for fusion, body in bodies.items()
    }


def pool_instructions(
    hlo: str, pool_shape: tuple[int, ...], *, layer_slices: bool = False
) -> list[PoolInstruction]:
    """The instructions whose result (or a member of whose tuple result)
    has ``pool_shape``; with ``layer_slices`` also those shaped like one
    layer of it, ``[P, page, n_kv, hd]`` or ``[1, P, page, n_kv, hd]``."""
    shapes = [pool_shape]
    if layer_slices:
        shapes += [pool_shape[1:], (1, *pool_shape[1:])]
    wanted = re.compile(
        "|".join(r"\[" + ",".join(map(str, s)) + r"\]" for s in shapes)
    )
    return [
        PoolInstruction(
            comp, name, op, result,
            op not in FREE and not (
                op == "custom-call" and name.startswith(IN_PLACE_KERNELS)),
        )
        for comp, name, op, result in instructions(hlo)
        if wanted.search(result)
    ]


def pool_layout(hlo: str, pool_shape: tuple[int, ...]) -> str:
    """The pool parameter's type as the compiled module prints it, layout
    and tiling included (``bf16[8,16384,16,640]{3,2,1,0:T(8,128)(2,1)}``):
    what the device holds, which a docstring can only guess."""
    for i in pool_instructions(hlo, pool_shape):
        if i.opcode == "parameter":
            return i.result
    return "not a parameter of the module"


def tuple_members(result: str) -> str:
    """A result type for a line of output: a tuple as its distinct members,
    each with its count."""
    if not result.startswith("("):
        return result
    members = re.sub(r"/\*index=\d+\*/", "", result[1:-1]).split(", ")
    return ", ".join(
        f"{members.count(m)} x {m}" for m in dict.fromkeys(members)
    )


def describe_v5e():
    """A v5e host of four chips (2x2), described, not attached: its
    ``.devices`` make shardings for shapes. Loads libtpu (a compile-only
    client; no device is opened), which one process at a time may hold."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


def compile_text(fn, *args, **kwargs) -> str:
    """Optimised HLO of ``fn`` (a ``jax.jit``) lowered for the shardings its
    ``ShapeDtypeStruct`` arguments carry. Compiled Pallas kernels refuse a
    host whose default backend is no TPU (``ops/_mosaic.py``); nothing runs
    here, so the refusal is lifted for the lowering alone."""
    modules = [
        importlib.import_module(f"llm_d_kv_cache_manager_tpu.ops.{name}")
        for name in (
            "flash_prefill", "gmm", "kda", "mla_attention", "paged_attention",
        )
    ]
    kept = [m.require_tpu_unless_interpret for m in modules]
    for m in modules:
        m.require_tpu_unless_interpret = lambda kernel, interpret: None
    try:
        return fn.lower(*args, **kwargs).compile().as_text()
    finally:
        for m, guard in zip(modules, kept):
            m.require_tpu_unless_interpret = guard


def routed_decode_calls() -> list[tuple[str, int, int, int, int]]:
    """(name, rows, experts held, d, f) of every grouped matmul the cells'
    decode programs make, from ``chipbench/configs``: rows = lanes x top-k
    (x the block of a block-diffusion model), gate/up ``[d, f]`` and down
    ``[f, d]`` of each configuration with routed layers."""
    from llm_d_kv_cache_manager_tpu.models import llama

    calls = []
    for path in sorted(CONFIGS.glob("*.json")):
        spec = json.loads(path.read_text())["chipbench"]
        cfg = dataclasses.replace(getattr(llama, spec["preset"]), **spec["replace"])
        if not cfg.n_experts:
            continue  # a dense model has no routed layer
        rows = (
            spec["env"]["DECODE_BATCH_SIZE"] * cfg.n_experts_per_tok
            * max(cfg.block_length, 1)
        )
        d, f = cfg.hidden_size, cfg.moe_intermediate_size
        calls += [
            (f"{path.stem}-up", rows, cfg.experts_held, d, f),
            (f"{path.stem}-down", rows, cfg.experts_held, f, d),
        ]
    return calls


def served_program(config: str, program: str, one_chip, **replace):
    """(jitted function, args, kwargs, pool shape) of a configuration's
    served ``program`` at the shapes its cell pins, or None where the
    configuration does not serve it (``decode_steps`` under a block mask,
    ``denoise_steps`` without one). ``replace``: fields of the model's
    configuration over the cell's own (a test's shallower ``n_layers``)."""
    import jax
    import jax.numpy as jnp

    from llm_d_kv_cache_manager_tpu.models import llama

    spec = json.loads((CONFIGS / f"{config}.json").read_text())["chipbench"]
    cfg = dataclasses.replace(
        getattr(llama, spec["preset"]), **{**spec["replace"], **replace}
    )
    env, engine = spec["env"], spec["engine"]
    lanes, page = env["DECODE_BATCH_SIZE"], env["BLOCK_SIZE"]
    table_w = engine["decode_pages_bucket"]

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, f32 = jnp.int32, jnp.float32
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: llama.init_params(k, cfg), jax.random.PRNGKey(0)),
    )
    # the pools as ``init_kv_pages`` makes them: K and V, or a latent pool
    # and the array of no pages that stands in the second place
    pool_shape, second_shape = (
        p.shape for p in jax.eval_shape(
            lambda: llama.init_kv_pages(cfg, env["TOTAL_PAGES"], page))
    )
    pool, second = S(pool_shape, jnp.bfloat16), S(second_shape, jnp.bfloat16)
    # ... and, for a model with convolution layers, the state pool beside them
    state = jax.eval_shape(
        lambda: llama.init_state_pages(cfg, env["TOTAL_PAGES"]))
    stateful = {} if state is None else {
        "state_pages": S(state.shape, state.dtype)}
    # ... or, for a model with linear-attention layers, the state pool of
    # slots (matrices, carried rows) and the rows' slots, sized as the
    # engine sizes it (``Engine.__init__``)
    slots = lanes + PREFILL_ROWS + env.get("STATE_SNAPSHOT_SLOTS", 0)
    kda = jax.eval_shape(lambda: llama.init_kda_state(cfg, slots))
    decode_slots, prefill_slots = {}, {}
    if kda is not None:
        stateful = {
            "state_pages": tuple(S(x.shape, x.dtype) for x in kda)}
        decode_slots = {"state_slots": S((lanes, 3), i32)}
        prefill_slots = {"state_slots": S((PREFILL_ROWS, 2), i32)}
    # ... or, for a model with sliding layers, the pair of window pools and
    # the dispatch's window tables (one width: ``Engine.window_table_pages``
    # in a decode dispatch, the context bucket in a prefill)
    window = jax.eval_shape(lambda: llama.init_window_pages(
        cfg, env.get("WINDOW_PAGES", env["TOTAL_PAGES"]), page))
    decode_window, prefill_window = {}, {}
    if window is not None:
        pools = tuple(S(w.shape, w.dtype) for w in window)
        decode_window = {"window_pages": pools, "window_packed": S(
            (lanes, cfg.sliding_window // page + 3 + 1), i32)}
        prefill_window = {"window_pages": pools, "window_packed": S(
            (PREFILL_ROWS, PREFILL_CHUNK + engine["prefill_ctx_bucket"] + 1),
            i32)}
    key = S((2,), jnp.uint32)
    if program == "decode_steps" and cfg.block_length == 0:
        # ids, then ``llama.pack_decode_inputs``' one array
        packed = S((lanes, table_w + llama.DECODE_PACKED_TAIL), i32)
        args = (params, cfg, S((lanes,), i32), packed, pool, second, key)
        kwargs = dict(page_size=page, num_steps=1, interpret=False, mesh=None,
                      **stateful, **decode_window, **decode_slots)
        return llama.decode_steps, args, kwargs, pool_shape
    if program == "denoise_steps" and cfg.block_length > 0:
        width = 2 * cfg.block_length + table_w + 5
        args = (params, cfg, S((lanes, width), i32), S((lanes, 3), f32), pool, second, key)
        # as the engine dispatches it: the forward before rides along
        kwargs = dict(
            page_size=page, table_w=table_w, mesh=None, attn_impl="pallas",
            interpret=False, carried=S((lanes, 2 * cfg.block_length + 1), i32),
        )
        return llama.denoise_steps, args, kwargs, pool_shape
    if program == "prefill":
        # as the engine dispatches it: ``llama.pack_prefill_inputs``' one array
        width = 5 * PREFILL_CHUNK + engine["prefill_ctx_bucket"] + 1
        args = (params, cfg, S((PREFILL_ROWS, width), i32), pool, second)
        kwargs = dict(chunk=PREFILL_CHUNK, mesh=None, attn_impl="pallas",
                      interpret=False, **stateful, **prefill_window,
                      **prefill_slots)
        return llama.prefill_packed, args, kwargs, pool_shape
    return None


def state_pool_shape(kwargs: dict):
    """The state pool's shape among a served program's keyword arguments
    (``served_program``), or None: the model has no convolution layers. A
    model with linear-attention layers has a pair there: the shape of the
    matrices' pool (``state_rows_shape``: the carried rows')."""
    state = kwargs.get("state_pages")
    if isinstance(state, tuple):
        state = state[0]
    return None if state is None else tuple(state.shape)


def state_rows_shape(kwargs: dict):
    """The shape of the carried rows' pool of a model with linear-attention
    layers among a served program's keyword arguments (``[linear layers,
    slots, *LlamaConfig.kda_conv_tile]``), or None."""
    state = kwargs.get("state_pages")
    return tuple(state[1].shape) if isinstance(state, tuple) else None


def window_pool_shape(kwargs: dict):
    """The shape of each of the two window pools among a served program's
    keyword arguments, or None: the model has no sliding layers."""
    window = kwargs.get("window_pages")
    return None if window is None else tuple(window[0].shape)


def main(argv=None) -> int:
    names = sorted(p.stem for p in CONFIGS.glob("*.json"))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", action="append", choices=names)
    ap.add_argument("--program", action="append", choices=PROGRAMS)
    ap.add_argument("--dump", help="directory for each program's whole HLO text")
    opts = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "cpu"  # the chip is described, never opened
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(describe_v5e().devices[0])
    for config in opts.config or names:
        for program in opts.program or PROGRAMS:
            served = served_program(config, program, one_chip)
            if served is None:
                continue
            fn, args, kwargs, pool_shape = served
            hlo = compile_text(fn, *args, **kwargs)
            if opts.dump:
                os.makedirs(opts.dump, exist_ok=True)
                Path(opts.dump, f"{config}.{program}.hlo.txt").write_text(hlo)
            pools = {"pool": pool_shape}
            if state_pool_shape(kwargs):
                pools["state pool"] = state_pool_shape(kwargs)
            if state_rows_shape(kwargs):
                pools["state rows pool"] = state_rows_shape(kwargs)
            if window_pool_shape(kwargs):
                pools["window pool"] = window_pool_shape(kwargs)
            for what, shape in pools.items():
                report(f"{config} {program} {what}", hlo, shape)
    return 0


def report(title: str, hlo: str, pool_shape: tuple[int, ...]) -> None:
    found = pool_instructions(hlo, pool_shape, layer_slices=True)
    moving = [i for i in found if i.moves_bytes]
    print(f"{title} {list(pool_shape)}: "
          f"{len(moving)} of {len(found)} instructions move bytes; "
          f"held as {pool_layout(hlo, pool_shape)}")
    # Eight layers make eight lines that differ in a suffix: one
    # line for each (opcode, result), with the first name.
    alike: dict[tuple, list[str]] = {}
    for i in found:
        key = (i.moves_bytes, i.opcode, tuple_members(i.result))
        alike.setdefault(key, []).append(i.name)
    for (moves, opcode, result), group in alike.items():
        print(f"  {'*' if moves else ' '} {len(group):>2} x {opcode:<18} "
              f"%{group[0]:<24} {result}")


if __name__ == "__main__":
    sys.exit(main())
