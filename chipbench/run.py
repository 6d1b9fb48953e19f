#!/usr/bin/env python3
"""One process, one cell, once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and everything that belongs to it by
name — ``configs/<config>.json``, ``traffic/<traffic>.json``,
``layer_metrics/<metric>.py``, ``references/<reference>.py`` — and holds no
table of them. Starts the scorer and the cell's 1 or 4 in-process pods,
makes weights on the device from ``--seed``, checks the system against the
float32 reference, fills the cache the traffic shares, warms exactly the
cell's shape set, measures for ``--seconds`` and prints the contract's one
last line. Everything else it says goes on earlier lines (``[chipbench]
...``) or into ``chipbench/out/``.

``--rehearse`` is the CPU rehearsal for tests only (tiny presets, Pallas
interpreter, virtual devices): it says ``platform: cpu``, writes no device
metric and is never a measurement. ``--rate`` overrides an open-loop mix's
rate for the one-off sweep that finds a cell's knee.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # setup_s runs from here to the window

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import costs, metrics, traffic  # noqa: E402
from chipbench.fleet import BenchFailure  # noqa: E402

#: seconds of the window the profiler covers in a ``--trace 1`` run
TRACE_SECONDS = 4.0
TRACE_MARGIN_S = 0.3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


# -- the cell, by name -------------------------------------------------------
def load_benchmark(path: str = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchFailure(f"no workload {name!r} in BENCHMARK.json")


def metrics_of_cell(entries, cell_name: str) -> list[dict]:
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def load_config(name: str, rehearse: bool = False) -> dict:
    """``configs/<name>.json``'s harness group (``chipbench``), checked
    against the published sizes the file itself states."""
    path = os.path.join(HERE, "configs", f"{name}.json")
    with open(path) as f:
        published = json.load(f)
    config = dict(published["chipbench"])
    if rehearse:
        config.update(config.get("rehearse", {}))
    config["published"] = published
    return config


def model_config(config: dict, rehearse: bool):
    """The program's preset with the configuration's ``replace`` keys; at
    full size every published width must equal the preset's: the keys below
    and those the configuration lists itself under ``widths`` ({published
    key: attribute of the preset}; a listed key that either side lacks
    fails the run)."""
    from llm_d_kv_cache_manager_tpu import models

    cfg = dataclasses.replace(
        getattr(models, config["preset"]), **config["replace"]
    )
    if not rehearse:
        pub = config["published"]
        want = {
            "hidden_size": cfg.hidden_size, "head_dim": cfg.hd,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab_size,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "intermediate_size": cfg.intermediate_size,
        }
        if cfg.n_experts:
            want.update(num_experts=cfg.n_experts,
                        num_experts_per_tok=cfg.n_experts_per_tok,
                        moe_intermediate_size=cfg.moe_inter,
                        norm_topk_prob=cfg.norm_topk_prob)
        for key, attr in config.get("widths", {}).items():
            if not hasattr(cfg, attr):
                raise BenchFailure(
                    f"widths: {key!r} is checked against {attr!r}, which "
                    f"the program's preset {config['preset']} does not have"
                )
            want[key] = getattr(cfg, attr)
        wrong = {k: (pub.get(k, "missing"), v) for k, v in want.items()
                 if k not in pub or pub[k] != v}
        if wrong:
            raise BenchFailure(
                f"configuration file and program preset disagree: {wrong}"
            )
    return cfg


def load_layer_metric(name: str):
    """The reader of one per-layer metric, ``read(records)``. It is the
    ``read`` of ``layer_metrics/<name>.py``; a name with a suffix and no
    file of its own (``kernel_time_share.gmm``) is read by the file of the
    part before the first dot, whose ``read(records, suffix)`` serves every
    suffix — such a metric is added with its BENCHMARK.json entry alone."""
    stem, _, suffix = name.partition(".")
    for file, args in ((name, ()), (stem, (suffix,))):
        path = os.path.join(HERE, "layer_metrics", f"{file}.py")
        if os.path.isfile(path):
            break
    else:
        raise BenchFailure(f"no reader for {name!r} under layer_metrics/")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + file.replace(".", "_").replace("-", "_"),
        path,
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return lambda records: module.read(records, *args)


@dataclasses.dataclass
class RunRecords:
    """What a per-layer reader may read."""
    cell: dict
    good: list
    failed: list
    in_flight: list
    in_flight_tokens: int  # generated by the requests in flight at the close
    late_s: list
    window_s: float
    stats_before: list  # per pod: GET /stats before the window
    stats_after: list
    running_samples: list  # 10 Hz: [running per pod]
    lanes: int
    page: int
    pods: list  # in-process handles (fleet.Pod)
    step_before: list  # per pod: Engine.step_stats copies (traced run only)
    step_after: list
    compiles_in_window: int
    memory_peak_bytes: int
    model_cfg: object
    peaks: dict
    trace: dict  # trace_reduce.reduce(...) or None


# -- device -------------------------------------------------------------------
def check_device(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    say(f"device: platform={platform} kind={kind!r} count={len(devs)}")
    if rehearse:
        if platform != "cpu":
            raise BenchFailure("--rehearse is the CPU rehearsal")
        peaks = {}
    else:
        if platform != "tpu":
            raise BenchFailure(
                f"JAX found no accelerator (platform={platform!r})"
            )
        peaks = costs.load_peaks(kind)  # unknown kind: KeyError, non-zero
    if len(devs) < chips:
        raise BenchFailure(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips], peaks


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included: a program
    that is new to the process is a shape the warm-up missed)."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


def at_the_close(pods) -> tuple:
    """``run_window``'s ``on_close`` for these pods, called at the instant
    the window closes, before anything is aborted: (what the requests still
    in the schedulers have generated so far, every one of them having
    arrived inside the window; a copy of each engine's ``step_stats``).
    In-process reads, nothing waited for, so the counters the readers see
    are the window's and hold none of the dispatches of fewer lanes that
    the aborts, the profiler's stop and the emptying tail add after it."""
    tokens = sum(
        seq.num_generated for p in pods
        for queue in (p.engine.scheduler.running,
                      p.engine.scheduler.prefilling,
                      p.engine.scheduler.waiting)
        for seq in list(queue)
    )
    return tokens, [dict(p.engine.step_stats) for p in pods]


# -- set-up traffic -----------------------------------------------------------
def must_succeed(records, what: str) -> None:
    for r in records:
        fault = metrics.response_fault(r)
        if fault:
            raise BenchFailure(f"{what}: request {r['index']}: {fault}")


def fill_cache(client, gateway, schedule, spec, seed, pods, scorer_url,
               model, page: int) -> None:
    """Make every shared prefix resident, group g on pod g mod n (cold
    placements spread, as a router does when the index knows nothing),
    and wait until the scorer sees the blocks."""
    from chipbench.fleet import wait_visible
    from chipbench.gateway import send_all

    n = len(pods)
    params = traffic.request_params(spec, len(schedule.prefixes), 7)
    for k, round_ in enumerate(traffic.fill_plan(schedule, spec, seed)):
        reqs = [traffic.Request(index=g, due_s=None, group=g, prefix_len=0,
                                prompt=p, max_tokens=1, params=params[g])
                for g, p in round_]
        records = client.run(
            send_all(gateway, reqs, pods=[g % n for g, _ in round_])
        )
        must_succeed(records, f"fill round {k}")
    for g, prefix in enumerate(schedule.prefixes):
        wait_visible(scorer_url, prefix, model, pods[g % n].name,
                     len(prefix) // page)
    say(f"fill: {len(schedule.prefixes)} prefixes resident and visible")


def warm_up(client, gateway, schedule, buckets, seed, pods, lanes) -> dict:
    """Exactly the cell's shape set, on every pod, then a burst."""
    from chipbench.gateway import send_all

    n = len(pods)
    plan = []
    for i in range(n):
        mine = [r for r in schedule.requests
                if r.group is None or r.group % n == i]
        sub = dataclasses.replace(schedule, requests=mine)
        plan.append(traffic.warmup_plan(sub, buckets, seed + i, 2 * lanes))

    took = []

    async def one_pod(i):
        out = []
        for r in plan[i][0]:  # one at a time: each its own dispatch shape
            t = time.perf_counter()
            out += await send_all(gateway, [r], pods=[i])
            took.append((r.prompt_len, r.max_tokens,
                         round(time.perf_counter() - t, 2)))
        out += await send_all(gateway, plan[i][1], pods=[i] * len(plan[i][1]))
        return out

    async def every_pod():
        return await asyncio.gather(*[one_pod(i) for i in range(n)])

    for i, records in enumerate(client.run(every_pod())):
        must_succeed(records, f"warm-up pod {i}")
    say(f"warm-up singles (prompt, tokens, seconds): {took}")
    prefill, decode = traffic.shape_set(schedule.requests, buckets)
    return {"prefill_shapes": sorted(prefill), "decode_widths": sorted(decode),
            "warmup_requests": sum(len(s) + len(m) for s, m in plan)}


# -- the run ------------------------------------------------------------------
def run(args) -> dict:
    rehearse = args.rehearse
    bench = load_benchmark(args.benchmark)
    cell = find_cell(bench, args.workload)
    config = load_config(cell["config"], rehearse)
    spec = traffic.load_traffic(cell["traffic"], rehearse)
    chips = int(cell["chips"])
    seed = int(args.seed) % (2**31 - 1)

    from chipbench import fleet as fl
    from chipbench import gateway as gw
    from chipbench import reference

    import jax

    from llm_d_kv_cache_manager_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    devices, peaks = check_device(chips, rehearse)
    cache_dir = None
    if not rehearse:  # the rehearsal leaves no cache behind
        cache_dir = enable_compile_cache()
        # every program, however small, comes from the cache on a second run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter()
    fl.build_native()
    model_cfg = model_config(config, rehearse)
    page = int(config["env"]["BLOCK_SIZE"])
    lanes = int(config["env"]["DECODE_BATCH_SIZE"])
    pool_tokens = int(config["env"]["TOTAL_PAGES"]) * page

    fleet, client = fl.Fleet(), gw.ClientLoop()
    svc, pods, gateway = None, [], None
    profiling = threading.Event()
    try:
        zmq_port = fl.free_port()
        svc, scorer_url = fl.start_scorer(fleet, zmq_port, page)
        t0 = time.perf_counter()
        for i, dev in enumerate(devices):
            pods.append(fl.make_pod(i, config, model_cfg, zmq_port, dev,
                                    seed, fleet, rehearse))
        say(f"{len(pods)} pod(s) built in {time.perf_counter() - t0:.1f} s "
            f"(compile cache {cache_dir})")
        time.sleep(0.5)  # PUB sockets finish connecting to the SUB
        engine = pods[0].engine
        if not rehearse and (engine.config.interpret
                             or engine.prefill_attn != "pallas"):
            raise BenchFailure("the engine is not on its compiled kernels")

        t0 = time.perf_counter()
        ref = reference.check(
            engine, config["reference"], seed, interpret=rehearse,
            **({"prompt_tokens": 16, "steps": 4} if rehearse else {}),
        )
        say(f"reference check: {json.dumps(ref)} "
            f"({time.perf_counter() - t0:.1f} s)")

        ecfg = engine.config
        buckets = traffic.Buckets(
            page=page, prefill_bucket=ecfg.prefill_bucket,
            prefill_ctx_bucket=ecfg.prefill_ctx_bucket,
            decode_pages_bucket=ecfg.decode_pages_bucket,
            max_pages=engine.max_pages_per_seq,
        )
        schedule = traffic.build_schedule(
            spec, seed, args.seconds, pods=len(pods),
            pool_tokens_per_pod=pool_tokens, lanes=lanes, rate_rps=args.rate,
        )
        say(f"traffic {cell['traffic']}: kind={schedule.kind} "
            f"rate={schedule.rate_rps} callers={schedule.callers} "
            f"requests={len(schedule.requests)} groups={len(schedule.prefixes)}")
        gateway = gw.Gateway(scorer_url, pods, config["model_name"],
                             capacity_blocks=pool_tokens // page)
        client.run(gateway.open())
        t0 = time.perf_counter()
        fill_cache(client, gateway, schedule, spec, seed, pods, scorer_url,
                   config["model_name"], page)
        t_fill = time.perf_counter() - t0
        t0 = time.perf_counter()
        shapes = warm_up(client, gateway, schedule, buckets, seed, pods, lanes)
        say(f"fill {t_fill:.1f} s, warm-up {time.perf_counter() - t0:.1f} s: "
            f"{json.dumps(shapes)}; {compiles.count} compilations in set-up")

        # -- the window ------------------------------------------------------
        traced = bool(args.trace) and not rehearse
        for p in pods:
            p.engine.obs_step_timing = bool(args.trace)  # instrumentation
        stats_before = [fl.http("GET", f"{p.url}/stats")[1] for p in pods]
        step_before = [dict(p.engine.step_stats) for p in pods]
        samples = []

        def tick():
            samples.append([len(p.engine.scheduler.running) for p in pods])

        trace_dir = os.path.join(HERE, "out", "trace")
        tracer = None
        trace_window = [0.0]
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)

            def trace_some():
                # the last seconds of the window; stop_trace, which stalls
                # the process while it writes, comes after the close
                covered = min(TRACE_SECONDS, args.seconds / 2)
                time.sleep(max(0.0, args.seconds - covered - TRACE_MARGIN_S))
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # it slows the host severalfold
                options.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                profiling.set()
                trace_window[0] = time.perf_counter()

            tracer = threading.Thread(target=trace_some, name="chipbench-trace")
        compiles_before = compiles.count
        setup_s = time.perf_counter() - T_PROCESS
        if tracer:
            tracer.start()
        result = client.run(gw.run_window(
            gateway, schedule, args.seconds, on_tick=tick,
            on_close=lambda: at_the_close(pods),
        ))
        compiles_in_window = compiles.count - compiles_before
        if tracer:
            tracer.join()
            trace_window[0] = time.perf_counter() - trace_window[0]
            jax.profiler.stop_trace()
            profiling.clear()
        # an HTTP round trip a pod at the close would hold the aborts back
        stats_after = [fl.http("GET", f"{p.url}/stats")[1] for p in pods]
        peak = memory_peak(devices)

        good, failed, _ = metrics.split(result["records"])
        in_flight = result["in_flight"]
        window_s = result["window_s"]
        in_flight_tokens, step_after = result["at_close"]
        for r in failed[:5]:
            say(f"FAILED request {r['index']}: {metrics.response_fault(r)}")
        say(f"window {window_s:.3f} s: {len(good)} completed, {len(failed)} "
            f"failed, {len(in_flight)} in flight at the close (aborted, "
            f"counted neither way; {in_flight_tokens} tokens generated by "
            f"then); {compiles_in_window} compilations inside")
        e2e = metrics.end_to_end(good, in_flight_tokens, window_s)
        e2e["setup_s"] = setup_s
        if result["late_s"]:
            late95 = metrics.percentile(result["late_s"], 95) * 1e3
            say(f"generator lateness p95 {late95:.3f} ms over "
                f"{len(result['late_s'])} sends")
            if late95 > 0.1 * e2e.get("ttft_ms_p50", float("inf")):
                say("WARNING: generator lateness is above a tenth of "
                    "ttft_ms_p50: the generator, not the system, is slow")
        if not metrics.tail_supported(len(good), 95):
            say(f"NOTE: {len(good)} completions: fewer than ten samples lie "
                "beyond the 95th percentile")
        say("end to end: " + json.dumps(e2e))

        reduced = None
        if traced:
            from chipbench import trace_reduce

            reduced = trace_reduce.reduce(trace_reduce.load(trace_dir))
            say(f"trace: {reduced['window_s']:.3f} s from its first event to "
                f"its last ({trace_window[0]:.3f} s on the host's clock "
                f"between start_trace and stop_trace), device busy "
                f"{reduced['busy_s']:.3f} s")
        records = RunRecords(
            cell=cell, good=good, failed=failed, in_flight=in_flight,
            in_flight_tokens=in_flight_tokens, late_s=result["late_s"], window_s=window_s,
            stats_before=stats_before, stats_after=stats_after,
            running_samples=samples, lanes=lanes, page=page, pods=pods,
            step_before=step_before, step_after=step_after,
            compiles_in_window=compiles_in_window, memory_peak_bytes=peak,
            model_cfg=model_cfg, peaks=peaks, trace=reduced,
        )
        out_metrics = {}
        if args.trace:
            for m in metrics_of_cell(bench["per_layer"], cell["name"]):
                if rehearse and m["source"] == "device_trace":
                    continue  # no device number off the chip
                value = load_layer_metric(m["name"])(records)
                if value is not None:
                    out_metrics[m["name"]] = {"value": float(value),
                                              "unit": m["unit"]}
        else:
            for m in metrics_of_cell(bench["end_to_end"], cell["name"]):
                if m["name"] in e2e:
                    out_metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                              "unit": m["unit"]}
        device = {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak,
        }
        line = {
            "correct": bool(ref["ok"] and not failed and good),
            "attempted": len(good) + len(failed), "failed": len(failed),
            "metrics": out_metrics, "device": device,
        }
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
        line["in_flight_at_close"] = len(in_flight)
        line["reference"] = ref
        line["end_to_end_all"] = e2e
        line["shapes"] = shapes
        write_details(args, line, reduced, good)
        return line
    finally:
        if profiling.is_set():
            jax.profiler.stop_trace()
        if gateway is not None:
            client.run(gateway.close())
        client.close()
        fleet.close()
        for p in pods:
            p.server.shutdown()
        if svc is not None:
            svc.shutdown()


def write_details(args, line: dict, reduced, good) -> None:
    out = os.path.join(HERE, "out")
    os.makedirs(out, exist_ok=True)
    name = f"{args.workload}.seed{args.seed}.trace{args.trace}.json"
    detail = dict(line)
    detail["requests"] = [
        [r["index"], r["due"], r["sent"], r["done"], r["body"]["ttft_s"],
         r["body"]["usage"]["completion_tokens"],
         r["body"]["usage"]["cached_prompt_tokens"], r["pod"]]
        for r in good
    ]  # index, due, sent, done, pod's ttft_s, tokens out, cached, pod
    if reduced is not None:
        top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:60]
        detail["trace_top_ops"] = [
            [n, s, reduced["ops_text"].get(n, "")[:400]] for n, s in top
        ]
        detail["trace_modules"] = reduced["modules"]
        detail["trace_module_calls"] = reduced["module_calls"]
    with open(os.path.join(out, name), "w") as f:
        json.dump(detail, f, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal for tests: never a measurement")
    ap.add_argument("--rate", type=float, default=None,
                    help="override an open-loop mix's rate (knee sweep only)")
    ap.add_argument("--benchmark", default=None,
                    help="another BENCHMARK.json (tests)")
    args = ap.parse_args(argv)
    if args.rehearse and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
    line = run(args)
    # each number compared beside its limit (``tol``), last on standard error
    print("[chipbench] compared: " + json.dumps({
        "reference": line["reference"], "failed_requests": line["failed"],
        "failed_requests_limit": 0, "completed": line["attempted"] - line["failed"],
        "completed_at_least": 1}), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
