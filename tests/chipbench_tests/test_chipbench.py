"""The benchmark's own tests: CPU, no chip, no network, no topology compile,
no child process that loads JAX. The rehearsals at the end drive the whole
run in this process on virtual CPU devices; nothing here is a measurement.
"""

import dataclasses
import json
import math
import os
import re

import numpy as np
import pytest

from chipbench import costs, metrics, run, trace_reduce, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TRAFFIC = sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "chipbench", "traffic"))
)
CONFIGS = sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "chipbench", "configs"))
)
FULL = traffic.Buckets(page=16, prefill_bucket=64, prefill_ctx_bucket=256,
                       decode_pages_bucket=64, max_pages=256)


def schedule(name, seed=11, seconds=45.0, pods=None, **kw):
    spec = traffic.load_traffic(name)
    pods = pods or (4 if name.endswith("-x4") else 1)
    if spec.get("rate_rps", 0) is None:  # a mix whose knee is not swept yet
        with pytest.raises(ValueError, match="no rate yet"):
            traffic.build_schedule(spec, seed, seconds, pods=pods,
                                   pool_tokens_per_pod=8192 * 16, lanes=16)
        kw.setdefault("rate_rps", 14.0)
    return spec, traffic.build_schedule(
        spec, seed, seconds, pods=pods, pool_tokens_per_pod=8192 * 16,
        lanes=16, **kw)


# -- traffic ------------------------------------------------------------------
@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_requests(name):
    _, a = schedule(name)
    _, b = schedule(name)
    assert a == b


@pytest.mark.parametrize("name", TRAFFIC)
def test_another_seed_other_text_same_work_at_the_same_times(name):
    _, a = schedule(name, seed=1)
    _, b = schedule(name, seed=2**31 + 5)
    assert all(x.prompt != y.prompt for x, y in zip(a.requests, b.requests))
    assert a.prefixes != b.prefixes or not a.prefixes

    def work(s):
        return [(r.prompt_len, r.prefix_len, r.max_tokens, r.group, r.due_s)
                for r in s.requests]

    assert work(a) == work(b)


@pytest.mark.parametrize("name", TRAFFIC)
def test_lengths_are_clipped(name):
    spec, s = schedule(name)
    for r in s.requests:
        assert spec["unique"]["min"] <= r.prompt_len - r.prefix_len <= spec["unique"]["max"]
        assert spec["output"]["min"] <= r.max_tokens <= spec["output"]["max"]
        assert all(33 <= ord(c) < 127 for c in r.prompt[:64])


@pytest.mark.parametrize("n,s", [(3, 1.0), (30, 1.0), (126, 1.0), (10, 0.5)])
def test_zipf_weights(n, s):
    w = traffic.zipf_weights(n, s)
    assert w.sum() == pytest.approx(1.0)
    assert all(w[i] > w[i + 1] for i in range(n - 1))
    assert w[0] / w[1] == pytest.approx(2.0**s)


def test_zipf_shares_of_groups():
    _, s = schedule("sessions", seconds=600.0)
    counts = np.bincount([r.group for r in s.requests],
                         minlength=len(s.prefixes))
    want = traffic.zipf_weights(len(s.prefixes), 1.0)
    assert counts[0] / counts.sum() == pytest.approx(want[0], rel=0.15)
    assert counts[0] > counts[5] > counts[-1]


@pytest.mark.parametrize("name", [t for t in TRAFFIC if t.startswith("sessions")])
def test_sessions_requests_land_in_the_declared_shape_set(name):
    spec, s = schedule(name)
    prefill, decode = traffic.shape_set(s.requests, FULL)
    assert prefill <= {(64 * k, 256) for k in range(1, 9)}
    assert decode <= {128, 192, 256}
    # equal shares of the prefix lengths, half of the fleet's pool in all
    lens = [len(p) for p in s.prefixes]
    assert {lens.count(n) for n in spec["groups"]["prefix_tokens"]} == {len(lens) // 3}
    pods = 4 if name.endswith("-x4") else 1
    assert sum(lens) <= spec["groups"]["pool_share"] * pods * 8192 * 16


def test_reasoning_shares_nothing_and_is_closed():
    _, s = schedule("reasoning")
    assert s.kind == "closed" and s.callers == 32 and not s.prefixes
    assert all(r.due_s is None and r.prefix_len == 0 for r in s.requests)
    prefill, decode = traffic.shape_set(s.requests, FULL)
    assert prefill == {(64 * k, 0) for k in range(2, 9)}
    assert decode == {64, 128}  # at bucket 64; the MoE configuration pins 128


@pytest.mark.parametrize("rate,seconds", [(4.0, 200.0), (20.0, 100.0)])
def test_due_times_are_poisson_at_the_stated_rate(rate, seconds):
    _, s = schedule("sessions", seconds=seconds, rate_rps=rate)
    due = np.array([r.due_s for r in s.requests])
    assert (np.diff(due) > 0).all() and 0 < due[0] and due[-1] < seconds
    n = rate * seconds
    assert abs(len(due) - n) < 4 * math.sqrt(n)
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.1)
    assert gaps.std() == pytest.approx(1 / rate, rel=0.15)  # exponential


@pytest.mark.parametrize("dist,lo,hi", [
    ({"dist": "uniform", "min": 128, "max": 512}, 128, 512),
    ({"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 32, "max": 512}, 32, 512),
    ({"dist": "lognormal", "median": 320, "sigma": 0.6, "min": 128, "max": 1024}, 128, 1024),
])
def test_draw_lengths(dist, lo, hi):
    x = traffic.draw_lengths(np.random.default_rng(0), dist, 4000)
    assert x.min() == lo and x.max() == hi
    if dist["dist"] == "lognormal":
        assert np.median(x) == pytest.approx(dist["median"], rel=0.1)


def test_unknown_distribution_and_kind_are_errors(tmp_path):
    with pytest.raises(ValueError):
        traffic.draw_lengths(np.random.default_rng(0),
                             {"dist": "pareto", "min": 1, "max": 2}, 3)
    with pytest.raises(FileNotFoundError):
        traffic.load_traffic("no-such-mix")


@pytest.mark.parametrize("prompt,cached,want", [
    (1056, 1024, (64, 256)), (3584, 3072, (512, 256)), (1040, 0, (1088, 0)),
    (2064, 1024, (1088, 256)), (128, 0, (128, 0)), (129, 0, (192, 0)),
])
def test_prefill_shape(prompt, cached, want):
    assert FULL.prefill_shape(prompt, cached) == want


@pytest.mark.parametrize("tokens,want", [
    (1, 64), (1024, 64), (1025, 128), (2048, 128), (2049, 192), (3712, 256),
    (4096, 256), (9999, 256),
])
def test_decode_width(tokens, want):
    assert FULL.decode_width(tokens) == want
    assert FULL.decode_width(FULL.first_tokens_of_width(want)) == want


@pytest.mark.parametrize("name", TRAFFIC)
def test_warmup_plan_covers_the_shape_set_with_fresh_text(name):
    _, s = schedule(name)
    singles, burst = traffic.warmup_plan(s, FULL, 11, 32)
    prefill, decode = traffic.shape_set(s.requests, FULL)
    assert {FULL.prefill_shape(r.prompt_len, r.prefix_len) for r in singles} == prefill
    reached = set()
    for r in singles:
        reached.update(range(FULL.decode_width(r.prompt_len + 1),
                             FULL.decode_width(r.prompt_len + r.max_tokens) + 1, 64))
    assert decode <= reached
    window = {r.prompt for r in s.requests}
    assert not window & {r.prompt for r in singles + burst}
    assert len(burst) == 32 and all(r.max_tokens <= 8 for r in burst)


def test_fill_plan_has_one_chunk_shape():
    spec, s = schedule("sessions")
    rounds = traffic.fill_plan(s, spec, 11)
    assert len(rounds) == 3
    for k, round_ in enumerate(rounds, 1):
        assert {len(p) for _, p in round_} == {1024 * k + 16}
        assert {FULL.prefill_shape(len(p), 1024 * (k - 1))[0] for _, p in round_} == {1088}
        for g, p in round_:
            assert p.startswith(s.prefixes[g][: 1024 * k])
    assert [g for g, _ in rounds[0]] == list(range(len(s.prefixes)))
    assert traffic.fill_plan(schedule("reasoning")[1], {}, 1) == []


# -- metric arithmetic ---------------------------------------------------------
@pytest.mark.parametrize("values,q,want", [
    ([5], 95, 5), ([1, 2, 3, 4], 50, 2.5), ([1, 2, 3, 4, 5], 50, 3),
    (list(range(101)), 95, 95), ([10, 20], 95, 19.5), ([3, 1, 2], 0, 1),
])
def test_percentile(values, q, want):
    assert metrics.percentile(values, q) == pytest.approx(want)
    assert metrics.percentile(values, q) == pytest.approx(np.percentile(values, q))


@pytest.mark.parametrize("n,q,want", [
    (200, 95, True), (199, 95, False), (20, 50, True), (19, 50, False),
    (1000, 99, True), (999, 99, False),
])
def test_ten_beyond_guard(n, q, want):
    assert metrics.tail_supported(n, q) is want


def record(**kw):
    rec = {
        "index": 0, "due": 1.0, "start": 1.001, "scored": 1.003, "sent": 1.004,
        "done": 1.504, "status": 200, "pod": 0, "prompt_len": 100,
        "prefix_len": 64, "group": 0, "max_tokens": 11, "error": None,
        "body": {"choices": [{"token_ids": list(range(11)),
                              "finish_reason": "length"}],
                 "usage": {"prompt_tokens": 100, "completion_tokens": 11,
                           "cached_prompt_tokens": 64},
                 "ttft_s": 0.1},
    }
    rec.update(kw)
    return rec


def body(**kw):
    b = json.loads(json.dumps(record()["body"]))
    for k, v in kw.items():
        if k in b["usage"]:
            b["usage"][k] = v
        elif k in b["choices"][0]:
            b["choices"][0][k] = v
        else:
            b[k] = v
    return b


def test_a_good_response_has_no_fault_and_its_times_add_up():
    r = record()
    assert metrics.response_fault(r) is None
    assert metrics.ttft_s(r) == pytest.approx(0.004 + 0.1)
    assert metrics.itl_s(r) == pytest.approx((0.504 - 0.104) / 10)


@pytest.mark.parametrize("change", [
    {"status": 429, "error": "overloaded"},
    {"status": None, "error": "TimeoutError"},
    {"body": body(token_ids=[1, 2])},
    {"body": body(finish_reason="stop")},
    {"body": body(prompt_tokens=99)},
    {"body": body(cached_prompt_tokens=101)},
    {"body": body(cached_prompt_tokens=-1)},
    {"body": body(ttft_s=None)},
    {"body": body(ttft_s=0.6)},  # above the client's whole-request time
    {"body": {"error": "boom"}},
], ids=lambda c: str(c)[:40])
def test_failures_are_counted_as_misses(change):
    bad = record(**change)
    assert metrics.response_fault(bad) is not None
    good, failed, in_flight = metrics.split([record(), bad, record(done=None)])
    assert (len(good), len(failed), len(in_flight)) == (1, 1, 1)


def test_itl_with_one_output_token_is_left_out():
    one = record(max_tokens=1, body=body(token_ids=[7], completion_tokens=1))
    assert metrics.response_fault(one) is None and metrics.itl_s(one) is None
    e2e = metrics.end_to_end([one], 0, 2.0)
    assert "itl_ms_p50" not in e2e
    assert e2e["out_tokens_per_s"] == pytest.approx(1 / 2.0)


def test_end_to_end_over_all_completed_requests():
    recs = [record(index=i, sent=1.004 + i * 0.01) for i in range(5)]
    # every token of the window over the whole window: the completed
    # requests' and what those in flight at the close had generated
    e2e = metrics.end_to_end(recs, 45, 4.0)
    assert e2e["out_tokens_per_s"] == (55 + 45) / 4.0
    assert e2e["ttft_ms_p50"] == pytest.approx(124.0)
    assert e2e["ttft_ms_p95"] == pytest.approx(104 + 0.95 * 40)
    assert metrics.end_to_end([], 0, 4.0) == {"out_tokens_per_s": 0.0}


# -- trace reduction -----------------------------------------------------------
@pytest.mark.parametrize("intervals,want", [
    ([], []), ([(0, 1)], [(0, 1)]), ([(0, 2), (1, 3)], [(0, 3)]),
    ([(5, 6), (0, 1), (1, 2)], [(0, 2), (5, 6)]),
    ([(0, 10), (2, 3)], [(0, 10)]),
])
def test_union(intervals, want):
    assert trace_reduce.union(intervals) == want


def ev(name, start_us, dur_us, text=None):
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3,
            "text": text or name}


def made_up_trace():
    ops0 = [ev("fusion.1", 0, 100), ev("custom-call.7", 50, 100,
                                       "custom-call.7 long_name=paged_attention_kernel"),
            ev("fusion.1", 400, 100), ev("custom-call.9", 900, 100,
                                         "custom-call.9 tf_op=jit(prefill)/flash_prefill")]
    ops1 = [ev("fusion.1", 0, 500)]
    mods0 = [ev("jit_decode_steps(123)", 0, 150), ev("jit_decode_steps(123)", 400, 100),
             ev("jit_prefill(9)", 900, 100)]
    host = [ev("engine.schedule", 160, 200), ev("sample_fetch", 520, 370)]
    return [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops0},
                                            {"name": "XLA Modules", "events": mods0}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": ops1}]},
        {"name": "/host:CPU", "lines": [{"name": "pod-0-loop", "events": host}]},
        {"name": "/device:CUSTOM:x", "lines": []},
    ]


def test_busy_union_idle_share_gaps_and_names():
    r = trace_reduce.reduce(made_up_trace(), top=3)
    # the window is the trace's own: first event (0) to the last's end (1000 us)
    assert r["chips"] == 2 and r["window_s"] == pytest.approx(1e-3)
    # chip 0: [0,150] + [400,500] + [900,1000] = 350 us; chip 1: 500 us
    assert r["busy_s"] == pytest.approx(425e-6)
    assert trace_reduce.idle_share(r) == pytest.approx(1 - 0.425)
    assert r["ops"]["fusion.1"] == pytest.approx((200 + 500) / 2 * 1e-6)
    assert r["device_ops"][0][0] == "fusion"
    assert r["idle_gaps"] == [
        ["pod-0-loop:sample_fetch", pytest.approx(400e-6)],
        ["pod-0-loop:engine.schedule", pytest.approx(250e-6)],
    ]
    assert trace_reduce.time_matching(r, "paged_attention") == pytest.approx(50e-6)
    assert trace_reduce.time_matching(r, "flash_prefill") == pytest.approx(50e-6)
    assert trace_reduce.time_matching(r, "gmm") == 0.0
    assert trace_reduce.module_mean_s(r, "decode_steps") == pytest.approx(125e-6)
    assert trace_reduce.module_mean_s(r, "no_such_module") is None


def test_a_trace_without_device_planes_reduces_to_nothing():
    r = trace_reduce.reduce(made_up_trace()[2:])
    assert r["chips"] == 0 and r["busy_s"] == 0.0 and r["device_ops"] == []


def test_recorded_profile_round_trip(tmp_path):
    """A real ``.xplane.pb`` (recorded here on the CPU) reads into the plain
    form through ``jax.profiler.ProfileData``."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes = trace_reduce.load(str(tmp_path))
    assert any(p["name"].startswith("/host:") for p in planes)
    some = [e for p in planes for line in p["lines"] for e in line["events"]]
    assert some and all(e["dur_ns"] >= 0 and e["name"] in e["text"] for e in some)
    reduced = trace_reduce.reduce(planes)
    assert reduced["chips"] == 0 and 0 < reduced["window_s"] < 60  # no TPU here
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path / "empty"))


# -- costs and peaks -----------------------------------------------------------
def model(name):
    return run.model_config(run.load_config(name), rehearse=False)


HAND = {
    # attention, FFN resident, FFN touched per token, head — parameters
    "qwen3-32b": dict(
        attn=5120 * 8192 + 2 * 5120 * 1024 + 8192 * 5120,
        ffn=3 * 5120 * 25600, touched=3 * 5120 * 25600, head=151936 * 5120,
        layers=5, kv_token=2 * 5 * 8 * 128 * 2),
    "qwen3-30b-a3b": dict(
        attn=2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048,
        ffn=128 * 3 * 2048 * 768 + 2048 * 128,
        touched=8 * 3 * 2048 * 768 + 2048 * 128, head=151936 * 2048,
        layers=8, kv_token=2 * 8 * 4 * 128 * 2),
}


#: the configurations ``costs.py`` cannot count (it knows one FFN kind and
#: ``2 x layers x n_kv x hd`` bytes a token): each has a cost file of its
#: own, held to hand sums by the test named here
HELD_BY_A_CELL_TEST = {
    "sdar-30b-a3b": ("test_block_diffusion_cell.py",
                     "test_costs_of_the_new_configuration_against_hand_sums"),
    "kanana-2-30b-a3b": ("test_latent_cell.py",
                         "test_cost_functions_against_hand_sums"),
    "lfm2-8b-a1b": ("test_hybrid_cell.py",
                    "test_cost_functions_against_hand_sums"),
    "longcat-flash-omni": ("test_scmoe_cell.py",
                           "test_cost_functions_against_hand_sums"),
    "trinity-large-preview": ("test_swa_cell.py",
                              "test_cost_functions_against_hand_sums"),
    "ling-3.0-flash": ("test_kda_cell.py",
                       "test_cost_functions_against_hand_sums"),
    "smallthinker-21b-a3b": ("test_prerouted_cell.py",
                             "test_cost_functions_against_hand_sums"),
}


@pytest.mark.parametrize("name", sorted(c["name"] for c in BENCH["configs"]))
def test_every_configuration_is_held_to_hand_sums(name):
    """By ``HAND`` here or by the cell test named for it, which exists, has
    that test and loads this configuration: the next configuration cannot
    slip between the two."""
    assert (name in HAND) != (name in HELD_BY_A_CELL_TEST)
    assert name in CONFIGS
    if name in HAND:
        return
    file, test = HELD_BY_A_CELL_TEST[name]
    with open(os.path.join(os.path.dirname(__file__), file)) as f:
        source = f.read()
    assert f"\ndef {test}(" in source and f'"{name}"' in source


@pytest.mark.parametrize("name", sorted(HAND))
def test_costs_against_hand_sums(name):
    cfg, h = model(name), HAND[name]
    assert costs.attn_params_per_layer(cfg) == h["attn"]
    assert costs.ffn_params_per_layer(cfg) == h["ffn"]
    assert costs.ffn_params_touched_per_token(cfg) == h["touched"]
    assert costs.kv_bytes_per_token(cfg) == h["kv_token"]
    weights = 2 * (2 * h["head"] + h["layers"] * (h["attn"] + h["ffn"]))
    assert costs.resident_weight_bytes(cfg) == weights
    step = costs.decode_step_min_bytes(cfg, 16, 1000.0)
    assert step == pytest.approx(
        weights - 2 * h["head"] + 2 * 16 * cfg.hidden_size
        + 16 * 1000 * h["kv_token"])
    assert costs.flops_per_token(cfg, 0.0, with_head=False) == (
        2 * h["layers"] * (h["attn"] + h["touched"]))
    assert costs.flops_per_token(cfg, 100.0) - costs.flops_per_token(cfg, 0.0) == (
        pytest.approx(h["layers"] * 4 * cfg.n_heads * 128 * 100))


def test_moe_decode_bytes_follow_the_experts_touched():
    cfg = model("qwen3-30b-a3b")
    full = costs.decode_step_min_bytes(cfg, 16, 0.0)
    one = costs.decode_step_min_bytes(cfg, 16, 0.0, experts_touched=8)
    assert full - one == 2 * 8 * (128 - 8) * 3 * 2048 * 768


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "", "_source"])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        costs.load_peaks(kind)


def test_v5e_peaks():
    p = costs.load_peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
        197e12, 819e9, 16e9)
    assert p["int8_ops_per_s"] == 393e12


# -- reference against the program at the tiny presets ---------------------------
@pytest.mark.parametrize("preset,replace,block", [
    ("TINY_LLAMA", {"qk_norm": True}, "dense"),
    ("TINY_LLAMA", {}, "dense"),
    ("TINY_QWEN3_MOE", {}, "moe"),
])
def test_reference_against_prefill_and_decode_step(preset, replace, block):
    from chipbench import reference
    from llm_d_kv_cache_manager_tpu import models
    from llm_d_kv_cache_manager_tpu.server import (
        BlockManagerConfig, Engine, EngineConfig)

    cfg = dataclasses.replace(getattr(models, preset), **replace)
    eng = Engine(EngineConfig(
        model=cfg, block_manager=BlockManagerConfig(total_pages=64, page_size=4),
        max_model_len=128, decode_batch_size=2, interpret=True))
    out = reference.check(eng, block, 3, interpret=True, prompt_tokens=24, steps=4)
    assert out["ok"] and out["rel_err"] < reference.TOL_F32 == out["tol"]["max"]
    if block == "moe":  # each layer alone; told apart by whether the routing is decided
        assert max(out["layer_rel_err"], out["layer_rel_err_p75"]) < reference.TOL_F32
        assert out["layer_positions"] + out["layer_tied_positions"] == 5 * cfg.n_layers
        assert out["layer_positions"] >= out["layer_tied_positions"]
    # the check can fail: a reference fed other tokens disagrees
    got, fed = reference.system_logits(eng, list(range(40, 64)), 2, True)
    other = np.asarray(reference.forward(eng.params, cfg, list(range(41, 65)) + fed, block))
    assert np.abs(got - other[23:]).max() / np.abs(other).max() > 100 * reference.TOL_F32
    with pytest.raises(ValueError):
        reference.forward(eng.params, cfg, [1, 2], "moe" if block == "dense" else "dense")


@pytest.mark.parametrize("variant,ok", [
    ([], True), (["--moe-gmm", "xla"], True),
    (["--pool", "int8"], False), (["--weights", "int8"], False),
    (["--order", "1,0"], False),
], ids=lambda v: "-".join(v).strip("-") or "as-served" if isinstance(v, list) else None)
def test_the_check_fails_under_lower_precision(variant, ok, capsys):
    """``probe_reference.py``, rehearsed: at the tiny f32 preset the check holds
    for the program as served, kernel or ``ragged_dot``, and fails with an int8
    pool, int8 weights and experts, or the layers in another order."""
    from chipbench import probe_reference

    argv = ["--config", "qwen3-30b-a3b", "--seeds", "5", "--rehearse"] + variant
    assert probe_reference.main(argv) == 0
    line = json.loads([l for l in capsys.readouterr().out.splitlines()
                       if l.startswith("{")][-1])
    assert line["ok"] is ok and (line["pool"], line["weights"]) == (
        "int8" if "--pool" in variant else None,
        "int8" if "--weights" in variant else None)
    assert (line["layer_rel_err_p75"] > line["tol"]["layer_p75"]) is (not ok)


# -- BENCHMARK.json and the files it names --------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ALL_METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
ALL_NAMES = (
    [m["name"] for m in ALL_METRICS] + [c["name"] for c in BENCH["configs"]]
    + [w["name"] for w in BENCH["workloads"]]
    + [w["traffic"] for w in BENCH["workloads"]]
    + [k for c in BENCH["configs"] for k in c["reduced"]]
)


@pytest.mark.parametrize("name", sorted(set(ALL_NAMES)))
def test_name_character_rules(name):
    assert NAME.match(name)


@pytest.mark.parametrize("m", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entries(m):
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if "bound" in m:  # end to end
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader(m):
    """By its own file, or by the file of the part before the first dot."""
    assert callable(run.load_layer_metric(m["name"]))
    with pytest.raises(run.BenchFailure, match="no reader"):
        run.load_layer_metric("no_such_metric." + m["name"])


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len(json.dumps(BENCH)) < 64 * 1024
    names = [m["name"] for m in ALL_METRICS]
    assert len(names) == len(set(names)) and "setup_s" in names
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "traffic", w["traffic"] + ".json"))
        e2e = run.metrics_of_cell(BENCH["end_to_end"], w["name"])
        assert len(e2e) >= 2 and any(m["name"] == "setup_s" for m in e2e)
        assert run.metrics_of_cell(BENCH["per_layer"], w["name"])
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/") and len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_file_states_the_published_sizes(name):
    config = run.load_config(name)
    pub = config["published"]
    cfg = run.model_config(config, rehearse=False)  # raises on any disagreement
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert pub["source"] == entry["source"] and list(pub["reduced"]) == entry["reduced"]
    assert pub["num_hidden_layers"] == cfg.n_layers == config["replace"]["n_layers"]
    assert cfg.dtype.__name__ == pub["torch_dtype"] == "bfloat16"
    # a width that moved is refused
    moved = {**config, "published": {**pub, "hidden_size": pub["hidden_size"] // 2}}
    with pytest.raises(run.BenchFailure):
        run.model_config(moved, rehearse=False)
    assert "n_layers" not in config["rehearse"].get("replace", {})


# -- per-layer readers on hand-made records --------------------------------------
def records(**kw):
    good = [record(index=i, scored=1.003 + i * 1e-3) for i in range(4)]
    base = dict(
        cell=BENCH["workloads"][0], good=good, failed=[], in_flight=[record(done=None)],
        in_flight_tokens=7, late_s=[0.001, 0.002, 0.003], window_s=10.0,
        stats_before=[{"prefill": {"dispatches": 10, "tokens_computed": 0}}],
        stats_after=[{"prefill": {"dispatches": 12, "tokens_computed": 90}}],
        running_samples=[[4], [8], [12]], lanes=16, page=16, pods=[object()],
        step_before=[{"steps": 0, "schedule_s": 0, "prefill_s": 0, "decode_s": 0,
                      "publish_s": 0, "sample_s": 0}],
        step_after=[{"steps": 100, "schedule_s": 0.1, "prefill_s": 0.4,
                     "decode_s": 1.4, "publish_s": 0.1, "sample_s": 1.0}],
        compiles_in_window=0, memory_peak_bytes=12 * 2**30,
        model_cfg=model("qwen3-30b-a3b"), peaks=costs.load_peaks("TPU v5 lite"),
        trace=None,
    )
    base.update(kw)
    return run.RunRecords(**base)


@pytest.mark.parametrize("name,want", [
    ("score_ms_p50", 3.5), ("prefix_hit_share", 64.0),
    ("prefix_hit_share.bypass", 64.0), ("warm_route_share", 100.0),
    ("pod_ttft_ms_p50", 100.0), ("lanes_busy_mean", 50.0),
    ("prefill_rows_mean", 2.5), ("step_ms_mean", 20.0),
    ("compiles_in_window.serve", 0), ("compiles_in_window.decode", 0),
    ("peak_hbm_gib", 12.0), ("loadgen_late_ms_p95", 2.9), ("ttft_ms_p95", None),
    ("device_idle_share", None), ("kernel_time_share.gmm", None),
    ("counted_decode_step_roofline", None),
])
def test_reader_on_hand_made_records(name, want):
    got = run.load_layer_metric(name)(records())
    assert got == (pytest.approx(want) if want is not None else None)


def test_trace_readers_on_the_made_up_trace():
    # 200 dispatches of one forward, 8 real lanes at 105.5 tokens, 40 experts
    # a routed layer a forward: what the program counted in the window
    counted = {"decode_dispatches": 200, "decode_forwards": 200,
               "decode_rows": 200 * 8, "attn_ctx_tokens": 200 * 8 * 105.5,
               "experts_touched": 200 * 8 * 40}
    r = records(trace=trace_reduce.reduce(made_up_trace()),
                step_before=[dict.fromkeys(counted, 0)], step_after=[counted],
                stats_after=[{"routed_layers": 8}])
    read = lambda n: run.load_layer_metric(n)(r)  # noqa: E731
    assert read("device_idle_share") == pytest.approx(57.5)
    assert read("kernel_time_share.paged_attention") == pytest.approx(100 * 50 / 425)
    assert read("kernel_time_share.flash_prefill") == pytest.approx(100 * 50 / 425)
    cfg = r.model_cfg
    touched = costs.expected_experts_touched(cfg, 8.0)
    assert touched == pytest.approx(128 * (1 - (15 / 16) ** 8))
    assert costs.expected_experts_touched(cfg, 16) == pytest.approx(82.4, abs=0.1)
    # the expectation is what the program does NOT do: the share is fed the
    # counted experts, and the same hand sum with them
    assert touched > 40
    least = costs.decode_step_min_bytes(cfg, 8.0, 105.5, experts_touched=40.0) / 819e9
    assert read("counted_decode_step_roofline") == pytest.approx(100 * least / 125e-6)
    # a reader that finds nothing to read returns nothing
    empty = records(good=[], running_samples=[], late_s=[],
                    stats_after=[{"prefill": {"dispatches": 10}}])
    for name in ("score_ms_p50", "prefix_hit_share", "lanes_busy_mean",
                 "prefill_rows_mean", "loadgen_late_ms_p95", "pod_ttft_ms_p50"):
        assert run.load_layer_metric(name)(empty) is None


@pytest.mark.parametrize("name,config,routed", [
    ("counted_decode_step_roofline", "qwen3-30b-a3b", 8),
    ("hybrid_decode_step_roofline", "lfm2-8b-a1b", 12),
])
def test_a_step_roofline_is_fed_the_programs_counts_or_not_reported(
        name, config, routed, monkeypatch):
    """A share of a step's roofline divides by a traced time, so its bytes
    are what the program counted: never the expectation over a router's
    draws, and nothing where a count is missing."""
    def never(*a, **kw):
        raise AssertionError("an expectation where a count belongs")

    monkeypatch.setattr(costs, "expected_experts_touched", never)
    counted = {"decode_dispatches": 200, "decode_forwards": 200,
               "decode_rows": 200 * 8, "attn_ctx_tokens": 200 * 8 * 105.5,
               "experts_touched": 200 * routed * 20}
    zero = dict.fromkeys(counted, 0)
    read = run.load_layer_metric(name)

    def share(after=counted, stats=None, **kw):
        kw.setdefault("trace", trace_reduce.reduce(made_up_trace()))
        return read(records(
            model_cfg=model(config), step_before=[zero], step_after=[after],
            stats_after=[{"routed_layers": routed} if stats is None else stats],
            **kw))

    assert share() > 0
    for missing in counted:  # a program from before that counter
        assert share({k: v for k, v in counted.items() if k != missing}) is None
    assert share(stats={}) is None  # /stats without ``routed_layers``
    assert share({**counted, "experts_touched": 0}) is None  # counted nothing
    assert share(zero) is None  # no dispatch in the window
    assert share(trace=None) is None


def test_step_after_is_what_the_close_saw():
    """``run.at_the_close`` copies the engines' counters at the instant the
    window closes: what the aborts and the emptying tail add afterwards (a
    stub engine that goes on counting until its request is cancelled, and
    counts the cancellation too) is not the window's."""
    import asyncio

    from chipbench import gateway

    stats = {"decode_dispatches": 5, "decode_rows": 80}
    queue = [type("Seq", (), {"num_generated": 3})() for _ in range(2)]
    sched = type("S", (), {"running": queue, "prefilling": [], "waiting": []})
    pod = type("P", (), {"engine": type("E", (), {
        "step_stats": stats, "scheduler": sched})})
    at_every_count = []

    class Stub:
        async def complete(self, req, due, clock, pod=None):
            try:
                while True:
                    await asyncio.sleep(0.005)
                    stats["decode_dispatches"] += 1
                    stats["decode_rows"] += 16
                    at_every_count.append(clock())
            finally:  # the tail: dispatches of fewer lanes after the close
                stats["decode_dispatches"] += 10
                stats["decode_rows"] += 10

    reqs = [traffic.Request(index=i, due_s=None, group=None, prefix_len=0,
                            prompt="x", max_tokens=1) for i in range(2)]
    sched_ = traffic.Schedule(kind="closed_loop", rate_rps=None, callers=1,
                              prefixes=[], requests=reqs)
    result = asyncio.run(gateway.run_window(
        Stub(), sched_, 0.1, on_close=lambda: run.at_the_close([pod])))
    tokens, step_after = result["at_close"]
    assert tokens == 6 and len(result["in_flight"]) == 1
    inside = sum(t <= result["window_s"] for t in at_every_count)
    assert inside >= 5
    assert step_after == [{"decode_dispatches": 5 + inside,
                           "decode_rows": 80 + 16 * inside}]
    assert step_after[0] is not stats
    assert stats["decode_dispatches"] >= step_after[0]["decode_dispatches"] + 10


def test_the_tail_is_reported_only_with_ten_samples_beyond_it():
    good = [record(index=i, sent=1.004 + (i % 50) * 1e-3) for i in range(200)]
    reader = run.load_layer_metric("ttft_ms_p95")
    assert reader(records(good=good)) == pytest.approx(104 + 0.95 * 49, abs=0.5)
    assert reader(records(good=good[:199])) is None


def test_pool_cached_share_reads_the_fullest_replica():
    class BM:
        def __init__(self, cached):
            self.num_cached_pages = cached
            self.config = type("C", (), {"total_pages": 200})

    pods = [type("P", (), {"engine": type("E", (), {"block_manager": BM(c)})})
            for c in (50, 150)]
    assert run.load_layer_metric("pool_cached_share")(records(pods=pods)) == 75.0


def test_native_libraries_are_built_before_they_are_first_asked_for(monkeypatch):
    """A fresh checkout has no ``*.so``; asking ``available()`` first would
    remember the miss for the whole process (the first proof run failed so)."""
    from chipbench import fleet
    from llm_d_kv_cache_manager_tpu.native import build

    calls = []
    monkeypatch.setattr(build, "LIBS", {"x.cpp": "libnot-there.so"})
    monkeypatch.setattr(build, "build", lambda verbose=True: calls.append(verbose))
    try:
        fleet.build_native()
    except fleet.BenchFailure:
        pass  # where the real libraries are not built either
    assert calls == [False]
    calls.clear()
    monkeypatch.setattr(build, "LIBS", {})  # nothing is missing
    try:
        fleet.build_native()
    except fleet.BenchFailure:
        pass
    assert calls == []


# -- the whole run, rehearsed ----------------------------------------------------
def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_off_the_chip_a_run_fails_without_a_metric_line(capsys):
    with pytest.raises(run.BenchFailure, match="no accelerator"):
        run.main(["--workload", BENCH["workloads"][0]["name"], "--seed", "1",
                  "--seconds", "1"])
    assert not [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    with pytest.raises(run.BenchFailure, match="no workload"):
        run.main(["--workload", "nope", "--seed", "1", "--seconds", "1", "--rehearse"])


@pytest.mark.parametrize("cell", ["qwen3-32b.sessions", "qwen3-30b-a3b.reasoning"])
def test_a_run_on_a_broken_program_is_not_correct(cell, monkeypatch, capsys):
    """The whole run, rehearsed, with the served decode program altered where
    it produces its logits (a thousandth of the largest, at one token a
    step): the line comes out, with ``correct`` false."""
    import jax.numpy as jnp

    from llm_d_kv_cache_manager_tpu.models import llama

    sound = llama.decode_step

    def broken(*args, **kwargs):
        logits, *rest = sound(*args, **kwargs)
        bump = 1e-3 * jnp.abs(logits).max()
        return (logits.at[..., 7].add(bump), *rest)

    monkeypatch.setattr(llama, "decode_step", broken)
    assert run.main(["--workload", cell, "--seed", "23", "--seconds", "2",
                     "--trace", "0", "--rehearse"]) == 0
    line, _ = last_line(capsys)
    assert line["correct"] is False and line["reference"]["ok"] is False
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["reference"]["rel_err"] > line["reference"]["tol"]["max"]


@pytest.mark.parametrize("cell,trace", [
    ("qwen3-32b.sessions", 1), ("qwen3-30b-a3b.reasoning", 0),
    ("qwen3-32b.sessions-x4", 0),
])
def test_rehearsal_runs_end_to_end(cell, trace, capsys):
    bench = dict(BENCH)
    path = None
    if cell not in {w["name"] for w in BENCH["workloads"]}:
        # a cell whose files are in place but which BENCHMARK.json leaves out
        bench["workloads"] = BENCH["workloads"] + [{
            "name": cell, "config": "qwen3-32b", "traffic": "sessions-x4",
            "chips": 4, "why": "rehearsal only"}]
        path = os.path.join(ROOT, "chipbench", "out", "rehearsal_benchmark.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        json.dump(bench, open(path, "w"))
    argv = ["--workload", cell, "--seed", str(2**31 + 17), "--seconds", "3",
            "--trace", str(trace), "--rehearse"]
    assert run.main(argv + (["--benchmark", path] if path else [])) == 0
    line, out = last_line(capsys)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 3
    chips = next(w["chips"] for w in bench["workloads"] if w["name"] == cell)
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": chips,
                              "memory_peak_bytes": 0}
    assert "breakdown" not in line and "busy_s" not in line["device"]
    want = run.metrics_of_cell(bench["per_layer" if trace else "end_to_end"], cell)
    device_only = {m["name"] for m in want if m["source"] == "device_trace"} | {"peak_hbm_gib"}
    # a 3-second rehearsal has no ten samples beyond a 95th percentile
    assert set(line["metrics"]) == (
        {m["name"] for m in want} - device_only - {"ttft_ms_p95"})
    for m in want:
        if m["name"] in line["metrics"]:
            assert line["metrics"][m["name"]]["unit"] == m["unit"]
    if trace:
        assert line["metrics"].get("prefix_hit_share", {"value": 50})["value"] > 30
        key = "compiles_in_window." + ("decode" if "reasoning" in cell else "serve")
        assert line["metrics"][key]["value"] == 0
    assert any("reference check" in l for l in out)


def test_a_cell_is_added_by_files_alone(capsys):
    """A configuration, a traffic mix, a per-layer metric and a cell, added
    with new files and new entries only — no file that is there is edited."""
    base = os.path.join(ROOT, "chipbench")
    added = {
        "configs/throwaway-moe.json": {
            "source": "test", "chipbench": {
                "model_name": "tiny-qwen3-moe", "preset": "TINY_QWEN3_MOE",
                "replace": {}, "reference": "moe",
                "env": {"BLOCK_SIZE": 4, "TOTAL_PAGES": 256,
                        "MAX_MODEL_LEN": 128, "DECODE_BATCH_SIZE": 2},
                "engine": {"prefill_bucket": 16, "decode_pages_bucket": 8}}},
        "traffic/throwaway-chat.json": {
            "kind": "open_poisson", "rate_rps": 5.0, "sizes_seed": 5,
            "groups": {"prefix_tokens": [16], "pool_share": 0.2, "zipf_s": 0.5},
            "unique": {"dist": "uniform", "min": 4, "max": 12},
            "output": {"dist": "uniform", "min": 2, "max": 5},
            "fill_piece_tokens": 16, "fill_tail_tokens": 4},
    }
    reader = 'def read(run):\n    return len(run.good)\n'
    paths = [os.path.join(base, rel) for rel in added]
    paths.append(os.path.join(base, "layer_metrics", "throwaway_requests.py"))
    bench_path = os.path.join(base, "out", "throwaway_benchmark.json")
    os.makedirs(os.path.dirname(bench_path), exist_ok=True)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "throwaway-moe", "source": "test",
                             "file": "chipbench/configs/throwaway-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway-moe.chat", "config": "throwaway-moe",
                               "traffic": "throwaway-chat", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "throwaway_requests", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "benchmark itself", "moves": "out_tokens_per_s",
                               "workloads": ["throwaway-moe.chat"]})
    # a metric with a suffix is read by its family's reader: an entry alone
    bench["per_layer"].append({"name": "compiles_in_window.chat", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "model step", "moves": "out_tokens_per_s",
                               "workloads": ["throwaway-moe.chat"]})
    try:
        for rel, content in added.items():
            json.dump(content, open(os.path.join(base, rel), "w"))
        open(paths[-1], "w").write(reader)
        json.dump(bench, open(bench_path, "w"))
        assert run.main(["--workload", "throwaway-moe.chat", "--seed", "4",
                         "--seconds", "2", "--trace", "1", "--rehearse",
                         "--benchmark", bench_path]) == 0
    finally:
        for p in paths + [bench_path]:
            if os.path.exists(p):
                os.remove(p)
    line, _ = last_line(capsys)
    assert line["correct"] and line["metrics"]["throwaway_requests"]["value"] == line["attempted"]
    assert "step_ms_mean" in line["metrics"] and "prefix_hit_share" not in line["metrics"]
    assert line["metrics"]["compiles_in_window.chat"]["value"] == 0
