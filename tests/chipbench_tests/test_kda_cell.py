"""What PR 47 added to the benchmark, rehearsed on the CPU: the cell
``ling-3.0-flash.threads`` (configuration, mix, reference, readers, cost
functions, probe) and that nothing the benchmark had was touched. No chip, no
child process; nothing here is a measurement.
"""

import json
import os
import types

import accepted_entries
import pytest

from chipbench import costs, costs_kda, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = "ling-3.0-flash"
CELL = "ling-3.0-flash.threads"
#: name -> (layer, the end-to-end metric it should move)
NEW_METRICS = {
    "kernel_time_share.kda_decode": ("kernels", "itl_ms_p50"),
    "kda_decode_roofline": ("kernels", "itl_ms_p50"),
    "linear_decode_step_roofline": ("model step", "itl_ms_p50"),
    "decode_scope_ms.kda": ("model step", "itl_ms_p50"),
    "prefill_scope_ms.kda": ("model step", "out_tokens_per_s"),
    "state_bytes_per_token": ("block manager", "out_tokens_per_s"),
    "state_cutback_tokens_mean": ("block manager", "out_tokens_per_s"),
    "state_cutback_lost_share": ("block manager", "out_tokens_per_s"),
    "prefix_hit_share.threads": ("block manager", "out_tokens_per_s"),
}

#: what the benchmark held when PR 47 began, by name (``BENCHMARK.json`` at
#: PR 46): nothing here says where in its list an entry stands
ACCEPTED = {
    "configs": """qwen3-32b qwen3-30b-a3b sdar-30b-a3b kanana-2-30b-a3b lfm2-8b-a1b
        longcat-flash-omni trinity-large-preview""",
    "workloads": """qwen3-32b.sessions qwen3-30b-a3b.reasoning sdar-30b-a3b.blockgen
        kanana-2-30b-a3b.docqa lfm2-8b-a1b.agentloop longcat-flash-omni.turns
        trinity-large-preview.longdocs""",
    "end_to_end": "ttft_ms_p50 itl_ms_p50 out_tokens_per_s setup_s",
    "per_layer": """
        score_ms_p50 prefix_hit_share prefix_hit_share.bypass
        pool_cached_share pod_ttft_ms_p50 ttft_ms_p95 lanes_busy_mean
        prefill_rows_mean step_ms_mean compiles_in_window.serve
        compiles_in_window.decode kernel_time_share.paged_attention kernel_time_share.flash_prefill
        kernel_time_share.gmm device_idle_share peak_hbm_gib
        loadgen_late_ms_p95 step_phase_ms.schedule step_phase_ms.decode_build
        step_phase_ms.decode_put step_phase_ms.decode_dispatch
        step_phase_ms.decode_fetch step_phase_ms.decode_commit
        step_phase_ms.publish step_phase_ms.loop step_phase_ms.prefill_build
        step_phase_ms.prefill_put step_phase_ms.prefill_dispatch
        step_phase_ms.prefill_fetch step_phase_ms.prefill_commit
        step_phase_ms.prefill idle_gap_share.schedule
        idle_gap_share.prefill_build idle_gap_share.prefill_put
        idle_gap_share.prefill_dispatch idle_gap_share.prefill_fetch
        idle_gap_share.prefill_commit idle_gap_share.decode_build
        idle_gap_share.decode_put idle_gap_share.decode_dispatch
        idle_gap_share.decode_fetch idle_gap_share.decode_commit
        idle_gap_share.publish idle_gap_share.loop idle_gap_share.unattributed
        queue_wait_ms_p50 staged_wait_ms_p50 decode_rows_mean
        sampled_dispatch_share tokens_per_forward_mean forwards_per_block_mean
        commit_forward_share denoise_step_roofline
        kernel_time_share.block_attention block_attention_roofline
        kernel_time_share.mla_decode mla_decode_roofline
        kernel_time_share.mla_prefill latent_bytes_per_token
        prefix_hit_share.docqa chained_dispatch_share cache_bytes_per_token.kv
        cache_bytes_per_token.state prefix_hit_share.agentloop
        hybrid_decode_step_roofline decode_scope_ms.attn
        decode_scope_ms.cache_write decode_scope_ms.head
        decode_scope_ms.sample decode_scope_ms.unscoped decode_scope_ms.ffn
        decode_scope_ms.moe_router decode_scope_ms.moe_experts
        decode_scope_ms.moe_shared decode_scope_ms.conv prefill_scope_ms.attn
        prefill_scope_ms.ffn prefill_scope_ms.head decode_experts_touched_mean
        counted_decode_step_roofline prefill_slot_fill_share
        prefill_scope_ms.moe_experts prefill_scope_ms.moe_router
        zero_place_share held_experts_touched_share held_rows_mean
        scmoe_decode_step_roofline decode_scope_ms.moe_zero
        prefix_hit_share.longdocs kernel_time_share.paged_attention_window
        window_ctx_share window_bytes_per_token window_pool_held_share
        window_short_hit_share swa_decode_step_roofline swa_attention_roofline
        page_run_share""",
}


def test_accepted_entries_are_as_they_were():
    """The benchmark PR 46 left (7 configurations, 7 cells, 4 end-to-end and
    98 per-layer metrics, command, paths, run_seconds), as they stood: each
    accepted entry is looked up by its name, so an entry that a later PR
    appends, wherever it stands, does not falsify this, and without its
    ``workloads`` list, which a ``benchmark`` PR may widen
    (``accepted_entries.py``)."""
    assert accepted_entries.digest(BENCH, ACCEPTED) == "4b700a8ea2608dd5d07fdc4c6d5d5ba148ef56b9b3b9cc0728c8275e477ee140"
    # ... less ``decode_step_roofline``, which PR 55 retired
    assert accepted_entries.count(ACCEPTED) == 7 + 7 + 4 + 97


def test_this_prs_entries_list_the_new_cell_alone():
    """They exist and list the cell; nothing here says that nobody else
    does, nor where in ``per_layer`` they stand (a ``benchmark`` PR lists a
    cell a reader works in, and later PRs append)."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (layer, moves) in NEW_METRICS.items():
        assert CELL in by_name[name]["workloads"]
        assert (by_name[name]["layer"], by_name[name]["moves"]) == (layer, moves)
        assert callable(run.load_layer_metric(name))  # by file or by family
    cell = run.find_cell(BENCH, CELL)
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "threads", 1)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.count(name) == 1 for name in NEW_METRICS)
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size"]
    assert config["source"] == ("https://huggingface.co/inclusionAI/"
                                "Ling-3.0-flash/blob/main/config.json")
    e2e = {m["name"] for m in run.metrics_of_cell(BENCH["end_to_end"], CELL)}
    assert e2e == {"itl_ms_p50", "out_tokens_per_s", "setup_s"}
    # the accepted metrics without a list are read in the new cell too (and
    # those a ``benchmark`` PR found to work here: a superset)
    read_here = {m["name"] for m in run.metrics_of_cell(BENCH["per_layer"], CELL)}
    assert read_here >= set(NEW_METRICS) | {
        "lanes_busy_mean", "step_ms_mean", "kernel_time_share.paged_attention",
        "device_idle_share", "peak_hbm_gib"}


def test_the_configuration_is_the_cut_the_issue_sets_out():
    config = run.load_config(CONFIG)
    pub = config["published"]
    assert set(pub["reduced"]) == {"num_hidden_layers", "first_k_dense_replace",
                                   "num_experts", "vocab_size"}
    assert (pub["num_hidden_layers"], pub["first_k_dense_replace"],
            pub["num_experts"], pub["vocab_size"]) == (7, 1, 512, 19648)
    assert pub["deployment"] and len(pub["assumed"]) >= 10 and pub["restated"]
    assert len(pub["expert_swiglu_limit_list"]) == 42
    assert len(pub["share_expert_swiglu_limit_list"]) == 42
    assert not any(pub["expert_swiglu_limit_list"][:7])
    cfg = run.model_config(config, rehearse=False)
    assert (cfg.n_layers, cfg.n_kda_layers, cfg.n_attn_layers, cfg.experts_held,
            cfg.expert_first, cfg.router_outputs, cfg.first_k_dense,
            cfg.kv_row_shape) == (7, 6, 1, 64, 0, 512, 1, (640,))
    assert [cfg.layer_kind(i) for i in range(7)] == (
        ["linear"] * 5 + ["attention", "linear"])
    # the held range is one routing group of the published eight
    assert cfg.n_experts // cfg.n_group == cfg.experts_held == 64
    # every width is checked against the preset at every run
    for key, moved in (("n_group", 4), ("topk_group", 2), ("kv_lora_rank", 256),
                       ("layer_group_size", 4), ("short_conv_kernel_size", 3),
                       ("kda_safe_gate", False), ("kda_lower_bound", -4),
                       ("use_kda_lora", True), ("num_shared_experts", 2),
                       ("routed_scaling_factor", 1.0), ("head_dim", 64),
                       ("expert_swiglu_limit_list", [0] * 42),
                       ("moe_intermediate_size", 1024), ("hidden_size", 4096),
                       ("num_experts", 64), ("num_experts_per_tok", 4)):
        with pytest.raises(run.BenchFailure, match=key):
            run.model_config({**config, "published": {**pub, key: moved}},
                             rehearse=False)
    assert config["env"] == {
        "BLOCK_SIZE": 16, "TOTAL_PAGES": 16384, "MAX_MODEL_LEN": 16384,
        "DECODE_BATCH_SIZE": 64, "STATE_SNAPSHOT_TOKENS": 512,
        "STATE_SNAPSHOT_SLOTS": 384}


def test_the_mix():
    """The issue's mix. Two of its threads' lengths are no multiples of the
    scorer's tokenization store's 256 characters: ``fleet.wait_visible`` sees
    them whole because the pool tokenizes what lies behind the store's tokens
    (``tests/test_tokenization_pool.py``)."""
    spec = traffic.load_traffic("threads")
    assert {k: spec[k] for k in ("kind", "callers_per_lane", "requests",
                                 "fill_piece_tokens", "fill_tail_tokens")} == {
        "kind": "closed", "callers_per_lane": 2, "requests": 4096,
        "fill_piece_tokens": 640, "fill_tail_tokens": 16}
    assert spec["groups"] == {"prefix_tokens": [1920, 4480, 8960],
                              "pool_share": 0.5, "zipf_s": 1.0}
    assert spec["unique"] == {"dist": "lognormal", "median": 96, "sigma": 0.7,
                              "min": 32, "max": 384}
    assert spec["output"] == {"dist": "lognormal", "median": 192, "sigma": 0.6,
                              "min": 64, "max": 768}
    assert "request" not in spec and spec["who"]
    others = {traffic.load_traffic(n)["sizes_seed"] for n in (
        "sessions", "reasoning", "blockgen", "docqa", "agentloop", "turns",
        "longdocs")}
    assert spec["sizes_seed"] not in others
    sched = traffic.build_schedule(spec, 5, 45.0, pods=1,
                                   pool_tokens_per_pod=16384 * 16, lanes=64)
    # eight rounds of the three lengths: 24 threads, 122880 tokens resident
    assert [len(p) for p in sched.prefixes] == [1920, 4480, 8960] * 8
    assert sum(len(p) for p in sched.prefixes) == 122880
    assert sched.callers == 128 and len(sched.requests) == 4096
    stride = 512
    # whole fill pieces, and each past a boundary: what an admission is cut
    # back by and prefills again
    cut = {1920: 384, 4480: 384, 8960: 256}
    for n, back in cut.items():
        assert n % 640 == 0 and n % stride == back
    # their snapshots: one every 512 tokens, 224 of the pool's 384
    assert 8 * sum(n // stride for n in cut) == 224
    cached = sum(r.prefix_len - cut[r.prefix_len] for r in sched.requests)
    asked = sum(r.prompt_len for r in sched.requests)
    assert 0.85 < cached / asked < 0.95
    assert 340 < sum(cut[r.prefix_len] for r in sched.requests) / 4096 < 360
    # nothing resident is evicted: the threads and 64 lanes' own turns
    assert 122880 + 64 * (512 + 384 + 768) <= 0.9 * 16384 * 16
    rounds = traffic.fill_plan(sched, spec, 5)
    assert len(rounds) == 14 and len(rounds[0]) == 24 and len(rounds[-1]) == 8
    buckets = traffic.Buckets(page=16, prefill_bucket=128, prefill_ctx_bucket=1024,
                              decode_pages_bucket=512, max_pages=1025)
    prefill, decode = traffic.shape_set(sched.requests, buckets)
    assert prefill == {(c, 1024) for c in (128, 256, 384)}
    assert decode == {512, 1024}


def test_cost_functions_against_hand_sums():
    cfg = run.model_config(run.load_config(CONFIG), rehearse=False)
    hk = 32 * 128
    kda = 5 * 2560 * hk + 2 * 2560 * 32
    assert costs_kda.kda_params(cfg) == kda == 52_592_640
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 32 * 128 * 2560)
    dense, expert = 3 * 2560 * 6144, 3 * 2560 * 768
    assert costs_kda.expert_params(cfg) == expert == 5_898_240
    assert costs_kda.dense_ffn_params(cfg) == dense == 47_185_920
    router = 2560 * 512 + 512
    assert costs_kda.layers_of(cfg) == (6, 1, 6)
    held = 6 * kda + mla + dense + 6 * (router + 65 * expert)
    assert costs_kda.model_params(cfg, 64) == held
    head = 19648 * 2560
    assert costs_kda.resident_weight_bytes(cfg) == 2 * (2 * head + held)
    assert 5.5e9 < costs_kda.resident_weight_bytes(cfg) < 5.7e9
    # a slot: 32 matrices of 128 x 128 float32 and 3 rows of 12288 bf16
    assert costs_kda.state_bytes_per_layer(cfg) == 2 * 2**20 + 72 * 2**10
    assert costs_kda.state_bytes_per_snapshot(cfg) == cfg.kda_state_bytes
    assert costs_kda.state_bytes_per_snapshot(cfg) == 13_025_280  # 12.42 MiB
    assert costs_kda.state_bytes_per_token(cfg, 512) == 25440
    assert costs_kda.latent_bytes_per_token(cfg) == 1280
    # the decode kernel's calls of a step of 64 lanes: the matrices read and
    # written in six layers and six rows of operands a head
    kernel = 6 * 64 * (2 * hk * 128 * 4 + 6 * hk * 4)
    assert costs_kda.kda_decode_bytes(cfg, 64) == kernel
    assert 1.6e9 < kernel < 1.65e9
    assert costs_kda.kda_decode_flops(cfg, 64) == 6 * 64 * 7 * 32 * 128 * 128
    # a decode step of 64 lanes at 5000 tokens each, 40 held experts read a
    # layer, 64 rows in the grouped matmuls
    ctx = 64 * 5000
    weights = 2 * (6 * kda + mla + dense + 6 * (router + 41 * expert) + head
                   + 64 * 2560)
    state = 2 * 64 * 6 * (2 * 2**20 + 72 * 2**10)
    want = weights + state + ctx * 1280
    assert costs_kda.decode_step_min_bytes(cfg, 64, ctx, 40) == want
    more = costs_kda.decode_step_min_bytes(cfg, 64, ctx, 64)
    assert more - want == 2 * 6 * 24 * expert
    flops = (2 * 64 * (6 * kda + mla + dense + 6 * (2560 * 512 + expert) + head)
             + 2 * 6 * 64 * expert + ctx * 2 * 32 * (576 + 512)
             + 6 * 64 * 7 * 32 * 128 * 128)
    assert costs_kda.decode_step_flops(cfg, 64, ctx, 64) == flops
    peaks = costs.load_peaks("TPU v5 lite")
    assert costs_kda.decode_step_min_s(cfg, peaks, 64, ctx, 40, 64) == (
        want / 819e9)
    assert 5.0e9 < want < 6.0e9 and want / 819e9 > 4 * flops / 197e12
    with pytest.raises(TypeError):  # no count, no cost: nothing is guessed
        costs_kda.decode_step_min_bytes(cfg, 64, ctx)


def records(**kw):
    forwards, layers = 100, 6
    counters = ("experts_touched", "decode_forwards", "decode_dispatches",
                "decode_rows", "attn_ctx_tokens", "routed_places",
                "zero_places", "held_places")
    pool = {"routed_layers": layers, "experts_held": 64, "zero_experts": 0,
            "state_slots": 456, "state_bytes_per_snapshot": 13_025_280,
            "state_snapshot_tokens": 512, "kv_bytes_per_token": 1280}
    zero = dict.fromkeys(("state_admissions", "state_snapshots_taken",
                          "state_restores", "state_snapshots_evicted",
                          "state_cutback_tokens", "state_cutback_lost"), 0)
    base = dict(
        cell=run.find_cell(BENCH, CELL), good=[{}] * 90, failed=[],
        in_flight=[{}] * 10, in_flight_tokens=0, late_s=[], window_s=10.0,
        stats_before=[{**pool, **zero, "state_snapshots_held": 224}],
        stats_after=[{**pool, "state_snapshots_held": 380,
                      "state_admissions": 200, "state_snapshots_taken": 300,
                      "state_restores": 200, "state_snapshots_evicted": 150,
                      "state_cutback_tokens": 51200, "state_cutback_lost": 3}],
        running_samples=[], lanes=64, page=16, pods=[object()],
        step_before=[dict.fromkeys(counters, 0)],
        step_after=[{"experts_touched": forwards * layers * 40,
                     "decode_forwards": forwards, "decode_dispatches": forwards,
                     "decode_rows": forwards * 64,
                     "attn_ctx_tokens": forwards * 64 * 5000,
                     "routed_places": forwards * layers * 64 * 8,
                     "zero_places": 0,
                     "held_places": forwards * layers * 64}],
        compiles_in_window=0, memory_peak_bytes=0,
        model_cfg=run.model_config(run.load_config(CONFIG), rehearse=False),
        peaks=costs.load_peaks("TPU v5 lite"),
        trace={"busy_s": 3.0, "window_s": 4.0,
               "ops": {"kda_decode.1": 0.3, "mla_decode.2": 0.1, "fusion.9": 2.6},
               "ops_text": {},
               "module_calls": {"jit_decode_steps(1)": 100},
               "modules": {"jit_decode_steps(1)": 1.8}},
    )
    base.update(kw)
    return run.RunRecords(**base)


def test_readers_on_hand_made_records():
    read = {name: run.load_layer_metric(name) for name in NEW_METRICS}
    r = records()
    cfg = r.model_cfg
    assert read["state_bytes_per_token"](r) == pytest.approx(13_025_280 / 512)
    assert read["state_cutback_tokens_mean"](r) == pytest.approx(256.0)
    assert read["state_cutback_lost_share"](r) == pytest.approx(1.5)
    assert read["kernel_time_share.kda_decode"](r) == pytest.approx(10.0)
    # the kernel's calls of a forward: 0.3 s over 100
    least_s = costs_kda.kda_decode_bytes(cfg, 64) / 819e9
    assert read["kda_decode_roofline"](r) == pytest.approx(100 * least_s / 0.003)
    assert 60 < read["kda_decode_roofline"](r) < 70
    step_s = costs_kda.decode_step_min_bytes(cfg, 64, 64 * 5000, 40) / 819e9
    assert read["linear_decode_step_roofline"](r) == pytest.approx(
        100 * step_s / 0.018)
    assert 30 < read["linear_decode_step_roofline"](r) < 45
    # a program from before the counters (the parent), a pod that does not
    # report the state pool, a run with no trace, another model: nothing to
    # read, and no error
    old = records(step_before=[{"decode_dispatches": 0, "experts_touched": 0}],
                  step_after=[{"decode_dispatches": 100, "experts_touched": 9}],
                  stats_before=[{}], stats_after=[{"routed_layers": 6}])
    for name in NEW_METRICS:
        if name.startswith(("prefix_hit_share", "kernel_time_share",
                            "decode_scope_ms", "prefill_scope_ms")):
            continue  # accepted readers, under a new suffix
        assert read[name](old) is None, name
    for name in ("kda_decode_roofline", "linear_decode_step_roofline"):
        assert read[name](records(trace=None)) is None
        other = records(model_cfg=types.SimpleNamespace(kda_head_dim=0))
        assert read[name](other) is None
    # a model whose state rides in its pages reports no snapshot: not read
    paged = records(stats_after=[{"state_bytes_per_token": 8192}])
    assert read["state_bytes_per_token"](paged) is None


def test_the_cell_rehearses(capsys):
    """The whole run on the CPU at the tiny preset: ``correct`` true (the
    probe's controls, below, are what read not correct)."""
    # (four seconds: under six workers' load a window of two has closed
    # before the first request of it completed, and ``correct`` wants one)
    assert run.main(["--workload", CELL, "--seed", str(2**31 + 47),
                     "--seconds", "4", "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["correct"] is True and line["reference"]["ok"] is True
    got = line["metrics"]
    # a slot of the tiny preset over its stride of 8
    assert got["state_bytes_per_token"]["value"] == 4 * (
        4 * 16 * 16 * 4 + 3 * 192 * 4) / 8
    assert got["state_cutback_tokens_mean"]["value"] >= 0
    assert "state_cutback_lost_share" in got
    assert got["prefix_hit_share.threads"]["value"] > 40
    # no device number off the chip
    assert "kda_decode_roofline" not in got
    assert "linear_decode_step_roofline" not in got
    assert "kernel_time_share.kda_decode" not in got
    assert "decode_scope_ms.kda" not in got


def test_the_probes_controls_each_read_not_correct(capsys):
    """``probe_kda.py`` at the tiny preset in float32: the sound run is
    correct and every control is not."""
    from chipbench import probe_kda

    # (one period of the preset's two: every kind of layer once, half the
    # programs to compile for each of the six runs)
    assert probe_kda.main(["--seeds", "3", "--rehearse", "--layers", "3"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["control"] for x in lines] == ["sound", *probe_kda.CONTROLS]
    assert [x["ok"] for x in lines] == [True] + [False] * len(probe_kda.CONTROLS)
