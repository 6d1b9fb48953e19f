"""What PR 58 added to the benchmark, rehearsed on the CPU: the cell
``solar-open2-250b.histories`` (configuration, mix, reference, readers, cost
functions, probe) and that its entries list the new cell alone. No chip, no
child process; nothing here is a measurement.
"""

import json
import os
import types

import pytest

from chipbench import costs, costs_kda, costs_kda_gqa, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = "solar-open2-250b"
CELL = "solar-open2-250b.histories"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: name -> (layer, the end-to-end metric it should move). FIVE, not the eight
#: ISSUE 58 lists: ``per_layer`` may hold 128 entries and the parent's holds
#: 123, so ``prefill_scope_ms.kda``, ``state_cutback_lost_share`` and
#: ``state_bytes_per_token`` are not reported in this cell until a
#: ``benchmark`` PR appends it to their lists (``ACCEPTED_READ_HERE_TOO``)
NEW_METRICS = {
    "linear_gqa_decode_step_roofline": ("model step", "itl_ms_p50"),
    "kda_decode_roofline.histories": ("kernels", "itl_ms_p50"),
    "decode_scope_ms.kda.histories": ("model step", "itl_ms_p50"),
    "state_cutback_tokens_mean.histories": ("block manager", "out_tokens_per_s"),
    "prefix_hit_share.histories": ("block manager", "out_tokens_per_s"),
}


#: accepted entries whose readers read this cell's records as they stand
ACCEPTED_READ_HERE_TOO = ("prefill_scope_ms.kda", "state_cutback_lost_share",
                          "state_bytes_per_token")


def test_this_prs_entries_list_the_new_cell_alone():
    assert len(BENCH["per_layer"]) <= 128  # the file's own limit: refused over it
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (layer, moves) in NEW_METRICS.items():
        assert by_name[name]["workloads"] == [CELL]
        assert (by_name[name]["layer"], by_name[name]["moves"]) == (layer, moves)
        assert callable(run.load_layer_metric(name))  # by file or by family
    # each reader is a NEW file named for the whole metric, but the hit
    # share's, which the accepted reader serves under any suffix
    for name in NEW_METRICS:
        own = os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py")
        assert os.path.isfile(own) != (name == "prefix_hit_share.histories")
    cell = run.find_cell(BENCH, CELL)
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert "attention and state at 8x their share" in cell["why"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "histories", 1)
    assert BENCH["workloads"][-1] is cell and BENCH["configs"][-1] is config
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW_METRICS):] == list(NEW_METRICS)
    # no accepted list names the new cell: a ``benchmark`` issue's to widen
    assert [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())] == list(NEW_METRICS)
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["source"] == ("https://huggingface.co/upstage/"
                                "Solar-Open2-250B/blob/main/config.json")
    e2e = {m["name"] for m in run.metrics_of_cell(BENCH["end_to_end"], CELL)}
    assert e2e == {"itl_ms_p50", "out_tokens_per_s", "setup_s"}
    read_here = {m["name"] for m in run.metrics_of_cell(BENCH["per_layer"], CELL)}
    assert read_here == set(NEW_METRICS) | {
        "lanes_busy_mean", "step_ms_mean", "kernel_time_share.paged_attention",
        "device_idle_share", "peak_hbm_gib"}


def _catalog_row():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Solar-Open2-250B":
                return row
    pytest.skip("the catalog has no such row")


def test_the_configuration_is_the_catalogs_row_cut_as_the_issue_sets_out():
    config = run.load_config(CONFIG)
    pub = config["published"]
    row = _catalog_row()
    assert pub["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if pub.get(k) != v}
    assert differ == set(pub["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (pub["num_hidden_layers"], pub["n_routed_experts"], pub["num_experts"],
            pub["vocab_size"]) == (4, 40, 320, 24576)
    assert pub["linear_attn_config"] == row["config"]["linear_attn_config"]
    assert pub["gqa_layers"] == list(range(0, 48, 4))
    assert pub["deployment"] and "HOW NEAR THE SHARE IS" in pub["deployment"]
    assert len(pub["assumed"]) >= 12 and pub["restated"]
    cfg = run.model_config(config, rehearse=False)
    assert (cfg.n_layers, cfg.n_kda_layers, cfg.n_attn_layers, cfg.experts_held,
            cfg.expert_first, cfg.router_outputs, cfg.first_k_dense,
            cfg.kv_row_shape, cfg.kv_lora_rank) == (4, 3, 1, 40, 0, 320, 0,
                                                    (8, 128), 0)
    assert [cfg.layer_kind(i) for i in range(4)] == ["attention"] + ["linear"] * 3
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.kda_head_dim,
            cfg.kda_conv_kernel, cfg.moe_inter, cfg.n_experts_per_tok,
            cfg.n_shared_experts) == (64, 8, 128, 128, 4, 1280, 8, 1)
    assert not cfg.use_rope and cfg.kda_neg_eigval and cfg.kda_lora
    assert cfg.kda_channel_gate and cfg.attn_output_gate and not cfg.kda_safe_gate
    # every width is checked against the preset at every run
    for key, moved in (("gqa_layers", list(range(0, 48, 6))), ("use_rope", True),
                       ("use_gqa_gate", False), ("kda_allow_neg_eigval", False),
                       ("kda_use_full_proj", True), ("short_conv_kernel_size", 3),
                       ("linear_head_dim", 64), ("linear_num_heads", 32),
                       ("n_shared_experts", 2), ("routed_scaling_factor", 2.5),
                       ("first_k_dense_replace", 1), ("head_dim", 64),
                       ("moe_intermediate_size", 1024), ("hidden_size", 2048),
                       ("num_experts", 40), ("num_experts_per_tok", 4),
                       ("num_key_value_heads", 64), ("intermediate_size", 1280)):
        with pytest.raises(run.BenchFailure, match=key):
            run.model_config({**config, "published": {**pub, key: moved}},
                             rehearse=False)
    assert config["env"] == {
        "BLOCK_SIZE": 16, "TOTAL_PAGES": 49152, "MAX_MODEL_LEN": 58368,
        "DECODE_BATCH_SIZE": 32, "STATE_SNAPSHOT_TOKENS": 1024,
        "STATE_SNAPSHOT_SLOTS": 192}
    # the pools' sums the file states
    assert 49152 * 16 * costs_kda_gqa.kv_bytes_per_token(cfg) == 3 * 2**30
    assert (32 + 8 + 192) * cfg.kda_state_bytes == 232 * 13_025_280


def test_the_mix():
    spec = traffic.load_traffic("histories")
    assert {k: spec[k] for k in ("kind", "callers_per_lane", "requests",
                                 "fill_piece_tokens", "fill_tail_tokens")} == {
        "kind": "closed", "callers_per_lane": 2, "requests": 2048,
        "fill_piece_tokens": 256, "fill_tail_tokens": 16}
    assert spec["groups"] == {"prefix_tokens": [7680, 24320, 56576],
                              "pool_share": 0.5, "zipf_s": 1.0}
    assert spec["unique"] == {"dist": "lognormal", "median": 96, "sigma": 0.7,
                              "min": 32, "max": 384}
    assert spec["output"] == {"dist": "lognormal", "median": 192, "sigma": 0.6,
                              "min": 64, "max": 768}
    assert "request" not in spec and spec["who"]
    others = {traffic.load_traffic(n)["sizes_seed"] for n in (
        "sessions", "reasoning", "blockgen", "docqa", "agentloop", "turns",
        "longdocs", "threads", "mixedlen")}
    assert spec["sizes_seed"] not in others
    sched = traffic.build_schedule(spec, 5, 45.0, pods=1,
                                   pool_tokens_per_pod=49152 * 16, lanes=32)
    # four rounds of the three lengths: 12 histories, 354304 tokens, 1.35 GiB
    assert [len(p) for p in sched.prefixes] == [7680, 24320, 56576] * 4
    assert sum(len(p) for p in sched.prefixes) == 354304
    assert 354304 * 4096 / 2**30 == pytest.approx(1.3516, abs=1e-4)
    assert sched.callers == 64 and len(sched.requests) == 2048
    stride = 1024
    # whole fill pieces of their greatest common divisor, and each past a
    # boundary: what an admission is cut back by and prefills again
    cut = {7680: 512, 24320: 768, 56576: 256}
    for n, back in cut.items():
        assert n % 256 == 0 and n % stride == back
    cached = sum(r.prefix_len - cut[r.prefix_len] for r in sched.requests)
    asked = sum(r.prompt_len for r in sched.requests)
    assert cached / asked > 0.9
    assert 480 < sum(cut[r.prefix_len] for r in sched.requests) / 2048 < 540
    mean_ctx = sum(r.prompt_len + r.max_tokens / 2 for r in sched.requests) / 2048
    assert 22_000 < mean_ctx < 27_000
    # nothing resident is evicted: the histories and 32 lanes' own turns
    assert 354304 + 32 * (1024 + 384 + 768) <= 0.9 * 49152 * 16
    # the longest sequence fits the model length the file states
    assert max(r.prompt_len + r.max_tokens for r in sched.requests) <= 58368
    rounds = traffic.fill_plan(sched, spec, 5)
    assert len(rounds) == 221 and len(rounds[0]) == 12 and len(rounds[-1]) == 4
    # what the fill prefills, cut back to the stride, for what it leaves
    # resident: 2.56 times the tokens
    filled = 4 * sum(((k - 1) % 4 + 1) * 256 + 16
                     for n in cut for k in range(1, n // 256 + 1))
    assert filled == 902784 and 2.5 < filled / 354304 < 2.6
    buckets = traffic.Buckets(page=16, prefill_bucket=256, prefill_ctx_bucket=4096,
                              decode_pages_bucket=1024, max_pages=3648)
    _, decode = traffic.shape_set(sched.requests, buckets)
    assert decode == {1024, 2048, 3648}


def test_cost_functions_against_hand_sums():
    cfg = run.model_config(run.load_config("solar-open2-250b"), rehearse=False)
    hk = 64 * 128
    pair = 4096 * 128 + 128 * hk
    kda = (4096 * 3 * hk + hk * 4096 + 2 * pair + 4096 * 64 + 4 * 3 * hk
           + hk + 64 + 128)
    assert (4096 * 3 * hk, hk * 4096, pair, 4096 * 64, 4 * 3 * hk) == (
        100_663_296, 33_554_432, 1_572_864, 262_144, 98_304)
    assert costs_kda_gqa.kda_params(cfg) == kda == 137_732_288
    gqa = 3 * 4096 * 8192 + 2 * 4096 * 1024
    assert costs_kda_gqa.gqa_params(cfg) == gqa == 109_051_904
    router, expert = 4096 * 320 + 320, 3 * 4096 * 1280
    assert costs_kda.router_params(cfg) == router == 1_311_040
    assert costs_kda.expert_params(cfg) == expert == 15_728_640
    assert costs_kda_gqa.layers_of(cfg) == (3, 1)
    assert costs_kda_gqa.routed_fixed_params(cfg) == router + expert
    held = 3 * kda + gqa + 4 * (router + 41 * expert)
    assert costs_kda_gqa.model_params(cfg, 40) == held
    head = 24576 * 4096
    assert costs_kda_gqa.resident_weight_bytes(cfg) == 2 * (2 * head + held)
    # 3,308 M parameters: 6.62 GB = 6.16 GiB
    assert 3.30e9 < 2 * head + held < 3.31e9
    assert 6.16 < costs_kda_gqa.resident_weight_bytes(cfg) / 2**30 < 6.17
    # the whole model counts what its name says
    whole = cfg.__class__(**{**cfg.__dict__, "n_layers": 48, "vocab_size": 196608,
                             "expert_count": None})
    total = costs_kda_gqa.model_params(whole, 320) + 2 * costs.head_params(whole)
    active = costs_kda_gqa.model_params(whole, 8) + 2 * costs.head_params(whole)
    assert 250.2e9 < total < 250.4e9 and 14.6e9 < active < 14.8e9
    # a token: 4096 B of K and V; a slot: 3 x (64 matrices of 128 x 128
    # float32 + 3 rows of 24576 bf16) = 12.42 MiB, 12,720 B a token at 1024
    assert costs_kda_gqa.kv_bytes_per_token(cfg) == 4096
    assert costs_kda.state_bytes_per_layer(cfg) == 64 * 128 * 128 * 4 + 3 * 24576 * 2
    assert costs_kda.state_bytes_per_snapshot(cfg) == cfg.kda_state_bytes == 13_025_280
    assert costs_kda.state_bytes_per_token(cfg, 1024) == 12_720
    # the decode kernel's calls of a step of 32 lanes: the matrices read and
    # written in three layers and six rows of operands a head
    kernel = 3 * 32 * (2 * hk * 128 * 4 + 6 * hk * 4)
    assert costs_kda.kda_decode_bytes(cfg, 32) == kernel
    assert 0.80e9 < kernel < 0.83e9
    # a decode step of 32 lanes at 25000 tokens each, 22 held experts read a
    # layer, 32 rows in the grouped matmuls
    ctx = 32 * 25000
    weights = 2 * (3 * kda + gqa + 4 * (router + 23 * expert) + head + 32 * 4096)
    state = 2 * 32 * 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    want = weights + state + ctx * 4096
    assert costs_kda_gqa.decode_step_min_bytes(cfg, 32, ctx, 22) == want
    # the issue's sum: 3.3 GB of K and V, 0.83 of state, 2.8 of touched
    # experts, 1.4 of other weights and head: 8.3 GB, 10.2 ms at 819 GB/s
    assert ctx * 4096 == pytest.approx(3.28e9, rel=0.01)
    assert state == pytest.approx(0.83e9, rel=0.01)
    assert 2 * 4 * 22 * expert == pytest.approx(2.77e9, rel=0.01)
    assert want == pytest.approx(8.3e9, rel=0.01)
    more = costs_kda_gqa.decode_step_min_bytes(cfg, 32, ctx, 40)
    assert more - want == 2 * 4 * 18 * expert
    flops = (2 * 32 * (3 * kda + gqa + 4 * (4096 * 320 + expert) + head)
             + 2 * 4 * 32 * expert + ctx * 4 * 64 * 128
             + 3 * 32 * 7 * 64 * 128 * 128)
    assert costs_kda_gqa.decode_step_flops(cfg, 32, ctx, 32) == flops
    peaks = costs.load_peaks("TPU v5 lite")
    assert costs_kda_gqa.decode_step_min_s(cfg, peaks, 32, ctx, 22, 32) == (
        want / 819e9)
    assert want / 819e9 == pytest.approx(10.1e-3, rel=0.02)
    assert want / 819e9 > 10 * flops / 197e12
    with pytest.raises(TypeError):  # no count, no cost: nothing is guessed
        costs_kda_gqa.decode_step_min_bytes(cfg, 32, ctx)


def records(**kw):
    forwards, layers = 100, 4
    counters = ("experts_touched", "decode_forwards", "decode_dispatches",
                "decode_rows", "attn_ctx_tokens", "routed_places",
                "zero_places", "held_places")
    pool = {"routed_layers": layers, "experts_held": 40, "zero_experts": 0,
            "state_slots": 232, "state_bytes_per_snapshot": 13_025_280,
            "state_snapshot_tokens": 1024, "kv_bytes_per_token": 4096}
    zero = dict.fromkeys(kda_pool_keys(), 0)
    good = [{"body": {"usage": {"prompt_tokens": 1000,
                                "cached_prompt_tokens": 920}}}] * 90
    base = dict(
        cell=run.find_cell(BENCH, CELL), good=good, failed=[],
        in_flight=[{}] * 10, in_flight_tokens=0, late_s=[], window_s=10.0,
        stats_before=[{**pool, **zero, "state_snapshots_held": 12}],
        stats_after=[{**pool, "state_snapshots_held": 190,
                      "state_admissions": 200, "state_snapshots_taken": 150,
                      "state_restores": 200, "state_snapshots_evicted": 0,
                      "state_cutback_tokens": 102400, "state_cutback_lost": 1}],
        running_samples=[], lanes=32, page=16, pods=[object()],
        step_before=[dict.fromkeys(counters, 0)],
        step_after=[{"experts_touched": forwards * layers * 22,
                     "decode_forwards": forwards, "decode_dispatches": forwards,
                     "decode_rows": forwards * 32,
                     "attn_ctx_tokens": forwards * 32 * 25000,
                     "routed_places": forwards * layers * 32 * 8,
                     "zero_places": 0,
                     "held_places": forwards * layers * 32}],
        compiles_in_window=0, memory_peak_bytes=0,
        model_cfg=run.model_config(run.load_config(CONFIG), rehearse=False),
        peaks=costs.load_peaks("TPU v5 lite"),
        trace={"busy_s": 3.0, "window_s": 4.0,
               "ops": {"kda_decode.1": 0.15, "paged_attention.2": 0.6,
                       "fusion.9": 2.25},
               "ops_text": {},
               "module_calls": {"jit_decode_steps(1)": 100},
               "modules": {"jit_decode_steps(1)": 1.5}},
    )
    base.update(kw)
    return run.RunRecords(**base)


def kda_pool_keys():
    from chipbench import kda_counts

    return kda_counts.POOL_KEYS


def test_readers_on_hand_made_records():
    read = {name: run.load_layer_metric(name)
            for name in (*NEW_METRICS, *ACCEPTED_READ_HERE_TOO)}
    r = records()
    cfg = r.model_cfg
    assert read["state_bytes_per_token"](r) == pytest.approx(12_720.0)
    assert read["state_cutback_tokens_mean.histories"](r) == pytest.approx(512.0)
    assert read["state_cutback_lost_share"](r) == pytest.approx(0.5)
    assert read["prefix_hit_share.histories"](r) == pytest.approx(92.0)
    # the kernel's calls of a forward: 0.15 s over 100
    least_s = costs_kda.kda_decode_bytes(cfg, 32) / 819e9
    assert read["kda_decode_roofline.histories"](r) == pytest.approx(
        100 * least_s / 0.0015)
    assert 60 < read["kda_decode_roofline.histories"](r) < 70
    step_s = costs_kda_gqa.decode_step_min_bytes(cfg, 32, 32 * 25000, 22) / 819e9
    assert read["linear_gqa_decode_step_roofline"](r) == pytest.approx(
        100 * step_s / 0.015)
    assert 60 < read["linear_gqa_decode_step_roofline"](r) < 75
    # a program from before the counters (the parent), a pod that does not
    # report the state pool, a run with no trace, another model: nothing to
    # read, and no error
    old = records(step_before=[{"decode_dispatches": 0, "experts_touched": 0}],
                  step_after=[{"decode_dispatches": 100, "experts_touched": 9}],
                  stats_before=[{}], stats_after=[{"routed_layers": 4}])
    for name in NEW_METRICS:
        if name.startswith(("prefix_hit_share", "decode_scope_ms",
                            "prefill_scope_ms")):
            continue  # accepted readers, under a new name
        assert read[name](old) is None, name
    for name in ("kda_decode_roofline.histories",
                 "linear_gqa_decode_step_roofline"):
        assert read[name](records(trace=None)) is None
        other = records(model_cfg=types.SimpleNamespace(kda_head_dim=0))
        assert read[name](other) is None
    # a latent pool between the linear layers is the accepted reader's model
    latent = records(model_cfg=run.model_config(
        run.load_config("ling-3.0-flash"), rehearse=False))
    assert read["linear_gqa_decode_step_roofline"](latent) is None
    # the scope readers find no scope in a trace without one, and say nothing
    assert read["decode_scope_ms.kda.histories"](records(trace=None)) is None
    assert read["prefill_scope_ms.kda"](records(trace=None)) is None


def test_the_cell_rehearses(capsys):
    """The whole run on the CPU at the tiny preset: ``correct`` true (the
    probe's controls, below, are what read not correct)."""
    assert run.main(["--workload", CELL, "--seed", str(2**31 + 58),
                     "--seconds", "4", "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["correct"] is True and line["reference"]["ok"] is True
    got = line["metrics"]
    assert got["state_cutback_tokens_mean.histories"]["value"] >= 0
    assert got["prefix_hit_share.histories"]["value"] > 40
    # no device number off the chip
    for name in ("kda_decode_roofline.histories", "decode_scope_ms.kda.histories",
                 "linear_gqa_decode_step_roofline",
                 "kernel_time_share.paged_attention"):
        assert name not in got


def test_the_probes_controls_each_read_not_correct(capsys):
    """``probe_kda_gqa.py`` at the tiny preset in float32: the sound run is
    correct and every control is not."""
    from chipbench import probe_kda_gqa

    # (the GQA layer and one linear layer: every kind of layer once, a
    # quarter of the programs to compile for each of the six runs)
    assert probe_kda_gqa.main(["--seeds", "3", "--rehearse", "--layers", "2"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["control"] for x in lines] == ["sound", *probe_kda_gqa.CONTROLS]
    assert [x["ok"] for x in lines] == [True] + [False] * len(probe_kda_gqa.CONTROLS)
