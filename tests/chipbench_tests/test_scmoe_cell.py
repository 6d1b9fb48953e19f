"""What PR 41 added to the benchmark, rehearsed on the CPU: the cell
``longcat-flash-omni.turns`` (configuration, mix, reference, readers, cost
functions, probe) and that nothing the benchmark had was touched. No chip, no
child process; nothing here is a measurement.
"""

import json
import os
import types

import accepted_entries
import pytest

from chipbench import costs, costs_scmoe, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = "longcat-flash-omni"
CELL = "longcat-flash-omni.turns"
NEW_METRICS = ["zero_place_share", "held_experts_touched_share",
               "held_rows_mean", "scmoe_decode_step_roofline",
               "decode_scope_ms.moe_zero"]

#: what the benchmark held when PR 41 began, by name (``BENCHMARK.json`` at
#: PR 40): nothing here says where in its list an entry stands
ACCEPTED = {
    "configs": "qwen3-32b qwen3-30b-a3b sdar-30b-a3b kanana-2-30b-a3b lfm2-8b-a1b",
    "workloads": """qwen3-32b.sessions qwen3-30b-a3b.reasoning
        sdar-30b-a3b.blockgen kanana-2-30b-a3b.docqa lfm2-8b-a1b.agentloop""",
    "end_to_end": "ttft_ms_p50 itl_ms_p50 out_tokens_per_s setup_s",
    "per_layer": """
        score_ms_p50 prefix_hit_share prefix_hit_share.bypass pool_cached_share
        pod_ttft_ms_p50 ttft_ms_p95 lanes_busy_mean prefill_rows_mean step_ms_mean
        compiles_in_window.serve compiles_in_window.decode kernel_time_share.paged_attention kernel_time_share.flash_prefill
        kernel_time_share.gmm device_idle_share peak_hbm_gib loadgen_late_ms_p95
        step_phase_ms.schedule step_phase_ms.decode_build step_phase_ms.decode_put
        step_phase_ms.decode_dispatch step_phase_ms.decode_fetch
        step_phase_ms.decode_commit step_phase_ms.publish step_phase_ms.loop
        step_phase_ms.prefill_build step_phase_ms.prefill_put
        step_phase_ms.prefill_dispatch step_phase_ms.prefill_fetch
        step_phase_ms.prefill_commit step_phase_ms.prefill idle_gap_share.schedule
        idle_gap_share.prefill_build idle_gap_share.prefill_put
        idle_gap_share.prefill_dispatch idle_gap_share.prefill_fetch
        idle_gap_share.prefill_commit idle_gap_share.decode_build
        idle_gap_share.decode_put idle_gap_share.decode_dispatch
        idle_gap_share.decode_fetch idle_gap_share.decode_commit
        idle_gap_share.publish idle_gap_share.loop idle_gap_share.unattributed
        queue_wait_ms_p50 staged_wait_ms_p50 decode_rows_mean sampled_dispatch_share
        tokens_per_forward_mean forwards_per_block_mean commit_forward_share
        denoise_step_roofline kernel_time_share.block_attention
        block_attention_roofline kernel_time_share.mla_decode mla_decode_roofline
        kernel_time_share.mla_prefill latent_bytes_per_token prefix_hit_share.docqa
        chained_dispatch_share cache_bytes_per_token.kv cache_bytes_per_token.state
        prefix_hit_share.agentloop hybrid_decode_step_roofline decode_scope_ms.attn
        decode_scope_ms.cache_write decode_scope_ms.head decode_scope_ms.sample
        decode_scope_ms.unscoped decode_scope_ms.ffn decode_scope_ms.moe_router
        decode_scope_ms.moe_experts decode_scope_ms.moe_shared decode_scope_ms.conv
        prefill_scope_ms.attn prefill_scope_ms.ffn prefill_scope_ms.head
        decode_experts_touched_mean counted_decode_step_roofline
        prefill_slot_fill_share prefill_scope_ms.moe_experts
        prefill_scope_ms.moe_router""",
}


def test_accepted_entries_are_as_they_were():
    """The benchmark PR 40 left (5 configurations, 5 cells, 4 end-to-end and
    84 per-layer metrics, command, paths, run_seconds), as they stood: each
    accepted entry is looked up by its name, so an entry that a later PR
    appends, wherever it stands, does not falsify this, and without its
    ``workloads`` list, which a ``benchmark`` PR may widen
    (``accepted_entries.py``)."""
    assert accepted_entries.digest(BENCH, ACCEPTED) == "fc53edea3e9b458ff8acbab1aac56011230b290a4824f6fe7e2eb6209efdad03"
    # ... less ``decode_step_roofline``, which PR 55 retired
    assert accepted_entries.count(ACCEPTED) == 5 + 5 + 4 + 83


def test_this_prs_entries_list_the_new_cell_alone():
    """They exist and list the cell; nothing here says that nobody else
    does, nor where in ``per_layer`` they stand (a ``benchmark`` PR lists a
    cell a reader works in, and later PRs append)."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["layer"] == "model step"
        assert by_name[name]["moves"] == "itl_ms_p50"
        assert callable(run.load_layer_metric(name))  # by file or by family
    cell = run.find_cell(BENCH, CELL)
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "turns", 1)
    assert config["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert config["source"] == ("https://huggingface.co/meituan-longcat/"
                                "LongCat-Flash-Omni/blob/main/config.json")
    e2e = {m["name"] for m in run.metrics_of_cell(BENCH["end_to_end"], CELL)}
    assert e2e == {"itl_ms_p50", "out_tokens_per_s", "setup_s"}
    # the accepted metrics without a list are read in the new cell too (and
    # those a ``benchmark`` PR found to work here: a superset)
    read_here = {m["name"] for m in run.metrics_of_cell(BENCH["per_layer"], CELL)}
    assert read_here >= set(NEW_METRICS) | {
        "lanes_busy_mean", "step_ms_mean", "kernel_time_share.paged_attention",
        "device_idle_share", "peak_hbm_gib"}


def test_the_configuration_is_the_catalog_row_with_three_cuts():
    """Every key of the catalog's row under its own name and value, but the
    three in ``reduced``; the program's preset agrees width for width."""
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12,
    }
    config = run.load_config(CONFIG)
    pub = config["published"]
    differs = {k for k, v in published.items() if pub.get(k, "missing") != v}
    assert differs == {"num_layers", "n_routed_experts", "vocab_size"}
    assert differs == set(pub["reduced"])
    assert (pub["num_layers"], pub["n_routed_experts"], pub["vocab_size"]) == (
        4, 16, 16384)
    # restated for the harness's built-in check, and said so
    assert (pub["num_hidden_layers"], pub["intermediate_size"],
            pub["moe_intermediate_size"], pub["num_experts_per_tok"],
            pub["num_experts"], pub["norm_topk_prob"]) == (
        4, 12288, 2048, 12, 512, False)
    assert "restates" in pub["restated"] and pub["deployment"]
    cfg = run.model_config(config, rehearse=False)
    assert (cfg.n_layers, cfg.n_attn_layers, cfg.experts_held, cfg.expert_first,
            cfg.router_outputs, cfg.kv_row_shape) == (4, 8, 16, 0, 768, (640,))
    # every width is checked against the preset at every run
    for key, moved in (("kv_lora_rank", 256), ("q_lora_rank", 768),
                       ("zero_expert_num", 0), ("n_routed_experts", 32),
                       ("moe_topk", 8), ("mla_scale_kv_lora", False),
                       ("ffn_hidden_size", 6144), ("num_experts", 16),
                       ("routed_scaling_factor", 1.0), ("hidden_size", 4096)):
        with pytest.raises(run.BenchFailure, match=key):
            run.model_config({**config, "published": {**pub, key: moved}},
                             rehearse=False)
    assert config["env"] == {"BLOCK_SIZE": 16, "TOTAL_PAGES": 16384,
                             "MAX_MODEL_LEN": 8192, "DECODE_BATCH_SIZE": 64}


def test_the_mix_is_the_issues_letter_for_letter():
    spec = traffic.load_traffic("turns")
    assert {k: spec[k] for k in ("kind", "callers_per_lane", "requests",
                                 "fill_piece_tokens", "fill_tail_tokens")} == {
        "kind": "closed", "callers_per_lane": 2, "requests": 4096,
        "fill_piece_tokens": 1024, "fill_tail_tokens": 16}
    assert spec["groups"] == {"prefix_tokens": [1024, 2048, 4096],
                              "pool_share": 0.4, "zipf_s": 1.0}
    assert spec["unique"] == {"dist": "lognormal", "median": 96, "sigma": 0.7,
                              "min": 32, "max": 384}
    assert spec["output"] == {"dist": "lognormal", "median": 192, "sigma": 0.6,
                              "min": 64, "max": 768}
    assert "request" not in spec and spec["who"]
    others = {traffic.load_traffic(n)["sizes_seed"] for n in (
        "sessions", "reasoning", "blockgen", "docqa", "agentloop")}
    assert spec["sizes_seed"] not in others
    sched = traffic.build_schedule(spec, 5, 45.0, pods=1,
                                   pool_tokens_per_pod=16384 * 16, lanes=64)
    # 14 rounds of the three lengths: 42 histories, 100352 tokens resident
    assert [len(p) for p in sched.prefixes] == [1024, 2048, 4096] * 14
    assert sum(len(p) for p in sched.prefixes) == 100352
    assert sched.callers == 128 and len(sched.requests) == 4096
    assert all(r.group is not None and r.prompt_len <= 4096 + 384
               for r in sched.requests)
    # nothing is evicted: the histories and 64 lanes' longest sequences
    assert 100352 + 64 * (384 + 768 + 16) <= 0.67 * 16384 * 16
    rounds = traffic.fill_plan(sched, spec, 5)
    assert len(rounds) == 4 and len(rounds[0]) == 42 and len(rounds[-1]) == 14
    # what the cell compiles at its pinned buckets: 6 turn shapes, 3 decode
    # widths (the fill's 3 shapes are the set-up's)
    buckets = traffic.Buckets(page=16, prefill_bucket=128, prefill_ctx_bucket=128,
                              decode_pages_bucket=128, max_pages=512)
    prefill, decode = traffic.shape_set(sched.requests, buckets)
    assert prefill == {(c, w) for c in (128, 256, 384) for w in (128, 256)}
    assert decode == {128, 256, 384}


def test_cost_functions_against_hand_sums():
    cfg = run.model_config(run.load_config(CONFIG), rehearse=False)
    attn = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
            + 64 * 128 * 6144)
    assert costs_scmoe.attention_params(cfg) == attn == 90_570_752
    dense, expert = 3 * 6144 * 12288, 3 * 6144 * 2048
    assert costs_scmoe.dense_ffn_params(cfg) == dense == 226_492_416
    assert costs_scmoe.expert_params(cfg) == expert == 37_748_736
    router = 6144 * 768 + 768
    layer = 2 * attn + 2 * dense + router + 16 * expert
    assert costs_scmoe.layer_params(cfg, 16) == layer
    head = 16384 * 6144
    assert costs_scmoe.resident_weight_bytes(cfg) == 2 * (2 * head + 4 * layer)
    assert 10.3e9 < costs_scmoe.resident_weight_bytes(cfg) < 10.4e9  # 9.64 GiB
    # a token's rows: 576 values held in 640, two attentions a layer
    assert costs_scmoe.latent_bytes_per_token(cfg) == 8 * 1280 == 10240
    # a decode step of 64 lanes at 2700 tokens each, 10 held experts read,
    # 16 rows in the grouped matmuls
    rows = 64 * 2700
    want = (2 * (4 * (2 * attn + 2 * dense + router + 10 * expert)
                 + head + 64 * 6144) + rows * 10240)
    assert costs_scmoe.decode_step_min_bytes(cfg, 64, rows, 10) == want
    more = costs_scmoe.decode_step_min_bytes(cfg, 64, rows, 16)
    assert more - want == 2 * 4 * 6 * expert
    flops = (2 * 64 * (4 * (2 * attn + 2 * dense + 6144 * 768) + head)
             + 2 * 4 * 16 * expert
             + 8 * rows * 2 * 64 * (576 + 512))
    assert costs_scmoe.decode_step_flops(cfg, 64, rows, 16) == flops
    peaks = costs.load_peaks("TPU v5 lite")
    # bound by the bytes it reads: 10 GB against half a TFLOP
    assert costs_scmoe.decode_step_min_s(cfg, peaks, 64, rows, 10, 16) == (
        want / 819e9)
    assert want / 819e9 > 4 * flops / 197e12
    with pytest.raises(TypeError):  # no count, no cost: nothing is guessed
        costs_scmoe.decode_step_min_bytes(cfg, 64, rows)


def records(**kw):
    forwards, layers = 100, 4
    base = dict(
        cell=run.find_cell(BENCH, CELL), good=[], failed=[], in_flight=[],
        in_flight_tokens=0, late_s=[], window_s=10.0, stats_before=[{}],
        stats_after=[{"routed_layers": layers, "experts_held": 16,
                      "zero_experts": 256}],
        running_samples=[], lanes=64, page=16, pods=[object()],
        step_before=[dict.fromkeys(
            ("experts_touched", "routed_places", "zero_places", "held_places",
             "decode_forwards", "decode_dispatches", "decode_rows",
             "attn_ctx_tokens"), 0)],
        step_after=[{"experts_touched": forwards * layers * 10,
                     "routed_places": forwards * layers * 64 * 12,
                     "zero_places": forwards * layers * 256,
                     "held_places": forwards * layers * 16,
                     "decode_forwards": forwards, "decode_dispatches": forwards,
                     "decode_rows": forwards * 64,
                     "attn_ctx_tokens": forwards * 64 * 2700}],
        compiles_in_window=0, memory_peak_bytes=0,
        model_cfg=run.model_config(run.load_config(CONFIG), rehearse=False),
        peaks=costs.load_peaks("TPU v5 lite"),
        trace={"busy_s": 3.0, "window_s": 4.0, "ops": {}, "ops_text": {},
               "module_calls": {"jit_decode_steps(1)": 100},
               "modules": {"jit_decode_steps(1)": 2.0}},
    )
    base.update(kw)
    return run.RunRecords(**base)


def test_readers_on_hand_made_records():
    read = {name: run.load_layer_metric(name) for name in NEW_METRICS[:4]}
    r = records()
    assert read["zero_place_share"](r) == pytest.approx(100 * 256 / 768)
    assert read["held_experts_touched_share"](r) == pytest.approx(62.5)
    assert read["held_rows_mean"](r) == pytest.approx(16.0)
    least_s = costs_scmoe.decode_step_min_bytes(
        r.model_cfg, 64, 64 * 2700, 10) / 819e9
    assert read["scmoe_decode_step_roofline"](r) == pytest.approx(
        100 * least_s / 0.02)
    assert 50 < read["scmoe_decode_step_roofline"](r) < 70
    # a program from before the counters (the parent), a pod that does not
    # say what it holds, a run with no trace, another model: nothing to
    # read, and no error
    old = records(step_before=[{"decode_dispatches": 0, "experts_touched": 0}],
                  step_after=[{"decode_dispatches": 100, "experts_touched": 9}])
    silent = records(stats_after=[{"routed_layers": 4}])
    for name in NEW_METRICS[:4]:
        assert read[name](old) is None and read[name](silent) is None
    assert read["scmoe_decode_step_roofline"](records(trace=None)) is None
    other = records(model_cfg=types.SimpleNamespace(double_layer=False))
    assert read["scmoe_decode_step_roofline"](other) is None


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "no-zero-experts"])
def test_the_cell_rehearses(broken, monkeypatch, capsys):
    """The whole run on the CPU at the tiny preset: ``correct`` true; with
    the zero experts' term dropped from the program's side (the probe's
    control; the reference and the weights stay) it is false."""
    from chipbench import probe_scmoe
    from llm_d_kv_cache_manager_tpu.models import llama

    programs = (llama.prefill, llama.decode_step, llama.decode_steps,
                llama.prefill_packed)
    undo = lambda: None  # noqa: E731
    if broken:
        for jitted in programs:
            jitted.clear_cache()
        undo = probe_scmoe.steer(llama, "no_zero")
    try:
        assert run.main(["--workload", CELL, "--seed", str(2**31 + 41),
                         "--seconds", "2", "--trace", "1", "--rehearse"]) == 0
    finally:
        undo()
        if broken:
            for jitted in programs:
                jitted.clear_cache()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["correct"] is (not broken)
    assert line["reference"]["ok"] is (not broken)
    if not broken:
        got = line["metrics"]
        assert 15 < got["zero_place_share"]["value"] < 55  # 8 of 24 outputs
        assert 0 < got["held_experts_touched_share"]["value"] <= 100
        assert got["held_rows_mean"]["value"] > 0
        # no device number off the chip
        assert "scmoe_decode_step_roofline" not in got
        assert "decode_scope_ms.moe_zero" not in got


def test_the_probes_controls_each_read_not_correct(capsys):
    """``probe_scmoe.py`` at the tiny preset in float32: the sound run is
    correct and every control is not."""
    from chipbench import probe_scmoe

    assert probe_scmoe.main(["--seeds", "3", "--rehearse"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["control"] for x in lines] == ["sound", *probe_scmoe.CONTROLS]
    assert [x["ok"] for x in lines] == [True] + [False] * len(probe_scmoe.CONTROLS)
