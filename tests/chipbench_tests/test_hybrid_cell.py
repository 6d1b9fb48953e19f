"""What PR 34 added to the benchmark, rehearsed on the CPU: the cell
``lfm2-8b-a1b.agentloop`` (configuration, mix, reference with its own system
side, readers, cost functions) and that nothing the benchmark had was
touched. No chip, no child process; nothing here is a measurement.
"""

import json
import os
import subprocess
import types

import accepted_entries
import pytest

from chipbench import costs, costs_hybrid, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = "lfm2-8b-a1b"
CELL = "lfm2-8b-a1b.agentloop"
NEW_METRICS = ["cache_bytes_per_token.kv", "cache_bytes_per_token.state",
               "prefix_hit_share.agentloop", "hybrid_decode_step_roofline"]

#: what the benchmark held when PR 34 began, by name (``BENCHMARK.json`` at
#: PR 33): ``test_latent_cell.ACCEPTED`` and what PRs 32 and 33 appended
ACCEPTED = {
    "configs": "qwen3-32b qwen3-30b-a3b sdar-30b-a3b kanana-2-30b-a3b",
    "workloads": "qwen3-32b.sessions qwen3-30b-a3b.reasoning "
                 "sdar-30b-a3b.blockgen kanana-2-30b-a3b.docqa",
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
        kernel_time_share.mla_prefill latent_bytes_per_token
        prefix_hit_share.docqa chained_dispatch_share""",
}


def test_accepted_entries_are_as_they_were():
    """The benchmark PR 33 left (4 configurations, 4 cells, 4 end-to-end and
    62 per-layer metrics, command, paths, run_seconds), as they stood: each
    accepted entry is looked up by its name, so an entry that a later PR
    appends, wherever it stands, does not falsify this, and without its
    ``workloads`` list, which a ``benchmark`` PR may widen
    (``accepted_entries.py``)."""
    assert accepted_entries.digest(BENCH, ACCEPTED) == "3a99c1218827ba99d8611a96090901099b5786a17601b8bbc746704592eac73e"
    # ... less ``decode_step_roofline``, which PR 55 retired
    assert accepted_entries.count(ACCEPTED) == 4 + 4 + 4 + 61


def test_no_file_the_benchmark_had_is_edited():
    """Every file under the benchmark's ``paths`` that the last ``benchmark``
    PR's commit holds is, byte for byte, what it held: every other PR only
    adds files there. The commit is found by its subject (``PR n: [benchmark]
    ...``), so a ``benchmark`` PR re-anchors this by being committed; while
    one is under way (``ISSUE.md``'s heading names the kind) it may edit
    and nothing is held. Skipped where the checkout is no git repository
    (the chip's copy)."""
    with open(os.path.join(ROOT, "ISSUE.md")) as f:
        if "[benchmark]" in f.readline():
            return
    try:
        base = subprocess.run(
            ["git", "log", "-1", "--format=%H", "--grep=^PR [0-9]*: \\[benchmark\\]"],
            cwd=ROOT, capture_output=True, text=True, check=True,
        ).stdout.strip()
        changed = subprocess.run(
            ["git", "diff", "--name-status", base, "--", *BENCH["paths"]],
            cwd=ROOT, capture_output=True, text=True, check=True,
        ).stdout.split("\n")
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("not a git checkout that holds a benchmark PR's commit")
    assert [line for line in changed if line and not line.startswith("A")] == []


def test_this_prs_entries_list_the_new_cell_alone():
    """They exist and list the cell; nothing here says that nobody else
    does (a ``benchmark`` PR lists a cell a reader works in)."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        assert CELL in by_name[name]["workloads"]
    assert len(run.find_cell(BENCH, CELL)["why"]) <= 200  # the contract's
    assert len(next(c for c in BENCH["configs"] if c["name"] == CONFIG)["why"]) <= 200


def test_the_entries_the_issue_names():
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert config["reduced"] == ["num_hidden_layers"]
    cell = run.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "agentloop", 1)
    e2e = {m["name"] for m in run.metrics_of_cell(BENCH["end_to_end"], CELL)}
    assert e2e == {"itl_ms_p50", "out_tokens_per_s", "setup_s"}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [by_name[n]["layer"] for n in NEW_METRICS] == [
        "block manager", "block manager", "block manager", "model step"]
    assert [by_name[n]["moves"] for n in NEW_METRICS] == [
        "out_tokens_per_s", "out_tokens_per_s", "out_tokens_per_s", "itl_ms_p50"]
    for name in NEW_METRICS:  # each has a reader, by file or by family
        assert callable(run.load_layer_metric(name))
    # every accepted metric with no list of cells is reported here too, by
    # a reader that needs nothing of this model
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert everywhere == {"lanes_busy_mean", "step_ms_mean", "device_idle_share",
                          "kernel_time_share.paged_attention", "peak_hbm_gib"}


def test_the_configuration_is_the_catalog_row_cut_in_depth():
    """Every number of the published config under its key, depth alone cut
    (``layer_types`` whole, as published: the 14 layers run are its first
    14); the program's preset agrees width for width, the operator kinds,
    the dense layers, the filter and the router among them."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                        "full_attention", "conv", "conv", "conv", "full_attention",
                        "conv", "conv", "conv", "full_attention", "conv", "conv",
                        "conv", "full_attention", "conv", "conv", "full_attention",
                        "conv", "conv"],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 24,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    }
    config = run.load_config(CONFIG)
    pub = config["published"]
    differs = {k for k, v in published.items() if pub.get(k, "missing") != v}
    assert differs == {"num_hidden_layers"} == set(pub["reduced"])
    assert pub["num_hidden_layers"] == 14
    assert (pub["head_dim"], pub["rms_norm_eps"]) == (64, pub["norm_eps"])  # restated
    assert set(config["widths"]) == {
        "layer_types", "num_dense_layers", "conv_L_cache", "conv_bias",
        "use_expert_bias", "routed_scaling_factor", "scoring_func",
        "tie_word_embeddings"}
    assert config["replace"] == {"n_layers": 14}
    cfg = run.model_config(config, rehearse=False)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.kv_row_shape) == (14, 2, (4, 128))
    assert (cfg.n_conv_layers, cfg.n_attn_layers, cfg.router_norm_eps) == (11, 3, 1e-6)
    for key, moved in (("conv_L_cache", 4), ("scoring_func", "softmax"),
                       ("num_dense_layers", 1), ("conv_bias", True),
                       ("layer_types", pub["layer_types"][::-1]),
                       ("head_dim", 128), ("tie_word_embeddings", False)):
        with pytest.raises(run.BenchFailure, match=key):
            run.model_config({**config, "published": {**pub, key: moved}},
                             rehearse=False)
    assert config["env"] == {"BLOCK_SIZE": 16, "TOTAL_PAGES": 16384,
                             "MAX_MODEL_LEN": 16384, "DECODE_BATCH_SIZE": 32}


def test_the_mix_is_the_issues_letter_for_letter():
    spec = traffic.load_traffic("agentloop")
    assert {k: spec[k] for k in ("kind", "callers_per_lane", "requests",
                                 "fill_piece_tokens", "fill_tail_tokens")} == {
        "kind": "closed", "callers_per_lane": 2, "requests": 2048,
        "fill_piece_tokens": 1024, "fill_tail_tokens": 16}
    assert spec["groups"] == {"prefix_tokens": [2048, 4096, 8192],
                              "pool_share": 0.5, "zipf_s": 1.0}
    assert spec["unique"] == {"dist": "lognormal", "median": 96, "sigma": 0.7,
                              "min": 32, "max": 384}
    assert spec["output"] == {"dist": "lognormal", "median": 128, "sigma": 0.6,
                              "min": 32, "max": 512}
    assert "request" not in spec and spec["who"]
    others = {traffic.load_traffic(n)["sizes_seed"]
              for n in ("sessions", "reasoning", "blockgen", "docqa")}
    assert spec["sizes_seed"] not in others
    sched = traffic.build_schedule(spec, 5, 45.0, pods=1,
                                   pool_tokens_per_pod=16384 * 16, lanes=32)
    # nine rounds of the three lengths: 27 prefixes, resident after set-up
    assert [len(p) for p in sched.prefixes] == [2048, 4096, 8192] * 9
    assert sum(len(p) for p in sched.prefixes) == 129024
    assert sched.callers == 64 and len(sched.requests) == 2048
    assert all(r.group is not None and r.prompt_len <= 8192 + 384
               for r in sched.requests)
    cached = sum(r.prefix_len for r in sched.requests)
    assert cached / sum(r.prompt_len for r in sched.requests) > 0.9
    rounds = traffic.fill_plan(sched, spec, 5)
    assert [len(r) for r in rounds] == [27, 27, 18, 18, 9, 9, 9, 9]
    # what the cell compiles at its pinned buckets: 3 turn shapes, 3 decode
    # widths (the fill's 2 shapes are the set-up's): 8 model programs
    engine = run.load_config(CONFIG)["engine"]
    buckets = traffic.Buckets(page=16, max_pages=1024, **engine)
    prefill, decode = traffic.shape_set(sched.requests, buckets)
    assert prefill == {(c, 512) for c in (128, 256, 384)}
    assert decode == {256, 384, 640}
    fills = {buckets.prefill_shape(len(p), len(p) - 1040)
             for r in rounds for _, p in r}
    assert fills == {(1152, 0), (1152, 512)}


def test_cost_functions_against_hand_sums():
    """ISSUE 34's arithmetic for the 14-layer cut: 9.33 GB of weights,
    188 416 B a page (6144 + 5632 B a token)."""
    cfg = run.model_config(run.load_config(CONFIG), rehearse=False)
    conv = 4 * 2048 * 2048 + 3 * 2048
    attn = 2 * 2048 * 32 * 64 + 2 * 2048 * 8 * 64
    assert costs_hybrid.conv_params_per_layer(cfg) == conv
    assert costs.attn_params_per_layer(cfg) == attn == 10_485_760
    assert costs_hybrid.kinds(cfg).count("conv") == 11
    assert costs_hybrid.operator_params(cfg) == 11 * conv + 3 * attn
    dense = 3 * 2048 * 7168
    expert = 32 * 3 * 2048 * 1792 + 2048 * 32 + 32
    assert costs_hybrid.dense_ffn_params(cfg) == dense == 44_040_192
    assert costs_hybrid.expert_ffn_params(cfg) == expert
    params = 65536 * 2048 + 11 * conv + 3 * attn + 2 * dense + 12 * expert
    assert costs_hybrid.resident_params(cfg) == params
    assert costs_hybrid.resident_weight_bytes(cfg) == 2 * params
    assert 9.33e9 < 2 * params < 9.34e9 and round(2 * params / 2**30, 2) == 8.69
    assert costs_hybrid.kv_bytes_per_token(cfg) == 3 * 2 * 8 * 64 * 2 == 6144
    assert costs_hybrid.state_bytes_per_page(cfg) == 11 * 8192
    assert costs_hybrid.state_bytes_per_token(cfg, 16) == 5632
    assert costs_hybrid.page_bytes(cfg, 16) == 188_416
    assert 16384 * costs_hybrid.page_bytes(cfg, 16) / 2**30 == 2.875
    # the published model, whole: 8.34 B parameters
    whole = run.model_config(
        {**run.load_config(CONFIG), "replace": {}}, rehearse=True)
    assert round(costs_hybrid.resident_params(whole) / 1e9, 2) == 8.34
    # a decode step of 32 lanes at 5 000 tokens each
    rows = 32 * 5000
    full = costs_hybrid.decode_step_min_bytes(cfg, 32, rows, experts_touched=32)
    some = costs_hybrid.decode_step_min_bytes(cfg, 32, rows, experts_touched=30)
    assert full - some == 2 * 12 * 2 * 3 * 2048 * 1792
    assert full == 2 * (params + 32 * 2048) + rows * 6144 + 32 * 11 * 8192
    with pytest.raises(TypeError):  # the experts are counted, never assumed
        costs_hybrid.decode_step_min_bytes(cfg, 32, rows)


#: 100 dispatches of one forward: 32 lanes at 5 000 tokens, 25 experts a
#: routed layer a forward (12 routed layers)
COUNTED = {"attn_ctx_tokens": 100 * 32 * 5000, "decode_rows": 3200,
           "decode_dispatches": 100, "decode_forwards": 100,
           "experts_touched": 100 * 12 * 25}


def records(**kw):
    base = dict(
        cell=run.find_cell(BENCH, CELL), good=[], failed=[], in_flight=[],
        in_flight_tokens=0, late_s=[], window_s=10.0, stats_before=[{}],
        running_samples=[], lanes=32, page=16, pods=[object()],
        stats_after=[{"kv_bytes_per_token": 6144, "state_bytes_per_token": 5632,
                      "routed_layers": 12}],
        step_before=[dict.fromkeys(COUNTED, 0)], step_after=[COUNTED],
        compiles_in_window=0, memory_peak_bytes=0,
        model_cfg=run.model_config(run.load_config(CONFIG), rehearse=False),
        peaks=costs.load_peaks("TPU v5 lite"),
        trace={"busy_s": 3.0, "window_s": 4.0, "ops": {}, "ops_text": {},
               "module_calls": {"jit_decode_steps(1)": 100},
               "modules": {"jit_decode_steps(1)": 1.5}},
    )
    base.update(kw)
    return run.RunRecords(**base)


def test_readers_on_hand_made_records():
    read = {name: run.load_layer_metric(name) for name in NEW_METRICS}
    r = records()
    assert read["cache_bytes_per_token.kv"](r) == 6144
    assert read["cache_bytes_per_token.state"](r) == 5632
    least_s = costs_hybrid.decode_step_min_bytes(
        r.model_cfg, 32, 32 * 5000, experts_touched=25) / 819e9
    assert read["hybrid_decode_step_roofline"](r) == pytest.approx(
        100 * least_s / 0.015)
    assert 0 < read["hybrid_decode_step_roofline"](r) < 100
    # a program from before the counters (the parent), a run with no trace,
    # a pod that reports no size and another model: nothing to read, no error
    old = records(step_before=[{"decode_dispatches": 0, "decode_rows": 0}],
                  step_after=[{"decode_dispatches": 100, "decode_rows": 3200}],
                  stats_after=[{"kv_bytes_per_token": 6144, "routed_layers": 12}])
    assert read["hybrid_decode_step_roofline"](old) is None
    assert read["cache_bytes_per_token.state"](old) is None
    assert read["cache_bytes_per_token.kv"](old) == 6144
    assert read["hybrid_decode_step_roofline"](records(trace=None)) is None
    other = records(model_cfg=types.SimpleNamespace(layer_types=None))
    assert read["hybrid_decode_step_roofline"](other) is None


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "taps-reversed"])
def test_the_cell_rehearses(broken, monkeypatch, capsys):
    """The whole run on the CPU at the tiny preset: ``correct`` true; with
    the filter's taps reversed on the program's side (the reference and the
    weights stay) it is false."""
    if broken:
        from llm_d_kv_cache_manager_tpu.models import llama

        def reversed_taps(layer, *a, **kw):
            return kept({**layer, "conv_w": layer["conv_w"][::-1]}, *a, **kw)

        kept = llama._conv_operator
        for jitted in (llama.prefill, llama.decode_step, llama.decode_steps):
            jitted.clear_cache()
        monkeypatch.setattr(llama, "_conv_operator", reversed_taps)
    try:
        assert run.main(["--workload", CELL, "--seed", str(2**31 + 34),
                         "--seconds", "2", "--trace", "1", "--rehearse"]) == 0
    finally:
        if broken:
            monkeypatch.undo()
            for jitted in (llama.prefill, llama.decode_step, llama.decode_steps):
                jitted.clear_cache()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["correct"] is (not broken)
    assert line["reference"]["ok"] is (not broken)
    if not broken:
        got = line["metrics"]
        # tiny, f32: 2 attention layers x 2 x 4 heads x 64; 6 conv layers x
        # 2 rows x 64 over a page of 4
        assert got["cache_bytes_per_token.kv"]["value"] == 2 * 2 * 4 * 64 * 4
        assert got["cache_bytes_per_token.state"]["value"] == 6 * 2 * 64 * 4 // 4
        assert got["prefix_hit_share.agentloop"]["value"] > 50
        assert {"lanes_busy_mean", "step_ms_mean"} <= set(got)
        assert "hybrid_decode_step_roofline" not in got  # no device number off the chip


def test_the_probe_rehearses(capsys):
    """``probe_conv_moe.py`` at the tiny preset: the sound run reads correct
    and every control not correct."""
    from chipbench import probe_conv_moe

    assert probe_conv_moe.main(["--seeds", "3", "--rehearse"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()
             if x.startswith("{")]
    assert [x["control"] for x in lines] == ["sound", *probe_conv_moe.CONTROLS]
    assert [x["ok"] for x in lines] == [True] + [False] * 5
