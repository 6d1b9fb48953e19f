"""What PR 28 added to the benchmark, rehearsed on the CPU: the cell
``sdar-30b-a3b.blockgen`` (configuration, mix, reference, readers, cost
functions) and that nothing the benchmark had was touched. No chip, no
child process; nothing here is a measurement.
"""

import json
import os
import types

import accepted_entries
import pytest

from chipbench import block_counters, costs, probe_block_diffusion, run, traffic
from chipbench import costs_block_diffusion as costs_bd

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "sdar-30b-a3b.blockgen"
NEW_METRICS = ["tokens_per_forward_mean", "forwards_per_block_mean",
               "commit_forward_share", "denoise_step_roofline",
               "kernel_time_share.block_attention", "block_attention_roofline"]


#: what the benchmark held when PR 28 began, by name (``BENCHMARK.json`` at
#: PR 27, less ``decode_step_roofline``, which PR 55 retired): nothing here
#: says where in its list an entry stands
ACCEPTED = {
    "configs": "qwen3-32b qwen3-30b-a3b",
    "workloads": "qwen3-32b.sessions qwen3-30b-a3b.reasoning",
    "end_to_end": "ttft_ms_p50 itl_ms_p50 out_tokens_per_s setup_s",
    "per_layer": """
        score_ms_p50 prefix_hit_share prefix_hit_share.bypass pool_cached_share
        pod_ttft_ms_p50 ttft_ms_p95 lanes_busy_mean prefill_rows_mean step_ms_mean
        compiles_in_window.serve compiles_in_window.decode
        kernel_time_share.paged_attention kernel_time_share.flash_prefill
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
        queue_wait_ms_p50 staged_wait_ms_p50 decode_rows_mean
        sampled_dispatch_share""",
}


def test_old_entries_are_as_they_were():
    """The benchmark PR 27 left (2 configurations, 2 cells, 4 end-to-end and
    50 per-layer metrics, command, paths, run_seconds), as they stood: each
    accepted entry is looked up by its NAME and held without its
    ``workloads`` list (``accepted_entries.py``), never by its place, so the
    cells and entries every later PR appended do not falsify this. The new
    entries exist, once each, and list the new cell."""
    assert accepted_entries.digest(BENCH, ACCEPTED) == "0a128620931e470d7a72a288f5d85b57929c416a2037a0ac19b2bd71af19a6ad"
    # ... less ``decode_step_roofline``, which PR 55 retired
    assert accepted_entries.count(ACCEPTED) == 2 + 2 + 4 + 49
    assert "sdar-30b-a3b" in [c["name"] for c in BENCH["configs"]]
    assert CELL in [w["name"] for w in BENCH["workloads"]]
    names = [m["name"] for m in BENCH["per_layer"]]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        assert names.count(name) == 1 and CELL in by_name[name]["workloads"]


def test_the_accepted_cells_mixes_still_state_no_request():
    """What ``test_the_cells_own_mixes_state_no_request`` held, for the cells
    it was written for; the mixes are looked up by name in the cells that
    run them, whatever cells came later."""
    by_cell = {w["name"]: w["traffic"] for w in BENCH["workloads"]}
    assert (by_cell["qwen3-32b.sessions"], by_cell["qwen3-30b-a3b.reasoning"],
            by_cell[CELL]) == ("sessions", "reasoning", "blockgen")
    for name in ("sessions", "reasoning"):
        spec = traffic.load_traffic(name)
        assert "request" not in spec and "request_share" not in spec
        assert traffic.request_params(spec, 3, 6) == [None] * 3


def test_costs_of_the_new_configuration_against_hand_sums():
    """``test_costs_against_hand_sums``' row for ``sdar-30b-a3b``: the sums
    of ``qwen3-30b-a3b``, whose widths it has."""
    cfg = run.model_config(run.load_config("sdar-30b-a3b"), rehearse=False)
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    ffn = 128 * 3 * 2048 * 768 + 2048 * 128
    assert costs.attn_params_per_layer(cfg) == attn
    assert costs.ffn_params_per_layer(cfg) == ffn
    assert costs.ffn_params_touched_per_token(cfg) == 8 * 3 * 2048 * 768 + 2048 * 128
    assert costs.kv_bytes_per_token(cfg) == 2 * 8 * 4 * 128 * 2
    assert costs.resident_weight_bytes(cfg) == 2 * (
        2 * 151936 * 2048 + 8 * (attn + ffn))


def test_the_entries_the_issue_names():
    config = next(c for c in BENCH["configs"] if c["name"] == "sdar-30b-a3b")
    assert config["source"] == ("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/"
                                "blob/main/config.json")
    assert config["reduced"] == ["num_hidden_layers"]
    cell = run.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b", "blockgen", 1)
    e2e = {m["name"] for m in run.metrics_of_cell(BENCH["end_to_end"], CELL)}
    assert e2e == {"itl_ms_p50", "out_tokens_per_s", "setup_s"}
    layers = {m["name"]: m["layer"] for m in BENCH["per_layer"]}
    assert [layers[n] for n in NEW_METRICS] == [
        "engine step", "scheduler", "engine step", "model step", "kernels", "kernels"]
    # the list-less metrics apply to the new cell as they are
    listless = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert listless == {"lanes_busy_mean", "step_ms_mean", "device_idle_share",
                        "kernel_time_share.paged_attention", "peak_hbm_gib"}


def test_the_configuration_is_the_catalog_row_cut_in_depth():
    """Every number of the published config under its key, depth alone cut;
    the program's preset agrees width for width, ``block_length`` among them."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936,
    }
    config = run.load_config("sdar-30b-a3b")
    pub = config["published"]
    differs = {k for k, v in published.items() if pub.get(k, "missing") != v}
    assert differs == {"num_hidden_layers"} == set(pub["reduced"])
    assert config["widths"] == {"block_length": "block_length"}
    cfg = run.model_config(config, rehearse=False)
    assert (cfg.block_length, cfg.mask_token_id, cfg.n_layers) == (4, 151669, 8)
    with pytest.raises(run.BenchFailure, match="block_length"):
        run.model_config({**config, "published": {**pub, "block_length": 8}},
                         rehearse=False)
    assumed = " ".join(pub["assumed"])
    for size in ("block_length 4", "denoising_steps 4", "low_confidence_dynamic",
                 "confidence_threshold 0.9", "mask_token_id 151669"):
        assert size in assumed


def test_the_mix_is_reasonings_sizes_with_a_quarter_at_two_steps():
    mine = traffic.load_traffic("blockgen")
    theirs = traffic.load_traffic("reasoning")
    same = ("kind", "callers_per_lane", "requests", "groups", "unique", "output")
    assert {k: mine[k] for k in same} == {k: theirs[k] for k in same}
    assert mine["sizes_seed"] != theirs["sizes_seed"]
    assert mine["request"] == {"denoising_steps": 2} and mine["request_share"] == 0.25
    sched = traffic.build_schedule(mine, 5, 45.0, pods=1,
                                   pool_tokens_per_pod=4096 * 16, lanes=16)
    carrying = [r for r in sched.requests if r.params]
    assert all(r.params == {"denoising_steps": 2} for r in carrying)
    assert 0.22 < len(carrying) / len(sched.requests) < 0.28
    again = traffic.build_schedule(mine, 6, 45.0, pods=1,
                                   pool_tokens_per_pod=4096 * 16, lanes=16)
    assert [bool(r.params) for r in again.requests] == [
        bool(r.params) for r in sched.requests]  # every seed sends the same


def test_cost_functions_against_hand_counts():
    cfg = types.SimpleNamespace(
        hidden_size=8, head_dim=4, hd=4, n_heads=2, n_kv_heads=1, n_layers=3,
        vocab_size=50, n_experts=4, n_experts_per_tok=2, moe_inter=6,
        intermediate_size=16, block_length=4, dtype="bfloat16",
        tie_word_embeddings=False)
    # keys and values of one token: 2 x 3 layers x 1 head x 4 x 2 bytes = 48
    assert costs.kv_bytes_per_token(cfg) == 48
    assert costs_bd.block_context_bytes(cfg, lanes=2, mean_context_tokens=10) == 960
    attn = 8 * 2 * 4 + 2 * 8 * 1 * 4 + 2 * 4 * 8  # wq, wk + wv, wo = 192
    ffn = 2.5 * 3 * 8 * 6 + 8 * 4  # the 2.5 experts a layer counted + router
    head_and_rows = 50 * 8 + 8 * 8  # the head + one embedding row a row
    want = 2 * (3 * (attn + ffn) + head_and_rows) + 960
    assert costs_bd.denoise_forward_min_bytes(cfg, 2, 10, 2.5) == pytest.approx(want)
    # a dense FFN is read whole, whatever was counted
    dense = types.SimpleNamespace(**{**vars(cfg), "n_experts": 0})
    want = 2 * (3 * (attn + 3 * 8 * 16) + head_and_rows) + 960
    assert costs_bd.denoise_forward_min_bytes(dense, 2, 10, None) == pytest.approx(want)


def test_full_size_bytes_of_a_forward():
    """At the cell's size independent draws would read nearly every expert
    (about 10.8 GB, 13 ms at 819 GB/s); the rows choose about 72 a layer
    (counted on the chip, PR 28): 6.6 GB, 8 ms, what
    ``denoise_step_roofline`` divides by."""
    cfg = run.model_config(run.load_config("sdar-30b-a3b"), rehearse=False)
    independent = costs.expected_experts_touched(cfg, 64)
    assert 125 < independent < 128
    assert 10.5e9 < costs_bd.denoise_forward_min_bytes(cfg, 16, 700, independent) < 11.1e9
    counted = costs_bd.denoise_forward_min_bytes(cfg, 16, 700, 72.0)
    assert 6.4e9 < counted < 6.8e9
    # an expert is three matrices of 2048 x 768 in bf16, in each of 8 layers
    assert costs_bd.denoise_forward_min_bytes(cfg, 16, 700, 73.0) - counted == (
        pytest.approx(8 * 3 * 2048 * 768 * 2))
    assert costs_bd.block_context_bytes(cfg, 16, 700) == 16 * 700 * 16384


def test_the_reference_states_its_limits():
    """As PR 28's readings set them (the file's docstring): looser than the
    sound runs' largest and tighter than every control's smallest."""
    from chipbench import reference

    ref = reference.load("moe_block_diffusion")
    assert ref.TOL_BF16 == {"max": 0.22, "p50": 0.15, "layer_p75": 1.3e-2}
    assert ref.ROUTER_GAP_MIN == reference.load("moe").ROUTER_GAP_MIN
    assert (ref.DENOISING_STEPS, ref.CONFIDENCE_THRESHOLD) == (4, 0.9)
    assert callable(ref.system) and "olerance" in ref.__doc__


def _records(after, before=None, **kw):
    before = before or dict.fromkeys(after, 0)
    return types.SimpleNamespace(step_after=[after], step_before=[before], **kw)


@pytest.mark.parametrize("name,want", [
    ("tokens_per_forward_mean", 36 / 50), ("forwards_per_block_mean", 50 / 10),
    ("commit_forward_share", 20.0)])
def test_counter_readers_on_hand_made_records(name, want):
    read = run.load_layer_metric(name)
    counted = {"denoise_lane_forwards": 40, "commit_lane_forwards": 10,
               "block_tokens_fixed": 36, "blocks_final": 10}
    assert read(_records(counted)) == pytest.approx(want)
    twice = {k: 2 * v + 7 for k, v in counted.items()}
    assert read(_records(twice, {k: v + 7 for k, v in counted.items()})) == (
        pytest.approx(want))
    # a program that does not count them, or a window without a dispatch
    assert read(_records({"steps": 3})) is None
    assert read(_records(dict.fromkeys(block_counters.KEYS, 5),
                         dict.fromkeys(block_counters.KEYS, 5))) is None


def test_trace_readers_on_a_made_up_trace():
    cfg = run.model_config(run.load_config("sdar-30b-a3b"), rehearse=False)
    trace = {"busy_s": 2.0, "window_s": 2.5,
             "ops": {"%block_attention.3 = bf16[16,4,8,8,128]": 0.2, "%fusion.1": 1.0},
             "ops_text": {}, "modules": {"jit_denoise_steps": 1.6},
             "module_calls": {"jit_denoise_steps": 80.0}}
    records = types.SimpleNamespace(
        trace=trace, model_cfg=cfg, peaks={"hbm_bytes_per_s": 819e9}, lanes=16,
        good=[{"prompt_len": 300, "max_tokens": 800}], running_samples=[[16], [16]],
        # 80 dispatches whose rows chose 96 experts a layer, in 8 layers
        step_before=[{"experts_touched": 5, "decode_dispatches": 20}],
        step_after=[{"experts_touched": 5 + 80 * 8 * 96, "decode_dispatches": 100}])
    assert run.load_layer_metric("kernel_time_share.block_attention")(records) == (
        pytest.approx(10.0))
    assert block_counters.experts_touched_per_layer(records) == pytest.approx(96.0)
    least = costs_bd.denoise_forward_min_bytes(cfg, 16, 700, 96.0) / 819e9
    roofline = run.load_layer_metric("denoise_step_roofline")
    assert roofline(records) == pytest.approx(100 * least / 0.02)
    # a program that does not count the experts gives nothing, not a bound
    counted = records.step_after
    records.step_after = [{"decode_dispatches": 100}]
    assert roofline(records) is None
    assert block_counters.experts_touched_per_layer(records) is None
    records.step_after = counted
    least = 16 * 700 * 16384 / 819e9
    assert run.load_layer_metric("block_attention_roofline")(records) == (
        pytest.approx(100 * least / (0.2 / 80)))
    # nothing to read: another program's trace, no trace, another model
    for blind in (dict(trace, modules={}, module_calls={}), None):
        records.trace = blind
        assert run.load_layer_metric("denoise_step_roofline")(records) is None
        assert run.load_layer_metric("block_attention_roofline")(records) is None
    records.trace, records.model_cfg = trace, types.SimpleNamespace()
    assert run.load_layer_metric("denoise_step_roofline")(records) is None


def test_the_cell_rehearses_to_correct(capsys):
    argv = ["--workload", CELL, "--seed", str(2**31 + 29), "--seconds", "3",
            "--trace", "1", "--rehearse"]
    assert run.main(argv) == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 3
    assert line["reference"]["ok"] and line["reference"]["rel_err"] < 2e-4
    want = run.metrics_of_cell(BENCH["per_layer"], CELL)
    readable = {m["name"] for m in want if m["source"] != "device_trace"}
    assert set(line["metrics"]) == readable - {"peak_hbm_gib"}
    assert {"tokens_per_forward_mean", "forwards_per_block_mean",
            "commit_forward_share", "lanes_busy_mean", "step_ms_mean"} <= readable
    value = {k: v["value"] for k, v in line["metrics"].items()}
    # four-step requests cost a block five forwards, two-step ones three
    assert 3.0 < value["forwards_per_block_mean"] < 5.0
    assert 100 / 5 < value["commit_forward_share"] < 100 / 3
    assert 0.7 < value["tokens_per_forward_mean"] < 4 / 3
    assert any("0 compilations inside" in l for l in out)


@pytest.mark.parametrize("argv,ok", [
    ([], True), (["--mask", "causal"], False), (["--order", "1,1"], False)])
def test_the_reference_check_holds_the_mask_and_the_layers(argv, ok, capsys):
    """``probe_block_diffusion.py``, rehearsed at the tiny f32 preset: the
    check passes the program, and fails the causal program and a layer used
    twice."""
    assert probe_block_diffusion.main(
        argv + ["--config", "sdar-30b-a3b", "--seeds", "5", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["ok"] is ok and out["config"] == "sdar-30b-a3b"
    if ok:
        assert out["rel_err"] < 2e-4 and out["layer_rel_err_p75"] < 2e-4
