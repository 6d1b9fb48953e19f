"""What PR 32 added to the benchmark, rehearsed on the CPU: the cell
``kanana-2-30b-a3b.docqa`` (configuration, mix, reference with its own system
side, readers, cost functions) and that nothing the benchmark had was
touched. No chip, no child process; nothing here is a measurement.
"""

import json
import os
import types

import accepted_entries
import pytest

from chipbench import costs, costs_mla, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = "kanana-2-30b-a3b"
CELL = "kanana-2-30b-a3b.docqa"
NEW_METRICS = ["kernel_time_share.mla_decode", "mla_decode_roofline",
               "kernel_time_share.mla_prefill", "latent_bytes_per_token",
               "prefix_hit_share.docqa"]


#: what the benchmark held when PR 32 began, by name (``BENCHMARK.json`` at
#: PR 31): nothing here says where in its list an entry stands
ACCEPTED = {
    "configs": "qwen3-32b qwen3-30b-a3b sdar-30b-a3b",
    "workloads": "qwen3-32b.sessions qwen3-30b-a3b.reasoning sdar-30b-a3b.blockgen",
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
        block_attention_roofline""",
}


def test_accepted_entries_are_as_they_were():
    """The benchmark PR 31 left (3 configurations, 3 cells, 4 end-to-end and
    56 per-layer metrics, command, paths, run_seconds), as they stood: each
    accepted entry is looked up by its name, so an entry that a later PR
    appends, wherever it stands, does not falsify this, and without its
    ``workloads`` list, which a ``benchmark`` PR may widen
    (``accepted_entries.py``)."""
    assert accepted_entries.digest(BENCH, ACCEPTED) == "b70ad6061e9e4eb8159e4acf50d4dd8e83b7e42ca6afc978790084a53a169a2c"
    # ... less ``decode_step_roofline``, which PR 55 retired
    assert accepted_entries.count(ACCEPTED) == 3 + 3 + 4 + 55


def test_this_prs_entries_list_the_new_cell_alone():
    """They exist and list the cell; a ``benchmark`` PR lists another cell
    a reader works in beside it (PR 55: the latent readers in ``turns`` and
    ``threads``), so nothing here says that nobody else does."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        assert CELL in by_name[name]["workloads"]
    assert len(run.find_cell(BENCH, CELL)["why"]) <= 200  # the contract's


def test_the_entries_the_issue_names():
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert config["source"] == ("https://huggingface.co/kakaocorp/"
                                "kanana-2-30b-a3b-instruct-2601/blob/main/config.json")
    assert config["reduced"] == ["num_hidden_layers"]
    cell = run.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "docqa", 1)
    e2e = {m["name"] for m in run.metrics_of_cell(BENCH["end_to_end"], CELL)}
    assert e2e == {"itl_ms_p50", "out_tokens_per_s", "setup_s"}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [by_name[n]["layer"] for n in NEW_METRICS] == [
        "kernels", "kernels", "kernels", "block manager", "block manager"]
    assert [by_name[n]["moves"] for n in NEW_METRICS] == [
        "itl_ms_p50", "itl_ms_p50", "itl_ms_p50", "out_tokens_per_s",
        "out_tokens_per_s"]
    for name in NEW_METRICS:  # each has a reader, by file or by family
        assert callable(run.load_layer_metric(name))


def test_the_configuration_is_the_catalog_row_cut_in_depth():
    """Every number of the published config under its key, depth alone cut;
    the program's preset agrees width for width, the latent sizes, the
    router's and the leading dense layer among them."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 1000000, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 128256,
    }
    config = run.load_config(CONFIG)
    pub = config["published"]
    differs = {k for k, v in published.items() if pub.get(k, "missing") != v}
    assert differs == {"num_hidden_layers"} == set(pub["reduced"])
    assert pub["num_experts"] == pub["n_routed_experts"]  # restated, said so
    assert set(config["widths"]) == {
        "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_interleave", "n_routed_experts", "n_shared_experts",
        "first_k_dense_replace", "scoring_func", "routed_scaling_factor",
        "n_group", "topk_group"}
    cfg = run.model_config(config, rehearse=False)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.kv_row_shape) == (8, 1, (640,))
    for key, moved in (("kv_lora_rank", 256), ("scoring_func", "softmax"),
                       ("first_k_dense_replace", 3), ("q_lora_rank", 1536)):
        with pytest.raises(run.BenchFailure, match=key):
            run.model_config({**config, "published": {**pub, key: moved}},
                             rehearse=False)
    assert config["env"] == {"BLOCK_SIZE": 16, "TOTAL_PAGES": 16384,
                             "MAX_MODEL_LEN": 32768, "DECODE_BATCH_SIZE": 32}


def test_the_mix_is_the_issues_letter_for_letter():
    spec = traffic.load_traffic("docqa")
    assert {k: spec[k] for k in ("kind", "callers_per_lane", "requests",
                                 "fill_piece_tokens", "fill_tail_tokens")} == {
        "kind": "closed", "callers_per_lane": 2, "requests": 1024,
        "fill_piece_tokens": 1024, "fill_tail_tokens": 16}
    assert spec["groups"] == {"prefix_tokens": [12288, 20480, 28672],
                              "pool_share": 0.6, "zipf_s": 1.0}
    assert spec["unique"] == {"dist": "lognormal", "median": 96, "sigma": 0.7,
                              "min": 32, "max": 256}
    assert spec["output"] == {"dist": "lognormal", "median": 160, "sigma": 0.6,
                              "min": 64, "max": 512}
    assert "request" not in spec and spec["who"]
    others = {traffic.load_traffic(n)["sizes_seed"]
              for n in ("sessions", "reasoning", "blockgen")}
    assert spec["sizes_seed"] not in others
    sched = traffic.build_schedule(spec, 5, 45.0, pods=1,
                                   pool_tokens_per_pod=16384 * 16, lanes=32)
    # two rounds of the three lengths: six documents, resident after set-up
    assert [len(p) for p in sched.prefixes] == [12288, 20480, 28672] * 2
    assert sched.callers == 64 and len(sched.requests) == 1024
    assert all(r.group is not None and r.prompt_len <= 28672 + 256
               for r in sched.requests)
    rounds = traffic.fill_plan(sched, spec, 5)
    assert len(rounds) == 28 and len(rounds[0]) == 6 and len(rounds[-1]) == 2
    # what the cell compiles at its pinned buckets: 6 question shapes, 3
    # decode widths (the fill's 5 shapes are the set-up's)
    buckets = traffic.Buckets(page=16, prefill_bucket=128, prefill_ctx_bucket=512,
                              decode_pages_bucket=512, max_pages=2048)
    prefill, decode = traffic.shape_set(sched.requests, buckets)
    assert prefill == {(c, w) for c in (128, 256) for w in (1024, 1536, 2048)}
    assert decode == {1024, 1536, 2048}


def test_cost_functions_against_hand_sums():
    cfg = run.model_config(run.load_config(CONFIG), rehearse=False)
    attn = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    assert costs_mla.attn_params_per_layer(cfg) == attn == 26_345_472
    shared = 3 * 2048 * 1536
    expert_layer = attn + 128 * 3 * 2048 * 768 + 2048 * 128 + 128 + shared
    dense_layer = attn + 3 * 2048 * 6144
    assert costs_mla.expert_layer_params(cfg) == expert_layer
    assert costs_mla.dense_layer_params(cfg) == dense_layer
    assert costs_mla.resident_weight_bytes(cfg) == 2 * (
        2 * 128256 * 2048 + dense_layer + 7 * expert_layer)
    assert 10.1e9 < costs_mla.resident_weight_bytes(cfg) < 10.2e9  # 9.44 GiB
    # one row a token a layer: 576 values, held in 640; no second pool
    assert (costs_mla.row_values(cfg), costs_mla.row_values_held(cfg)) == (576, 640)
    assert costs_mla.latent_bytes_per_token(cfg) == 8 * 1280
    assert costs_mla.latent_bytes_per_token(cfg, held=False) == 8 * 1152
    assert costs.kv_bytes_per_token(cfg) != costs_mla.latent_bytes_per_token(cfg)
    # a decode step of 32 lanes at 18 400 tokens each
    rows = 32 * 18400
    assert costs_mla.mla_decode_bytes(cfg, rows) == rows * 10240
    assert costs_mla.mla_decode_flops(cfg, rows) == 8 * rows * 2 * 32 * (576 + 512)
    full = costs_mla.decode_step_min_bytes(cfg, 32, rows, experts_touched=128)
    some = costs_mla.decode_step_min_bytes(cfg, 32, rows, experts_touched=100)
    assert full - some == 2 * 7 * 28 * 3 * 2048 * 768


@pytest.mark.parametrize("config,layers,attentions", [
    ("kanana-2-30b-a3b", 8, 8),    # single layers that all attend
    ("longcat-flash-omni", 4, 8),  # two attentions a published layer
    ("ling-3.0-flash", 7, 1),      # six linear layers and one latent
])
def test_the_latent_costs_count_the_pools_attentions(config, layers, attentions):
    """``mla_decode_roofline``'s bytes and FLOPs are a row in every ATTENTION
    of the pool, not in every layer of the configuration (``turns`` would
    read half, ``threads`` seven times, what the kernel must): the product
    is what ``/stats``' ``kv_bytes_per_token`` reports, which each model's
    own cost file states by hand."""
    from chipbench import costs_kda, costs_scmoe

    cfg = run.model_config(run.load_config(config), rehearse=False)
    assert (cfg.n_layers, costs_mla.n_attentions(cfg)) == (layers, attentions)
    assert costs_mla.latent_bytes_per_token_per_layer(cfg) == 1280
    assert costs_mla.latent_bytes_per_token(cfg) == attentions * 1280
    rows = 64 * 3000
    assert costs_mla.mla_decode_bytes(cfg, rows) == rows * attentions * 1280
    assert costs_mla.mla_decode_flops(cfg, rows) == (
        attentions * rows * 2 * cfg.n_heads * (576 + 512))
    own = {"longcat-flash-omni": costs_scmoe.latent_bytes_per_token,
           "ling-3.0-flash": costs_kda.latent_bytes_per_token}.get(config)
    assert own is None or own(cfg) == costs_mla.latent_bytes_per_token(cfg)
    with pytest.raises(TypeError):  # the experts are counted, never assumed
        costs_mla.decode_step_min_bytes(cfg, 32, rows)


def records(**kw):
    base = dict(
        cell=run.find_cell(BENCH, CELL), good=[], failed=[], in_flight=[],
        in_flight_tokens=0, late_s=[], window_s=10.0, stats_before=[{}],
        stats_after=[{"kv_bytes_per_token": 10240}], running_samples=[],
        lanes=32, page=16, pods=[object()],
        step_before=[{"latent_ctx_tokens": 0, "decode_dispatches": 0}],
        step_after=[{"latent_ctx_tokens": 100 * 32 * 18400,
                     "decode_dispatches": 100}],
        compiles_in_window=0, memory_peak_bytes=0,
        model_cfg=run.model_config(run.load_config(CONFIG), rehearse=False),
        peaks=costs.load_peaks("TPU v5 lite"),
        trace={"busy_s": 3.0, "window_s": 4.0,
               "ops": {"mla_decode.1": 1.6, "mla_prefill.2": 0.3, "fusion.3": 1.1},
               "ops_text": {}, "module_calls": {"jit_decode_steps(1)": 100},
               "modules": {}},
    )
    base.update(kw)
    return run.RunRecords(**base)


def test_readers_on_hand_made_records():
    read = {name: run.load_layer_metric(name) for name in NEW_METRICS}
    r = records()
    assert read["latent_bytes_per_token"](r) == 10240
    assert read["kernel_time_share.mla_decode"](r) == pytest.approx(100 * 1.6 / 3.0)
    assert read["kernel_time_share.mla_prefill"](r) == pytest.approx(10.0)
    # 32 x 18400 rows x 10240 B = 6.03 GB = 7.36 ms at 819 GB/s, over 16 ms
    least_ms = 32 * 18400 * 10240 / 819e9 * 1e3
    assert read["mla_decode_roofline"](r) == pytest.approx(100 * least_ms / 16.0)
    # a program from before the counters (the parent), a run with no trace
    # and a pod that reports no size: nothing to read, and no error
    old = records(step_before=[{"decode_dispatches": 0}],
                  step_after=[{"decode_dispatches": 100}], stats_after=[{}])
    assert read["mla_decode_roofline"](old) is None
    assert read["latent_bytes_per_token"](old) is None
    assert read["mla_decode_roofline"](records(trace=None)) is None
    other = records(model_cfg=types.SimpleNamespace(kv_lora_rank=0))
    assert read["mla_decode_roofline"](other) is None


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "no-shared-expert"])
def test_the_cell_rehearses(broken, monkeypatch, capsys):
    """The whole run on the CPU at the tiny preset: ``correct`` true; with
    the shared experts dropped from the program's side (the reference and
    the weights stay) it is false."""
    if broken:
        from llm_d_kv_cache_manager_tpu.models import llama

        def without(layer, *a, **kw):
            bare = {k: v for k, v in layer.items() if not k.startswith("ws_")}
            return kept(bare, *a, **kw)

        kept = llama._mlp
        for jitted in (llama.prefill, llama.decode_step, llama.decode_steps):
            jitted.clear_cache()
        monkeypatch.setattr(llama, "_mlp", without)
    try:
        assert run.main(["--workload", CELL, "--seed", str(2**31 + 29),
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
        assert got["latent_bytes_per_token"]["value"] == 4 * 128 * 4  # tiny, f32
        assert got["prefix_hit_share.docqa"]["value"] > 50
        assert "mla_decode_roofline" not in got  # no device number off the chip
