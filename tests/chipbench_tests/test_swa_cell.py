"""What PR 43 added to the benchmark, rehearsed on the CPU: the cell
``trinity-large-preview.longdocs`` (configuration, mix, reference, readers,
cost functions, probe) and that nothing the benchmark had was touched. No
chip, no child process; nothing here is a measurement.
"""

import json
import os
import types

import accepted_entries
import pytest

from chipbench import costs, costs_swa, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = "trinity-large-preview"
CELL = "trinity-large-preview.longdocs"
#: name -> (layer, the end-to-end metric it should move)
NEW_METRICS = {
    "prefix_hit_share.longdocs": ("block manager", "out_tokens_per_s"),
    "kernel_time_share.paged_attention_window": ("kernels", "itl_ms_p50"),
    "window_ctx_share": ("model step", "itl_ms_p50"),
    "window_bytes_per_token": ("block manager", "out_tokens_per_s"),
    "window_pool_held_share": ("block manager", "out_tokens_per_s"),
    "window_short_hit_share": ("block manager", "out_tokens_per_s"),
    "swa_decode_step_roofline": ("model step", "itl_ms_p50"),
    "swa_attention_roofline": ("kernels", "itl_ms_p50"),
}

#: what the benchmark held when PR 43 began, by name (``BENCHMARK.json`` at
#: PR 42): nothing here says where in its list an entry stands
ACCEPTED = {
    "configs": """qwen3-32b qwen3-30b-a3b sdar-30b-a3b kanana-2-30b-a3b
        lfm2-8b-a1b longcat-flash-omni""",
    "workloads": """qwen3-32b.sessions qwen3-30b-a3b.reasoning
        sdar-30b-a3b.blockgen kanana-2-30b-a3b.docqa lfm2-8b-a1b.agentloop
        longcat-flash-omni.turns""",
    "end_to_end": "ttft_ms_p50 itl_ms_p50 out_tokens_per_s setup_s",
    "per_layer": """
        score_ms_p50 prefix_hit_share prefix_hit_share.bypass pool_cached_share
        pod_ttft_ms_p50 ttft_ms_p95 lanes_busy_mean prefill_rows_mean
        step_ms_mean compiles_in_window.serve compiles_in_window.decode
        kernel_time_share.paged_attention
        kernel_time_share.flash_prefill kernel_time_share.gmm device_idle_share
        peak_hbm_gib loadgen_late_ms_p95 step_phase_ms.schedule
        step_phase_ms.decode_build step_phase_ms.decode_put
        step_phase_ms.decode_dispatch step_phase_ms.decode_fetch
        step_phase_ms.decode_commit step_phase_ms.publish step_phase_ms.loop
        step_phase_ms.prefill_build step_phase_ms.prefill_put
        step_phase_ms.prefill_dispatch step_phase_ms.prefill_fetch
        step_phase_ms.prefill_commit step_phase_ms.prefill
        idle_gap_share.schedule idle_gap_share.prefill_build
        idle_gap_share.prefill_put idle_gap_share.prefill_dispatch
        idle_gap_share.prefill_fetch idle_gap_share.prefill_commit
        idle_gap_share.decode_build idle_gap_share.decode_put
        idle_gap_share.decode_dispatch idle_gap_share.decode_fetch
        idle_gap_share.decode_commit idle_gap_share.publish idle_gap_share.loop
        idle_gap_share.unattributed queue_wait_ms_p50 staged_wait_ms_p50
        decode_rows_mean sampled_dispatch_share tokens_per_forward_mean
        forwards_per_block_mean commit_forward_share denoise_step_roofline
        kernel_time_share.block_attention block_attention_roofline
        kernel_time_share.mla_decode mla_decode_roofline
        kernel_time_share.mla_prefill latent_bytes_per_token
        prefix_hit_share.docqa chained_dispatch_share cache_bytes_per_token.kv
        cache_bytes_per_token.state prefix_hit_share.agentloop
        hybrid_decode_step_roofline decode_scope_ms.attn
        decode_scope_ms.cache_write decode_scope_ms.head decode_scope_ms.sample
        decode_scope_ms.unscoped decode_scope_ms.ffn decode_scope_ms.moe_router
        decode_scope_ms.moe_experts decode_scope_ms.moe_shared
        decode_scope_ms.conv prefill_scope_ms.attn prefill_scope_ms.ffn
        prefill_scope_ms.head decode_experts_touched_mean
        counted_decode_step_roofline prefill_slot_fill_share
        prefill_scope_ms.moe_experts prefill_scope_ms.moe_router
        zero_place_share held_experts_touched_share held_rows_mean
        scmoe_decode_step_roofline decode_scope_ms.moe_zero""",
}


def test_accepted_entries_are_as_they_were():
    """The benchmark PR 42 left (6 configurations, 6 cells, 4 end-to-end and
    89 per-layer metrics, command, paths, run_seconds), as they stood: each
    accepted entry is looked up by its name, so an entry that a later PR
    appends, wherever it stands, does not falsify this, and without its
    ``workloads`` list, which a ``benchmark`` PR may widen
    (``accepted_entries.py``)."""
    assert accepted_entries.digest(BENCH, ACCEPTED) == "c9592f9e160d2f2c310928fff75d7fda06f833bf7df14dbcfa0e649f4c688159"
    # ... less ``decode_step_roofline``, which PR 55 retired
    assert accepted_entries.count(ACCEPTED) == 6 + 6 + 4 + 88


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
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdocs", 1)
    assert config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert config["source"] == ("https://huggingface.co/arcee-ai/"
                                "Trinity-Large-Preview/blob/main/config.json")
    e2e = {m["name"] for m in run.metrics_of_cell(BENCH["end_to_end"], CELL)}
    assert e2e == {"itl_ms_p50", "out_tokens_per_s", "setup_s"}
    # the accepted metrics without a list are read in the new cell too (and
    # those a ``benchmark`` PR found to work here: a superset)
    read_here = {m["name"] for m in run.metrics_of_cell(BENCH["per_layer"], CELL)}
    assert read_here >= set(NEW_METRICS) | {
        "lanes_busy_mean", "step_ms_mean", "kernel_time_share.paged_attention",
        "device_idle_share", "peak_hbm_gib"}


def test_the_configuration_is_the_catalog_row_with_four_cuts():
    """Every key of the catalog's row under its own name and value, but the
    four in ``reduced``; the program's preset agrees width for width."""
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 3072, "intermediate_size": 12288,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 15,
        "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
        "model_type": "afmoe", "moe_intermediate_size": 3072,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
        "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
        "num_experts_per_tok": 4, "num_hidden_layers": 60,
        "num_key_value_heads": 8, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
        "score_func": "sigmoid", "sliding_window": 4096,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
        "vocab_size": 200192,
    }
    config = run.load_config(CONFIG)
    pub = config["published"]
    differs = {k for k, v in published.items() if pub.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "num_dense_layers", "num_experts",
                       "vocab_size"}
    assert differs == set(pub["reduced"])
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            pub["num_experts"], pub["vocab_size"]) == (5, 1, 32, 25024)
    # restated for the harness's built-in check, and said so
    assert (pub["norm_topk_prob"], pub["router_outputs"]) == (True, 256)
    assert "restates" in pub["restated"] and pub["deployment"] and pub["assumed"]
    cfg = run.model_config(config, rehearse=False)
    assert (cfg.n_layers, cfg.n_attn_layers, cfg.n_window_layers,
            cfg.experts_held, cfg.expert_first, cfg.router_outputs,
            cfg.first_k_dense, cfg.kv_row_shape) == (5, 1, 4, 32, 0, 256, 1,
                                                     (8, 128))
    assert [cfg.layer_kind(i) for i in range(5)] == [
        "sliding", "sliding", "sliding", "attention", "sliding"]
    # every width is checked against the preset at every run
    for key, moved in (("sliding_window", 2048), ("num_experts", 64),
                       ("router_outputs", 128), ("route_scale", 1.0),
                       ("num_shared_experts", 2), ("mup_enabled", False),
                       ("num_dense_layers", 2), ("score_func", "softmax"),
                       ("layer_types", ["full_attention"] * 60),
                       ("moe_intermediate_size", 1024), ("hidden_size", 4096),
                       ("num_key_value_heads", 4)):
        with pytest.raises(run.BenchFailure, match=key):
            run.model_config({**config, "published": {**pub, key: moved}},
                             rehearse=False)
    assert config["env"] == {"BLOCK_SIZE": 16, "TOTAL_PAGES": 28672,
                             "WINDOW_PAGES": 8192, "MAX_MODEL_LEN": 34816,
                             "DECODE_BATCH_SIZE": 32}


def test_the_mix_is_the_issues_letter_for_letter():
    spec = traffic.load_traffic("longdocs")
    assert {k: spec[k] for k in ("kind", "callers_per_lane", "requests",
                                 "fill_piece_tokens", "fill_tail_tokens")} == {
        "kind": "closed", "callers_per_lane": 2, "requests": 2048,
        "fill_piece_tokens": 1024, "fill_tail_tokens": 16}
    assert spec["groups"] == {"prefix_tokens": [8192, 16384, 32768],
                              "pool_share": 0.8, "zipf_s": 1.0}
    assert spec["unique"] == {"dist": "lognormal", "median": 96, "sigma": 0.7,
                              "min": 32, "max": 256}
    assert spec["output"] == {"dist": "lognormal", "median": 160, "sigma": 0.6,
                              "min": 64, "max": 512}
    assert "request" not in spec and spec["who"]
    others = {traffic.load_traffic(n)["sizes_seed"] for n in (
        "sessions", "reasoning", "blockgen", "docqa", "agentloop", "turns")}
    assert spec["sizes_seed"] not in others
    sched = traffic.build_schedule(spec, 5, 45.0, pods=1,
                                   pool_tokens_per_pod=28672 * 16, lanes=32)
    # six rounds of the three lengths: 18 documents, 344064 tokens resident
    assert [len(p) for p in sched.prefixes] == [8192, 16384, 32768] * 6
    assert sum(len(p) for p in sched.prefixes) == 344064
    assert sched.callers == 64 and len(sched.requests) == 2048
    assert all(r.group is not None and r.prompt_len <= 32768 + 256
               for r in sched.requests)
    mean_context = sum(r.prefix_len for r in sched.requests) / 2048
    assert 15000 < mean_context < 18000  # two to eight windows, in one queue
    # no shared context page is evicted: the documents and 32 lanes' turns
    assert 344064 + 32 * (256 + 512 + 16) <= 0.81 * 28672 * 16
    # ... nor a document's last window: 18 x 257 pages and the lanes' own
    assert 18 * 257 + 32 * 50 + 66 < 8192 - 1
    # one page id for all five layers would not fit beside the weights
    assert 344064 * 5 * 4096 > 6.5 * 2**30
    rounds = traffic.fill_plan(sched, spec, 5)
    assert len(rounds) == 32 and len(rounds[0]) == 18 and len(rounds[-1]) == 6
    # what the cell compiles at its pinned buckets: 6 question shapes and 3
    # decode widths (the fill's 5 shapes are the set-up's)
    buckets = traffic.Buckets(page=16, prefill_bucket=128, prefill_ctx_bucket=512,
                              decode_pages_bucket=512, max_pages=2176)
    prefill, decode = traffic.shape_set(sched.requests, buckets)
    assert prefill == {(c, w) for c in (128, 256) for w in (512, 1024, 2048)}
    assert decode == {1024, 1536, 2176}


def test_cost_functions_against_hand_sums():
    cfg = run.model_config(run.load_config(CONFIG), rehearse=False)
    attn = 3072 * 6144 + 2 * 3072 * 1024 + 6144 * 3072 + 3072 * 6144
    assert costs_swa.attention_params(cfg) == attn == 62_914_560
    dense, expert = 3 * 3072 * 12288, 3 * 3072 * 3072
    assert costs_swa.dense_ffn_params(cfg) == dense == 113_246_208
    assert costs_swa.expert_params(cfg) == expert == 28_311_552
    router = 3072 * 256 + 256
    assert costs_swa.layers_of(cfg) == (1, 4, 4)
    held = 5 * attn + dense + 4 * (router + 33 * expert)
    assert costs_swa.model_params(cfg, 32) == held
    head = 25024 * 3072
    assert costs_swa.resident_weight_bytes(cfg) == 2 * (2 * head + held)
    assert 8.6e9 < costs_swa.resident_weight_bytes(cfg) < 8.7e9  # 8.05 GiB
    # a token's slot: one full layer in the context pool, four sliding ones
    # in the window pool
    assert costs_swa.kv_bytes_per_token(cfg) == 4096
    assert costs_swa.window_bytes_per_token(cfg) == 16384
    # a decode step of 32 lanes at 16.6k tokens each (4096 in a window), 13
    # held experts read a layer, 16 rows in the grouped matmuls
    ctx, win = 32 * 16600, 32 * 4096
    want = (2 * (5 * attn + dense + 4 * (router + 14 * expert) + head + 32 * 3072)
            + ctx * 4096 + win * 16384)
    assert costs_swa.decode_step_min_bytes(cfg, 32, ctx, win, 13) == want
    assert costs_swa.attention_min_bytes(cfg, ctx, win) == ctx * 4096 + win * 16384
    more = costs_swa.decode_step_min_bytes(cfg, 32, ctx, win, 32)
    assert more - want == 2 * 4 * 19 * expert
    flops = (2 * 32 * (5 * attn + dense + 4 * (3072 * 256 + expert) + head)
             + 2 * 4 * 16 * expert + 4 * 48 * 128 * (ctx + 4 * win))
    assert costs_swa.decode_step_flops(cfg, 32, ctx, win, 16) == flops
    peaks = costs.load_peaks("TPU v5 lite")
    # bound by the bytes it reads: 8.5 GB against a fifth of a TFLOP
    assert costs_swa.decode_step_min_s(cfg, peaks, 32, ctx, win, 13, 16) == (
        want / 819e9)
    assert 8.4e9 < want < 8.7e9 and want / 819e9 > 4 * flops / 197e12
    with pytest.raises(TypeError):  # no count, no cost: nothing is guessed
        costs_swa.decode_step_min_bytes(cfg, 32, ctx, win)


def records(**kw):
    forwards, layers = 100, 4
    counters = ("experts_touched", "decode_forwards", "decode_dispatches",
                "decode_rows", "attn_ctx_tokens", "window_ctx_tokens")
    pool = {"window_pages": 8192, "window_bytes_per_token": 16384,
            "routed_layers": layers}
    base = dict(
        cell=run.find_cell(BENCH, CELL), good=[{}] * 90, failed=[],
        in_flight=[{}] * 10, in_flight_tokens=0, late_s=[], window_s=10.0,
        stats_before=[{**pool, "window_pages_held": 4700,
                       "window_pages_dropped": 100, "window_pages_evicted": 50,
                       "window_short_hits": 0, "window_short_hit_tokens": 0}],
        stats_after=[{**pool, "window_pages_held": 5120,
                      "window_pages_dropped": 900, "window_pages_evicted": 700,
                      "window_short_hits": 2, "window_short_hit_tokens": 16384}],
        running_samples=[], lanes=32, page=16, pods=[object()],
        step_before=[dict.fromkeys(counters, 0)],
        step_after=[{"experts_touched": forwards * layers * 13,
                     "decode_forwards": forwards, "decode_dispatches": forwards,
                     "decode_rows": forwards * 32,
                     "attn_ctx_tokens": forwards * 32 * 16600,
                     "window_ctx_tokens": forwards * 32 * 4096}],
        compiles_in_window=0, memory_peak_bytes=0,
        model_cfg=run.model_config(run.load_config(CONFIG), rehearse=False),
        peaks=costs.load_peaks("TPU v5 lite"),
        trace={"busy_s": 3.0, "window_s": 4.0,
               "ops": {"paged_attention.1": 3.0, "paged_attention_window.2": 1.5,
                       "fusion.9": 0.5},
               "ops_text": {},
               "module_calls": {"jit_decode_steps(1)": 100},
               "modules": {"jit_decode_steps(1)": 6.0}},
    )
    base.update(kw)
    return run.RunRecords(**base)


def test_readers_on_hand_made_records():
    read = {name: run.load_layer_metric(name) for name in NEW_METRICS}
    r = records()
    assert read["window_ctx_share"](r) == pytest.approx(100 * 4096 / 16600)
    assert read["window_bytes_per_token"](r) == 16384
    assert read["window_pool_held_share"](r) == pytest.approx(62.5)
    assert read["window_short_hit_share"](r) == pytest.approx(2.0)
    assert read["kernel_time_share.paged_attention_window"](r) == pytest.approx(50.0)
    cfg = r.model_cfg
    ctx, win = 32 * 16600, 32 * 4096
    least_s = costs_swa.decode_step_min_bytes(cfg, 32, ctx, win, 13) / 819e9
    assert read["swa_decode_step_roofline"](r) == pytest.approx(
        100 * least_s / 0.06)
    assert 15 < read["swa_decode_step_roofline"](r) < 20
    # both kernels' time a forward: 4.5 s over 100 calls
    attn_s = costs_swa.attention_min_bytes(cfg, ctx, win) / 819e9
    assert read["swa_attention_roofline"](r) == pytest.approx(
        100 * attn_s / 0.045)
    assert 10 < read["swa_attention_roofline"](r) < 13
    # a program from before the counters (the parent), a pod that does not
    # report the window pool, a run with no trace, another model: nothing to
    # read, and no error
    old = records(step_before=[{"decode_dispatches": 0, "experts_touched": 0}],
                  step_after=[{"decode_dispatches": 100, "experts_touched": 9}],
                  stats_before=[{}], stats_after=[{"routed_layers": 4}])
    for name in NEW_METRICS:
        if name.startswith(("prefix_hit_share", "kernel_time_share")):
            continue  # accepted readers, under a new suffix
        assert read[name](old) is None, name
    for name in ("swa_decode_step_roofline", "swa_attention_roofline"):
        assert read[name](records(trace=None)) is None
        other = records(model_cfg=types.SimpleNamespace(sliding_window=0))
        assert read[name](other) is None


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "no-gate"])
def test_the_cell_rehearses(broken, monkeypatch, capsys):
    """The whole run on the CPU at the tiny preset: ``correct`` true; with the
    gate on the attention's output read as open by the program (the probe's
    control; the reference and the weights stay) it is false."""
    from llm_d_kv_cache_manager_tpu.models import llama

    programs = (llama.prefill, llama.decode_step, llama.decode_steps,
                llama.prefill_packed)
    if broken:
        for jitted in programs:
            jitted.clear_cache()
        monkeypatch.setattr(llama, "_attn_gate", lambda layer, x, heads: heads)
    try:
        assert run.main(["--workload", CELL, "--seed", str(2**31 + 43),
                         "--seconds", "2", "--trace", "1", "--rehearse"]) == 0
    finally:
        if broken:
            monkeypatch.undo()
            for jitted in programs:
                jitted.clear_cache()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["correct"] is (not broken)
    assert line["reference"]["ok"] is (not broken)
    if not broken:
        got = line["metrics"]
        assert got["window_bytes_per_token"]["value"] == 4 * 2 * 2 * 16 * 4
        assert 0 < got["window_ctx_share"]["value"] < 100
        assert 0 < got["window_pool_held_share"]["value"] < 100
        assert got["prefix_hit_share.longdocs"]["value"] > 50
        assert "window_short_hit_share" in got
        # no device number off the chip
        assert "swa_decode_step_roofline" not in got
        assert "swa_attention_roofline" not in got
        assert "kernel_time_share.paged_attention_window" not in got


def test_the_probes_controls_each_read_not_correct(capsys):
    """``probe_swa.py`` at the tiny preset in float32: the sound run is
    correct and every control is not."""
    from chipbench import probe_swa

    assert probe_swa.main(["--seeds", "3", "--rehearse"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["control"] for x in lines] == ["sound", *probe_swa.CONTROLS]
    assert [x["ok"] for x in lines] == [True] + [False] * len(probe_swa.CONTROLS)
