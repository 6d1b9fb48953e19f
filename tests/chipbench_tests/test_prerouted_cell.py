"""What PR 54 added to the benchmark, rehearsed on the CPU: the cell
``smallthinker-21b-a3b.mixedlen`` (configuration, mix, reference, readers,
cost functions) and that its entries list the new cell alone. No chip, no
child process; nothing here is a measurement.
"""

import json
import os
import types

import pytest

from chipbench import costs, costs_prerouted, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = "smallthinker-21b-a3b"
CELL = "smallthinker-21b-a3b.mixedlen"
SOURCE = ("https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
          "blob/main/config.json")
#: name -> (layer, the end-to-end metric it should move)
NEW_METRICS = {
    "decode_scope_ms.moe_preroute": ("model step", "itl_ms_p50"),
    "prerouted_decode_step_roofline": ("model step", "itl_ms_p50"),
    "ctx_table_fill_share": ("engine step", "itl_ms_p50"),
    "prefix_hit_share.mixedlen": ("block manager", "out_tokens_per_s"),
    "window_short_hit_share.mixedlen": ("block manager", "out_tokens_per_s"),
    "window_ctx_share.mixedlen": ("model step", "itl_ms_p50"),
}


def test_this_prs_entries_list_the_new_cell_alone():
    """They exist and list the cell; nothing here says that nobody else
    does, nor where in ``per_layer`` they stand (a ``benchmark`` PR lists a
    cell a reader works in, as PR 55 did this cell under accepted entries,
    and later PRs append)."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (layer, moves) in NEW_METRICS.items():
        assert CELL in by_name[name]["workloads"]
        assert (by_name[name]["layer"], by_name[name]["moves"]) == (layer, moves)
        assert callable(run.load_layer_metric(name))  # by file or by family
    # no end-to-end entry was given a list for the new cell
    for m in BENCH["end_to_end"]:
        assert CELL not in m.get("workloads", [])
    cell = run.find_cell(BENCH, CELL)
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "mixedlen", 1)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == SOURCE
    e2e = {m["name"] for m in run.metrics_of_cell(BENCH["end_to_end"], CELL)}
    assert e2e == {"itl_ms_p50", "out_tokens_per_s", "setup_s"}
    # the accepted metrics without a list are read in the new cell too (and
    # those a ``benchmark`` PR found to work here: a superset)
    read_here = {m["name"] for m in run.metrics_of_cell(BENCH["per_layer"], CELL)}
    assert read_here >= set(NEW_METRICS) | {
        "lanes_busy_mean", "step_ms_mean", "kernel_time_share.paged_attention",
        "device_idle_share", "peak_hbm_gib"}


def test_the_configuration_is_the_catalog_row_with_one_cut():
    """Every key of the catalog's row under its own name and value, but the
    depth; the program's preset agrees width for width."""
    layout = [0, 1, 1, 1] * 13
    published = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": layout,
        "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "vocab_size": 151936,
    }
    config = run.load_config(CONFIG)
    pub = config["published"]
    differs = {k for k, v in published.items() if pub.get(k, "missing") != v}
    assert differs == {"num_hidden_layers"} == set(pub["reduced"])
    assert pub["num_hidden_layers"] == 8 and pub["source"] == SOURCE
    # restated for the harness's built-in check, and said so
    assert (pub["num_experts"], pub["num_experts_per_tok"],
            pub["moe_intermediate_size"], pub["intermediate_size"]) == (
        64, 6, 768, 768)
    assert "restates" in pub["restated"] and pub["deployment"]
    said = " ".join(pub["assumed"])
    for point in ("un-normed", "ReLU", "no q/k norm", "t - 4096 < j <= t",
                  "TOTAL_PAGES 32768", "WINDOW_PAGES 14336", "MAX_MODEL_LEN",
                  "prefill_bucket 128", "seeded random weights", "smallthinker"):
        assert point in said, point
    cfg = run.model_config(config, rehearse=False)
    assert (cfg.n_layers, cfg.n_attn_layers, cfg.n_window_layers, cfg.n_experts,
            cfg.experts_held, cfg.n_experts_per_tok, cfg.first_k_dense,
            cfg.kv_row_shape, cfg.hidden_act, cfg.router_before_attention) == (
        8, 2, 6, 64, 64, 6, 0, (4, 128), "relu", True)
    assert cfg.holds_every_expert and cfg.n_heads // cfg.n_kv_heads == 7
    assert [cfg.layer_kind(i) for i in range(8)] == [
        "attention", "sliding", "sliding", "sliding"] * 2
    # every width is checked against the preset at every run
    for key, moved in (("sliding_window_size", 2048),
                       ("sliding_window_layout", [1, 1, 1, 0] * 13),
                       ("rope_layout", [1] * 52), ("hidden_act", "silu"),
                       ("router_before_attention", False),
                       ("moe_num_primary_experts", 32),
                       ("moe_num_active_primary_experts", 8),
                       ("moe_ffn_hidden_size", 1024), ("num_experts", 32),
                       ("moe_primary_router_apply_softmax", False),
                       ("hidden_size", 4096), ("num_attention_heads", 32),
                       ("num_key_value_heads", 8), ("vocab_size", 32000),
                       ("rope_theta", 10000)):
        with pytest.raises(run.BenchFailure, match=key):
            run.model_config({**config, "published": {**pub, key: moved}},
                             rehearse=False)
    assert config["env"] == {"BLOCK_SIZE": 16, "TOTAL_PAGES": 32768,
                             "WINDOW_PAGES": 14336, "MAX_MODEL_LEN": 13568,
                             "DECODE_BATCH_SIZE": 32}
    assert config["engine"] == {"prefill_bucket": 128, "prefill_ctx_bucket": 512,
                                "decode_pages_bucket": 512}
    assert config["rehearse"]["preset"] == "TINY_SMALLTHINKER"


def test_the_mix_is_the_issues_letter_for_letter():
    spec = traffic.load_traffic("mixedlen")
    assert {k: spec[k] for k in ("kind", "callers_per_lane", "requests",
                                 "fill_piece_tokens", "fill_tail_tokens")} == {
        "kind": "closed", "callers_per_lane": 2, "requests": 2048,
        "fill_piece_tokens": 1024, "fill_tail_tokens": 16}
    assert spec["groups"] == {"prefix_tokens": [1024, 4096, 12288],
                              "pool_share": 0.5, "zipf_s": 1.0}
    assert spec["unique"] == {"dist": "lognormal", "median": 96, "sigma": 0.7,
                              "min": 32, "max": 384}
    assert spec["output"] == {"dist": "lognormal", "median": 192, "sigma": 0.6,
                              "min": 64, "max": 768}
    assert "request" not in spec and spec["who"]
    others = {traffic.load_traffic(n)["sizes_seed"] for n in (
        "sessions", "reasoning", "blockgen", "docqa", "agentloop", "turns",
        "longdocs", "threads")}
    assert spec["sizes_seed"] not in others
    sched = traffic.build_schedule(spec, 5, 45.0, pods=1,
                                   pool_tokens_per_pod=32768 * 16, lanes=32)
    # fifteen rounds of the three lengths: 45 prefixes, 261120 tokens resident
    assert [len(p) for p in sched.prefixes] == [1024, 4096, 12288] * 15
    assert sum(len(p) for p in sched.prefixes) == 261120
    assert sched.callers == 64 and len(sched.requests) == 2048
    assert all(r.group is not None and r.prompt_len <= 12288 + 384
               and r.prompt_len + r.max_tokens <= 13568 for r in sched.requests)
    by_len = {n: sum(r.prefix_len == n for r in sched.requests) / 2048
              for n in (1024, 4096, 12288)}
    # a quarter of a window, one window, three windows: 44 / 31 / 25 %
    assert 0.40 < by_len[1024] < 0.48 and 0.27 < by_len[4096] < 0.35
    assert 0.21 < by_len[12288] < 0.29
    mean_context = sum(r.prefix_len for r in sched.requests) / 2048
    assert 4500 < mean_context < 5700
    # what a sliding layer reads of such contexts beside a full layer: the
    # 1k lanes read all of theirs
    window_share = sum(min(r.prefix_len, 4096) for r in sched.requests) / sum(
        r.prefix_len for r in sched.requests)
    assert 0.5 < window_share < 0.62
    # no shared context page is evicted: the prefixes, their fill's tails and
    # 400 admissions (a 45-second window at 1000 tokens/s completes about 200)
    # of a turn and a reply of 600 tokens together (the means are 117 and 226)
    turn = sum(r.prompt_len - r.prefix_len for r in sched.requests) / 2048
    reply = sum(r.max_tokens for r in sched.requests) / 2048
    assert 105 < turn < 130 and 210 < reply < 240
    assert 261120 + 255 * 16 + 400 * 600 <= 32768 * 16
    # ... nor a prefix's last window: 15 x (64 + 256 + 256) pages, the lanes'
    # own turns and a fill's chunk of 66 pages a row
    assert 15 * (64 + 256 + 256) + 32 * 72 + 8 * 66 < 14336 - 1
    # one page id for all eight layers would cost 4 GiB for the prefixes alone
    assert 261120 * 8 * 2048 > 3.9 * 2**30
    rounds = traffic.fill_plan(sched, spec, 5)
    assert [len(r) for r in rounds] == [45] + [30] * 3 + [15] * 8
    # what the cell compiles at its pinned buckets
    buckets = traffic.Buckets(page=16, prefill_bucket=128, prefill_ctx_bucket=512,
                              decode_pages_bucket=512, max_pages=848)
    prefill, decode = traffic.shape_set(sched.requests, buckets)
    assert prefill == {(c, w) for c in (128, 256, 384) for w in (512, 1024)}
    assert decode == {512, 848}


def test_cost_functions_against_hand_sums():
    """The sums of ISSUE 54's Motivation."""
    cfg = run.model_config(run.load_config(CONFIG), rehearse=False)
    attn = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560
    assert costs_prerouted.attention_params(cfg) == attn == 20_971_520
    router, expert = 2560 * 64, 3 * 2560 * 768
    assert costs_prerouted.router_params(cfg) == router == 163_840
    assert costs_prerouted.expert_params(cfg) == expert == 5_898_240
    assert 64 * expert == 377_487_360
    layer = attn + router + 64 * expert
    assert 398.6e6 < layer < 398.7e6  # 797 MB
    assert costs_prerouted.layers_of(cfg) == (2, 6)
    assert costs_prerouted.model_params(cfg, 64) == 8 * layer
    head = 151936 * 2560
    assert costs_prerouted.resident_weight_bytes(cfg) == 2 * (2 * head + 8 * layer)
    assert costs_prerouted.resident_weight_bytes(cfg) == costs.resident_weight_bytes(cfg)
    assert 7.38 < costs_prerouted.resident_weight_bytes(cfg) / 2**30 < 7.40
    # a token's slot: two full layers in the context pool, six sliding ones
    # in the window pool
    assert costs_prerouted.kv_bytes_per_token(cfg) == 4096
    assert costs_prerouted.window_bytes_per_token(cfg) == 12288
    assert 32768 * 16 * 4096 == 2 * 2**30  # the context pool: 2.0 GiB
    assert 2.62 < 14336 * 16 * 12288 / 2**30 < 2.63  # the window pool
    # a decode step of 32 lanes at 5.1k tokens each (2870 in a window), 55
    # experts read a layer
    ctx, win = 32 * 5100, 32 * 2870
    want = (2 * (8 * (attn + router + 55 * expert) + head + 32 * 2560)
            + ctx * 4096 + win * 12288)
    assert costs_prerouted.decode_step_min_bytes(cfg, 32, ctx, win, 55) == want
    assert costs_prerouted.attention_min_bytes(cfg, ctx, win) == (
        ctx * 4096 + win * 12288)
    assert 5.1e9 < 2 * 8 * 55 * expert < 5.3e9  # the experts: 5.2 GB
    assert 0.33e9 < 2 * 8 * attn < 0.34e9  # attention weights
    assert 0.77e9 < 2 * head < 0.79e9  # the head
    assert 0.66e9 < ctx * 4096 < 0.68e9 and 1.1e9 < win * 12288 < 1.2e9
    assert 8.0e9 < want < 8.2e9  # 8.1 GB a step
    more = costs_prerouted.decode_step_min_bytes(cfg, 32, ctx, win, 64)
    assert more - want == 2 * 8 * 9 * expert
    flops = (2 * 32 * (8 * (attn + router + 6 * expert) + head)
             + 4 * 28 * 128 * (2 * ctx + 6 * win))
    assert costs_prerouted.decode_step_flops(cfg, 32, ctx, win) == flops
    peaks = costs.load_peaks("TPU v5 lite")
    # bound by the bytes it reads: 9.9 ms at 819 GB/s
    least = costs_prerouted.decode_step_min_s(cfg, peaks, 32, ctx, win, 55)
    assert least == want / 819e9 and 9.8e-3 < least < 10.0e-3
    assert want / 819e9 > 10 * flops / 197e12
    with pytest.raises(TypeError):  # no count, no cost: nothing is guessed
        costs_prerouted.decode_step_min_bytes(cfg, 32, ctx, win)


def records(**kw):
    forwards, layers = 100, 8
    counters = ("experts_touched", "decode_forwards", "decode_dispatches",
                "decode_rows", "attn_ctx_tokens", "window_ctx_tokens",
                "decode_table_slots")
    pool = {"window_pages": 14336, "window_bytes_per_token": 12288,
            "routed_layers": layers}
    usage = {"prompt_tokens": 1000, "cached_prompt_tokens": 960}
    base = dict(
        cell=run.find_cell(BENCH, CELL),
        good=[{"body": {"usage": usage}}] * 90, failed=[],
        in_flight=[{}] * 10, in_flight_tokens=0, late_s=[], window_s=10.0,
        stats_before=[{**pool, "window_pages_dropped": 100,
                       "window_pages_evicted": 50, "window_short_hits": 0,
                       "window_short_hit_tokens": 0}],
        stats_after=[{**pool, "window_pages_dropped": 900,
                      "window_pages_evicted": 700, "window_short_hits": 3,
                      "window_short_hit_tokens": 3072}],
        running_samples=[], lanes=32, page=16, pods=[object()],
        step_before=[dict.fromkeys(counters, 0)],
        step_after=[{"experts_touched": forwards * layers * 55,
                     "decode_forwards": forwards, "decode_dispatches": forwards,
                     "decode_rows": forwards * 32,
                     "attn_ctx_tokens": forwards * 32 * 5100,
                     "window_ctx_tokens": forwards * 32 * 2870,
                     "decode_table_slots": forwards * 32 * 848 * 16}],
        compiles_in_window=0, memory_peak_bytes=0,
        model_cfg=run.model_config(run.load_config(CONFIG), rehearse=False),
        peaks=costs.load_peaks("TPU v5 lite"),
        trace={"busy_s": 3.0, "window_s": 4.0, "chips": 1,
               "ops": {"paged_attention.1": 2.0, "fusion.9": 1.0},
               "ops_text": {},
               "module_calls": {"jit_decode_steps(1)": 100},
               "modules": {"jit_decode_steps(1)": 3.3}},
    )
    base.update(kw)
    return run.RunRecords(**base)


def test_readers_on_hand_made_records():
    read = {name: run.load_layer_metric(name) for name in NEW_METRICS}
    r = records()
    assert read["ctx_table_fill_share"](r) == pytest.approx(
        100 * 5100 / (848 * 16))
    assert read["window_ctx_share.mixedlen"](r) == pytest.approx(
        100 * 2870 / 5100)
    assert read["window_short_hit_share.mixedlen"](r) == pytest.approx(3.0)
    assert read["prefix_hit_share.mixedlen"](r) == pytest.approx(96.0)
    cfg = r.model_cfg
    least_s = costs_prerouted.decode_step_min_bytes(
        cfg, 32, 32 * 5100, 32 * 2870, 55) / 819e9
    assert read["prerouted_decode_step_roofline"](r) == pytest.approx(
        100 * least_s / 0.033)
    assert 29 < read["prerouted_decode_step_roofline"](r) < 31
    # a program from before the counters (the parent), a pod that does not
    # report the window pool, a run with no trace, another model: nothing to
    # read, and no error
    old = records(step_before=[{"decode_dispatches": 0, "experts_touched": 0,
                                "attn_ctx_tokens": 0}],
                  step_after=[{"decode_dispatches": 100, "experts_touched": 9,
                               "attn_ctx_tokens": 7}],
                  stats_before=[{}], stats_after=[{"routed_layers": 8}])
    for name in ("prerouted_decode_step_roofline", "ctx_table_fill_share",
                 "window_short_hit_share.mixedlen", "window_ctx_share.mixedlen"):
        assert read[name](old) is None, name
    assert read["prerouted_decode_step_roofline"](records(trace=None)) is None
    for other in (types.SimpleNamespace(sliding_window=0),
                  types.SimpleNamespace(sliding_window=4096,
                                        router_before_attention=False)):
        assert read["prerouted_decode_step_roofline"](
            records(model_cfg=other)) is None
    assert read["decode_scope_ms.moe_preroute"](records(trace=None)) is None


@pytest.mark.parametrize("broken", [None, "router-after-attention", "silu"])
def test_the_cell_rehearses(broken, monkeypatch, capsys):
    """The whole run on the CPU at the tiny preset: ``correct`` true; with the
    router fed the post-attention stream (every other model's placement), or
    SiLU for ReLU, in the PROGRAM (the reference and the weights stay) it is
    false."""
    from llm_d_kv_cache_manager_tpu.models import llama

    programs = (llama.prefill, llama.decode_step, llama.decode_steps,
                llama.prefill_packed)
    if broken:
        for jitted in programs:
            jitted.clear_cache()
    if broken == "router-after-attention":
        own_mlp = llama._mlp
        monkeypatch.setattr(llama, "_preroute", lambda layer, cfg, h: None)
        monkeypatch.setattr(
            llama, "_mlp", lambda layer, *a, **kw: own_mlp(
                {k: v for k, v in layer.items() if k != "preroute"}, *a, **kw))
    elif broken == "silu":
        import jax

        monkeypatch.setattr(
            llama.LlamaConfig, "act_fn", property(lambda self: jax.nn.silu))
    try:
        assert run.main(["--workload", CELL, "--seed", str(2**31 + 54),
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
        assert 0 < got["ctx_table_fill_share"]["value"] < 100
        assert 0 < got["window_ctx_share.mixedlen"]["value"] < 100
        assert got["window_short_hit_share.mixedlen"]["value"] == 0
        assert got["prefix_hit_share.mixedlen"]["value"] > 25
        # no device number off the chip
        assert "prerouted_decode_step_roofline" not in got
        assert "decode_scope_ms.moe_preroute" not in got
