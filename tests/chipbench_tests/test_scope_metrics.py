"""What PR 38 added to the benchmark: the readers of the model's named scopes
(``decode_scope_ms.*``, ``prefill_scope_ms.*``) and of the fused decode
path's expert count (``decode_experts_touched_mean``,
``counted_decode_step_roofline``), on planes and records made by hand. No
chip, no child process; nothing here is a measurement."""

import json
import os
import types

import pytest

from chipbench import costs, program_counts, run, scope_times, xplane_meta
from llm_d_kv_cache_manager_tpu.models import llama

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
S, R, B, D, A = ("qwen3-32b.sessions", "qwen3-30b-a3b.reasoning",
                 "sdar-30b-a3b.blockgen", "kanana-2-30b-a3b.docqa",
                 "lfm2-8b-a1b.agentloop")
SCOPES = llama.MODEL_SCOPES

DECODE_PARTS = {
    "attn": [S, R, B, D, A], "cache_write": [S, R, B, D, A],
    "head": [S, R, B, D, A], "sample": [S, R, B, D, A],
    "unscoped": [S, R, B, D, A], "ffn": [S, D, A],
    "moe_router": [R, B, D, A], "moe_experts": [R, B, D, A],
    "moe_shared": [D], "conv": [A],
}
NEW = {
    **{f"decode_scope_ms.{part}": ("ms", "lower", "device_trace",
                                   "itl_ms_p50", cells)
       for part, cells in DECODE_PARTS.items()},
    **{f"prefill_scope_ms.{part}": ("ms", "lower", "device_trace",
                                    "ttft_ms_p50", [S])
       for part in ("attn", "ffn", "head")},
    "decode_experts_touched_mean": ("count", "lower", "program_counter",
                                    "itl_ms_p50", [R, B, D, A]),
    "counted_decode_step_roofline": ("%", "higher", "device_trace",
                                     "itl_ms_p50", [S, R]),
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry(name):
    unit, better, source, moves, cells = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {**entry, "workloads": cells} == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": "model step", "moves": moves, "workloads": cells}
    assert set(cells) <= set(entry["workloads"])  # a benchmark PR adds cells
    part = name.partition(".")[2]
    assert not part or part == "unscoped" or part in SCOPES
    run.load_layer_metric(name)  # a reader is found by the name
    for cell in entry["workloads"]:  # every listed cell reports what it moves
        moved = next(m for m in BENCH["end_to_end"] if m["name"] == moves)
        assert "workloads" not in moved or cell in moved["workloads"]


# -- planes made by hand --------------------------------------------------------
def ev(name, start, dur, op_name=""):
    text = name + (f" tf_op={op_name}" if op_name else "")
    return {"name": name, "start_ns": float(start), "dur_ns": float(dur),
            "text": text}


def planes():
    """One chip. Two ``decode_steps`` modules of different widths (1000 ns
    and 600 ns), one ``prefill`` module, and an operation outside every
    module."""
    scan = "jit(decode_steps)/jit(main)/while/body/"
    ops = [
        ev("%copy.1", 10, 40, "jit(other)/model.attn/copy"),  # no module
        # decode_steps(1): 100..1100
        ev("%while.3", 100, 900),  # wraps the body's operations, no part
        ev("%fusion.1", 100, 300, scan + "model.attn/dot_general"),
        ev("%paged_attention.2", 400, 200,
           scan + "model.attn/pallas_call[name=paged_attention]"),
        ev("%fusion.7", 600, 100),  # a fusion with no scope, inside the while
        ev("%gmm.1", 700, 250, scan + "model.moe_router/model.moe_experts/gmm"),
        # 950..1000: the while alone; then outside the while:
        ev("%fusion.9", 1000, 60, "jit(decode_steps)/jit(main)/model.head/dot"),
        # decode_steps(2): 2000..2600, busy 500
        ev("%fusion.1", 2000, 200, scan + "model.attn/dot_general"),
        ev("%conditional.4", 2200, 300, scan + "jit(sample_tokens)/model.sample/cond"),
        ev("%fusion.5", 2250, 100),  # inside the conditional: its part
        # prefill: 3000..4000
        ev("%fusion.2", 3000, 700, "jit(prefill_packed)/model.ffn/dot_general"),
        ev("%fusion.3", 3700, 100, "jit(prefill_packed)/model.head/dot_general"),
        ev("%gather.1", 3800, 50),
    ]
    mods = [ev("jit_decode_steps(11)", 100, 1000),
            ev("jit_decode_steps(22)", 2000, 600),
            ev("jit_prefill_packed(33)", 3000, 1000),
            ev("jit_sample_tokens_packed(44)", 4100, 20)]
    return [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops},
                   {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ev("engine.decode_fetch", 0, 5000)]}]},
    ]


# -- the event metadata's stats, read from the file itself ----------------------
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One protocol-buffer field: an int as a varint, bytes / str / a list of
    fields as length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    if isinstance(value, list):
        value = b"".join(value)
    return varint(number << 3 | 2) + varint(len(value)) + value


def xspace():
    """``xplane.proto`` by hand: one device plane whose event metadata carry
    ``tf_op`` (a string), ``hlo_category`` (a reference to a stat metadata's
    name) and a number; a host plane without stats."""
    def stat_meta(ident, name):
        return field(5, [field(1, ident), field(2, [field(1, ident), field(2, name)])])

    def event_meta(ident, name, stats):
        return field(4, [field(1, ident), field(2, [
            field(1, ident), field(2, name), *[field(5, s) for s in stats]])])

    device = [
        field(1, 3), field(2, "/device:TPU:0"),
        event_meta(1, "%fusion.1 = f32[16]{0} fusion(...)", [
            [field(1, 7), field(5, "jit(decode_steps)/model.attn/dot_general:")],
            [field(1, 8), field(7, 9)],
            [field(1, 10), field(3, 123456)],  # uint64: not a string, skipped
        ]),
        event_meta(2, "%copy-start.4 = (...) copy-start(...)", []),
        event_meta(300, "%gmm.2 = f32[128,768]{1,0} custom-call(...)", [
            [field(1, 7), field(5, "jit(decode_steps)/model.moe_experts/jit(gmm)/pallas_call:")],
        ]),
        stat_meta(7, "tf_op"), stat_meta(8, "hlo_category"),
        stat_meta(9, "loop fusion"), stat_meta(10, "flops"),
        # a line with an event: never read here (ProfileData reads those)
        field(3, [field(1, 1), field(2, "XLA Ops"),
                  field(4, [field(1, 1), field(2, 5000), field(3, 70000)])]),
    ]
    host = [field(2, "/host:CPU"), event_meta(1, "engine.decode_fetch", [])]
    return field(1, device) + field(1, host)


def test_the_metadata_stats_are_read_from_the_file(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace())
    texts = xplane_meta.load(str(path))
    assert texts == {
        "/device:TPU:0": {
            "%fusion.1 = f32[16]{0} fusion(...)":
                "tf_op=jit(decode_steps)/model.attn/dot_general: "
                "hlo_category=loop fusion",
            "%gmm.2 = f32[128,768]{1,0} custom-call(...)":
                "tf_op=jit(decode_steps)/model.moe_experts/jit(gmm)/pallas_call:",
        },
        "/host:CPU": {},
    }
    hand = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ev("%fusion.1 = f32[16]{0} fusion(...)", 0, 10),
        ev("%copy-start.4 = (...) copy-start(...)", 10, 1),
        ev("%gmm.2 = f32[128,768]{1,0} custom-call(...)", 20, 10),
    ]}]}]
    hand[0]["lines"].append({"name": "XLA Modules", "events": [
        ev("jit_decode_steps(5)", 0, 40)]})
    got = scope_times.module_parts(hand, "decode_steps", SCOPES, texts)
    assert got == {"calls": 1, "ns": {"attn": 10, "unscoped": 1,
                                      "moe_experts": 10}}
    # without the metadata's stats the same planes name no part
    assert scope_times.module_parts(hand, "decode_steps", SCOPES) is None


def test_an_event_belongs_to_its_innermost_scope():
    assert scope_times.part_of("x tf_op=a/model.attn/dot", SCOPES) == "attn"
    assert scope_times.part_of(
        "x tf_op=model.moe_router/model.moe_experts/gmm", SCOPES
    ) == "moe_experts"
    assert scope_times.part_of("x long_name=fusion.1", SCOPES) is None
    # a name the program's tuple lacks is no part
    assert scope_times.part_of("x tf_op=model.other/dot", SCOPES) is None


def test_module_parts_on_hand_made_planes():
    got = scope_times.module_parts(planes(), "decode_steps", SCOPES)
    assert got["calls"] == 2
    assert got["ns"] == {
        "attn": 300 + 200 + 200, "unscoped": 100 + 50, "moe_experts": 250,
        "head": 60, "sample": 300,
    }
    # the parts sum to the modules' busy time: nothing is counted twice
    assert sum(got["ns"].values()) == 960 + 500
    pre = scope_times.module_parts(planes(), "prefill", SCOPES)
    assert pre == {"calls": 1, "ns": {"ffn": 700, "head": 100, "unscoped": 50}}
    assert scope_times.module_parts(planes(), "denoise_steps", SCOPES) is None
    # a program from before the scopes: its operations carry no part
    bare = planes()
    for e in bare[0]["lines"][0]["events"]:
        e["text"] = e["name"]
    assert scope_times.module_parts(bare, "decode_steps", SCOPES) is None


def records(cell=R, **kw):
    config = run.find_cell(BENCH, cell)["config"]
    base = dict(
        cell=run.find_cell(BENCH, cell), good=[], failed=[], in_flight=[],
        in_flight_tokens=0, late_s=[], window_s=10.0, stats_before=[{}],
        stats_after=[{"routed_layers": 8}], running_samples=[], lanes=16,
        page=16, pods=[object()],
        step_before=[dict.fromkeys(program_counts.KEYS, 0)],
        step_after=[{"experts_touched": 100 * 8 * 60, "decode_forwards": 100,
                     "decode_dispatches": 100, "decode_rows": 1500,
                     "attn_ctx_tokens": 1500 * 600}],
        compiles_in_window=0, memory_peak_bytes=0,
        model_cfg=run.model_config(run.load_config(config), rehearse=False),
        peaks=costs.load_peaks("TPU v5 lite"),
        trace={"chips": 1, "busy_s": 3.0, "window_s": 4.0, "ops": {},
               "ops_text": {}, "module_calls": {"jit_decode_steps(1)": 100},
               "modules": {"jit_decode_steps(1)": 1.1}},
    )
    base.update(kw)
    return run.RunRecords(**base)


def test_scope_readers_on_hand_made_records(monkeypatch):
    loads = []
    monkeypatch.setattr(
        scope_times, "load_trace", lambda: loads.append(1) or (planes(), {}))
    read = {name: run.load_layer_metric(name) for name in NEW}
    r = records()
    got = {name: read[name](r) for name in NEW if "_scope_ms." in name}
    assert got["decode_scope_ms.attn"] == pytest.approx(700 / 2 / 1e6)
    assert got["decode_scope_ms.moe_experts"] == pytest.approx(250 / 2 / 1e6)
    assert got["decode_scope_ms.unscoped"] == pytest.approx(150 / 2 / 1e6)
    assert got["decode_scope_ms.conv"] == 0.0  # no such operation traced
    assert got["prefill_scope_ms.ffn"] == pytest.approx(700 / 1e6)
    assert sum(v for k, v in got.items() if k.startswith("decode_")) == (
        pytest.approx((960 + 500) / 2 / 1e6))
    assert len(loads) == 1  # one load serves every suffix of both families
    # block diffusion: the decode-side module is ``denoise_steps``
    blocks = records(B)
    assert read["decode_scope_ms.attn"](blocks) is None  # no such module here
    assert read["prefill_scope_ms.ffn"](blocks) == pytest.approx(700 / 1e6)
    # no trace, no chip, a program without the tuple: nothing, and no error
    assert read["decode_scope_ms.attn"](records(trace=None)) is None
    off_chip = records()
    off_chip.trace["chips"] = 0
    assert read["decode_scope_ms.attn"](off_chip) is None
    monkeypatch.delattr(llama, "MODEL_SCOPES")
    assert read["decode_scope_ms.attn"](records()) is None
    assert read["prefill_scope_ms.head"](records()) is None


def test_count_readers_on_hand_made_records():
    touched = run.load_layer_metric("decode_experts_touched_mean")
    roofline = run.load_layer_metric("counted_decode_step_roofline")
    r = records()
    assert touched(r) == 60.0
    cfg = r.model_cfg
    least_s = costs.decode_step_min_bytes(cfg, 15, 600, experts_touched=60) / 819e9
    assert roofline(r) == pytest.approx(100 * least_s / 0.011)
    assert 0 < roofline(r) < 100
    # the uniform expectation the old reader takes counts more bytes
    assert costs.expected_experts_touched(cfg, 15) > 60
    # two fused steps a dispatch: twice the bytes against the module's time
    two = records(step_after=[{**r.step_after[0], "decode_forwards": 200,
                               "experts_touched": 200 * 8 * 60,
                               "attn_ctx_tokens": 2 * 1500 * 600}])
    assert touched(two) == 60.0
    assert roofline(two) == pytest.approx(2 * roofline(r))
    # a dense model needs no count of experts
    dense = records(S, stats_after=[{"routed_layers": 0}], step_after=[
        {**r.step_after[0], "experts_touched": 0}])
    assert touched(dense) is None
    want = costs.decode_step_min_bytes(dense.model_cfg, 15, 600) / 819e9
    assert roofline(dense) == pytest.approx(100 * want / 0.011)
    # a program without the counters (the parent), one whose /stats lacks the
    # routed layers, a window without a dispatch, a run without a trace
    old = {k: 0 for k in program_counts.KEYS if k != "decode_forwards"}
    parent = records(step_before=[old], step_after=[
        {k: v for k, v in r.step_after[0].items() if k != "decode_forwards"}])
    assert touched(parent) is None and roofline(parent) is None
    no_layers = records(stats_after=[{"kv_bytes_per_token": 1}])
    assert touched(no_layers) is None and roofline(no_layers) is None
    idle = records(step_after=[dict.fromkeys(program_counts.KEYS, 0)])
    assert touched(idle) is None and roofline(idle) is None
    assert roofline(records(trace=None)) is None
    assert touched(records(trace=None)) == 60.0  # a count needs no trace


def test_blockgen_reads_what_block_counters_reads():
    """Under block diffusion a dispatch is a forward and every layer is
    routed: the new reader and ``block_counters`` divide the same numbers."""
    from chipbench import block_counters

    after = {**dict.fromkeys(block_counters.KEYS, 7), "experts_touched": 123457,
             "decode_forwards": 211, "decode_dispatches": 211,
             "decode_rows": 3000, "attn_ctx_tokens": 0}
    before = dict.fromkeys(after, 0)
    r = records(B, step_before=[before], step_after=[after],
                model_cfg=types.SimpleNamespace(n_layers=8, block_length=4))
    assert run.load_layer_metric("decode_experts_touched_mean")(r) == (
        block_counters.experts_touched_per_layer(r))
