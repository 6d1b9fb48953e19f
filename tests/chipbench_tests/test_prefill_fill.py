"""What PR 40 added to the benchmark: ``prefill_slot_fill_share`` (real
tokens over the token slots the window's prefill dispatches computed, from
``/stats``' ``prefill`` block) and two entries the scopes' family reader
serves as it is (``prefill_scope_ms.moe_experts``, ``.moe_router``). The
reader on records made by hand, the entries looked up by NAME and never by
place, and the program's side: ``/stats`` carries ``prefill.token_slots``.
No chip; nothing here is a measurement."""

import asyncio
import json
import os

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from chipbench import run
from llm_d_kv_cache_manager_tpu.models import TINY_QWEN3_MOE
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    EngineConfig,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
S, R, B, D, A = ("qwen3-32b.sessions", "qwen3-30b-a3b.reasoning",
                 "sdar-30b-a3b.blockgen", "kanana-2-30b-a3b.docqa",
                 "lfm2-8b-a1b.agentloop")
NEW = {
    "prefill_slot_fill_share": ("%", "higher", "program_counter",
                                "engine step", [S, R, B, D, A]),
    "prefill_scope_ms.moe_experts": ("ms", "lower", "device_trace",
                                     "model step", [R, B, D, A]),
    "prefill_scope_ms.moe_router": ("ms", "lower", "device_trace",
                                    "model step", [R, B, D, A]),
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry(name):
    unit, better, source, layer, cells = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {**entry, "workloads": cells} == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "out_tokens_per_s", "workloads": cells}
    assert set(entry["workloads"]) >= set(cells)  # a benchmark PR adds cells
    run.load_layer_metric(name)  # a reader is found by the name
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    assert "workloads" not in moved  # every cell reports what it moves
    assert layer in {m["layer"] for m in BENCH["per_layer"] if m is not entry}
    assert set(cells) <= {w["name"] for w in BENCH["workloads"]}


def records(before, after):
    return run.RunRecords(
        cell=run.find_cell(BENCH, R), good=[], failed=[], in_flight=[],
        in_flight_tokens=0, late_s=[], window_s=10.0,
        stats_before=[{"prefill": b} for b in before],
        stats_after=[{"prefill": a} for a in after],
        running_samples=[], lanes=16, page=16, pods=[object()] * len(after),
        step_before=[], step_after=[], compiles_in_window=0,
        memory_peak_bytes=0, model_cfg=None, peaks={}, trace=None,
    )


FILL_CASES = {
    # 3 dispatches of 8 x 128 slots in the window, 330 real tokens
    "one-pod": ([{"dispatches": 10, "tokens_computed": 5000, "token_slots": 40960}],
                [{"dispatches": 13, "tokens_computed": 5330, "token_slots": 44032}],
                100.0 * 330 / 3072),
    # two replicas: the window's tokens over the window's slots, not a mean
    "two-pods": ([{"dispatches": 0, "tokens_computed": 0, "token_slots": 0},
                  {"dispatches": 4, "tokens_computed": 100, "token_slots": 4096}],
                 [{"dispatches": 1, "tokens_computed": 1024, "token_slots": 1024},
                  {"dispatches": 5, "tokens_computed": 228, "token_slots": 7168}],
                 100.0 * (1024 + 128) / (1024 + 3072)),
    # a window without a prefill dispatch: nothing to divide by
    "no-dispatch": ([{"dispatches": 7, "tokens_computed": 900, "token_slots": 8192}],
                    [{"dispatches": 7, "tokens_computed": 900, "token_slots": 8192}],
                    None),
    # the parent: a program that does not count its slots reports nothing
    "parent": ([{"dispatches": 7, "tokens_computed": 900}],
               [{"dispatches": 9, "tokens_computed": 1100}], None),
}


@pytest.mark.parametrize("case", sorted(FILL_CASES))
def test_the_reader_on_hand_made_stats(case):
    before, after, want = FILL_CASES[case]
    got = run.load_layer_metric("prefill_slot_fill_share")(records(before, after))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
        assert 0 < got <= 100


def test_the_scope_entries_read_nothing_without_a_trace():
    for part in ("moe_experts", "moe_router"):
        read = run.load_layer_metric(f"prefill_scope_ms.{part}")
        assert read(records(*FILL_CASES["one-pod"][:2])) is None


def test_stats_carries_the_token_slots():
    rows, bucket, ps = 4, 8, 4
    server = PodServer(PodServerConfig(
        model_name="tiny-qwen3-moe", pod_identifier="fill-pod",
        publish_events=False,
        engine=EngineConfig(
            model=TINY_QWEN3_MOE,
            block_manager=BlockManagerConfig(total_pages=64, page_size=ps),
            scheduler=SchedulerConfig(max_prefill_batch=rows),
            max_model_len=64, decode_batch_size=4, prefill_bucket=bucket,
            interpret=True,
        ),
    ))
    server.start()
    prompt = list(map(int, np.random.default_rng(0).integers(1, 200, 11)))

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            before = (await (await client.get("/stats")).json())["prefill"]
            resp = await client.post(
                "/v1/completions",
                json={"prompt_token_ids": prompt, "max_tokens": 2},
            )
            assert resp.status == 200
            return before, (await (await client.get("/stats")).json())["prefill"]
        finally:
            await client.close()

    try:
        before, after = asyncio.run(scenario())
    finally:
        server.shutdown()
    assert before == {"tokens_computed": 0, "dispatches": 0, "token_slots": 0}
    # one dispatch of the rows computed (one holds a sequence, of the
    # max_prefill_batch the dispatch has room for: PR 42) x the prompt's
    # bucketed width
    assert rows > 1
    assert after == {"tokens_computed": len(prompt), "dispatches": 1,
                     "token_slots": 1 * 2 * bucket}
    read = run.load_layer_metric("prefill_slot_fill_share")
    assert read(records([before], [after])) == pytest.approx(100 * 11 / 16)
