"""KV-capacity observability suite (ISSUE 15 acceptance).

- **Ledger**: tier transitions recorded off the real block-manager hooks
  (allocate / spill / restore / prefetch / demote / import / evict) and
  pinned against the block manager's own counters; bounded ring +
  tracked-state cap; chain-hash filtering.
- **MRC**: the reuse-distance estimator's predicted hit rate EXACTLY
  matches a simulated LRU cache over the same stream (the stack-distance
  theorem, at sample_rate 1.0), stays close under spatial sampling, and
  saturates honestly at the tracking cap.
- **Flight recorder**: bounded rings, causally-ordered trigger
  timelines, rate-limited file dumps, SLO burn-crossing callback
  (edge-triggered, re-arming on recovery).
- **Knobs-off parity**: with ``OBS_LIFECYCLE``/``OBS_FLIGHT`` unset the
  completion response keys, ``/stats`` top-level fields, exposition
  series, emitted KV events, and heartbeat wire bytes are bit-identical
  legacy — and with the knobs ON the wire bytes still are (everything
  derives from in-process hooks; no new wire fields).
- **Fleet acceptance**: a 2-pod demote→pull-back run over the real ZMQ
  fabric whose ledger matches engine ground truth, and a forced SLO-burn
  crossing whose flight dump carries the triggering burn sample, the
  engine steps, and the interleaved fleet events in causal order.
"""

import asyncio
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_d_kv_cache_manager_tpu.kvcache.kvevents.events import (
    BlockRemoved,
    BlockStored,
    EventBatch,
    Heartbeat,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvevents.pool import (
    KVEventsPool,
    KVEventsPoolConfig,
    Message,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock import (
    InMemoryIndex,
    InMemoryIndexConfig,
)
from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
from llm_d_kv_cache_manager_tpu.obs.flight import (
    FlightRecorder,
    debug_flight_payload,
)
from llm_d_kv_cache_manager_tpu.obs.lifecycle import (
    BlockLifecycleLedger,
    ReuseDistanceEstimator,
    debug_lifecycle_payload,
    debug_mrc_payload,
)
from llm_d_kv_cache_manager_tpu.obs.slo import SLObjective, SLORecorder
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

PS = 4
MODEL = "tiny-llama"


def _engine_cfg(total_pages=64, **kw):
    return EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(
            total_pages=total_pages,
            page_size=PS,
            host_pages=kw.pop("host_pages", 0),
        ),
        scheduler=SchedulerConfig(max_prefill_batch=4),
        max_model_len=64,
        decode_batch_size=4,
        prefill_bucket=8,
        interpret=True,
        **kw,
    )


def _pod_config(pod_id, total_pages=64, **kw):
    return PodServerConfig(
        model_name=MODEL,
        pod_identifier=pod_id,
        publish_events=kw.pop("publish_events", False),
        engine=_engine_cfg(
            total_pages=total_pages, host_pages=kw.pop("host_pages", 0)
        ),
        **kw,
    )


def _prompt(seed, n):
    return list(
        map(int, np.random.default_rng(seed).integers(0, TINY_LLAMA.vocab_size, n))
    )


def _wait(cond, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return False


# ---------------------------------------------------------------------------
# Ledger
# ---------------------------------------------------------------------------
class TestLedger:
    def test_transitions_and_residency(self):
        clock = [0.0]
        seen = []
        res = []
        led = BlockLifecycleLedger(
            clock=lambda: clock[0],
            on_transition=lambda f, t, r: seen.append((f, t, r)),
            on_residency=lambda tier, s: res.append((tier, s)),
        )
        led.record(1, "tpu_hbm", "allocate")
        clock[0] = 2.0
        led.record(1, "host_dram", "spill")
        clock[0] = 5.0
        led.record(1, "none", "evict")
        assert seen == [
            ("none", "tpu_hbm", "allocate"),
            ("tpu_hbm", "host_dram", "spill"),
            ("host_dram", "none", "evict"),
        ]
        assert res == [("tpu_hbm", 2.0), ("host_dram", 3.0)]
        assert led.resident_by_tier() == {}
        assert led.transition_counts()["tpu_hbm>host_dram:spill"] == 1

    def test_ring_and_tracked_state_bounded(self):
        led = BlockLifecycleLedger(ring=16, max_tracked=16)
        for h in range(100):
            led.record(h, "tpu_hbm", "allocate")
        assert len(led.recent(limit=1000)) == 16
        snap = led.snapshot()
        assert snap["tracked_blocks"] == 16
        assert snap["tracked_evicted"] == 84
        assert snap["transitions"] == 100

    def test_chain_filter(self):
        led = BlockLifecycleLedger()
        led.record(7, "tpu_hbm", "allocate")
        led.record(8, "tpu_hbm", "allocate")
        led.record(7, "none", "evict")
        rows = led.recent(chain_hash=7)
        assert [r["reason"] for r in rows] == ["allocate", "evict"]
        status, payload = debug_lifecycle_payload(led, {"chain": "7"})
        assert status == 200 and len(payload["recent"]) == 2
        status, _ = debug_lifecycle_payload(led, {"block": "nope"})
        assert status == 400
        status, payload = debug_lifecycle_payload(None, {})
        assert status == 200 and payload == {
            "enabled": False, "recent": [],
        }

    def test_limit_zero_returns_nothing(self):
        led = BlockLifecycleLedger()
        led.record(1, "tpu_hbm", "allocate")
        assert led.recent(limit=0) == []
        assert led.recent(limit=-3) == []

    def test_pod_gone_bulk_ends_residencies(self):
        clock = [0.0]
        res = []
        led = BlockLifecycleLedger(
            clock=lambda: clock[0],
            on_residency=lambda tier, s: res.append((tier, s)),
        )
        led.observe_stored("p0", [1, 2], "tpu_hbm")
        led.observe_stored("p0", [3], "remote")
        led.observe_stored("other", [9], "tpu_hbm")
        clock[0] = 4.0
        led.observe_pod_gone("p0", "drained")
        # Only p0's residencies ended; one summary ring row, not three.
        assert led.resident_by_tier() == {"tpu_hbm": 1}
        assert sorted(res) == [("remote", 4.0), ("tpu_hbm", 4.0),
                               ("tpu_hbm", 4.0)]
        row = led.recent()[-1]
        assert row["reason"] == "drained" and row["blocks"] == 3
        counts = led.transition_counts()
        assert counts["tpu_hbm>none:drained"] == 2
        assert counts["remote>none:drained"] == 1
        # Idempotent: nothing tracked, nothing recorded.
        n = led.transitions
        led.observe_pod_gone("p0", "drained")
        assert led.transitions == n

    def test_end_if_tier_guards_newer_residency(self):
        led = BlockLifecycleLedger()
        led.record(1, "remote", "demote")
        led.record(2, "remote", "demote")
        led.record(2, "tpu_hbm", "allocate")  # re-registered locally
        led.end_if_tier(1, "remote", "demote_failed")
        led.end_if_tier(2, "remote", "demote_failed")  # newer tier stands
        by_tier = led.resident_by_tier()
        assert by_tier == {"tpu_hbm": 1}
        assert led.transition_counts()["remote>none:demote_failed"] == 1

    def test_scorer_event_feed_medium_semantics(self):
        """The spill sequence a pod actually publishes — Stored(host) then
        Removed(tpu_hbm) — must leave the block host-resident; a
        medium-less Removed clears any tier."""
        led = BlockLifecycleLedger()
        led.observe_stored("p0", [1], "tpu_hbm")
        led.observe_stored("p0", [1], "host_dram")  # spill's stored half
        led.observe_removed("p0", [1], "tpu_hbm")  # stale-tier goodbye
        assert led.resident_by_tier() == {"host_dram": 1}
        led.observe_removed("p0", [1], None)  # cleared everywhere
        assert led.resident_by_tier() == {}
        # Per-pod identity: two pods holding the same hash are two rows.
        led.observe_stored("a", [9], "tpu_hbm")
        led.observe_stored("b", [9], "remote")
        assert led.resident_by_tier() == {"tpu_hbm": 1, "remote": 1}


class TestLedgerOnEngine:
    def test_host_tier_transitions_match_block_manager_counters(self):
        """Ground-truth pin: every ledger spill/restore/evict row has a
        matching block-manager counter increment."""
        eng = Engine(_engine_cfg(total_pages=12, host_pages=8,
                                 host_tier_policy="always"))
        led = BlockLifecycleLedger(ring=1 << 14)
        mrc = ReuseDistanceEstimator()
        eng.block_manager.attach_lifecycle(led, mrc)
        for i in range(6):
            eng.add_request(_prompt(i, 16), SamplingParams(max_new_tokens=4))
            eng.run_until_complete()
        # Re-run prompt 0: its chain restores from the host tier.
        eng.add_request(_prompt(0, 16), SamplingParams(max_new_tokens=4))
        eng.run_until_complete()
        counts = {}
        for row in led.recent(limit=1 << 14):
            counts[row["reason"]] = counts.get(row["reason"], 0) + 1
        bm = eng.block_manager
        assert counts.get("spill", 0) == bm.host_stats["spilled"]
        restores = counts.get("restore", 0) + counts.get("prefetch", 0)
        assert restores == bm.host_stats["restored"]
        assert counts.get("evict", 0) == bm.host_stats["host_evicted"]
        assert bm.host_stats["spilled"] > 0  # the run actually tiered
        # Residency view matches the pools exactly.
        by_tier = led.resident_by_tier()
        assert by_tier.get("tpu_hbm", 0) == bm.num_cached_pages
        assert by_tier.get("host_dram", 0) == bm.num_host_cached_pages
        # The MRC saw every allocate walk.
        assert mrc.accesses >= 7 * 4

    def test_rollback_retry_observes_chain_once(self):
        """A scheduler rollback (free + reset + later re-allocate) and a
        preemption re-prefill walk the same chain again — the MRC must
        observe a request's chain once, or retries feed tiny artificial
        reuse distances that bias the curve upward."""
        from llm_d_kv_cache_manager_tpu.server.sequence import Sequence

        eng = Engine(_engine_cfg(total_pages=32))
        mrc = ReuseDistanceEstimator()
        eng.block_manager.attach_lifecycle(None, mrc)
        seq = Sequence(prompt_tokens=_prompt(0, 16))
        eng.block_manager.allocate(seq)
        first = mrc.accesses
        assert first > 0
        # Budget-overflow rollback: pages freed, bookkeeping reset, the
        # sequence re-allocates on a later step.
        eng.block_manager.free_sequence(seq)
        seq.reset_allocation()
        eng.block_manager.allocate(seq)
        assert mrc.accesses == first

    def test_raising_observer_never_fails_the_transition(self):
        def boom(*_a):
            raise RuntimeError("observer kaput")

        led = BlockLifecycleLedger(on_transition=boom, on_residency=boom)
        led.record(1, "tpu_hbm", "allocate")  # must not raise
        led.record(1, "none", "evict")
        led.observe_stored("p", [2], "tpu_hbm")
        led.observe_pod_gone("p", "drained")
        assert led.transitions == 4

    def test_outputs_identical_with_and_without_ledger(self):
        outs = {}
        for attached in (False, True):
            eng = Engine(_engine_cfg(total_pages=12, host_pages=8,
                                     host_tier_policy="always"))
            if attached:
                eng.block_manager.attach_lifecycle(
                    BlockLifecycleLedger(), ReuseDistanceEstimator()
                )
            toks = []
            for i in range(6):
                seq = eng.add_request(
                    _prompt(i, 16), SamplingParams(max_new_tokens=4)
                )
                eng.run_until_complete()
                toks.append(list(seq.generated_tokens))
            outs[attached] = toks
        assert outs[False] == outs[True]


# ---------------------------------------------------------------------------
# MRC
# ---------------------------------------------------------------------------
class TestMRC:
    def _lru_hit_rate(self, stream, capacity):
        from collections import OrderedDict

        cache, hits = OrderedDict(), 0
        for h in stream:
            if h in cache:
                hits += 1
                cache.move_to_end(h)
            else:
                cache[h] = None
                if len(cache) > capacity:
                    cache.popitem(last=False)
        return hits / len(stream)

    def test_exact_match_against_simulated_lru(self):
        """The stack-distance theorem, end to end: predicted_hit_rate(C)
        equals a simulated C-block LRU cache's hit rate on the SAME
        stream, for every C at once — the property the tier-sizing
        validation rests on."""
        rng = np.random.default_rng(3)
        # Zipf-flavored block popularity over 64 distinct blocks.
        stream = [int(h) for h in rng.zipf(1.3, 4000) % 64]
        est = ReuseDistanceEstimator(sample_rate=1.0)
        for h in stream:
            est.observe_chain([h])
        for cap in (1, 2, 4, 8, 16, 32, 64, 128):
            actual = self._lru_hit_rate(stream, cap)
            predicted = est.predicted_hit_rate(cap)
            assert abs(predicted - actual) < 1e-9, (cap, predicted, actual)

    def test_sampling_stays_close(self):
        """SHARDS sampling trades resolution for cost: over a population
        wide enough that the sampled subset is representative, the
        half-rate curve tracks the full curve. (Tiny populations with a
        dominating head are exactly where sampling is noisy — operators
        raise OBS_MRC_SAMPLE there; the default is 1.0.)"""
        rng = np.random.default_rng(7)
        stream = [int(h) for h in rng.zipf(1.2, 50000) % 1024]
        full = ReuseDistanceEstimator(sample_rate=1.0)
        sampled = ReuseDistanceEstimator(sample_rate=0.5)
        for h in stream:
            full.observe_chain([h])
            sampled.observe_chain([h])
        assert sampled.sampled < full.sampled
        for cap in (32, 128, 512):
            assert abs(
                sampled.predicted_hit_rate(cap) - full.predicted_hit_rate(cap)
            ) < 0.08, cap

    def test_exact_across_timestamp_compaction(self):
        """The Fenwick timestamp domain (4x max_tracked) compacts and
        renumbers when exhausted — distances must stay exact straight
        through several compactions."""
        rng = np.random.default_rng(11)
        stream = [int(h) for h in rng.integers(0, 12, 500)]
        est = ReuseDistanceEstimator(sample_rate=1.0, max_tracked=16)
        for h in stream:
            est.observe_chain([h])  # domain 64: compacts ~8 times
        for cap in (1, 2, 4, 8, 16):
            assert est.predicted_hit_rate(cap) == pytest.approx(
                self._lru_hit_rate(stream, cap)
            ), cap

    def test_tracking_cap_reads_as_cold(self):
        est = ReuseDistanceEstimator(sample_rate=1.0, max_tracked=16)
        # 32 distinct blocks cycled twice: true distance 31, but the
        # 16-deep stack forgets — the second pass must read cold, never
        # a made-up finite distance.
        for _ in range(2):
            for h in range(32):
                est.observe_chain([h])
        assert est.capped > 0
        assert est.predicted_hit_rate(1 << 20) <= 0.5
        snap = est.snapshot()
        assert snap["tracked_blocks"] <= 16

    def test_distance_callback_and_payload(self):
        dists = []
        est = ReuseDistanceEstimator(on_distance=dists.append)
        est.observe_chain([1, 2, 1])
        assert dists == [float("inf"), float("inf"), 1.0]
        payload = debug_mrc_payload(est, tier_capacities={"tpu_hbm": 4})[1]
        assert payload["enabled"] is True
        assert payload["tiers"]["tpu_hbm"]["predicted_hit_rate"] is not None
        assert debug_mrc_payload(None) == (200, {"enabled": False})

    def test_bad_sample_rate_rejected(self):
        with pytest.raises(ValueError):
            ReuseDistanceEstimator(sample_rate=0.0)
        with pytest.raises(ValueError):
            ReuseDistanceEstimator(sample_rate=1.5)


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_step_deltas_and_ring_bound(self):
        clock = [100.0]
        fr = FlightRecorder(ring=16, clock=lambda: clock[0])
        stats = {"steps": 0, "prefill_s": 0.0, "decode_s": 0.0}
        for i in range(1, 40):
            stats = {"steps": i, "prefill_s": 0.5 * i, "decode_s": 0.25 * i}
            clock[0] += 1.0
            fr.record_step(stats, occupancy=0.5, free_pages=7)
        snap = fr.snapshot()
        assert snap["steps_recorded"] == 39
        assert snap["steps_buffered"] == 16
        # Idle loop (no new engine step) records nothing.
        fr.record_step(stats)
        assert fr.snapshot()["steps_recorded"] == 39

    def test_trigger_timeline_causally_ordered(self, tmp_path):
        clock = [10.0]
        fr = FlightRecorder(
            ring=64, out_dir=str(tmp_path), pod="p0",
            clock=lambda: clock[0],
        )
        fr.record_step({"steps": 1, "prefill_s": 0.1}, free_pages=3)
        clock[0] = 11.0
        fr.record_event("breaker", endpoint="tcp://x", state="open")
        clock[0] = 12.0
        fr.record_step({"steps": 2, "prefill_s": 0.2})
        clock[0] = 13.0
        path = fr.trigger("slo_burn", objective="ttft", rate=9.0)
        assert path is not None
        timeline = fr.timeline()
        ts = [e["t"] for e in timeline["entries"]]
        assert ts == sorted(ts)
        kinds = [e["kind"] for e in timeline["entries"]]
        assert kinds == ["step", "breaker", "step", "trigger:slo_burn"]
        # The dump file holds the same causally-ordered payload.
        import json

        with open(path) as f:
            on_disk = json.load(f)
        assert on_disk["reason"] == "slo_burn"
        assert [e["kind"] for e in on_disk["entries"]] == kinds
        payload = debug_flight_payload(fr)[1]
        assert payload["enabled"] and payload["timeline"]["reason"] == "slo_burn"

    def test_dump_rate_limited_per_reason(self, tmp_path):
        clock = [0.0]
        fr = FlightRecorder(
            out_dir=str(tmp_path), min_dump_interval_s=5.0,
            clock=lambda: clock[0],
        )
        assert fr.trigger("resync") is not None
        clock[0] = 1.0
        assert fr.trigger("resync") is None  # rate-limited
        assert fr.trigger("breaker_open") is not None  # other reason free
        clock[0] = 6.0
        assert fr.trigger("resync") is not None
        assert fr.snapshot()["triggers"] == 4

    def test_no_dir_keeps_timeline_in_memory(self):
        fr = FlightRecorder()
        assert fr.trigger("resync") is None
        assert fr.timeline()["reason"] == "resync"


class TestSLOBurnCallback:
    def test_edge_triggered_and_rearms(self):
        clock = [0.0]
        fired = []
        rec = SLORecorder(
            [SLObjective(metric="ttft", threshold_s=0.1, target=0.9)],
            windows_s=(60.0,),
            clock=lambda: clock[0],
            on_burn=lambda o, w, r: fired.append((o, w, r)),
            burn_threshold=1.0,
        )
        rec.observe(1.0, None)  # violation: burn = 1.0/0.1 = 10x
        assert len(fired) == 1 and fired[0][2] >= 1.0
        clock[0] = 2.0
        rec.observe(1.0, None)  # still burning: edge, no re-fire
        assert len(fired) == 1
        # Recovery: the window ages the violations out, an OK request
        # re-arms, the next violation fires again.
        clock[0] = 70.0
        rec.observe(0.01, None)
        assert len(fired) == 1
        clock[0] = 72.0
        rec.observe(1.0, None)
        assert len(fired) == 2
        assert rec.burn_crossings == 2

    def test_throttled_between_checks(self):
        clock = [0.0]
        fired = []
        rec = SLORecorder(
            [SLObjective(metric="ttft", threshold_s=0.1, target=0.5)],
            windows_s=(60.0,),
            clock=lambda: clock[0],
            on_burn=lambda *a: fired.append(a),
            burn_threshold=1.0,
            burn_check_interval_s=10.0,
        )
        rec.observe(0.01, None)  # ok; arms the throttle window
        rec.observe(1.0, None)  # within throttle: not evaluated
        assert fired == []
        clock[0] = 11.0
        rec.observe(1.0, None)  # next check due: fires
        assert len(fired) == 1

    def test_no_callback_is_legacy(self):
        rec = SLORecorder(
            [SLObjective(metric="ttft", threshold_s=0.1, target=0.9)]
        )
        rec.observe(1.0, None)  # no burn machinery touched
        assert rec.burn_crossings == 0


# ---------------------------------------------------------------------------
# Scorer-side feed through the events pool
# ---------------------------------------------------------------------------
class TestScorerPoolFeed:
    def _msg(self, events, pod="pod-a", seq=0):
        return Message(
            topic=f"kv@{pod}@{MODEL}",
            pod_identifier=pod,
            model_name=MODEL,
            payload=EventBatch(ts=0.0, events=list(events)).to_payload(),
            seq=seq,
        )

    def test_pool_feeds_ledger(self):
        led = BlockLifecycleLedger()
        pool = KVEventsPool(
            InMemoryIndex(InMemoryIndexConfig()),
            KVEventsPoolConfig(concurrency=1),
            lifecycle=led,
        )
        pool.start()
        try:
            pool.add_task(
                self._msg(
                    [
                        BlockStored(
                            block_hashes=[1, 2],
                            parent_block_hash=None,
                            token_ids=list(range(PS)),
                            block_size=PS,
                            medium="tpu_hbm",
                        ),
                        BlockRemoved(block_hashes=[1], medium="tpu_hbm"),
                    ]
                )
            )
            assert pool.drain(timeout=5.0)
        finally:
            pool.shutdown()
        assert led.resident_by_tier() == {"tpu_hbm": 1}
        counts = led.transition_counts()
        assert counts["none>tpu_hbm:stored"] == 2
        assert counts["tpu_hbm>none:removed"] == 1

    def test_pool_without_ledger_is_legacy(self):
        pool = KVEventsPool(
            InMemoryIndex(InMemoryIndexConfig()), KVEventsPoolConfig()
        )
        assert pool.lifecycle is None

    def test_pod_drained_ends_ledger_residencies(self):
        from llm_d_kv_cache_manager_tpu.kvcache.kvevents.events import (
            PodDrained,
        )

        led = BlockLifecycleLedger()
        pool = KVEventsPool(
            InMemoryIndex(InMemoryIndexConfig()),
            KVEventsPoolConfig(concurrency=1),
            lifecycle=led,
        )
        pool.start()
        try:
            pool.add_task(
                self._msg(
                    [
                        BlockStored(
                            block_hashes=[1, 2],
                            parent_block_hash=None,
                            token_ids=list(range(PS)),
                            block_size=PS,
                            medium="tpu_hbm",
                        )
                    ]
                )
            )
            assert pool.drain(timeout=5.0)
            assert led.resident_by_tier() == {"tpu_hbm": 2}
            pool.add_task(self._msg([PodDrained()], seq=1))
            assert pool.drain(timeout=5.0)
        finally:
            pool.shutdown()
        # The drained pod left the ledger too (the index-eviction mirror).
        assert led.resident_by_tier() == {}
        assert led.transition_counts()["tpu_hbm>none:drained"] == 2

    def test_demote_queue_drop_corrects_ledger(self):
        """The pusher's drop-oldest overflow is plain eviction: the
        optimistic `demote` record is corrected with `demote_failed` so
        phantom remote residency never accumulates."""

        class _Payload:
            def __init__(self, h):
                self.block_hash = h

        server = PodServer(
            _pod_config(
                "drop-pod",
                remote_tier=True,
                remote_peers="tcp://127.0.0.1:1",
                remote_demote_queue=1,
                obs_lifecycle=True,
            )
        )
        try:
            led = server.lifecycle
            led.record(11, "remote", "demote")
            led.record(12, "remote", "demote")
            server._stage_demotions([_Payload(11), _Payload(12)])
            # Queue cap 1: payload 11 dropped — its residency ends.
            assert server.demote_dropped == 1
            assert led.resident_by_tier() == {"remote": 1}
            counts = led.transition_counts()
            assert counts["remote>none:demote_failed"] == 1
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# Knobs-off parity
# ---------------------------------------------------------------------------
class TestKnobsOffParity:
    def _run(self, scenario, **cfg_kw):
        server = PodServer(_pod_config("parity-pod", **cfg_kw))
        server.start()

        async def runner():
            ts = TestServer(server.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                await scenario(client, server)
            finally:
                await client.close()

        try:
            asyncio.run(runner())
        finally:
            server.shutdown()

    def test_stats_and_response_keys_pinned(self):
        async def scenario(c, server):
            resp = await c.post(
                "/v1/completions",
                json={"prompt_token_ids": _prompt(0, 10), "max_tokens": 3},
            )
            assert resp.status == 200
            data = await resp.json()
            assert set(data) == {
                "id", "object", "model", "choices", "usage", "ttft_s",
                "queue_s", "staged_s",
            }
            resp = await c.get("/stats")
            stats = await resp.json()
            assert set(stats) == {
                "pod", "model", "data_parallel_rank", "staged", "waiting",
                "running", "free_pages", "total_pages", "kv_bytes_per_token",
                "state_bytes_per_token", "window_bytes_per_token", "window_pages",
                "window_pages_held", "routed_layers",
                "experts_held", "zero_experts", "prefill",
                "transfer", "self_heal", "admission", "drain",
            }

        self._run(scenario)

    def test_debug_endpoints_report_disabled(self):
        async def scenario(c, server):
            resp = await c.get("/debug/lifecycle")
            assert resp.status == 200
            assert await resp.json() == {"enabled": False, "recent": []}
            resp = await c.get("/debug/mrc")
            assert await resp.json() == {"enabled": False}
            resp = await c.get("/debug/flight")
            assert await resp.json() == {"enabled": False}

        self._run(scenario)

    def test_no_new_exposition_series_knobs_off(self):
        pytest.importorskip("prometheus_client")
        server = PodServer(_pod_config("parity-pod-m", obs_metrics=True))
        try:
            text = server.metrics.exposition().decode()
            assert "kvcache_block_tier_transitions_total" not in text
            assert "kvcache_block_tier_residency_seconds" not in text
            assert "kvcache_reuse_distance_blocks" not in text
        finally:
            server.shutdown()

    def test_knobs_off_no_hooks_attached(self):
        server = PodServer(_pod_config("parity-pod-h"))
        try:
            bm = server.engine.block_manager
            assert bm._lifecycle is None and bm._mrc is None
            assert server.lifecycle is None and server.mrc is None
            assert server.flight is None
            assert not server.engine.obs_step_timing
        finally:
            server.shutdown()

    def test_wire_bytes_identical_knobs_on(self):
        """No new wire fields: the events a knobs-ON pod emits and the
        heartbeat it publishes are byte-identical to a knobs-off pod's."""

        class _Rec:
            dropped_batches = 0

            def __init__(self):
                self.events = []

            def publish(self, events):
                self.events.extend(events)

            def close(self):
                pass

        emitted = {}
        heartbeats = {}
        for on in (False, True):
            rec = _Rec()
            kw = (
                dict(
                    obs_lifecycle=True,
                    obs_flight=True,
                    obs_slo="ttft:0.5:0.99",
                )
                if on
                else {}
            )
            server = PodServer(
                _pod_config(f"wire-{on}", publish_events=True, **kw),
                publisher=rec,
            )
            server.start()
            try:
                server.generate(
                    _prompt(3, 12), SamplingParams(max_new_tokens=3),
                    timeout=120,
                )
                server._publish_heartbeat()
            finally:
                server.shutdown()
            emitted[on] = EventBatch(
                ts=0.0,
                events=[e for e in rec.events if not isinstance(e, Heartbeat)],
            ).to_payload()
            heartbeats[on] = EventBatch(
                ts=0.0,
                events=[e for e in rec.events if isinstance(e, Heartbeat)],
            ).to_payload()
        assert emitted[True] == emitted[False]
        assert heartbeats[True] == heartbeats[False]

    def test_outputs_identical_knobs_on_vs_off(self):
        outs = {}
        for on in (False, True):
            kw = (
                dict(obs_lifecycle=True, obs_flight=True)
                if on
                else {}
            )
            server = PodServer(_pod_config(f"out-{on}", total_pages=16, **kw))
            server.start()
            try:
                toks = []
                for i in range(4):
                    seq = server.generate(
                        _prompt(i, 12), SamplingParams(max_new_tokens=3),
                        timeout=120,
                    )
                    toks.append(list(seq.generated_tokens))
                outs[on] = toks
            finally:
                server.shutdown()
        assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# Pod surfaces with the knobs on
# ---------------------------------------------------------------------------
class TestPodSurfaces:
    def test_lifecycle_mrc_stats_and_endpoints(self):
        server = PodServer(
            _pod_config("obs-pod", total_pages=16, obs_lifecycle=True)
        )
        server.start()

        async def scenario():
            ts = TestServer(server.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                for i in range(3):
                    await client.post(
                        "/v1/completions",
                        json={
                            "prompt_token_ids": _prompt(0, 12),
                            "max_tokens": 2,
                        },
                    )
                resp = await client.get("/stats")
                stats = await resp.json()
                assert stats["lifecycle"]["transitions"] > 0
                assert stats["lifecycle"]["mrc"]["accesses"] > 0
                resp = await client.get("/debug/lifecycle")
                data = await resp.json()
                assert data["enabled"] and data["recent"]
                assert data["transitions"] > 0
                resp = await client.get("/debug/mrc")
                mrc = await resp.json()
                assert mrc["enabled"]
                assert mrc["tiers"]["tpu_hbm"]["capacity_blocks"] == 15
                # The repeated prompt's blocks have small reuse distance:
                # the curve must predict a hit at HBM capacity.
                assert mrc["tiers"]["tpu_hbm"]["predicted_hit_rate"] > 0
            finally:
                await client.close()

        try:
            asyncio.run(scenario())
        finally:
            server.shutdown()

    def test_lifecycle_exposition_series(self):
        pytest.importorskip("prometheus_client")
        server = PodServer(
            _pod_config("obs-pod-m", total_pages=16, obs_lifecycle=True)
        )
        server.start()
        try:
            for i in range(2):
                server.generate(
                    _prompt(0, 12), SamplingParams(max_new_tokens=2),
                    timeout=120,
                )
            text = server.metrics.exposition().decode()
            assert (
                'kvcache_block_tier_transitions_total{from="none",'
                'reason="allocate",to="tpu_hbm"}' in text
            )
            assert "kvcache_reuse_distance_blocks_bucket" in text
        finally:
            server.shutdown()

    def test_flight_records_steps(self):
        server = PodServer(
            _pod_config("flight-pod", total_pages=32, obs_flight=True)
        )
        server.start()
        try:
            assert server.engine.obs_step_timing  # implied by the knob
            server.generate(
                _prompt(1, 12), SamplingParams(max_new_tokens=3), timeout=120
            )
            assert _wait(
                lambda: server.flight.snapshot()["steps_recorded"] > 0
            )
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# Fleet acceptance
# ---------------------------------------------------------------------------
class TestFleetAcceptance:
    def test_demote_pull_back_ledger_matches_engine_truth(self):
        """2-pod fleet over the real ZMQ fabric: the demoter's ledger
        tells the same story its engine counters do, and a demoted→
        pulled-back chain shows the full arc (allocate → demote →
        import)."""
        from conftest import free_tcp_port

        endpoint = f"tcp://127.0.0.1:{free_tcp_port()}"
        holder = PodServer(
            _pod_config(
                "kv-holder",
                transfer_endpoint=endpoint,
                pod_role="kvstore",
                remote_tier=True,
                remote_store_pages=128,
            )
        )
        demoter = PodServer(
            _pod_config(
                "demoter",
                total_pages=12,
                remote_tier=True,
                remote_peers=endpoint,
                obs_lifecycle=True,
            )
        )
        holder.start()
        demoter.start()
        try:
            outs = {}
            for i in range(5):
                seq = demoter.generate(
                    _prompt(i, 16), SamplingParams(max_new_tokens=4),
                    timeout=60,
                )
                outs[i] = list(seq.generated_tokens)
            assert _wait(
                lambda: holder.engine.remote_store is not None
                and len(holder.engine.remote_store) > 0
            ), "demotions never reached the holder"
            hashes = demoter.engine.block_manager.token_db.prefix_hashes(
                _prompt(0, 16)
            )
            _wait(
                lambda: any(
                    h in holder.engine.remote_store for h in hashes[:1]
                )
            )
            pulled = 0
            if any(h in holder.engine.remote_store for h in hashes[:1]):
                pulled = demoter.pull_prefix(_prompt(0, 16), endpoint)
                assert pulled >= 1
            seq = demoter.generate(
                _prompt(0, 16), SamplingParams(max_new_tokens=4), timeout=60
            )
            assert list(seq.generated_tokens) == outs[0]

            led = demoter.lifecycle
            counts = {}
            for row in led.recent(limit=1 << 20):
                counts[row["reason"]] = counts.get(row["reason"], 0) + 1
            eng = demoter.engine
            # Ledger vs engine ground truth, transition class by class.
            assert counts.get("demote", 0) == eng.remote_stats[
                "demoted_blocks"
            ] + len(eng._pending_demotions)
            assert counts.get("import", 0) == eng.transfer_stats[
                "imported_blocks"
            ]
            assert counts["demote"] > 0 and counts.get("import", 0) >= pulled
            assert led.resident_by_tier().get("tpu_hbm", 0) == (
                eng.block_manager.num_cached_pages
            )
            # The pulled-back chain's full arc: registered, demoted on
            # eviction, re-imported.
            if pulled:
                reasons = [
                    r["reason"] for r in led.recent(chain_hash=hashes[0])
                ]
                assert reasons[0] == "allocate"
                assert "demote" in reasons and "import" in reasons
                assert reasons.index("demote") < reasons.index("import")
        finally:
            demoter.shutdown()
            holder.shutdown()

    def test_forced_burn_dumps_causal_timeline(self, tmp_path):
        """2-pod fleet, impossible SLO: the crossing dumps a timeline
        holding the triggering burn sample, the engine steps, and the
        interleaved fleet events (breaker OPEN on the dead peer), all in
        causal order."""
        from conftest import free_tcp_port

        dead = f"tcp://127.0.0.1:{free_tcp_port()}"  # nothing listens
        a = PodServer(
            _pod_config(
                "burn-a",
                obs_flight=True,
                obs_flight_dir=str(tmp_path),
                obs_slo="ttft:0.000001:0.99",  # every request violates
                transfer_breaker_failures=1,
                transfer_timeout_s=0.3,
            )
        )
        b = PodServer(_pod_config("burn-b"))
        a.start()
        b.start()
        try:
            b.generate(
                _prompt(9, 12), SamplingParams(max_new_tokens=2), timeout=120
            )
            # Step telemetry + a real fleet event: the pull to the dead
            # peer fails, the breaker opens, the open rides the ring.
            a.generate(
                _prompt(1, 12), SamplingParams(max_new_tokens=2), timeout=120
            )
            assert a.pull_prefix(_prompt(2, 12), dead) == 0
            assert _wait(
                lambda: any(
                    e["kind"] == "breaker"
                    for e in (a.flight.timeline() or {}).get("entries", [])
                )
                or any(
                    e["kind"] == "breaker" for e in a.flight._events
                )
            )
            # The burn crossing (throttle window expired on the second
            # request ≥1 s later, or already fired on the first).
            deadline = time.monotonic() + 10
            while (
                a.slo.burn_crossings == 0 and time.monotonic() < deadline
            ):
                a.generate(
                    _prompt(3, 12), SamplingParams(max_new_tokens=2),
                    timeout=120,
                )
                time.sleep(0.3)
            assert a.slo.burn_crossings >= 1
            timeline = a.flight.timeline()
            assert timeline is not None
            entries = timeline["entries"]
            ts = [e["t"] for e in entries]
            assert ts == sorted(ts), "timeline not causally ordered"
            kinds = {e["kind"] for e in entries}
            assert "slo_burn" in kinds, kinds  # the triggering sample
            assert "step" in kinds, kinds  # engine telemetry
            assert "breaker" in kinds, kinds  # interleaved fleet event
            # The dump landed on disk.
            dumps = list(tmp_path.glob("flight-*.json"))
            assert dumps, "no flight dump written"
        finally:
            a.shutdown()
            b.shutdown()
