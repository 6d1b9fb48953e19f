"""Routing-quality observability suite (ISSUE 10 acceptance).

The audit plane closes the loop between prediction and reality:

- **Staleness probes**: publish→index-visibility lag per (pod, event
  type) from the EventBatch timestamps the wire already carries, plus a
  per-pod events-behind gauge from the subscriber seq numbers.
- **Route audit**: the router records predicted matched blocks + the
  scoreboard per request id; the pod reports realized prefix-cache hits
  via a trailing-append ``RequestAudit`` KV event; the ``RouteAuditor``
  joins them (ratio, regret, bounded ring at ``/debug/audit``).
- **Miss attribution**: realized < predicted is classified with current
  index + fleet-health state: ``stale_index`` / ``evicted_on_pod`` /
  ``never_stored`` / ``dead_pod_reroute``.
- **SLO burn-rate recording**: ``OBS_SLO`` objectives evaluated
  in-process over sliding windows.
- **Knobs-off parity** (the hard contract): with ``OBS_AUDIT``/``OBS_SLO``
  unset — response keys, ``/stats`` key sets, heartbeat + transfer +
  KV-event wire bytes, and the pod's published event stream are
  bit-identical legacy.
- **Fleet acceptance**: a 2-pod in-process fleet joins predicted ==
  realized on a warm route end to end (real engines, real event wire),
  and a forced eviction after scoring attributes ``stale_index``.
"""

import asyncio
import time

import msgpack
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from chaos import ChaosLink
from llm_d_kv_cache_manager_tpu.kvcache import (
    BlendedRouter,
    KVCacheIndexer,
    KVCacheIndexerConfig,
    PrefixAffinityTracker,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock import (
    ChunkedTokenDatabase,
    TokenProcessorConfig,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.in_memory import InMemoryIndex
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.keys import Key, PodEntry
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import (
    BlockRemoved,
    EventBatch,
    FleetHealth,
    FleetHealthConfig,
    Heartbeat,
    KVEventsPool,
    KVEventsPoolConfig,
    Message,
    RequestAudit,
    decode_event_batch,
)
from llm_d_kv_cache_manager_tpu.kvcache.transfer import encode_request
from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
from llm_d_kv_cache_manager_tpu.obs.audit import (
    RouteAuditor,
    StalenessTracker,
    debug_audit_payload,
    debug_staleness_payload,
)
from llm_d_kv_cache_manager_tpu.obs.slo import (
    SLORecorder,
    parse_slo_spec,
    parse_windows,
)
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.serve import PodServer, PodServerConfig

PS = 4
MODEL = "tiny-llama"


def _engine_config(total_pages=64):
    return EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(total_pages=total_pages, page_size=PS),
        scheduler=SchedulerConfig(max_prefill_batch=4),
        max_model_len=64,
        decode_batch_size=4,
        prefill_bucket=8,
        interpret=True,
    )


def _pod_config(pod_id, **kw):
    return PodServerConfig(
        model_name=MODEL,
        pod_identifier=pod_id,
        publish_events=kw.pop("publish_events", False),
        engine=_engine_config(total_pages=kw.pop("total_pages", 64)),
        **kw,
    )


def _prompt(seed, n):
    return list(
        map(int, np.random.default_rng(seed).integers(0, TINY_LLAMA.vocab_size, n))
    )


def _keys(hashes, model=MODEL):
    return [Key(model_name=model, chunk_hash=h) for h in hashes]


def _entries(pods):
    return [PodEntry(pod_identifier=p, device_tier="tpu_hbm") for p in pods]


# ---------------------------------------------------------------------------
# Wire format
# ---------------------------------------------------------------------------


class TestRequestAuditWire:
    def test_round_trip(self):
        payload = EventBatch(
            ts=1.5, events=[RequestAudit(request_id="r-1", realized_blocks=7)]
        ).to_payload()
        batch = decode_event_batch(payload)
        (ev,) = batch.events
        assert isinstance(ev, RequestAudit)
        assert ev.request_id == "r-1" and ev.realized_blocks == 7

    def test_wire_bytes_are_trailing_append(self):
        payload = EventBatch(
            ts=1.0, events=[RequestAudit("rid", 3)]
        ).to_payload()
        assert payload == msgpack.packb(
            [1.0, [["RequestAudit", "rid", 3]]], use_bin_type=True
        )

    def test_malformed_fields_tolerated(self):
        raw = msgpack.packb([1.0, [["RequestAudit", 42, "x"]]], use_bin_type=True)
        (ev,) = decode_event_batch(raw).events
        assert ev.request_id == "" and ev.realized_blocks == 0

    def test_legacy_event_bytes_unchanged(self):
        """The PR adds a NEW tag; every pre-existing event's bytes are
        untouched (heartbeat + KV-event wire parity pin)."""
        assert EventBatch(
            ts=1.0, events=[Heartbeat(dropped_batches=3)]
        ).to_payload() == msgpack.packb(
            [1.0, [["Heartbeat", 3]]], use_bin_type=True
        )
        assert EventBatch(
            ts=1.0, events=[BlockRemoved(block_hashes=[5])]
        ).to_payload() == msgpack.packb(
            [1.0, [["BlockRemoved", [5], None]]], use_bin_type=True
        )

    def test_transfer_request_bytes_unchanged(self):
        assert encode_request("m", [1, 2], 8) == msgpack.packb(
            ["FetchBlocks", "m", [1, 2], 8], use_bin_type=True
        )


# ---------------------------------------------------------------------------
# StalenessTracker
# ---------------------------------------------------------------------------


class TestStalenessTracker:
    def test_lag_recorded_per_pod_and_event(self):
        now = [100.0]
        t = StalenessTracker(clock=lambda: now[0])
        t.observe_batch("pa", 1, 99.9, ["BlockStored", "BlockStored"])
        t.observe_batch("pb", 1, 99.0, ["Heartbeat"])
        snap = t.snapshot()
        assert snap["events_observed"] == 3
        assert abs(snap["max_lag_s"] - 1.0) < 1e-9
        d = t.detail()
        assert d["per_pod_event"]["pa/BlockStored"]["count"] == 2
        assert d["per_pod_event"]["pb/Heartbeat"]["count"] == 1

    def test_zero_ts_records_nothing(self):
        t = StalenessTracker(clock=lambda: 100.0)
        t.observe_batch("pa", 1, 0.0, ["BlockStored"])
        assert t.snapshot()["events_observed"] == 0

    def test_clock_skew_clamps_to_zero(self):
        t = StalenessTracker(clock=lambda: 100.0)
        t.observe_batch("pa", 1, 100.5, ["BlockStored"])  # publisher ahead
        assert t.snapshot()["max_lag_s"] == 0.0

    def test_events_behind_from_seq_high_waters(self):
        t = StalenessTracker(clock=lambda: 0.0)
        t.observe_received("pa", 5)
        t.observe_received("pa", 9)
        t.observe_batch("pa", 7, 0.0, [])
        assert t.events_behind() == {"pa": 2}
        t.observe_batch("pa", 9, 0.0, [])
        assert t.events_behind() == {"pa": 0}

    def test_events_behind_counts_enqueued_before_first_apply(self):
        # Cold-start storm: the subscriber enqueues a burst the shard
        # worker hasn't touched — the gauge must read the backlog, not 0
        # (the applied high-water seeds one below the first seq seen).
        t = StalenessTracker(clock=lambda: 0.0)
        t.observe_received("pa", 0)
        t.observe_received("pa", 4)
        assert t.events_behind() == {"pa": 5}
        t.observe_batch("pa", 4, 0.0, [])
        assert t.events_behind() == {"pa": 0}

    def test_percentiles(self):
        now = [10.0]
        t = StalenessTracker(clock=lambda: now[0])
        for lag in (0.01, 0.02, 0.03, 0.04, 1.0):
            t.observe_batch("pa", 1, now[0] - lag, ["BlockStored"])
        p = t.percentiles()
        assert 0.02 <= p["p50"] <= 0.04
        assert p["p99"] == 1.0

    def test_pool_integration_observes_wire_batches(self):
        idx = InMemoryIndex()
        now = [50.0]
        tracker = StalenessTracker(clock=lambda: now[0])
        pool = KVEventsPool(
            idx, KVEventsPoolConfig(concurrency=1), staleness=tracker
        )
        pool.start()
        try:
            from llm_d_kv_cache_manager_tpu.kvcache.kvevents import BlockStored

            payload = EventBatch(
                ts=49.9,
                events=[BlockStored(block_hashes=[1, 2], block_size=PS)],
            ).to_payload()
            pool.add_task(
                Message(
                    topic=f"kv@pa@{MODEL}",
                    pod_identifier="pa",
                    model_name=MODEL,
                    payload=payload,
                    seq=3,
                )
            )
            assert pool.drain(timeout=5.0)
        finally:
            pool.shutdown()
        snap = tracker.snapshot()
        assert snap["events_observed"] == 1
        assert abs(snap["max_lag_s"] - 0.1) < 1e-6
        assert tracker.events_behind() == {"pa": 0}
        # The index itself saw the blocks — observation never filters.
        assert idx.lookup(_keys([1, 2]), None)

    def test_unattached_pool_has_no_tracker(self):
        pool = KVEventsPool(InMemoryIndex(), KVEventsPoolConfig(concurrency=1))
        assert pool.staleness is None and pool.audit is None


# ---------------------------------------------------------------------------
# RouteAuditor
# ---------------------------------------------------------------------------


class TestRouteAuditor:
    def test_exact_prediction_joins_with_ratio_one_and_no_cause(self):
        a = RouteAuditor()
        a.record_decision(
            "r1", chosen_pod="pa", predicted_blocks=4,
            scoreboard={"pa": 4, "pb": 2},
        )
        rec = a.record_realized("r1", "pa", 4)
        assert rec.ratio == 1.0 and rec.cause is None
        assert rec.regret_blocks == 0
        snap = a.snapshot()
        assert snap["joined"] == 1 and snap["pending"] == 0
        assert all(v == 0 for v in snap["miss_causes"].values())

    def test_regret_is_best_minus_chosen(self):
        a = RouteAuditor()
        a.record_decision(
            "r1", chosen_pod="pb", predicted_blocks=2,
            scoreboard={"pa": 6, "pb": 2}, decision="cold",
        )
        rec = a.record_realized("r1", "pb", 2)
        assert rec.regret_blocks == 4 and rec.decision == "cold"

    def test_unmatched_realized_counted(self):
        a = RouteAuditor()
        assert a.record_realized("nope", "pa", 1) is None
        assert a.snapshot()["unmatched_realized"] == 1

    def test_pending_cap_evicts_oldest(self):
        a = RouteAuditor(pending_cap=2)
        for i in range(3):
            a.record_decision(
                f"r{i}", chosen_pod="pa", predicted_blocks=1,
                scoreboard={"pa": 1},
            )
        assert a.snapshot()["pending"] == 2
        assert a.snapshot()["pending_evicted"] == 1
        assert a.record_realized("r0", "pa", 1) is None  # evicted

    def test_ring_is_bounded(self):
        a = RouteAuditor(ring=2)
        for i in range(5):
            a.record_decision(
                f"r{i}", chosen_pod="pa", predicted_blocks=1,
                scoreboard={"pa": 1},
            )
            a.record_realized(f"r{i}", "pa", 1)
        assert len(a.recent(limit=10)) == 2

    # -- miss attribution ----------------------------------------------------
    def _warm_index(self, hashes, pod="pa"):
        idx = InMemoryIndex()
        idx.add(_keys(hashes), _entries([pod]))
        return idx

    def test_attribution_dead_pod_reroute_on_pod_mismatch(self):
        a = RouteAuditor()
        a.record_decision(
            "r1", chosen_pod="pa", predicted_blocks=4, scoreboard={"pa": 4}
        )
        rec = a.record_realized("r1", "pb", 0)
        assert rec.cause == "dead_pod_reroute"

    def test_attribution_dead_pod_reroute_on_unroutable_pod(self):
        fh = FleetHealth(FleetHealthConfig())
        fh.observe_drained("pa")
        a = RouteAuditor(fleet_health=fh)
        a.record_decision(
            "r1", chosen_pod="pa", predicted_blocks=4, scoreboard={"pa": 4}
        )
        rec = a.record_realized("r1", "pa", 0)
        assert rec.cause == "dead_pod_reroute"

    def test_attribution_never_stored_when_index_never_claimed(self):
        a = RouteAuditor(index=InMemoryIndex())
        # Prediction came from affinity memory: index_blocks=0.
        a.record_decision(
            "r1", chosen_pod="pa", predicted_blocks=4, index_blocks=0,
            scoreboard={}, chain_hashes=(1, 2, 3, 4),
        )
        rec = a.record_realized("r1", "pa", 0)
        assert rec.cause == "never_stored"

    def test_attribution_stale_index_when_entries_evicted_after_scoring(self):
        hashes = (1, 2, 3, 4)
        idx = self._warm_index(hashes)
        a = RouteAuditor(index=idx, model_name=MODEL)
        a.record_decision(
            "r1", chosen_pod="pa", predicted_blocks=4,
            scoreboard={"pa": 4}, chain_hashes=hashes,
        )
        # The eviction lands AFTER scoring (the forced-eviction regime):
        # the index catches up before the realized report arrives.
        for h in hashes[2:]:
            idx.evict(_keys([h])[0], _entries(["pa"]))
        rec = a.record_realized("r1", "pa", 2)
        assert rec.cause == "stale_index"

    def test_attribution_evicted_on_pod_when_index_still_claims(self):
        hashes = (1, 2, 3, 4)
        idx = self._warm_index(hashes)
        a = RouteAuditor(index=idx, model_name=MODEL)
        a.record_decision(
            "r1", chosen_pod="pa", predicted_blocks=4,
            scoreboard={"pa": 4}, chain_hashes=hashes,
        )
        # Index unchanged, pod truth short: phantom locality.
        rec = a.record_realized("r1", "pa", 2)
        assert rec.cause == "evicted_on_pod"

    def test_attribution_without_probe_degrades_to_stale_index(self):
        a = RouteAuditor()  # no index attached
        a.record_decision(
            "r1", chosen_pod="pa", predicted_blocks=4, scoreboard={"pa": 4}
        )
        rec = a.record_realized("r1", "pa", 1)
        assert rec.cause == "stale_index"

    # -- debug payloads ------------------------------------------------------
    def test_debug_audit_payload_filters_and_bad_limit(self):
        a = RouteAuditor()
        a.record_decision(
            "r1", chosen_pod="pa", predicted_blocks=1, scoreboard={"pa": 1},
            trace_id="t1",
        )
        a.record_realized("r1", "pa", 1)
        status, payload = debug_audit_payload(a, {})
        assert status == 200 and len(payload["audits"]) == 1
        status, payload = debug_audit_payload(a, {"request_id": "zz"})
        assert payload["audits"] == []
        status, payload = debug_audit_payload(a, {"trace_id": "t1"})
        assert len(payload["audits"]) == 1
        status, _ = debug_audit_payload(a, {"limit": "bogus"})
        assert status == 400
        status, payload = debug_audit_payload(None, {})
        assert status == 200 and payload == {"enabled": False, "audits": []}

    def test_debug_staleness_payload_disabled_without_tracker(self):
        assert debug_staleness_payload(None, {}) == (200, {"enabled": False})
        t = StalenessTracker(clock=lambda: 1.0)
        assert debug_staleness_payload(t, {})[1]["enabled"] is True


# ---------------------------------------------------------------------------
# BlendedRouter audit hook
# ---------------------------------------------------------------------------


class TestRouterAuditHook:
    def _router(self, score_fn, auditor):
        return BlendedRouter(
            score_fn=score_fn,
            affinity=PrefixAffinityTracker(
                2, 64,
                token_processor=ChunkedTokenDatabase(
                    TokenProcessorConfig(block_size=PS)
                ),
            ),
            loads_fn=lambda names: [0.0, 0.0],
            auditor=auditor,
        )

    def test_route_records_decision_with_index_prediction(self):
        a = RouteAuditor()
        router = self._router(lambda toks, names: {"pa": 3, "pb": 1}, a)
        router.route(list(range(16)), ["pa", "pb"], request_id="r1")
        rec = a.record_realized("r1", "pa", 3)
        assert rec.predicted_blocks == 3 and rec.ratio == 1.0

    def test_cold_route_predicts_from_affinity_and_flags_never_stored(self):
        a = RouteAuditor(index=InMemoryIndex())
        router = self._router(lambda toks, names: {}, a)
        toks = list(range(16))
        # First pass warms the affinity memory for pod index 0.
        router.route(toks, ["pa", "pb"], request_id="r0")
        router.route(toks, ["pa", "pb"], request_id="r1")
        rec = a.record_realized("r1", "pa", 0)
        # Index never claimed the chain: the affinity-based optimism is
        # attributed never_stored, not an index fault.
        assert rec.predicted_blocks == len(toks) // PS
        assert rec.cause == "never_stored"

    def test_pull_decision_predicts_pull_blocks(self):
        # A pull decision promises the SOURCE's warm chain lands on the
        # target: predicted = pull_blocks, not the cold target's own
        # score — otherwise every pull drops out of the ratio histogram.
        class AlwaysPull:
            def decide(self, **kw):
                return "pull"

        a = RouteAuditor()
        router = self._router(lambda toks, names: {"pa": 3}, a)
        router.loads_fn = lambda names: [1.0, 0.0]
        router.cost_model = AlwaysPull()
        decision = router.route(
            list(range(16)), ["pa", "pb"], request_id="r-pull"
        )
        assert decision.action == "pull" and decision.pod == "pb"
        rec = a.record_realized("r-pull", "pb", 3)
        assert rec.predicted_blocks == 3 and rec.ratio == 1.0
        assert rec.cause is None and rec.decision == "pull"

    def test_failed_pull_miss_is_attributable(self):
        # Dead peer → cold fallback: the target realizes nothing against
        # the pull promise, and the miss surfaces (never_stored: the
        # index never claimed the chain on the target; the row's
        # decision="pull" names the failed mechanism).
        class AlwaysPull:
            def decide(self, **kw):
                return "pull"

        a = RouteAuditor()
        router = self._router(lambda toks, names: {"pa": 3}, a)
        router.loads_fn = lambda names: [1.0, 0.0]
        router.cost_model = AlwaysPull()
        router.route(list(range(16)), ["pa", "pb"], request_id="r-dead")
        rec = a.record_realized("r-dead", "pb", 0)
        assert rec.predicted_blocks == 3 and rec.ratio == 0.0
        assert rec.cause == "never_stored" and rec.decision == "pull"

    def test_no_auditor_or_no_request_id_records_nothing(self):
        a = RouteAuditor()
        router = self._router(lambda toks, names: {"pa": 2}, a)
        router.route(list(range(8)), ["pa", "pb"])  # no request_id
        assert a.snapshot()["decisions_recorded"] == 0
        router.auditor = None
        router.route(list(range(8)), ["pa", "pb"], request_id="r1")
        assert a.snapshot()["decisions_recorded"] == 0


# ---------------------------------------------------------------------------
# SLO recording
# ---------------------------------------------------------------------------


class TestSLO:
    def test_parse_spec(self):
        (a, b) = parse_slo_spec("ttft:0.5:0.99;itl:0.05:0.95")
        assert a.metric == "ttft" and a.threshold_s == 0.5 and a.target == 0.99
        assert b.label == "itl_le_0.05s_p0.95"
        assert parse_slo_spec("") == []

    @pytest.mark.parametrize(
        "spec", ["ttft:0.5", "e2e:1:0.9", "ttft:0:0.9", "ttft:1:1.5", "ttft:1:0"]
    )
    def test_parse_spec_rejects_malformed(self, spec):
        with pytest.raises(ValueError):
            parse_slo_spec(spec)

    def test_parse_windows(self):
        assert parse_windows("") == (60.0, 300.0)
        assert parse_windows("10,20") == (10.0, 20.0)
        with pytest.raises(ValueError):
            parse_windows("0,10")

    def test_burn_rate_is_violating_fraction_over_budget(self):
        now = [0.0]
        r = SLORecorder(
            parse_slo_spec("ttft:0.5:0.9"), windows_s=(10.0,),
            clock=lambda: now[0],
        )
        for ttft in (0.1, 0.1, 0.1, 1.0):  # 25% violating, budget 10%
            r.observe(ttft, None)
        rates = r.burn_rates()
        assert rates["ttft_le_0.5s_p0.9"]["10s"] == 2.5

    def test_window_pruning(self):
        now = [0.0]
        r = SLORecorder(
            parse_slo_spec("ttft:0.5:0.9"), windows_s=(10.0,),
            clock=lambda: now[0],
        )
        r.observe(1.0, None)  # violation at t=0
        now[0] = 20.0
        r.observe(0.1, None)  # only sample inside the window
        assert r.burn_rates()["ttft_le_0.5s_p0.9"]["10s"] == 0.0

    def test_empty_window_is_none_and_gauge_skipped(self):
        r = SLORecorder(parse_slo_spec("itl:0.05:0.99"), windows_s=(60.0,))
        assert r.burn_rates()["itl_le_0.05s_p0.99"]["60s"] is None
        calls = []
        r.sync_gauges(lambda o, w, v: calls.append((o, w, v)))
        assert calls == []

    def test_none_measurement_skipped(self):
        r = SLORecorder(parse_slo_spec("itl:0.05:0.9"), windows_s=(60.0,))
        r.observe(0.3, None)  # single-token request: no ITL
        assert r.burn_rates()["itl_le_0.05s_p0.9"]["60s"] is None

    def test_malformed_spec_fails_pod_construction(self):
        with pytest.raises(ValueError):
            PodServer(_pod_config("slo-bad", obs_slo="garbage"))


# ---------------------------------------------------------------------------
# Knobs-off parity (the hard contract)
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

    def test_pod_response_and_stats_keys_pinned_with_knobs_off(self):
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

    def test_pod_publishes_no_audit_events_with_knob_off(self):
        pool = KVEventsPool(InMemoryIndex(), KVEventsPoolConfig(concurrency=1))
        pool.start()
        link = ChaosLink(pool, "parity-pod", MODEL)
        server = PodServer(
            _pod_config("parity-pod", publish_events=True), publisher=link
        )
        server.start()
        try:
            server.generate(
                _prompt(1, 12), SamplingParams(max_new_tokens=3), timeout=120
            )
            assert pool.drain(timeout=5.0)
        finally:
            server.shutdown()
            pool.shutdown()
        assert server.audits_published == 0
        assert server.slo is None

    def test_pod_slo_and_audit_blocks_absent_with_knobs_off(self):
        async def scenario(c, server):
            stats = await (await c.get("/stats")).json()
            assert "slo" not in stats and "audit" not in stats

        self._run(scenario)

    def test_scorer_stats_keys_pinned_with_knobs_off(self):
        from llm_d_kv_cache_manager_tpu.server.api import (
            ScoringService,
            ServiceConfig,
        )

        svc = ScoringService(
            ServiceConfig(native_index=False, enable_metrics=False)
        )
        assert svc.staleness is None and svc.route_auditor is None

        async def runner():
            ts = TestServer(svc.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                stats = await (await client.get("/stats")).json()
                assert set(stats) == {
                    "fleet", "subscriber", "events_rejected_after_shutdown",
                    "index_size", "index",
                }
                data = await (await client.get("/debug/staleness")).json()
                assert data == {"enabled": False}
                data = await (await client.get("/debug/audit")).json()
                assert data == {"enabled": False, "audits": []}
            finally:
                await client.close()

        try:
            asyncio.run(runner())
        finally:
            svc.indexer.shutdown()


# ---------------------------------------------------------------------------
# Scoring service with knobs on
# ---------------------------------------------------------------------------


class TestScoringServiceAudit:
    def _svc(self, **kw):
        from llm_d_kv_cache_manager_tpu.server.api import (
            ScoringService,
            ServiceConfig,
        )

        return ScoringService(
            ServiceConfig(native_index=False, enable_metrics=False, **kw)
        )

    def test_audit_knob_records_scoreboard_keyed_by_request_id(self):
        svc = self._svc(obs_audit=True)
        svc.indexer.get_pod_scores = (
            lambda prompt, model, pods, placement=None: {"pa": 5, "pb": 2}
        )

        async def runner():
            ts = TestServer(svc.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                resp = await client.post(
                    "/score_completions",
                    json={
                        "prompt": "x", "model": MODEL, "request_id": "req-9",
                    },
                )
                assert resp.status == 200
                stats = await (await client.get("/stats")).json()
                assert stats["audit"]["decisions_recorded"] == 1
                assert stats["audit"]["pending"] == 1
                assert "staleness" in stats
            finally:
                await client.close()

        try:
            asyncio.run(runner())
            rec = svc.route_auditor.record_realized("req-9", "pa", 5)
            assert rec.ratio == 1.0 and rec.cause is None
        finally:
            svc.indexer.shutdown()

    def test_obs_metrics_adds_scoreboard_and_events_behind_block(self):
        svc = self._svc(obs_metrics=True)
        svc.indexer.get_pod_scores = (
            lambda prompt, model, pods, placement=None: {"pa": 1, "pb": 1}
        )
        assert svc.staleness is not None  # events-behind needs the tracker
        assert svc.route_auditor is None  # audit knob separately gated

        async def runner():
            ts = TestServer(svc.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                await client.post(
                    "/score_completions", json={"prompt": "x", "model": MODEL}
                )
                stats = await (await client.get("/stats")).json()
                assert stats["obs"]["scoreboard_size"] == 2
                assert stats["obs"]["events_behind"] == {}
                # Audit-only blocks stay out without OBS_AUDIT.
                assert "staleness" not in stats and "audit" not in stats
            finally:
                await client.close()

        try:
            asyncio.run(runner())
        finally:
            svc.indexer.shutdown()


# ---------------------------------------------------------------------------
# Pod RequestAudit publishing
# ---------------------------------------------------------------------------


class TestPodAuditPublish:
    def test_realized_blocks_published_per_finished_request(self):
        idx = InMemoryIndex()
        pool = KVEventsPool(InMemoryIndex(), KVEventsPoolConfig(concurrency=1))
        auditor = RouteAuditor(index=idx, model_name=MODEL)
        pool.audit = auditor
        pool.start()
        link = ChaosLink(pool, "audit-pod", MODEL)
        server = PodServer(
            _pod_config("audit-pod", publish_events=True, obs_audit=True),
            publisher=link,
        )
        server.start()
        prefix = _prompt(30, 16)
        try:
            # Cold pass caches the prefix; warm pass realizes hits on it.
            server.generate(
                prefix + _prompt(31, 4), SamplingParams(max_new_tokens=2),
                timeout=120,
            )
            warm_fut = server.submit(
                prefix + _prompt(32, 4), SamplingParams(max_new_tokens=2),
                request_id="warm-1",
            )
            auditor.record_decision(
                "warm-1", chosen_pod="audit-pod",
                predicted_blocks=len(prefix) // PS,
                scoreboard={"audit-pod": len(prefix) // PS},
            )
            seq = warm_fut.result(timeout=120)
            assert pool.drain(timeout=10.0)
        finally:
            server.shutdown()
            pool.shutdown()
        assert server.audits_published == 2
        assert seq.num_cached_prompt == len(prefix)
        snap = auditor.snapshot()
        # The cold request had no recorded decision (unmatched); the warm
        # one joined with predicted == realized.
        assert snap["unmatched_realized"] == 1
        assert snap["joined"] == 1
        (row,) = auditor.recent(request_id="warm-1")
        assert row["predicted_blocks"] == row["realized_blocks"] == len(prefix) // PS
        assert row["cause"] is None and row["ratio"] == 1.0


# ---------------------------------------------------------------------------
# 2-pod fleet acceptance
# ---------------------------------------------------------------------------


class TestFleetAuditAcceptance:
    """The acceptance pins: predicted == realized on a warm route through
    the REAL path (engines → BlockStored wire → index → BlendedRouter →
    serve → RequestAudit wire → join), and a forced eviction between
    scoring and serving attributes ``stale_index``."""

    def _fleet(self):
        indexer = KVCacheIndexer(
            KVCacheIndexerConfig(
                token_processor=TokenProcessorConfig(block_size=PS)
            )
        )
        fh = FleetHealth(FleetHealthConfig())
        now = [time.time()]
        tracker = StalenessTracker(clock=lambda: now[0])
        auditor = RouteAuditor(
            index=indexer.kv_block_index, fleet_health=fh, model_name=MODEL
        )
        pool = KVEventsPool(
            indexer.kv_block_index,
            KVEventsPoolConfig(concurrency=2),
            health=fh,
            staleness=tracker,
            audit=auditor,
        )
        pool.start()
        pods = {}
        links = {}
        for name in ("pod-a", "pod-b"):
            links[name] = ChaosLink(pool, name, MODEL)
            pods[name] = PodServer(
                _pod_config(name, publish_events=True, obs_audit=True),
                publisher=links[name],
            )
            pods[name].start()
        router = BlendedRouter(
            score_fn=lambda toks, names: indexer.score_tokens(
                toks, MODEL, names
            ),
            affinity=PrefixAffinityTracker(
                2, 64,
                token_processor=ChunkedTokenDatabase(
                    TokenProcessorConfig(block_size=PS)
                ),
            ),
            loads_fn=lambda names: [
                pods[n].queue_depth for n in names
            ],
            auditor=auditor,
        )
        return indexer, pool, pods, links, router, auditor, tracker, now

    def test_warm_route_predicted_equals_realized(self):
        indexer, pool, pods, links, router, auditor, tracker, now = self._fleet()
        names = ["pod-a", "pod-b"]
        prefix = _prompt(40, 16)
        try:
            # Warm pod-a through the real serving path; its BlockStored
            # events reach the index over the (in-process) wire.
            pods["pod-a"].generate(
                prefix + _prompt(41, 4), SamplingParams(max_new_tokens=2),
                timeout=120,
            )
            assert pool.drain(timeout=10.0)
            prompt = prefix + _prompt(42, 4)
            decision = router.route(prompt, names, request_id="acc-1")
            assert decision.pod == "pod-a"
            assert decision.index_score == len(prefix) // PS
            seq = pods["pod-a"].submit(
                prompt, SamplingParams(max_new_tokens=2), request_id="acc-1"
            ).result(timeout=120)
            assert seq.num_cached_prompt == len(prefix)
            assert pool.drain(timeout=10.0)
        finally:
            for p in pods.values():
                p.shutdown()
            pool.shutdown()
            indexer.shutdown()
        (row,) = auditor.recent(request_id="acc-1")
        assert row["predicted_blocks"] == len(prefix) // PS
        assert row["realized_blocks"] == row["predicted_blocks"]
        assert row["ratio"] == 1.0 and row["cause"] is None
        # The staleness probes saw the fleet's event traffic.
        assert tracker.snapshot()["events_observed"] > 0

    def test_forced_eviction_after_scoring_attributes_stale_index(self):
        indexer, pool, pods, links, router, auditor, tracker, now = self._fleet()
        names = ["pod-a", "pod-b"]
        prefix = _prompt(50, 16)
        prompt = prefix + _prompt(51, 4)
        try:
            pods["pod-a"].generate(
                prefix + _prompt(52, 4), SamplingParams(max_new_tokens=2),
                timeout=120,
            )
            assert pool.drain(timeout=10.0)
            decision = router.route(prompt, names, request_id="evict-1")
            assert decision.pod == "pod-a" and decision.index_score > 0
            # Forced eviction AFTER scoring: pod-a's pool churns and it
            # publishes BlockRemoved for the scored chain — exactly what
            # capacity pressure does between scoring and serving.
            hashes = indexer.token_processor.prefix_hashes(prompt)
            links["pod-a"].publish(
                [BlockRemoved(block_hashes=list(hashes))]
            )
            assert pool.drain(timeout=10.0)
            # The pod's realized report arrives over the same wire.
            links["pod-a"].publish(
                [RequestAudit(request_id="evict-1", realized_blocks=0)]
            )
            assert pool.drain(timeout=10.0)
        finally:
            for p in pods.values():
                p.shutdown()
            pool.shutdown()
            indexer.shutdown()
        (row,) = auditor.recent(request_id="evict-1")
        assert row["cause"] == "stale_index"
        assert auditor.snapshot()["miss_causes"]["stale_index"] == 1
