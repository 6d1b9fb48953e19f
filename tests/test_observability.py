"""Observability suite (ISSUE 5 acceptance).

End-to-end request tracing + latency decomposition across the fleet:

- **Traceparent**: W3C parse/format round-trips; malformed headers never
  raise (tracing is best-effort).
- **Tracer**: disabled = shared no-op span (nothing recorded); enabled =
  parent links, bounded ring, request-id filtering.
- **Fleet trace** (the acceptance pin): with ``OBS_TRACING`` on a 2-pod
  in-process fleet, one request that pulls a warm prefix yields ONE trace
  id with spans from the scorer, the serving pod (queue/prefill/decode),
  and the exporting peer — retrievable from ``/debug/traces``.
- **Exposition parity pins**: the metric name/type surface is pinned so
  renames fail CI.
- **Knobs-off parity**: with every ``OBS_*`` knob unset, the completion
  response (body keys AND headers), the ``/stats`` top-level fields, and
  the transfer request wire bytes are bit-identical to pre-PR-5 behavior.
- Satellites: metrics-beat stop/start fix, index-occupancy gauges,
  log-context injection, route-decision counter, engine-step telemetry,
  ``/debug/profile`` gating.
"""

import asyncio
import logging
import threading
import time

import msgpack
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_d_kv_cache_manager_tpu.kvcache.metrics import collector
from llm_d_kv_cache_manager_tpu.kvcache.transfer import (
    decode_request,
    encode_request,
)
from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
from llm_d_kv_cache_manager_tpu.obs.tracing import (
    NOOP_SPAN,
    SpanContext,
    Tracer,
    format_traceparent,
    parse_traceparent,
)
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.sequence import Sequence
from llm_d_kv_cache_manager_tpu.server.serve import (
    PodServer,
    PodServerConfig,
    _ServingMetrics,
)
from llm_d_kv_cache_manager_tpu.utils import get_logger, log_context

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
        publish_events=False,
        engine=_engine_config(total_pages=kw.pop("total_pages", 64)),
        **kw,
    )


def _prompt(seed, n):
    return list(
        map(int, np.random.default_rng(seed).integers(0, TINY_LLAMA.vocab_size, n))
    )


class TestTraceparent:
    def test_round_trip(self):
        ctx = SpanContext(trace_id="0af7651916cd43dd8448eb211c80319c",
                          span_id="b7ad6b7169203331")
        assert parse_traceparent(format_traceparent(ctx)) == ctx
        assert format_traceparent(ctx) == (
            "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"
        )

    def test_case_and_whitespace_tolerant(self):
        hdr = "  00-0AF7651916CD43DD8448EB211C80319C-B7AD6B7169203331-01 "
        ctx = parse_traceparent(hdr)
        assert ctx is not None and ctx.trace_id.islower()

    def test_malformed_headers_never_raise(self):
        bad = [
            None,
            "",
            "garbage",
            "00-abc-def-01",  # short ids
            "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",  # forbidden version
            "00-" + "0" * 32 + "-" + "b" * 16 + "-01",  # all-zero trace
            "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # all-zero span
            "00-" + "g" * 32 + "-" + "b" * 16 + "-01",  # non-hex
            "00-" + "a" * 32 + "-" + "b" * 16,  # missing flags
            42,
        ]
        for hdr in bad:
            assert parse_traceparent(hdr) is None, hdr


class TestTracer:
    def test_disabled_is_noop(self):
        t = Tracer(enabled=False)
        span = t.start_span("x", attrs={"a": 1})
        assert span is NOOP_SPAN and span.context is None
        span.set_attr("b", 2)
        span.end()
        t.record_span("y", None, 0.0, 1.0)
        assert t.traces() == []
        assert t.snapshot()["spans_recorded"] == 0

    def test_parent_links_and_trace_inheritance(self):
        t = Tracer(enabled=True)
        root = t.start_span("root")
        child = t.start_span("child", parent=root)
        assert child.context.trace_id == root.context.trace_id
        assert child.parent_span_id == root.context.span_id
        # SpanContext parents work too (the cross-process path).
        remote = t.start_span("remote", parent=root.context)
        assert remote.context.trace_id == root.context.trace_id
        child.end(), remote.end(), root.end()
        (trace,) = t.traces(trace_id=root.context.trace_id)
        assert {s["name"] for s in trace["spans"]} == {"root", "child", "remote"}

    def test_ring_is_bounded(self):
        t = Tracer(enabled=True, max_spans=16)
        for i in range(50):
            t.start_span(f"s{i}").end()
        assert t.snapshot()["spans_buffered"] == 16
        assert t.snapshot()["spans_dropped"] == 50 - 16

    def test_non_positive_limit_returns_nothing(self):
        t = Tracer(enabled=True)
        t.start_span("s").end()
        assert t.traces(limit=0) == []
        assert t.traces(limit=-5) == []

    def test_request_id_filter(self):
        t = Tracer(enabled=True)
        a = t.start_span("req", attrs={"request_id": "ra"})
        a.end()
        b = t.start_span("req", attrs={"request_id": "rb"})
        b.end()
        (trace,) = t.traces(request_id="rb")
        assert trace["trace_id"] == b.context.trace_id

    def test_span_name_filter(self):
        """ISSUE 15 satellite: ``span=`` keeps traces CONTAINING a span
        of that name (whole trace returned — the match stays readable in
        context), composing with the id filters."""
        from llm_d_kv_cache_manager_tpu.obs.tracing import (
            debug_traces_payload,
        )

        t = Tracer(enabled=True)
        root = t.start_span("disagg.request")
        t.start_span("disagg.handoff", parent=root).end()
        root.end()
        t.start_span("pod.request").end()  # no handoff span
        (trace,) = t.traces(span_name="disagg.handoff")
        assert trace["trace_id"] == root.context.trace_id
        assert {s["name"] for s in trace["spans"]} == {
            "disagg.request", "disagg.handoff"
        }
        assert t.traces(span_name="nope") == []
        # The shared /debug/traces contract reads the `span` query key.
        status, payload = debug_traces_payload(
            t, {"span": "disagg.handoff"}
        )
        assert status == 200 and len(payload["traces"]) == 1
        # Composes with trace_id: both filters must match.
        assert (
            t.traces(
                trace_id=root.context.trace_id, span_name="pod.request"
            )
            == []
        )

    def test_record_span_backdates(self):
        t = Tracer(enabled=True)
        now = time.monotonic()
        t.record_span("past", None, now - 2.0, now - 1.0, attrs={"k": "v"})
        (trace,) = t.traces()
        (span,) = trace["spans"]
        assert abs(span["duration_s"] - 1.0) < 0.01
        assert span["attrs"] == {"k": "v"}

    def test_context_manager_records_error(self):
        t = Tracer(enabled=True)
        with pytest.raises(RuntimeError):
            with t.start_span("boom"):
                raise RuntimeError("kaput")
        (trace,) = t.traces()
        assert "kaput" in trace["spans"][0]["attrs"]["error"]


class TestMetricsBeatLifecycle:
    """Satellite: ``stop_metrics_logging`` joins the beat thread and
    resets it so start→stop→start in one process actually restarts."""

    def test_stop_joins_and_restart_spawns_fresh_thread(self):
        collector.start_metrics_logging(0.01)
        first = collector._beat_thread
        assert first is not None and first.is_alive()
        collector.stop_metrics_logging()
        assert collector._beat_thread is None
        assert not first.is_alive()
        # The pre-fix bug: this start() saw the old thread alive and
        # silently did nothing.
        collector.start_metrics_logging(0.01)
        second = collector._beat_thread
        assert second is not None and second.is_alive() and second is not first
        collector.stop_metrics_logging()
        assert collector._beat_thread is None

    def test_stop_without_start_is_safe(self):
        collector.stop_metrics_logging()
        collector.stop_metrics_logging()


#: Exposition pin for the pod's OBS_METRICS surface: full name -> type.
#: A rename (or type change) of any serving metric fails here before it
#: silently breaks dashboards.
_POD_OBS_METRICS = {
    "kvcache_request_ttft_seconds": "histogram",
    "kvcache_request_itl_seconds": "histogram",
    "kvcache_request_queue_seconds": "histogram",
    "kvcache_request_e2e_seconds": "histogram",
    "kvcache_transfer_pull_seconds": "histogram",
    # Async KV-pull overlap decomposition (ISSUE 7)
    "kvcache_transfer_pull_overlap_seconds": "histogram",
    "kvcache_engine_steps_total": "counter",
    "kvcache_engine_step_phase_seconds_total": "counter",
    "kvcache_engine_batch_occupancy": "gauge",
    "kvcache_engine_free_pages": "gauge",
    "kvcache_engine_loop_lag_seconds": "gauge",
    # Host-DRAM tier + prefetch (ISSUE 6)
    "kvcache_host_pages": "gauge",
    "kvcache_host_hits_total": "counter",
    "kvcache_host_prefetch_seconds": "histogram",
    # SLO burn-rate recording (ISSUE 10; series appear when OBS_SLO feeds
    # them, the family is registered with the obs surface)
    "kvcache_slo_burn_rate": "gauge",
}

#: Scorer-side collector metrics added by PR 5 + the ISSUE 10 audit plane
#: (global registry).
_SCORER_OBS_METRICS = {
    "kvcache_scorer_route_decisions_total": "counter",
    "kvcache_scorer_score_seconds": "histogram",
    "kvcache_index_blocks": "gauge",
    "kvcache_index_pods": "gauge",
    # Routing-quality audit plane (ISSUE 10)
    "kvcache_index_staleness_seconds": "histogram",
    "kvcache_index_events_behind": "gauge",
    "kvcache_scorer_scoreboard_size": "gauge",
    "kvcache_route_predicted_vs_realized_blocks": "histogram",
    "kvcache_route_regret_blocks": "histogram",
    "kvcache_route_miss_attributed_total": "counter",
    # Fleet observability federation (ISSUE 20; series appear when
    # OBS_FED scrapes feed them, the families register unconditionally
    # like every collector family above)
    "kvcache_fleet_health_score": "gauge",
    "kvcache_fleet_scrape_seconds": "histogram",
    "kvcache_fleet_scrape_errors_total": "counter",
    "kvcache_fleet_scrape_pods_skipped_total": "counter",
}


def _exposition_types(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ")
            out[name] = typ
    return out


class TestExpositionParity:
    def test_pod_obs_metric_names_and_types_pinned(self):
        pytest.importorskip("prometheus_client")
        m = _ServingMetrics(obs=True)
        types = _exposition_types(m.exposition().decode())
        for name, typ in _POD_OBS_METRICS.items():
            assert types.get(name) == typ, (name, types.get(name))

    def test_obs_off_adds_no_new_series(self):
        pytest.importorskip("prometheus_client")
        m = _ServingMetrics(obs=False)
        types = _exposition_types(m.exposition().decode())
        assert not set(types) & set(_POD_OBS_METRICS)

    def test_collector_metric_names_and_types_pinned(self):
        prom = pytest.importorskip("prometheus_client")
        collector.register()  # idempotent; global registry
        types = _exposition_types(prom.generate_latest().decode())
        for name, typ in _SCORER_OBS_METRICS.items():
            assert types.get(name) == typ, (name, types.get(name))


class TestLatencyDecomposition:
    def _finished_seq(self, cached=0, route_action=None, gen=4):
        now = time.monotonic()
        seq = Sequence(prompt_tokens=list(range(8)))
        seq.arrival_time = now - 1.0
        seq.prefill_start_time = now - 0.8
        seq.first_token_time = now - 0.6
        seq.finish_time = now
        seq.num_generated = gen
        seq.num_cached_prompt = cached
        seq.sampling.max_new_tokens = gen
        seq.route_action = route_action
        return seq

    def test_histograms_labeled_by_outcome_and_finish(self):
        pytest.importorskip("prometheus_client")
        m = _ServingMetrics(obs=True)
        m.observe_finished(self._finished_seq(cached=4))
        m.observe_finished(self._finished_seq(cached=0))
        m.observe_finished(self._finished_seq(route_action="pull"))
        text = m.exposition().decode()
        for outcome in ("warm", "cold", "pull"):
            assert (
                f'kvcache_request_e2e_seconds_count{{finish="length",'
                f'outcome="{outcome}"}} 1.0' in text
            ), text
        # ITL = (finish - first_token) / (gen - 1); gen=4 -> 3 intervals.
        assert 'kvcache_request_itl_seconds_count{finish="length",outcome="warm"} 1.0' in text

    def test_pull_histogram_outcomes(self):
        pytest.importorskip("prometheus_client")
        m = _ServingMetrics(obs=True)
        m.observe_pull(0.1, "ok")
        m.observe_pull(0.2, "failed")
        text = m.exposition().decode()
        assert 'kvcache_transfer_pull_seconds_count{outcome="ok"} 1.0' in text
        assert 'kvcache_transfer_pull_seconds_count{outcome="failed"} 1.0' in text

    def test_deadline_exhausted_pull_is_skipped_not_empty(self):
        pytest.importorskip("prometheus_client")
        server = PodServer(_pod_config("pull-pod", obs_metrics=True))
        server.start()
        try:
            n = server.pull_prefix(
                _prompt(9, 8),
                "tcp://127.0.0.1:1",
                deadline=time.monotonic() - 1.0,
            )
            assert n == 0
            text = server.metrics.exposition().decode()
            assert (
                'kvcache_transfer_pull_seconds_count{outcome="skipped"} 1.0'
                in text
            )
            assert 'outcome="empty"' not in text
        finally:
            server.shutdown()

    def test_step_stats_delta_sync(self):
        pytest.importorskip("prometheus_client")
        m = _ServingMetrics(obs=True)
        stats = {"steps": 2, "schedule_s": 0.5, "prefill_s": 1.0,
                 "decode_s": 0.25, "sample_s": 0.0625, "gather_s": 0.0,
                 "demote_s": 0.03125, "publish_s": 0.125}
        m.sync_step_stats(stats, lag_s=0.01)
        m.sync_step_stats(stats, lag_s=0.01)  # no double count
        text = m.exposition().decode()
        assert "kvcache_engine_steps_total 2.0" in text
        assert 'kvcache_engine_step_phase_seconds_total{phase="prefill"} 1.0' in text
        # The decode fast path's fusion evidence: the blocking share of
        # the sampled-token fetch is its own phase.
        assert (
            'kvcache_engine_step_phase_seconds_total{phase="sample"} 0.0625'
            in text
        )
        # Remote-tier demotion payload builds are their own phase (ISSUE
        # 15 satellite): PR 12 folded them into the flush gather, where
        # the tier's quantize+serialize cost hid untagged.
        assert (
            'kvcache_engine_step_phase_seconds_total{phase="demote"} 0.03125'
            in text
        )
        assert "kvcache_engine_loop_lag_seconds 0.01" in text

    def test_engine_demote_phase_key_present(self):
        # The engine's step_stats dict itself carries the label's feed.
        from llm_d_kv_cache_manager_tpu.server.engine import Engine

        eng = Engine(_engine_config())
        assert "demote_s" in eng.step_stats

    def test_ttft_itl_buckets_cover_sub_100ms_decade(self):
        """ISSUE 15 satellite: the TTFT/ITL histograms carry a full
        sub-100 ms decade plus the 0.15/0.2 splits of the old 0.1–0.25
        gap (the r12 CPU-smoke p50 ≈ 0.17 s lived inside one 2.5x-wide
        bucket). queue/e2e keep the legacy SLO grid."""
        pytest.importorskip("prometheus_client")
        m = _ServingMetrics(obs=True)
        m.observe_finished(self._finished_seq())
        text = m.exposition().decode()
        for le in ("0.0075", "0.015", "0.02", "0.03", "0.04", "0.06",
                   "0.08", "0.15", "0.2"):
            assert f'kvcache_request_ttft_seconds_bucket{{finish="length",le="{le}"' in text, le
            assert f'kvcache_request_itl_seconds_bucket{{finish="length",le="{le}"' in text, le
            # The legacy grid on queue/e2e is untouched (no new bounds).
            assert f'kvcache_request_queue_seconds_bucket{{finish="length",le="{le}"' not in text, le

    def test_pull_overlap_histogram_kinds(self):
        pytest.importorskip("prometheus_client")
        m = _ServingMetrics(obs=True)
        m.observe_pull_overlap(0.4, 0.1)
        text = m.exposition().decode()
        assert (
            'kvcache_transfer_pull_overlap_seconds_count{kind="hidden"} 1.0'
            in text
        )
        assert (
            'kvcache_transfer_pull_overlap_seconds_count{kind="exposed"} 1.0'
            in text
        )
        assert (
            'kvcache_transfer_pull_overlap_seconds_sum{kind="hidden"} 0.4'
            in text
        )


class TestTransferWireParity:
    def test_request_without_traceparent_is_legacy_bytes(self):
        assert encode_request("m", [1, 2], 8) == msgpack.packb(
            ["FetchBlocks", "m", [1, 2], 8], use_bin_type=True
        )

    def test_traceparent_rides_the_envelope(self):
        tp = "00-" + "a" * 32 + "-" + "b" * 16 + "-01"
        payload = encode_request("m", [1], None, tp)
        assert decode_request(payload) == ("m", [1], None, tp)

    def test_malformed_traceparent_field_tolerated(self):
        raw = msgpack.packb(["FetchBlocks", "m", [1], None, 123])
        assert decode_request(raw) == ("m", [1], None, None)


class TestKnobsOffParity:
    """With every OBS_* knob unset the serving surface is bit-identical
    legacy: response keys/headers, /stats fields, no obs block."""

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

    def test_completion_response_and_stats_fields_pinned(self):
        async def scenario(c, server):
            resp = await c.post(
                "/v1/completions",
                json={"prompt_token_ids": _prompt(0, 10), "max_tokens": 3},
                headers={"traceparent": "00-" + "a" * 32 + "-" + "b" * 16 + "-01"},
            )
            assert resp.status == 200
            data = await resp.json()
            assert set(data) == {
                "id", "object", "model", "choices", "usage", "ttft_s",
                "queue_s", "staged_s",
            }
            assert set(data["choices"][0]) == {
                "index", "text", "token_ids", "finish_reason"
            }
            # Tracing off: the inbound traceparent is not echoed.
            assert "traceparent" not in resp.headers
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

    def test_debug_traces_reports_disabled(self):
        async def scenario(c, server):
            resp = await c.get("/debug/traces")
            assert resp.status == 200
            data = await resp.json()
            assert data == {"enabled": False, "traces": []}
            # Malformed limit: tolerant 400, never a traceback 500.
            resp = await c.get("/debug/traces?limit=abc")
            assert resp.status == 400

        self._run(scenario)

    def test_debug_profile_disabled_without_knob(self):
        async def scenario(c, server):
            resp = await c.post("/debug/profile?seconds=1")
            assert resp.status == 400

        self._run(scenario)

    def test_no_spans_recorded_and_engine_untimed(self):
        server = PodServer(_pod_config("parity-pod-2"))
        server.start()
        try:
            server.generate(_prompt(1, 12), SamplingParams(max_new_tokens=3),
                            timeout=120)
            assert server.tracer.snapshot()["spans_recorded"] == 0
            assert server.engine.step_stats["steps"] == 0
        finally:
            server.shutdown()


class TestPodTracing:
    def test_request_span_tree_single_pod(self):
        server = PodServer(_pod_config("trace-pod", obs_tracing=True))
        server.start()
        try:
            fut = server.submit(
                _prompt(2, 12), SamplingParams(max_new_tokens=4)
            )
            fut.result(timeout=120)
            rid = fut.request_id
        finally:
            server.shutdown()
        (trace,) = server.tracer.traces(request_id=rid)
        by_name = {s["name"]: s for s in trace["spans"]}
        assert {"pod.request", "pod.queue", "pod.prefill", "pod.decode"} <= set(
            by_name
        )
        req = by_name["pod.request"]
        assert req["parent_span_id"] is None  # no inbound ctx: pod minted
        for child in ("pod.queue", "pod.prefill", "pod.decode"):
            assert by_name[child]["parent_span_id"] == req["span_id"]
            assert by_name[child]["trace_id"] == req["trace_id"]
        assert req["attrs"]["request_id"] == rid
        assert req["attrs"]["outcome"] == "cold"

    def test_debug_profile_runs_with_knob(self, tmp_path, monkeypatch):
        calls = []
        import jax

        monkeypatch.setattr(
            jax.profiler, "start_trace", lambda d: calls.append(("start", d))
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: calls.append(("stop", None))
        )
        server = PodServer(
            _pod_config("prof-pod", obs_profile_dir=str(tmp_path))
        )
        server.start()

        async def runner():
            ts = TestServer(server.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                resp = await client.post("/debug/profile?seconds=0.01")
                assert resp.status == 200
                data = await resp.json()
                assert data["profile_dir"] == str(tmp_path)
                resp = await client.post("/debug/profile?seconds=0")
                assert resp.status == 400
                resp = await client.post("/debug/profile?seconds=bogus")
                assert resp.status == 400
            finally:
                await client.close()

        try:
            asyncio.run(runner())
        finally:
            server.shutdown()
        assert calls == [("start", str(tmp_path)), ("stop", None)]


class TestEngineStepTelemetry:
    def test_step_stats_accumulate_and_surface(self):
        server = PodServer(
            _pod_config("obs-pod", obs_metrics=True, obs_tracing=True)
        )
        server.start()

        async def runner():
            ts = TestServer(server.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                resp = await client.post(
                    "/v1/completions",
                    json={"prompt_token_ids": _prompt(3, 10), "max_tokens": 4},
                )
                assert resp.status == 200
                resp = await client.get("/stats")
                stats = await resp.json()
                assert "obs" in stats
                assert stats["obs"]["step_stats"]["steps"] > 0
                assert stats["obs"]["step_stats"]["prefill_s"] > 0
                assert stats["obs"]["tracing"]["enabled"] is True
                resp = await client.get("/metrics")
                text = await resp.text()
                assert "kvcache_engine_steps_total" in text
                assert "kvcache_request_ttft_seconds_count" in text
            finally:
                await client.close()

        try:
            asyncio.run(runner())
        finally:
            server.shutdown()


class TestLogContext:
    def test_context_injected_into_records(self, caplog):
        log = get_logger("testctx")
        with caplog.at_level(logging.INFO, logger="llm_d_kv_cache_manager_tpu.testctx"):
            with log_context(request_id="r-123", trace_id="t-456"):
                log.info("inner", step=1)
            log.info("outer")
        inner, outer = caplog.messages
        assert "request_id='r-123'" in inner and "trace_id='t-456'" in inner
        assert "step=1" in inner
        assert "request_id" not in outer

    def test_explicit_kwargs_win_and_none_skipped(self, caplog):
        log = get_logger("testctx2")
        with caplog.at_level(logging.INFO, logger="llm_d_kv_cache_manager_tpu.testctx2"):
            with log_context(request_id="ctx", trace_id=None):
                log.info("msg", request_id="explicit")
        assert "request_id='explicit'" in caplog.messages[0]
        assert "trace_id" not in caplog.messages[0]


class TestIndexSizeInfo:
    def _keys(self, hashes):
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock.keys import Key

        return [Key(model_name=MODEL, chunk_hash=h) for h in hashes]

    def _entries(self, pods):
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock.keys import PodEntry

        return [PodEntry(pod_identifier=p, device_tier="tpu_hbm") for p in pods]

    def test_in_memory_size_info(self):
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock.in_memory import (
            InMemoryIndex,
        )

        idx = InMemoryIndex()
        assert idx.size_info() == {"blocks": 0, "pods": 0}
        idx.add(self._keys([1, 2]), self._entries(["pa", "pb"]))
        assert idx.size_info() == {"blocks": 2, "pods": 2}
        idx.evict_pod("pa")
        assert idx.size_info() == {"blocks": 2, "pods": 1}

    def test_cost_aware_size_info(self):
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock.cost_aware import (
            CostAwareMemoryIndex,
        )

        idx = CostAwareMemoryIndex()
        idx.add(self._keys([1]), self._entries(["pa"]))
        assert idx.size_info() == {"blocks": 1, "pods": 1}

    def test_instrumented_delegates(self):
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock.in_memory import (
            InMemoryIndex,
        )
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock.instrumented import (
            InstrumentedIndex,
        )

        idx = InstrumentedIndex(InMemoryIndex())
        assert idx.size_info() == {"blocks": 0, "pods": 0}

    def test_scoring_stats_carries_index_size(self):
        from llm_d_kv_cache_manager_tpu.server.api import (
            ScoringService,
            ServiceConfig,
        )

        svc = ScoringService(
            ServiceConfig(native_index=False, enable_metrics=False)
        )

        async def runner():
            ts = TestServer(svc.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                resp = await client.get("/stats")
                data = await resp.json()
                assert data["index_size"] == {"blocks": 0, "pods": 0}
            finally:
                await client.close()

        asyncio.run(runner())


def test_route_decisions_counted():
    from llm_d_kv_cache_manager_tpu.kvcache import (
        BlendedRouter,
        PrefixAffinityTracker,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock import (
        ChunkedTokenDatabase,
        TokenProcessorConfig,
    )

    router = BlendedRouter(
        score_fn=lambda toks, names: {"a": 2},
        affinity=PrefixAffinityTracker(
            2, 16,
            token_processor=ChunkedTokenDatabase(
                TokenProcessorConfig(block_size=PS)
            ),
        ),
        loads_fn=lambda names: [0.0, 0.0],
    )
    before = collector.snapshot().get("route_decisions_route_warm", 0)
    before_cold = collector.snapshot().get("route_decisions_cold", 0)
    decision = router.route(list(range(8)), ["a", "b"])
    assert decision.action == "route_warm"
    assert collector.snapshot()["route_decisions_route_warm"] == before + 1
    # A zero-index-score placement is a COLD placement even though the
    # legacy action string stays "route_warm" — the metric must not read
    # 100% warm on a cold fleet.
    router.score_fn = lambda toks, names: {}
    decision = router.route(list(range(8)), ["a", "b"])
    assert decision.action == "route_warm"  # legacy behavior unchanged
    assert collector.snapshot()["route_decisions_cold"] == before_cold + 1
    assert collector.snapshot()["route_decisions_route_warm"] == before + 1


class TestFleetTraceAcceptance:
    """The acceptance pin: OBS_TRACING=1 on a 2-pod in-process fleet — one
    request that pulls a warm prefix yields a single trace id with spans
    from the scorer, the serving pod, and the exporting peer, retrievable
    from /debug/traces."""

    def test_one_trace_spans_scorer_pod_and_transfer_peer(self):
        from conftest import free_tcp_port
        from llm_d_kv_cache_manager_tpu.server.api import (
            ScoringService,
            ServiceConfig,
        )

        svc = ScoringService(
            ServiceConfig(
                native_index=False, enable_metrics=False, obs_tracing=True
            )
        )
        # The scorer's index plumbing is not under test here (the fleet
        # cold-join test covers it); pin the scoreboard so the test needs
        # no event plane.
        svc.indexer.get_pod_scores = lambda prompt, model, pods: {"pod-warm": 4}

        endpoint = f"tcp://127.0.0.1:{free_tcp_port()}"
        warm = PodServer(
            _pod_config(
                "pod-warm", transfer_endpoint=endpoint, obs_tracing=True
            )
        )
        cold = PodServer(_pod_config("pod-cold", obs_tracing=True))
        warm.start(), cold.start()

        prefix = _prompt(20, 16)
        prompt = prefix + _prompt(21, 4)

        async def runner():
            sts = TestServer(svc.build_app())
            sclient = TestClient(sts)
            await sclient.start_server()
            cts = TestServer(cold.build_app())
            cclient = TestClient(cts)
            await cclient.start_server()
            try:
                # 1. Scorer mints the trace and echoes the traceparent.
                resp = await sclient.post(
                    "/score_completions",
                    json={"prompt": "irrelevant", "model": MODEL},
                )
                assert resp.status == 200
                tp = resp.headers["traceparent"]
                ctx = parse_traceparent(tp)
                assert ctx is not None

                # 2. Warm the source pod, then pull onto the cold pod with
                # the scorer's trace context (the router's "pull" arm).
                await asyncio.get_running_loop().run_in_executor(
                    None,
                    lambda: warm.generate(
                        prefix, SamplingParams(max_new_tokens=2), timeout=120
                    ),
                )
                n = await asyncio.get_running_loop().run_in_executor(
                    None,
                    lambda: cold.pull_prefix(prompt, endpoint, trace_ctx=ctx),
                )
                assert n == len(prefix) // PS

                # 3. Serve on the cold pod, forwarding the traceparent.
                resp = await cclient.post(
                    "/v1/completions",
                    json={"prompt_token_ids": prompt, "max_tokens": 3},
                    headers={"traceparent": tp, "X-Route-Action": "pull"},
                )
                assert resp.status == 200
                assert parse_traceparent(
                    resp.headers["traceparent"]
                ).trace_id == ctx.trace_id

                # 4. One trace id across all three services.
                resp = await cclient.get(
                    f"/debug/traces?trace_id={ctx.trace_id}"
                )
                (cold_trace,) = (await resp.json())["traces"]
                return cold_trace
            finally:
                await sclient.close()
                await cclient.close()

        try:
            cold_trace = asyncio.run(runner())
        finally:
            warm.shutdown(), cold.shutdown()
            svc.indexer.shutdown()

        tid = cold_trace["trace_id"]
        (scorer_trace,) = svc.tracer.traces(trace_id=tid)
        (peer_trace,) = warm.tracer.traces(trace_id=tid)

        scorer_spans = {s["name"]: s for s in scorer_trace["spans"]}
        peer_spans = {s["name"]: s for s in peer_trace["spans"]}
        cold_spans = {s["name"]: s for s in cold_trace["spans"]}

        # Span tree: scorer.score is the root; the pod's pull and request
        # spans are its children; the peer's export span parents on the
        # pull span (carried in the transfer msgpack envelope); the
        # queue/prefill/decode decomposition parents on the request span.
        root = scorer_spans["scorer.score"]
        assert root["parent_span_id"] is None
        pull = cold_spans["pod.pull_prefix"]
        req = cold_spans["pod.request"]
        assert pull["parent_span_id"] == root["span_id"]
        assert req["parent_span_id"] == root["span_id"]
        export = peer_spans["transfer.export"]
        assert export["parent_span_id"] == pull["span_id"]
        assert export["attrs"]["served_blocks"] == len(prefix) // PS
        for child in ("pod.queue", "pod.prefill", "pod.decode"):
            assert cold_spans[child]["parent_span_id"] == req["span_id"]
        # The serving-side labels saw the pull verdict and the warm hit.
        assert pull["attrs"]["outcome"] == "ok"
        assert req["attrs"]["outcome"] == "pull"
        assert req["attrs"]["finish"] == "length"
        # Every span in every process carries the ONE trace id.
        for spans in (scorer_spans, peer_spans, cold_spans):
            assert all(s["trace_id"] == tid for s in spans.values())


class _GateHolder:
    """Tiny helper so the queue-span test can hold the engine briefly."""

    def __init__(self, server):
        self.server = server
        self.orig_step = server.engine.step
        self.gate = threading.Event()
        self.gate.set()

    def install(self):
        def gated():
            self.gate.wait(timeout=10)
            return self.orig_step()

        self.server.engine.step = gated


def test_queue_span_covers_staging_wait():
    """The queue span starts at submit (staging included), so a request
    held behind a slow engine shows its wait in pod.queue."""
    server = PodServer(_pod_config("queue-pod", obs_tracing=True))
    holder = _GateHolder(server)
    holder.install()
    server.start()
    try:
        holder.gate.clear()
        fut = server.submit(_prompt(5, 8), SamplingParams(max_new_tokens=2))
        time.sleep(0.25)  # request sits staged/waiting behind the gate
        holder.gate.set()
        fut.result(timeout=120)
        (trace,) = server.tracer.traces(request_id=fut.request_id)
        queue = next(s for s in trace["spans"] if s["name"] == "pod.queue")
        assert queue["duration_s"] >= 0.2
    finally:
        holder.gate.set()
        server.shutdown()
