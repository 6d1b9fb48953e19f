"""Decode fast path (ISSUE 7): async KV-pull overlap + device-resident
decode loop.

The acceptance pins of the subsystem:

- the device-resident decode loop (one dispatch ahead wherever lanes are
  full and no budget is near: ``Engine._next_schedule_decided``) gives
  greedy outputs identical to the engine that never runs ahead at every
  burst width, including k=1 (the step-per-token loop).
- ``ASYNC_PULL`` off = the legacy blocking pull flow untouched; on = a
  pull-routed request imports its warm prefix on a worker thread while
  queued ``importing``, the scheduler admits it only once the blocks
  land, and EVERY failure mode (dead peer, timeout, expired deadline,
  abort) degrades to cold prefill or a clean abort — never a stuck
  request, never a stalled batchmate, never a leaked page.
- Aborting a sequence stuck mid-import cancels the in-flight fetch and
  returns free pages to baseline (the PR 4 abort-accounting contract
  extended to the ``importing`` state).
"""

import time

import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
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
    kw.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    kw.setdefault("decode_batch_size", 4)
    return EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(total_pages=total_pages, page_size=PS),
        max_model_len=64,
        prefill_bucket=8,
        interpret=True,
        **kw,
    )


def _prompt(seed, n):
    return list(
        map(int, np.random.default_rng(seed).integers(0, TINY_LLAMA.vocab_size, n))
    )


def _pod_config(pod_id, transfer_endpoint=None, total_pages=64, **kw):
    return PodServerConfig(
        model_name=MODEL,
        pod_identifier=pod_id,
        publish_events=False,
        transfer_endpoint=transfer_endpoint,
        engine=_engine_cfg(total_pages=total_pages),
        **kw,
    )


def _wait_until(cond, timeout=30.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


class TestFusedSampling:
    """Device-resident decode loop: greedy parity, with the engine that
    never runs ahead, at every burst width."""

    PROMPTS = [(0, 10), (1, 17), (2, 5)]

    def _both(self, drive, monkeypatch, **kw):
        from run_ahead import both

        return both(lambda: Engine(_engine_cfg(**kw)), drive, monkeypatch)

    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_greedy_parity_all_widths(self, k, monkeypatch):
        def drive(eng):
            seqs = [
                eng.add_request(_prompt(s, n), SamplingParams(max_new_tokens=18))
                for s, n in self.PROMPTS
            ]
            eng.run_until_complete()
            assert all(s.error is None for s in seqs)
            return [s.generated_tokens for s in seqs]

        # three lanes for three requests: lanes full, so the rule holds
        self._both(
            drive, monkeypatch, decode_batch_size=3, decode_steps_per_iter=k
        )

    def test_k1_runs_ahead_with_lanes_full_and_not_with_one_free(self):
        for lanes, ahead in ((1, True), (4, False)):
            eng = Engine(_engine_cfg(decode_batch_size=lanes))
            eng.add_request(_prompt(0, 10), SamplingParams(max_new_tokens=12))
            eng.step()
            eng.step()
            assert (eng._inflight is not None) == ahead
            eng.run_until_complete()
            assert eng._inflight is None

    def test_parity_under_pool_pressure_with_preemption(self, monkeypatch):
        # A pool too small for every lane forces preemption mid-burst;
        # running ahead must recover to the same greedy outputs.
        def drive(eng):
            seqs = [
                eng.add_request(_prompt(s, 9), SamplingParams(max_new_tokens=16))
                for s in (3, 4)
            ]
            eng.run_until_complete()
            assert all(s.error is None for s in seqs)
            return [s.generated_tokens for s in seqs]

        self._both(drive, monkeypatch, total_pages=10, decode_batch_size=2)

    def test_warm_cache_hit_parity(self, monkeypatch):
        # Second request shares a prefix: the engine that runs ahead must
        # serve the hit identically (register_full_pages lags one burst on
        # commit).
        prefix = _prompt(5, 12)

        def drive(eng):
            a = eng.add_request(prefix + _prompt(6, 4), SamplingParams(max_new_tokens=12))
            eng.run_until_complete()
            b = eng.add_request(prefix + _prompt(7, 4), SamplingParams(max_new_tokens=12))
            eng.run_until_complete()
            assert b.num_cached_prompt >= PS
            return (a.generated_tokens, b.generated_tokens)

        self._both(drive, monkeypatch, decode_batch_size=1)

    def test_sample_phase_recorded(self):
        eng = Engine(_engine_cfg())
        eng.obs_step_timing = True
        eng.add_request(_prompt(8, 10), SamplingParams(max_new_tokens=4))
        eng.run_until_complete()
        assert eng.step_stats["sample_s"] > 0.0
        # With timing off the key exists but never accrues (legacy path).
        eng2 = Engine(_engine_cfg())
        eng2.add_request(_prompt(8, 10), SamplingParams(max_new_tokens=4))
        eng2.run_until_complete()
        assert eng2.step_stats["sample_s"] == 0.0


class TestSchedulerImportingState:
    """Waiting sequences mid-import are skipped in place, never block
    admission of later arrivals, and stamp the overlap boundary."""

    def test_importing_seq_skipped_and_later_seq_admitted(self):
        eng = Engine(_engine_cfg())
        a = eng.add_request(_prompt(10, 8), SamplingParams(max_new_tokens=2))
        a.importing = True
        b = eng.add_request(_prompt(11, 8), SamplingParams(max_new_tokens=2))
        out = eng.scheduler.schedule()
        assert out.prefill == [b]
        assert a.import_wanted_time is not None  # overlap boundary stamped
        assert a in eng.scheduler.waiting
        # Import lands: the sequence becomes admittable in FCFS position.
        a.importing = False
        out2 = eng.scheduler.schedule()
        assert a in out2.prefill

    def test_importing_seq_skipped_in_chunked_mode(self):
        eng = Engine(
            _engine_cfg(scheduler=SchedulerConfig(
                max_prefill_batch=4, chunked_prefill_tokens=8
            ))
        )
        a = eng.add_request(_prompt(12, 8), SamplingParams(max_new_tokens=2))
        a.importing = True
        b = eng.add_request(_prompt(13, 8), SamplingParams(max_new_tokens=2))
        out = eng.scheduler.schedule()
        assert out.prefill == [b]
        assert a in eng.scheduler.waiting

    def test_has_ready_work_gates_import_only_queues(self):
        eng = Engine(_engine_cfg())
        assert not eng.has_ready_work
        a = eng.add_request(_prompt(14, 8), SamplingParams(max_new_tokens=2))
        assert eng.has_ready_work
        a.importing = True
        assert eng.has_work and not eng.has_ready_work
        eng.add_request(_prompt(15, 8), SamplingParams(max_new_tokens=2))
        assert eng.has_ready_work


class TestAsyncPull:
    def test_async_pull_parity_and_warm_hit(self):
        from conftest import free_tcp_port

        endpoint = f"tcp://127.0.0.1:{free_tcp_port()}"
        warm = PodServer(_pod_config("ap-warm", transfer_endpoint=endpoint))
        cold = PodServer(_pod_config("ap-cold", async_pull=True))
        ref = PodServer(_pod_config("ap-ref"))
        warm.start(), cold.start(), ref.start()
        try:
            prefix = _prompt(20, 16)
            prompt = prefix + _prompt(21, 4)
            warm.generate(prefix, SamplingParams(max_new_tokens=2), timeout=120)

            fut = cold.submit(
                prompt, SamplingParams(max_new_tokens=4), pull_source=endpoint
            )
            s = fut.result(timeout=120)
            s_ref = ref.generate(prompt, SamplingParams(max_new_tokens=4), timeout=120)
            assert s.generated_tokens == s_ref.generated_tokens
            # Admission waited for the import: the warm prefix MUST hit.
            assert s.num_cached_prompt == len(prefix)
            assert cold.async_pulls == 1 and cold.transfer_pulls == 1
            assert not cold._pull_jobs
        finally:
            warm.shutdown(), cold.shutdown(), ref.shutdown()

    def test_dead_peer_falls_back_to_cold_with_parity(self):
        from conftest import free_tcp_port

        cold = PodServer(
            _pod_config("ap-cold2", async_pull=True, transfer_timeout_s=0.5)
        )
        ref = PodServer(_pod_config("ap-ref2"))
        cold.start(), ref.start()
        try:
            prompt = _prompt(22, 12)
            fut = cold.submit(
                prompt,
                SamplingParams(max_new_tokens=3),
                pull_source=f"tcp://127.0.0.1:{free_tcp_port()}",
            )
            s = fut.result(timeout=120)
            s_ref = ref.generate(prompt, SamplingParams(max_new_tokens=3), timeout=120)
            assert s.generated_tokens == s_ref.generated_tokens
            assert s.num_cached_prompt == 0  # cold prefill, not a failure
            assert cold.async_pull_fallbacks == 1
            assert cold.transfer_pull_failures == 1
        finally:
            cold.shutdown(), ref.shutdown()

    def test_stalled_import_never_blocks_other_requests(self):
        from conftest import free_tcp_port

        cold = PodServer(
            _pod_config("ap-cold3", async_pull=True, transfer_timeout_s=10.0)
        )
        cold.start()
        try:
            stalled = cold.submit(
                _prompt(23, 12),
                SamplingParams(max_new_tokens=2),
                pull_source=f"tcp://127.0.0.1:{free_tcp_port()}",
            )
            assert _wait_until(lambda: bool(cold._pull_jobs), timeout=10)
            # A later arrival is admitted straight past the importing head.
            other = cold.submit(_prompt(24, 8), SamplingParams(max_new_tokens=4))
            s = other.result(timeout=60)
            assert len(s.generated_tokens) == 4
            assert not stalled.done()  # the import is still on the wire
            s_stalled = stalled.result(timeout=60)  # then falls back cold
            assert len(s_stalled.generated_tokens) == 2
        finally:
            cold.shutdown()

    def test_abort_mid_import_cancels_fetch_and_frees_pages(self):
        from conftest import free_tcp_port

        cold = PodServer(
            _pod_config("ap-cold4", async_pull=True, transfer_timeout_s=2.0)
        )
        cold.start()
        try:
            free0 = cold.engine.block_manager.num_free
            fut = cold.submit(
                _prompt(25, 12),
                SamplingParams(max_new_tokens=4),
                pull_source=f"tcp://127.0.0.1:{free_tcp_port()}",
            )
            assert _wait_until(lambda: bool(cold._pull_jobs), timeout=10)
            assert cold.abort(fut.request_id).result(timeout=30)
            s = fut.result(timeout=30)
            assert s.finish_reason == "abort"
            # The in-flight fetch is canceled, installs nothing, and the
            # pool returns to baseline (regression: importing-state abort
            # accounting).
            assert _wait_until(lambda: cold.async_pull_canceled == 1, timeout=30)
            assert cold.engine.block_manager.num_free == free0
            assert not cold._pull_jobs
        finally:
            cold.shutdown()

    def test_deadline_clamps_import_and_sheds(self):
        from conftest import free_tcp_port

        cold = PodServer(
            _pod_config("ap-cold5", async_pull=True, transfer_timeout_s=30.0)
        )
        cold.start()
        try:
            t0 = time.monotonic()
            fut = cold.submit(
                _prompt(26, 12),
                SamplingParams(max_new_tokens=4),
                deadline_s=0.3,
                pull_source=f"tcp://127.0.0.1:{free_tcp_port()}",
            )
            s = fut.result(timeout=30)
            # The fetch was clamped to the remaining deadline budget (not
            # the 30 s transfer timeout) and the expired sequence shed.
            assert s.finish_reason == "deadline"
            assert time.monotonic() - t0 < 10.0
        finally:
            cold.shutdown()

    def test_knob_off_ignores_pull_source(self):
        from conftest import free_tcp_port

        plain = PodServer(_pod_config("ap-plain"))
        plain.start()
        try:
            fut = plain.submit(
                _prompt(27, 10),
                SamplingParams(max_new_tokens=3),
                pull_source=f"tcp://127.0.0.1:{free_tcp_port()}",
            )
            s = fut.result(timeout=120)
            assert len(s.generated_tokens) == 3
            assert plain.async_pulls == 0 and plain.async_pull_fallbacks == 0
            assert plain._pull_pool is None  # nothing was ever spawned
        finally:
            plain.shutdown()

    def test_stats_block_gated_on_knob(self):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        async def fetch_stats(server):
            ts = TestServer(server.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                resp = await client.get("/stats")
                return await resp.json()
            finally:
                await client.close()

        on = PodServer(_pod_config("ap-stats-on", async_pull=True))
        off = PodServer(_pod_config("ap-stats-off"))
        on.start(), off.start()
        try:
            stats_on = asyncio.run(fetch_stats(on))
            stats_off = asyncio.run(fetch_stats(off))
            assert set(stats_on["transfer"]["async_pull"]) == {
                "workers", "importing", "pulls", "fallbacks", "canceled"
            }
            assert "async_pull" not in stats_off["transfer"]
        finally:
            on.shutdown(), off.shutdown()


class TestPullOverlapObservability:
    def test_overlap_recorded_on_async_pull(self):
        pytest.importorskip("prometheus_client")
        from conftest import free_tcp_port

        endpoint = f"tcp://127.0.0.1:{free_tcp_port()}"
        warm = PodServer(_pod_config("ov-warm", transfer_endpoint=endpoint))
        cold = PodServer(
            _pod_config(
                "ov-cold", async_pull=True, obs_metrics=True, obs_tracing=True
            )
        )
        warm.start(), cold.start()
        try:
            prefix = _prompt(30, 16)
            warm.generate(prefix, SamplingParams(max_new_tokens=2), timeout=120)
            fut = cold.submit(
                prefix + _prompt(31, 4),
                SamplingParams(max_new_tokens=3),
                pull_source=endpoint,
            )
            fut.result(timeout=120)
            text = cold.metrics.exposition().decode()
            assert 'kvcache_transfer_pull_overlap_seconds_count{kind="hidden"} 1.0' in text
            assert 'kvcache_transfer_pull_overlap_seconds_count{kind="exposed"} 1.0' in text
            # The pull span carries async + overlap attrs.
            spans = [
                sp
                for tr in cold.tracer.traces()
                for sp in tr["spans"]
                if sp["name"] == "pod.pull_prefix"
            ]
            assert spans and spans[0]["attrs"]["async"] is True
            assert "overlap" in spans[0]["attrs"]
        finally:
            warm.shutdown(), cold.shutdown()
