"""Multi-tenant QoS suite (ISSUE 18 acceptance).

The ``TENANT_QOS`` dimension end to end on one pod:

- **Grammar**: the policy parser accepts the documented spec and fails
  loudly at construction on malformed input.
- **429 helper**: one shared reject shape — the ``Retry-After`` header is
  always >= 1 (rounded UP), the JSON body carries the float hint.
- **Per-tenant admission**: a tenant over ITS budget (waiting / queued
  tokens / request rate) gets a tenant-shaped ``AdmissionError`` while
  other tenants keep admitting; rate rejections carry an exact hint.
- **Priority scheduling**: the waiting queue orders by class with
  weighted-fair shares within a class; a blocked higher class preempts a
  strictly lower one (pages back to baseline, greedy outputs preserved,
  ``priority_preempted`` counted).
- **Preempt/shed interplay**: a preempted-then-expired sequence is shed
  exactly once and pages return to baseline through the chain.
- **Cache isolation**: a flooding tenant over its ``cache_share``
  recycles its own LRU pages instead of evicting other tenants' warm
  prefixes.
- **Two-class overload drill**: premium completes token-identical to an
  unloaded run while background degrades to 429/preemption (never 5xx);
  a drain mid-burst leaks no tenant budget accounting.
- **Knobs-off parity**: with ``TENANT_QOS`` unset nothing appears — no
  ``/stats`` keys, no scheduler reordering, no block-manager hooks, no
  tenant metric families.
"""

import asyncio
import json
import threading
import time

import numpy as np
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)
from llm_d_kv_cache_manager_tpu.server.qos import (
    DEFAULT_TENANT,
    RATE_WINDOW_S,
    TenantQoS,
    parse_tenant_qos,
)
from llm_d_kv_cache_manager_tpu.server.scheduler import Scheduler
from llm_d_kv_cache_manager_tpu.server.sequence import Sequence, SequenceStatus
from llm_d_kv_cache_manager_tpu.server.serve import (
    AdmissionError,
    DrainingError,
    PodServer,
    PodServerConfig,
    admission_reject_response,
)

PS = 4
MODEL = "tiny-llama"

TWO_CLASS = "premium:prio=0,weight=4;batch:prio=1"


def _engine_config(total_pages=64, **kw):
    kw.setdefault("max_model_len", 64)
    return EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(total_pages=total_pages, page_size=PS),
        scheduler=SchedulerConfig(max_prefill_batch=4, **kw.pop("scheduler_kw", {})),
        decode_batch_size=4,
        prefill_bucket=8,
        interpret=True,
        **kw,
    )


def _server(total_pages=64, **cfg_kw):
    cfg = PodServerConfig(
        model_name=MODEL,
        pod_identifier="qos-pod",
        publish_events=False,
        engine=_engine_config(total_pages=total_pages, **cfg_kw.pop("engine_kw", {})),
        **cfg_kw,
    )
    return PodServer(cfg)


def _prompt(seed, n):
    return list(
        map(int, np.random.default_rng(seed).integers(0, TINY_LLAMA.vocab_size, n))
    )


def _gate_engine(server, gate):
    """Block engine steps while ``gate`` is cleared (requests pile up in
    staging/waiting deterministically; admissions still run)."""
    orig = server.engine.step

    def gated_step():
        if not gate.is_set():
            gate.wait(10)
        return orig()

    server.engine.step = gated_step
    return orig


def _wait_until(predicate, timeout=30.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return bool(predicate())


def _seq(tenant="", priority=0, weight=1.0, n=4, seed=0):
    s = Sequence(prompt_tokens=_prompt(seed, n), sampling=SamplingParams())
    s.tenant = tenant
    s.priority = priority
    s.qos_weight = weight
    return s


class TestGrammar:
    def test_full_spec_parses(self):
        p = parse_tenant_qos(
            "premium:prio=0,weight=4;"
            "batch:prio=1,max_waiting=8,max_queued_tokens=512,rps=5,"
            "cache_share=0.25;*:prio=1"
        )
        assert sorted(p) == ["*", "batch", "premium"]
        assert p["premium"].priority == 0 and p["premium"].weight == 4.0
        b = p["batch"]
        assert (b.max_waiting, b.max_queued_tokens, b.rps, b.cache_share) == (
            8, 512, 5.0, 0.25,
        )

    def test_default_entry_synthesized_at_lowest_class(self):
        p = parse_tenant_qos("premium:prio=0;batch:prio=3")
        assert p[DEFAULT_TENANT].priority == 3  # never above a named tenant
        assert p[DEFAULT_TENANT].max_waiting == 0  # and never hard-rejected

    def test_bare_name_entry(self):
        p = parse_tenant_qos("premium")
        assert p["premium"].priority == 0 and p["premium"].weight == 1.0

    @pytest.mark.parametrize(
        "spec",
        [
            "",  # set but empty
            "  ;  ",  # no entries
            ":prio=0",  # no name
            "a:prio=0;a:prio=1",  # duplicate
            "a:bogus=1",  # unknown key
            "a:prio=zero",  # bad value
            "a:prio",  # no '='
            "a:weight=0",  # weight must be > 0
            "a:weight=-1",
            "a:cache_share=1.5",  # share outside [0, 1]
            "a:max_waiting=-1",  # negative budget
            "a:rps=-2",
        ],
    )
    def test_malformed_specs_raise(self, spec):
        with pytest.raises(ValueError, match="TENANT_QOS"):
            parse_tenant_qos(spec)

    def test_unknown_tenant_collapses_to_default(self):
        q = TenantQoS(parse_tenant_qos("premium:prio=0;*:prio=2"))
        assert q.key("premium") == "premium"
        assert q.key("") == DEFAULT_TENANT
        assert q.key("invented-name") == DEFAULT_TENANT
        assert q.policy("invented-name").priority == 2


class TestRejectResponseHelper:
    """Satellite: one 429 shape — header rounded UP and floored at 1,
    body carries the float hint verbatim."""

    @pytest.mark.parametrize(
        "hint,header", [(0.2, "1"), (1.0, "1"), (3.2, "4"), (59.5, "60")]
    )
    def test_header_rounds_up_and_floors_at_one(self, hint, header):
        resp = admission_reject_response(web, AdmissionError("overloaded", hint))
        assert resp.status == 429
        assert resp.headers["Retry-After"] == header
        body = json.loads(resp.text)
        assert body["retry_after_s"] == hint  # float, not the rounded int
        assert body["error"] == "overloaded"


class TestTenantAdmission:
    def test_per_tenant_max_waiting_isolates(self):
        """batch over ITS cap is rejected while premium keeps admitting —
        and the pod-wide caps never fired (they are off)."""
        server = _server(tenant_qos="premium:prio=0;batch:prio=1,max_waiting=2")
        gate = threading.Event()
        _gate_engine(server, gate)
        server.start()
        try:
            ok = [
                server.submit(
                    _prompt(i, 8), SamplingParams(max_new_tokens=2), tenant="batch"
                )
                for i in range(2)
            ]
            with pytest.raises(AdmissionError, match="'batch' over max_waiting"):
                server.submit(
                    _prompt(9, 8), SamplingParams(max_new_tokens=2), tenant="batch"
                )
            # Premium is untouched by batch's budget.
            prem = server.submit(
                _prompt(10, 8), SamplingParams(max_new_tokens=2), tenant="premium"
            )
            assert server.admission_rejected == 1
            assert server.qos.rejected["batch"]["waiting"] == 1
            gate.set()
            for f in ok + [prem]:
                assert f.result(timeout=120).num_generated == 2
            # Budgets drain with the queue: batch admits again.
            f = server.submit(
                _prompt(11, 8), SamplingParams(max_new_tokens=2), tenant="batch"
            )
            assert f.result(timeout=120).num_generated == 2
        finally:
            gate.set()
            server.shutdown()

    def test_per_tenant_queued_tokens_cap(self):
        server = _server(
            tenant_qos="batch:max_queued_tokens=20;*:prio=0"
        )
        gate = threading.Event()
        _gate_engine(server, gate)
        server.start()
        try:
            server.submit(
                _prompt(0, 16), SamplingParams(max_new_tokens=1), tenant="batch"
            )
            with pytest.raises(AdmissionError, match="over max_queued_tokens"):
                server.submit(
                    _prompt(1, 16), SamplingParams(max_new_tokens=1), tenant="batch"
                )
            assert server.qos.rejected["batch"]["tokens"] == 1
        finally:
            gate.set()
            server.shutdown()

    def test_rate_budget_exact_hint(self):
        """Unit: the rps window rejects with an exact expiry hint."""
        q = TenantQoS(
            parse_tenant_qos("batch:rps=0.2"), clock=lambda: 100.0
        )
        # budget = rps * window = 2 admissions per sliding window
        assert q.admit("batch", 4, now=100.0) is None
        q.on_admitted("batch", 4, now=100.0)
        assert q.admit("batch", 4, now=101.0) is None
        q.on_admitted("batch", 4, now=101.0)
        verdict = q.admit("batch", 4, now=102.0)
        assert verdict is not None
        cap, message, hint, _, _ = verdict
        assert cap == "rate" and "request-rate budget" in message
        # Oldest event (t=100) leaves the 10 s window at t=110 → hint 8 s.
        assert hint == pytest.approx(100.0 + RATE_WINDOW_S - 102.0)
        # The window slides: at t=111 both events expired, admits again.
        assert q.admit("batch", 4, now=111.0) is None

    def test_rate_budget_rejects_over_http_with_tenant_shape(self):
        """Integration: the tenant 429 rides the shared helper — header
        int >= 1, body float, tenant named in the error."""
        server = _server(tenant_qos="batch:rps=0.1;*:prio=0")
        server.start()

        async def scenario():
            ts = TestServer(server.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                first = await client.post(
                    "/v1/completions",
                    json={"prompt_token_ids": _prompt(0, 8), "max_tokens": 1},
                    headers={"X-Tenant": "batch"},
                )
                assert first.status == 200
                resp = await client.post(
                    "/v1/completions",
                    json={"prompt_token_ids": _prompt(1, 8), "max_tokens": 1},
                    headers={"X-Tenant": "batch"},
                )
                assert resp.status == 429
                assert int(resp.headers["Retry-After"]) >= 1
                data = await resp.json()
                assert "'batch'" in data["error"]
                assert isinstance(data["retry_after_s"], float)
                # Unknown tenants share "*" — not batch's burned budget.
                other = await client.post(
                    "/v1/completions",
                    json={"prompt_token_ids": _prompt(2, 8), "max_tokens": 1},
                    headers={"X-Tenant": "someone-else"},
                )
                assert other.status == 200
            finally:
                await client.close()

        try:
            asyncio.run(scenario())
        finally:
            server.shutdown()


class TestPriorityScheduling:
    def test_waiting_queue_orders_by_class_then_fair_share(self):
        """Unit: stable sort by (class, served/weight) — priority first,
        then the tenant furthest under its weighted share, FIFO within a
        tenant."""
        sch = Scheduler(block_manager=None)
        sch.attach_qos()
        a1 = _seq("batch", priority=1, seed=1)
        b1 = _seq("premium", priority=0, weight=4.0, seed=2)
        a2 = _seq("batch", priority=1, seed=3)
        c1 = _seq("bulk", priority=1, weight=1.0, seed=4)
        for s in (a1, b1, a2, c1):
            sch.add(s)
        # batch has been served 100 tokens; bulk none → within class 1,
        # bulk goes first. premium (class 0) leads regardless.
        sch._qos_charge(a1, 100)
        sch.qos_reorder_waiting()
        assert list(sch.waiting) == [b1, c1, a1, a2]
        # Weight scales the share: premium's 400 served / weight 4 == 100
        # normalized — still ahead of nothing in its own class.
        sch._qos_charge(b1, 400)
        sch.qos_reorder_waiting()
        assert list(sch.waiting)[0] is b1

    def test_reorder_off_is_noop(self):
        sch = Scheduler(block_manager=None)
        s1, s2 = _seq(seed=1), _seq(seed=2)
        sch.add(s1)
        sch.add(s2)
        sch.qos_reorder_waiting()  # qos_enabled is False
        assert list(sch.waiting) == [s1, s2]

    def test_priority_preemption_end_to_end(self):
        """A blocked premium prefill preempts the background decode; both
        finish with the exact unloaded greedy outputs and every page
        returns to baseline."""
        # 10-page pool: bg holds ~3 pages while decoding, so premium's
        # 28-token prompt (8 pages) cannot allocate without preemption.
        bg_prompt, prem_prompt = _prompt(50, 8), _prompt(51, 28)
        bg_params = SamplingParams(max_new_tokens=12)
        prem_params = SamplingParams(max_new_tokens=4)

        baseline = _server(total_pages=10)
        baseline.start()
        try:
            expect_bg = baseline.generate(
                bg_prompt, bg_params, timeout=120
            ).generated_tokens
            expect_prem = baseline.generate(
                prem_prompt, prem_params, timeout=120
            ).generated_tokens
        finally:
            baseline.shutdown()

        server = _server(total_pages=10, tenant_qos=TWO_CLASS)
        server.start()
        try:
            free0 = server.engine.block_manager.num_free
            bg = server.submit(bg_prompt, bg_params, tenant="batch")
            assert _wait_until(
                lambda: any(
                    s.num_generated > 0 for s in server.engine.scheduler.running
                )
            )
            prem = server.submit(prem_prompt, prem_params, tenant="premium")
            bg_seq = bg.result(timeout=120)
            prem_seq = prem.result(timeout=120)
            # The background sequence was preempted for the premium
            # prefill (pool of 9 usable pages cannot hold both)...
            assert server.engine.lifecycle_stats.get("priority_preempted", 0) >= 1
            # ...and the recompute fold preserved its greedy output.
            assert bg_seq.generated_tokens == expect_bg
            assert prem_seq.generated_tokens == expect_prem
            assert bg_seq.finish_reason is None and prem_seq.finish_reason is None
            assert _wait_until(
                lambda: server.engine.block_manager.num_free == free0
            )
        finally:
            server.shutdown()

    def test_same_class_never_preempted(self):
        """Preemption only crosses DOWN in class: an equal-class victim
        candidate set is empty, the head just waits."""
        server = _server(total_pages=10, tenant_qos="a:prio=1;b:prio=1")
        server.start()
        try:
            f1 = server.submit(
                _prompt(60, 8), SamplingParams(max_new_tokens=12), tenant="a"
            )
            _wait_until(
                lambda: any(
                    s.num_generated > 0 for s in server.engine.scheduler.running
                )
            )
            f2 = server.submit(
                _prompt(61, 20), SamplingParams(max_new_tokens=4), tenant="b"
            )
            assert f1.result(timeout=120).generated_tokens
            assert f2.result(timeout=120).generated_tokens
            assert server.engine.lifecycle_stats.get("priority_preempted", 0) == 0
        finally:
            server.shutdown()


class TestPreemptShedInterplay:
    def test_preempted_then_expired_sequence_shed_once(self):
        """Satellite: preempt → deadline-expire → shed counts ONE shed,
        one preemption, and the pages walk back to baseline through the
        whole chain; a late abort of the dead request is a clean no-op."""
        server = _server(total_pages=10, tenant_qos=TWO_CLASS)
        server.start()
        try:
            free0 = server.engine.block_manager.num_free
            # Expire the preempted (now WAITING) background request: the
            # next shed scan drops it before any re-prefill compute. On the
            # engine's thread, as the victim is folded: a loaded test thread
            # that came to it a moment later found it admitted again.
            scheduler = server.engine.scheduler
            on_preempted = scheduler.on_preempted

            def expire(seq):
                on_preempted(seq)
                seq.deadline = time.monotonic() - 1.0

            scheduler.on_preempted = expire
            bg = server.submit(
                _prompt(70, 8),
                SamplingParams(max_new_tokens=32),
                tenant="batch",
                deadline_s=600,
            )
            assert _wait_until(
                lambda: any(s.num_generated > 0 for s in scheduler.running),
                timeout=120,  # the first programs compile under six workers
            )
            prem = server.submit(
                _prompt(71, 28), SamplingParams(max_new_tokens=4), tenant="premium"
            )
            assert _wait_until(
                lambda: server.engine.lifecycle_stats.get("priority_preempted", 0)
                >= 1,
                timeout=120,
            )
            bg_seq = bg.result(timeout=120)
            prem_seq = prem.result(timeout=120)
            assert bg_seq.finish_reason == "deadline"
            assert prem_seq.finish_reason is None
            assert server.engine.lifecycle_stats["deadline_shed"] == 1
            assert server.engine.lifecycle_stats.get("priority_preempted", 0) == 1
            assert _wait_until(
                lambda: server.engine.block_manager.num_free == free0
            )
            # Aborting the already-shed request finds nothing alive.
            assert server.abort(bg.request_id).result(timeout=30) is False
            assert server.engine.lifecycle_stats["aborted"] == 0
        finally:
            server.shutdown()


class TestCacheShare:
    def test_flooding_tenant_recycles_its_own_pages(self):
        """batch over its evictable share recycles its own LRU pages, so
        premium's warm prefix survives a flood that would have evicted it
        under plain pool-wide LRU."""
        server = _server(
            total_pages=16,
            tenant_qos="premium:prio=0;batch:prio=1,cache_share=0.25",
        )
        server.start()
        try:
            prem_prompt = _prompt(80, 12)
            params = SamplingParams(max_new_tokens=2)
            # Warm premium's prefix chain.
            server.submit(prem_prompt, params, tenant="premium").result(120)
            for i in range(8):  # distinct prompts: pure churn
                fut = server.submit(_prompt(81 + i, 12), params, tenant="batch")
                fut.result(timeout=120)
            seq = server.submit(prem_prompt, params, tenant="premium").result(120)
            bm = server.engine.block_manager
            # The flood hit batch's cap (recycled its own pages)...
            assert bm.tenant_stats["batch"]["capped_evictions"] > 0
            # ...and premium's warm chain survived it.
            assert seq.num_cached_prompt > 0
            assert bm.tenant_stats["premium"]["cached_tokens"] > 0
        finally:
            server.shutdown()

    def test_cache_cap_pages_floor(self):
        q = TenantQoS(parse_tenant_qos("a:cache_share=0.001;b:prio=0"))
        assert q.cache_cap_pages("a", 100) == 1  # floored at one page
        assert q.cache_cap_pages("b", 100) is None  # uncapped
        assert q.cache_cap_pages("unknown", 100) is None


class TestOverloadDrill:
    def test_premium_token_identical_while_background_degrades(self):
        """Satellite: a background burst over its budget degrades to
        429s; every admitted request (both classes) completes; premium's
        greedy outputs match the unloaded run bit-for-bit."""
        prem_prompts = [_prompt(200 + i, 10) for i in range(3)]
        params = SamplingParams(max_new_tokens=4)

        baseline = _server()
        baseline.start()
        try:
            expect = [
                baseline.generate(p, params, timeout=120).generated_tokens
                for p in prem_prompts
            ]
        finally:
            baseline.shutdown()

        server = _server(
            tenant_qos="premium:prio=0,weight=4;batch:prio=1,max_waiting=2"
        )
        gate = threading.Event()
        _gate_engine(server, gate)
        server.start()
        try:
            admitted, rejected = [], 0
            for i in range(6):
                try:
                    admitted.append(
                        server.submit(_prompt(300 + i, 8), params, tenant="batch")
                    )
                except AdmissionError:
                    rejected += 1  # the 429 arm: graceful, not an error
            assert len(admitted) == 2 and rejected == 4
            prem_futs = [
                server.submit(p, params, tenant="premium") for p in prem_prompts
            ]
            gate.set()
            for fut, want in zip(prem_futs, expect):
                assert fut.result(timeout=120).generated_tokens == want
            for fut in admitted:  # background degrades, never 5xx
                assert fut.result(timeout=120).num_generated == 4
            snap = server.qos.snapshot()["tenants"]
            assert snap["batch"]["rejected"]["waiting"] == 4
            assert snap["premium"]["rejected"] == {
                "waiting": 0, "tokens": 0, "rate": 0,
            }
        finally:
            gate.set()
            server.shutdown()

    def test_drain_mid_burst_leaks_no_tenant_budget(self):
        """Satellite: a graceful drain in the middle of a two-class burst
        resolves every admitted request and walks every tenant budget
        back to zero; draining rejects never touch the budgets."""
        server = _server(
            tenant_qos=TWO_CLASS, drain_timeout_s=60.0
        )
        gate = threading.Event()
        _gate_engine(server, gate)
        server.start()
        try:
            params = SamplingParams(max_new_tokens=2)
            futs = [
                server.submit(_prompt(400 + i, 8), params, tenant=t)
                for i, t in enumerate(["premium", "batch", "premium", "batch"])
            ]
            with server._mu:
                assert server.qos.pending["premium"] == 2
                assert server.qos.pending["batch"] == 2
            drainer = threading.Thread(target=server.drain, daemon=True)
            drainer.start()
            assert _wait_until(lambda: server._draining)
            with pytest.raises(DrainingError):
                server.submit(_prompt(499, 8), params, tenant="premium")
            gate.set()
            drainer.join(timeout=120)
            assert not drainer.is_alive()
            for fut in futs:
                assert fut.result(timeout=120).num_generated == 2
            with server._mu:
                assert all(v == 0 for v in server.qos.pending.values())
                assert all(v == 0 for v in server.qos.pending_tokens.values())
        finally:
            gate.set()
            server.shutdown()


class TestShedDedup:
    def test_finished_sequence_in_waiting_not_counted_again(self):
        """Scheduler unit: a sequence that already finished (e.g. aborted
        after a preemption re-queued it) is dropped from waiting without
        re-entering the shed list."""
        sch = Scheduler(block_manager=None)
        dead = _seq(seed=1)
        dead.status = SequenceStatus.FINISHED
        dead.finish_reason = "abort"
        dead.deadline = 0.0  # expired — but must NOT be shed again
        live_expired = _seq(seed=2)
        live_expired.deadline = 0.0
        survivor = _seq(seed=3)
        survivor.deadline = 1e12
        for s in (dead, live_expired, survivor):
            sch.add(s)
        dead.status = SequenceStatus.FINISHED  # add() resets status
        shed = sch.shed_expired(now=1.0)
        assert shed == [live_expired]
        assert live_expired.finish_reason == "deadline"
        assert dead.finish_reason == "abort"  # untouched
        assert list(sch.waiting) == [survivor]
        # Idempotent: nothing left to shed.
        assert sch.shed_expired(now=2.0) == []


class TestTenantObservability:
    def test_stats_mrc_and_metrics_slices(self):
        server = _server(
            tenant_qos=TWO_CLASS,
            obs_slo="ttft:30:0.9",
            obs_lifecycle=True,
            obs_metrics=True,
        )
        server.start()

        async def scenario():
            ts = TestServer(server.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                for tenant, seed in (("premium", 0), ("batch", 1), (None, 2)):
                    headers = {"X-Tenant": tenant} if tenant else {}
                    resp = await client.post(
                        "/v1/completions",
                        json={
                            "prompt_token_ids": _prompt(seed, 8),
                            "max_tokens": 2,
                        },
                        headers=headers,
                    )
                    assert resp.status == 200
                stats = await (await client.get("/stats")).json()
                tq = stats["tenant_qos"]
                assert set(tq["tenants"]) == {"*", "batch", "premium"}
                assert tq["tenants"]["premium"]["admitted"] == 1
                assert tq["tenants"]["*"]["admitted"] == 1  # headerless
                assert tq["qos_served_tokens"]["premium"] > 0
                assert "evictable_pages" in tq["cache"]
                assert tq["cache"]["stats"]["premium"]["requests"] == 1
                # Per-tenant SLO burn slices (same objectives).
                assert "ttft_le_30s_p0.9" in tq["slo_burn"]["premium"]
                # Tenant-labeled ledger rows.
                assert stats["lifecycle"]["tenants"]["premium"] > 0
                # Per-tenant MRC curves.
                mrc = await (await client.get("/debug/mrc")).json()
                assert set(mrc["tenants"]) >= {"batch", "premium"}
                assert mrc["tenants"]["premium"]["enabled"] is True
                # The tenant burn gauge appears on the exposition.
                metrics = await (await client.get("/metrics")).text()
                assert 'kvcache_tenant_slo_burn_rate{' in metrics
                assert 'tenant="premium"' in metrics
            finally:
                await client.close()

        try:
            asyncio.run(scenario())
        finally:
            server.shutdown()


class TestKnobsOffParity:
    def test_config_defaults_off(self):
        assert PodServerConfig().tenant_qos == ""

    def test_no_tenant_surface_anywhere(self):
        """With TENANT_QOS unset: no /stats keys, no scheduler ordering,
        no block-manager hooks, no tenant metric family — and a tenant
        passed anyway is ignored."""
        server = _server(obs_slo="ttft:30:0.9", obs_lifecycle=True)
        server.start()

        async def scenario():
            ts = TestServer(server.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                resp = await client.post(
                    "/v1/completions",
                    json={"prompt_token_ids": _prompt(0, 8), "max_tokens": 2},
                    headers={"X-Tenant": "premium"},  # ignored, knob off
                )
                assert resp.status == 200
                stats = await (await client.get("/stats")).json()
                assert "tenant_qos" not in stats
                assert "priority_preempted" not in stats["admission"]
                assert "tenants" not in stats["lifecycle"]
                mrc = await (await client.get("/debug/mrc")).json()
                assert "tenants" not in mrc
                metrics = await (await client.get("/metrics")).text()
                assert "kvcache_tenant_slo_burn_rate" not in metrics
            finally:
                await client.close()

        try:
            asyncio.run(scenario())
            assert server.qos is None
            assert server.engine.scheduler.qos_enabled is False
            assert server.engine.block_manager._qos is None
            assert server.slo.track_tenants is False
        finally:
            server.shutdown()
