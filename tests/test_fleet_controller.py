"""Fleet controller acceptance (ISSUE 17): MRC-driven cache-aware
autoscaling with live KV migration.

Four layers, bottom-up:

- the MigrateSeq/MigrateAck wire frames (round-trip, tolerance, and the
  legacy-service refusal that keeps knob-off fleets interoperable);
- fleet MRC aggregation — the satellite-2 identity: the aggregate curve
  equals the per-pod sampled-weighted sum on a synthetic stream;
- the controller's decision table over a scripted fleet, including the
  chaos flap scenario (scale-up demanded right after a scale-down
  converges under hysteresis instead of oscillating);
- live migration over real ZMQ between real ``PodServer``s: greedy
  parity migrated-vs-unmigrated, the chaos fallback (target dies
  mid-migration → the sequence finishes locally, token-identical, pages
  back to baseline), and the in-process fleet's end-to-end scale-down /
  warm-revival scale-up.
"""

import time

import numpy as np
import pytest

from conftest import free_tcp_port
from llm_d_kv_cache_manager_tpu.kvcache.controller import (
    FleetController,
    FleetControllerConfig,
    FleetDecision,
    InProcessFleet,
    PodSignals,
    aggregate_mrc,
    fleet_burn,
    hit_rate_at,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvevents import (
    FleetHealth,
    FleetHealthConfig,
)
from llm_d_kv_cache_manager_tpu.kvcache.transfer import (
    KVTransferService,
    MigrationPayload,
    TransferServiceConfig,
    decode_migrate,
    decode_migrate_ack,
    encode_migrate,
    encode_migrate_ack,
)
from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
from llm_d_kv_cache_manager_tpu.obs.lifecycle import (
    REUSE_DISTANCE_BUCKETS,
    ReuseDistanceEstimator,
    debug_mrc_payload,
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


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _pod_config(pod_id, total_pages=64, **kw):
    return PodServerConfig(
        model_name=MODEL,
        pod_identifier=pod_id,
        publish_events=False,
        engine=EngineConfig(
            model=TINY_LLAMA,
            block_manager=BlockManagerConfig(
                total_pages=total_pages, page_size=PS
            ),
            scheduler=SchedulerConfig(max_prefill_batch=4),
            max_model_len=64,
            decode_batch_size=4,
            prefill_bucket=8,
            interpret=True,
        ),
        **kw,
    )


def _prompt(seed, n):
    return list(
        map(int, np.random.default_rng(seed).integers(0, TINY_LLAMA.vocab_size, n))
    )


#: what a request generates that is to be caught in the middle of its decode:
#: with the page-walking decode kernel (PR 57) the interpreted tiny model makes
#: a token in a millisecond, 12 tokens were over between two looks of
#: ``_wait_mid_decode`` on an idle machine, and the migration found the
#: request finished. 16 + 40 fit ``max_model_len``.
MID_DECODE_TOKENS = 40


def _wait_mid_decode(server, rid, min_generated=4, timeout=120):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            seqs = list(server.engine.scheduler.running) + list(
                server.engine.scheduler.prefilling
            )
        except RuntimeError:  # deque mutated mid-iteration; retry
            continue
        if any(
            s.request_id == rid and s.num_generated >= min_generated
            for s in seqs
        ):
            return
        time.sleep(0.002)
    raise AssertionError(f"{rid} never reached mid-decode")


def _migration(rid="r1", n_tokens=8, **kw):
    fields = dict(
        request_id=rid,
        token_ids=list(range(n_tokens)),
        user_prompt_len=4,
        num_generated=4,
        max_new_tokens=16,
        temperature=0.0,
        top_k=0,
        top_p=1.0,
        stop_token_ids=(2,),
        deadline_remaining_s=1.5,
        blocks=[],
    )
    fields.update(kw)
    return MigrationPayload(**fields)


# ---------------------------------------------------------------------------
# Wire frames
# ---------------------------------------------------------------------------
class TestMigrateProtocol:
    def test_migrate_round_trip(self):
        m = _migration()
        got = decode_migrate(encode_migrate(MODEL, "pod-src", m))
        assert got is not None
        model, source, out = got
        assert (model, source) == (MODEL, "pod-src")
        assert out.token_ids == m.token_ids
        assert out.user_prompt_len == 4 and out.num_generated == 4
        assert out.max_new_tokens == 16 and out.temperature == 0.0
        assert out.stop_token_ids == (2,)
        assert out.deadline_remaining_s == pytest.approx(1.5)

    def test_no_deadline_round_trips_as_none(self):
        m = _migration(deadline_remaining_s=None)
        _, _, out = decode_migrate(encode_migrate(MODEL, "p", m))
        assert out.deadline_remaining_s is None

    def test_ack_round_trip(self):
        assert decode_migrate_ack(encode_migrate_ack(3, True)) == (3, True, None)
        assert decode_migrate_ack(encode_migrate_ack(0, False)) == (
            0,
            False,
            None,
        )

    def test_garbage_decodes_to_none(self):
        for junk in (b"", b"\xc1", encode_migrate_ack(1, True)):
            assert decode_migrate(junk) is None
        for junk in (b"", b"\xc1", encode_migrate(MODEL, "p", _migration())):
            assert decode_migrate_ack(junk) is None

    def test_legacy_service_refuses_migrate(self):
        """A FLEET_CONTROLLER-off service answers a migrate with a plain
        error the source reads as "resume locally" — no knob-off service
        ever admits a migrated sequence."""
        svc = KVTransferService(
            TransferServiceConfig(model_name=MODEL), handler=lambda h, c: []
        )
        reply = svc._handle(encode_migrate(MODEL, "p", _migration()))
        _, _, err = decode_migrate_ack(reply)
        assert err is not None and "unsupported" in err
        assert svc.migrations_served == 0


# ---------------------------------------------------------------------------
# Fleet MRC aggregation (satellite 2)
# ---------------------------------------------------------------------------
class TestFleetMRC:
    def _payload(self, stream, sample_rate=1.0):
        est = ReuseDistanceEstimator(sample_rate=sample_rate)
        for chain in stream:
            est.observe_chain(chain)
        return debug_mrc_payload(est)[1], est

    def test_aggregate_equals_per_pod_sum_on_synthetic_stream(self):
        """THE satellite-2 identity: at every grid capacity the aggregate
        hit rate equals the per-pod sampled-weighted sum — what a single
        estimator over the pooled (disjoint) stream would measure."""
        # Pod A: tight loop over 4 chains of 8 blocks — short distances.
        a_chains = [[h for h in range(c * 100, c * 100 + 8)] for c in range(4)]
        stream_a = a_chains * 20
        # Pod B: wide scan over 64 chains — long distances, mostly cold.
        b_chains = [
            [h for h in range(10_000 + c * 100, 10_000 + c * 100 + 8)]
            for c in range(64)
        ]
        stream_b = b_chains * 2
        pay_a, est_a = self._payload(stream_a)
        pay_b, est_b = self._payload(stream_b)
        agg = aggregate_mrc({"a": pay_a, "b": pay_b})
        assert agg["enabled"] and agg["pods"] == 2
        assert agg["sampled"] == est_a.sampled + est_b.sampled
        for row in agg["curve"]:
            cap = row["capacity_blocks"]
            ha = est_a.predicted_hit_rate(cap)
            hb = est_b.predicted_hit_rate(cap)
            want = (ha * est_a.sampled + hb * est_b.sampled) / (
                est_a.sampled + est_b.sampled
            )
            assert row["predicted_hit_rate"] == pytest.approx(want, abs=1e-3)

    def test_empty_and_disabled_pods_contribute_nothing(self):
        pay, est = self._payload([[1, 2, 3]] * 10)
        agg = aggregate_mrc(
            {"a": pay, "off": {"enabled": False}, "none": None}
        )
        assert agg["pods"] == 1
        assert agg["sampled"] == est.sampled
        assert aggregate_mrc({}) == aggregate_mrc({"x": None})

    def test_hit_rate_at_interpolates(self):
        curve = [
            {"capacity_blocks": 64, "predicted_hit_rate": 0.2},
            {"capacity_blocks": 128, "predicted_hit_rate": 0.6},
        ]
        assert hit_rate_at(curve, 32) == pytest.approx(0.2)
        assert hit_rate_at(curve, 96) == pytest.approx(0.4)
        assert hit_rate_at(curve, 500) == pytest.approx(0.6)
        assert hit_rate_at([], 64) is None

    def test_scorer_fleet_debug_mrc(self):
        """The scorer aggregates whatever pods report and answers
        disabled-shaped until anyone does."""
        from llm_d_kv_cache_manager_tpu.server.api import (
            ScoringService,
            ServiceConfig,
        )

        svc = ScoringService(
            ServiceConfig(native_index=False, enable_metrics=False)
        )
        assert svc.fleet_mrc()["enabled"] is False
        pay, est = self._payload([[1, 2, 3, 4]] * 10)
        svc.report_mrc("pod-a", pay)
        agg = svc.fleet_mrc()
        assert agg["enabled"] and agg["pods"] == 1
        assert agg["sampled"] == est.sampled
        svc.report_mrc("pod-a", None)  # retired pod stops voting
        assert svc.fleet_mrc()["enabled"] is False


# ---------------------------------------------------------------------------
# Decision table (scripted fleet, no real pods)
# ---------------------------------------------------------------------------
def _curve(hit_fn):
    return [
        {
            "capacity_blocks": c,
            "predicted_hit_rate": round(hit_fn(c), 4),
            "miss_ratio": round(1 - hit_fn(c), 4),
        }
        for c in REUSE_DISTANCE_BUCKETS
    ]


#: steep MRC: one more pod's capacity buys real hit rate
STEEP = {
    "enabled": True,
    "sampled": 1000,
    "accesses": 1000,
    "cold": 10,
    "curve": _curve(lambda c: min(c / 512.0, 0.95)),
}
#: flat MRC: the working set already fits — capacity buys nothing
FLAT = {
    "enabled": True,
    "sampled": 1000,
    "accesses": 1000,
    "cold": 10,
    "curve": _curve(lambda c: 0.9),
}
BURNING = {"ttft_le_0.5s_p0.99": {"60s": 5.0, "300s": 3.0}}
CALM = {"ttft_le_0.5s_p0.99": {"60s": 0.1, "300s": 0.2}}


def _signals(n, burn, mrc, live=0, capacity=63):
    return [
        PodSignals(
            pod_id=f"pod-{i}",
            transfer_endpoint=f"tcp://pod-{i}",
            capacity_blocks=capacity,
            burn_rates=burn,
            mrc=mrc,
            live_requests=[f"req-{i}-{j}" for j in range(live)],
        )
        for i in range(n)
    ]


class ScriptedFleet:
    """FleetAdapter whose observation is set by the test."""

    def __init__(self, signals):
        self.signals = signals
        self.added = []
        self.migrations = []
        self.retired = []

    def observe(self):
        return self.signals

    def add_pod(self):
        pod = PodSignals(
            pod_id=f"new-{len(self.added)}",
            transfer_endpoint=None,
            capacity_blocks=63,
        )
        self.added.append(pod.pod_id)
        self.signals = self.signals + [pod]
        return pod

    def migrate(self, pod_id, request_id, target_endpoint):
        self.migrations.append((pod_id, request_id, target_endpoint))
        return True

    def retire(self, pod_id):
        self.retired.append(pod_id)
        self.signals = [p for p in self.signals if p.pod_id != pod_id]

    def warm_sets(self, limit):
        return []

    def revive(self, pod_id, source_endpoint, chain_hashes):
        return 0


def _controller(fleet, clock, **cfg_kw):
    kw = dict(enabled=True, hysteresis_s=60.0, min_pods=1, max_pods=4)
    kw.update(cfg_kw)
    return FleetController(
        FleetControllerConfig(**kw), fleet, clock=clock
    )


class TestDecisions:
    def test_fleet_burn_is_the_worst_window(self):
        pods = _signals(2, CALM, None) + _signals(1, BURNING, None)
        assert fleet_burn(pods) == 5.0
        assert fleet_burn(_signals(2, None, None)) is None

    def test_scale_up_on_burn_with_mrc_headroom(self):
        fleet = ScriptedFleet(_signals(2, BURNING, STEEP))
        ctl = _controller(fleet, FakeClock())
        d = ctl.reconcile()
        assert d.action == "scale_up" and d.reason == "burn_with_mrc_headroom"
        assert fleet.added == ["new-0"]
        assert d.hit_up > d.hit_now

    def test_burning_but_flat_mrc_holds(self):
        """Latency burns but more cache can't absorb it: compute-bound —
        the controller records the blocked decision instead of buying
        pages that cannot help."""
        fleet = ScriptedFleet(_signals(2, BURNING, FLAT))
        d = _controller(fleet, FakeClock()).reconcile()
        assert d.action == "hold" and d.reason == "burning_mrc_flat"
        assert fleet.added == []

    def test_burning_without_mrc_holds(self):
        fleet = ScriptedFleet(_signals(2, BURNING, None))
        d = _controller(fleet, FakeClock()).reconcile()
        assert d.action == "hold" and d.reason == "burning_no_mrc"

    def test_burning_at_max_pods_holds(self):
        fleet = ScriptedFleet(_signals(2, BURNING, STEEP))
        d = _controller(fleet, FakeClock(), max_pods=2).reconcile()
        assert d.action == "hold" and d.reason == "burning_at_max_pods"

    def test_scale_down_when_idle_and_flat(self):
        fleet = ScriptedFleet(_signals(3, CALM, FLAT, live=1))
        ctl = _controller(fleet, FakeClock())
        d = ctl.reconcile()
        assert d.action == "scale_down" and d.reason == "idle_mrc_flat"
        assert len(fleet.retired) == 1
        # Every one of the victim's live sequences was migrated to a
        # survivor, least-loaded first.
        assert d.migrated == 1 and d.migration_fallbacks == 0
        assert fleet.migrations[0][0] == d.pod_id

    def test_scale_down_respects_min_pods(self):
        fleet = ScriptedFleet(_signals(1, CALM, FLAT))
        d = _controller(fleet, FakeClock(), min_pods=1).reconcile()
        assert d.action == "hold" and fleet.retired == []

    def test_steep_curve_blocks_scale_down(self):
        """The curve still climbs at current capacity: the last pod's
        pages ARE earning hits — keep them."""
        fleet = ScriptedFleet(_signals(3, CALM, STEEP))
        d = _controller(fleet, FakeClock()).reconcile()
        assert d.action == "hold" and d.reason == "steady"

    def test_flap_converges_under_hysteresis(self):
        """The chaos scenario: scale-up pressure arriving right after a
        scale-down (and vice versa) must not oscillate the fleet — every
        action is followed by a hold-down window."""
        clock = FakeClock()
        fleet = ScriptedFleet(_signals(3, CALM, FLAT, live=1))
        ctl = _controller(fleet, clock, hysteresis_s=60.0)
        assert ctl.reconcile().action == "scale_down"

        # Burst lands immediately: scale-up wanted — held.
        fleet.signals = _signals(2, BURNING, STEEP)
        for _ in range(5):
            clock.advance(5.0)
            d = ctl.reconcile()
            assert d.action == "hold" and d.reason == "hysteresis"

        clock.advance(60.0)  # window expires → the scale-up proceeds
        assert ctl.reconcile().action == "scale_up"

        # And the counter-pressure right after is held again.
        fleet.signals = _signals(3, CALM, FLAT, live=0)
        d = ctl.reconcile()
        assert d.action == "hold" and d.reason == "hysteresis"

        actions = [x.action for x in ctl.decisions if x.action != "hold"]
        assert actions == ["scale_down", "scale_up"]  # converged, no flap

    def test_victim_is_cheapest_pod(self):
        pods = _signals(3, CALM, FLAT, live=2)
        pods[1].live_requests = ["only-one"]
        fleet = ScriptedFleet(pods)
        d = _controller(fleet, FakeClock()).reconcile()
        assert d.action == "scale_down" and d.pod_id == "pod-1"

    def test_disabled_controller_never_starts(self):
        ctl = FleetController(
            FleetControllerConfig(enabled=False), ScriptedFleet([])
        )
        ctl.start()
        assert ctl._thread is None

    def test_from_env_defaults_off(self):
        cfg = FleetControllerConfig.from_env()
        assert cfg.enabled is False


# ---------------------------------------------------------------------------
# Live migration over real ZMQ (real PodServers)
# ---------------------------------------------------------------------------
class TestLiveMigration:
    def test_migrated_sequence_is_greedy_identical(self):
        """THE parity acceptance: migrate an in-flight decode mid-sequence
        and the continuation's generated tokens equal an unmigrated run,
        token for token."""
        ep = f"tcp://127.0.0.1:{free_tcp_port()}"
        src = PodServer(_pod_config("mig-src", fleet_controller=True))
        tgt = PodServer(
            _pod_config("mig-tgt", fleet_controller=True, transfer_endpoint=ep)
        )
        ref = PodServer(_pod_config("mig-ref"))
        src.start(), tgt.start(), ref.start()
        try:
            prompt = _prompt(42, 16)
            sampling = SamplingParams(max_new_tokens=MID_DECODE_TOKENS)
            base = ref.generate(prompt, sampling, timeout=300)

            fut = src.submit(prompt, sampling, request_id="mig-1")
            _wait_mid_decode(src, "mig-1")
            t0 = time.monotonic()
            assert src.migrate_out("mig-1", ep)
            migrate_s = time.monotonic() - t0

            local = fut.result(timeout=60)
            assert local.finish_reason == "migrated"
            cont = tgt.migrated_future("mig-1").result(timeout=300)
            assert cont.generated_tokens == base.generated_tokens
            # Warm handoff: the shipped chain cache-hits the continuation.
            assert cont.num_cached_prompt > 0
            assert src.migrations_out == 1 and tgt.migrations_in == 1
            # Instant relative to a drain: the whole migration is a wire
            # round-trip, far under the 30 s default drain budget.
            assert migrate_s < src.config.drain_timeout_s
        finally:
            src.shutdown(), tgt.shutdown(), ref.shutdown()

    def test_dead_target_falls_back_to_local_with_parity(self):
        """Chaos: the migration target dies mid-migration. The frozen
        sequence resumes locally (cold recompute over surviving cached
        pages), finishes token-identical, and the source's pages return
        to baseline — compared against a reference pod that ran the same
        request unmigrated."""
        src = PodServer(_pod_config("dead-src", fleet_controller=True))
        src.config.transfer_timeout_s = 0.4
        ref = PodServer(_pod_config("dead-ref"))
        src.start(), ref.start()
        try:
            prompt = _prompt(7, 16)
            sampling = SamplingParams(max_new_tokens=MID_DECODE_TOKENS)
            base = ref.generate(prompt, sampling, timeout=300)

            fut = src.submit(prompt, sampling, request_id="mig-x")
            _wait_mid_decode(src, "mig-x")
            # Nothing listens here: the wire leg times out mid-migration.
            assert not src.migrate_out(
                "mig-x", f"tcp://127.0.0.1:{free_tcp_port()}"
            )
            assert src.migration_fallbacks == 1
            out = fut.result(timeout=300)
            assert out.finish_reason != "migrated"
            # generated_tokens, not output_tokens: the freeze folded the
            # partial output into the prompt, and generated_tokens is the
            # representation-stable user-visible slice.
            assert out.generated_tokens == base.generated_tokens
            assert (
                src.engine.lifecycle_stats.get("migration_fallback") == 1
            )
            # Pages back to baseline: same free-page count as the
            # reference engine after the identical workload.
            assert (
                src.engine.block_manager.num_free
                == ref.engine.block_manager.num_free
            )
        finally:
            src.shutdown(), ref.shutdown()

    def test_draining_target_refuses_and_source_falls_back(self):
        ep = f"tcp://127.0.0.1:{free_tcp_port()}"
        src = PodServer(_pod_config("drn-src", fleet_controller=True))
        tgt = PodServer(
            _pod_config("drn-tgt", fleet_controller=True, transfer_endpoint=ep)
        )
        src.start(), tgt.start()
        try:
            tgt.drain(timeout_s=5)
            prompt = _prompt(8, 12)
            fut = src.submit(
                prompt, SamplingParams(max_new_tokens=MID_DECODE_TOKENS),
                request_id="r-d",
            )
            _wait_mid_decode(src, "r-d", min_generated=2)
            assert not src.migrate_out("r-d", ep)
            out = fut.result(timeout=300)
            assert len(out.generated_tokens) == MID_DECODE_TOKENS
            assert tgt.migrations_in == 0
        finally:
            src.shutdown(), tgt.shutdown()

    def test_knob_off_migrate_out_is_inert(self):
        """FLEET_CONTROLLER off: migrate_out refuses without touching the
        engine, the transfer service refuses inbound migrations, and the
        config default stays off — the legacy pinning."""
        pod = PodServer(_pod_config("legacy"))
        pod.start()
        try:
            assert pod.config.fleet_controller is False
            assert PodServerConfig.from_env().fleet_controller is False
            assert not pod.migrate_out("anything", "tcp://nowhere")
            assert pod.migrations_out == 0 and pod.migration_fallbacks == 0
            assert pod.warm_chains(4) == []
            assert pod.revive_chain([1, 2], "tcp://nowhere") == 0
        finally:
            pod.shutdown()

    def test_migrating_unknown_or_finished_request_is_false(self):
        ep = f"tcp://127.0.0.1:{free_tcp_port()}"
        src = PodServer(_pod_config("u-src", fleet_controller=True))
        src.start()
        try:
            assert not src.migrate_out("never-submitted", ep)
            seq = src.generate(
                _prompt(3, 8), SamplingParams(max_new_tokens=2), timeout=300
            )
            assert not src.migrate_out(seq.request_id, ep)
        finally:
            src.shutdown()


# ---------------------------------------------------------------------------
# Warm chains (the scale-up revival donor side)
# ---------------------------------------------------------------------------
class TestWarmChains:
    def test_hot_chains_are_chain_ordered_longest_first(self):
        pod = PodServer(_pod_config("warm-donor", fleet_controller=True))
        pod.start()
        try:
            long_prefix = _prompt(20, 24)
            short_prefix = _prompt(21, 8)
            pod.generate(long_prefix, SamplingParams(max_new_tokens=1), timeout=300)
            pod.generate(short_prefix, SamplingParams(max_new_tokens=1), timeout=300)
            chains = pod.warm_chains(8)
            assert len(chains) >= 2
            assert len(chains[0]) >= len(chains[-1])
            # Chain order: each chain must be a prefix-hash walk the
            # export path can serve in one consecutive run.
            db = pod.engine.block_manager.token_db
            want = db.prefix_hashes(long_prefix)[: len(chains[0])]
            assert chains[0] == want
        finally:
            pod.shutdown()


# ---------------------------------------------------------------------------
# End-to-end: the in-process fleet under the real controller
# ---------------------------------------------------------------------------
class SteeredFleet(InProcessFleet):
    """Real pods, scripted *signals*: burn/MRC are injected so the tests
    drive the decision deterministically while migration, revival, drain,
    and retirement all run for real."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.steer_burn = None
        self.steer_mrc = None

    def observe(self):
        pods = super().observe()
        for p in pods:
            p.burn_rates = self.steer_burn
            p.mrc = self.steer_mrc
        return pods


class TestFleetEndToEnd:
    def test_scale_down_live_migrates_then_retires(self):
        ep_a = f"tcp://127.0.0.1:{free_tcp_port()}"
        ep_b = f"tcp://127.0.0.1:{free_tcp_port()}"
        pod_a = PodServer(
            _pod_config("pod-a", fleet_controller=True, transfer_endpoint=ep_a)
        )
        pod_b = PodServer(
            _pod_config(
                "pod-b",
                total_pages=48,  # smaller: the tie-broken victim
                fleet_controller=True,
                transfer_endpoint=ep_b,
            )
        )
        ref = PodServer(_pod_config("pod-ref"))
        pod_a.start(), pod_b.start(), ref.start()
        health = FleetHealth(FleetHealthConfig())
        fleet = SteeredFleet(fleet_health=health)
        fleet.register("pod-a", pod_a, ep_a)
        fleet.register("pod-b", pod_b, ep_b)
        fleet.steer_burn = CALM
        fleet.steer_mrc = FLAT
        ctl = FleetController(
            FleetControllerConfig(enabled=True, min_pods=1), fleet
        )
        try:
            prompt_a, prompt_b = _prompt(30, 12), _prompt(31, 12)
            sampling = SamplingParams(max_new_tokens=40)
            base_b = ref.generate(prompt_b, sampling, timeout=600)
            # pod-b (the victim) first: its compile happens here, so its
            # request is still early in decode when we reconcile. pod-a
            # then carries TWO live requests submitted last — it stays
            # strictly busier than pod-b through the decision, and the
            # capacity tie-break (48 < 64 pages) also points at pod-b.
            fut_b = pod_b.submit(prompt_b, sampling, request_id="rb")
            _wait_mid_decode(pod_b, "rb", min_generated=2)
            fut_a = pod_a.submit(prompt_a, sampling, request_id="ra")
            fut_a2 = pod_a.submit(
                _prompt(32, 12), sampling, request_id="ra2"
            )
            _wait_mid_decode(pod_a, "ra", min_generated=1)

            d = ctl.reconcile()
            assert d.action == "scale_down" and d.pod_id == "pod-b"
            assert d.migrated == 1 and d.migration_fallbacks == 0
            # The victim is gone from the fleet, unrouted in FleetHealth,
            # and its sequence finished on the survivor, token-identical.
            assert fleet.pod_ids() == ["pod-a"]
            assert health.pods_removed == 1
            assert not health.is_routable("pod-b")
            cont = pod_a.migrated_future("rb").result(timeout=600)
            assert cont.generated_tokens == base_b.generated_tokens
            assert fut_b.result(timeout=60).finish_reason == "migrated"
            assert len(fut_a.result(timeout=600).generated_tokens) == 40
            assert len(fut_a2.result(timeout=600).generated_tokens) == 40
        finally:
            pod_a.shutdown(), ref.shutdown()
            for s in fleet.retired:
                s.shutdown()
            pod_b.shutdown()

    def test_scale_up_revives_warm_sets_on_the_new_pod(self):
        ep = f"tcp://127.0.0.1:{free_tcp_port()}"
        donor = PodServer(
            _pod_config("donor", fleet_controller=True, transfer_endpoint=ep)
        )
        donor.start()
        spawned = []

        def make_pod(pod_id):
            server = PodServer(_pod_config(pod_id, fleet_controller=True))
            server.start()
            spawned.append(server)
            return server, None

        health = FleetHealth(FleetHealthConfig())
        fleet = SteeredFleet(make_pod=make_pod, fleet_health=health)
        fleet.register("donor", donor, ep)
        fleet.steer_burn = BURNING
        fleet.steer_mrc = STEEP
        ctl = FleetController(
            FleetControllerConfig(enabled=True, max_pods=4), fleet
        )
        try:
            prefix = _prompt(50, 20)
            donor.generate(prefix, SamplingParams(max_new_tokens=1), timeout=300)
            d = ctl.reconcile()
            assert d.action == "scale_up" and d.pod_id == "fleet-1"
            assert d.revived_blocks == len(prefix) // PS
            assert health.pods_added == 1
            # The revived chain serves warm: a request over the same
            # prefix on the NEW pod cache-hits without ever computing it.
            newcomer = fleet.server("fleet-1")
            out = newcomer.generate(
                prefix + _prompt(51, 4),
                SamplingParams(max_new_tokens=2),
                timeout=300,
            )
            assert out.num_cached_prompt == len(prefix)
        finally:
            donor.shutdown()
            for s in spawned:
                s.shutdown()


# ---------------------------------------------------------------------------
# /stats gating
# ---------------------------------------------------------------------------
class TestStatsGating:
    def test_fleet_block_only_with_knob_on(self):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        async def check(server, expect_fleet):
            ts = TestServer(server.build_app())
            client = TestClient(ts)
            await client.start_server()
            try:
                stats = await (await client.get("/stats")).json()
                assert ("fleet" in stats) is expect_fleet
                if expect_fleet:
                    assert stats["fleet"] == {
                        "migrations_out": 0,
                        "migrations_in": 0,
                        "migration_fallbacks": 0,
                        "migrations_served": 0,
                        "migration_blocks_accepted": 0,
                    }
            finally:
                await client.close()

        on = PodServer(_pod_config("st-on", fleet_controller=True))
        off = PodServer(_pod_config("st-off"))
        on.start(), off.start()
        try:
            asyncio.run(check(on, True))
            asyncio.run(check(off, False))
        finally:
            on.shutdown(), off.shutdown()
