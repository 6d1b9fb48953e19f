"""Headline benchmark: KV-cache-aware ("precise") routing vs comparators.

Reproduces the reference's capacity benchmarks (`benchmarking/37-capacity`,
`73-capacity`: precise vs estimated/load/random scheduling under
shared-prefix Poisson load) on TPU with the in-tree JAX serving engine,
per the BASELINE.json north star: *p50-TTFT reduction vs round-robin on
shared-prefix load*, plus req/s/chip and prefix-cache hit-rate.

Method — virtual-clock fleet co-simulation on one real chip:

- N "pods", each a real `Engine` (own KV page pool, block manager,
  continuous-batching scheduler) running the real Pallas paged-attention
  model; all pods share one copy of the weights (pods differ only by KV
  cache state, which is what routing exploits).
- Each pod has a virtual clock advanced by the *measured wall time* of its
  engine steps on the TPU. Pods are independent machines in a real
  deployment, so time-slicing them on one chip while accounting time
  per-pod is a faithful simulation of fleet behavior.
- KV events flow through the real write path: BlockStored/BlockRemoved →
  msgpack EventBatch → sharded KVEventsPool → shared in-memory block index
  (SURVEY §3.2). The router's read path is `KVCacheIndexer.score_tokens`
  (chunked sha256-CBOR hashing + longest-prefix scorer, SURVEY §3.1).
- Workload: G prefix groups (default 32-way), each a shared prefix of
  `PREFIX_LEN` tokens plus a unique suffix; Poisson arrivals on a 3-step
  QPS ramp (0.7x/1.0x/1.4x of the calibrated saturation rate) — the
  analogue of the reference's 3→20 QPS ramp.
- Policies (the reference's four, `37-capacity/README.md`):
  * `round_robin` — the reference's "random"/default-k8s analogue
  * `load`        — least outstanding requests
  * `estimated`   — prefix-affinity WITHOUT the index: models each pod's
    cache as a capacity-bounded LRU of routed token-block chains (with
    optional TTL decay) but never sees KV events, so it cannot know about
    real evictions, preemptions or actual cache state
  * `precise`     — KV-cache index scores (this project)

Prints ONE JSON line:
  {"metric": "p50_ttft_reduction_vs_round_robin", "value": <pct>,
   "unit": "%", "vs_baseline": <pct/50>,
   "req_s_per_chip": <precise fleet req/s per chip>,
   "prefix_cache_hit_rate": <precise prompt-token cache hit fraction>}
vs_baseline >= 1.0 means the north-star target (>=50% reduction) is met.

Env knobs (for ad-hoc runs; the driver uses defaults):
  BENCH_SMOKE=1        tiny CPU-sized run (auto when not on TPU)
  BENCH_POLICIES=a,b   subset of policies to run
  BENCH_HOST_PAGES=N   host-DRAM offload tier slots per pod (tier evidence)
  BENCH_TOTAL_PAGES=N  override per-pod HBM page-pool size
  BENCH_QPS_SCALES=x,y,z  override the ramp multipliers
  BENCH_EVENT_LAG_MS=N publish→index event visibility lag (default 2 ms —
                       the ms-scale ZMQ+decode hop of a real deployment;
                       0 restores the drain-everything optimistic co-sim)
  BENCH_EST_TTL_S=N    estimated-router affinity TTL (default off; the
                       capacity-LRU is the binding bound in these runs)
  BENCH_PRESSURE=0     skip the second (pool-pressure) pass
  BENCH_PRESSURE_PAGES=N pressure-pass pool size (default 1536 @1p4b,
                       640 @8b-int8 — past the working set, so pods evict
                       and the index's eviction awareness shows; the
                       reference's own headline regime)
  BENCH_PRESSURE_HOST_PAGES=N host-DRAM tier size for the pressure pass's
                       precise_host arm (default = the pressure pool size,
                       i.e. >=2x effective pages; 0 skips the arm). The
                       arm reruns `precise` under the SAME shrunken HBM
                       pool with the host tier + prefetch + int8 KV spill
                       on — the capacity story of ISSUE 6
  BENCH_KV_QUANT=int8  paged-KV quantization for the precise_host arm and
                       (with BENCH_HOST_PAGES) the main pass ("" = off:
                       spill full-width pages)
  BENCH_HOST_PREFETCH=1 bring-back ahead of the scheduler in host-tier
                       arms (0 = blocking allocate-time restore only)
  BENCH_HOST_TIER_POLICY=always  tier admission for host-tier arms
                       (default pins the mechanism; "auto" lets the
                       recompute-vs-restore model gate on this rig's link)
  BENCH_CHUNKED_PREFILL_TOKENS=N  per-step prefill chunk budget (chunked
                       prefill + mixed prefill/decode steps; 0/unset =
                       legacy either-or scheduling) — the TTFT/ITL
                       trade-off knob
  BENCH_TRANSFER=1     cross-pod KV transfer for the precise policy: the
                       BlendedRouter runs with the transfer cost model and
                       a "pull" decision actually moves the prefix blocks
                       (source export → target import through the real
                       engine endpoints), charging the target's virtual
                       clock with the measured wall time plus modeled link
                       time; pull counts land in the detail JSON
  BENCH_TRANSFER_GBPS=N  modeled DCN link rate for the pull charge and the
                       cost model's seed transfer rate (default 10)
  BENCH_DECODE_FASTPATH=1  decode fast path on every arm's engines
                       (DECODE_FUSED_SAMPLING + DECODE_PIPELINE: device-
                       resident last tokens across steps, async D2H of
                       sampled ids) — the ISSUE 7 throughput knob
  BENCH_SPEC_DECODE=prompt_lookup  adds a `precise_spec` arm (precise
                       routing with speculative decoding) reporting an
                       acceptance-rate column
  BENCH_STEP_PHASES=1  per-arm engine step-phase decomposition
                       (schedule/prefill/decode/sample/gather/publish
                       seconds) in the detail JSON
  BENCH_DISAGG=1       disaggregated prefill/decode arm (ISSUE 9): the
                       same qps-ramp workload served by N prefill + M
                       decode pods — the TwoHopPlanner places ingest on
                       the prefill tier (warmth + measured prefill rate),
                       the chain moves over the real export/import
                       endpoints (charged wall + modeled link time), and
                       the decode tier streams tokens. Decode-tier ITL is
                       the headline: ingest never shares an engine with a
                       decode lane, so the interference chunked prefill
                       bounds is REMOVED, not amortized. Compared against
                       the same-total-pod-count mixed fleet (`precise`)
  BENCH_DISAGG_PREFILL_PODS=N  prefill-tier size (default n_pods/2,
                       min 1); decode tier gets the rest
  BENCH_REMOTE_TIER=1  remote-tier arm (ISSUE 13): re-run `precise` under
                       the pressure pool with REMOTE_TIER on — evictions
                       that would destroy the last copy of a chain demote
                       (int8 wire triple) to a simulated kvstore holder on
                       the event bus, the index learns the
                       medium="remote" entries under the HOLDER identity,
                       and the router pulls chains back (import may
                       recycle evictable pages — victims demote, so the
                       trade is lossless) instead of recomputing. Reports
                       an effective-capacity headline: fleet tokens
                       cached (all tiers + kvstore) per HBM byte
  BENCH_REMOTE_STORE_PAGES=N  kvstore holder capacity in pages (default =
                       4x the arm's per-pod pool, so the fleet working
                       set survives demotion)
  BENCH_KV_QUANT_HBM=1 quantized-HBM arm (ISSUE 16): re-run `precise`
                       under the pressure pool's HBM BYTE budget with
                       KV_QUANT_HBM=int8 — int8 pages halve bytes/page,
                       so the same bytes hold 2x the pages. The summary's
                       `kv_quant_hbm` block closes the pre-registration
                       loop (bare arm's MRC forecast at the 2x capacity
                       point vs this arm's measured hit, within 0.05) and
                       carries the tok/s/chip A/B plus the decode/sample
                       phase deltas when BENCH_STEP_PHASES=1
  BENCH_REPEATS=N      re-run the pressure arms N times and report MEDIAN
                       hit-rate fields (hit_{arm}) + the estimated/precise
                       p90 race median with spread — single noisy rounds
                       stop masquerading as signal (default 1 = legacy
                       single-shot fields). Since ISSUE 14 the median
                       treatment also covers the per-arm TTFT/ITL
                       percentile fields (p50/p90/p99 of both, with a
                       latency_spread block) and the workload-family
                       arms, so the predicted-vs-precise comparison is a
                       median, not a single draw
  BENCH_WORKLOAD_FAMILY=1  (default on) the ISSUE 14 workload-generator
                       family: four arms — `burst` (4x QPS square-wave
                       bursts over a quiet baseline), `ramp` (diurnal
                       rise-and-fall), `session` (multi-turn session
                       affinity: each session's turn k prompt extends
                       turn k-1's prefix), `swarm` (agent-swarm
                       deep-shared-prefix waves) — each run under
                       round_robin, precise, and the new `predicted`
                       policy (BlendedRouter + TTFTPredictor: routes on
                       modeled queue-wait + miss-prefill + pull cost,
                       with the audit join feeding the per-pod
                       corrector online). Acceptance: predicted p50/p99
                       TTFT <= both comparators on burst and ramp with
                       hit-rate parity vs precise (0 skips the pass)
  BENCH_TENANT_QOS=1   two-class tenant-QoS arm (ISSUE 18): a steady
                       premium trickle over a small hot-prefix set plus
                       a background tenant running the burst shape over
                       a wide churny set, on ONE capacity-constrained
                       pod. Three runs (premium alone / knob off / knob
                       on under BENCH_TENANT_QOS_SPEC) report per-tenant
                       TTFT tails, hit rates, 429s-at-the-door, priority
                       preemptions, and per-tenant MRC slices — the
                       isolation evidence for TENANT_QOS
  BENCH_TENANT_PAGES=N pool size for the tenant-QoS arm (default: the
                       premium warm set + ~6 active sequences)
  BENCH_TENANT_QOS_SPEC=...  policy for the knob-on run (default:
                       premium prio 0 weight 4; batch prio 1 with
                       max_waiting=6 and cache_share=0.3)
  BENCH_KV_INTEGRITY=1 corruption-drill arm (ISSUE 19): three runs of a
                       spill-heavy host-tier workload on ONE pod — knob
                       off (the baseline outputs), KV_INTEGRITY on clean
                       (the digest-overhead A/B), and KV_INTEGRITY on
                       with byte flips injected into spilled host pages
                       mid-run. Every flip must be detected at
                       restore/export/scrub and quarantined BEFORE any
                       token is emitted, and the drill's greedy outputs
                       must match the baseline exactly — the
                       zero-corrupted-tokens evidence; the makespan
                       ratios price the digest overhead (clean/off) and
                       the quarantine+cold-recompute recovery
                       (drill/clean)
  BENCH_KV_INTEGRITY_FLIPS=N  byte flips injected by the drill run
                       (default 4; each lands on a distinct chain)
  BENCH_KV_INTEGRITY_PAGES=N  HBM pool size for the arm (default ~2
                       active sequences, so every warm prefix lives on
                       the spill→restore edge the digests guard)
  BENCH_OBS_FED=1      fleet-federation overhead arm (ISSUE 20):
                       headline is 4-pod FleetFederator.scrape() join
                       latency (p50/p99 over 200 scrapes against fully
                       loaded in-process payloads — three tiers, SLO
                       burn, tenant slices, integrity, MRC/lifecycle/
                       audit); the A/B is engine step p50 with a ~10 Hz
                       background scraper reading LIVE engine state vs
                       the bare engine. Acceptance: step p50 ratio
                       <= 1.02x (the observation plane must not tax the
                       hot path)
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

MODEL_NAME = "bench/llama"
ALL_POLICIES = ("round_robin", "load", "estimated", "precise")
#: `predicted` (ISSUE 14) is run by the workload-family pass (and
#: BENCH_POLICIES opt-in), not the legacy main pass — the headline
#: round_robin/load/estimated/precise comparison keeps its field set.
RUNNABLE_POLICIES = ALL_POLICIES + ("predicted",)


def build_session_workload(
    rng, n_sessions, turns, prefix_len, suffix_len, vocab, qps
):
    """Multi-turn session-affinity workload (ISSUE 14 family): each
    session has a private base prefix; turn k's prompt is the first
    ``(k+1)/turns`` of it plus a unique suffix, so turn k+1 shares turn
    k's entire prefix — the pod that served the last turn holds the
    warmth, and a router that scatters a session pays full re-prefill.
    Sessions start Poisson-staggered and think between turns, so many
    sessions are in flight at once. Returns the ``build_workload``
    shape: [(arrival_time, segment=turn_idx, tokens)]."""
    out = []
    start = 0.0
    #: sessions arrive at qps/turns so total request rate ~= qps
    session_rate = max(qps / turns, 1e-9)
    for _ in range(n_sessions):
        start += float(rng.exponential(1.0 / session_rate))
        base = rng.integers(0, vocab, prefix_len).tolist()
        t = start
        for k in range(turns):
            if k:
                # Think time between turns: the session produces at
                # ~qps/n_active, keeping ~`turns` sessions concurrent.
                t += float(rng.exponential(turns / max(qps, 1e-9)))
            shared = base[: max(prefix_len * (k + 1) // turns, 1)]
            toks = shared + rng.integers(0, vocab, suffix_len).tolist()
            out.append((t, k, toks))
    out.sort(key=lambda r: r[0])
    return out


def build_swarm_workload(
    rng, n_agents, waves, prefix_len, suffix_len, vocab, qps
):
    """Agent-swarm deep-shared-prefix workload (ISSUE 14 family): every
    agent shares ONE deep system prompt; agents fire in
    near-simultaneous waves (a planner fanning out sub-agents), so the
    fleet sees a thundering herd of identical prefixes — the regime
    where warmth-first routing piles the whole wave onto one pod and
    queue time eats the cache win."""
    base = rng.integers(0, vocab, prefix_len).tolist()
    out = []
    t = 0.0
    for w in range(waves):
        t += float(rng.exponential(n_agents / max(qps, 1e-9)))
        for _ in range(n_agents):
            jitter = float(rng.exponential(0.2 / max(qps, 1e-9)))
            toks = base + rng.integers(0, vocab, suffix_len).tolist()
            out.append((t + jitter, w, toks))
    out.sort(key=lambda r: r[0])
    return out


def build_workload(
    rng, n_groups, reqs_per_group, prefix_len, suffix_len, vocab, qps_ramp
):
    """Poisson arrival schedule over shared-prefix groups, on a QPS ramp.

    ``qps_ramp`` is a list of rates; the request stream is split into
    equal consecutive segments, one per rate. Returns
    [(arrival_time, segment_idx, tokens)] plus the segment boundaries.
    """
    prefixes = [
        rng.integers(0, vocab, prefix_len).tolist() for _ in range(n_groups)
    ]
    reqs = []
    for g in range(n_groups):
        for _ in range(reqs_per_group):
            reqs.append(prefixes[g] + rng.integers(0, vocab, suffix_len).tolist())
    rng.shuffle(reqs)
    n = len(reqs)
    seg_size = -(-n // len(qps_ramp))
    t = 0.0
    out = []
    for i, toks in enumerate(reqs):
        seg = min(i // seg_size, len(qps_ramp) - 1)
        t += float(rng.exponential(1.0 / qps_ramp[seg]))
        out.append((t, seg, toks))
    return out


class LaggedEventBus:
    """Models the publish→index latency of a real deployment: an event
    batch a pod publishes at virtual time T becomes visible to the indexer
    at T + lag (the ZMQ hop + pool decode the reference's deployments eat,
    `37-capacity/README.md` numbers include it). lag=0 reproduces the
    optimistic drain-everything co-sim. Stable sort on (visible_at, stage
    order) preserves per-pod FIFO — every pod has the same lag and
    monotonically increasing stamps."""

    def __init__(self, pool, lag_s: float):
        self.pool = pool
        self.lag_s = lag_s
        self._staged: list[tuple[float, object]] = []

    def stage(self, msg, published_at: float) -> None:
        self._staged.append((published_at + self.lag_s, msg))

    def release(self, now: float) -> None:
        """Deliver every staged message visible by ``now`` and drain the
        ingestion pool, so a routing decision at ``now`` sees exactly the
        events a real indexer would have by then."""
        keep = []
        send = []
        for item in self._staged:
            (send if item[0] <= now else keep).append(item)
        if send:
            send.sort(key=lambda item: item[0])
            for _, msg in send:
                self.pool.add_task(msg)
            self.pool.drain(timeout=10.0)
        self._staged = keep

    def flush_all(self) -> None:
        self.release(float("inf"))


#: Per-arm engine step-phase decomposition (BENCH_STEP_PHASES=1): every
#: pod engine records schedule/prefill/decode/sample/gather/publish wall
#: seconds (the PR 5 telemetry), aggregated into the detail JSON — the
#: "where did the step time go" columns of the decode-fast-path record.
#: Off by default: the extra clock reads, though small, perturb measured
#: step times.
STEP_PHASES = os.environ.get("BENCH_STEP_PHASES", "0") == "1"


class Pod:
    """One simulated serving replica: a real engine + a virtual clock."""

    def __init__(self, pod_id, engine_cfg, params, publish, bus):
        from llm_d_kv_cache_manager_tpu.server.engine import Engine

        self.pod_id = pod_id
        self._make_msg = publish(pod_id)
        self.bus = bus
        self._unstamped: list[object] = []
        # Stage the raw events; step_timed builds the wire message with the
        # post-step clock as the batch's publish timestamp (events are
        # flushed at the end of engine.step()), so the staleness probes see
        # honest virtual publish times.
        self.engine = Engine(
            engine_cfg,
            params=params,
            on_events=lambda events: self._unstamped.append(list(events)),
        )
        self.engine.obs_step_timing = STEP_PHASES
        self.clock = 0.0
        self.seqs = []  # every sequence routed here
        self.hit_stats: dict[int, tuple[int, int]] = {}  # first-prefill hits
        self._first_token_seen: set[int] = set()
        #: virtual-clock first-token / finish instants, for ITL percentiles
        self.first_clock: dict[int, float] = {}
        self.finish_clock: dict[int, float] = {}

    @property
    def load(self) -> int:
        s = self.engine.scheduler
        return len(s.waiting) + len(s.running)

    def step_timed(self, ttfts, arrivals):
        t0 = time.perf_counter()
        done = self.engine.step()
        dt = time.perf_counter() - t0
        self.clock += dt
        self.flush_staged()
        # Record first-token virtual times (running lanes catch prefill
        # first-tokens; `done` catches sequences that finished this step).
        sched = self.engine.scheduler
        for seq in done:
            self.finish_clock[seq.seq_id] = self.clock
        for seq in list(sched.running) + done:
            if seq.num_generated >= 1 and seq.seq_id not in self._first_token_seen:
                self._first_token_seen.add(seq.seq_id)
                self.first_clock[seq.seq_id] = self.clock
                if seq.seq_id in arrivals:
                    ttfts[seq.seq_id] = self.clock - arrivals[seq.seq_id]
                # Snapshot cache-hit accounting at FIRST prefill: a later
                # preemption re-prefill "hits" the sequence's own surviving
                # pages (and folds generated tokens into the prompt), which
                # would overstate shared-prefix reuse under saturation.
                self.hit_stats[seq.seq_id] = (
                    seq.num_cached_prompt,
                    len(seq.prompt_tokens),
                )

    def flush_staged(self):
        # Stage any events the engine emitted outside step() (e.g. an
        # import_kv_blocks flush): a pod with no work never steps, so
        # without this the index would never learn those blocks landed.
        if self._unstamped:
            for events in self._unstamped:
                self.bus.stage(self._make_msg(events, self.clock), self.clock)
            self._unstamped.clear()

    def advance_to(self, t, ttfts, arrivals):
        while self.engine.has_work and self.clock < t:
            self.step_timed(ttfts, arrivals)

    def drain(self, ttfts, arrivals, max_steps=200_000):
        for _ in range(max_steps):
            if not self.engine.has_work:
                return
            self.step_timed(ttfts, arrivals)
        raise RuntimeError("pod failed to drain")


def make_event_pipeline(index, n_pods, staleness=None, audit=None):
    """Real write path: msgpack-encode batches, shard into the events pool.

    ``staleness``/``audit`` (optional ``obs.audit`` trackers) attach the
    ISSUE 10 probes to the same pool the product runs — the bench measures
    the audit plane itself, not a stand-in."""
    from llm_d_kv_cache_manager_tpu.kvcache.kvevents import (
        KVEventsPool,
        KVEventsPoolConfig,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.kvevents.events import EventBatch
    from llm_d_kv_cache_manager_tpu.kvcache.kvevents.pool import Message

    pool = KVEventsPool(
        index,
        KVEventsPoolConfig(concurrency=min(4, n_pods)),
        staleness=staleness,
        audit=audit,
    )
    pool.start()

    _seqs = {}

    def publish(pod_id):
        # Int ids name engine pods; string ids name auxiliary publishers
        # (the remote arm's kvstore holder) verbatim.
        pod_name = pod_id if isinstance(pod_id, str) else f"tpu-pod-{pod_id}"

        def make_msg(events, ts=0.0):
            # Virtual publish timestamp + per-publisher seq: the staleness
            # probes read both off the wire exactly as in production.
            batch = EventBatch(ts=ts, events=list(events))
            seq = _seqs.get(pod_name, 0)
            _seqs[pod_name] = seq + 1
            return Message(
                topic=f"kv@{pod_name}@{MODEL_NAME}",
                pod_identifier=pod_name,
                model_name=MODEL_NAME,
                payload=batch.to_payload(),
                seq=seq,
            )

        return make_msg

    return pool, publish


def _audit_summary(auditor) -> dict:
    """Fleet-level predicted-vs-realized columns from the joined audits:
    the realized hit ratio (sum realized / sum predicted over decisions
    that promised warmth) and the attributed miss mix."""
    rows = auditor.recent(limit=1_000_000)
    predicted = sum(r["predicted_blocks"] for r in rows)
    realized = sum(
        min(r["realized_blocks"], r["predicted_blocks"]) for r in rows
    )
    ratios = sorted(r["ratio"] for r in rows if r["ratio"] is not None)
    snap = auditor.snapshot()
    return {
        "joined": snap["joined"],
        "unmatched": snap["unmatched_realized"],
        "predicted_blocks": predicted,
        "realized_blocks": sum(r["realized_blocks"] for r in rows),
        # Capped per-request (a request can't realize MORE than promised
        # toward this ratio — overshoot is a different, happy story).
        "realized_over_predicted": (
            round(realized / predicted, 4) if predicted else None
        ),
        "ratio_p50": (
            ratios[len(ratios) // 2] if ratios else None
        ),
        "misses": {k: v for k, v in snap["miss_causes"].items() if v},
        # Predicted-TTFT honesty (ISSUE 14, predicted arm only): median
        # realized/predicted TTFT over the joined decisions — the
        # acceptance band is [0.8, 1.25].
        **(
            {"ttft_ratio_p50": snap["ttft_ratio_p50"]}
            if "ttft_ratio_p50" in snap
            else {}
        ),
    }


def run_policy(
    policy, workload, params, engine_cfg, n_pods, max_new_tokens,
    remote=False, mrc=False,
):
    """Run one routing policy over the workload; returns per-request and
    fleet-level metrics.

    ``remote=True`` (requires ``engine_cfg.remote_tier``) attaches the
    ISSUE 13 remote tier: every pod's last-copy evictions demote to a
    simulated kvstore holder (``tpu-kvstore-0``) whose
    ``BlockStored(medium="remote")`` events ride the same lagged bus
    under the HOLDER identity; the router's remote arm pulls demoted
    chains back through the real import endpoints (charged measured wall
    + modeled link time, demotions charged link time on the visibility
    clock only — the push itself is background work on a real pod).

    ``mrc=True`` (ISSUE 15) attaches the PRODUCT reuse-distance
    estimator (``obs/lifecycle.ReuseDistanceEstimator``, full sampling)
    to every pod's block manager and reports the miss-ratio curve's
    predicted hit rate at the arm's configured tier capacities — the
    number the pressure-arm validation compares against the measured
    ``prefix_cache_hit_rate``."""
    from llm_d_kv_cache_manager_tpu.kvcache import (
        KVCacheIndexer,
        KVCacheIndexerConfig,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock import TokenProcessorConfig
    from llm_d_kv_cache_manager_tpu.server.sequence import SamplingParams

    page = engine_cfg.block_manager.page_size
    indexer = KVCacheIndexer(
        KVCacheIndexerConfig(token_processor=TokenProcessorConfig(block_size=page))
    )
    # Routing-quality audit plane (ISSUE 10), on the PRODUCT trackers:
    # staleness (publish→index-visibility on the virtual clock) and the
    # predicted-vs-realized join are only meaningful for arms that consume
    # the index — other policies never release events at decision time, so
    # their lag would just measure the final drain.
    staleness = auditor = None
    vnow = [0.0]  # virtual "apply instant" the tracker's clock reads
    if policy in ("precise", "predicted"):
        from llm_d_kv_cache_manager_tpu.obs.audit import (
            RouteAuditor,
            StalenessTracker,
        )

        staleness = StalenessTracker(clock=lambda: vnow[0])
        auditor = RouteAuditor(
            index=indexer.kv_block_index,
            model_name=MODEL_NAME,
            ring=len(workload) + 1,
            pending_cap=len(workload) + 1,
        )
    pool, publish = make_event_pipeline(
        indexer.kv_block_index, n_pods, staleness=staleness, audit=auditor
    )
    lag_s = float(os.environ.get("BENCH_EVENT_LAG_MS", "2")) / 1000.0
    bus = LaggedEventBus(pool, lag_s)
    pods = [Pod(i, engine_cfg, params, publish, bus) for i in range(n_pods)]
    pod_names = [f"tpu-pod-{i}" for i in range(n_pods)]
    mrc_est = None
    if mrc:
        from llm_d_kv_cache_manager_tpu.obs.lifecycle import (
            ReuseDistanceEstimator,
        )

        # Full sampling + a stack deep enough that no distance in the
        # smoke working set truncates: the validation judges the MRC
        # math, not its sampling variance.
        mrc_est = [
            ReuseDistanceEstimator(sample_rate=1.0, max_tracked=1 << 15)
            for _ in pods
        ]
        for p, est in zip(pods, mrc_est):
            p.engine.block_manager.attach_lifecycle(None, est)
    blended = None
    est = aff = None
    predictor = None
    if policy in ("estimated", "precise", "predicted"):
        from llm_d_kv_cache_manager_tpu.kvcache import PrefixAffinityTracker
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock import (
            ChunkedTokenDatabase,
            TokenProcessorConfig,
        )

        ttl_env = os.environ.get("BENCH_EST_TTL_S", "")
        # The tracker IS product code (kvcache/router.py): as `estimated`
        # it is the index-free comparator; as `aff` it is precise's
        # cold-index tiebreak. Modeled capacity covers everything the pod
        # can serve hits from: HBM pages plus the host-DRAM tier when
        # enabled (otherwise the estimated baseline would be handicapped
        # in exactly the BENCH_HOST_PAGES tier-evidence runs).
        router = PrefixAffinityTracker(
            n_pods,
            capacity_blocks=engine_cfg.block_manager.total_pages
            + engine_cfg.block_manager.host_pages,
            ttl_s=float(ttl_env) if ttl_env else None,
            token_processor=ChunkedTokenDatabase(
                TokenProcessorConfig(block_size=page)
            ),
        )
        if policy == "estimated":
            est = router
        else:
            aff = router  # precise's cold-index affinity tiebreak
            from llm_d_kv_cache_manager_tpu.kvcache import BlendedRouter

            blended = BlendedRouter(
                score_fn=lambda toks, names: indexer.score_tokens(
                    toks, MODEL_NAME, names
                ),
                affinity=aff,
                loads_fn=lambda names: [
                    pods[pod_names.index(nm)].load for nm in names
                ],
                auditor=auditor,
            )
        if policy == "predicted":
            # Predicted-TTFT routing (ISSUE 14): THE PRODUCT PATH —
            # BlendedRouter with a TTFTPredictor attached routes on
            # modeled queue wait + miss-prefill (+ pull cost), signals
            # read live off the pod engines (queue depth + the online
            # prefill-rate EMA, the same carriers heartbeats ship). The
            # audit join below feeds realized TTFT back into the per-pod
            # corrector ONLINE, so the model self-corrects mid-run.
            from llm_d_kv_cache_manager_tpu.kvcache import (
                PodSignals,
                TTFTPredictor,
                TTFTPredictorConfig,
            )

            # NOTE default_concurrency stays 1: the engine's prefill-rate
            # EMA is BATCH-AGGREGATE tokens/s, so q x (tokens/rate) is
            # already amortized over the batch width — dividing again
            # would double-count the parallelism and under-weight queues.
            tie_env = os.environ.get("BENCH_PREDICT_TIE_BAND", "")
            predictor = TTFTPredictor(
                TTFTPredictorConfig(
                    block_size=page,
                    **({"tie_band": float(tie_env)} if tie_env else {}),
                )
            )
            auditor.ttft_corrector = predictor.corrector
            blended.predictor = predictor
            def _signals(names):
                out = []
                for nm in names:
                    sched = pods[pod_names.index(nm)].engine.scheduler
                    out.append(
                        PodSignals(
                            name=nm,
                            # The TTFT-relevant queue is the PREFILL
                            # backlog: this engine schedules prefill
                            # first, so decode-running lanes barely
                            # delay a new arrival's first token —
                            # counting them as full queue slots (the
                            # load tiebreak's definition) made busy-but-
                            # prefill-idle pods look slow and convoyed
                            # arrivals onto genuinely idle ones.
                            queue_depth=float(
                                len(sched.waiting)
                                + len(sched.prefilling)
                                + 0.4 * len(sched.running)
                            ),
                            prefill_rate=pods[
                                pod_names.index(nm)
                            ].engine._prefill_rate,
                        )
                    )
                return out

            blended.signals_fn = _signals

    # Cross-pod KV transfer arm (BENCH_TRANSFER=1, precise only): the
    # router runs with the transfer cost model, and a "pull" decision
    # actually moves the blocks through the real engine export/import
    # endpoints. The pull is charged end-to-end to the TARGET pod's
    # virtual clock: measured export+import wall time (the real gather/
    # scatter cost on this rig) plus wire_bytes / BENCH_TRANSFER_GBPS
    # (the DCN hop an in-process co-sim cannot measure).
    cost_model = None
    link_bytes_s = 0.0
    pull_stats = {"pulls": 0, "pulled_blocks": 0, "pull_s": 0.0}
    if blended is not None and (
        remote or os.environ.get("BENCH_TRANSFER", "0") == "1"
    ):
        from llm_d_kv_cache_manager_tpu.kvcache.transfer import (
            TransferCostModel,
            TransferCostModelConfig,
        )

        link_bytes_s = (
            float(os.environ.get("BENCH_TRANSFER_GBPS", "10")) * 1e9 / 8
        )
        cost_model = TransferCostModel(
            TransferCostModelConfig(
                block_bytes=pods[0].engine.kv_block_bytes, block_size=page
            )
        )
        # Seed the link rate so the first pull can happen at all (the
        # EMA then blends in measured end-to-end samples); prefill rate
        # feeds from the engines' own online EMAs per arrival.
        cost_model.seed_rates(transfer_bytes_s=link_bytes_s)
        blended.cost_model = cost_model

    # Remote tier (BENCH_REMOTE_TIER=1, precise only): a simulated
    # kvstore holder backed by the PRODUCT RemoteBlockStore. Demotions
    # are wire-ready payloads the engines build on eviction (int8 triple
    # under kv_quant); acceptance publishes BlockStored(medium="remote")
    # under the HOLDER identity through the same lagged bus, so the
    # index's remote entries — and their death-of-holder eviction
    # semantics — are exactly the product path.
    kv_name = "tpu-kvstore-0"
    store = None
    remote_detail = None
    if remote:
        assert blended is not None and engine_cfg.remote_tier
        import jax.numpy as jnp

        from llm_d_kv_cache_manager_tpu.kvcache.transfer import (
            RemoteBlockStore,
            RemoteStoreConfig,
        )
        from llm_d_kv_cache_manager_tpu.models import quant as _quant

        mc = engine_cfg.model
        shape = (mc.n_layers, page, mc.n_kv_heads, mc.hd)
        store_pages = int(
            os.environ.get(
                "BENCH_REMOTE_STORE_PAGES",
                str(engine_cfg.block_manager.total_pages * 4),
            )
        )
        kv_make_msg = publish(kv_name)
        kv_clock = [0.0]  # holder-side publish instant (set per demotion)
        store = RemoteBlockStore(
            RemoteStoreConfig(
                capacity_pages=store_pages,
                page_size=page,
                page_shape=shape,
                dtype=str(np.dtype(jnp.dtype(mc.dtype).name)),
                scale_bytes=int(np.prod(_quant.kv_scale_shape(shape))) * 4,
                init_hash=pods[0].engine.block_manager.token_db.init_hash,
            ),
            on_events=lambda events: bus.stage(
                kv_make_msg(events, kv_clock[0]), kv_clock[0]
            ),
        )
        remote_detail = {
            "store_pages": store_pages,
            "demoted_blocks": 0,
            "demote_wire_bytes": 0,
            "remote_pulls": 0,
            "remote_pulled_blocks": 0,
        }

        def demotion_sink(pod):
            def sink(payloads):
                wire = sum(b.wire_bytes for b in payloads)
                remote_detail["demoted_blocks"] += len(payloads)
                remote_detail["demote_wire_bytes"] += wire
                # The push is background work on a real pod; only the
                # event-visibility clock pays the link time.
                kv_clock[0] = pod.clock + (
                    wire / link_bytes_s if link_bytes_s else 0.0
                )
                store.accept(payloads)

            return sink

        for pod in pods:
            pod.engine.on_demotion = demotion_sink(pod)
        # Remote read path: the index's score for the holder alone — the
        # router pulls only when the measured cost model says the move
        # beats both the warm local option and recompute. placement=
        # "pull_source" is the product pattern: a FleetHealth-wired
        # scorer must not blank kvstore holders out of THIS query (the
        # serving filter rightly would).
        blended.remote_score_fn = lambda toks: {
            p: s
            for p, s in indexer.score_tokens(
                toks, MODEL_NAME, [kv_name], placement="pull_source"
            ).items()
            if s > 0
        }

    ttfts: dict[int, float] = {}
    arrivals: dict[int, float] = {}
    segments: dict[int, int] = {}
    rid_of: dict[int, str] = {}  # seq_id -> audit request id (precise)
    joined: set[int] = set()

    def join_realized():
        """Join every first-tokened request's ground truth (realized
        cache hits + realized TTFT on the virtual clock) against its
        recorded decision. The predicted arm calls this ONLINE per
        arrival so the corrector learns mid-run (the audit plane as an
        actuator); every audited arm calls it once more at drain so the
        end-of-run columns cover the full workload."""
        for i, pod in enumerate(pods):
            for sid in list(pod.first_clock):
                if sid in joined or sid not in pod.hit_stats:
                    continue
                rid = rid_of.get(sid)
                if rid is None:
                    continue
                joined.add(sid)
                cached, _ = pod.hit_stats[sid]
                auditor.record_realized(
                    rid,
                    pod_names[i],
                    cached // page,
                    realized_ttft_s=ttfts.get(sid),
                )

    rr = 0
    for req_i, (t, seg, tokens) in enumerate(workload):
        # Advance every pod to the arrival instant so the index reflects
        # fleet state at routing time, then drain in-flight events.
        for pod in pods:
            pod.advance_to(t, ttfts, arrivals)
        if policy == "predicted":
            join_realized()  # online corrector feedback
        if policy in ("precise", "predicted"):
            # Events released now APPLY now on the virtual clock — the
            # staleness tracker's "index visibility" instant.
            vnow[0] = t
            # The index sees exactly the events a real deployment's
            # indexer would have by the arrival instant (publish + lag);
            # routing is THE PRODUCT PATH (kvcache/router.BlendedRouter:
            # index score → routed-affinity tiebreak → load — the blend
            # that fixed the round-4 cold-index scatter under thrash).
            bus.release(t)
            if cost_model is not None:
                rates = [
                    p.engine._prefill_rate
                    for p in pods
                    if p.engine._prefill_rate
                ]
                if rates:
                    cost_model.seed_rates(
                        prefill_tokens_s=float(np.median(rates))
                    )
            decision = blended.route(
                tokens, pod_names, now=t, request_id=f"req-{req_i}"
            )
            best = pod_names.index(decision.pod)
            if decision.action == "pull" and decision.pull_source is not None:
                tgt = pods[best]
                hashes = indexer.token_processor.prefix_hashes(tokens)
                t0 = time.perf_counter()
                if store is not None and decision.pull_source == kv_name:
                    # Bring-back from the kvstore holder: wire-ready
                    # payloads, no source engine work.
                    blocks = store.serve(hashes)
                else:
                    src = pods[pod_names.index(decision.pull_source)]
                    blocks = src.engine.export_kv_blocks(hashes)
                n_imp = tgt.engine.import_kv_blocks(blocks)
                wall = time.perf_counter() - t0
                wire = sum(b.wire_bytes for b in blocks)
                link_s = wire / link_bytes_s if wire and link_bytes_s else 0.0
                tgt.clock = max(tgt.clock, t) + wall + link_s
                if wire:
                    cost_model.observe_transfer(wire, wall + link_s)
                pull_stats["pulls"] += 1
                pull_stats["pulled_blocks"] += n_imp
                pull_stats["pull_s"] += wall + link_s
                if store is not None and decision.pull_source == kv_name:
                    remote_detail["remote_pulls"] += 1
                    remote_detail["remote_pulled_blocks"] += n_imp
        elif policy == "estimated":
            keys = est.keys(tokens)
            best = max(
                range(n_pods),
                key=lambda i: (est.score(keys, i, t), -pods[i].load, -i),
            )
            est.record(keys, best, t)
        elif policy == "load":
            best = min(range(n_pods), key=lambda i: (pods[i].load, i))
        else:  # round_robin
            best = rr % n_pods
            rr += 1
        pod = pods[best]
        if not pod.engine.has_work:
            pod.clock = max(pod.clock, t)
        seq = pod.engine.add_request(
            tokens, SamplingParams(max_new_tokens=max_new_tokens)
        )
        pod.seqs.append(seq)
        arrivals[seq.seq_id] = t
        segments[seq.seq_id] = seg
        if auditor is not None:
            rid_of[seq.seq_id] = f"req-{req_i}"
    for pod in pods:
        pod.drain(ttfts, arrivals)
    if staleness is not None:
        # Leftover events apply at the end of the run on the virtual clock.
        vnow[0] = max(p.clock for p in pods)
    bus.flush_all()
    pool.drain(timeout=10.0)
    if auditor is not None:
        # Join the pods' ground truth (first-prefill cache hits + virtual
        # TTFT, the same accounting the headlines use) against every
        # recorded decision — the predicted-vs-realized / miss-attribution
        # columns. The predicted arm already joined most online; this
        # sweeps the tail.
        join_realized()
    pool.shutdown()
    indexer.shutdown()

    n_req = len(workload)
    assert len(ttfts) == n_req, f"lost requests: {len(ttfts)}/{n_req}"
    all_ttfts = np.asarray(list(ttfts.values()))
    n_segments = max(segments.values()) + 1
    per_seg = [
        np.asarray([ttfts[sid] for sid, s in segments.items() if s == seg])
        for seg in range(n_segments)
    ]

    # Fleet accounting. Makespan = the slowest pod's busy clock: the
    # virtual duration of the whole run. Each pod is one chip here.
    makespan = max(p.clock for p in pods)
    prompt_tokens = sum(n for p in pods for _, n in p.hit_stats.values())
    cached_tokens = sum(c for p in pods for c, _ in p.hit_stats.values())
    out_tokens = sum(len(s.output_tokens) for p in pods for s in p.seqs)
    # Per-request mean ITL on the virtual clock: (finish - first token) /
    # (generated - 1). The serving-SLO companion to TTFT — decode-lane
    # interference (chunked prefill, batching width) shows here first.
    itls = np.asarray(
        [
            (p.finish_clock[s.seq_id] - p.first_clock[s.seq_id])
            / (s.num_generated - 1)
            for p in pods
            for s in p.seqs
            if s.num_generated > 1
            and s.seq_id in p.first_clock
            and s.seq_id in p.finish_clock
        ]
    )
    # Host-DRAM tier evidence (host-tier arms): fleet-aggregated spill/
    # restore/prefetch counters, so the detail JSON shows the tier WORKING
    # (a hit-rate win with zero restores would mean the pool was simply
    # never pressured).
    host_detail = None
    if engine_cfg.block_manager.host_pages > 0:
        host_detail = {}
        for p in pods:
            for key, val in p.engine.block_manager.host_stats.items():
                host_detail[key] = host_detail.get(key, 0) + val
            for key, val in p.engine.host_prefetch_stats.items():
                key = f"prefetch_{key}"
                host_detail[key] = host_detail.get(key, 0) + val
    # Speculative-decode evidence (spec arms): fleet-aggregated proposal/
    # acceptance counters — the acceptance-rate column of the record.
    spec_detail = None
    if engine_cfg.spec_decode != "off":
        spec_detail = {"proposed": 0, "accepted": 0, "verify_steps": 0, "bursts": 0}
        for p in pods:
            for key in spec_detail:
                spec_detail[key] += p.engine.spec_stats[key]
        spec_detail["acceptance_rate"] = (
            round(spec_detail["accepted"] / spec_detail["proposed"], 4)
            if spec_detail["proposed"]
            else None
        )
    # Step-phase decomposition (BENCH_STEP_PHASES=1): fleet-summed engine
    # phase seconds, so each arm's record shows where step time went
    # (sample ~ 0 when the fused fast path overlaps the device_get).
    phase_detail = None
    if STEP_PHASES:
        phase_detail = {}
        for p in pods:
            for key, val in p.engine.step_stats.items():
                phase_detail[key] = round(phase_detail.get(key, 0) + val, 4)
    # Routing-quality columns (ISSUE 10): event-plane staleness
    # percentiles on the virtual clock, and the predicted-vs-realized
    # audit join with miss attribution — the ground truth ROADMAP items
    # 3 and 4 will be judged against.
    staleness_detail = None
    if staleness is not None:
        pct = staleness.percentiles()
        snap = staleness.snapshot()
        staleness_detail = {
            "events": snap["events_observed"],
            "p50_ms": (
                round(pct["p50"] * 1000, 3) if pct["p50"] is not None else None
            ),
            "p99_ms": (
                round(pct["p99"] * 1000, 3) if pct["p99"] is not None else None
            ),
            "max_ms": round(snap["max_lag_s"] * 1000, 3),
        }
    audit_detail = _audit_summary(auditor) if auditor is not None else None
    if remote_detail is not None:
        # Effective-capacity headline (ISSUE 13): tokens the fleet holds
        # cached across EVERY tier (HBM + host + kvstore) per HBM byte it
        # actually paid for — the number a single-pod tier cannot reach.
        import jax.numpy as jnp

        mc = engine_cfg.model
        page_bytes = (
            2
            * mc.n_layers
            * page
            * mc.n_kv_heads
            * mc.hd
            * np.dtype(jnp.dtype(mc.dtype).name).itemsize
        )
        fleet_pages = (
            sum(
                p.engine.block_manager.num_cached_pages
                + p.engine.block_manager.num_host_cached_pages
                for p in pods
            )
            + len(store)
        )
        hbm_pages = n_pods * (engine_cfg.block_manager.total_pages - 1)
        remote_detail.update(
            {
                "store_cached": len(store),
                "store_stats": dict(store.stats),
                "fleet_cached_tokens": fleet_pages * page,
                "hbm_pages": hbm_pages,
                "hbm_bytes": hbm_pages * page_bytes,
                "effective_capacity_x_hbm": (
                    round(fleet_pages / hbm_pages, 4) if hbm_pages else None
                ),
                "tokens_per_hbm_gib": (
                    round(
                        fleet_pages * page / (hbm_pages * page_bytes / 2**30),
                        1,
                    )
                    if hbm_pages
                    else None
                ),
            }
        )
    # Reuse-distance MRC columns (ISSUE 15): the fleet-weighted predicted
    # hit rate at each tier's cumulative capacity (per-pod curves weighted
    # by sampled accesses — each pod's curve only speaks for the stream it
    # saw). "hbm_fleet_share" models the remote tier as extra per-pod LRU
    # capacity: HBM plus this pod's share of the shared store.
    mrc_detail = None
    if mrc_est is not None:
        total_cap = engine_cfg.block_manager.total_pages - 1
        caps = {"hbm": total_cap}
        # KV_QUANT_HBM sizing point (ISSUE 16): int8 HBM pages halve the
        # bytes per page, so the same HBM byte budget holds 2x the pages
        # (minus the reserved page 0). Read on the UNQUANTIZED arm, this
        # is the pre-registered forecast the quantized arm must then
        # measure within 0.05 — the "2x point" of the MRC sizing runbook.
        caps["hbm_2x"] = 2 * engine_cfg.block_manager.total_pages - 1
        if engine_cfg.block_manager.host_pages > 0:
            caps["hbm_host"] = total_cap + engine_cfg.block_manager.host_pages
        if remote and store is not None:
            caps["hbm_fleet_share"] = (
                total_cap + store.config.capacity_pages // n_pods
            )

        def fleet_hit(cap):
            num, den = 0.0, 0
            for est in mrc_est:
                h = est.predicted_hit_rate(cap)
                if h is not None:
                    num += h * est.sampled
                    den += est.sampled
            return round(num / den, 4) if den else None

        sampled = sum(est.sampled for est in mrc_est)
        cold = sum(est.cold for est in mrc_est)
        mrc_detail = {
            "accesses": sum(est.accesses for est in mrc_est),
            "sampled": sampled,
            "cold_fraction": round(cold / sampled, 4) if sampled else None,
            "capacities": caps,
            "predicted_hit": {name: fleet_hit(c) for name, c in caps.items()},
        }
    # The Pod.on_events closure references the Pod (staging buffer), so
    # Pod <-> Engine is now a reference CYCLE: without an explicit collect,
    # each policy's engines (~GBs of donated KV pools on the chip) survive
    # into the next policy until the cycle collector happens to run — which
    # OOMs the second policy on a 16 GB chip.
    pods.clear()
    gc.collect()
    return {
        "p50_ttft_s": float(np.median(all_ttfts)),
        "p90_ttft_s": float(np.percentile(all_ttfts, 90)),
        "p99_ttft_s": float(np.percentile(all_ttfts, 99)),
        "mean_ttft_s": float(np.mean(all_ttfts)),
        "p50_itl_s": float(np.median(itls)) if itls.size else None,
        "p90_itl_s": float(np.percentile(itls, 90)) if itls.size else None,
        "p99_itl_s": float(np.percentile(itls, 99)) if itls.size else None,
        "mean_itl_s": float(np.mean(itls)) if itls.size else None,
        "p50_ttft_per_qps_segment_s": [float(np.median(s)) for s in per_seg],
        "req_s_per_chip": float(n_req / makespan / n_pods) if makespan else 0.0,
        "output_tok_s_per_chip": (
            float(out_tokens / makespan / n_pods) if makespan else 0.0
        ),
        "prefix_cache_hit_rate": (
            float(cached_tokens / prompt_tokens) if prompt_tokens else 0.0
        ),
        "makespan_s": float(makespan),
        # Cross-pod pull accounting (BENCH_TRANSFER=1, precise only).
        **(
            {"transfer": {**pull_stats, "pull_s": round(pull_stats["pull_s"], 3)}}
            if cost_model is not None
            else {}
        ),
        **({"host": host_detail} if host_detail is not None else {}),
        **({"remote": remote_detail} if remote_detail is not None else {}),
        **({"mrc": mrc_detail} if mrc_detail is not None else {}),
        **({"spec": spec_detail} if spec_detail is not None else {}),
        **({"phases": phase_detail} if phase_detail is not None else {}),
        **({"staleness": staleness_detail} if staleness_detail is not None else {}),
        **({"audit": audit_detail} if audit_detail is not None else {}),
    }


def run_fleet_arm(
    workload, params, engine_cfg, max_pods, max_new_tokens, dynamic,
    start_pods=None, roomy_pool=False,
):
    """ISSUE 17 controller arm: the same co-sim engines with POD COUNT in
    the loop, under the PRODUCT ``FleetController`` (the real decision
    logic — burn x MRC-headroom with hysteresis — driven by a co-sim
    adapter whose migrate/revive actions move KV through the real engine
    export/import endpoints). ``dynamic=False`` is the comparator: the
    identical fleet pinned at ``max_pods`` for the whole run (the static
    peak fleet a capacity planner would provision for the burst top).

    The judged pair: the dynamic arm must hold TTFT percentiles through
    the bursts at FEWER pod-seconds than the static peak (pod-seconds =
    virtual provisioned time summed over pods, the bill a fleet actually
    pays). Engines run with a pool small enough that one pod cannot hold
    the workload's prefix working set but the full fleet can — the
    capacity regime where the MRC gate has something to say; burn alone
    (a compute-bound queue spike with a flat curve) correctly holds with
    ``burning_mrc_flat``.

    Scale-down live-migrates the victim's in-flight sequences through
    the product freeze/export/import/fold path; first-token times and
    first-prefill hit accounting stay with the sequence across the move
    (TTFT is a property of the REQUEST, not of whichever pod finished
    it).

    ``start_pods`` overrides the dynamic arm's initial fleet width (the
    scale-DOWN drill starts at max_pods, over-provisioned);
    ``roomy_pool`` sizes the pool so ONE pod holds the whole working
    set — the flat-MRC regime where ``idle_mrc_flat`` scale-down is the
    CORRECT call (the family default is the opposite: capacity-starved,
    where the MRC gate rightly refuses to shed warmth)."""
    import dataclasses as _dc

    from llm_d_kv_cache_manager_tpu.kvcache import (
        KVCacheIndexer,
        KVCacheIndexerConfig,
        PrefixAffinityTracker,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.controller import (
        FleetController,
        FleetControllerConfig,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.controller import (
        PodSignals as FleetPodSignals,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock import (
        ChunkedTokenDatabase,
        TokenProcessorConfig,
    )
    from llm_d_kv_cache_manager_tpu.obs.lifecycle import (
        ReuseDistanceEstimator,
        debug_mrc_payload,
    )
    from llm_d_kv_cache_manager_tpu.server.sequence import SamplingParams

    page = engine_cfg.block_manager.page_size
    # Pool sizing: the fleet at max_pods holds the whole prefix working
    # set with slack; one pod holds only a fraction of it. BENCH_FLEET_
    # PAGES overrides.
    prompt_pages = max(
        -(-(len(toks) + max_new_tokens + 1) // page) for _, _, toks in workload
    )
    distinct = len({tuple(toks[: page * 2]) for _, _, toks in workload})
    working = max(distinct, 2) * prompt_pages
    if roomy_pool:
        fleet_pages = working + prompt_pages + 1
    else:
        fleet_pages = int(
            os.environ.get(
                "BENCH_FLEET_PAGES",
                str(max(-(-working * 2 // max_pods), prompt_pages + 3) + 1),
            )
        )
    # The drill runs a longer decode tail than the family regime; widen
    # the model length (and its page buckets) when the prompt + tail
    # would not fit the family shape.
    need_len = (
        max(len(toks) for _, _, toks in workload) + max_new_tokens + page
    )
    mml = max(engine_cfg.max_model_len, need_len)
    cfg = _dc.replace(
        engine_cfg,
        max_model_len=mml,
        prefill_ctx_bucket=-(-mml // page),
        decode_pages_bucket=-(-mml // page),
        block_manager=_dc.replace(
            engine_cfg.block_manager, total_pages=fleet_pages
        ),
    )
    # The shrunken pool is a NEW kv-pool shape: compile it on a scratch
    # engine (main()'s warmup covered the full-size pool only) so neither
    # arm's virtual clocks eat the XLA compiles — the first arm to run
    # would otherwise be charged seconds of compile as fake queueing.
    longest = max((toks for _, _, toks in workload), key=len)
    warmup(
        params, cfg, max(len(longest) - 8, page), 8,
        engine_cfg.model.vocab_size, max_new_tokens,
    )
    # Unloaded cold service time, measured on a compiled scratch engine:
    # the TTFT objective self-grounds at 2x this (an SLO an operator
    # would set from a capability probe, NOT from loaded samples — a
    # threshold calibrated during a pile-up learns to call the pile-up
    # normal).
    from llm_d_kv_cache_manager_tpu.server.engine import Engine as _Engine

    probe = _Engine(cfg, params=params)
    probe.add_request(
        list(longest), SamplingParams(max_new_tokens=max_new_tokens)
    )
    t0 = time.perf_counter()
    probe.run_until_complete()
    t_cold = time.perf_counter() - t0
    del probe
    gc.collect()
    indexer = KVCacheIndexer(
        KVCacheIndexerConfig(token_processor=TokenProcessorConfig(block_size=page))
    )
    pool, publish = make_event_pipeline(indexer.kv_block_index, max_pods)
    lag_s = float(os.environ.get("BENCH_EVENT_LAG_MS", "2")) / 1000.0
    bus = LaggedEventBus(pool, lag_s)
    pods = [Pod(i, cfg, params, publish, bus) for i in range(max_pods)]
    pod_cap = fleet_pages - 1
    mrc_est = [
        ReuseDistanceEstimator(sample_rate=1.0, max_tracked=1 << 15)
        for _ in pods
    ]
    for p, est in zip(pods, mrc_est):
        p.engine.block_manager.attach_lifecycle(None, est)
    aff = PrefixAffinityTracker(
        max_pods,
        capacity_blocks=pod_cap,
        token_processor=ChunkedTokenDatabase(TokenProcessorConfig(block_size=page)),
    )
    link_bytes_s = float(os.environ.get("BENCH_TRANSFER_GBPS", "10")) * 1e9 / 8

    # THE PRODUCT ROUTER over the active subset, WITH the transfer cost
    # model. The pull arm matters more here than in the pinned-width
    # arms: score-max pins each prefix group on the one pod that is warm
    # for it (load only breaks score ties), so after a scale-up the old
    # pod would keep thrashing its pool on every group it seeded while
    # the new pods idle — the cost model is what MOVES warmth to where
    # the headroom is. BlendedRouter ranks candidates positionally; the
    # shim maps positions back to global pod slots as the active set
    # changes per arrival.
    from llm_d_kv_cache_manager_tpu.kvcache import BlendedRouter
    from llm_d_kv_cache_manager_tpu.kvcache.transfer import (
        TransferCostModel,
        TransferCostModelConfig,
    )

    class _ActiveAff:
        order: list = []

        @staticmethod
        def keys(tokens):
            return aff.keys(tokens)

        @staticmethod
        def score(keys, i, now):
            return aff.score(keys, _ActiveAff.order[i], now)

        @staticmethod
        def record(keys, i, now):
            aff.record(keys, _ActiveAff.order[i], now)

    cost_model = TransferCostModel(
        TransferCostModelConfig(
            block_bytes=pods[0].engine.kv_block_bytes, block_size=page
        )
    )
    cost_model.seed_rates(transfer_bytes_s=link_bytes_s)
    blended = BlendedRouter(
        score_fn=lambda toks, names: indexer.score_tokens(
            toks, MODEL_NAME, names
        ),
        affinity=_ActiveAff,
        loads_fn=lambda names: [
            pods[int(nm.rsplit("-", 1)[1])].load for nm in names
        ],
        cost_model=cost_model,
    )
    pull_stats = {"pulls": 0, "pulled_blocks": 0, "pull_s": 0.0}

    ttfts: dict[int, float] = {}
    arrivals: dict[int, float] = {}
    segments: dict[int, int] = {}
    vnow = [0.0]
    n0 = (
        max_pods
        if not dynamic
        else (start_pods if start_pods is not None else 1)
    )
    active: set[int] = set(range(n0))
    retired: set[int] = set()
    span_start = {i: 0.0 for i in active}
    pod_seconds = [0.0]
    live: dict[str, tuple[int, object]] = {}  # request_id -> (pod idx, seq)
    actions: list[dict] = []
    peak_pods = [len(active)]
    migrations = {"migrated": 0, "migrated_blocks": 0, "revived_blocks": 0}
    # Measured wall time of the migration path (freeze/export/import +
    # modeled link), summed over migrations: the acceptance comparison
    # against the 30 s DRAIN_TIMEOUT_S a drain-based removal pays.
    migrate_wall = [0.0]

    # SLO-burn signal on the virtual clock: objective "TTFT <= T at p90"
    # where T self-calibrates to 2x the median of the first completions
    # (the co-sim has no absolute latency scale across rigs); burn =
    # windowed miss fraction / the 10% error budget — the same burn-rate
    # definition obs/slo.py exports as kvcache_slo_burn_rate.
    span_t = workload[-1][0] if workload else 1.0
    rec_interval = max(span_t / 60.0, 1e-3)
    burn_window = 8 * rec_interval
    samples: list[tuple[float, float]] = []  # (first-token instant, ttft)
    seen_first: set[int] = set()
    slo_t = float(
        os.environ.get("BENCH_FLEET_SLO_TTFT_S", "") or 2.0 * t_cold
    )

    def harvest():
        for p in pods:
            for sid, ft in p.first_clock.items():
                if sid in seen_first or sid not in ttfts:
                    continue
                seen_first.add(sid)
                samples.append((ft, ttfts[sid]))

    def burn_rates_now():
        recent = [v for ft, v in samples if ft >= vnow[0] - burn_window]
        # Overdue-in-queue requests count as misses NOW: a saturated pod
        # delays its own first tokens, so a burn signal built only from
        # REALIZED TTFTs goes quiet exactly when the fleet is drowning —
        # the alarm must fire while the queue is growing, not after it
        # drains.
        overdue = sum(
            1
            for sid, at in arrivals.items()
            if sid not in seen_first and vnow[0] - at > slo_t
        )
        if not recent and not overdue:
            return None
        miss = (sum(1 for v in recent if v > slo_t) + overdue) / (
            len(recent) + overdue
        )
        return {"ttft_bench_p0.9": {"w": miss / 0.1}}

    class CosimFleet:
        """FleetAdapter over the co-sim pods (indices name endpoints)."""

        def observe(self):
            burn = burn_rates_now()
            out = []
            for i in sorted(active):
                out.append(
                    FleetPodSignals(
                        pod_id=f"tpu-pod-{i}",
                        transfer_endpoint=str(i),
                        capacity_blocks=pod_cap,
                        burn_rates=burn,
                        mrc=debug_mrc_payload(mrc_est[i])[1],
                        live_requests=[
                            rid
                            for rid, (pi, s) in live.items()
                            if pi == i and not s.is_finished()
                        ],
                    )
                )
            return out

        def add_pod(self):
            idx = next(
                (
                    i
                    for i in range(max_pods)
                    if i not in active and i not in retired
                ),
                None,
            )
            if idx is None:
                return None
            active.add(idx)
            peak_pods[0] = max(peak_pods[0], len(active))
            span_start[idx] = vnow[0]
            pods[idx].clock = max(pods[idx].clock, vnow[0])
            return FleetPodSignals(
                pod_id=f"tpu-pod-{idx}",
                transfer_endpoint=str(idx),
                capacity_blocks=pod_cap,
            )

        def migrate(self, pod_id, request_id, target_endpoint):
            src = pods[int(pod_id.rsplit("-", 1)[1])]
            tgt = pods[int(target_endpoint)]
            frozen = src.engine.freeze_for_migration(request_id)
            if frozen is None:
                return False
            seq, hashes = frozen
            t0 = time.perf_counter()
            blocks = src.engine.export_kv_blocks(hashes)
            n_imp = tgt.engine.import_kv_blocks(blocks)
            wall = time.perf_counter() - t0
            wire = sum(b.wire_bytes for b in blocks)
            link_s = wire / link_bytes_s if link_bytes_s else 0.0
            tgt.clock = max(tgt.clock, vnow[0]) + wall + link_s
            migrate_wall[0] += wall + link_s
            cont = tgt.engine.add_request(
                list(seq.prompt_tokens),
                SamplingParams(max_new_tokens=seq.sampling.max_new_tokens),
                request_id=request_id,
            )
            cont.user_prompt_len = seq.user_prompt_len
            cont.num_generated = seq.num_generated
            src.engine.finish_migrated(seq)
            src.flush_staged()
            tgt.flush_staged()
            old, new = seq.seq_id, cont.seq_id
            for d in (arrivals, segments):
                if old in d:
                    d[new] = d.pop(old)
            if old in ttfts:
                # First token already served at the source: the TTFT (and
                # the first-prefill hit snapshot) is settled history — the
                # continuation must not re-record either, and the burn
                # signal's overdue scan must not see a served request as
                # still queued under its new seq_id.
                ttfts[new] = ttfts.pop(old)
                tgt._first_token_seen.add(new)
                seen_first.add(new)
                if old in src.hit_stats:
                    tgt.hit_stats[new] = src.hit_stats[old]
            tgt.seqs.append(cont)
            live[request_id] = (int(target_endpoint), cont)
            migrations["migrated"] += 1
            migrations["migrated_blocks"] += n_imp
            return True

        def retire(self, pod_id):
            idx = int(pod_id.rsplit("-", 1)[1])
            active.discard(idx)
            retired.add(idx)
            # Migration fallbacks (none expected) finish locally before
            # the pod is deprovisioned; the straggler time is billed.
            pods[idx].drain(ttfts, arrivals)
            end = max(vnow[0], pods[idx].clock)
            pod_seconds[0] += end - span_start.pop(idx)

        def warm_sets(self, limit):
            rows = []
            for i in sorted(active):
                for chain in pods[i].engine.block_manager.hot_chains(limit):
                    rows.append((str(i), chain))
            rows.sort(key=lambda r: len(r[1]), reverse=True)
            return rows[:limit]

        def revive(self, pod_id, source_endpoint, chain_hashes):
            tgt = pods[int(pod_id.rsplit("-", 1)[1])]
            src = pods[int(source_endpoint)]
            t0 = time.perf_counter()
            blocks = src.engine.export_kv_blocks(chain_hashes)
            n_imp = tgt.engine.import_kv_blocks(blocks)
            wall = time.perf_counter() - t0
            wire = sum(b.wire_bytes for b in blocks)
            tgt.clock = max(tgt.clock, vnow[0]) + wall + (
                wire / link_bytes_s if link_bytes_s else 0.0
            )
            # The revived pod has no work yet, so it will not step: stage
            # the import's BlockStored events now or the index never sees
            # the revival and routing never warms to the new pod.
            tgt.flush_staged()
            migrations["revived_blocks"] += n_imp
            return n_imp

    ctl = None
    if dynamic:
        ctl = FleetController(
            FleetControllerConfig(
                enabled=True,
                reconcile_interval_s=rec_interval,
                burn_threshold=float(
                    os.environ.get("BENCH_FLEET_BURN", "") or "1.5"
                ),
                mrc_headroom=float(
                    os.environ.get("BENCH_FLEET_HEADROOM", "") or "0.01"
                ),
                hysteresis_s=2 * rec_interval,
                min_pods=1,
                max_pods=max_pods,
            ),
            CosimFleet(),
            clock=lambda: vnow[0],
        )

    next_rec = rec_interval
    for req_i, (t, seg, tokens) in enumerate(workload):
        if ctl is not None:
            while next_rec <= t:
                for i in sorted(active):
                    pods[i].advance_to(next_rec, ttfts, arrivals)
                vnow[0] = next_rec
                harvest()
                d = ctl.reconcile()
                if d.action != "hold":
                    actions.append({"t": round(next_rec, 3), **d.as_attrs()})
                next_rec += rec_interval
        for i in sorted(active):
            pods[i].advance_to(t, ttfts, arrivals)
        vnow[0] = t
        # Release in-flight events so the index reflects fleet state at
        # the arrival instant — including the BlockStored batch from a
        # warm-set revival, which is what makes a freshly added pod
        # attract its share of the working set (the index SEES the
        # revived chains). Routing and the pull arm mirror run_policy's
        # precise+transfer path over the active subset.
        bus.release(t)
        order = sorted(active)
        names = [f"tpu-pod-{i}" for i in order]
        _ActiveAff.order = order
        rates = [
            pods[i].engine._prefill_rate
            for i in order
            if pods[i].engine._prefill_rate
        ]
        if rates:
            cost_model.seed_rates(prefill_tokens_s=float(np.median(rates)))
        decision = blended.route(tokens, names, now=t, request_id=f"r{req_i}")
        best = int(decision.pod.rsplit("-", 1)[1])
        if decision.action == "pull" and decision.pull_source is not None:
            tgt = pods[best]
            src = pods[int(decision.pull_source.rsplit("-", 1)[1])]
            hashes = indexer.token_processor.prefix_hashes(tokens)
            t0p = time.perf_counter()
            blocks = src.engine.export_kv_blocks(hashes)
            n_imp = tgt.engine.import_kv_blocks(blocks)
            wallp = time.perf_counter() - t0p
            wire = sum(b.wire_bytes for b in blocks)
            link_s = wire / link_bytes_s if wire and link_bytes_s else 0.0
            tgt.clock = max(tgt.clock, t) + wallp + link_s
            if wire:
                cost_model.observe_transfer(wire, wallp + link_s)
            tgt.flush_staged()
            pull_stats["pulls"] += 1
            pull_stats["pulled_blocks"] += n_imp
            pull_stats["pull_s"] += wallp + link_s
        pod = pods[best]
        if not pod.engine.has_work:
            pod.clock = max(pod.clock, t)
        seq = pod.engine.add_request(
            tokens,
            SamplingParams(max_new_tokens=max_new_tokens),
            request_id=f"r{req_i}",
        )
        pod.seqs.append(seq)
        arrivals[seq.seq_id] = t
        segments[seq.seq_id] = seg
        live[f"r{req_i}"] = (best, seq)
    if ctl is not None:
        # Keep reconciling through the decode tail: arrivals stopped, the
        # burn signal goes calm, the curve flattens — the controller
        # scales the fleet back down, LIVE-MIGRATING in-flight decodes to
        # survivors (the scale-down path the pod-seconds bill rewards).
        for _ in range(100_000):
            if not any(pods[i].engine.has_work for i in active):
                break
            for i in sorted(active):
                pods[i].advance_to(next_rec, ttfts, arrivals)
            vnow[0] = max(next_rec, vnow[0])
            harvest()
            d = ctl.reconcile()
            if d.action != "hold":
                actions.append({"t": round(next_rec, 3), **d.as_attrs()})
            next_rec += rec_interval
        else:
            raise RuntimeError("fleet arm failed to drain")
    for i in sorted(active):
        pods[i].drain(ttfts, arrivals)
    bus.flush_all()
    pool.drain(timeout=10.0)
    pool.shutdown()
    indexer.shutdown()

    n_req = len(workload)
    assert len(ttfts) == n_req, f"lost requests: {len(ttfts)}/{n_req}"
    makespan = max(p.clock for p in pods)
    for idx, start in span_start.items():
        pod_seconds[0] += max(makespan, vnow[0]) - start
    prompt_tokens = sum(n for p in pods for _, n in p.hit_stats.values())
    cached_tokens = sum(c for p in pods for c, _ in p.hit_stats.values())
    all_ttfts = np.asarray(list(ttfts.values()))
    # Per-QPS-segment tails: reactive autoscaling concedes the FIRST
    # spike (detection needs samples), then holds the repeats — the
    # segment columns are where that shows.
    n_segments = max(segments.values()) + 1
    seg_p99 = [
        round(
            float(
                np.percentile(
                    [ttfts[sid] for sid, s in segments.items() if s == seg],
                    99,
                )
            ),
            4,
        )
        if any(s == seg for s in segments.values())
        else None
        for seg in range(n_segments)
    ]
    itls = np.asarray(
        [
            (p.finish_clock[s.seq_id] - p.first_clock[s.seq_id])
            / (s.num_generated - 1)
            for p in pods
            for s in p.seqs
            if s.num_generated > 1
            and s.seq_id in p.first_clock
            and s.seq_id in p.finish_clock
        ]
    )
    out = {
        "p50_ttft_s": float(np.median(all_ttfts)),
        "p90_ttft_s": float(np.percentile(all_ttfts, 90)),
        "p99_ttft_s": float(np.percentile(all_ttfts, 99)),
        "p50_itl_s": float(np.median(itls)) if itls.size else None,
        "p99_itl_s": float(np.percentile(itls, 99)) if itls.size else None,
        "prefix_cache_hit_rate": (
            float(cached_tokens / prompt_tokens) if prompt_tokens else 0.0
        ),
        "makespan_s": float(makespan),
        "seg_p99_ttft_s": seg_p99,
        "pod_seconds": round(pod_seconds[0], 3),
        "peak_pods": peak_pods[0],
        "pod_pages": fleet_pages,
        "slo_ttft_s": round(slo_t, 4),
        "cold_service_s": round(t_cold, 4),
        **migrations,
        "migration_wall_s": round(migrate_wall[0], 4),
        "pulls": pull_stats["pulls"],
        "pulled_blocks": pull_stats["pulled_blocks"],
        "pull_s": round(pull_stats["pull_s"], 4),
    }
    if dynamic:
        out["actions"] = actions
        out["decisions"] = len(ctl.decisions)
    pods.clear()
    gc.collect()
    return out


def run_tenant_qos_arm(
    workload, tenant_of, params, engine_cfg, max_new_tokens, qos_spec=None,
):
    """ISSUE 18 two-class arm: ONE capacity-constrained pod (tenant QoS
    is a per-pod mechanism) on the virtual clock, serving an interleaved
    premium + background schedule. ``tenant_of(i)`` names request i's
    tenant; ``qos_spec=None`` is the knob-off comparator — the identical
    engine and schedule with no tenant dimension anywhere (requests are
    still sliced by tenant for reporting, the engine never sees it).

    With a spec, the arm drives the PRODUCT machinery end to end: the
    parsed ``TenantQoS`` budget table gates admission on the virtual
    clock (a budget rejection is the 429 arm — the request is shed at
    the door, exactly what the serving layer does), the scheduler runs
    priority ordering + preemption, and the block manager runs
    cache_share accounting with per-tenant MRC slices. Budgets release
    on finish, mirroring ``_forget_pending``; first-prefill hit
    accounting and first-token TTFT stay with the request across
    preemption (same rationale as ``Pod.step_timed``)."""
    from llm_d_kv_cache_manager_tpu.obs.lifecycle import ReuseDistanceEstimator
    from llm_d_kv_cache_manager_tpu.server.engine import Engine
    from llm_d_kv_cache_manager_tpu.server.qos import TenantQoS, parse_tenant_qos
    from llm_d_kv_cache_manager_tpu.server.sequence import SamplingParams

    engine = Engine(engine_cfg, params=params, on_events=lambda _ev: None)
    qos = None
    if qos_spec:
        qos = TenantQoS(parse_tenant_qos(qos_spec))
        engine.scheduler.attach_qos()
        engine.block_manager.attach_qos(qos, mrc_factory=ReuseDistanceEstimator)

    # A throwaway warm-up burst before the timed loop: the in-process
    # trace/dispatch cost of this run's shapes (batched prefill widths,
    # decode widths) is paid once per PROCESS, so without it the cost
    # lands entirely in the FIRST arm's TTFTs and poisons the
    # unloaded/off/on three-way comparison. Concurrent requests exercise
    # the same batch widths the arms hit; the warm chains' pages are
    # untenanted LRU fodder, identical across arms.
    warm_len = len(workload[0][2]) if workload else 8
    wrng = np.random.default_rng(97)
    warm_prompts = [
        wrng.integers(0, engine_cfg.model.vocab_size, warm_len).tolist()
        for _ in range(8)
    ]
    for p in warm_prompts:
        engine.add_request(
            p, SamplingParams(max_new_tokens=max_new_tokens)
        )
    while engine.has_work:
        engine.step()
    # ...and one repeated prompt: the warm-prefill (paged prefix-cache
    # context) dispatch is a DIFFERENT shape than the cold prefills
    # above, and the workloads are built around prefix reuse.
    engine.add_request(
        warm_prompts[0], SamplingParams(max_new_tokens=max_new_tokens)
    )
    while engine.has_work:
        engine.step()

    clock = 0.0
    seq_tenant = {}  # seq_id -> tenant slice key (arm-side bookkeeping)
    arrivals = {}
    ttfts = {}
    hits = {}  # seq_id -> (cached, prompt) at FIRST prefill
    first_seen = set()
    rejected = {}

    def step():
        nonlocal clock
        t0 = time.perf_counter()
        done = engine.step()
        dt = time.perf_counter() - t0
        clock += dt
        for seq in list(engine.scheduler.running) + done:
            if seq.num_generated >= 1 and seq.seq_id not in first_seen:
                first_seen.add(seq.seq_id)
                ttfts[seq.seq_id] = clock - arrivals[seq.seq_id]
                hits[seq.seq_id] = (
                    seq.num_cached_prompt, len(seq.prompt_tokens)
                )
        if qos is not None:
            for seq in done:
                qos.on_resolved(seq.tenant, seq.user_prompt_len)

    for i, (t_arr, _seg, tokens) in enumerate(workload):
        while engine.has_work and clock < t_arr:
            step()
        clock = max(clock, t_arr)
        tenant = tenant_of(i)
        sampling = SamplingParams(max_new_tokens=max_new_tokens)
        if qos is None:
            seq = engine.add_request(tokens, sampling)
        else:
            if qos.admit(tenant, len(tokens), now=clock) is not None:
                rejected[tenant] = rejected.get(tenant, 0) + 1
                continue
            pol = qos.policy(tenant)
            seq = engine.add_request(
                tokens, sampling,
                tenant=tenant, priority=pol.priority, qos_weight=pol.weight,
            )
            qos.on_admitted(tenant, len(tokens), now=clock)
        seq_tenant[seq.seq_id] = tenant
        arrivals[seq.seq_id] = t_arr
    while engine.has_work:
        step()

    def _slice(tenant):
        ids = [s for s, t in seq_tenant.items() if t == tenant]
        lat = [ttfts[s] for s in ids if s in ttfts]
        cached = sum(hits[s][0] for s in ids if s in hits)
        total = sum(hits[s][1] for s in ids if s in hits)
        return {
            "served": len(lat),
            "rejected": rejected.get(tenant, 0),
            "p50_ttft_s": round(float(np.percentile(lat, 50)), 4) if lat else None,
            "p90_ttft_s": round(float(np.percentile(lat, 90)), 4) if lat else None,
            "p99_ttft_s": round(float(np.percentile(lat, 99)), 4) if lat else None,
            "prefix_cache_hit_rate": (
                round(cached / total, 4) if total else None
            ),
        }

    out = {
        "tenants": {
            t: _slice(t)
            for t in sorted(set(seq_tenant.values()) | set(rejected))
        },
        "priority_preempted": engine.lifecycle_stats.get(
            "priority_preempted", 0
        ),
        "makespan_s": round(clock, 4),
    }
    if qos is not None:
        pool = engine_cfg.block_manager.total_pages
        out["cache"] = {
            t: dict(s) for t, s in engine.block_manager.tenant_stats.items()
        }
        # Per-tenant MRC slices: the /debug/mrc sizing evidence — what
        # each tenant's hit rate would be at the pool / half the pool,
        # i.e. the curve an operator reads to size cache_share.
        out["mrc"] = {}
        for t, est in sorted(engine.block_manager._tenant_mrc.items()):
            hit_pool = est.predicted_hit_rate(pool)
            hit_half = est.predicted_hit_rate(max(pool // 2, 1))
            out["mrc"][t] = {
                "predicted_hit_at_pool": (
                    round(hit_pool, 4) if hit_pool is not None else None
                ),
                "predicted_hit_at_half_pool": (
                    round(hit_half, 4) if hit_half is not None else None
                ),
            }
    del engine
    gc.collect()
    return out


def run_kv_integrity_arm(
    workload, params, engine_cfg, max_new_tokens, flips=0, flip_seed=0,
):
    """ISSUE 19 corruption-drill arm: ONE pod, requests served
    SEQUENTIALLY (add → run to completion → next) against a pool sized
    so every warm prefix spills to the host tier between revisits — the
    spill→restore edge the write-time digests guard. Sequential on
    purpose: the trio below is judged on EXACT greedy token parity, and
    the co-sim's Poisson pacing makes batch composition (hence padding
    and reduction order, hence near-tie argmaxes) a function of wall
    time — identical step sequences are what make the parity bar and
    the makespan A/B sound. ``flips`` > 0 injects single-byte flips
    into resident host slots at evenly spaced requests (the same fault
    ``tests/chaos``'s ``corrupt_host_slot`` models: bit rot in the
    spilled copy, invisible until the page is next restored, exported,
    or scrubbed); a final full scrub sweeps whatever latent rot the
    traffic never revisited.

    Returns ``(metrics, outputs)`` — outputs are the per-request greedy
    token ids, so the caller can assert exact parity across the
    off / on-clean / on-drill trio: detection + quarantine + cold
    recompute must serve ZERO corrupted tokens, and the clean knob-on
    run must be bit-identical to the knob-off baseline."""
    from llm_d_kv_cache_manager_tpu.server.engine import Engine
    from llm_d_kv_cache_manager_tpu.server.sequence import SamplingParams

    engine = Engine(engine_cfg, params=params, on_events=lambda _ev: None)
    frng = np.random.default_rng(flip_seed)
    flipped: set[int] = set()

    def flip_host_page() -> int:
        # One byte, one distinct resident chain per injection; quarantined
        # chains are excluded (their host copy is already destroyed).
        engine._flush_page_moves()
        bm = engine.block_manager
        cands = [
            h
            for h in bm._host_cached
            if h not in flipped
            and (
                engine.integrity is None
                or not engine.integrity.is_quarantined(h)
            )
        ]
        if not cands:
            return 0
        h = cands[int(frng.integers(len(cands)))]
        flat = engine._host_k[bm._host_cached[h]].reshape(-1).view(np.uint8)
        flat[int(frng.integers(flat.size))] ^= 0xFF
        flipped.add(h)
        return 1

    # Same rationale as run_tenant_qos_arm's warm-up: pay this pool
    # shape's trace/dispatch cost before the timed loop, so the FIRST of
    # the three runs (the knob-off baseline) isn't charged compile time
    # the other two never see — that would understate the overhead A/B.
    warm_len = len(workload[0][2]) if workload else 8
    wrng = np.random.default_rng(97)
    warm = wrng.integers(0, engine_cfg.model.vocab_size, warm_len).tolist()
    for _ in range(2):
        engine.add_request(warm, SamplingParams(max_new_tokens=max_new_tokens))
        while engine.has_work:
            engine.step()

    clock = 0.0
    seqs = []
    lat = []
    injected = 0

    def step():
        nonlocal clock
        t0 = time.perf_counter()
        engine.step()
        dt = time.perf_counter() - t0
        clock += dt

    cadence = max(len(workload) // (flips + 1), 1) if flips else 0
    for i, (_t, _seg, tokens) in enumerate(workload):
        if flips and injected < flips and i and i % cadence == 0:
            injected += flip_host_page()
        seq = engine.add_request(
            tokens, SamplingParams(max_new_tokens=max_new_tokens)
        )
        seqs.append(seq)
        rt0 = clock
        while engine.has_work:
            step()
        lat.append(clock - rt0)
    if engine.integrity is not None:
        # Final latent-rot sweep: the scrub path's detection, charged to
        # the virtual clock like any other engine work.
        t0 = time.perf_counter()
        engine.scrub_host_pages(1 << 30)
        clock += time.perf_counter() - t0

    out = {
        "p50_request_s": (
            round(float(np.percentile(lat, 50)), 4) if lat else None
        ),
        "p99_request_s": (
            round(float(np.percentile(lat, 99)), 4) if lat else None
        ),
        "makespan_s": round(clock, 4),
        "injected_flips": injected,
        "host": dict(engine.block_manager.host_stats),
        "integrity": (
            engine.integrity.snapshot() if engine.integrity else None
        ),
    }
    outputs = [list(s.output_tokens) for s in seqs]
    del engine
    gc.collect()
    return out, outputs


def run_disagg(
    workload, params, engine_cfg, n_prefill, n_decode, max_new_tokens,
    link_gbps,
):
    """Disaggregated prefill/decode fleet over the same workload: N
    prefill pods run ingest and stop at the first token; each finished
    chain is handed off over the real engine export/import endpoints
    (charged the measured wall time plus the modeled DCN link, exactly
    like BENCH_TRANSFER) and the decode tier streams the remaining
    tokens. Placement is THE PRODUCT PATH (kvcache/router.TwoHopPlanner:
    warmth + measured prefill rate for the prefill hop, queue-depth
    headroom for the decode hop)."""
    from llm_d_kv_cache_manager_tpu.kvcache import (
        KVCacheIndexer,
        KVCacheIndexerConfig,
        PodView,
        TwoHopPlanner,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock import TokenProcessorConfig
    from llm_d_kv_cache_manager_tpu.server.sequence import SamplingParams

    page = engine_cfg.block_manager.page_size
    indexer = KVCacheIndexer(
        KVCacheIndexerConfig(token_processor=TokenProcessorConfig(block_size=page))
    )
    from llm_d_kv_cache_manager_tpu.obs.audit import (
        RouteAuditor,
        StalenessTracker,
    )

    vnow = [0.0]
    staleness = StalenessTracker(clock=lambda: vnow[0])
    auditor = RouteAuditor(
        index=indexer.kv_block_index,
        model_name=MODEL_NAME,
        ring=len(workload) + 1,
        pending_cap=len(workload) + 1,
    )
    n_pods = n_prefill + n_decode
    pool, publish = make_event_pipeline(
        indexer.kv_block_index, n_pods, staleness=staleness, audit=auditor
    )
    lag_s = float(os.environ.get("BENCH_EVENT_LAG_MS", "2")) / 1000.0
    bus = LaggedEventBus(pool, lag_s)
    pods = [Pod(i, engine_cfg, params, publish, bus) for i in range(n_pods)]
    prefill_pods = {f"tpu-pod-{i}": pods[i] for i in range(n_prefill)}
    decode_pods = {
        f"tpu-pod-{i}": pods[i] for i in range(n_prefill, n_pods)
    }
    planner = TwoHopPlanner(
        score_fn=lambda toks, names: indexer.score_tokens(toks, MODEL_NAME, names)
    )
    link_bytes_s = link_gbps * 1e9 / 8

    def views():
        vs = [
            PodView(
                name, role="prefill", transfer_endpoint=name,
                queue_depth=pod.load, prefill_rate=pod.engine._prefill_rate,
            )
            for name, pod in prefill_pods.items()
        ]
        vs += [
            PodView(name, role="decode", queue_depth=pod.load)
            for name, pod in decode_pods.items()
        ]
        return vs

    ttfts: dict[int, float] = {}
    arrivals: dict[int, float] = {}
    #: prefill-hop seq -> (seq, prompt tokens, source pod, decode pod
    #: name, audit request id)
    pending: dict[int, tuple] = {}
    #: audit rid -> (tier pod object, seq_id) for the realized join; the
    #: ingest entry is the prediction's subject, the decode entry feeds
    #: the both-tier hit accounting.
    ingest_of: dict[str, tuple] = {}
    decode_of: dict[str, tuple] = {}
    handoff = {"count": 0, "blocks": 0, "transfer_s": 0.0, "replans": 0}
    cont_sampling = SamplingParams(max_new_tokens=max_new_tokens - 1)

    def process_handoffs():
        """Move every finished prefill hop's chain to its decode pod and
        admit the continuation there (virtual clocks charged: the decode
        pod cannot admit before the chain existed, nor before its own
        clock, and it pays the measured export/import wall + link time)."""
        for sid in list(pending):
            seq, tokens, src, dec_name, rid = pending[sid]
            if not seq.is_finished():
                continue
            del pending[sid]
            tgt = decode_pods[dec_name]
            hashes = indexer.token_processor.prefix_hashes(tokens)
            t0 = time.perf_counter()
            blocks = src.engine.export_kv_blocks(hashes)
            n_imp = tgt.engine.import_kv_blocks(blocks)
            wall = time.perf_counter() - t0
            wire = sum(b.wire_bytes for b in blocks)
            link_s = wire / link_bytes_s if wire and link_bytes_s else 0.0
            ready_at = src.finish_clock.get(sid, src.clock)
            tgt.clock = max(tgt.clock, ready_at) + wall + link_s
            cont = tgt.engine.add_request(
                tokens + seq.generated_tokens, cont_sampling
            )
            tgt.seqs.append(cont)
            decode_of[rid] = (tgt, cont.seq_id)
            handoff["count"] += 1
            handoff["blocks"] += n_imp
            handoff["transfer_s"] += wall + link_s

    for req_i, (t, _seg, tokens) in enumerate(workload):
        for pod in pods:
            pod.advance_to(t, ttfts, arrivals)
        process_handoffs()
        vnow[0] = t
        bus.release(t)
        plan = planner.plan(tokens, views())
        src = prefill_pods[plan.prefill_pod]
        dec_name = plan.decode_pod
        rid = f"req-{req_i}"
        # The planner's warmth claim for the ingest hop IS the prediction
        # under audit; realized comes from the prefill pod's first-prefill
        # hit accounting below.
        auditor.record_decision(
            rid,
            chosen_pod=plan.prefill_pod,
            predicted_blocks=plan.prefill_score,
            index_blocks=plan.prefill_score,
            scoreboard={plan.prefill_pod: plan.prefill_score},
            decision="disagg",
            chain_hashes=indexer.token_processor.prefix_hashes(tokens),
        )
        if not src.engine.has_work:
            src.clock = max(src.clock, t)
        seq = src.engine.add_request(tokens, SamplingParams(max_new_tokens=1))
        src.seqs.append(seq)
        arrivals[seq.seq_id] = t
        pending[seq.seq_id] = (seq, tokens, src, dec_name, rid)
        ingest_of[rid] = (src, seq.seq_id)
    while True:
        for pod in pods:
            pod.drain(ttfts, arrivals)
        process_handoffs()
        if not pending and not any(p.engine.has_work for p in pods):
            break
    vnow[0] = max(p.clock for p in pods)
    bus.flush_all()
    pool.drain(timeout=10.0)
    for rid, (src, sid) in ingest_of.items():
        if sid in src.hit_stats:
            auditor.record_realized(
                rid, f"tpu-pod-{src.pod_id}", src.hit_stats[sid][0] // page
            )
    pool.shutdown()
    indexer.shutdown()

    n_req = len(workload)
    assert len(ttfts) == n_req, f"lost requests: {len(ttfts)}/{n_req}"
    all_ttfts = np.asarray(list(ttfts.values()))
    makespan = max(p.clock for p in pods)
    # Decode-tier ITL: the isolation headline — continuation lanes never
    # share an engine with 2k-token ingest, so their inter-token gaps are
    # pure decode cadence (plus the handoff's own admission prefill).
    itls = np.asarray(
        [
            (p.finish_clock[s.seq_id] - p.first_clock[s.seq_id])
            / (s.num_generated - 1)
            for p in decode_pods.values()
            for s in p.seqs
            if s.num_generated > 1
            and s.seq_id in p.first_clock
            and s.seq_id in p.finish_clock
        ]
    )
    # Realized cache behavior, BOTH tiers via the audit path (the r08
    # record counted the ingest tier only — a decode-hop handoff that
    # failed to cache-hit its imported chain was invisible). The tiers
    # answer different questions and are reported separately: the ingest
    # rate is the workload's shared-prefix reuse (comparable to the mixed
    # arms' definition), the decode rate is handoff efficiency (~1.0 when
    # every imported chain is hit; a drop means the transfer fabric
    # delivered chains the decode engine then recomputed). The headline
    # `prefix_cache_hit_rate` is the combined both-tier number.
    ingest_prompt = sum(
        n for p in prefill_pods.values() for _, n in p.hit_stats.values()
    )
    ingest_cached = sum(
        c for p in prefill_pods.values() for c, _ in p.hit_stats.values()
    )
    decode_prompt = decode_cached = 0
    for tgt, sid in decode_of.values():
        if sid in tgt.hit_stats:
            c, n = tgt.hit_stats[sid]
            decode_cached += c
            decode_prompt += n
    prompt_tokens = ingest_prompt + decode_prompt
    cached_tokens = ingest_cached + decode_cached
    out_tokens = sum(len(s.output_tokens) for p in pods for s in p.seqs)
    res = {
        "n_prefill": n_prefill,
        "n_decode": n_decode,
        "p50_ttft_s": float(np.median(all_ttfts)),
        "p90_ttft_s": float(np.percentile(all_ttfts, 90)),
        "p50_itl_s": float(np.median(itls)) if itls.size else None,
        "p90_itl_s": float(np.percentile(itls, 90)) if itls.size else None,
        "p99_itl_s": float(np.percentile(itls, 99)) if itls.size else None,
        "req_s_per_chip": float(n_req / makespan / n_pods) if makespan else 0.0,
        "output_tok_s_per_chip": (
            float(out_tokens / makespan / n_pods) if makespan else 0.0
        ),
        "prefix_cache_hit_rate": (
            float(cached_tokens / prompt_tokens) if prompt_tokens else 0.0
        ),
        "ingest_hit_rate": (
            float(ingest_cached / ingest_prompt) if ingest_prompt else 0.0
        ),
        "decode_hit_rate": (
            float(decode_cached / decode_prompt) if decode_prompt else None
        ),
        "makespan_s": float(makespan),
        "handoffs": handoff["count"],
        "handoff_blocks": handoff["blocks"],
        "handoff_transfer_s": round(handoff["transfer_s"], 3),
        "staleness": {
            "events": staleness.snapshot()["events_observed"],
            "p50_ms": (
                round(staleness.percentiles()["p50"] * 1000, 3)
                if staleness.percentiles()["p50"] is not None
                else None
            ),
            "p99_ms": (
                round(staleness.percentiles()["p99"] * 1000, 3)
                if staleness.percentiles()["p99"] is not None
                else None
            ),
        },
        "audit": _audit_summary(auditor),
    }
    pods.clear()
    gc.collect()
    return res


def lifecycle_overhead_ab(params, engine_cfg, workload, max_new_tokens):
    """ISSUE 15 overhead A/B: per-engine-step wall time with the full
    OBS_LIFECYCLE + OBS_FLIGHT instrumentation attached (step timing,
    ledger, MRC, per-step flight recording — everything the serving loop
    pays with the knobs on) vs the bare legacy engine, on an identical
    single-engine request stream. The acceptance bar is knobs-on step
    p50 within 2% of knobs-off."""
    from llm_d_kv_cache_manager_tpu.obs.flight import FlightRecorder
    from llm_d_kv_cache_manager_tpu.obs.lifecycle import (
        BlockLifecycleLedger,
        ReuseDistanceEstimator,
    )
    from llm_d_kv_cache_manager_tpu.server.engine import Engine
    from llm_d_kv_cache_manager_tpu.server.sequence import SamplingParams

    reqs = [tokens for _, _, tokens in workload[:24]]
    p50 = {}
    lanes = max(engine_cfg.decode_batch_size, 1)
    for mode in ("off", "on"):
        eng = Engine(engine_cfg, params=params)
        flight = None
        if mode == "on":
            eng.obs_step_timing = True
            eng.block_manager.attach_lifecycle(
                BlockLifecycleLedger(), ReuseDistanceEstimator()
            )
            flight = FlightRecorder()
        steps = []
        for tokens in reqs:
            eng.add_request(tokens, SamplingParams(max_new_tokens=max_new_tokens))
            while eng.has_work:
                t0 = time.perf_counter()
                eng.step()
                steps.append(time.perf_counter() - t0)
                if flight is not None:
                    # The serving loop's per-step flight work, replayed
                    # faithfully so the A/B charges it too.
                    flight.record_step(
                        eng.step_stats,
                        occupancy=len(eng.scheduler.running) / lanes,
                        free_pages=eng.block_manager.num_free,
                    )
        p50[mode] = float(np.median(steps))
        n_steps = len(steps)
        del eng
        gc.collect()
    return {
        "requests": len(reqs),
        "steps": n_steps,
        "p50_step_off_s": round(p50["off"], 6),
        "p50_step_on_s": round(p50["on"], 6),
        "p50_on_over_off": (
            round(p50["on"] / p50["off"], 4) if p50["off"] else None
        ),
    }


def obs_fed_overhead_ab(params, engine_cfg, workload, max_new_tokens):
    """ISSUE 20 overhead A/B: (a) the headline — 4-pod
    ``FleetFederator.scrape()`` join latency (p50/p99 over ~200 scrapes
    against in-process pods carrying realistic fully-loaded payloads:
    three tiers, SLO burn, tenant slices, integrity, MRC/lifecycle/audit
    surfaces); (b) per-engine-step wall time with a background scraper
    thread hammering a federator whose fetch hooks read the LIVE engine
    state during stepping, vs the bare engine on an identical stream.
    The scraper runs at ~10 Hz — an order above any real deployment's
    scrape cadence, and strictly pessimistic beyond that: it shares the
    engine's process (and GIL), which a deployed scorer-side federator
    never does. The bar: knobs-on step p50 within 2% of knobs-off."""
    import threading

    from llm_d_kv_cache_manager_tpu.obs.federation import FleetFederator
    from llm_d_kv_cache_manager_tpu.server.engine import Engine
    from llm_d_kv_cache_manager_tpu.server.sequence import SamplingParams

    # -- headline: 4-pod snapshot join latency ---------------------------
    def stub_fetch(seed):
        # One pod's surfaces, every presence-gated block populated so the
        # join pays its full price (legacy pods would be cheaper).
        stats = {
            "model": "bench/llama",
            "total_pages": 1024,
            "free_pages": 128 + seed,
            "staged": 2,
            "waiting": 3,
            "running": 8,
            "host": {"cached": 512, "host_pages": 2048},
            "remote": {"store_cached": 256, "store_pages": 4096},
            "prefill": {"cached_tokens": 40960 + seed, "computed_tokens": 8192},
            "drain": {"draining": False},
            "transfer": {
                "breakers": {
                    f"tcp://pod-{j}:5558": {"state": "closed"}
                    for j in range(4)
                }
            },
            "slo": {
                "burn_rates": {
                    "ttft": {"5m": 0.4, "1h": 0.2},
                    "itl": {"5m": 0.1, "1h": 0.05},
                }
            },
            "tenant_qos": {
                "slo_burn": {"premium": {"ttft": {"5m": 0.3}}},
                "cache": {
                    "stats": {
                        "premium": {"pages": 300, "share": 0.3},
                        "batch": {"pages": 596, "share": 0.6},
                    }
                },
            },
            "integrity": {
                "quarantined": 0,
                "checks_corrupt": 0,
                "bad_blocks_published": 0,
            },
            "flight": {
                "triggers": 1,
                "events_recorded": 2048,
                "dumps_written": 1,
            },
        }
        surfaces = {
            "/stats": stats,
            "/debug/mrc": {
                "enabled": True,
                "sampled": 4096,
                "cold_fraction": 0.12,
                "curve": [
                    {"pages": c, "miss_ratio": round(1.0 - c / 1100, 4)}
                    for c in range(64, 1025, 64)
                ],
            },
            "/debug/lifecycle": {
                "enabled": True,
                "transitions_recorded": 10000 + seed,
            },
            "/debug/audit": {
                "enabled": True,
                "joined": 512,
                "miss_causes": {"cold": 30, "evicted": 10, "stale_index": 2},
            },
            "/debug/staleness": None,
        }
        return lambda path: surfaces.get(path)

    fed = FleetFederator(ring=256)
    for i in range(4):
        fed.register_pod(f"bench-p{i}", fetch=stub_fetch(i))
    joins = []
    for _ in range(200):
        t0 = time.perf_counter()
        fed.scrape()
        joins.append(time.perf_counter() - t0)
    join_p50 = float(np.percentile(joins, 50))
    join_p99 = float(np.percentile(joins, 99))

    # -- step A/B: bare engine vs engine + live-state scrape hammer ------
    # The stream is repeated 3x: the instrument under test costs well
    # under 1% duty cycle, so the median needs enough steps to resolve
    # it from smoke-scale CPU jitter (a 36-step median wanders +-3%
    # run-to-run on its own — see lifecycle_overhead_ab across records).
    reqs = [tokens for _, _, tokens in workload[:24]] * 3
    total_pages = engine_cfg.block_manager.total_pages
    p50 = {}
    scrapes_on = 0
    for mode in ("off", "on"):
        eng = Engine(engine_cfg, params=params)
        stop = scraper = None
        if mode == "on":
            def live_stats():
                # What a real in-process fetch hook reads mid-step: the
                # live pool/scheduler counters, no locks the step path
                # holds.
                return {
                    "model": "bench/llama",
                    "total_pages": total_pages,
                    "free_pages": eng.block_manager.num_free,
                    "running": len(eng.scheduler.running),
                    "prefill": dict(getattr(eng, "prefill_stats", {}) or {}),
                    "drain": {"draining": False},
                }

            def live_fetch(path):
                return live_stats() if path == "/stats" else None

            live = FleetFederator(ring=256)
            for i in range(4):
                live.register_pod(f"live-p{i}", fetch=live_fetch)
            stop = threading.Event()

            def hammer():
                while not stop.is_set():
                    live.scrape()
                    stop.wait(0.1)

            scraper = threading.Thread(
                target=hammer, name="bench-fed-scraper", daemon=True
            )
            scraper.start()
        steps = []
        for tokens in reqs:
            eng.add_request(tokens, SamplingParams(max_new_tokens=max_new_tokens))
            while eng.has_work:
                t0 = time.perf_counter()
                eng.step()
                steps.append(time.perf_counter() - t0)
        if stop is not None:
            stop.set()
            scraper.join(timeout=5)
            scrapes_on = live.snapshot()["scrapes"]
        p50[mode] = float(np.median(steps))
        n_steps = len(steps)
        del eng
        gc.collect()
    return {
        "requests": len(reqs),
        "steps": n_steps,
        "join_pods": 4,
        "join_iters": len(joins),
        "join_p50_s": round(join_p50, 6),
        "join_p99_s": round(join_p99, 6),
        "scrapes_during_on": scrapes_on,
        "p50_step_off_s": round(p50["off"], 6),
        "p50_step_on_s": round(p50["on"], 6),
        "p50_on_over_off": (
            round(p50["on"] / p50["off"], 4) if p50["off"] else None
        ),
    }


def warmup(params, engine_cfg, prefix_len, suffix_len, vocab, max_new_tokens):
    """Compile every jit shape the measured runs will hit (cold prefill,
    warm suffix-only prefill, mixed batch, decode) on a scratch engine."""
    from llm_d_kv_cache_manager_tpu.server.engine import Engine
    from llm_d_kv_cache_manager_tpu.server.sequence import SamplingParams

    rng = np.random.default_rng(1234)
    eng = Engine(engine_cfg, params=params)
    prefix = rng.integers(0, vocab, prefix_len).tolist()

    def req():
        return eng.add_request(
            prefix + rng.integers(0, vocab, suffix_len).tolist(),
            SamplingParams(max_new_tokens=max_new_tokens),
        )

    req()  # cold: (chunk=full, ctx=0)
    eng.run_until_complete()
    req()  # warm: (chunk=suffix bucket, ctx=max)
    eng.run_until_complete()
    cold = rng.integers(0, vocab, prefix_len + suffix_len).tolist()
    eng.add_request(cold, SamplingParams(max_new_tokens=max_new_tokens))
    req()  # mixed cold+warm batch: (chunk=full, ctx=max)
    eng.run_until_complete()


def main() -> int:
    import jax
    import jax.numpy as jnp

    from llm_d_kv_cache_manager_tpu.models import llama
    from llm_d_kv_cache_manager_tpu.models.llama import LlamaConfig
    from llm_d_kv_cache_manager_tpu.server.block_manager import BlockManagerConfig
    from llm_d_kv_cache_manager_tpu.server.engine import EngineConfig
    from llm_d_kv_cache_manager_tpu.server.scheduler import SchedulerConfig
    from llm_d_kv_cache_manager_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    # Smoke mode (tiny model, Pallas interpreter, CPU) only when asked
    # for: a measurement run that finds no chip fails instead of timing
    # the interpreter under the benchmark's name.
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    platform = jax.devices()[0].platform
    if not smoke and platform != "tpu":
        raise SystemExit(
            f"bench.py: no TPU (jax.devices()[0].platform={platform!r}). "
            "The benchmark measures the chip; BENCH_SMOKE=1 asks for the "
            "tiny CPU smoke explicitly."
        )
    enable_compile_cache()
    quantize = None
    bench_model = os.environ.get("BENCH_MODEL", "1p4b")
    assert bench_model in ("1p4b", "8b-int8"), bench_model
    if bench_model == "8b-int8" and smoke:
        raise SystemExit(
            "BENCH_MODEL=8b-int8 needs the TPU backend (smoke would "
            "run the tiny config under the 8B label)"
        )

    if smoke:
        model_label = "tiny"
        model_cfg = llama.TINY_LLAMA
        n_pods, n_groups, reqs_per_group = 2, 4, 3
        prefix_len, suffix_len, max_new = 64, 16, 4
        total_pages, page = 256, 16
        decode_burst = 2
        interpret = True
    elif bench_model == "8b-int8":
        model_label = bench_model
        # North-star scale: the REAL Llama-3-8B architecture, int8 weights
        # (one shared copy, ~8.3 GB) + 2 pods' KV pools on one chip.
        model_cfg = llama.LLAMA_3_8B
        quantize = "int8"
        n_pods, n_groups, reqs_per_group = 2, 8, 5
        prefix_len, suffix_len, max_new = 2048, 48, 16
        total_pages, page = 1024, 16
        decode_burst = 8
        interpret = False
    else:
        model_label = bench_model  # "1p4b"
        # Llama-3-8B-family architecture scaled (1.4B) so a 4-pod fleet
        # (one weight copy + 4 KV pools) fits one v5e chip while cold
        # prefills stay compute-bound — the analogue of the reference's
        # 8k-prefix/70B capacity runs.
        model_cfg = LlamaConfig(
            vocab_size=32_000,
            hidden_size=3072,
            intermediate_size=8192,
            n_layers=12,
            n_heads=24,
            n_kv_heads=8,
            rope_scaling=llama.LLAMA_3_8B.rope_scaling,
            dtype=jnp.bfloat16,
        )
        n_pods, n_groups, reqs_per_group = 4, 32, 8
        prefix_len, suffix_len, max_new = 4096, 48, 16
        # Pool sized so a precise pod's share of prefixes (~8 groups ×
        # 257 pages) stays resident while a round-robin pod (which sees
        # all 32 prefixes) thrashes its prefix cache — the regime of the
        # reference's capacity benchmarks.
        total_pages, page = 2560, 16
        decode_burst = 8
        interpret = False

    host_pages = int(os.environ.get("BENCH_HOST_PAGES", "0"))
    total_pages = int(os.environ.get("BENCH_TOTAL_PAGES", total_pages))
    n_groups = int(os.environ.get("BENCH_GROUPS", n_groups))
    reqs_per_group = int(os.environ.get("BENCH_REQS_PER_GROUP", reqs_per_group))
    prefix_len = int(os.environ.get("BENCH_PREFIX_LEN", prefix_len))
    policies = tuple(
        os.environ.get("BENCH_POLICIES", ",".join(ALL_POLICIES)).split(",")
    )
    assert all(p in RUNNABLE_POLICIES for p in policies), policies

    max_len = prefix_len + suffix_len + max_new + page
    chunked = int(os.environ.get("BENCH_CHUNKED_PREFILL_TOKENS", 0))
    # Host-tier arm knobs (ISSUE 6): paged-KV quantization on spill, the
    # ahead-of-scheduler prefetch stage, and tier admission policy. They
    # bind wherever a config carries host_pages > 0 (the main pass with
    # BENCH_HOST_PAGES, and the pressure pass's precise_host arm).
    kv_quant = os.environ.get("BENCH_KV_QUANT", "int8") or None
    host_prefetch = os.environ.get("BENCH_HOST_PREFETCH", "1") == "1"
    host_tier_policy = os.environ.get("BENCH_HOST_TIER_POLICY", "always")
    # Decode fast path (ISSUE 7): device-resident last tokens across steps
    # + async D2H of sampled ids, on EVERY arm's engines so the policy
    # comparison stays apples-to-apples.
    decode_fastpath = os.environ.get("BENCH_DECODE_FASTPATH", "0") == "1"
    spec_mode = os.environ.get("BENCH_SPEC_DECODE", "") or None
    engine_cfg = EngineConfig(
        model=model_cfg,
        block_manager=BlockManagerConfig(
            total_pages=total_pages, page_size=page, host_pages=host_pages
        ),
        kv_quant=kv_quant if host_pages > 0 else None,
        host_prefetch=host_prefetch and host_pages > 0,
        host_tier_policy=host_tier_policy if host_pages > 0 else "auto",
        scheduler=SchedulerConfig(
            max_prefill_batch=4,
            max_prefill_tokens=8192,
            chunked_prefill_tokens=chunked if chunked > 0 else None,
        ),
        max_model_len=max_len,
        decode_batch_size=8,
        decode_steps_per_iter=decode_burst,
        decode_pipeline=decode_fastpath,
        decode_fused_sampling=decode_fastpath,
        prefill_bucket=64,
        # Pin warm prefills AND decode tables to a single width → one
        # compiled shape each. Mid-run XLA compiles (~30-60s on this model)
        # otherwise land in whichever pod's virtual clock hits a fresh
        # decode width first, blowing up its tail latencies.
        prefill_ctx_bucket=-(-max_len // page),
        decode_pages_bucket=-(-max_len // page),
        interpret=interpret,
    )

    params = llama.init_params(jax.random.PRNGKey(0), model_cfg, quantize=quantize)
    jax.block_until_ready(params)

    warmup(params, engine_cfg, prefix_len, suffix_len, model_cfg.vocab_size, max_new)
    gc.collect()  # scratch engine's KV pool must be gone before the fleet

    # Calibrate the arrival rate off the measured cold-request service time
    # so the middle of the QPS ramp saturates round-robin (its regime in
    # the reference benchmarks: random/RR explodes to ~85 s TTFT while
    # precise stays sub-second) without hand-tuned absolute QPS.
    from llm_d_kv_cache_manager_tpu.server.engine import Engine
    from llm_d_kv_cache_manager_tpu.server.sequence import SamplingParams

    cal_rng = np.random.default_rng(7)
    cal_eng = Engine(engine_cfg, params=params)
    batch_w = engine_cfg.scheduler.max_prefill_batch
    t0 = time.perf_counter()
    for _ in range(batch_w):
        cal_eng.add_request(
            cal_rng.integers(0, model_cfg.vocab_size, prefix_len + suffix_len).tolist(),
            SamplingParams(max_new_tokens=max_new),
        )
    cal_eng.run_until_complete()
    t_cold = (time.perf_counter() - t0) / batch_w  # per-request, batched cold
    del cal_eng  # release its KV pool before building the fleet
    gc.collect()
    qps_mid = 1.4 * n_pods / max(t_cold, 1e-4)
    scales = [
        float(s)
        for s in os.environ.get("BENCH_QPS_SCALES", "0.7,1.0,1.4").split(",")
    ]
    qps_ramp = [qps_mid * s for s in scales]

    rng = np.random.default_rng(42)
    workload = build_workload(
        rng, n_groups, reqs_per_group, prefix_len, suffix_len,
        model_cfg.vocab_size, qps_ramp,
    )

    results = {}
    for policy in policies:
        results[policy] = run_policy(
            policy, workload, params, engine_cfg, n_pods, max_new
        )

    # Speculative-decode arm (BENCH_SPEC_DECODE=prompt_lookup): precise
    # routing with the prompt-lookup speculative path live in every pod
    # engine — graduated from dryrun-only to a measured arm with an
    # acceptance-rate column.
    if spec_mode and "precise" in policies:
        import dataclasses as _dc

        spec_cfg = _dc.replace(engine_cfg, spec_decode=spec_mode)
        results["precise_spec"] = run_policy(
            "precise", workload, params, spec_cfg, n_pods, max_new
        )

    # -- Pressure regime (the product's differentiator) -------------------
    # Under an ample pool, index-free affinity ("estimated") ties precise:
    # nothing it believes about pod caches is ever wrong. The index's
    # reason to exist is EVICTION AWARENESS, which only shows when pods
    # actually evict — the reference's own headline regime
    # (37-capacity/README.md:235-238: precise p90 0.275 s vs estimated
    # 7.5 s at capacity). Re-run rr/estimated/precise on the same workload
    # with the pool shrunk past the working set so the round record
    # carries both regimes.
    pressure_results = {}
    pressure_pages = 0
    pressure_host_pages = 0
    if os.environ.get("BENCH_PRESSURE", "1") == "1":
        # Smoke fallback is total_pages/16, not /2: the tiny workload's
        # working set is so small that a half-size pool never evicts, and
        # a pressure pass with zero evictions (hence zero spills in the
        # host arm) exercises nothing.
        default_pp = {"1p4b": 1536, "8b-int8": 640}.get(
            model_label, max(total_pages // 16, 16)
        )
        pressure_pages = int(os.environ.get("BENCH_PRESSURE_PAGES", default_pp))
        import dataclasses

        pressure_cfg = dataclasses.replace(
            engine_cfg,
            block_manager=dataclasses.replace(
                engine_cfg.block_manager, total_pages=pressure_pages
            ),
        )
        #: every pressure arm as (policy, config, remote) so the first
        #: run and the BENCH_REPEATS re-runs execute identically.
        pressure_arms: dict[str, tuple] = {}
        for policy in ("round_robin", "estimated", "precise"):
            if policy in policies:
                pressure_arms[policy] = (policy, pressure_cfg, False)
        # Host-tier + int8-KV-spill arm (ISSUE 6): precise routing under
        # the SAME shrunken HBM pool, but evictions spill (quantized) to a
        # host-DRAM tier and waiting sequences' host-cached prefixes are
        # prefetched back ahead of the scheduler — the ">=2x effective
        # pages" capacity claim, measured in the regime where routing
        # alone stopped helping (r05).
        pressure_host_pages = int(
            os.environ.get("BENCH_PRESSURE_HOST_PAGES", str(pressure_pages))
        )
        if "precise" in policies and pressure_host_pages > 0:
            host_cfg = dataclasses.replace(
                pressure_cfg,
                block_manager=dataclasses.replace(
                    pressure_cfg.block_manager,
                    host_pages=pressure_host_pages,
                ),
                kv_quant=kv_quant,
                host_prefetch=host_prefetch,
                host_tier_policy=host_tier_policy,
            )
            pressure_arms["precise_host"] = ("precise", host_cfg, False)
        # Remote-tier arm (ISSUE 13): precise routing under the SAME
        # shrunken HBM pool with NO host tier — last-copy evictions demote
        # (int8 wire) to the kvstore holder and the router pulls them
        # back, so the fleet-wide pool, not the per-pod pool, bounds the
        # working set. The regime where the host tier plateaued at the
        # single-pod ceiling (hit 0.533, r06) is exactly where this arm
        # must push the hit rate back toward the unpressured number.
        if (
            "precise" in policies
            and os.environ.get("BENCH_REMOTE_TIER", "0") == "1"
        ):
            remote_cfg = dataclasses.replace(
                pressure_cfg,
                kv_quant=kv_quant,
                remote_tier=True,
            )
            pressure_arms["precise_remote"] = ("precise", remote_cfg, True)
        # Quantized-HBM arm (ISSUE 16): precise routing under the SAME
        # HBM byte budget as the bare pressure pool, but KV_QUANT_HBM=int8
        # halves the bytes per page, so those bytes hold 2x the pages.
        # The unquantized arm's MRC forecast at the 2x capacity point
        # (mrc_predicted_hit_2x, pre-registered in BENCH_r14.json before
        # the kernel landed) is the number this arm's measured hit must
        # land within 0.05 of.
        if (
            "precise" in policies
            and os.environ.get("BENCH_KV_QUANT_HBM", "0") == "1"
        ):
            hbm_q8_cfg = dataclasses.replace(
                pressure_cfg,
                block_manager=dataclasses.replace(
                    pressure_cfg.block_manager,
                    total_pages=2 * pressure_pages,
                ),
                kv_quant_hbm="int8",
            )
            pressure_arms["precise_hbm_q8"] = ("precise", hbm_q8_cfg, False)
        for name, (policy, cfg_, rmt) in pressure_arms.items():
            # MRC estimators ride every pressure arm (ISSUE 15): the
            # forced-eviction regime is where predicted-vs-measured
            # capacity modeling is falsifiable.
            pressure_results[name] = run_policy(
                policy, workload, params, cfg_, n_pods, max_new, remote=rmt,
                mrc=True,
            )
        # Interpret-mode variance control (r09 note): on CPU smoke the
        # estimated/precise p90 race swings 0.485↔1.038 between rounds on
        # timing jitter alone. BENCH_REPEATS > 1 re-runs every pressure
        # arm except round_robin and reports MEDIAN hit-rate fields (the
        # ISSUE 13 >=0.8 acceptance number is a median, not a single-shot
        # draw) plus the estimated/precise p90 race median with spread.
        # Default 1 = the legacy single-round output, field for field.
        pressure_race_ratios = []
        pressure_hits: dict[str, list] = {
            name: [res["prefix_cache_hit_rate"]]
            for name, res in pressure_results.items()
        }
        #: per-arm MRC predicted-hit samples across repeat rounds (the
        #: validation compares MEDIANS on both sides of the claim)
        pressure_mrc: dict[str, dict[str, list]] = {
            name: {
                cap: [v]
                for cap, v in res.get("mrc", {})
                .get("predicted_hit", {})
                .items()
                if v is not None
            }
            for name, res in pressure_results.items()
        }
        #: per-arm TTFT/ITL percentile samples across the repeat rounds
        #: (ISSUE 14 satellite: the latency race fields become medians
        #: too, with a spread block — a single CPU-jitter draw stops
        #: masquerading as a latency signal)
        LAT_KEYS = (
            "p50_ttft_s", "p90_ttft_s", "p99_ttft_s",
            "p50_itl_s", "p90_itl_s", "p99_itl_s",
        )
        pressure_lat: dict[str, dict[str, list]] = {
            name: {
                k: [res[k]] for k in LAT_KEYS if res.get(k) is not None
            }
            for name, res in pressure_results.items()
        }
        repeats = int(os.environ.get("BENCH_REPEATS", "1"))

        def race_ratio(est, prec):
            return (
                est["p90_ttft_s"] / prec["p90_ttft_s"]
                if prec["p90_ttft_s"] > 0
                else None
            )

        if repeats > 1:
            if "estimated" in pressure_results and "precise" in pressure_results:
                r0 = race_ratio(
                    pressure_results["estimated"], pressure_results["precise"]
                )
                if r0 is not None:
                    pressure_race_ratios.append(r0)
            for _ in range(repeats - 1):
                round_res = {}
                for name, (policy, cfg_, rmt) in pressure_arms.items():
                    if name == "round_robin":
                        continue
                    round_res[name] = run_policy(
                        policy, workload, params, cfg_, n_pods, max_new,
                        remote=rmt, mrc=True,
                    )
                    pressure_hits[name].append(
                        round_res[name]["prefix_cache_hit_rate"]
                    )
                    for cap, v in (
                        round_res[name]
                        .get("mrc", {})
                        .get("predicted_hit", {})
                        .items()
                    ):
                        if v is not None:
                            pressure_mrc[name].setdefault(cap, []).append(v)
                    for k in LAT_KEYS:
                        if round_res[name].get(k) is not None:
                            pressure_lat[name].setdefault(k, []).append(
                                round_res[name][k]
                            )
                if "estimated" in round_res and "precise" in round_res:
                    r = race_ratio(round_res["estimated"], round_res["precise"])
                    if r is not None:
                        pressure_race_ratios.append(r)

    # -- Lifecycle/flight overhead A/B (ISSUE 15) -------------------------
    # Same engine, same stream, instruments on vs off: the observability
    # plane's acceptance includes NOT taxing the hot path (knobs-on step
    # p50 within 2% of knobs-off).
    overhead_ab = None
    if os.environ.get("BENCH_LIFECYCLE_AB", "1") == "1":
        overhead_ab = lifecycle_overhead_ab(
            params, engine_cfg, workload, max_new
        )

    # -- Disaggregated prefill/decode arm (ISSUE 9) -----------------------
    # Same workload, same total pod count, but the fleet is split into a
    # prefill tier (ingest at full batch width, stop at first token) and a
    # decode tier (pull the chain, stream tokens). The comparison against
    # the mixed `precise` fleet is the isolation headline: decode-tier ITL
    # with ingest REMOVED from decode engines vs merely chunked/batched in.
    disagg_result = None
    n_disagg_prefill = 0
    if os.environ.get("BENCH_DISAGG", "0") == "1":
        n_disagg_prefill = int(
            os.environ.get(
                "BENCH_DISAGG_PREFILL_PODS", str(max(n_pods // 2, 1))
            )
        )
        n_disagg_prefill = min(max(n_disagg_prefill, 1), n_pods - 1)
        disagg_result = run_disagg(
            workload, params, engine_cfg,
            n_disagg_prefill, n_pods - n_disagg_prefill, max_new,
            link_gbps=float(os.environ.get("BENCH_TRANSFER_GBPS", "10")),
        )

    # -- Workload-generator family + predicted-TTFT arm (ISSUE 14) --------
    # Four traffic shapes beyond the steady shared-prefix ramp, each run
    # under round_robin / precise / predicted. The burst and ramp arms
    # are the acceptance regime: pile-on traffic where score-max queues
    # behind the warm pod and predicted-TTFT routing must win BOTH tails
    # while holding hit-rate parity with precise.
    family_results = None
    family_spreads = None
    fam_repeats = int(os.environ.get("BENCH_REPEATS", "1"))
    if os.environ.get("BENCH_WORKLOAD_FAMILY", "1") == "1":
        import statistics as _stats

        fam_groups = n_groups if smoke else max(n_groups // 2, 2)
        # ~48 requests per smoke arm: enough for queues to form in the
        # bursts and for p99 to mean something, small enough that the
        # 4-arm x 3-policy grid stays a smoke.
        fam_reqs = (
            max(-(-48 // fam_groups), 2)
            if smoke
            else max(reqs_per_group // 2, 2)
        )
        # A 2-pod fleet makes balance-vs-warmth nearly zero-sum; the
        # family judges routing POLICY separation, which needs enough
        # pods for round_robin to scatter prefixes and precise to pile
        # on. Smoke engines are tiny, so widen the fleet there.
        fam_pods = max(n_pods, 4) if smoke else n_pods
        fam_qps = qps_mid * fam_pods / n_pods
        fam_rng = np.random.default_rng(1412)
        fam_workloads = {
            # Square-wave bursts over a quiet baseline: the thundering-
            # herd regime where warmth-first routing pays more in queue
            # time than it saves in prefill.
            "burst": build_workload(
                fam_rng, fam_groups, fam_reqs, prefix_len, suffix_len,
                model_cfg.vocab_size,
                [fam_qps * s for s in (0.7, 5.0, 0.7, 5.0, 0.7)],
            ),
            # Diurnal rise-and-fall.
            "ramp": build_workload(
                fam_rng, fam_groups, fam_reqs, prefix_len, suffix_len,
                model_cfg.vocab_size,
                [fam_qps * s for s in (0.4, 0.9, 1.4, 0.9, 0.4)],
            ),
            # Multi-turn sessions: turn k+1 extends turn k's prefix.
            "session": build_session_workload(
                fam_rng,
                n_sessions=max(fam_groups * fam_reqs // 4, 2),
                turns=4,
                prefix_len=prefix_len,
                suffix_len=suffix_len,
                vocab=model_cfg.vocab_size,
                qps=fam_qps,
            ),
            # Agent swarm: waves of one deep shared prefix.
            "swarm": build_swarm_workload(
                fam_rng,
                n_agents=max(fam_groups, 4),
                waves=max(fam_reqs, 2),
                prefix_len=prefix_len,
                suffix_len=suffix_len,
                vocab=model_cfg.vocab_size,
                qps=fam_qps,
            ),
        }
        fam_lat_keys = (
            "p50_ttft_s", "p90_ttft_s", "p99_ttft_s",
            "p50_itl_s", "p90_itl_s", "p99_itl_s",
            "prefix_cache_hit_rate",
        )
        family_results = {}
        family_spreads = {}
        for wname, wl in fam_workloads.items():
            per_pol = {}
            spread_pol = {}
            for pol in ("round_robin", "precise", "predicted"):
                # MEDIANS are what the acceptance is judged on, so the
                # repeat budget goes to the acceptance arms; the color
                # arms (session, swarm) run single-shot.
                n_rounds = (
                    fam_repeats if wname in ("burst", "ramp") else 1
                )
                rounds = [
                    run_policy(pol, wl, params, engine_cfg, fam_pods, max_new)
                    for _ in range(n_rounds)
                ]
                # MEDIANS over the repeat rounds for the percentile
                # fields (the ISSUE 14 acceptance comparison must not be
                # a single draw); the rest of the detail (audit columns,
                # hit accounting) is the last round's.
                res = dict(rounds[-1])
                spread = {}
                for k in fam_lat_keys:
                    vals = [r[k] for r in rounds if r.get(k) is not None]
                    if vals:
                        res[k] = float(_stats.median(vals))
                        if len(vals) > 1:
                            spread[k] = {
                                "rounds": len(vals),
                                "min": round(min(vals), 4),
                                "max": round(max(vals), 4),
                            }
                per_pol[pol] = res
                if spread:
                    spread_pol[pol] = spread
            family_results[wname] = per_pol
            if spread_pol:
                family_spreads[wname] = spread_pol

    # -- Fleet controller arm (ISSUE 17): pod count in the loop ----------
    # The family re-judged as an AUTOSCALING problem: the same four
    # traffic shapes served twice on identical capacity-constrained
    # engines — once by a fleet pinned at the burst peak (what a planner
    # provisions statically), once starting at one pod under the product
    # FleetController (scale-up on burn x MRC headroom with warm-set
    # revival, scale-down by live migration). The verdict column is
    # pod-seconds at comparable tail latency.
    fleet_detail = None
    if (
        os.environ.get("BENCH_FLEET", "1") == "1"
        and family_results is not None
    ):
        fleet_detail = {}
        # The family runs at fam_qps (rates scaled UP by fam_pods/n_pods
        # so a pinned fam_pods fleet saturates — right for comparing
        # routing policies at fixed width, wrong for autoscaling, where
        # the premise is a quiet baseline ONE pod can carry and bursts
        # only the peak fleet can). Dilate arrivals back to the n_pods-
        # calibrated rate — identical request mix and shape, segment
        # durations long relative to the reconcile cadence (the real-
        # world analogue: minutes-long traffic shifts vs a seconds-scale
        # reconcile loop). Both arms see the same schedule.
        dil = fam_pods / n_pods

        def fleet_med(rolls):
            # Per-metric MEDIANS over the BENCH_REPEATS rolls (CPU-smoke
            # wall-clock jitter between identical runs is large; a
            # single draw can eat a 1 s stall in one segment). The last
            # roll's full dict carries the non-judged color (actions,
            # pulls, revived counts); seg tails median element-wise.
            out = dict(rolls[-1])
            for k in (
                "p50_ttft_s", "p90_ttft_s", "p99_ttft_s", "makespan_s",
                "pod_seconds", "prefix_cache_hit_rate", "migration_wall_s",
            ):
                out[k] = round(float(np.median([r[k] for r in rolls])), 4)
            out["migrated"] = int(np.median([r["migrated"] for r in rolls]))
            segs = [r["seg_p99_ttft_s"] for r in rolls]
            out["seg_p99_ttft_s"] = [
                round(float(np.median([s[j] for s in segs])), 4)
                for j in range(len(segs[0]))
            ]
            out["peak_pods"] = max(r["peak_pods"] for r in rolls)
            return out

        for wname, wl in fam_workloads.items():
            wl = [(t * dil, seg, toks) for t, seg, toks in wl]
            static = fleet_med(
                [
                    run_fleet_arm(
                        wl, params, engine_cfg, fam_pods, max_new,
                        dynamic=False,
                    )
                    for _ in range(fam_repeats)
                ]
            )
            dyn = fleet_med(
                [
                    run_fleet_arm(
                        wl, params, engine_cfg, fam_pods, max_new,
                        dynamic=True,
                    )
                    for _ in range(fam_repeats)
                ]
            )
            fleet_detail[wname] = {
                "static_peak": static,
                "controller": dyn,
                "pod_seconds_saved_pct": (
                    round(
                        100.0
                        * (static["pod_seconds"] - dyn["pod_seconds"])
                        / static["pod_seconds"],
                        2,
                    )
                    if static["pod_seconds"]
                    else None
                ),
            }
        # Scale-DOWN drill (the acceptance's "well under DRAIN_TIMEOUT_S,
        # measured in the bench"): start OVER-provisioned (all pods up)
        # with a roomy pool (one pod holds the whole working set, so the
        # aggregate MRC is flat at reduced capacity — `idle_mrc_flat` is
        # the correct call) on a SHORT quiet workload whose decode tails
        # outlive the arrivals. Once traffic ends the controller sheds
        # pods, LIVE-MIGRATING the victims' in-flight decodes;
        # migration_wall_s is the measured freeze/export/import + link
        # time where a drain-based removal waits out DRAIN_TIMEOUT_S
        # (30 s default) per pod. Deliberately NOT a burst arm: with a
        # flat curve the decision table holds on burn (burning_mrc_flat
        # — capacity is not the bottleneck), so bursts would judge the
        # routing regime, not the scale-down path under test here.
        drill_wl = [
            (t * dil, seg, toks) for t, seg, toks in fam_workloads["burst"]
        ][: max(2 * fam_pods, 8)]
        fleet_detail["scaledown_drill"] = fleet_med(
            [
                run_fleet_arm(
                    drill_wl, params, engine_cfg, fam_pods,
                    max(max_new, 32), dynamic=True,
                    start_pods=fam_pods, roomy_pool=True,
                )
                for _ in range(fam_repeats)
            ]
        )

    # -- Tenant QoS arm (ISSUE 18): two classes on one pod ---------------
    # The noisy-neighbor regime the feature exists for: a steady premium
    # trickle over a SMALL hot-prefix set, plus a background tenant
    # running the PR 13 square-wave burst shape over a wide churny
    # prefix set, both against ONE capacity-constrained pod. Three runs:
    # premium alone (the unloaded reference), both classes with the knob
    # off (the background burst wrecks premium's tail and evicts its
    # warm set), and both classes under TENANT_QOS (admission budgets
    # shed background at the door, priority preemption keeps premium's
    # prefill first in line, cache_share keeps its warm set resident).
    tenant_qos_detail = None
    if os.environ.get("BENCH_TENANT_QOS", "0") == "1":
        import dataclasses as _dc

        tq_rng = np.random.default_rng(1812)
        tq_prem_groups = max(n_groups // 4, 2)
        tq_bg_groups = max(n_groups, 4)
        tq_reqs = max(reqs_per_group * 2, 6)
        prem_wl = build_workload(
            tq_rng, tq_prem_groups, tq_reqs, prefix_len, suffix_len,
            model_cfg.vocab_size, [qps_mid * 0.5] * 5,
        )
        bg_wl = build_workload(
            tq_rng, tq_bg_groups, tq_reqs, prefix_len, suffix_len,
            model_cfg.vocab_size,
            [qps_mid * s for s in (0.7, 5.0, 0.7, 5.0, 0.7)],
        )
        merged = sorted(
            [(t, seg, toks, "premium") for t, seg, toks in prem_wl]
            + [(t, seg, toks, "batch") for t, seg, toks in bg_wl],
            key=lambda r: r[0],
        )
        tq_wl = [(t, seg, toks) for t, seg, toks, _name in merged]
        tq_tenants = [name for _t, _seg, _toks, name in merged]
        # Pool sized to hold premium's warm prefix set plus a few active
        # sequences but NOT the background churn — the regime where
        # cache_share has something to protect. (A pool that fits both
        # working sets shows nothing; the main pass already covers it.)
        prefix_pages = -(-prefix_len // page)
        seq_pages = -(-(prefix_len + suffix_len + max_new + 1) // page)
        tq_pages = int(
            os.environ.get(
                "BENCH_TENANT_PAGES",
                str(tq_prem_groups * prefix_pages + 6 * seq_pages),
            )
        )
        tq_cfg = _dc.replace(
            engine_cfg,
            block_manager=_dc.replace(
                engine_cfg.block_manager, total_pages=tq_pages
            ),
        )
        tq_spec = os.environ.get(
            "BENCH_TENANT_QOS_SPEC",
            "premium:prio=0,weight=4;"
            "batch:prio=1,max_waiting=6,cache_share=0.3",
        )
        prem_only = [r for r, t in zip(tq_wl, tq_tenants) if t == "premium"]
        tenant_qos_detail = {
            "total_pages": tq_pages,
            "qos_spec": tq_spec,
            "n_premium": len(prem_only),
            "n_background": len(tq_wl) - len(prem_only),
            "unloaded_premium": run_tenant_qos_arm(
                prem_only, lambda _i: "premium", params, tq_cfg, max_new
            ),
            "knob_off": run_tenant_qos_arm(
                tq_wl, lambda i: tq_tenants[i], params, tq_cfg, max_new
            ),
            "knob_on": run_tenant_qos_arm(
                tq_wl, lambda i: tq_tenants[i], params, tq_cfg, max_new,
                qos_spec=tq_spec,
            ),
        }

    # -- KV integrity arm (ISSUE 19): corruption drill + overhead A/B ----
    # Three runs of one spill-heavy workload on one pod: knob off (the
    # baseline greedy outputs), KV_INTEGRITY on clean (what the digests
    # cost when nothing is wrong — the knob's price tag), and KV_INTEGRITY
    # on with byte flips injected into spilled host pages (the drill:
    # every flip detected + quarantined before any token, recovery by
    # cold recompute to EXACT output parity with the baseline).
    kv_integrity_detail = None
    if os.environ.get("BENCH_KV_INTEGRITY", "0") == "1":
        import dataclasses as _dc

        ki_rng = np.random.default_rng(1907)
        ki_groups = max(n_groups // 2, 4)
        ki_wl = build_workload(
            ki_rng, ki_groups, max(reqs_per_group, 3), prefix_len,
            suffix_len, model_cfg.vocab_size, [qps_mid] * 3,
        )
        prefix_pages = -(-prefix_len // page)
        seq_pages = -(-(prefix_len + suffix_len + max_new + 1) // page)
        # Pool holds ~2 active sequences; the host tier holds the whole
        # prefix working set with slack — every revisit restores from
        # host, so the verify-on-transition path carries the run.
        ki_pages = int(
            os.environ.get(
                "BENCH_KV_INTEGRITY_PAGES", str(2 * seq_pages + 2)
            )
        )
        ki_host = ki_groups * (prefix_pages + seq_pages) * 2
        ki_flips = int(os.environ.get("BENCH_KV_INTEGRITY_FLIPS", "4"))

        def ki_cfg(knob):
            return _dc.replace(
                engine_cfg,
                kv_integrity=knob,
                host_tier_policy="always",
                block_manager=_dc.replace(
                    engine_cfg.block_manager,
                    total_pages=ki_pages,
                    host_pages=ki_host,
                ),
            )

        # Throwaway prelude: a tiny knob-off pass (with one revisit, so
        # the spill→restore path runs) absorbs the process-level
        # one-time costs of this pool shape — trace/dispatch of the
        # cold-prefill, warm-prefill, and restore paths — which would
        # otherwise land entirely in the FIRST timed run and skew the
        # overhead A/B.
        run_kv_integrity_arm(
            ki_wl[:3] + ki_wl[:1], params, ki_cfg(False), max_new
        )
        ki_off, ki_off_out = run_kv_integrity_arm(
            ki_wl, params, ki_cfg(False), max_new
        )
        ki_clean, ki_clean_out = run_kv_integrity_arm(
            ki_wl, params, ki_cfg(True), max_new
        )
        ki_drill, ki_drill_out = run_kv_integrity_arm(
            ki_wl, params, ki_cfg(True), max_new,
            flips=ki_flips, flip_seed=1907,
        )
        kv_integrity_detail = {
            "total_pages": ki_pages,
            "host_pages": ki_host,
            "n_requests": len(ki_wl),
            "off": ki_off,
            "on_clean": ki_clean,
            "on_drill": ki_drill,
            # The zero-corrupted-tokens bars: clean knob-on must be
            # bit-identical to knob-off, and the drill — with every
            # injected flip detected and recomputed — must be too.
            "clean_parity_ok": bool(ki_clean_out == ki_off_out),
            "drill_parity_ok": bool(ki_drill_out == ki_off_out),
            "overhead_makespan_x": (
                round(ki_clean["makespan_s"] / ki_off["makespan_s"], 3)
                if ki_off["makespan_s"]
                else None
            ),
            # Median per-request latency is the sturdier overhead stat at
            # smoke sizes — makespan is a sum of ~ms steps and CPU jitter
            # swamps a crc32's worth of signal.
            "overhead_p50_x": (
                round(ki_clean["p50_request_s"] / ki_off["p50_request_s"], 3)
                if ki_off["p50_request_s"]
                else None
            ),
            "drill_over_clean_x": (
                round(ki_drill["makespan_s"] / ki_clean["makespan_s"], 3)
                if ki_clean["makespan_s"]
                else None
            ),
        }

    # -- Fleet-federation arm (ISSUE 20): scrape/join overhead A/B --------
    # Headline: 4-pod FleetFederator.scrape() join latency against fully
    # loaded in-process payloads. A/B: engine step p50 with a ~10 Hz
    # background scraper reading LIVE engine state vs the bare engine —
    # the observation plane must not tax the hot path (<= 2%).
    obs_fed_detail = None
    if os.environ.get("BENCH_OBS_FED", "0") == "1":
        obs_fed_detail = obs_fed_overhead_ab(
            params, engine_cfg, workload, max_new
        )

    # Headline metrics are precise-vs-round_robin by definition: when a
    # BENCH_POLICIES subset omits either, the corresponding fields are
    # null rather than silently reporting another policy's numbers.
    precise = results.get("precise")
    rr = results.get("round_robin")
    reduction = None
    if precise is not None and rr is not None and rr["p50_ttft_s"] > 0:
        reduction = (
            100.0
            * (rr["p50_ttft_s"] - precise["p50_ttft_s"])
            / rr["p50_ttft_s"]
        )

    detail = {
        "backend": jax.default_backend(),
        "smoke": smoke,
        "model": model_label,  # the config branch actually taken
        "quantize": quantize,
        "n_pods": n_pods,
        "n_groups": n_groups,
        "n_requests": len(workload),
        "prefix_len": prefix_len,
        "host_pages": host_pages,
        "total_pages": total_pages,
        "chunked_prefill_tokens": chunked if chunked > 0 else None,
        "decode_fastpath": decode_fastpath,
        "spec_decode": spec_mode,
        "step_phases": STEP_PHASES,
        "transfer": os.environ.get("BENCH_TRANSFER", "0") == "1",
        "remote_tier": os.environ.get("BENCH_REMOTE_TIER", "0") == "1",
        "event_lag_ms": float(os.environ.get("BENCH_EVENT_LAG_MS", "2")),
        "qps_ramp": [round(q, 2) for q in qps_ramp],
        # Host-arm knobs are reported only when a host-tier arm actually
        # ran; otherwise a default run would record knob defaults for
        # arms that never executed.
        "kv_quant": kv_quant if (host_pages or pressure_host_pages) else None,
        "host_prefetch": (
            host_prefetch if (host_pages or pressure_host_pages) else None
        ),
        "host_tier_policy": (
            host_tier_policy if (host_pages or pressure_host_pages) else None
        ),
        "results": results,
        "pressure_total_pages": pressure_pages,
        "pressure_host_pages": pressure_host_pages,
        "pressure_results": pressure_results,
        "lifecycle_overhead_ab": overhead_ab,
        "disagg": disagg_result,
        "workload_family": family_results,
        "workload_family_spread": family_spreads,
        "fleet_controller": fleet_detail,
        "tenant_qos": tenant_qos_detail,
        "kv_integrity": kv_integrity_detail,
        "obs_fed": obs_fed_detail,
    }
    print(json.dumps(detail), file=sys.stderr)

    pressure = None
    if pressure_results:
        import statistics

        pressure = {"total_pages": pressure_pages}
        for pol, res in pressure_results.items():
            # MEDIANS over the BENCH_REPEATS rounds for every TTFT/ITL
            # percentile field, not just the hit rate (single round =
            # the legacy single-shot field, value for value).
            lat = pressure_lat.get(pol, {})

            def med(key, fallback=None):
                vals = lat.get(key) or (
                    [res[key]] if res.get(key) is not None else []
                )
                return round(statistics.median(vals), 4) if vals else fallback

            pressure[f"p50_{pol}"] = med("p50_ttft_s")
            pressure[f"p90_{pol}"] = med("p90_ttft_s")
            pressure[f"p99_{pol}"] = med("p99_ttft_s")
            pressure[f"itl_p90_{pol}"] = med("p90_itl_s")
            hits = pressure_hits.get(pol) or [res["prefix_cache_hit_rate"]]
            pressure[f"hit_{pol}"] = round(statistics.median(hits), 4)
        if any(len(h) > 1 for h in pressure_hits.values()):
            pressure["hit_spread"] = {
                pol: {
                    "rounds": len(h),
                    "min": round(min(h), 4),
                    "max": round(max(h), 4),
                }
                for pol, h in pressure_hits.items()
                if len(h) > 1
            }
        if any(
            len(vals) > 1
            for lat in pressure_lat.values()
            for vals in lat.values()
        ):
            pressure["latency_spread"] = {
                pol: {
                    k: {
                        "rounds": len(vals),
                        "min": round(min(vals), 4),
                        "max": round(max(vals), 4),
                    }
                    for k, vals in lat.items()
                    if len(vals) > 1
                }
                for pol, lat in pressure_lat.items()
                if any(len(v) > 1 for v in lat.values())
            }
        pe, pp = (
            pressure_results.get("estimated"),
            pressure_results.get("precise"),
        )
        if pe and pp and pp["p90_ttft_s"] > 0:
            # The eviction-awareness headline: how much worse the
            # index-free router's tail is once pods evict. With
            # BENCH_REPEATS > 1 the reported ratio is the MEDIAN over the
            # repeated races and a spread field carries the min/max, so
            # CPU-jitter rounds stop masquerading as signal.
            if len(pressure_race_ratios) > 1:
                import statistics

                pressure["p90_estimated_over_precise"] = round(
                    statistics.median(pressure_race_ratios), 3
                )
                pressure["p90_estimated_over_precise_spread"] = {
                    "rounds": len(pressure_race_ratios),
                    "min": round(min(pressure_race_ratios), 3),
                    "max": round(max(pressure_race_ratios), 3),
                }
            else:
                pressure["p90_estimated_over_precise"] = round(
                    pe["p90_ttft_s"] / pp["p90_ttft_s"], 3
                )
        if pp and "audit" in pp:
            # The forced-eviction regime's audit columns: pool pressure
            # makes pods evict between scoring and serving, so this is
            # where the miss attribution proves itself.
            pressure["audit_precise"] = pp["audit"]
            pressure["staleness_precise"] = pp.get("staleness")
        ph = pressure_results.get("precise_host")
        if ph is not None:
            # The capacity headline (ISSUE 6): host tier + int8 KV spill
            # under pressure, vs the UNPRESSURED precise arm (target:
            # p50 within 2x, hit rate back above 0.8).
            pressure["host_pages"] = pressure_host_pages
            pressure["kv_quant"] = kv_quant
            if precise is not None and precise["p50_ttft_s"] > 0:
                pressure["p50_host_over_unpressured_precise"] = round(
                    ph["p50_ttft_s"] / precise["p50_ttft_s"], 3
                )
        # MRC validation (ISSUE 15 acceptance): the reuse-distance curve's
        # predicted hit rate at each TIER arm's configured cumulative
        # capacity must sit within 0.05 of the measured pressure-arm hit
        # rate — medians over the repeat rounds on both sides. The
        # bare-HBM point of the same curve is recorded as an honest
        # diagnostic, NOT an acceptance row: under churn the pool is not
        # a clean LRU (ref-pinned active pages + decode growth shrink the
        # effective capacity below the page count), so the curve
        # overpredicts there — the TIER-sizing delta (what host/remote
        # capacity adds on top) is exactly where the model is exact.
        def _mrc_point(arm, capname):
            res_arm = pressure_results.get(arm)
            if res_arm is None or "mrc" not in res_arm:
                return None
            preds = pressure_mrc.get(arm, {}).get(capname) or []
            measured = pressure.get(f"hit_{arm}")
            if not preds or measured is None:
                return None
            predicted = round(statistics.median(preds), 4)
            return {
                "capacity_blocks": res_arm["mrc"]["capacities"][capname],
                "predicted_hit": predicted,
                "measured_hit": measured,
                "abs_error": round(abs(predicted - measured), 4),
                "ok": bool(abs(predicted - measured) <= 0.05),
                "cold_fraction": res_arm["mrc"]["cold_fraction"],
            }

        mrc_val = {}
        for arm, capname in (
            ("precise_host", "hbm_host"),
            ("precise_remote", "hbm_fleet_share"),
        ):
            point = _mrc_point(arm, capname)
            if point is not None:
                mrc_val[arm] = point
        if mrc_val:
            pressure["mrc_validation"] = mrc_val
            hbm_point = _mrc_point("precise", "hbm")
            if hbm_point is not None:
                hbm_point.pop("ok", None)  # diagnostic, not a bar
                pressure["mrc_hbm_point"] = hbm_point
        prm = pressure_results.get("precise_remote")
        if prm is not None:
            # The fleet-pool headline (ISSUE 13): eviction-as-demotion
            # under pressure. Acceptance: median hit back >= 0.8 (vs the
            # 0.533 host-tier ceiling), pressure-arm evicted_on_pod
            # attributed misses ~ 0, and the effective-capacity number
            # (fleet tokens cached / HBM bytes) no single-pod tier holds.
            pressure["remote"] = {
                k: prm["remote"][k]
                for k in (
                    "store_pages",
                    "store_cached",
                    "demoted_blocks",
                    "demote_wire_bytes",
                    "remote_pulls",
                    "remote_pulled_blocks",
                    "fleet_cached_tokens",
                    "hbm_bytes",
                    "effective_capacity_x_hbm",
                    "tokens_per_hbm_gib",
                )
            }
            pressure["audit_precise_remote"] = prm.get("audit")
            if precise is not None and precise["p50_ttft_s"] > 0:
                pressure["p50_remote_over_unpressured_precise"] = round(
                    prm["p50_ttft_s"] / precise["p50_ttft_s"], 3
                )
        pq = pressure_results.get("precise_hbm_q8")
        if pq is not None and pp is not None:
            # The quantized-HBM headline (ISSUE 16): same HBM bytes, 2x
            # the pages. Forecast-vs-measured closes the pre-registration
            # loop (the predicted number was recorded from the bare arm's
            # curve BEFORE the kernel landed); the throughput A/B and the
            # per-phase deltas show what in-kernel dequant costs (or
            # saves — decode is DMA-bound) on the same workload.
            preds_2x = pressure_mrc.get("precise", {}).get("hbm_2x") or []
            measured = pressure.get("hit_precise_hbm_q8")
            hbm_q8 = {
                "kv_quant_hbm": "int8",
                "total_pages_2x": 2 * pressure_pages,
                "measured_hit": measured,
            }
            if preds_2x and measured is not None:
                predicted = round(statistics.median(preds_2x), 4)
                hbm_q8["mrc_predicted_hit_2x"] = predicted
                hbm_q8["abs_error"] = round(abs(predicted - measured), 4)
                hbm_q8["ok"] = bool(abs(predicted - measured) <= 0.05)
            if pp["output_tok_s_per_chip"] > 0:
                hbm_q8["tok_s_per_chip"] = {
                    "precise": round(pp["output_tok_s_per_chip"], 3),
                    "precise_hbm_q8": round(pq["output_tok_s_per_chip"], 3),
                    "ratio": round(
                        pq["output_tok_s_per_chip"]
                        / pp["output_tok_s_per_chip"],
                        3,
                    ),
                }
            if "phases" in pp and "phases" in pq:
                hbm_q8["phase_deltas"] = {
                    key: {
                        "precise_s": pp["phases"].get(key, 0),
                        "precise_hbm_q8_s": pq["phases"].get(key, 0),
                        "delta_s": round(
                            pq["phases"].get(key, 0)
                            - pp["phases"].get(key, 0),
                            4,
                        ),
                    }
                    for key in ("decode_s", "sample_s")
                }
            pressure["kv_quant_hbm"] = hbm_q8

    # Workload-family headline (ISSUE 14): per-arm p50/p99 TTFT for the
    # three policies, the burst+ramp acceptance verdicts (predicted must
    # beat BOTH comparators on both tails, medians over BENCH_REPEATS,
    # with hit parity vs precise), and the latency model's honesty
    # (median realized/predicted TTFT over the predicted arms' joins).
    fam_headline = None
    if family_results:
        import statistics as _stats

        fam_acceptance = {}
        for arm in ("burst", "ramp"):
            per = family_results.get(arm, {})
            pred, rr_, prec = (
                per.get("predicted"), per.get("round_robin"),
                per.get("precise"),
            )
            if not (pred and rr_ and prec):
                continue
            fam_acceptance[arm] = {
                "p50_ok": bool(
                    pred["p50_ttft_s"] <= rr_["p50_ttft_s"]
                    and pred["p50_ttft_s"] <= prec["p50_ttft_s"]
                ),
                "p99_ok": bool(
                    pred["p99_ttft_s"] <= rr_["p99_ttft_s"]
                    and pred["p99_ttft_s"] <= prec["p99_ttft_s"]
                ),
                "hit_parity_ok": bool(
                    pred["prefix_cache_hit_rate"]
                    >= prec["prefix_cache_hit_rate"] - 0.02
                ),
            }
        ttft_ratios = [
            per["predicted"]["audit"]["ttft_ratio_p50"]
            for per in family_results.values()
            if per.get("predicted", {}).get("audit", {}).get("ttft_ratio_p50")
            is not None
        ]
        fam_headline = {
            "repeats": fam_repeats,
            "arms": {
                wname: {
                    pol: {
                        "p50_ttft_s": round(res["p50_ttft_s"], 4),
                        "p99_ttft_s": round(res["p99_ttft_s"], 4),
                        "hit": round(res["prefix_cache_hit_rate"], 4),
                    }
                    for pol, res in per_pol.items()
                }
                for wname, per_pol in family_results.items()
            },
            "acceptance": fam_acceptance,
            "ttft_ratio_p50": (
                round(float(_stats.median(ttft_ratios)), 4)
                if ttft_ratios
                else None
            ),
        }
    print(
        json.dumps(
            {
                "metric": "p50_ttft_reduction_vs_round_robin",
                "value": round(reduction, 2) if reduction is not None else None,
                "unit": "%",
                "vs_baseline": (
                    round(reduction / 50.0, 4) if reduction is not None else None
                ),
                "req_s_per_chip": (
                    round(precise["req_s_per_chip"], 3) if precise else None
                ),
                "prefix_cache_hit_rate": (
                    round(precise["prefix_cache_hit_rate"], 4) if precise else None
                ),
                "output_tok_s_per_chip": (
                    round(precise["output_tok_s_per_chip"], 1) if precise else None
                ),
                "decode_fastpath": decode_fastpath,
                # Spec-decode arm headline: acceptance rate + throughput
                # (null unless BENCH_SPEC_DECODE ran the arm).
                "spec": (
                    {
                        "mode": spec_mode,
                        "acceptance_rate": results["precise_spec"]["spec"][
                            "acceptance_rate"
                        ],
                        "output_tok_s_per_chip": round(
                            results["precise_spec"]["output_tok_s_per_chip"], 1
                        ),
                    }
                    if "precise_spec" in results
                    else None
                ),
                # Serving-SLO latency columns (precise policy): the perf
                # trajectory tracks tails, not just medians/throughput.
                "latency": (
                    {
                        k: (round(precise[k], 4) if precise[k] is not None else None)
                        for k in (
                            "p50_ttft_s", "p90_ttft_s", "p99_ttft_s",
                            "p50_itl_s", "p90_itl_s", "p99_itl_s",
                        )
                    }
                    if precise
                    else None
                ),
                # Routing-quality audit columns (ISSUE 10; precise arm):
                # event-plane staleness percentiles + the realized share
                # of predicted warmth with attributed misses.
                "routing_audit": (
                    {
                        "staleness_p50_ms": precise["staleness"]["p50_ms"],
                        "staleness_p99_ms": precise["staleness"]["p99_ms"],
                        "realized_over_predicted": precise["audit"][
                            "realized_over_predicted"
                        ],
                        "misses": precise["audit"]["misses"],
                    }
                    if precise and "audit" in precise and "staleness" in precise
                    else None
                ),
                "pressure": pressure,
                # Lifecycle/flight overhead A/B (ISSUE 15): knobs-on
                # engine-step p50 over knobs-off (bar: within 2%).
                "lifecycle_overhead_ab": overhead_ab,
                # Disagg arm headline (null unless BENCH_DISAGG ran): the
                # decode-tier ITL isolation win over the same-size mixed
                # fleet, and the two-hop placement/handoff accounting.
                "disagg": (
                    {
                        "n_prefill": disagg_result["n_prefill"],
                        "n_decode": disagg_result["n_decode"],
                        "p90_itl_s": (
                            round(disagg_result["p90_itl_s"], 4)
                            if disagg_result["p90_itl_s"] is not None
                            else None
                        ),
                        "p50_ttft_s": round(disagg_result["p50_ttft_s"], 4),
                        "handoffs": disagg_result["handoffs"],
                        "p90_itl_mixed_over_disagg": (
                            round(
                                precise["p90_itl_s"]
                                / disagg_result["p90_itl_s"],
                                3,
                            )
                            if precise is not None
                            and precise.get("p90_itl_s")
                            and disagg_result["p90_itl_s"]
                            else None
                        ),
                    }
                    if disagg_result is not None
                    else None
                ),
                # Predicted-TTFT routing headline (ISSUE 14; null unless
                # the workload-family pass ran): per-arm tails, the
                # burst+ramp acceptance verdicts, and the latency
                # model's realized/predicted honesty median.
                "workload_family": fam_headline,
                # Fleet-controller headline (ISSUE 17; null unless the
                # BENCH_FLEET pass ran): per-shape controller-vs-static-
                # peak pod-seconds and p99 TTFT, plus the controller's
                # action log sizes — the autoscaling verdict columns.
                "fleet_controller": (
                    {
                        wname: {
                            # The scale-down drill is a single dynamic
                            # arm (no static comparator): its verdict
                            # columns are the shed/migration measurements.
                            "scale_actions": len(row.get("actions", [])),
                            "pods_shed": sum(
                                1
                                for a in row.get("actions", [])
                                if a["action"] == "scale_down"
                            ),
                            "migrated": row["migrated"],
                            "migration_wall_s": row["migration_wall_s"],
                            "p99_ttft_s": round(row["p99_ttft_s"], 4),
                            "pod_seconds": row["pod_seconds"],
                        }
                        if "static_peak" not in row
                        else {
                            "static_p99_ttft_s": round(
                                row["static_peak"]["p99_ttft_s"], 4
                            ),
                            "controller_p99_ttft_s": round(
                                row["controller"]["p99_ttft_s"], 4
                            ),
                            "static_pod_seconds": row["static_peak"][
                                "pod_seconds"
                            ],
                            "controller_pod_seconds": row["controller"][
                                "pod_seconds"
                            ],
                            "pod_seconds_saved_pct": row[
                                "pod_seconds_saved_pct"
                            ],
                            "peak_pods": row["controller"]["peak_pods"],
                            "scale_actions": len(
                                row["controller"].get("actions", [])
                            ),
                            "migrated": row["controller"]["migrated"],
                            "revived_blocks": row["controller"][
                                "revived_blocks"
                            ],
                        }
                        for wname, row in fleet_detail.items()
                    }
                    if fleet_detail
                    else None
                ),
                # Tenant-QoS headline (ISSUE 18; null unless the
                # BENCH_TENANT_QOS pass ran): premium's tail with the
                # knob off vs on vs unloaded, its hit-rate protection,
                # and the background degradation mix (429s at the door +
                # priority preemptions — never errors).
                "tenant_qos": (
                    {
                        "premium_p99_unloaded_s": tenant_qos_detail[
                            "unloaded_premium"
                        ]["tenants"]["premium"]["p99_ttft_s"],
                        "premium_p99_off_s": tenant_qos_detail["knob_off"][
                            "tenants"
                        ]["premium"]["p99_ttft_s"],
                        "premium_p99_on_s": tenant_qos_detail["knob_on"][
                            "tenants"
                        ]["premium"]["p99_ttft_s"],
                        "premium_hit_unloaded": tenant_qos_detail[
                            "unloaded_premium"
                        ]["tenants"]["premium"]["prefix_cache_hit_rate"],
                        "premium_hit_off": tenant_qos_detail["knob_off"][
                            "tenants"
                        ]["premium"]["prefix_cache_hit_rate"],
                        "premium_hit_on": tenant_qos_detail["knob_on"][
                            "tenants"
                        ]["premium"]["prefix_cache_hit_rate"],
                        "background_rejected": tenant_qos_detail["knob_on"][
                            "tenants"
                        ]["batch"]["rejected"],
                        "priority_preempted": tenant_qos_detail["knob_on"][
                            "priority_preempted"
                        ],
                    }
                    if tenant_qos_detail
                    else None
                ),
                # KV-integrity headline (ISSUE 19; null unless the
                # BENCH_KV_INTEGRITY pass ran): detection completeness
                # for the injected flips, both parity bars (zero
                # corrupted tokens), and the two makespan price tags —
                # the digests when nothing is wrong, the recovery when
                # something is.
                "kv_integrity": (
                    {
                        "injected_flips": kv_integrity_detail["on_drill"][
                            "injected_flips"
                        ],
                        "detected": kv_integrity_detail["on_drill"][
                            "integrity"
                        ]["checks_corrupt"],
                        "quarantined": kv_integrity_detail["on_drill"][
                            "integrity"
                        ]["quarantined"],
                        "clean_parity_ok": kv_integrity_detail[
                            "clean_parity_ok"
                        ],
                        "drill_parity_ok": kv_integrity_detail[
                            "drill_parity_ok"
                        ],
                        "overhead_makespan_x": kv_integrity_detail[
                            "overhead_makespan_x"
                        ],
                        "overhead_p50_x": kv_integrity_detail[
                            "overhead_p50_x"
                        ],
                        "drill_over_clean_x": kv_integrity_detail[
                            "drill_over_clean_x"
                        ],
                    }
                    if kv_integrity_detail
                    else None
                ),
                # Fleet-federation headline (ISSUE 20; null unless the
                # BENCH_OBS_FED pass ran): the 4-pod snapshot join
                # latency (p50/p99) and the step-p50 price of a live
                # federator scraping the engine at ~100 Hz mid-decode.
                "obs_fed": (
                    {
                        "join_pods": obs_fed_detail["join_pods"],
                        "join_p50_s": obs_fed_detail["join_p50_s"],
                        "join_p99_s": obs_fed_detail["join_p99_s"],
                        "scrapes_during_on": obs_fed_detail[
                            "scrapes_during_on"
                        ],
                        "step_p50_on_over_off": obs_fed_detail[
                            "p50_on_over_off"
                        ],
                    }
                    if obs_fed_detail
                    else None
                ),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
