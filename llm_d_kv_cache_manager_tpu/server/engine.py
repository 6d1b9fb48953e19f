"""The JAX paged-KV inference engine with continuous batching.

This is the in-tree TPU serving engine the BASELINE north star calls for:
the component the reference *drives externally* (vLLM pods) is a
first-class part of this framework. Per step the engine either prefills a
batch of admitted prompts (suffix-only on prefix-cache hits) or decodes one
token for every running sequence via the Pallas paged-attention kernel —
or, with ``chunked_prefill_tokens`` set, runs a MIXED step that packs a
token-budgeted batch of prefill chunks *and* all decode lanes into one
iteration (Sarathi-style stall-free ingest) — then publishes
``BlockStored``/``BlockRemoved`` events so the routing indexer tracks this
replica's cache (SURVEY §3.2 write path).

A model whose configuration has ``block_length`` > 0 generates by diffusion
over blocks, and the engine takes that path from the configuration alone:
the prefill commits the whole blocks of the prompt and samples nothing, a
running lane holds a block in progress, and a decode dispatch
(``_run_decode_block``) advances each lane by 0..``block_length`` tokens
(one dispatch ahead under the fused path's rule: the block in progress then
stays on the device between two forwards).

XLA discipline: all jitted entry points see bucketed static shapes
(prefill length rounded up to a bucket, decode batch padded to a fixed
lane count), so steady-state serving replays cached executables.
"""

from __future__ import annotations

import functools
import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kvcache.kvevents.events import Event
from ..models import llama, quant
from ..models.llama import LlamaConfig
from ..utils import get_logger
from .block_manager import AllocationError, BlockManager, BlockManagerConfig
from ..ops.sampling import pack_sampling_params, sample_tokens_packed
from .phases import NO_PHASE
from .scheduler import Scheduler, SchedulerConfig
from .sequence import (
    DEFAULT_CONFIDENCE_THRESHOLD,
    SamplingParams,
    Sequence,
    SequenceStatus,
    check_block_sampling,
)

log = get_logger("server.engine")

#: The phases of one trip round the engine loop, in the order they run: the
#: names ``Engine.phase`` takes, the ``step_stats[<phase>_s]`` keys, and the
#: ``engine.<phase>`` spans on the profiler's timeline (readers and docs
#: quote this tuple). ``*_build`` is host work in Python and numpy alone
#: (slot reservation and preemption, block tables, input arrays);
#: ``*_put`` hands the inputs to the device (queued page moves, the rng
#: split, every ``device_put``); ``*_dispatch`` is the jitted call, which
#: returns when the program is enqueued; ``*_fetch`` is the ``np.asarray``
#: of the sampled tokens — the only phases that wait for the device;
#: ``*_commit`` appends tokens, accounts pages and registers blocks.
#: ``schedule`` is deadline shed, QoS preemption, host prefetch and the
#: scheduler; ``publish`` is finish detection and the KV-event flush;
#: ``loop`` belongs to the serving loop around ``step()`` (``PodServer``):
#: everything between one step and the next while work is pending.
STEP_PHASES = (
    "schedule",
    "prefill_build", "prefill_put", "prefill_dispatch", "prefill_fetch",
    "prefill_commit",
    "decode_build", "decode_put", "decode_dispatch", "decode_fetch",
    "decode_commit",
    "publish",
    "loop",
)


def _phase_keys(name: str) -> tuple[str, ...]:
    """The ``step_stats`` keys a phase's seconds go to: its own, and the
    sums that were there before the split — ``prefill_s``/``decode_s`` =
    their five, ``sample_s`` = the two fetches."""
    keys = [f"{name}_s"]
    half, _, part = name.partition("_")
    if part:
        keys.append(f"{half}_s")
        if part == "fetch":
            keys.append("sample_s")
    return tuple(keys)


_PHASE_KEYS = {name: _phase_keys(name) for name in STEP_PHASES}

#: The parts of one admission (``BlockManager.allocate``, and what the
#: scheduler undoes of it), in the order they run: the names ``Engine.part``
#: takes, the ``step_stats["admit_<part>_s"]`` keys, and the child spans
#: ``admit.<part>`` inside the span ``admit`` (``Engine.part()``, one a call
#: of ``allocate``), which lie inside ``engine.schedule`` on the profiler's
#: timeline (readers and docs quote this tuple). ``hash`` is the prompt's
#: chain of block hashes; ``walk`` the loop over them (cached lookup,
#: host-restore decisions, the reference counts) and the
#: never-the-whole-prompt pop; ``window`` / ``state`` the cut back to what
#: the window pool / the state pool still holds, and what is taken there (a
#: model with such a pool only); ``pages`` the pop of the fresh pages,
#: evictions included; ``rollback`` what undoes an admission: ``allocate``
#: out of pages, or the scheduler's when the fresh suffix is over the step's
#: budget (that one FOLLOWS its ``admit`` span). ``admit`` less its parts
#: is the rest of ``allocate``.
ADMIT_PARTS = ("hash", "walk", "window", "state", "pages", "rollback")
#: their ``step_stats`` keys, the whole call's first
ADMIT_SECONDS = ("admit_s", *(f"admit_{name}_s" for name in ADMIT_PARTS))

#: The counts an admission adds to ``step_stats`` through ``_Part.add``:
#: calls of ``allocate``; attempts undone (out of pages, or over the step's
#: budget: so ``admit_attempts - admit_rollbacks`` stood); prompt tokens of
#: the attempts; whole blocks served from the cache; fresh pages popped;
#: cached pages that lost their hash to serve a pop; admissions whose prefill
#: was dispatched behind the burst that ends their predecessor
#: (``Engine._admit_ahead``: counted by the engine at that dispatch, so over
#: ``admit_attempts - admit_rollbacks`` it is the share admitted ahead).
ADMIT_COUNTS = (
    "admit_attempts", "admit_rollbacks", "admit_tokens",
    "admit_blocks_hit", "admit_pages", "admit_evictions", "admit_ahead",
)


class _Phase:
    """One timed phase (``Engine.phase``). While it runs its wall time goes
    to ``step_stats`` and a ``jax.profiler.TraceAnnotation`` named
    ``engine.<name>`` is open on the calling thread, carrying the step's
    number and the replica, so the span sits on the profiler's own clock
    under the device's timeline.

    Phases run one after another and never contain one another. The one
    thing that can start inside another phase is the drain of the decode
    burst in flight (``Engine._inflight``: its ``decode_fetch`` and
    ``decode_commit``), which a reservation out of pages, a QoS preemption
    or an abort forces where it stands. Such a phase SUSPENDS the one it
    starts in — the outer span ends where the inner begins, and a new span
    of the outer's name starts where the inner ends — so a reader that
    names an idle gap after the span that overlaps it most never finds an
    enclosing span winning every gap. A burst that is chained from
    suspends nothing: its fetch and commit follow the next burst's
    ``decode_dispatch``."""

    __slots__ = ("_engine", "_name", "_outer", "_t0", "_span")

    def __init__(self, engine: "Engine", name: str):
        self._engine = engine
        self._name = name

    def __enter__(self) -> None:
        engine = self._engine
        # nothing inside an admission drains the burst in flight: a phase
        # that started there would lie inside spans that cannot suspend
        assert not engine._open_parts, (
            f"phase {self._name!r} entered inside an open admit span"
        )
        self._outer = engine._open_phase
        if self._outer is not None:
            self._outer._stop()
        engine._open_phase = self
        self._start()

    def __exit__(self, *_exc) -> None:
        self._stop()
        self._engine._open_phase = self._outer
        if self._outer is not None:
            self._outer._start()

    def _start(self) -> None:
        # the clock is read outside the span on both sides, so that what
        # the span itself costs is inside the phase and not between two
        engine = self._engine
        self._t0 = time.perf_counter()
        self._span = jax.profiler.TraceAnnotation(
            f"engine.{self._name}",
            step=engine._step_count,
            replica=engine.replica,
        )
        self._span.__enter__()

    def _stop(self) -> None:
        self._span.__exit__(None, None, None)
        elapsed = time.perf_counter() - self._t0
        stats = self._engine.step_stats
        for key in _PHASE_KEYS[self._name]:
            stats[key] += elapsed


class _Part:
    """One timed part of an admission (``Engine.part``): a child span of the
    phase that is open. While it runs its wall time goes to
    ``step_stats["admit_s"]`` (no name) or ``["admit_<name>_s"]`` and a
    ``jax.profiler.TraceAnnotation`` named ``admit`` / ``admit.<name>`` is
    open on the calling thread, carrying the step's number and the replica
    as a phase's does, and what the caller adds.

    It NESTS: it suspends nothing and leaves ``Engine._open_phase`` alone,
    and its name does not start with ``engine.``, so the phases still tile
    the loop's time. ``add`` puts counts (``ADMIT_COUNTS``) into
    ``step_stats`` from where the work happened."""

    __slots__ = ("_engine", "_key", "_span", "_t0")

    def __init__(self, engine: "Engine", name: str, stats: dict):
        self._engine = engine
        self._key = f"admit_{name}_s" if name else "admit_s"  # ADMIT_SECONDS
        self._span = jax.profiler.TraceAnnotation(
            f"admit.{name}" if name else "admit",
            step=engine._step_count,
            replica=engine.replica,
            **stats,
        )

    def __enter__(self) -> "_Part":
        self._engine._open_parts += 1
        self._t0 = time.perf_counter()
        self._span.__enter__()
        return self

    def __exit__(self, *_exc) -> None:
        self._span.__exit__(None, None, None)
        engine = self._engine
        engine.step_stats[self._key] += time.perf_counter() - self._t0
        engine._open_parts -= 1

    def add(self, **counts: int) -> None:
        stats = self._engine.step_stats
        for key, n in counts.items():
            stats[key] += n


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _same_lanes(a: list, b: list) -> bool:
    """The same sequences in the same lanes (identity, not equality)."""
    return len(a) == len(b) and all(x is y for x, y in zip(a, b))


@jax.jit
def _read_pages_batch(pages: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Gather a batch of KV pages [n_layers, n, page_size, n_kv, hd]."""
    return jnp.take(pages, idx, axis=1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_pages_batch(
    pages: jnp.ndarray, idx: jnp.ndarray, data: jnp.ndarray
) -> jnp.ndarray:
    """Scatter a batch of pages into the pool (donated; padded slots carry
    an out-of-range index and are dropped)."""
    return pages.at[:, idx].set(data, mode="drop")


@dataclass
class EngineConfig:
    model: LlamaConfig = field(default_factory=lambda: llama.TINY_LLAMA)
    block_manager: BlockManagerConfig = field(default_factory=BlockManagerConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    max_model_len: int = 2048
    #: decode batch lanes (padded); also the max concurrent running seqs
    decode_batch_size: int = 8
    #: fused decode steps per engine iteration (device-resident loop with
    #: on-device sampling — one host sync per this many tokens). 1 = one
    #: token per dispatch; sampling is on-device at every setting, so no
    #: config ever round-trips logits to the host.
    decode_steps_per_iter: int = 1
    #: prefill length bucket granularity (shape-bucketing for jit caching)
    prefill_bucket: int = 64
    #: decode block-table width bucket (pages): the table is sized to the
    #: longest ACTIVE context rounded up to this, not to max_model_len —
    #: the paged-attention grid (and its per-page DMAs) then scales with
    #: real context length instead of the worst case.
    decode_pages_bucket: int = 16
    #: context block-table width bucket granularity for warm prefills; raise
    #: to the max pages/seq to pin one shape (fewer XLA recompiles)
    prefill_ctx_bucket: int = 4
    #: run Pallas kernels in interpreter mode — CPU tests and dry runs say
    #: so HERE; nothing below infers it from the backend. The kernel
    #: wrappers raise when asked for a compiled kernel off-TPU, and the
    #: engine refuses interpret=True on TPU devices.
    interpret: bool = False
    #: tensor-parallel degree over the ICI mesh. 1 = single-chip replica.
    #: Params follow the Megatron-style specs in parallel/sharding.py, KV
    #: pages shard head-parallel, and decode attention runs in shard_map;
    #: everything else is GSPMD-partitioned by XLA. Requires
    #: n_heads % tp == 0 and n_kv_heads % tp == 0.
    tp: int = 1
    #: sequence-parallel degree for PREFILL: the fresh chunk is sharded
    #: over an "sp" mesh axis and attended via ring attention with an
    #: exact paged-context merge (models/llama._sp_prefill_attention) —
    #: the long-context path for prompts whose chunk would blow a single
    #: chip's compute/activation budget. Decode stays tp-only (one token
    #: per lane has nothing to shard). Composes with tp (mesh is sp × tp);
    #: requires sp | prefill_bucket.
    sp: int = 1
    #: prefill attention implementation: "pallas" (flash kernel), "xla"
    #: (scan), or "auto" — the flash kernel, except that ``interpret``
    #: (CPU tests) and ``kv_quant_hbm`` (the kernel reads pages
    #: full-width) take the XLA scan. Never chosen from the backend; the
    #: choice is logged at construction.
    prefill_attn: str = "auto"
    #: speculative decoding: "off" or "prompt_lookup" (draft-model-free —
    #: propose the continuation of the context's own last n-gram from an
    #: earlier occurrence; accept via one verify dispatch that scores all
    #: k+1 tokens — exactly a warm prefill over [context ++ proposals]).
    #: Greedy lanes accept iff draft == argmax; temperature>0 lanes run
    #: deterministic-draft speculative SAMPLING (accept with prob
    #: P(draft), residual sample on rejection — exact for each lane's
    #: filtered distribution; ops/sampling.spec_sample).
    spec_decode: str = "off"
    #: proposed tokens per verify step (accepted 0..k, +1 corrected/bonus
    #: token always emitted — a spec step never yields fewer tokens than a
    #: normal decode step).
    spec_k: int = 4
    #: n-gram length to match for prompt-lookup proposals
    spec_ngram: int = 3
    #: cap on how far back the proposal search scans (host-side cost)
    spec_max_scan: int = 4096
    #: fused speculative rounds per dispatch: propose → verify → accept →
    #: advance runs ``spec_rounds`` times ON DEVICE per host sync
    #: (proposals matched against a device-resident token window;
    #: llama.spec_decode_steps). 1 = one verify per dispatch (the classic
    #: loop, still with on-device acceptance; it pays the window upload —
    #: ~4 B x min(spec_max_scan, max_model_len) per lane per burst, noise
    #: next to a dispatch — to keep ONE spec implementation). Raising this
    #: composes speculation with the fused-burst idea: per-dispatch host
    #: latency is amortized over rounds, at the cost of gate/fallback
    #: decisions lagging a burst (a round whose proposals dry up degrades
    #: to a one-token verify round instead of a cheaper plain decode).
    spec_rounds: int = 1
    #: adaptive per-sequence gate: once a sequence has had at least
    #: spec_min_sample proposed tokens, stop proposing for it while its
    #: acceptance rate sits below spec_min_accept — a low-acceptance
    #: sequence then takes the plain/fused path at zero extra cost, so
    #: spec never pays verify dispatches that return less than they cost.
    #: The gate is per-sequence and one-way: once closed it stays
    #: closed for that sequence (sequences are short-lived).
    spec_min_accept: float = 0.4
    spec_min_sample: int = 8
    #: host-DRAM tier admission: "auto" (recompute-vs-restore cost model
    #: from online-measured rates gates BOTH spills and restores — the
    #: self-calibrating default) or "always" (unconditional spill/restore;
    #: use when the link is known-good and warm-up declines are unwanted).
    host_tier_policy: str = "auto"
    #: paged-KV quantization for the host-DRAM tier and the transfer wire:
    #: None (full-width pages everywhere, bit-identical legacy) or "int8"
    #: (symmetric per-page-per-head int8, models/quant.quantize_kv_page —
    #: halves host-tier bytes per page and transfer wire bytes, so the
    #: same host budget holds 2x the blocks). Pages are dequantized on
    #: bring-back/import BEFORE re-entering the Pallas paged-attention
    #: path; the device-side kernels never see an int8 page.
    kv_quant: Optional[str] = None
    #: paged-KV quantization for the HBM tier itself (ISSUE 16,
    #: ``KV_QUANT_HBM``): None (full-width bf16 pages in HBM, bit-identical
    #: legacy) or "int8" (the page pools hold int8 codes plus a per-page-
    #: per-(layer, kv_head) f32 scale pool; the Pallas decode kernel DMAs
    #: half the bytes per page and dequantizes in-register). Doubles the
    #: blocks a fixed HBM budget holds — read the MRC's 2x point
    #: (docs/operations.md) to forecast the hit-rate payoff BEFORE turning
    #: this on. "float8_e4m3" is reserved (declared follow-on storage
    #: mode; rejected with NotImplementedError until the kernel grows an
    #: fp8 dequant path). Composes with ``kv_quant``: with both int8, a
    #: page's codes+scales move host↔HBM and onto the wire directly,
    #: never widening. Incompatible (rejected at init) with sp>1,
    #: spec_decode, and the pallas prefill kernel.
    kv_quant_hbm: Optional[str] = None
    #: host-tier prefetch: bring a waiting sequence's host-cached prefix
    #: back into HBM ahead of the scheduler (device↔host copies overlap
    #: the current step) instead of restoring synchronously inside
    #: allocate. Off by default = bit-identical legacy scheduling.
    host_prefetch: bool = False
    #: remote tier (ISSUE 13, ``REMOTE_TIER``): when local eviction (HBM
    #: recycle or host-LRU drop) would destroy the LAST local copy of a
    #: cached block, build a wire-ready demotion payload (int8-quantized
    #: under ``kv_quant``, halving demotion bytes) and hand it to
    #: ``on_demotion`` — the serving layer pushes it to a peer with
    #: headroom / a kvstore pod over the transfer fabric. Also relaxes
    #: the import path to the normal eviction ladder (victims demote, so
    #: making room for routed-for warmth is lossless). Off by default =
    #: bit-identical legacy eviction.
    remote_tier: bool = False
    #: remote-store capacity in pages: how many demoted blocks THIS pod
    #: will hold for peers (0 = accept nothing; a dedicated kvstore pod
    #: sets this large and serves nothing else). Gated behind
    #: ``remote_tier``; sizing guidance in docs/operations.md.
    remote_store_pages: int = 0
    #: KV-block content integrity (ISSUE 19, ``KV_INTEGRITY``): write-time
    #: per-page digests over stored/wire bytes (kvcache/integrity), verified
    #: at every tier transition (host restore/prefetch bring-back, remote
    #: pull-back, transfer import, migration install) before a page becomes
    #: servable; a failed check quarantines the copy, truncates the chain at
    #: the bad suffix (cold prefill recomputes it), and publishes a
    #: ``BadBlock`` revocation. Off by default = bit-identical legacy
    #: behavior, /stats keys, and wire bytes.
    kv_integrity: bool = False
    #: digest side-table capacity in entries (LRU-bounded; a dropped entry
    #: just means that block restores unverified on the legacy trust
    #: model). Sized to cover the host tier + remote store several times
    #: over at 12 bytes/entry; only read when ``kv_integrity`` is on.
    kv_integrity_table_cap: int = 65536
    #: weight quantization: None (serve in model dtype) or "int8"
    #: (symmetric per-output-channel weight-only int8 — halves weight HBM
    #: bytes so 8B-class models fit one v5e chip with a KV pool;
    #: see models/quant.py). Applied to whatever params the engine gets,
    #: random-init or checkpoint-loaded.
    quantize: Optional[str] = None
    #: also quantize MoE expert stacks. Off by default (conservative:
    #: expert numerics are routing-sensitive); the gmm kernel dequantizes
    #: int8 experts in VMEM while halving expert HBM — opt in where
    #: capacity matters.
    quantize_experts: bool = False
    seed: int = 0


class Engine:
    def __init__(
        self,
        config: EngineConfig,
        params=None,
        on_events: Optional[Callable[[list[Event]], None]] = None,
        mesh=None,
    ):
        """``mesh``: the (dp=1, sp, tp) Mesh over the device(s) this
        engine OWNS, at every ``tp`` — a multi-replica host gives each
        engine its own chip (``tp=1``: a one-device mesh) or its own
        slice (two tp=2 pods on four chips). Weights, KV pools and every
        step's staged inputs live there and nowhere else. Default: the
        first sp*tp visible devices."""
        self.config = config
        cfg = config.model
        self.model_cfg = cfg
        ps = config.block_manager.page_size
        self.page_size = ps
        # Width includes fused-burst headroom: a sequence finishing at
        # max_model_len mid-burst keeps writing its surplus KV into reserved
        # pages of its own row, never into another sequence's pages. A
        # burst chained from one in flight needs no more: it is enqueued
        # only where no lane comes within a burst of max_model_len
        # (``_next_schedule_decided``), so its last write lies no further
        # out than a lone burst's.
        # ... and, for block diffusion, the block a sequence that stops at
        # max_model_len had opened past it.
        self.max_pages_per_seq = -(
            -(
                config.max_model_len
                + max(config.decode_steps_per_iter - 1, cfg.block_length)
            )
            // ps
        )

        import dataclasses
        import math

        if cfg.n_window_layers:
            # Sliding layers keep their keys and values in a window pool
            # with page ids of its own (``llama.init_window_pages``,
            # ``block_manager.WindowPool``): what does not know the second
            # pool is refused here by name. Unset, the window pool has as
            # many pages as the context pool.
            refused = {
                "host_pages > 0 (the host tier moves the context pool's "
                "pages alone)": config.block_manager.host_pages > 0,
                "remote_tier (demotion payloads are the context pool's "
                "pages)": config.remote_tier,
                "kv_quant_hbm (the window pool has no int8 form)":
                    config.kv_quant_hbm is not None,
                "tp > 1 (the window kernels run on one shard)": config.tp > 1,
                "sp > 1 (the ring knows no window)": config.sp > 1,
                "spec_decode (the verify scan is not run over a window "
                "pool)": config.spec_decode != "off",
                "block_length > 0 (no block mask over a window)":
                    cfg.block_length > 0,
                "conv layers or kv_lora_rank > 0 (one second pool a model)":
                    cfg.n_conv_layers > 0 or cfg.kv_lora_rank > 0,
                f"sliding_window={cfg.sliding_window} (whole pages of {ps})":
                    cfg.sliding_window < ps or cfg.sliding_window % ps != 0,
            }
            for what, on in refused.items():
                if on:
                    raise ValueError(
                        f"layer_types with {cfg.n_window_layers} sliding "
                        f"layers (a window pool beside the KV pool) is "
                        f"incompatible with {what}"
                    )
            config.block_manager = dataclasses.replace(
                config.block_manager,
                window_pages=config.block_manager.window_pages
                or config.block_manager.total_pages,
                sliding_window=cfg.sliding_window,
            )
            # a lone sequence holds at most the run of a hit and the last
            # window of a long chunk at once
            least = 2 * (cfg.sliding_window // ps + 2) + 1
            if config.block_manager.window_pages < least:
                raise ValueError(
                    f"window_pages={config.block_manager.window_pages}: a "
                    f"window of {cfg.sliding_window} tokens in pages of {ps} "
                    f"needs at least {least}"
                )
        elif config.block_manager.window_pages:
            # a number a model without sliding layers never reads
            config.block_manager = dataclasses.replace(
                config.block_manager, window_pages=0
            )
        if cfg.n_kda_layers:
            # Linear-attention layers keep a matrix a head in a state pool of
            # slots (``llama.init_kda_state``, ``block_manager.StatePool``)
            # beside the pool of the layers between them: latent rows, or
            # per-head keys and values over ``n_attn_layers``. What does not
            # carry a slot is refused here by name. Events, the index and the
            # scorer keep speaking of pages (``block_manager``'s docstring).
            stride = config.block_manager.state_snapshot_tokens
            rows = config.decode_batch_size + config.scheduler.max_prefill_batch
            clamps = (cfg.expert_swiglu_limits or ())[: cfg.n_layers] + (
                cfg.shared_swiglu_limits or ())[: cfg.n_layers]
            refused = {
                "host_pages > 0 (the host tier moves the context pool's "
                "pages; a state slot has no tier)":
                    config.block_manager.host_pages > 0,
                "remote_tier (demotion payloads are pages, not slots)":
                    config.remote_tier,
                "kv_quant_hbm (the state has no int8 form, and the pool "
                "beside it none in a program that carries slots)":
                    config.kv_quant_hbm is not None,
                "tp > 1 (the state is not sharded)": config.tp > 1,
                "sp > 1 (the ring carries no state across shards)":
                    config.sp > 1,
                "spec_decode (a rejected draft would have advanced a state "
                "slot)": config.spec_decode != "off",
                "block_length > 0 (a block is not forwarded token by token)":
                    cfg.block_length > 0,
                "chunked_prefill_tokens (a prompt is already prefilled in "
                "chunks, cut where a snapshot is due: one scheduling path)":
                    config.scheduler.chunked_prefill_tokens is not None,
                "sliding or conv layers (a window pool or a page's state "
                "beside: one second pool a model)":
                    cfg.n_window_layers > 0 or cfg.n_conv_layers > 0,
                "a non-zero SwiGLU limit on a run layer (the published "
                "config gives the limits and not where they clamp)":
                    any(clamps),
                f"state_snapshot_tokens={stride} (whole pages of {ps}, at "
                f"least a burst of {config.decode_steps_per_iter})":
                    stride < ps or stride % ps != 0
                    or stride < config.decode_steps_per_iter,
                "kda_head_dim / kda_conv_kernel (a state of a matrix a head "
                "and a convolution of at least two taps)":
                    cfg.kda_head_dim < 1 or cfg.kda_conv_kernel < 2,
            }
            for what, on in refused.items():
                if on:
                    raise ValueError(
                        f"layer_types with {cfg.n_kda_layers} linear_attention "
                        f"layers (a state pool of slots beside the "
                        f"{cfg.context_pool_name}) is incompatible with {what}"
                    )
            # a live slot a row that can hold a sequence, a second one while
            # it passes a boundary, then the snapshots; slot 0 is reserved
            config.block_manager = dataclasses.replace(
                config.block_manager,
                state_slots=max(
                    rows + config.block_manager.state_snapshot_slots,
                    2 * rows + 1,
                ),
            )
        elif config.block_manager.state_slots:
            config.block_manager = dataclasses.replace(
                config.block_manager, state_slots=0
            )
        self.block_manager = BlockManager(config.block_manager, on_events=on_events)

        cpt = config.scheduler.chunked_prefill_tokens
        if cpt is not None and cpt < 1:
            raise ValueError(
                "chunked_prefill_tokens must be >= 1 (None disables chunking)"
            )
        sched_cfg = dataclasses.replace(
            config.scheduler,
            max_running=min(config.scheduler.max_running, config.decode_batch_size),
            # Non-final chunks must end page-aligned (the next chunk's paged
            # context is whole pages) and land on the prefill shape buckets.
            chunk_align=math.lcm(config.prefill_bucket, ps),
        )
        self.scheduler = Scheduler(self.block_manager, sched_cfg)
        self.block_manager.part = self.scheduler.part = self.part

        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel import MeshConfig, make_mesh, shard_params
        from ..parallel.sharding import kv_pages_sharding

        if mesh is None:
            mesh = make_mesh(MeshConfig(dp=1, sp=config.sp, tp=config.tp))
        elif (
            mesh.shape.get("sp", 1) != config.sp
            or mesh.shape.get("tp", 1) != config.tp
        ):
            raise ValueError(
                f"provided mesh {dict(mesh.shape)} does not match "
                f"config sp={config.sp}, tp={config.tp}"
            )
        #: the devices this engine owns (one chip at tp=sp=1)
        self.devices = list(mesh.devices.flat)
        platform = self.devices[0].platform
        if config.interpret == (platform == "tpu"):
            # Refuse here, not at the first dispatch inside a serving
            # loop: a pod that cannot run its kernels must not come up.
            raise ValueError(
                f"interpret={config.interpret} on {platform!r} devices: "
                "compiled Pallas kernels need a TPU, and the interpreter "
                "(EngineConfig.interpret / INTERPRET=1) is for CPU tests "
                "and dry runs only"
            )
        #: host values staged for a step go here — replicated over the
        #: engine's own devices, never the process default device (which
        #: on a multi-replica host is another replica's chip)
        self._replicated = NamedSharding(mesh, PartitionSpec())
        #: which replica this is, as the profiler names its device plane
        #: (``/device:TPU:0`` -> ``tpu:0``): the phase spans carry it, so
        #: with several replicas in one process a chip's idle gaps are
        #: matched to its own loop
        self.replica = f"{platform}:{self.devices[0].id}"
        with jax.default_device(self.devices[0]):
            # Tiny key computations and (without a checkpoint) the random
            # weights are made on the engine's first device: a tp=1
            # replica never touches another chip; a tp>1 init passes
            # through one chip of its own slice before sharding.
            rng = jax.random.PRNGKey(config.seed ^ 0x5EED)
            greedy_key = jax.random.PRNGKey(0)
            if params is None:
                params = llama.init_params(
                    jax.random.PRNGKey(config.seed),
                    cfg,
                    quantize=config.quantize,
                    quantize_experts=config.quantize_experts,
                )
        if config.quantize is not None:
            if config.quantize != "int8":
                raise ValueError(f"unknown quantize mode {config.quantize!r}")
            if not quant.is_quantized(params):
                # NB: the caller's full-precision tree stays alive during
                # this; for models near HBM capacity init with
                # llama.init_params(..., quantize="int8") instead.
                params = quant.quantize_params(
                    params, quantize_experts=config.quantize_experts
                )
        if config.prefill_attn not in ("auto", "pallas", "xla"):
            raise ValueError(f"unknown prefill_attn {config.prefill_attn!r}")
        if config.spec_decode not in ("off", "prompt_lookup"):
            raise ValueError(f"unknown spec_decode {config.spec_decode!r}")
        if config.spec_decode != "off":
            if config.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if config.spec_ngram < 1:
                raise ValueError("spec_ngram must be >= 1")
            if config.spec_rounds < 1:
                raise ValueError("spec_rounds must be >= 1")
        if cfg.block_length > 0:
            # Generation by diffusion over blocks: what is not done for it
            # is refused here by name, not found at the first dispatch.
            refused = {
                "sp > 1 (the ring masks causally)": config.sp > 1,
                "kv_quant_hbm (the block forward reads pages full-width)":
                    config.kv_quant_hbm is not None,
                "spec_decode (a block is not one drafted token)":
                    config.spec_decode != "off",
                "decode_steps_per_iter > 1 (one forward of a block a "
                "dispatch)": config.decode_steps_per_iter > 1,
            }
            for what, on in refused.items():
                if on:
                    raise ValueError(
                        f"block_length={cfg.block_length} (generation by "
                        f"diffusion over blocks) is incompatible with {what}"
                    )
            if ps % cfg.block_length:
                # a page then holds whole blocks: its keys and values are a
                # function of the tokens up to its end, which is what the
                # prefix cache, the index and the scorer take a page for
                raise ValueError(
                    f"page_size={ps} must be a multiple of "
                    f"block_length={cfg.block_length}"
                )
            if not 0 <= cfg.mask_token_id < cfg.vocab_size:
                raise ValueError(
                    f"mask_token_id={cfg.mask_token_id} outside the "
                    f"vocabulary of {cfg.vocab_size}"
                )
        if cfg.kv_lora_rank > 0:
            # A latent pool (one row a token a layer, no value pool): what
            # is not done for it is refused here by name. The prefix cache,
            # KV events, the index and the scorer know tokens, not heads,
            # and serve it as they serve every model.
            refused = {
                "kv_quant_hbm (a latent pool has no int8 form or scales)":
                    config.kv_quant_hbm is not None,
                "host_pages > 0 (the host tier moves K and V pages)":
                    config.block_manager.host_pages > 0,
                "remote_tier (demotion payloads are K and V pages)":
                    config.remote_tier,
                "sp > 1 (the ring rotates per-head keys and values)":
                    config.sp > 1,
                "tp > 1 (every head reads the one row: nothing to shard "
                "on heads)": config.tp > 1,
                "spec_decode (the verify scan is not run over latent rows)":
                    config.spec_decode != "off",
                "block_length > 0 (no block mask in the latent kernel)":
                    cfg.block_length > 0,
            }
            for what, on in refused.items():
                if on:
                    raise ValueError(
                        f"kv_lora_rank={cfg.kv_lora_rank} (a latent KV "
                        f"pool) is incompatible with {what}"
                    )
        if cfg.n_experts and not cfg.holds_every_expert:
            # Routed layers that are told what they hold (one rank's share
            # of the experts, zero-compute experts among the router's
            # outputs): the routed dispatch alone leaves out what is held
            # elsewhere and adds the zero experts' part.
            first, count = cfg.expert_first, cfg.experts_held
            refused = {
                'moe_dispatch="dense" (the masked-dense oracle scores every '
                "expert against every token)": cfg.moe_dispatch == "dense",
                "tp > 1 (a held range is not sharded again)": config.tp > 1,
                f"n_experts={cfg.n_experts} (the held range lies past the "
                "experts the router scores)":
                    first < 0 or count < 1 or first + count > cfg.n_experts,
            }
            for what, on in refused.items():
                if on:
                    raise ValueError(
                        f"experts {first}..{first + count - 1} held, "
                        f"{cfg.n_zero_experts} zero experts: incompatible "
                        f"with {what}"
                    )
        if cfg.n_conv_layers:
            # Convolution layers keep state beside the keys and values of
            # the layers that attend, a slot a page (``llama.
            # init_state_pages``): what does not carry that state is refused
            # here by name. The prefix cache, KV events, the index and the
            # scorer know pages, and a page's slot is written by the program
            # that writes its last token: they serve it as every model.
            refused = {
                "spec_decode (rejected drafts would have advanced the state)":
                    config.spec_decode != "off",
                "block_length > 0 (a block is not forwarded token by token)":
                    cfg.block_length > 0,
                "kv_quant_hbm (the state has no int8 form)":
                    config.kv_quant_hbm is not None,
                "host_pages > 0 (the host tier moves K and V pages)":
                    config.block_manager.host_pages > 0,
                "remote_tier (demotion payloads are K and V pages)":
                    config.remote_tier,
                "sp > 1 (the ring carries no state across shards)":
                    config.sp > 1,
                "tp > 1 (the state and a pool row of two KV heads are not "
                "sharded)": config.tp > 1,
                "conv_bias (a biased convolution is not run)": cfg.conv_bias,
                "kv_lora_rank > 0 (no latent pool beside a state pool)":
                    cfg.kv_lora_rank > 0,
                "conv_L_cache < 2 (a convolution of one tap keeps no state)":
                    cfg.conv_L_cache < 2,
            }
            for what, on in refused.items():
                if on:
                    raise ValueError(
                        f"layer_types with {cfg.n_conv_layers} conv layers "
                        f"(convolution state beside the KV pool) is "
                        f"incompatible with {what}"
                    )
        if config.kv_quant_hbm is not None:
            if config.kv_quant_hbm not in quant.KV_QUANT_HBM_MODES:
                raise ValueError(
                    f"unknown kv_quant_hbm mode {config.kv_quant_hbm!r}"
                )
            if config.kv_quant_hbm == "float8_e4m3":
                raise NotImplementedError(
                    "kv_quant_hbm='float8_e4m3' is the declared follow-on "
                    "storage mode; the paged-attention kernel has no fp8 "
                    "dequant path yet — use 'int8'"
                )
            # Scope limits: the quantized pools thread through the decode
            # kernel and the xla prefill context gather only. The sp ring,
            # the pallas prefill kernel, and the fused spec-decode scan all
            # read pages full-width and would silently widen — reject
            # rather than quietly fall back.
            if config.sp > 1:
                raise ValueError("kv_quant_hbm is incompatible with sp > 1")
            if config.spec_decode != "off":
                raise ValueError(
                    "kv_quant_hbm is incompatible with spec_decode"
                )
            if config.prefill_attn == "pallas":
                raise ValueError(
                    "kv_quant_hbm requires the xla prefill path "
                    "(prefill_attn='auto' or 'xla')"
                )
        #: speculative-decode observability: proposed/accepted draft
        #: tokens, verify ROUNDS, and host-sync bursts (acceptance rate =
        #: accepted/proposed; rounds-per-sync = verify_steps/bursts).
        self.spec_stats = {
            "proposed": 0, "accepted": 0, "verify_steps": 0, "bursts": 0,
        }
        if cfg.n_heads % config.tp or cfg.n_kv_heads % config.tp:
            raise ValueError(
                f"tp={config.tp} must divide n_heads={cfg.n_heads} and "
                f"n_kv_heads={cfg.n_kv_heads}"
            )
        # ONE stated rule, never the backend: the flash kernel serves
        # prefill unless the engine was told to interpret (CPU tests take
        # the XLA scan — the interpreted kernel is minutes per chunk), the
        # pool is int8 (the kernel reads pages full-width), or a shard
        # holds ONE KV head (tp = n_kv_heads: Mosaic copies no page tile
        # out of a 16-bit pool tiled two rows deep over an axis of one).
        # (Pinned to "pallas" there, the kernel's wrapper refuses by name.)
        self.prefill_attn = config.prefill_attn
        if self.prefill_attn == "auto":
            self.prefill_attn = (
                "xla"
                if config.interpret
                or config.kv_quant_hbm is not None
                or cfg.n_kv_heads // config.tp == 1
                else "pallas"
            )
        if config.sp > 1 and config.prefill_bucket % config.sp:
            raise ValueError(
                f"sp={config.sp} must divide "
                f"prefill_bucket={config.prefill_bucket} (chunk lengths "
                f"are bucket multiples and must shard evenly)"
            )
        #: what the jitted steps shard over: the mesh when there is more
        #: than one device to shard across, else None (no shard_map, no
        #: collectives — placement alone comes from the committed arrays)
        self.mesh = mesh if config.tp > 1 or config.sp > 1 else None
        self.params = shard_params(params, mesh, cfg)
        self._rng = jax.device_put(rng, self._replicated)
        self._greedy_key = jax.device_put(greedy_key, self._replicated)
        # Pools are created ON their devices: a staging copy through the
        # default device would not fit beside another replica's pool.
        self.k_pages, self.v_pages = llama.init_kv_pages(
            cfg, config.block_manager.total_pages, ps,
            kv_quant_hbm=config.kv_quant_hbm,
            # a latent pool has no head axis to shard (tp > 1 is refused)
            sharding=self._replicated if cfg.kv_lora_rank
            else kv_pages_sharding(mesh),
        )
        #: what a token costs in the pools, all layers: the arrays' bytes
        #: over the pool's token slots (a latent row says its held width
        #: itself, ``LlamaConfig.kv_row_shape``, so ``nbytes`` is what the
        #: device holds). ``/stats`` reports it beside ``total_pages``;
        #: ``kv_block_bytes`` follows it for a latent pool.
        self.kv_bytes_per_token = (
            self.k_pages.nbytes + self.v_pages.nbytes
        ) // (config.block_manager.total_pages * ps)
        #: the convolution layers' state pool (None: the model has no such
        #: layer), addressed by the same page ids, and what it costs a
        #: token slot: ``/stats`` reports it beside ``kv_bytes_per_token``
        self.state_pages = llama.init_state_pages(
            cfg, config.block_manager.total_pages, sharding=self._replicated
        )
        self.state_bytes_per_token = (
            0 if self.state_pages is None else self.state_pages.nbytes
        ) // (config.block_manager.total_pages * ps)
        if cfg.n_kda_layers:
            # the linear layers' state pool of slots, a (matrices, carried
            # rows) pair in the same keyword: what prefix caching costs a
            # token here is a snapshot's bytes over the stride
            self.state_pages = llama.init_kda_state(
                cfg, config.block_manager.state_slots,
                sharding=self._replicated,
            )
            self.state_bytes_per_token = (
                cfg.kda_state_bytes
                // config.block_manager.state_snapshot_tokens
            )
        #: the sliding layers' window pools, a (K, V) pair with page ids of
        #: their own (None: the model has no such layer), what a token slot
        #: costs there (``/stats`` reports it beside ``kv_bytes_per_token``,
        #: which is then the full layers' alone) and how wide a decode
        #: dispatch's window table is: a window, the page its first slot
        #: lies in, and what two bursts add (one may be in flight)
        self.window_pages: Optional[tuple] = llama.init_window_pages(
            cfg, config.block_manager.window_pages, ps,
            sharding=self._replicated,
        )
        self.window_bytes_per_token = 0
        self.window_table_pages = 0
        if self.window_pages is not None:
            self.window_bytes_per_token = sum(
                pool.nbytes for pool in self.window_pages
            ) // (config.block_manager.window_pages * ps)
            self.window_table_pages = (
                cfg.sliding_window // ps + 2
                + -(-2 * config.decode_steps_per_iter // ps)
            )
        #: how many of the model's layers route their rows to experts, read
        #: from the layers' own parameters as ``llama._mlp`` reads them:
        #: ``step_stats["experts_touched"]`` sums over these, so ``/stats``
        #: states the number beside ``kv_bytes_per_token`` and no reader
        #: keeps a table of which layers are dense
        self.routed_layers = sum(
            "router" in layer or "moe" in layer
            for layer in self.params["layers"]
        )
        #: columns of counts before a fused burst's tokens
        #: (``llama.burst_counts``: one, or ``llama.BURST_COUNTS_HELD``)
        self._burst_counts = llama.burst_counts(cfg)
        # Scale pools ride alongside the int8 page pools (None when the
        # knob is off — every scale-threading call site keys off this).
        self.k_scales: Optional[jnp.ndarray] = None
        self.v_scales: Optional[jnp.ndarray] = None
        if config.kv_quant_hbm == "int8":
            self.k_scales, self.v_scales = llama.init_kv_scales(
                cfg, config.block_manager.total_pages,
                sharding=NamedSharding(
                    mesh, PartitionSpec(None, None, "tp")
                ),
            )
        log.info(
            "engine placed",
            platform=platform,
            device_kind=self.devices[0].device_kind,
            devices=[d.id for d in self.devices],
            tp=config.tp,
            sp=config.sp,
            interpret=config.interpret,
            prefill_attn=self.prefill_attn,
            moe_gmm=cfg.moe_gmm if cfg.n_experts else None,
        )

        # Online rate estimates driving the recompute-vs-restore cost
        # model (EMAs, measured on the real dispatches of THIS process —
        # self-calibrating to the rig: where restores are slow the model
        # prefers recompute; fast host DMA flips the break-even).
        self._prefill_rate: Optional[float] = None  # chunk tokens / s
        self._restore_rate: Optional[float] = None  # restored pages / s
        self._offload_rate: Optional[float] = None  # D2H gathered pages / s

        # Host-DRAM offload tier: numpy slot pool + jitted page movers.
        # With kv_quant="int8" the slot pool is int8 + per-(layer, head)
        # f32 scales — half the bytes per page of a bf16 pool, so a fixed
        # host-DRAM budget holds ~2x the blocks. kv_quant_hbm="int8" forces
        # the same host layout regardless of kv_quant: the HBM source is
        # already int8 codes+scales, so storing the host tier full-width
        # would DOUBLE host bytes and add a dequant→requant round trip per
        # spill/restore — with the HBM knob on, the whole ladder is int8.
        if config.kv_quant is not None:
            if config.kv_quant not in quant.KV_QUANT_MODES:
                raise ValueError(f"unknown kv_quant mode {config.kv_quant!r}")
        self._host_int8 = (
            config.kv_quant == "int8" or config.kv_quant_hbm == "int8"
        )
        hp = config.block_manager.host_pages
        if hp > 0:
            slot_shape = (hp, cfg.n_layers, ps, *cfg.kv_row_shape)
            np_dtype = np.dtype(jnp.dtype(cfg.dtype).name)
            if self._host_int8:
                self._host_k = np.zeros(slot_shape, np.int8)
                self._host_v = np.zeros(slot_shape, np.int8)
                sc_shape = (hp,) + quant.kv_scale_shape(slot_shape[1:])
                self._host_k_scale = np.zeros(sc_shape, np.float32)
                self._host_v_scale = np.zeros(sc_shape, np.float32)
            else:
                self._host_k = np.zeros(slot_shape, np_dtype)
                self._host_v = np.zeros(slot_shape, np_dtype)
            if config.host_tier_policy not in ("auto", "always"):
                raise ValueError(
                    f"unknown host_tier_policy {config.host_tier_policy!r}"
                )
            self.block_manager.attach_host_pool(
                self._offload_page,
                self._restore_page,
                self._restore_beats_recompute
                if config.host_tier_policy == "auto"
                else None,
            )
            if config.host_tier_policy == "auto":
                # Probe the device→host link ONCE at init so the cost
                # model gates the very first spill wave — without this,
                # everything evicted before the first flush ships
                # ungated, which is exactly the expensive warm-up on slow
                # links the model exists to avoid. Probe a 16-page batch:
                # a single page would mostly measure dispatch latency and
                # wrongly condemn the tier on fast links.
                n_probe = min(16, config.block_manager.total_pages)
                idx = self._dev(np.zeros((n_probe,), np.int32))
                # Warm-up call first: the timed sample must not include
                # the jit trace+compile of the gather (a compile-polluted
                # rate would understate fast links ~100x and permanently
                # decline every spill — no flush would ever run to
                # replace the bogus sample). Probe BOTH k and v pools: a
                # "page" everywhere else in the cost model means a k+v
                # pair (flush gathers both), so a k-only probe would
                # overstate the link 2x.
                np.asarray(_read_pages_batch(self.k_pages, idx))
                t0 = time.perf_counter()
                np.asarray(_read_pages_batch(self.k_pages, idx))
                np.asarray(_read_pages_batch(self.v_pages, idx))
                self._offload_rate = n_probe / max(
                    time.perf_counter() - t0, 1e-6
                )
        #: prefill observability: tokens actually pushed through prefill
        #: dispatches (the FLOP proxy — prefix-cache hits and imported
        #: blocks reduce it), dispatch count, and the token slots those
        #: dispatches computed (rows the program computed x bucketed
        #: width, padding included: ``tokens_computed / token_slots`` is how
        #: full a dispatch is; the rest is the width's bucket and the
        #: shorter rows' tails).
        self.prefill_stats = {
            "tokens_computed": 0, "dispatches": 0, "token_slots": 0,
        }
        #: cross-pod KV transfer observability (kvcache/transfer).
        self.transfer_stats = {
            "exported_blocks": 0,
            "imported_blocks": 0,
            "import_rejected": 0,
        }
        self._pending_offloads: list = []
        self._pending_restores: list = []
        self._off_by_slot: dict = {}
        self._restore_by_page: dict = {}
        # -- KV-block content integrity (KV_INTEGRITY; off = None, every
        # path below is bit-identical legacy) ------------------------------
        self.integrity = None
        if config.kv_integrity:
            from ..kvcache.integrity import BlockIntegrity

            self.integrity = BlockIntegrity(
                table_cap=config.kv_integrity_table_cap
            )
            self.block_manager.attach_integrity(
                self.integrity, self._verify_host_slot
            )
        # -- remote tier (REMOTE_TIER; off = none of this exists) ----------
        #: demotion payload sink, set by the serving layer (PodServer's
        #: background pusher) or a test; None drops demotions on
        #: the floor = plain eviction.
        self.on_demotion: Optional[Callable[[list], None]] = None
        #: queued (info, src) demotions, resolved at the page-move flush
        self._pending_demotions: list = []
        self.remote_stats = {
            "demoted_blocks": 0,
            "demote_batches": 0,
            "accepted_blocks": 0,
        }
        self.remote_store = None
        if config.remote_tier and config.remote_store_pages > 0:
            from ..kvcache.transfer.remote_store import (
                RemoteBlockStore,
                RemoteStoreConfig,
            )

            def _store_events(events):
                # Late-bound: PodServer may attach the publisher to the
                # block manager AFTER engine construction (injected
                # engines); the store must see the same sink it does.
                sink = self.block_manager.on_events
                if sink is not None:
                    sink(events)

            shape = (cfg.n_layers, ps, *cfg.kv_row_shape)
            self.remote_store = RemoteBlockStore(
                RemoteStoreConfig(
                    capacity_pages=config.remote_store_pages,
                    page_size=ps,
                    page_shape=shape,
                    dtype=str(np.dtype(jnp.dtype(cfg.dtype).name)),
                    scale_bytes=int(np.prod(quant.kv_scale_shape(shape))) * 4,
                    init_hash=self.block_manager.token_db.init_hash,
                ),
                on_events=_store_events,
                integrity=self.integrity,
            )
        if config.remote_tier:
            self.block_manager.attach_demoter(self._queue_demotion)
        #: host-tier prefetch observability (host_prefetch knob): rounds =
        #: steps where the stage ran and found work, pages = host blocks
        #: brought back ahead of allocate, seqs = waiting sequences whose
        #: chains were warmed.
        self.host_prefetch_stats = {"rounds": 0, "pages": 0, "seqs": 0}
        #: (pages, start_mono, end_mono) of the most recent prefetch round
        #: that moved pages — the serving layer turns it into a
        #: ``pod.host_bringback`` span + prefetch-seconds sample, then
        #: clears it. Engine-internal timing stays off the default path.
        self.last_prefetch: Optional[tuple[int, float, float]] = None
        #: per-step prefetch page cap: one prefill batch's worth of pages,
        #: so the bring-back gather stays the same order of work as the
        #: prefill dispatch it overlaps.
        self._prefetch_page_cap = max(
            1, config.scheduler.max_prefill_tokens // ps
        )
        self.finished: list[Sequence] = []
        self._step_count = 0
        #: set once any request carries a deadline — gates the per-step
        #: expiry scan so the no-deadline path stays bit-identical legacy.
        self._deadlines_used = False
        #: request-lifecycle observability (deadline sheds/expiries, aborts)
        self.lifecycle_stats = {
            "deadline_shed": 0,
            "deadline_expired": 0,
            "aborted": 0,
        }
        #: engine-step telemetry (``OBS_METRICS``, ``OBS_FLIGHT``, the
        #: benchmark's traced run): cumulative wall seconds of each of
        #: ``STEP_PHASES`` (``<phase>_s``, through ``phase``), and beside
        #: them the sums that predate the split: ``prefill_s`` /
        #: ``decode_s`` (their five phases), ``sample_s`` (the two
        #: fetches: the blocking share of the sampled-token device_get;
        #: for a burst that was chained from, the wait for that burst
        #: while the next is queued behind it). ``gather_s`` (host<->device
        #: page moves) and
        #: ``demote_s`` (remote-tier demotion payload builds) are slices
        #: INSIDE other phases and get no span. Counters: ``steps``,
        #: ``decode_dispatches``, ``decode_rows`` (real lanes of each
        #: decode dispatch, summed), ``decode_sampled_dispatches`` (those
        #: with a ``temperature > 0`` lane: the sampler's gate runs its
        #: vocabulary filter in these and in no other),
        #: ``decode_chained_dispatches`` (those whose input ids came from
        #: the burst in flight, on the device: enqueued before that
        #: burst's tokens were fetched), ``decode_uploads`` /
        #: ``prefill_uploads`` (host arrays a decode / prefill dispatch
        #: staged on the device, ``_stage``: one or two a dispatch); prefill
        #: dispatches are counted, always, in ``prefill_stats``. Every decode
        #: path: ``decode_forwards`` (forwards of the model the decode
        #: dispatches ran: dispatches x the steps fused in each) and
        #: ``experts_touched`` (distinct experts a forward's rows chose,
        #: summed over the ``routed_layers`` and the forwards: counted on
        #: the device in ``llama._moe_mlp_routed`` and fetched with the
        #: tokens, first column of a fused burst (``_commit_burst``), last
        #: of a block dispatch; 0 for a model without routed layers, and
        #: the speculative path does not count it), and beside it, from the
        #: same fetch, ``routed_places`` (rows x top-k x ``routed_layers``
        #: of the fused bursts' forwards, padded lanes' rows included),
        #: ``zero_places`` (those that fell on zero-compute experts) and
        #: ``held_places`` (on experts this process holds: the rows its
        #: grouped matmuls computed); the last two are counted on the
        #: device for a model whose routed layers are told what they hold
        #: (``llama.burst_counts``) and stay 0 for every other, whose
        #: ``held_places`` is ``routed_places``. Block diffusion
        #: (``_run_decode_block``, beside those):
        #: ``denoise_lane_forwards`` (lanes x dispatches in which the lane
        #: had a masked row), ``commit_lane_forwards`` (in which it had none:
        #: the forward that stores the block's keys and values),
        #: ``block_tokens_fixed`` (rows fixed), ``blocks_final``.
        #: A latent pool: ``latent_ctx_tokens`` (the real lanes' context
        #: lengths, summed over the decode dispatches: the rows the
        #: ``mla_decode`` kernel must read, a layer). Every model:
        #: ``attn_ctx_tokens``, the same sum (what each layer that attends
        #: reads of its pool in the fused decode dispatches; a full layer,
        #: in a model with sliding ones), and ``decode_table_slots``, the
        #: token slots the block tables of the same lanes and steps had room
        #: for (real lanes x the dispatch's table width x the page: every
        #: lane's table is as wide as the longest lane's bucket, so
        #: ``attn_ctx_tokens`` over it is what of a table is context).
        #: Sliding layers:
        #: ``window_ctx_tokens``, the sum of ``min(context, window)`` over
        #: the same lanes and steps (what a sliding layer reads of the
        #: window pool; 0 for a model without such layers). A kernel that
        #: copies its lanes' table pages itself (the latent kernel, the
        #: sliding layers'): ``ctx_pages``, the pages a layer's call copies
        #: over the same dispatches, and ``ctx_run_pages``, those copied as
        #: part of a run of consecutive pool pages (``_count_ctx_pages``);
        #: ``full_ctx_pages`` / ``full_ctx_run_pages``, the same of a full
        #: layer's ``paged_attention`` over the block tables.
        #: Off by default: ``obs_step_timing=False`` skips every clock
        #: read and every count, so the legacy step path is untouched.
        self.obs_step_timing = False
        self.step_stats = {
            "steps": 0,
            "decode_dispatches": 0,
            "decode_rows": 0,
            "decode_sampled_dispatches": 0,
            "decode_chained_dispatches": 0,
            "decode_forwards": 0,
            "decode_uploads": 0,
            "prefill_uploads": 0,
            "denoise_lane_forwards": 0,
            "commit_lane_forwards": 0,
            "block_tokens_fixed": 0,
            "blocks_final": 0,
            "experts_touched": 0,
            "routed_places": 0,
            "zero_places": 0,
            "held_places": 0,
            "latent_ctx_tokens": 0,
            "attn_ctx_tokens": 0,
            "decode_table_slots": 0,
            "window_ctx_tokens": 0,
            "ctx_pages": 0,
            "ctx_run_pages": 0,
            "full_ctx_pages": 0,
            "full_ctx_run_pages": 0,
            "prefill_s": 0.0,
            "decode_s": 0.0,
            "sample_s": 0.0,
            "gather_s": 0.0,
            "demote_s": 0.0,
            **{f"{name}_s": 0.0 for name in STEP_PHASES},
            **dict.fromkeys(ADMIT_SECONDS, 0.0),
            **dict.fromkeys(ADMIT_COUNTS, 0),
        }
        #: the phase open right now (the burst in flight, drained inside
        #: it, suspends and resumes it: ``_Phase``)
        self._open_phase: Optional[_Phase] = None
        #: child spans open inside it (``_Part``: they nest)
        self._open_parts = 0
        #: the decode burst left on the device over the end of a step
        #: (``_next_schedule_decided``): toks device array, lane-ordered
        #: active list, and the np position/len arrays the NEXT burst
        #: derives from. Of ``_run_decode_block``: the forward's ``packed``
        #: device array (the lanes' blocks after it, which the next forward
        #: takes from there) and the active list; the blocks it was given
        #: are the sequences' own until it is committed. None whenever a
        #: lane is free.
        self._inflight: Optional[dict] = None
        #: The prefill dispatched behind the burst that ends its rows'
        #: predecessors (``_admit_ahead``), not yet fetched: what
        #: ``_enqueue_prefill`` returned. Its sequences wait in
        #: ``scheduler.prefilling``; the next ``step()`` commits it before
        #: anything else, an abort or a migration before it looks for its
        #: sequence. Never set while ``_inflight`` is.
        self._prefill_ahead: Optional[dict] = None
        #: what an unchained ``_run_decode_block`` dispatch hands over as
        #: the forward before it: resident, never read
        self._no_block = (
            jnp.zeros(
                (config.decode_batch_size, 2 * cfg.block_length + 1),
                jnp.int32, device=self._replicated,
            )
            if cfg.block_length else None
        )

    def _dev(self, x, dtype=None) -> jax.Array:
        """Stage a host value on the device(s) this engine owns."""
        return jax.device_put(np.asarray(x, dtype), self._replicated)

    def _stage(self, kind: str, *host: np.ndarray) -> list[jax.Array]:
        """How a dispatch's host inputs reach the device, last thing before
        the device call: queued page moves first (restores must land before
        attention reads them, spilled pages be snapshotted before the
        dispatch overwrites them, and a reservation may have queued more),
        then every array of ``host`` in one upload each. A dispatch packs
        what it can into one array, which its program slices apart: an
        upload costs the host about as much for forty bytes as for forty
        thousand. ``kind`` ("decode" | "prefill") names the counter."""
        self._flush_page_moves()
        if self.obs_step_timing:
            self.step_stats[kind + "_uploads"] += len(host)
        return [self._dev(x) for x in host]

    def _draw_key(self, temperature: np.ndarray) -> jax.Array:
        """The key of a dispatch that samples on the device. With a sampled
        lane, a split of the engine's rng. All greedy: the sampler's gate
        never reads the key, so the engine's rng stays where it is (a
        device program less, and sampled streams elsewhere in the run do
        not shift because greedy lanes ran)."""
        if (temperature > 0).any():
            self._rng, key = jax.random.split(self._rng)
            return key
        return self._greedy_key

    def phase(self, name: str):
        """Context manager for one of ``STEP_PHASES``. Off (the default):
        the shared ``NO_PHASE``. On: see ``_Phase``."""
        if not self.obs_step_timing:
            return NO_PHASE
        return _Phase(self, name)

    def part(self, name: str = "", **stats):
        """Context manager for an admission (no name: the span ``admit``)
        or one of its ``ADMIT_PARTS`` (``admit.<name>``), with ``stats`` on
        the span. The block manager and the scheduler call it as their
        ``part``. Off (the default): the shared ``NO_PHASE``. On: see
        ``_Part``."""
        if not self.obs_step_timing:
            return NO_PHASE
        return _Part(self, name, stats)

    # -- host-DRAM tier movers (batched) ------------------------------------
    #
    # The block manager calls the movers synchronously during scheduling,
    # but paying a device round-trip PER PAGE makes the tier unusable under
    # thrash (a dispatch per page; devices prefer few large DMAs to many
    # small ones). The movers therefore
    # only QUEUE moves; `_flush_page_moves` runs before the next device
    # dispatch — the only point where pool contents are read or
    # overwritten — as ONE batched gather and ONE batched scatter.
    #
    # Ordering hazards handled (all within a single scheduling round):
    # - restore from a slot whose offload is still pending → source the
    #   restore from the offloading device page, not the stale host slot;
    # - offload of a page that has a pending restore into it (restored
    #   then evicted again) → source the offload from the restore's data;
    # - host snapshots are taken at queue time, so later slot reuse cannot
    #   corrupt an already-queued restore.
    def _offload_page(self, page: int, slot: int) -> None:
        src = self._restore_by_page.get(page, ("page", page))
        self._pending_offloads.append((slot, src))
        self._off_by_slot[slot] = src

    def _read_host_slot(self, slot: int) -> tuple[np.ndarray, np.ndarray]:
        """One host slot's KV as full-width model-dtype arrays (dequantized
        when the tier is int8), snapshotted so they outlive slot reuse —
        the restore scatter's source. (Exports read the slot pools
        directly: quantized wire ships the stored codes, and tobytes()
        needs no snapshot.) NOT used under kv_quant_hbm: the quantized
        HBM pool wants the codes themselves — see ``_restore_page``."""
        if self._host_int8:
            np_dtype = np.dtype(jnp.dtype(self.model_cfg.dtype).name)
            return (
                quant.dequantize_kv_page(
                    self._host_k[slot], self._host_k_scale[slot], np_dtype
                ),
                quant.dequantize_kv_page(
                    self._host_v[slot], self._host_v_scale[slot], np_dtype
                ),
            )
        return self._host_k[slot].copy(), self._host_v[slot].copy()

    def _restore_page(self, slot: int, page: int) -> None:
        src = self._off_by_slot.get(slot)
        if src is None:
            if self.config.kv_quant_hbm == "int8" and self._host_int8:
                # Both tiers store the same int8 codes + per-(layer, head)
                # scales: bring the block back by COPYING them, never by
                # dequantizing through a full-width staging page (which
                # would both double the staged bytes and re-quantize —
                # an avoidable second rounding).
                src = (
                    "qdata",
                    self._host_k[slot].copy(),
                    self._host_v[slot].copy(),
                    self._host_k_scale[slot].copy(),
                    self._host_v_scale[slot].copy(),
                )
            else:
                src = ("data",) + self._read_host_slot(slot)
        self._pending_restores.append((page, src))
        self._restore_by_page[page] = src

    # -- KV-block content integrity (KV_INTEGRITY) --------------------------
    def _host_slot_digest(self, slot: int) -> int:
        """Content digest of one host slot's STORED representation: int8
        codes + scales under a quantized host tier, raw dtype bytes
        otherwise — the exact bytes a restore reads back and a host-tier
        export ships, so one digest spans spill→restore and
        host→wire→store→pull-back."""
        from ..kvcache.integrity import page_digest

        if self._host_int8:
            return page_digest(
                self._host_k[slot].tobytes(),
                self._host_v[slot].tobytes(),
                self._host_k_scale[slot].tobytes(),
                self._host_v_scale[slot].tobytes(),
            )
        return page_digest(
            self._host_k[slot].tobytes(), self._host_v[slot].tobytes()
        )

    def _verify_host_slot(self, slot: int, h: int, reason: str) -> bool:
        """Block-manager integrity hook: recompute the digest over the
        host arrays for ``slot`` and compare against the write-time
        record. Returns False ONLY for a corrupt copy (and quarantines it
        first); a missing record passes — blocks spilled before the knob
        (or whose queued offload has not flushed yet) are served on the
        legacy trust model, never truncated on absence of evidence."""
        from ..kvcache.integrity import CHECK_CORRUPT

        outcome = self.integrity.check(h, self._host_slot_digest(slot), reason)
        if outcome == CHECK_CORRUPT:
            self.integrity.quarantine(h, tier="host_dram")
            return False
        return True

    def scrub_host_pages(self, max_pages: int) -> int:
        """Background integrity scrub, staged onto the engine loop by the
        serving layer's scrub timer: flush queued page moves first (so
        slot bytes — and their write-time digests — are committed, making
        fresh spills verifiable), then verify a bounded rotating batch of
        resident host slots. Corrupt copies quarantine with the full
        recovery choreography; the resulting events flush immediately so
        the fleet revokes without waiting for engine traffic."""
        if self.integrity is None:
            return 0
        self._flush_page_moves()
        n = self.block_manager.scrub_host_tier(max_pages)
        if n:
            self.block_manager.flush_events()
        return n

    def _verify_demote_src(self, info, src) -> bool:
        """Pre-ship verify for a demotion snapshot: never push a payload
        whose bytes already fail their write-time digest — shipping
        poison just moves the quarantine to a peer. Only snapshots still
        in the STORED representation are comparable against the side
        table (int8 codes + scales, or full-width bytes on an
        unquantized host tier); device-sourced or re-transformed
        snapshots verify at the receiver via the payload digest instead.
        A corrupt snapshot quarantines here: digest dropped, ledger
        records the loss, and ``BadBlock`` revokes fleet-wide."""
        from ..kvcache.integrity import CHECK_CORRUPT, page_digest
        from ..kvcache.kvevents.events import BadBlock

        if src[0] == "qdata":
            d = page_digest(
                src[1].tobytes(),
                src[2].tobytes(),
                src[3].tobytes(),
                src[4].tobytes(),
            )
        elif src[0] == "data" and not self._host_int8:
            d = page_digest(src[1].tobytes(), src[2].tobytes())
        else:
            return True
        h = info.chain_hash
        if self.integrity.check(h, d, "export") != CHECK_CORRUPT:
            return True
        self.integrity.quarantine(h, tier="host_dram")
        self.block_manager._record_lifecycle(
            h, "none", "quarantine", tenant=getattr(info, "tenant", "")
        )
        self.block_manager._emit(BadBlock(block_hashes=[h], medium="host_dram"))
        log.warning(
            "demotion payload failed digest check; quarantined", block=h
        )
        return False

    # -- remote-tier demotion (REMOTE_TIER) ---------------------------------
    def _queue_demotion(self, info, tier: str, idx: int) -> None:
        """Block-manager demotion hook: the last local copy of
        ``info.chain_hash`` is being destroyed — queue a snapshot so the
        flush builds a wire-ready payload for the serving layer's pusher.
        HBM pages defer to the flush gather (contents are intact until
        the next dispatch, same window the offload path uses); host slots
        snapshot NOW (the slot is reused immediately). No sink attached =
        plain eviction, zero work."""
        if self.on_demotion is None:
            return
        if tier == "tpu_hbm":
            src = self._restore_by_page.get(idx, ("page", idx))
        else:  # host_dram
            src = self._off_by_slot.get(idx)
            if src is None:
                if self._host_int8:
                    # Ship the stored int8 codes + scales directly — the
                    # PR 6 wire triple, no dequant/requant round trip.
                    src = (
                        "qdata",
                        self._host_k[idx].copy(),
                        self._host_v[idx].copy(),
                        self._host_k_scale[idx].copy(),
                        self._host_v_scale[idx].copy(),
                    )
                else:
                    src = (
                        "data",
                        self._host_k[idx].copy(),
                        self._host_v[idx].copy(),
                    )
        self._pending_demotions.append((info, src))

    def _build_demotions(self, page_data: dict) -> None:
        """Resolve queued demotions against the flush gather and hand the
        wire-ready payloads to ``on_demotion`` (serving-layer pusher)."""
        from ..kvcache.transfer.protocol import BlockPayload

        cfg = self.model_cfg
        ps = self.page_size
        shape = (cfg.n_layers, ps, *cfg.kv_row_shape)
        sc_shape = quant.kv_scale_shape(shape)
        np_dtype = np.dtype(jnp.dtype(cfg.dtype).name)
        hbmq = self.config.kv_quant_hbm == "int8"
        quantize_wire = self.config.kv_quant == "int8" or hbmq
        payloads = []
        for info, src in self._pending_demotions:
            if self.integrity is not None and not self._verify_demote_src(
                info, src
            ):
                continue
            extra = {}
            if src[0] == "qdata":
                kd, vd = src[1], src[2]
                extra = {
                    "quant": "int8",
                    "k_scale": np.ascontiguousarray(
                        src[3], np.float32
                    ).tobytes(),
                    "v_scale": np.ascontiguousarray(
                        src[4], np.float32
                    ).tobytes(),
                }
            elif src[0] == "page" and hbmq:
                # Quantized HBM: the flush gather already carries the
                # stored codes + scales — ship them as-is (the wire scale
                # layout is the host tier's [L, 1, n_kv, 1]).
                kd, vd, sk, sv = page_data[src[1]]
                extra = {
                    "quant": "int8",
                    "k_scale": sk.reshape(sc_shape).tobytes(),
                    "v_scale": sv.reshape(sc_shape).tobytes(),
                }
            else:
                kd, vd = (
                    page_data[src[1]] if src[0] == "page" else (src[1], src[2])
                )
                if quantize_wire:
                    kd, sk = quant.quantize_kv_page(kd)
                    vd, sv = quant.quantize_kv_page(vd)
                    extra = {
                        "quant": "int8",
                        "k_scale": sk.tobytes(),
                        "v_scale": sv.tobytes(),
                    }
            payload = BlockPayload(
                block_hash=info.chain_hash,
                parent_block_hash=info.parent_hash,
                token_ids=list(info.token_ids),
                block_size=ps,
                dtype=str(np_dtype) if quantize_wire else str(kd.dtype),
                shape=shape,
                k_data=kd.tobytes(),
                v_data=vd.tobytes(),
                **extra,
            )
            if self.integrity is not None:
                # Stamp the wire digest over the FINAL payload bytes (the
                # representation the receiver stores and re-serves), and
                # drop the local record — the last local copy is being
                # destroyed; the digest now travels with the bytes.
                from ..kvcache.integrity import page_digest

                payload.digest = page_digest(
                    payload.k_data,
                    payload.v_data,
                    payload.k_scale,
                    payload.v_scale,
                )
                self.integrity.drop(info.chain_hash)
            payloads.append(payload)
        self._pending_demotions.clear()
        self.remote_stats["demoted_blocks"] += len(payloads)
        self.remote_stats["demote_batches"] += 1
        sink = self.on_demotion
        if sink is not None:
            sink(payloads)

    def accept_remote_blocks(self, source_pod: str, payloads) -> tuple[int, int]:
        """Commit a peer's demotion push into this pod's remote store and
        flush the resulting ``BlockStored(medium="remote")`` events so the
        index learns the new holder without waiting for engine traffic.
        Returns ``(accepted, headroom)``. Must run on the engine thread
        (the store shares the event stream's ordering)."""
        if self.remote_store is None:
            return 0, 0
        accepted = self.remote_store.accept(payloads, source_pod=source_pod)
        if accepted:
            self.remote_stats["accepted_blocks"] += accepted
        return accepted, self.remote_store.headroom

    @property
    def remote_headroom(self) -> Optional[int]:
        """Pages the remote store will still accept (heartbeat headroom
        advertisement); None when the tier is off — the heartbeat then
        carries no headroom field and its bytes stay legacy."""
        if not self.config.remote_tier:
            return None
        # `is not None`, not truthiness: the store defines __len__ and an
        # EMPTY store is exactly when headroom is largest.
        return (
            self.remote_store.headroom if self.remote_store is not None else 0
        )

    def block_digest(self) -> dict[str, list[int]]:
        """Resync digest across every tier this pod holds, including the
        remote store — an ``IndexSnapshot`` replace-all must never wipe
        the demoted entries the holder is responsible for."""
        digest = self.block_manager.block_digest()
        if self.remote_store is not None and len(self.remote_store):
            digest["remote"] = self.remote_store.hashes()
        return digest

    @staticmethod
    def _ema(prev: Optional[float], sample: float, alpha: float = 0.3) -> float:
        return sample if prev is None else (1 - alpha) * prev + alpha * sample

    def _restore_beats_recompute(self, n_pages: int) -> bool:
        """Recompute-vs-restore cost model (block-manager callback): is
        DMA-ing ``n_pages`` host-cached pages back cheaper than
        recomputing their ``n_pages * page_size`` tokens? Decided from
        the online-measured rates. Until a restore has been measured, the
        offload (D2H gather) rate stands in as the link-bandwidth proxy —
        it exists from the FIRST spill flush, which closes the bootstrap
        hole where spills run ungated (on a slow host link, ruinously)
        before any restore ever produced a sample. Optimistic
        only while NO tier transfer has been measured."""
        tier_rate = (
            self._restore_rate
            if self._restore_rate is not None
            else self._offload_rate
        )
        if tier_rate is None or self._prefill_rate is None:
            return True
        restore_s = n_pages / tier_rate
        recompute_s = n_pages * self.page_size / self._prefill_rate
        return restore_s <= recompute_s

    def _prefetch_host_pages(self) -> None:
        """Prefetch stage: walk the first prefill batch's worth of WAITING
        sequences in FCFS order and bring their host-cached prefix chains
        back into HBM (ref-0 evictable pages, data queued through the
        batched movers) so the scheduler's later ``allocate`` sees plain
        warm pages. Bounded per step by ``_prefetch_page_cap``; the
        recompute-vs-restore cost model gates every run exactly as the
        blocking path would, so outputs are identical with the knob off."""
        bm = self.block_manager
        if bm.num_host_cached_pages == 0 or not self.scheduler.waiting:
            return
        budget = self._prefetch_page_cap
        # islice, not list()[:n]: this runs every step and the waiting
        # deque can be hundreds deep under the pressure regime.
        head = list(
            itertools.islice(
                self.scheduler.waiting, self.config.scheduler.max_prefill_batch
            )
        )
        pages = 0
        seqs = 0
        t0 = time.monotonic()
        for seq in head:
            if budget <= 0:
                break
            if seq.prefetch_hashes is None:
                seq.prefetch_hashes = bm.token_db.prefix_hashes(
                    seq.prompt_tokens
                )
            n = bm.prefetch_chain(seq.prefetch_hashes, budget)
            if n:
                pages += n
                seqs += 1
                budget -= n
        if pages:
            self.host_prefetch_stats["rounds"] += 1
            self.host_prefetch_stats["pages"] += pages
            self.host_prefetch_stats["seqs"] += seqs
            self.last_prefetch = (pages, t0, time.monotonic())

    def _flush_page_moves(self) -> None:
        if (
            not self._pending_offloads
            and not self._pending_restores
            and not self._pending_demotions
        ):
            return
        # The rate samples below time fenced copies; a decode burst still
        # on the device (``_inflight``) would be waited for inside the
        # first of them and read as a slow link by the cost model.
        jax.block_until_ready(self.k_pages)
        t_flush = time.perf_counter() if self.obs_step_timing else 0.0
        # One batched gather for every device page any queued move reads
        # (demotion snapshots ride the same gather as offloads/restores).
        need = []
        for _, src in (
            self._pending_offloads
            + self._pending_restores
            + self._pending_demotions
        ):
            if src[0] == "page" and src[1] not in need:
                need.append(src[1])
        hbmq = self.config.kv_quant_hbm == "int8"
        page_data = {}
        if need:
            # Bucket the gather width to limit compile count.
            n = 1 << (len(need) - 1).bit_length()
            idx = np.asarray(need + [need[0]] * (n - len(need)), np.int32)
            t_gather = time.perf_counter()
            idx = self._dev(idx)
            k_data = np.asarray(_read_pages_batch(self.k_pages, idx))
            v_data = np.asarray(_read_pages_batch(self.v_pages, idx))
            if hbmq:
                # Quantized HBM: the gathered pages are int8 codes — pull
                # their [L, n_kv] scale rows through the same batched
                # mover (scale pools index axis 1 exactly like the page
                # pools, so the jitted gather is reused as-is).
                k_sc = np.asarray(_read_pages_batch(self.k_scales, idx))
                v_sc = np.asarray(_read_pages_batch(self.v_scales, idx))
            # D2H rate sample (np.asarray fences): the cost model's
            # link-bandwidth bound, available from the first spill. Divide
            # by the PADDED gather width — those pages were actually
            # transferred — so this sample measures the same pages/s the
            # init probe and the restore sample do (an unpadded divisor
            # understated the rate up to 2x near power-of-2 boundaries and
            # could flip recompute-vs-restore on near-break-even links).
            self._offload_rate = self._ema(
                self._offload_rate,
                n / max(time.perf_counter() - t_gather, 1e-6),
            )
            for i, p in enumerate(need):
                page_data[p] = (
                    (k_data[:, i], v_data[:, i], k_sc[:, i], v_sc[:, i])
                    if hbmq
                    else (k_data[:, i], v_data[:, i])
                )

        def resolve(src):
            return page_data[src[1]] if src[0] == "page" else (src[1], src[2])

        def resolve_q(src):
            """Mixed-width source → (k codes, v codes, k scales, v scales)
            with scales in the HBM pool's [L, n_kv] layout. Every tier
            crossing under kv_quant_hbm lands here: stored codes move
            as-is, and only genuinely full-width sources (a legacy peer's
            unquantized import) pay a quantize."""
            if src[0] == "page":
                return page_data[src[1]]
            if src[0] == "qdata":
                L = self.model_cfg.n_layers
                n_kv = self.model_cfg.n_kv_heads
                return (
                    src[1], src[2],
                    np.asarray(src[3], np.float32).reshape(L, n_kv),
                    np.asarray(src[4], np.float32).reshape(L, n_kv),
                )
            kq, sk = quant.quantize_kv_page(src[1])
            vq, sv = quant.quantize_kv_page(src[2])
            return (
                kq, vq,
                sk.reshape(sk.shape[0], -1), sv.reshape(sv.shape[0], -1),
            )

        if hbmq:
            sc_host = (self.model_cfg.n_layers, 1, self.model_cfg.n_kv_heads, 1)
            for slot, src in self._pending_offloads:
                kd, vd, sk, sv = resolve_q(src)
                self._host_k[slot] = kd
                self._host_v[slot] = vd
                self._host_k_scale[slot] = sk.reshape(sc_host)
                self._host_v_scale[slot] = sv.reshape(sc_host)
        elif self.config.kv_quant == "int8":
            for slot, src in self._pending_offloads:
                kd, vd = resolve(src)
                self._host_k[slot], self._host_k_scale[slot] = (
                    quant.quantize_kv_page(kd)
                )
                self._host_v[slot], self._host_v_scale[slot] = (
                    quant.quantize_kv_page(vd)
                )
        else:
            for slot, src in self._pending_offloads:
                self._host_k[slot], self._host_v[slot] = resolve(src)

        if self.integrity is not None and self._pending_offloads:
            # Write-time digests (KV_INTEGRITY): the slot bytes just
            # landed and are hot in cache — record each written slot's
            # stored-representation digest now, keyed by the block hash
            # the block manager mapped to the slot. Reversed + seen-set:
            # when a slot was written more than once this flush, only the
            # LAST write's mapping is current.
            seen: set = set()
            for slot, _src in reversed(self._pending_offloads):
                if slot in seen:
                    continue
                seen.add(slot)
                info = self.block_manager._host_info.get(slot)
                if info is not None and info.chain_hash is not None:
                    self.integrity.record(
                        info.chain_hash, self._host_slot_digest(slot)
                    )

        if self._pending_restores:
            # Rate window starts HERE: a mixed flush must not charge the
            # offload snapshots' gather/memcpys to the restores (that
            # understated restore_rate ~15x under thrash and biased the
            # cost model toward declining genuinely-cheap restores).
            t0 = time.perf_counter()
            total = self.config.block_manager.total_pages
            # Dedupe by destination page, LAST queued restore wins: a page
            # restored, rolled back, recycled, and restored again within
            # one window must land the second block's data (duplicate
            # scatter indices have no ordering guarantee in XLA).
            by_dst = {p: src for p, src in self._pending_restores}
            dst = list(by_dst.keys())
            datas = [
                (resolve_q if hbmq else resolve)(src)
                for src in by_dst.values()
            ]
            n = 1 << (len(dst) - 1).bit_length()
            pad = n - len(dst)
            idx = self._dev(dst + [total] * pad, np.int32)  # pad → drop
            k_stack = np.stack([d[0] for d in datas] + [datas[0][0]] * pad, 1)
            v_stack = np.stack([d[1] for d in datas] + [datas[0][1]] * pad, 1)
            self.k_pages = _write_pages_batch(
                self.k_pages, idx, self._dev(k_stack)
            )
            self.v_pages = _write_pages_batch(
                self.v_pages, idx, self._dev(v_stack)
            )
            if hbmq:
                # Scales land through the same scatter (axis-1 indexed
                # pools), so a restored page and its scale commit in the
                # same flush — never a codes/scale skew window.
                ks_stack = np.stack(
                    [d[2] for d in datas] + [datas[0][2]] * pad, 1
                )
                vs_stack = np.stack(
                    [d[3] for d in datas] + [datas[0][3]] * pad, 1
                )
                self.k_scales = _write_pages_batch(
                    self.k_scales, idx, self._dev(ks_stack)
                )
                self.v_scales = _write_pages_batch(
                    self.v_scales, idx, self._dev(vs_stack)
                )
            # Fence with a scalar fetch so the restore-rate sample covers
            # the real DMA.
            # Padded-width divisor, same rationale as the offload sample.
            np.asarray(self.k_pages[0, 0, 0, 0, 0])
            self._restore_rate = self._ema(
                self._restore_rate,
                n / max(time.perf_counter() - t0, 1e-6),
            )

        demote_s = 0.0
        if self._pending_demotions:
            # Demotion payload builds (quantize + serialize) ride the
            # flush but are REMOTE_TIER work, not page-move work: timed
            # under their own `demote` phase label so the tier's cost
            # never hides inside `gather`.
            t_dem = time.perf_counter() if self.obs_step_timing else 0.0
            self._build_demotions(page_data)
            if self.obs_step_timing:
                demote_s = time.perf_counter() - t_dem
        self._pending_offloads.clear()
        self._pending_restores.clear()
        self._off_by_slot.clear()
        self._restore_by_page.clear()
        if self.obs_step_timing:
            self.step_stats["demote_s"] += demote_s
            self.step_stats["gather_s"] += (
                time.perf_counter() - t_flush - demote_s
            )

    # -- cross-pod KV transfer (kvcache/transfer) ---------------------------
    @property
    def kv_block_bytes(self) -> int:
        """Wire bytes of one transferred KV block (k + v page slices) —
        the ``block_bytes`` feed of the router's transfer cost model. With
        ``kv_quant="int8"`` this is the int8 payload plus scales: the
        measured transfer rate is learned from real (quantized) wire
        bytes, so a full-width figure here would overestimate pull cost
        ~2x and wrongly decline break-even pulls."""
        cfg = self.model_cfg
        if (cfg.kv_lora_rank or cfg.n_conv_layers or cfg.n_window_layers
                or cfg.n_kda_layers):
            # one pool of latent rows, or the attention layers' K and V
            # beside the convolution layers' state, or the full layers' K
            # and V alone (a window page never leaves the engine): a block
            # is one page of every pool whose pages live as long as a prefix
            # (a state slot of linear layers is no page's and never moves)
            return self.page_size * (
                self.kv_bytes_per_token
                + (0 if cfg.n_kda_layers else self.state_bytes_per_token)
            )
        elems = cfg.n_layers * self.page_size * cfg.n_kv_heads * cfg.hd
        if (
            self.config.kv_quant == "int8"
            or self.config.kv_quant_hbm == "int8"
        ):
            return 2 * (elems + cfg.n_layers * cfg.n_kv_heads * 4)
        return 2 * elems * jnp.dtype(cfg.dtype).itemsize

    def _refuse_latent_page_moves(self, what: str) -> None:
        """Pages leave and enter an engine as a K and a V page of KV heads
        (the wire's payload, the digests, the int8 form): not done for a
        latent pool, whose second pool holds no page, nor for a model with
        convolution layers, whose pages have a state slot beside them.
        ``PodServer`` refuses ``transfer_endpoint`` for such a model at
        construction; this holds any other caller."""
        if self.model_cfg.n_kda_layers:
            raise ValueError(
                f"layer_types with {self.model_cfg.n_kda_layers} "
                f"linear_attention layers (a state pool of slots beside the "
                f"{self.model_cfg.context_pool_name}) is incompatible with "
                f"{what} (export, import "
                f"and migration move pages and no state slot)"
            )
        if self.model_cfg.kv_lora_rank:
            raise ValueError(
                f"kv_lora_rank={self.model_cfg.kv_lora_rank} (a latent KV "
                f"pool) is incompatible with {what} (export and import move "
                f"K and V pages)"
            )
        if self.model_cfg.n_conv_layers:
            raise ValueError(
                f"layer_types with {self.model_cfg.n_conv_layers} conv "
                f"layers (convolution state beside the KV pool) is "
                f"incompatible with {what} (export, import and migration "
                f"move K and V pages and no state)"
            )
        if self.model_cfg.n_window_layers:
            raise ValueError(
                f"layer_types with {self.model_cfg.n_window_layers} sliding "
                f"layers (a window pool beside the KV pool) is incompatible "
                f"with {what} (export, import and migration move the "
                f"context pool's pages and no window page)"
            )

    def export_kv_blocks(self, hashes: list, max_blocks: Optional[int] = None):
        """Serve a peer's prefix fetch: the longest consecutive resident
        run of ``hashes`` as ``BlockPayload``s, sourced from HBM (one
        batched gather) and the host-DRAM tier. Must run on the engine
        thread — it reads page pools and flushes queued page moves so the
        exported bytes reflect committed state, not in-flight snapshots."""
        from ..kvcache.transfer.protocol import BlockPayload

        self._refuse_latent_page_moves("export_kv_blocks")
        self._flush_page_moves()
        chain = self.block_manager.lookup_chain(hashes, max_blocks)
        # Remote-store continuation: a kvstore pod (or a peer holding
        # demoted blocks) serves the rest of the requested run from its
        # wire-ready store — same stop-at-first-gap walk, zero device
        # work. Pure store hits (no local page resident) serve too.
        remote_tail: list = []
        if self.remote_store is not None:
            cap = len(hashes) if max_blocks is None else min(max_blocks, len(hashes))
            remote_tail = self.remote_store.serve(
                hashes[len(chain) : cap], cap - len(chain)
            )
        if not chain:
            if remote_tail:
                self.transfer_stats["exported_blocks"] += len(remote_tail)
            return remote_tail
        dev = [(i, idx) for i, (_, _, tier, idx) in enumerate(chain) if tier == "tpu_hbm"]
        hbmq = self.config.kv_quant_hbm == "int8"
        page_data: dict[int, tuple] = {}
        if dev:
            # Bucket the gather width to a power of two (the flush path's
            # rule): peers fetch chains of arbitrary length, and an
            # unbucketed width would compile a fresh executable per
            # length — each stalling the engine loop between steps.
            pages = [p for _, p in dev]
            n = 1 << (len(pages) - 1).bit_length()
            idx = self._dev(pages + [pages[0]] * (n - len(pages)), np.int32)
            k = np.asarray(_read_pages_batch(self.k_pages, idx))
            v = np.asarray(_read_pages_batch(self.v_pages, idx))
            if hbmq:
                k_sc = np.asarray(_read_pages_batch(self.k_scales, idx))
                v_sc = np.asarray(_read_pages_batch(self.v_scales, idx))
            for j, (i, _) in enumerate(dev):
                page_data[i] = (
                    (k[:, j], v[:, j], k_sc[:, j], v_sc[:, j])
                    if hbmq
                    else (k[:, j], v[:, j])
                )
        quantize_wire = self.config.kv_quant == "int8" or hbmq
        np_dtype = np.dtype(jnp.dtype(self.model_cfg.dtype).name)
        sc_shape = quant.kv_scale_shape(
            (
                self.model_cfg.n_layers,
                self.page_size,
                *self.model_cfg.kv_row_shape,
            )
        )
        blocks = []
        for i, (h, info, tier, idx) in enumerate(chain):
            # Halved wire bytes under kv_quant: ship int8 + f32 scales;
            # dtype/shape stay the LOGICAL page geometry so the importer's
            # checks are scheme-independent. Host-tier blocks already
            # store exactly the int8 codes + scales the wire wants — ship
            # them directly (no dequant/requant round trip); HBM blocks
            # quantize from the gathered full-width pages.
            extra = {}
            qshape: tuple
            if tier == "tpu_hbm":
                if hbmq:
                    # Quantized HBM: the gathered pages ARE the stored
                    # codes — ship them with their scales, no widening.
                    kd, vd, sk_, sv_ = page_data[i]
                    qshape = tuple(kd.shape)
                    extra = {
                        "quant": "int8",
                        "k_scale": sk_.reshape(sc_shape).tobytes(),
                        "v_scale": sv_.reshape(sc_shape).tobytes(),
                    }
                else:
                    kd, vd = page_data[i]
                    qshape = tuple(kd.shape)
                    if quantize_wire:
                        kd, sk = quant.quantize_kv_page(kd)
                        vd, sv = quant.quantize_kv_page(vd)
                        extra = {
                            "quant": "int8",
                            "k_scale": sk.tobytes(),
                            "v_scale": sv.tobytes(),
                        }
            else:
                # Views into the slot pools; tobytes() below materializes
                # C-order bytes without a staging copy.
                kd, vd = self._host_k[idx], self._host_v[idx]
                qshape = tuple(kd.shape)
                if self._host_int8:
                    extra = {
                        "quant": "int8",
                        "k_scale": self._host_k_scale[idx].tobytes(),
                        "v_scale": self._host_v_scale[idx].tobytes(),
                    }
            dtype_s = str(np_dtype) if quantize_wire else str(kd.dtype)
            # tobytes() emits C-order bytes from any view — no
            # ascontiguousarray staging copy.
            payload = BlockPayload(
                block_hash=h,
                parent_block_hash=info.parent_hash,
                token_ids=list(info.token_ids),
                block_size=self.page_size,
                dtype=dtype_s,
                shape=qshape,
                k_data=kd.tobytes(),
                v_data=vd.tobytes(),
                **extra,
            )
            if self.integrity is not None:
                from ..kvcache.integrity import CHECK_CORRUPT, page_digest

                # Host-tier payload bytes ARE the stored slot bytes, so
                # this digest doubles as the pre-serve verify against the
                # write-time record. HBM blocks are freshly gathered from
                # the trusted tier — their digest is stamped, not checked.
                d = page_digest(
                    payload.k_data,
                    payload.v_data,
                    payload.k_scale,
                    payload.v_scale,
                )
                if (
                    tier == "host_dram"
                    and self.integrity.check(h, d, "export") == CHECK_CORRUPT
                ):
                    # Never ship poison: quarantine the host copy, revoke
                    # fleet-wide, and truncate the export at the corrupt
                    # block — the importer's stop-at-first-gap walk means
                    # anything past it could never prefix-hit anyway.
                    self.integrity.quarantine(h, tier="host_dram")
                    self.block_manager.quarantine_host_block(h)
                    self.block_manager.flush_events()
                    truncated = True
                    break
                payload.digest = d
            blocks.append(payload)
        else:
            truncated = False
        if not truncated:
            blocks.extend(remote_tail)
        self.transfer_stats["exported_blocks"] += len(blocks)
        return blocks

    def import_kv_blocks(
        self,
        blocks,
        allow_evict: Optional[bool] = None,
        source_pod: str = "",
    ) -> int:
        """Install fetched prefix blocks as committed prefix-cache pages.

        Each block must extend a resident chain (its parent is the chain
        root, an already-resident block, or the block installed just
        before it) and match this engine's page geometry exactly — the
        first violation stops the import (a block behind a gap can never
        prefix-hit). Page bytes are queued through the same batched-mover
        path host-tier restores use and land before the next device
        dispatch, so a subsequent local prefill hits imported pages
        exactly like locally-computed cache. ``BlockStored`` events flush
        immediately so the global index learns the new warmth without
        waiting for engine traffic. Returns the number of blocks
        installed. Must run on the engine thread.

        ``allow_evict``: None (default) follows ``config.remote_tier`` —
        with the remote tier on, an import may recycle evictable LRU
        pages to make room (the victim spills to host or demotes over
        the fabric, so the trade is lossless); off keeps the legacy
        free-pages-only rule.

        ``source_pod``: where the bytes came from (push sender, pull
        endpoint, migration source). Under KV_INTEGRITY a payload whose
        carried digest fails the recompute is rejected and a ``BadBlock``
        naming that holder is published — the importer that catches a
        peer's corrupt export is the one that revokes it fleet-wide."""
        from ..kvcache.kvblock.token_processor import hash_block

        self._refuse_latent_page_moves("import_kv_blocks")
        if allow_evict is None:
            allow_evict = self.config.remote_tier

        cfg = self.model_cfg
        ps = self.page_size
        expected_shape = (cfg.n_layers, ps, *cfg.kv_row_shape)
        np_dtype = np.dtype(jnp.dtype(cfg.dtype).name)
        page_bytes = int(np.prod(expected_shape)) * np_dtype.itemsize
        # Quantized frames ship int8 payloads + f32 scales of the page's
        # logical shape; any peer's quantized export is importable
        # regardless of this engine's own kv_quant knob (dequantized
        # before the page pool ever sees it).
        q_page_bytes = int(np.prod(expected_shape))
        scale_bytes = int(np.prod(quant.kv_scale_shape(expected_shape))) * 4
        installed = 0
        for blk in blocks:
            try:
                blk_dtype = np.dtype(blk.dtype)
            except TypeError:
                blk_dtype = None
            quantized = blk.quant is not None
            if quantized:
                payload_ok = (
                    blk.quant == "int8"
                    and len(blk.k_data) == q_page_bytes
                    and len(blk.v_data) == q_page_bytes
                    and len(blk.k_scale) == scale_bytes
                    and len(blk.v_scale) == scale_bytes
                )
            else:
                payload_ok = (
                    len(blk.k_data) == page_bytes
                    and len(blk.v_data) == page_bytes
                )
            if (
                blk.block_size != ps
                or tuple(blk.shape) != expected_shape
                or blk_dtype != np_dtype
                or len(blk.token_ids) != ps
                or not payload_ok
            ):
                self.transfer_stats["import_rejected"] += 1
                break  # geometry mismatch: nothing later can be valid either
            h = blk.block_hash
            if self.block_manager.is_block_resident(h):
                continue  # local copy wins; chain continuity is preserved
            parent = blk.parent_block_hash
            if parent is not None and not self.block_manager.is_block_resident(parent):
                self.transfer_stats["import_rejected"] += 1
                break  # chain gap: unreachable by any prefix walk
            # Verify the chain hash against the tokens the peer claims the
            # block holds: the prefix cache's truth is this hash chain, so
            # an entry whose hash this engine would not itself compute
            # (tampered/corrupt payload, or a hash_seed-misaligned fleet)
            # must never register. The KV bytes themselves are covered by
            # the carried content digest below when KV_INTEGRITY is on;
            # with the knob off they are served on the legacy trust model
            # (verifying without a digest would be the recompute we are
            # avoiding).
            chain_parent = (
                parent if parent is not None else self.block_manager.token_db.init_hash
            )
            if hash_block(chain_parent, blk.token_ids) != h:
                self.transfer_stats["import_rejected"] += 1
                break
            if self.integrity is not None:
                from ..kvcache.integrity import CHECK_CORRUPT, page_digest
                from ..kvcache.kvevents.events import BadBlock

                computed = page_digest(
                    blk.k_data, blk.v_data, blk.k_scale, blk.v_scale
                )
                if (
                    self.integrity.check_carried(
                        h, blk.digest, computed, "import"
                    )
                    == CHECK_CORRUPT
                ):
                    # The bytes rotted between the exporter's write-time
                    # digest and here (wire frame or the holder's store).
                    # Reject, quarantine the identity locally, and revoke
                    # the named holder's entry fleet-wide — then stop:
                    # later blocks chain onto the one we just refused.
                    self.transfer_stats["import_rejected"] += 1
                    self.integrity.quarantine(h, tier="wire")
                    self.block_manager._emit(
                        BadBlock(block_hashes=[h], pod=source_pod)
                    )
                    self.block_manager.flush_events()
                    log.warning(
                        "imported KV payload failed digest check; rejected",
                        block=h,
                        source=source_pod or "<unknown>",
                    )
                    break
            try:
                page = self.block_manager.install_imported_block(
                    h, parent, blk.token_ids, allow_evict=allow_evict
                )
            except AllocationError:
                break  # pool full: keep what landed, never evict for imports
            if page is None:
                continue
            if quantized:
                sc_shape = quant.kv_scale_shape(expected_shape)
                kq = np.frombuffer(blk.k_data, np.int8).reshape(expected_shape)
                vq = np.frombuffer(blk.v_data, np.int8).reshape(expected_shape)
                ksc = np.frombuffer(blk.k_scale, np.float32).reshape(sc_shape)
                vsc = np.frombuffer(blk.v_scale, np.float32).reshape(sc_shape)
                if self.config.kv_quant_hbm == "int8":
                    # Quantized pool: land the peer's codes + scales as-is
                    # (the batched flush scatters them into the int8 page
                    # pool and the scale pool) — imports never widen.
                    src = ("qdata", kq, vq, ksc, vsc)
                else:
                    src = (
                        "data",
                        quant.dequantize_kv_page(kq, ksc, np_dtype),
                        quant.dequantize_kv_page(vq, vsc, np_dtype),
                    )
            else:
                k = np.frombuffer(blk.k_data, dtype=np_dtype).reshape(expected_shape)
                v = np.frombuffer(blk.v_data, dtype=np_dtype).reshape(expected_shape)
                src = ("data", k, v)
            self._pending_restores.append((page, src))
            self._restore_by_page[page] = src
            installed += 1
        if installed:
            self.transfer_stats["imported_blocks"] += installed
            self.block_manager.flush_events()
        return installed

    # -- public API ---------------------------------------------------------
    def add_request(
        self,
        prompt_tokens: list[int],
        sampling: Optional[SamplingParams] = None,
        request_id: Optional[str] = None,
        deadline: Optional[float] = None,
        tenant: str = "",
        priority: int = 0,
        qos_weight: float = 1.0,
        submit_time: Optional[float] = None,
    ) -> Sequence:
        """``submit_time``: when the serving layer took the request
        (``time.monotonic()``); None = now.

        ``deadline``: absolute ``time.monotonic()`` deadline. Expired
        waiting sequences are shed before prefill; running sequences past
        it finish early with ``finish_reason="deadline"``. None (default)
        = no deadline, bit-identical legacy behavior.

        ``tenant``/``priority``/``qos_weight``: TENANT_QOS dimension
        (serving layer resolves them from the parsed policy). Defaults =
        knob off — every sequence shares one anonymous class and the
        scheduler's QoS ordering never fires."""
        if len(prompt_tokens) == 0:
            raise ValueError("empty prompt")
        if len(prompt_tokens) >= self.config.max_model_len:
            raise ValueError("prompt exceeds max_model_len")
        # A prompt whose pages can never all fit would wait forever and
        # starve the FCFS queue behind it; reject it up front.
        check_block_sampling(
            sampling or SamplingParams(), self.model_cfg.block_length
        )
        prompt_pages = -(-(len(prompt_tokens) + 1) // self.page_size)
        if prompt_pages > self.config.block_manager.total_pages - 1:
            raise ValueError(
                f"prompt needs {prompt_pages} pages but the pool holds only "
                f"{self.config.block_manager.total_pages - 1}"
            )
        seq = Sequence(
            prompt_tokens=list(prompt_tokens),
            sampling=sampling or SamplingParams(),
            request_id=request_id,
            deadline=deadline,
            tenant=tenant,
            priority=priority,
            qos_weight=qos_weight,
            submit_time=submit_time,
            block_length=self.model_cfg.block_length,
        )
        if deadline is not None:
            self._deadlines_used = True
        self.scheduler.add(seq)
        return seq

    def abort(self, request_id: str) -> Optional[Sequence]:
        """Abort a request mid-flight — client disconnect, generate()
        timeout, operator action — releasing its pages/slots immediately
        instead of decoding into the void. (A prefill dispatched ahead is
        committed first, so its sequences are where a prefill leaves them.)
        Finds the sequence in whichever
        scheduler state holds it (waiting, mid-prefill, running), removes
        it, frees its pages, and marks it FINISHED with
        ``finish_reason="abort"``. Returns the aborted sequence, or None
        when no live sequence carries ``request_id`` (already finished, or
        never admitted). Must run on the engine thread (page-pool
        ownership rule — the serving layer stages aborts onto the loop)."""
        self._commit_prefill_ahead()
        seq = None
        for cand in (
            list(self.scheduler.waiting)
            + self.scheduler.prefilling
            + self.scheduler.running
        ):
            if cand.request_id == request_id:
                seq = cand
                break
        if seq is None:
            return None
        # The burst in flight may hold this lane on device: commit
        # it first so batchmates keep their tokens and the lane set the
        # next dispatch sees matches scheduler state.
        if self._inflight is not None and any(
            s is seq for s in self._inflight["active"]
        ):
            self._drain_inflight()
        if seq in self.scheduler.waiting:
            self.scheduler.waiting.remove(seq)
        else:
            self.scheduler.on_preempted(seq)  # removes from running/prefilling
        self.block_manager.free_sequence(seq)
        seq.status = SequenceStatus.FINISHED
        seq.finish_reason = "abort"
        seq.finish_time = time.monotonic()
        self.lifecycle_stats["aborted"] += 1
        self.finished.append(seq)
        # Ship any pending BlockStored/BlockRemoved now: an idle engine may
        # not step again for a while, and the index must not hold stale
        # state for pages this abort just released.
        self.block_manager.flush_events()
        log.warning(
            "aborted request; pages released",
            request=request_id,
            seq=seq.seq_id,
            generated=seq.num_generated,
        )
        return seq

    def abort_all(self) -> list[Sequence]:
        """Abort every live sequence (the drain-timeout hammer): commits
        any in-flight burst, then releases all pages. Engine thread only."""
        self._drain_inflight()
        self._commit_prefill_ahead()
        out: list[Sequence] = []
        for seq in (
            list(self.scheduler.waiting)
            + list(self.scheduler.prefilling)
            + list(self.scheduler.running)
        ):
            self.scheduler.on_preempted(seq)  # removes from running/prefilling
            if seq in self.scheduler.waiting:
                self.scheduler.waiting.remove(seq)
            self.block_manager.free_sequence(seq)
            seq.status = SequenceStatus.FINISHED
            seq.finish_reason = "abort"
            seq.finish_time = time.monotonic()
            self.lifecycle_stats["aborted"] += 1
            self.finished.append(seq)
            out.append(seq)
        if out:
            self.block_manager.flush_events()
            log.warning("aborted all live requests", count=len(out))
        return out

    def freeze_for_migration(
        self, request_id: str
    ) -> Optional[tuple[Sequence, list[int]]]:
        """Freeze a live request for live migration (``FLEET_CONTROLLER``
        scale-down): commit any in-flight burst, remove the sequence from
        scheduling preemption-style — its registered pages survive in the
        prefix cache, exportable by chain hash — fold generated tokens
        into the prompt (the continuation context), and park it back in
        the waiting queue ``importing`` so the scheduler skips it while
        the wire transfer runs. Returns ``(seq, chain_hashes)`` — the
        hashes of the folded prompt's full pages, i.e. exactly the chain
        ``export_kv_blocks`` can serve this same engine-loop cycle — or
        None when no live sequence carries ``request_id`` (or it is
        already importing/migrating). The caller MUST later either finish
        the sequence (migration committed) or clear ``importing``
        (fallback: local recompute, pages back to baseline). Engine
        thread only."""
        if (self.model_cfg.n_conv_layers or self.model_cfg.n_window_layers
                or self.model_cfg.n_kda_layers):
            self._refuse_latent_page_moves("freeze_for_migration")
        self._commit_prefill_ahead()
        seq = None
        for cand in (
            list(self.scheduler.waiting)
            + self.scheduler.prefilling
            + self.scheduler.running
        ):
            if cand.request_id == request_id:
                seq = cand
                break
        if seq is None or seq.importing or self._should_finish(seq):
            return None
        if self._inflight is not None and any(
            s is seq for s in self._inflight["active"]
        ):
            self._drain_inflight()
        if seq in self.scheduler.waiting:
            self.scheduler.waiting.remove(seq)
        else:
            self.scheduler.on_preempted(seq)  # removes from running/prefilling
        self.block_manager.free_sequence(seq)
        seq.fold_for_preemption()
        seq.importing = True
        self.scheduler.waiting.append(seq)
        # Ship the release events now: the index must not advertise this
        # pod as exclusive holder of pages a scale-down is about to move.
        self.block_manager.flush_events()
        self.lifecycle_stats["migration_frozen"] = (
            self.lifecycle_stats.get("migration_frozen", 0) + 1
        )
        return seq, self.block_manager.token_db.prefix_hashes(seq.prompt_tokens)

    def finish_migrated(self, seq: Sequence) -> None:
        """Commit a migration: the target resumed ``seq``, so finish the
        local half (pages were already released at freeze; the parked
        waiting entry is withdrawn) with ``finish_reason="migrated"`` —
        the submit future resolves with the partial sequence whose
        ``generated_tokens`` the target continues. Engine thread only."""
        seq.importing = False
        if seq in self.scheduler.waiting:
            self.scheduler.waiting.remove(seq)
        seq.status = SequenceStatus.FINISHED
        seq.finish_reason = "migrated"
        seq.finish_time = time.monotonic()
        self.lifecycle_stats["migrated_out"] = (
            self.lifecycle_stats.get("migrated_out", 0) + 1
        )
        self.finished.append(seq)

    def cancel_migration(self, seq: Sequence) -> None:
        """Roll back a freeze (wire failure / target refusal): clear
        ``importing`` so the scheduler re-admits the folded sequence —
        warm re-prefill over whatever registered pages survived, cold
        recompute at worst, exactly the legacy preemption outcome. Pages
        are already back to baseline (freeze released them). Engine
        thread only."""
        seq.importing = False
        self.lifecycle_stats["migration_fallback"] = (
            self.lifecycle_stats.get("migration_fallback", 0) + 1
        )

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    @property
    def has_ready_work(self) -> bool:
        """``has_work`` minus waiting sequences still importing their
        async-pulled prefix — the serving loop's step gate, so a stalled
        wire parks the loop on its condition instead of busy-spinning."""
        return self.scheduler.has_ready_work

    def step(self) -> list[Sequence]:
        """One engine iteration. Returns sequences finished this step.

        Legacy scheduling runs either a prefill batch or a decode step.
        With ``chunked_prefill_tokens`` set the scheduler returns a MIXED
        step — a budgeted chunk batch *and* every running decode lane —
        and both dispatch in the same iteration, so a long prompt's ingest
        never stalls running decodes for more than one chunk's compute.

        A step may end with work on the device: a decode burst whose
        successor is decided (``_next_schedule_decided``), or the prefill of
        the requests that take the lanes a burst has just ended
        (``_admit_ahead``). That prefill is committed here first, so its
        rows decode in this step and the scheduler below finds them where a
        prefill step would have left them."""
        timed = self.obs_step_timing
        self._commit_prefill_ahead()
        with self.phase("schedule"):
            shed: list[Sequence] = []
            if self._deadlines_used:
                # Deadline shedding BEFORE scheduling: an expired waiting
                # seq must never reach prefill, and an expired mid-prefill
                # seq releases its pages for work that can still meet its
                # SLO.
                now = time.monotonic()
                shed = self.scheduler.shed_expired(now)
                for seq in shed:
                    seq.finish_time = now
                    self.lifecycle_stats["deadline_shed"] += 1
                    self.finished.append(seq)
            if self.scheduler.qos_enabled:
                # TENANT_QOS priority preemption BEFORE scheduling: when
                # the highest-class waiting prefill cannot allocate, free
                # pages by preempting one strictly lower-class active
                # sequence so the schedule below can admit it.
                self._preempt_for_priority()
            if (
                self.config.host_prefetch
                and self.config.block_manager.host_pages
            ):
                # Host-tier prefetch AHEAD of the scheduler: waiting
                # sequences' host-cached prefixes start their device↔host
                # copies now, so they batch into this step's flush
                # (overlapping the dispatch) instead of blocking inside a
                # later allocate.
                self._prefetch_host_pages()
            out = self.scheduler.schedule()
        if out.prefill:
            # A prefill the scheduler hands out here must see committed
            # decode state (page accounting, finish detection): its rows
            # were admitted against what the drain may change. (The one
            # prefill that does follow a burst still on the device is
            # ``_admit_ahead``'s, admitted without a page of that burst's.)
            self._drain_inflight()
            self._run_prefill(out.prefill, out.chunks)
        if out.decode:
            # Mixed step: decode lanes snapshotted at schedule time — a
            # final-chunk sequence published above joins NEXT step (same
            # cadence as a legacy prefill step), and lanes the chunk batch
            # preempted are dropped by the decode paths' block_table/finish
            # filters.
            self._run_decode(out.decode)
        elif not out.prefill:
            self._drain_inflight()

        with self.phase("publish"):
            newly_finished = list(shed)
            for seq in list(self.scheduler.running):
                if self._should_finish(seq):
                    seq.finish_time = time.monotonic()
                    self.scheduler.on_finished(seq)
                    self.finished.append(seq)
                    newly_finished.append(seq)
            self.block_manager.flush_events()
        if timed:
            self.step_stats["steps"] += 1
        self._step_count += 1
        return newly_finished

    def run_until_complete(self, max_steps: int = 100_000) -> list[Sequence]:
        done: list[Sequence] = []
        for _ in range(max_steps):
            if not self.has_work:
                break
            done.extend(self.step())
        return done

    # -- internals ----------------------------------------------------------
    def _should_finish(self, seq: Sequence) -> bool:
        if seq.num_generated == 0:
            return False
        if seq.num_generated >= seq.sampling.max_new_tokens:
            return True
        if seq.last_token in seq.sampling.stop_token_ids:
            return True
        if seq.deadline is not None and time.monotonic() >= seq.deadline:
            # Past-deadline running lane: finish with what it has — the
            # client's SLO is blown either way, so stop burning pages and
            # decode lanes on tokens nobody will wait for.
            if seq.finish_reason is None:
                seq.finish_reason = "deadline"
                self.lifecycle_stats["deadline_expired"] += 1
            return True
        return seq.num_tokens >= self.config.max_model_len

    def _run_prefill(
        self, seqs: list[Sequence], chunks: Optional[list[int]] = None
    ) -> None:
        """Prefill one batch and wait for it: ``_enqueue_prefill`` (build,
        upload, dispatch), then ``_commit_prefill`` (fetch, commit)."""
        self._commit_prefill(self._enqueue_prefill(seqs, chunks))

    def _enqueue_prefill(
        self, seqs: list[Sequence], chunks: Optional[list[int]] = None,
        ahead: bool = False,
    ) -> Optional[dict]:
        """Build, upload and dispatch one prefill batch; returns what
        ``_commit_prefill`` needs to fetch and commit it (None: no row was
        left to dispatch). ``chunks[i]`` = prompt tokens to process for
        ``seqs[i]`` this step (chunked mixed-step scheduling); ``None`` =
        each sequence's whole fresh suffix (legacy whole-prompt prefill).
        Either way every row is the same warm-prefill dispatch shape: a
        fresh slice attending over the paged context already resident —
        prefix-cache hits for chunk 0, plus the pages written by chunks
        0..N-1 for later chunks. Only a sequence's FINAL chunk samples a
        first token and publishes it to the decode lanes.

        Block diffusion (``block_length`` > 0): the rows are the prompt's
        whole blocks under the block mask, chunks are cut at block
        boundaries, and the final chunk samples nothing — the prompt's
        tail opens the first generated block, in the decode lanes.

        ``ahead``: the batch follows a decode burst still on the device
        (``_admit_ahead``). Nothing here reads that burst's result: the
        rows write pages they were just given and attend over pages
        registered at earlier commits, and the pools are the burst's own
        outputs, so the device runs the prefill after it. Nothing is
        preempted for such a row: one whose window pages are short goes
        back to the head of the queue."""
        diffusion = self.model_cfg.block_length > 0
        with self.phase("prefill_build"):
            ps = self.page_size
            if chunks is None:
                chunks = [s.prompt_remaining for s in seqs]
            # Queue→compute boundary for the latency decomposition: one clock
            # read per batch, stamped only on each sequence's FIRST chunk.
            t_prefill_start = time.monotonic()
            for seq in seqs:
                if seq.prefill_start_time is None:
                    seq.prefill_start_time = t_prefill_start
            if not any(chunks):
                # Block diffusion only: every whole block of these prompts
                # is cached already (or the prompt is shorter than a block),
                # so there is nothing to forward.
                return {"seqs": seqs, "chunks": chunks, "out": None}
            if self.window_pages is not None:
                # The chunks' window pages, given back and taken before any
                # row is built: taking may preempt (a mid-prefill batchmate
                # too, in chunked mode), and a preempted row leaves the batch.
                rows = [
                    (seq, n) for seq, n in zip(seqs, chunks)
                    if seq.block_table and self._reserve_window_or_requeue(
                        seq, seq.num_prefilled, seq.num_prefilled + n, ahead
                    )
                ]
                # (a later row's reservation may have preempted an earlier one)
                rows = [(seq, n) for seq, n in rows if seq.block_table]
                if not rows:
                    return None
                seqs, chunks = [s for s, _ in rows], [n for _, n in rows]
            # Static shapes for jit-cache stability: batch padded to the
            # configured prefill width, chunk length and context pages
            # bucketed. The width is bucketed on what is left of the whole
            # prompt, the tail a final block-diffusion chunk leaves out
            # included, so a prompt compiles the shapes it would for an
            # autoregressive model.
            chunk = _round_up(
                max(
                    n + (s.prompt_tail if n >= s.prompt_remaining else 0)
                    for s, n in zip(seqs, chunks)
                ),
                self.config.prefill_bucket,
            )
            b = self.config.scheduler.max_prefill_batch

            tokens = np.zeros((b, chunk), np.int32)
            positions = np.zeros((b, chunk), np.int32)
            valid = np.zeros((b, chunk), bool)
            page_ids = np.zeros((b, chunk), np.int32)
            slot_ids = np.zeros((b, chunk), np.int32)
            # Zero-width context when the whole batch is cache-cold: skips the
            # per-layer context gather/score entirely (its own jit trace).
            max_ctx = max(s.num_prefilled // ps for s in seqs)
            ctx_pages = _round_up(max_ctx, self.config.prefill_ctx_bucket)
            ctx_bt = np.zeros((b, ctx_pages), np.int32)
            ctx_lens = np.zeros((b,), np.int32)
            windowed = self.window_pages is not None
            if windowed:
                # the rows' window tables (the window pages that hold their
                # context, from the block ``window_first`` on), bucketed as
                # the context tables are, and their tokens' window pages
                w_ctx = _round_up(
                    max(s.num_prefilled // ps - s.window_first for s in seqs),
                    self.config.prefill_ctx_bucket,
                )
                w_tables = np.zeros((b, w_ctx), np.int32)
                w_page_ids = np.zeros((b, chunk), np.int32)
                w_starts = np.zeros((b,), np.int32)

            for i, (seq, n) in enumerate(zip(seqs, chunks)):
                start = seq.num_prefilled
                tokens[i, :n] = seq.prompt_tokens[start : start + n]
                pos = np.arange(start, start + n)
                positions[i, :n] = pos
                valid[i, :n] = True
                page_ids[i, :n] = np.asarray(seq.block_table, np.int32)[pos // ps]
                slot_ids[i, :n] = pos % ps
                n_ctx_pages = start // ps
                ctx_bt[i, :n_ctx_pages] = seq.block_table[:n_ctx_pages]
                ctx_lens[i] = start
                if windowed:
                    held = np.asarray(seq.window_table, np.int32)
                    n_w = n_ctx_pages - seq.window_first
                    w_tables[i, :n_w] = held[:n_w]
                    w_page_ids[i, :n] = held[pos // ps - seq.window_first]
                    w_starts[i] = seq.window_first * ps

            packed = llama.pack_prefill_inputs(
                tokens, positions, valid, page_ids, slot_ids, ctx_bt, ctx_lens
            )
            # the rows the program computes (``llama.prefill_packed`` reads
            # the same mask on the device): as far as the last that holds a
            # sequence; under a mesh the one body of all ``b``
            held = int(np.flatnonzero(valid.any(axis=1))[-1]) + 1
            job = {
                "seqs": seqs,
                "chunks": chunks,
                "tokens": int(valid.sum()),
                "slots": chunk * (held if self.mesh is None else b),
            }

            uploads, second = [packed], ()
            if windowed:
                second = ("window_packed",)
                uploads.append(
                    llama.pack_window_rows(w_page_ids, w_tables, w_starts)
                )
            elif self.block_manager.state is not None:
                # the slot each row's state is read from and written to
                # (rows that hold no sequence: the reserved slot 0)
                second = ("state_slots",)
                slots = np.zeros((b, 2), np.int32)
                for i, seq in enumerate(seqs):
                    slots[i] = seq.state_from, seq.state_slot
                uploads.append(slots)

        with self.phase("prefill_put"):
            packed_d, *window_d = self._stage("prefill", *uploads)
            window = dict(zip(second, window_d))
            t0 = time.perf_counter()
        with self.phase("prefill_dispatch"):
            out = llama.prefill_packed(
                self.params,
                self.model_cfg,
                packed_d,
                self.k_pages,
                self.v_pages,
                chunk=chunk,
                mesh=self.mesh,
                attn_impl=self.prefill_attn,
                k_scales=self.k_scales,
                v_scales=self.v_scales,
                interpret=self.config.interpret,
                **self._state_arg(),
                **window,
            )
            out = self._keep_pools(out)
        if not diffusion:
            # (nothing is sampled from a block-diffusion prefill)
            out = self._sample(out, seqs)
        if ahead and self.obs_step_timing:
            self.step_stats["admit_ahead"] += len(seqs)
        # (a dispatch that waited behind a burst would time that burst too)
        return {**job, "out": out, "t0": None if ahead else t0}

    def _commit_prefill_ahead(self) -> None:
        """Fetch and commit the prefill ``_admit_ahead`` left on the device,
        if there is one."""
        if self._prefill_ahead is not None:
            job, self._prefill_ahead = self._prefill_ahead, None
            self._commit_prefill(job)

    def _commit_prefill(self, job: Optional[dict]) -> None:
        """The second half of a prefill (``_enqueue_prefill``'s result):
        wait for the dispatch, fetch the first tokens, and make the rows
        whose prompt is through running lanes."""
        if job is None:
            return
        seqs, chunks = job["seqs"], job["chunks"]
        diffusion = self.model_cfg.block_length > 0
        if job["out"] is None:
            # nothing was forwarded (block diffusion only)
            self.scheduler.on_prefill_done(seqs)
            return
        with self.phase("prefill_fetch"):
            if diffusion:
                # the wait for the dispatch keeps the phase what it is
                jax.block_until_ready(job["out"])
                first_tokens = [None] * len(seqs)
            else:
                first_tokens = np.asarray(job["out"])  # syncs the dispatch
        with self.phase("prefill_commit"):
            if job["t0"] is not None:
                # Online prefill-rate sample for the recompute-vs-restore
                # model (chunk tokens over the synced dispatch wall time).
                self._prefill_rate = self._ema(
                    self._prefill_rate,
                    job["tokens"] / max(time.perf_counter() - job["t0"], 1e-6),
                )
            self.prefill_stats["tokens_computed"] += job["tokens"]
            self.prefill_stats["dispatches"] += 1
            self.prefill_stats["token_slots"] += job["slots"]
            now = time.monotonic()
            finals = [
                seq for seq, n in zip(seqs, chunks) if n >= seq.prompt_remaining
            ]
            # Admit to running BEFORE appending slots: batchmates must be
            # preemption candidates if page growth exhausts the pool here.
            self.scheduler.on_prefill_done(finals)
            for (seq, n), tok in zip(zip(seqs, chunks), first_tokens):
                if not seq.block_table:
                    continue  # preempted by an earlier seq in this very batch
                seq.num_prefilled += n
                seq.num_computed = seq.num_prefilled
                if seq.prompt_remaining == 0 and not diffusion:
                    # Final chunk: the last-position logits are the first-token
                    # logits of the whole prompt — sample and publish.
                    seq.output_tokens.append(int(tok))
                    seq.num_generated += 1
                    if seq.first_token_time is None:
                        seq.first_token_time = now
                    self._append_slot_or_preempt(seq)
                self.block_manager.register_full_pages(seq)
                if seq.block_table:
                    self.block_manager.state_prefill_done(seq)

    def state_pool_stats(self) -> dict:
        """What ``/stats`` says of the linear layers' state pool of slots
        (nothing for every other model): its size, the snapshots held, what a
        snapshot weighs and how many tokens lie between two, and the block
        manager's monotone counts (``StatePool.stats``)."""
        st = self.block_manager.state
        if st is None:
            return {}
        return {
            "state_slots": st.n_slots,
            "state_snapshots_held": st.num_snapshots,
            "state_bytes_per_snapshot": self.model_cfg.kda_state_bytes,
            "state_snapshot_tokens": st.stride,
            **st.stats,
        }

    def _state_arg(self) -> dict:
        """The pool beside the key/value pools as ``llama.prefill`` /
        ``decode_steps`` take it: the state pool or the pair of window pools,
        a keyword a model without convolution or sliding layers never
        sees."""
        if self.state_pages is not None:
            return {"state_pages": self.state_pages}
        if self.window_pages is not None:
            return {"window_pages": self.window_pages}
        return {}

    def _keep_pools(self, out: tuple):
        """Take back the donated pools a model program returned after its
        first result, ``(first, k_pages, v_pages[, k_scales, v_scales][,
        state_pages][, window_pages])``; returns the first."""
        first, self.k_pages, self.v_pages, *rest = out
        if self.k_scales is not None:
            self.k_scales, self.v_scales, *rest = rest
        if self.state_pages is not None:
            (self.state_pages,) = rest
        if self.window_pages is not None:
            (self.window_pages,) = rest
        return first

    def _decode_table_width(self, seqs: list[Sequence]) -> int:
        """Block-table width for this decode call: longest active context in
        pages, rounded up to ``decode_pages_bucket`` for jit-cache stability
        (a handful of compiled shapes instead of one worst-case shape that
        DMAs max_model_len worth of pages for every sequence)."""
        used = max((len(s.block_table) for s in seqs), default=1)
        bucket = max(1, self.config.decode_pages_bucket)
        return min(self.max_pages_per_seq, _round_up(used, bucket))

    def _run_decode(self, seqs: list[Sequence]) -> None:
        if self.model_cfg.block_length > 0:
            self._run_decode_block(seqs)
            return
        if self.config.spec_decode == "prompt_lookup":
            # Commit lag: the drain can finish lanes — never reserve for or
            # dispatch a finished sequence (same rule as the fused path).
            # Lanes a mixed step's prefill half preempted (empty block
            # table) are dropped too: their proposals must not defeat the
            # all-empty fast path back to plain decode.
            self._drain_inflight()
            seqs = [
                s for s in seqs
                if s.block_table and not self._should_finish(s)
            ]
            if not seqs:
                return
            if self._run_decode_spec(seqs):
                return
            # Every lane's proposal came up empty: a verify dispatch would
            # emit exactly one token at prefill-dispatch cost — fall
            # through to the strictly cheaper plain/fused decode step.
        # Every decode goes through the fused path — at k=1 it is the
        # classic step-per-token loop, but sampling happens ON DEVICE
        # inside the same dispatch (one transfer of sampled ids instead of
        # a [lanes, vocab] logit round-trip per token). One decode
        # implementation; `llama.decode_step` remains as the model-level
        # logits API for tests and external callers.
        self._run_decode_fused(seqs)

    def _next_schedule_decided(
        self, active: list[Sequence], k: "int | list[int]"
    ) -> bool:
        """The rule for running one decode dispatch ahead, read when the
        burst over ``active`` has been enqueued: may it stay on the device
        over the end of this step, so that the next step enqueues its
        successor before fetching it? Only where the next schedule is
        already decided, the same lanes decoding again. ``k``: the tokens
        the dispatch may add to a lane, one number for a fused burst (its
        steps) or one a lane for a forward of ``_run_decode_block`` (the new
        tokens of the lane's block where the forward commits it, 0 where it
        denoises):

        - the scheduler could admit nothing (``admission_closed``) and
          every running lane is in this burst. With a lane free an arrival
          must find no burst in the way of its prefill;
        - no lane reaches its token budget (``max_new_tokens``,
          ``max_model_len``) within this burst, which the host knows before
          the burst returns: the lane leaves with this step, no surplus row
          is computed for it, and the request that takes its place is
          admitted now, behind this burst (``_admit_ahead``), or by the
          next step. (A lane the commit just before found finished, by a
          stop token or a deadline, leaves the same way.)

        What it cannot foresee — a stop token inside the burst, a deadline,
        an abort, a preemption, a migration — meets the drains."""
        return (
            _same_lanes(active, self.scheduler.running)
            and self.scheduler.admission_closed()
            and not self._lanes_leaving(active, k)
        )

    def _lanes_leaving(self, active: list[Sequence], k: "int | list[int]") -> int:
        """How many running lanes this step's ``publish`` is certain to
        finish, known while the burst over ``active`` is still on the
        device (``k`` as ``_next_schedule_decided`` takes it): those that
        reach their token budget within the burst, and those the commit
        just before found finished."""
        limit = self.config.max_model_len
        gains = itertools.repeat(k) if isinstance(k, int) else k
        gain_of = {id(seq): gain for seq, gain in zip(active, gains)}

        def leaves(seq: Sequence) -> bool:
            gain = gain_of.get(id(seq), 0)  # (a lane not in the burst: none)
            return (
                seq.num_generated + gain >= seq.sampling.max_new_tokens
                or seq.num_tokens + gain >= limit
                or self._should_finish(seq)
            )

        return sum(map(leaves, self.scheduler.running))

    def _admit_ahead(self, active: list[Sequence], k: "int | list[int]") -> None:
        """Admission ahead, at the seam where the burst over ``active`` has
        been enqueued and is about to be fetched because lanes of it leave:
        the requests that take those lanes are admitted NOW
        (``Scheduler.schedule(leaving=)``: the next step's walk, the same
        head, budget and roll-back) and their prefill is built, uploaded
        and dispatched behind the burst (``_enqueue_prefill``), so the
        host's work of an admission runs while the device does. The burst
        is fetched and committed after it, ``publish`` finishes the lanes,
        and the next ``step()`` finds the prefill in ``_prefill_ahead``.
        The device's order is the waiting engine's: this burst, the
        prefill, the next burst with the new lanes.

        A rule read from the engine's own state, no switch. It DECLINES,
        and the next step admits as it always did, wherever the view from
        here could differ from the view one step later:

        - no lane is certain to leave (``_lanes_leaving``: a stop token or a
          deadline is not foreseen), nobody waits, or the head is still
          importing;
        - the head cannot allocate as the pool stands: the leaving lanes'
          pages are theirs until ``publish``, and nothing is preempted for
          an admission ahead (``can_allocate`` in the walk; a row short of
          window pages goes back to the head of the queue);
        - tenant QoS (an arrival of a higher class becomes the head),
          deadlines (shedding comes before a step's walk), chunked prefill
          (a mixed step), speculation, or a chunk still owed.

        A state pool or a window pool is no reason: what an admission reads
        there (snapshots, last windows) was registered at earlier commits.
        What is NOT seen one step early is what the leaving lanes register
        at their last commit: a successor whose prompt continues its
        predecessor's output hits those blocks a step later and not here.

        Outputs are the waiting engine's. Greedy lanes: token for token
        (the device runs the same programs in the same order). Sampled
        lanes: an admission ahead draws no key the waiting engine would not
        draw (the prefill's sampler takes its split of ``_rng`` after this
        burst's and before the next one's, where it took it one step
        later), so the streams are the same too unless a request arrives
        between the two steps and the waiting engine batches it with these:
        identically distributed then, as after a discarded surplus burst."""
        sched = self.scheduler
        if (
            not sched.waiting
            or sched.prefilling
            or sched.qos_enabled
            or self._deadlines_used
            or sched.config.chunked_prefill_tokens is not None
            or self.config.spec_decode != "off"
        ):
            return
        leaving = self._lanes_leaving(active, k)
        if not leaving:
            return
        with self.phase("schedule"):
            out = sched.schedule(leaving=leaving)
        if out.prefill:
            self._prefill_ahead = self._enqueue_prefill(
                out.prefill, out.chunks, ahead=True
            )

    def _run_decode_fused(self, seqs: list[Sequence]) -> None:
        """Fused multi-token decode: reserve page capacity for the whole
        burst up front, run ``decode_steps`` (on-device sampling, single
        host sync), then commit sampled tokens per sequence, truncating at
        stop conditions. Surplus device-side KV writes land in pages the
        sequence owns (or reserved page 0 for padded lanes) and are never
        registered in the prefix cache, so discarding them is safe.

        One dispatch ahead where that is free: a burst that
        ``_next_schedule_decided`` left in flight is not fetched before its
        successor is enqueued. The successor's input ids are that burst's
        own sampled ids, still on the device, so the host's work of a step
        (commit, publish, schedule, build, uploads, dispatch) runs while
        the device does. The chain only continues while the lane set is
        unchanged; anything else drains first, making greedy results
        identical to the engine that never runs ahead (a finished or
        preempted lane's surplus burst is discarded by the same rules as
        surplus tokens within a burst). temperature>0 streams are
        identically distributed but not bit-identical — discarded surplus
        bursts consume extra engine-rng splits."""
        k = self.config.decode_steps_per_iter
        lanes = self.config.decode_batch_size
        assert len(seqs) <= lanes

        prev = self._inflight
        if prev is not None:
            # What the rule could not foresee changed the lane set (a stop
            # token, a deadline, an abort, a preemption): commit first.
            if not _same_lanes(prev["active"], seqs):
                self._drain_inflight()
                prev = None

        # Commit lag means any drain can finish lanes mid-call; never
        # reserve pages for (or redispatch) a finished sequence — the
        # engine that never runs ahead would have finished it a step() ago.
        seqs = [s for s in seqs if not self._should_finish(s)]
        if not seqs:
            return

        with self.phase("decode_build"):
            # Reserve capacity for the burst's growth per sequence (× 2 over
            # a burst still in flight, whose tokens are not yet counted);
            # preemption inside reservation may knock batchmates out of
            # `seqs` — or the in-flight set.
            reserve = k * (2 if prev is not None else 1)
            for seq in seqs:
                # The finished re-check matters after a mid-loop degrade-drain
                # (below): committing the lagged burst can finish any lane, and
                # reserving (worse: preempting a batchmate, or aborting) for a
                # sequence that already completed is the waiting engine's
                # never-happens case.
                if not seq.block_table or self._should_finish(seq):
                    continue
                if reserve > k:
                    # Double-burst headroom is an optimization, not a
                    # requirement: when the pool is too tight for it, drain and
                    # degrade to the single reservation rather than
                    # preempting/aborting lanes the waiting engine would
                    # complete. (Preemption stays reserved for genuine
                    # single-burst pressure below, keeping behavior identical
                    # to the engine that never runs ahead under the same pool.)
                    try:
                        self.block_manager.reserve_slots(seq, reserve)
                        continue
                    except AllocationError:
                        self._drain_inflight()
                        prev = None
                        reserve = k
                        if self._should_finish(seq):
                            continue  # the drain just finished this lane
                self._reserve_slots_or_preempt(seq, reserve)
            # A degrade-drain above may also have finished lanes.
            active = [
                s for s in seqs if s.block_table and not self._should_finish(s)
            ]
            if prev is not None:
                # reservation preempted an in-flight lane
                if not _same_lanes(prev["active"], active):
                    self._drain_inflight()
                    prev = None
                    active = [s for s in active if not self._should_finish(s)]
            if not active:
                self._drain_inflight()
                return

            positions = np.zeros((lanes,), np.int32)
            seq_lens = np.zeros((lanes,), np.int32)  # 0 = inactive lane
            block_tables = np.zeros((lanes, self._decode_table_width(active)), np.int32)
            temperature = np.zeros((lanes,), np.float32)
            top_k = np.zeros((lanes,), np.int32)
            top_p = np.ones((lanes,), np.float32)

            for i, seq in enumerate(active):
                bt = seq.block_table
                block_tables[i, : len(bt)] = bt
                temperature[i] = seq.sampling.temperature
                top_k[i] = seq.sampling.top_k
                top_p[i] = seq.sampling.top_p

            if prev is not None:
                # Chain from the in-flight burst: positions/lengths advance
                # by k without a host sync. Inactive padded lanes keep their
                # 0 = inactive sentinel — they must not run garbage attention
                # or write KV into reserved page 0 just because the active
                # lanes advanced.
                was_active = prev["seq_lens"] > 0
                positions = np.where(was_active, prev["positions"] + k, 0)
                seq_lens = np.where(was_active, prev["seq_lens"] + k, 0)
            else:
                # The program takes its input ids from the last column of a
                # burst's [lanes, counts + k] output; an unchained dispatch
                # hands it the same shape, so both are one compiled program.
                tokens = np.zeros((lanes, self._burst_counts + k), np.int32)
                for i, seq in enumerate(active):
                    tokens[i, -1] = seq.last_token
                    positions[i] = seq.num_tokens - 1
                    seq_lens[i] = seq.num_tokens

            packed = llama.pack_decode_inputs(
                positions, block_tables, seq_lens, temperature, top_k, top_p
            )
            uploads, second = [packed], ()
            if self.window_pages is not None:
                # the lanes' window tables, one fixed width (a window and
                # what a boundary and two bursts add), and where each starts
                second = ("window_packed",)
                w_tables = np.zeros((lanes, self.window_table_pages), np.int32)
                w_starts = np.zeros((lanes,), np.int32)
                for i, seq in enumerate(active):
                    w_tables[i, : len(seq.window_table)] = seq.window_table
                    w_starts[i] = seq.window_first * self.page_size
                uploads.append(llama.pack_window_rows(None, w_tables, w_starts))
            elif self.block_manager.state is not None:
                # the lanes' state slots ``[a, b, switch]`` (idle lanes: the
                # reserved slot 0); a lane that passes a boundary inside the
                # burst leaves its slot behind there and goes on in another
                second = ("state_slots",)
                slots = np.zeros((lanes, 3), np.int32)
                for i, seq in enumerate(active):
                    slots[i] = self.block_manager.state_decode_slots(
                        seq, int(positions[i]), k
                    )
                self.block_manager.state_release_reads(active)
                uploads.append(slots)

        with self.phase("decode_put"):
            key = self._draw_key(temperature)
            if prev is not None:
                # chained: the burst's sampled ids stay on the device, placed
                # as an upload is (no copy where they already lie so)
                packed_d, *window_d = self._stage("decode", *uploads)
                tokens_d = jax.device_put(prev["toks"], self._replicated)
            else:
                packed_d, tokens_d, *window_d = self._stage(
                    "decode", packed, tokens, *uploads[1:]
                )
            window = dict(zip(second, window_d))
        with self.phase("decode_dispatch"):
            out = llama.decode_steps(
                self.params,
                self.model_cfg,
                tokens_d,
                packed_d,
                self.k_pages,
                self.v_pages,
                key,
                page_size=self.page_size,
                num_steps=k,
                interpret=self.config.interpret,
                mesh=self.mesh,
                k_scales=self.k_scales,
                v_scales=self.v_scales,
                **self._state_arg(),
                **window,
            )
            toks = self._keep_pools(out)
        self._count_decode_dispatch(
            len(active), temperature, seq_lens, k, chained=prev is not None,
            block_tables=block_tables,
            window_tables=(
                (w_tables, w_starts) if self.window_pages is not None else None
            ),
        )
        # Start the D2H copy of the sampled ids now: the bytes land while
        # the host goes on (with a burst chained behind this one, while
        # that one runs), so the fetch finds them on the host. A transfer
        # hint: results are unchanged.
        toks.copy_to_host_async()
        burst = {
            "toks": toks,
            "active": active,
            "k": k,
            "positions": positions,
            "seq_lens": seq_lens,
        }
        if prev is not None:
            # Commit burst N while burst N+1 executes on device.
            self._inflight = None
            self._commit_burst(prev)
        if self._next_schedule_decided(active, k):
            self._inflight = burst
        else:
            self._admit_ahead(active, k)
            self._commit_burst(burst)

    def _propose_prompt_lookup(self, seq: Sequence) -> list[int]:
        """Draft-model-free proposals: find the latest earlier occurrence of
        the context's final ``spec_ngram`` tokens and propose the tokens
        that followed it (classic prompt-lookup decoding — strongest on
        extractive/structured generations where the output echoes the
        prompt). Host-side, O(spec_max_scan)."""
        n = self.config.spec_ngram
        # Clamp to the remaining token budget: drafts past budget-1 (the
        # verify emits accepted+1) can never be emitted — scoring them
        # would reserve pages and KV-write positions past the effective
        # cap for nothing under pool pressure. Shares _spec_budget with
        # the device path: round-1 device prop_len must equal this k for
        # the exact single-round reservation to cover the KV writes.
        k = min(self.config.spec_k, self._spec_budget(seq) - 1)
        if k < 1:
            return []
        toks = seq.all_tokens
        if len(toks) < n + 1:
            return []
        if not self._gate_open(seq):
            return []  # adaptive gate: this sequence isn't echoing
        pattern = toks[-n:]
        lo = max(0, len(toks) - 1 - self.config.spec_max_scan)
        # Latest match wins (recency correlates with continuation quality);
        # the terminal occurrence itself (start == len-n) is excluded.
        for start in range(len(toks) - n - 1, lo - 1, -1):
            if toks[start : start + n] == pattern:
                return [int(t) for t in toks[start + n : start + n + k]]
        return []

    def _gate_open(self, seq: Sequence) -> bool:
        """Adaptive spec gate (one-way, per sequence): closed once the
        sample fills with acceptance below the threshold."""
        return not (
            seq.spec_proposed >= self.config.spec_min_sample
            and seq.spec_accepted
            < self.config.spec_min_accept * seq.spec_proposed
        )

    def _spec_budget(self, seq: Sequence) -> int:
        """Remaining emittable tokens (max_new_tokens and max_model_len
        caps) — the ONE definition both the host proposal clamp and the
        device burst's budget array derive from; their agreement is what
        lets the single-round reservation size off the host proposal."""
        return max(
            0,
            min(
                seq.sampling.max_new_tokens - seq.num_generated,
                self.config.max_model_len - seq.num_tokens,
            ),
        )

    def _run_decode_spec(self, seqs: list[Sequence]) -> bool:
        """Speculative decode via prompt-lookup, fused on device: each
        verify round scores the last committed token plus up to ``spec_k``
        proposed tokens — exactly a warm prefill over
        [paged context ++ chunk] with full-position logits — and
        ``spec_rounds`` rounds run inside ONE dispatch
        (``llama.spec_decode_steps``): proposals are matched against a
        device-resident token window, acceptance is computed on device,
        and the window/positions advance on device, so the host syncs once
        per burst instead of once per verify. This composes speculation
        with the fused-burst idea — the serial host round-trip the old
        single-round path paid per verify is amortized across rounds.

        Acceptance: greedy lanes take the longest proposal prefix matching
        the model's own argmax, plus the argmax at the first mismatch (or
        a bonus token when everything matched); temperature>0 lanes run
        deterministic-draft speculative SAMPLING (``ops/sampling.
        spec_sample``) — exact for each lane's filtered distribution.
        A round emits 1..k+1 tokens per lane and never fewer than plain
        decode. Returns False (nothing dispatched) when every lane's
        round-1 proposal is empty; the caller then runs the cheaper
        plain/fused step. Later rounds whose proposals dry up degrade to
        one-token verify rounds (correct; costs one chunk forward).

        Greedy emitted tokens are the model's choices as scored by the
        PREFILL path; in interpret/XLA numerics that is bit-identical to
        plain greedy decode (the parity the tests pin). On-chip, verify
        (flash-prefill kernel) and plain decode (paged-attention kernel)
        reduce in different orders, so a near-tie can resolve differently
        — outputs remain exact samples of the verify logits, but
        cross-path bit-equality is not guaranteed on TPU. Sampled lanes
        consume the engine rng differently from plain decode (identically
        DISTRIBUTED, not bit-identical — the caveat of a burst run ahead).

        Rejected drafts leave stale K/V in slots the sequence already owns
        beyond ``num_computed``; nothing ever attends past ``seq_len`` and
        page registration is bounded by ``num_computed``, so rollback is
        pure bookkeeping (same safety argument as fused-decode surplus
        tokens)."""
        import math

        ps = self.page_size
        k = self.config.spec_k
        rounds = self.config.spec_rounds
        # Chunk width must satisfy both the lane alignment and the sp
        # sharding of the prefill path.
        s_chunk = _round_up(k + 1, math.lcm(8, max(1, self.config.sp)))
        b = self.config.decode_batch_size
        assert len(seqs) <= b

        with self.phase("decode_build"):
            # Round-1 proposals are recomputed on device; this host pass (same
            # algorithm) only decides entry — an all-empty round must cost
            # nothing (caller falls back to plain decode) — and sizes the
            # exact single-round reservation.
            prop_by_id = {s.seq_id: self._propose_prompt_lookup(s) for s in seqs}
            if not any(prop_by_id.values()):
                return False

            if rounds > 1:
                # Multi-round bursts reserve the budget-capped worst case
                # (later rounds' proposals are decided on device), which under
                # pool pressure can preempt batchmates for capacity that is
                # mostly unused at low acceptance. When the worst case doesn't
                # fit the free pool, degrade THIS burst to a single round: its
                # reservation is exact (the host proposal), so speculation
                # never evicts a batchmate for headroom it may not use. Shapes
                # stay static per dispatch — the degraded burst uses the
                # spec_rounds=1 executable family (one extra compile the first
                # time pressure hits).
                need = 0
                for seq in seqs:
                    if not seq.block_table:
                        continue
                    worst = 1 + min(rounds * (k + 1), self._spec_budget(seq))
                    need += max(
                        0,
                        -(-(seq.num_tokens + worst - 1) // ps)
                        - len(seq.block_table),
                    )
                if need > self.block_manager.num_free:
                    rounds = 1

            # Reserve before building tables (can preempt batchmates — or
            # abort; both leave block_table empty). Single-round bursts
            # reserve the sequence's exact growth (1 committed + its clamped
            # proposals — NOT the lane-aligned/lcm-inflated s_chunk: the KV
            # scatter drops invalid positions, so padding needs no pages);
            # multi-round bursts reserve the budget-capped worst case, since
            # later rounds' proposals are decided on device.
            for seq in seqs:
                if not seq.block_table:
                    continue
                if rounds == 1:
                    n_res = 1 + len(prop_by_id[seq.seq_id])
                else:
                    n_res = 1 + min(rounds * (k + 1), self._spec_budget(seq))
                self._reserve_slots_or_preempt(seq, n_res)
            active = [s for s in seqs if s.block_table]
            if not active:
                return True

            # Device-resident token window: the last `scan_need` committed
            # tokens (everything prompt lookup may match against) plus room
            # for the burst's growth. All int32 inputs ship as ONE packed
            # upload ([window | block_tables | 5 per-lane scalars]) and the
            # f32 sampling params as another, not nine separate small uploads.
            scan_need = min(
                self.config.spec_max_scan + self.config.spec_ngram + 1,
                self.config.max_model_len,
            )
            W = scan_need + rounds * (k + 1)
            table_w = self._decode_table_width(active)
            packed_i32 = np.zeros((b, W + table_w + 5), np.int32)
            fparams = np.zeros((b, 2), np.float32)
            fparams[:, 1] = 1.0  # top_p disabled default for padded lanes

            for i, seq in enumerate(active):
                toks = seq.all_tokens
                n_win = min(len(toks), scan_need)
                packed_i32[i, :n_win] = toks[-n_win:]
                packed_i32[i, W : W + len(seq.block_table)] = seq.block_table
                packed_i32[i, W + table_w] = n_win  # wlen
                packed_i32[i, W + table_w + 1] = seq.num_tokens
                packed_i32[i, W + table_w + 2] = self._spec_budget(seq)
                packed_i32[i, W + table_w + 3] = int(self._gate_open(seq))
                packed_i32[i, W + table_w + 4] = seq.sampling.top_k
                fparams[i, 0] = seq.sampling.temperature
                fparams[i, 1] = seq.sampling.top_p

        with self.phase("decode_put"):
            key = self._draw_key(fparams[:, 0])
            packed_i32_d, fparams_d = self._stage("decode", packed_i32, fparams)
        with self.phase("decode_dispatch"):
            packed, self.k_pages, self.v_pages = (
                llama.spec_decode_steps(
                    self.params,
                    self.model_cfg,
                    packed_i32_d,
                    fparams_d,
                    self.k_pages,
                    self.v_pages,
                    key,
                    page_size=ps,
                    num_rounds=rounds,
                    s_chunk=s_chunk,
                    ngram=self.config.spec_ngram,
                    spec_k=k,
                    max_scan=self.config.spec_max_scan,
                    table_w=table_w,
                    mesh=self.mesh,
                    attn_impl=self.prefill_attn,
                    interpret=self.config.interpret,
                )
            )
        self._count_decode_dispatch(len(active), fparams[:, 0], steps=rounds)
        # The one host sync of the burst: ONE packed fetch (emit tokens +
        # per-round counters in a single array — separate fetches would
        # serialize several blocking round-trips on high-latency links).
        with self.phase("decode_fetch"):
            packed = np.asarray(packed)  # [rounds, b, k+4]
        with self.phase("decode_commit"):
            emit = packed[..., : k + 1]
            emit_len = packed[..., k + 1]
            prop_len = packed[..., k + 2]
            acc = packed[..., k + 3]

            self.spec_stats["verify_steps"] += rounds
            self.spec_stats["bursts"] += 1
            for i, seq in enumerate(active):
                if not seq.block_table:
                    continue  # preempted by a batchmate's reservation
                for r in range(rounds):
                    if self._should_finish(seq):
                        break  # later rounds are surplus (discarded)
                    # Stats/gate updates only for rounds whose emissions are
                    # (at least partly) committed: a discarded surplus round
                    # would inflate the reported acceptance rate and mutate
                    # gate state for a finished sequence.
                    pl = int(prop_len[r, i])
                    ac = int(acc[r, i])
                    self.spec_stats["proposed"] += pl
                    self.spec_stats["accepted"] += ac
                    seq.spec_proposed += pl
                    seq.spec_accepted += ac
                    for j in range(int(emit_len[r, i])):
                        if self._should_finish(seq):
                            break
                        seq.num_computed = seq.num_tokens
                        seq.output_tokens.append(int(emit[r, i, j]))
                        seq.num_generated += 1
                # The burst reservation covered exactly the burst's writes; a
                # full acceptance in the last committed round advances
                # num_tokens past them, so the NEXT dispatch's input token
                # (written at the new num_tokens - 1) needs its slot ensured
                # here — same post-emit append every other decode path does;
                # without it the write lands in padding page 0.
                if not self._should_finish(seq):
                    self._append_slot_or_preempt(seq)
                self.block_manager.register_full_pages(seq)
        return True

    def _run_decode_block(self, seqs: list[Sequence]) -> None:
        """One step of generation by diffusion over blocks for every lane:
        one forward of each lane's block in progress against its paged
        context (``llama.denoise_steps``), confidence and transfer on the
        device, one packed fetch.

        A lane whose block still has masked rows gets between one and
        ``block_length`` of them fixed (its ``denoising_steps`` and
        ``confidence_threshold`` ride the dispatch as data); a lane whose
        block has none left runs the COMMITTING forward, which alone
        stores final keys and values: the block's tokens then become
        output (``num_generated``, ``first_token_time``: the earliest a
        client could be shown them in order), its pages become registrable
        and the next dispatch opens the next block. So a dispatch advances
        a lane by 0..``block_length`` tokens, and lanes sit at different
        points of their blocks.

        One dispatch ahead where that is free, in ``_run_decode_fused``'s
        order and under its rule: a forward that ``_next_schedule_decided``
        left in flight is not fetched before its successor is enqueued.
        What the successor needs of a lane the forward in flight is
        denoising is that forward's own result, still on the device
        (``denoise_steps``' ``carried``: the tokens after the step and the
        rows still masked; the host adds ``step + 1``); a lane whose block
        the forward in flight commits opens its next block, masks at
        ``num_computed + block_length``, which the host writes without the
        result. Which of the two a lane is the host knows from the block as
        the forward in flight was given it (``Sequence.block_masked``, the
        commit just before). Anything but an unchanged lane set drains
        first, so greedy results are those of the engine that never runs
        ahead; a lane found finished or preempted when its forward is
        committed loses that forward (``_commit_block``).

        What a denoising forward writes lies beyond ``num_computed`` in
        pages reserved a whole block ahead (two, for a lane whose block the
        forward in flight commits), which nothing reads and no
        event names (registration stops at ``num_computed``: the argument
        ``_run_decode_spec`` makes for rejected drafts). A lane preempted or
        aborted in the middle of a block loses the block, not its final
        tokens: ``fold_for_preemption`` closes it and it starts again from
        masks."""
        cfg = self.model_cfg
        width = cfg.block_length
        lanes = self.config.decode_batch_size
        assert len(seqs) <= lanes

        prev = self._inflight
        if prev is not None and not _same_lanes(prev["active"], seqs):
            # what the rule could not foresee changed the lane set
            self._drain_inflight()
            prev = None
        # a drain can finish lanes (commit lag), as on the fused path
        seqs = [s for s in seqs if not self._should_finish(s)]
        if not seqs:
            self._drain_inflight()
            return

        def commits(seq: Sequence) -> bool:
            """The forward in flight is ``seq``'s committing one."""
            return prev is not None and seq.block_fixed

        with self.phase("decode_build"):
            # Pages for the whole block ahead (positions below num_computed +
            # width); reserving can preempt batchmates, or abort.
            for seq in seqs:
                if not seq.block_table or self._should_finish(seq):
                    continue
                if commits(seq):
                    # ... and for the block after it, which this dispatch
                    # opens. Not worth a lane: a pool too tight for it
                    # drains and waits, as the fused path's second burst.
                    try:
                        self.block_manager.reserve_slots(
                            seq, seq.num_computed + 2 * width + 1 - seq.num_tokens
                        )
                        continue
                    except AllocationError:
                        self._drain_inflight()
                        prev = None
                        if self._should_finish(seq):
                            continue  # the drain just finished this lane
                self._reserve_slots_or_preempt(
                    seq, seq.num_computed + width + 1 - seq.num_tokens
                )
            active = [
                s for s in seqs if s.block_table and not self._should_finish(s)
            ]
            if prev is not None and not _same_lanes(prev["active"], active):
                # reservation preempted a lane of the forward in flight
                self._drain_inflight()
                prev = None
                active = [s for s in active if not self._should_finish(s)]
            if not active:
                self._drain_inflight()
                return
            table_w = self._decode_table_width(active)
            # ONE int32 and ONE f32 upload, as ``spec_decode_steps`` packs:
            # [tokens | masked | block_table | seq_len, step, steps, top_k,
            # active] and (threshold, temperature, top_p).
            packed_i32 = np.zeros((lanes, 2 * width + table_w + 5), np.int32)
            fparams = np.zeros((lanes, 3), np.float32)
            fparams[:, 2] = 1.0  # top_p disabled default for padded lanes
            tail = 2 * width + table_w
            for i, seq in enumerate(active):
                if seq.block_tokens is None:
                    # (chained: the block the forward in flight was given)
                    seq.open_block(cfg.mask_token_id)
                sp = seq.sampling
                start, step, source = seq.num_computed, seq.block_step, 1
                if prev is None:
                    packed_i32[i, :width] = seq.block_tokens
                    packed_i32[i, width : 2 * width] = seq.block_masked
                elif commits(seq):
                    start, step = start + width, 0
                    packed_i32[i, :width] = cfg.mask_token_id
                    packed_i32[i, width : 2 * width] = 1
                else:
                    step, source = step + 1, llama.BLOCK_CARRIED
                packed_i32[i, 2 * width : 2 * width + len(seq.block_table)] = (
                    seq.block_table
                )
                packed_i32[i, tail:] = (
                    start, step, sp.denoising_steps or width, sp.top_k, source,
                )
                fparams[i] = (
                    DEFAULT_CONFIDENCE_THRESHOLD
                    if sp.confidence_threshold is None
                    else sp.confidence_threshold,
                    sp.temperature, sp.top_p,
                )

        with self.phase("decode_put"):
            key = self._draw_key(fparams[:, 1])
            packed_i32_d, fparams_d = self._stage("decode", packed_i32, fparams)
            # One program a (lanes, table width): every dispatch hands the
            # operand over, an unchained one an array that lies there and
            # that no lane's word points at.
            carried_d = (
                self._no_block if prev is None
                else jax.device_put(prev["packed"], self._replicated)
            )
        with self.phase("decode_dispatch"):
            packed, self.k_pages, self.v_pages = llama.denoise_steps(
                self.params,
                cfg,
                packed_i32_d,
                fparams_d,
                self.k_pages,
                self.v_pages,
                key,
                page_size=self.page_size,
                table_w=table_w,
                mesh=self.mesh,
                attn_impl=self.prefill_attn,
                interpret=self.config.interpret,
                carried=carried_d,
            )
        self._count_decode_dispatch(
            len(active), fparams[:, 1], chained=prev is not None
        )
        packed.copy_to_host_async()  # as the fused path's: a hint
        burst = {"packed": packed, "active": active}
        if prev is not None:
            # commit forward N while forward N+1 runs
            self._inflight = None
            self._commit_block(prev)
        # What this forward may add to a lane is known now: a whole block's
        # new tokens where it is the committing one, else nothing.
        gains = [
            width - (seq.num_tokens - seq.num_computed) if seq.block_fixed else 0
            for seq in active
        ]
        if self._next_schedule_decided(active, gains):
            self._inflight = burst
        else:
            self._admit_ahead(active, gains)
            self._commit_block(burst)

    def _commit_block(self, burst: dict) -> None:
        """Fetch and commit one dispatch of ``_run_decode_block``: each
        lane's block is, on the host, still what that forward was given. A
        lane that finished or was preempted since the forward was enqueued
        (a stop token inside the block committed before it, a deadline, an
        abort) loses it: its writes lie beyond ``num_computed`` in pages the
        lane owned, and it is counted nowhere."""
        width = self.model_cfg.block_length
        with self.phase("decode_fetch"):
            packed = np.asarray(burst["packed"])  # [lanes, 2 * width + 1]
        with self.phase("decode_commit"):
            now = time.monotonic()
            n_denoise = n_commit = n_fixed = 0
            for i, seq in enumerate(burst["active"]):
                if not seq.block_table or self._should_finish(seq):
                    continue
                if seq.block_tokens is None:
                    # a chained forward opened this block itself
                    seq.open_block(self.model_cfg.mask_token_id)
                if not seq.block_fixed:
                    # a denoising forward: some of its masked rows are fixed
                    n_denoise += 1
                    still = packed[i, width : 2 * width] != 0
                    n_fixed += sum(seq.block_masked) - int(still.sum())
                    seq.block_tokens = packed[i, :width].tolist()
                    seq.block_masked = still.tolist()
                    seq.block_step += 1
                    continue
                # the committing forward: the block is final
                n_commit += 1
                fresh = seq.block_tokens[seq.num_tokens - seq.num_computed :]
                seq.block_tokens = seq.block_masked = None
                for tok in fresh:
                    # the last block's surplus rows (max_new_tokens, a stop
                    # token inside the block) are dropped
                    if self._should_finish(seq):
                        break
                    seq.output_tokens.append(tok)
                    seq.num_generated += 1
                # final keys and values stand for every kept token; a block
                # cut short ends the sequence, and its page is never full
                seq.num_computed = seq.num_tokens
                if seq.first_token_time is None:
                    seq.first_token_time = now
                self.block_manager.register_full_pages(seq)
            if self.obs_step_timing and n_denoise + n_commit:
                stats = self.step_stats
                stats["denoise_lane_forwards"] += n_denoise
                stats["commit_lane_forwards"] += n_commit
                stats["block_tokens_fixed"] += n_fixed
                stats["blocks_final"] += n_commit
                stats["experts_touched"] += int(packed[0, 2 * width])

    def _drain_inflight(self) -> None:
        if self._inflight is None:
            return
        burst, self._inflight = self._inflight, None
        if "packed" in burst:
            self._commit_block(burst)
        else:
            self._commit_burst(burst)

    def _commit_burst(self, burst: dict) -> None:
        with self.phase("decode_fetch"):
            # The one host sync; its blocking share is near zero when the
            # fused fast path's async copy already landed the bytes.
            # [lanes, 1 + k]: the experts the burst read, then its tokens
            fetched = np.asarray(burst["toks"])
        with self.phase("decode_commit"):
            toks = fetched[:, self._burst_counts:]
            if self.obs_step_timing:
                counts = fetched[0, : self._burst_counts]
                for name, count in zip(llama.BURST_COUNTS_HELD, counts):
                    self.step_stats[name] += int(count)
                if self.routed_layers:
                    # every place a routed layer's rows took (padded
                    # lanes' included, as the device counts them)
                    self.step_stats["routed_places"] += (
                        fetched.shape[0] * burst["k"] * self.routed_layers
                        * self.model_cfg.n_experts_per_tok
                    )
            for i, seq in enumerate(burst["active"]):
                if not seq.block_table:
                    continue  # preempted after this burst was dispatched
                for j in range(burst["k"]):
                    # Pre-check keeps the num_generated <= max_new_tokens
                    # invariant even when a reservation abort clamped the
                    # cap before the burst ran.
                    if self._should_finish(seq):
                        break
                    seq.num_computed = seq.num_tokens
                    seq.output_tokens.append(int(toks[i, j]))
                    seq.num_generated += 1
                self.block_manager.register_full_pages(seq)
                self.block_manager.state_commit(seq)

    def _reserve_slots_or_preempt(self, seq: Sequence, n: int) -> None:
        """Ensure ``seq`` can grow by ``n`` tokens (KV slots for positions
        up to ``num_tokens + n - 1``) — preemption policy shared with
        ``_append_slot_or_preempt``."""
        self._grow_or_preempt(seq, lambda: self.block_manager.reserve_slots(seq, n))

    def _append_slot_or_preempt(self, seq: Sequence) -> None:
        """Grow ``seq`` by one slot, preempting on pool exhaustion."""
        self._grow_or_preempt(seq, lambda: self.block_manager.append_slot(seq))

    def _reserve_window_or_requeue(
        self, seq: Sequence, start: int, end: int, ahead: bool = False
    ) -> bool:
        """The window pages of ``seq``'s prefill chunk ``[start, end)``
        (``BlockManager.reserve_window``). Where the window pool is dry,
        victims are preempted as ``_grow_or_preempt`` preempts them; with
        none left (or ``ahead``: nothing is preempted for an admission
        ahead) ``seq`` itself goes back to the head of the queue (its
        batchmates of one admission walk may together have taken what each
        was promised alone) and False is returned."""
        from .block_manager import AllocationError

        while True:
            try:
                self.block_manager.reserve_window(seq, start, end, chunk=True)
                return True
            except AllocationError:
                victim = (None if ahead else self._pick_victim(seq)) or seq
                log.warning(
                    "preempting sequence for window pages",
                    victim=victim.seq_id,
                    for_seq=seq.seq_id,
                )
                self.scheduler.on_preempted(victim)
                self.block_manager.free_sequence(victim)
                victim.fold_for_preemption()
                self.scheduler.waiting.appendleft(victim)
                if victim is seq:
                    return False

    def _bring_back_cost_s(self, cand: Sequence) -> float:
        """Modeled cost of preempting ``cand`` and bringing it back later:
        registered pages survive in the prefix cache or spill to the
        host tier (per-page cost = the cheaper of restore DMA and
        recompute), unregistered COMPUTED tokens are pure recompute.
        Counted off ``num_computed``, not ``num_tokens``: a mid-prefill
        sequence's unprefilled prompt tail costs the same whether or not
        it is preempted, so it must not inflate the marginal cost (it
        would steer the policy away from exactly the barely-started
        prefills that are the cheapest victims)."""
        reg_pages = cand.num_registered_pages
        fresh_toks = max(cand.num_computed - reg_pages * self.page_size, 0)
        per_page_recompute = self.page_size / self._prefill_rate
        per_page = (
            min(1.0 / self._restore_rate, per_page_recompute)
            if self._restore_rate
            else per_page_recompute
        )
        return fresh_toks / self._prefill_rate + reg_pages * per_page

    def _pick_victim(self, seq: Sequence) -> Optional[Sequence]:
        """Preemption victim policy. Recency (most recently admitted) by
        default; with the host tier attached and rates measured, the
        candidate with the LOWEST modeled bring-back cost
        (recompute-vs-restore aware) wins, recency breaking ties.
        Never picks sequences that are done generating (they finish right
        after the caller's loop) — re-prefilling one would emit an extra
        token beyond its max_new_tokens contract. Mid-prefill sequences
        (chunked mode holds their pages across steps) are candidates after
        every running lane: their registered chunk pages survive in the
        prefix cache, so the re-prefill is cheap, but knocking out a decode
        lane loses less progress."""
        candidates = [
            cand
            for cand in list(reversed(self.scheduler.running))
            + list(reversed(self.scheduler.prefilling))
            if cand is not seq and not self._should_finish(cand)
        ]
        if not candidates:
            return None
        if self.scheduler.qos_enabled:
            # TENANT_QOS: prefer victims from a strictly lower priority
            # class than the sequence that needs pages; fall back to the
            # full candidate set so growth never wedges just because only
            # same-or-higher-class work is active. The recency/cost policy
            # below then runs unchanged within the preferred set.
            lower = [c for c in candidates if c.priority > seq.priority]
            if lower:
                candidates = lower
        if (
            self.config.block_manager.host_pages > 0
            and self._prefill_rate is not None
        ):
            return min(candidates, key=self._bring_back_cost_s)
        return candidates[0]

    def _grow_or_preempt(self, seq: Sequence, grow) -> None:
        """Run ``grow()``; on pool exhaustion, preempt another running
        sequence (recompute-style: its pages are freed — surviving cached
        pages make its later re-prefill cheap — and it requeues); victim
        per ``_pick_victim``. When nothing is left to reclaim, aborts
        ``seq`` rather than wedging the engine."""
        from .block_manager import AllocationError

        while True:
            try:
                grow()
                return
            except AllocationError:
                victim = self._pick_victim(seq)
                if victim is None:
                    # Nothing left to reclaim: the pool cannot hold even this
                    # one sequence. Abort the request rather than wedging the
                    # whole engine.
                    seq.error = "KV page pool too small for sequence growth"
                    seq.sampling.max_new_tokens = seq.num_generated
                    log.error("aborting sequence: pool exhausted", seq=seq.seq_id)
                    return
                log.warning(
                    "preempting sequence for pages",
                    victim=victim.seq_id,
                    for_seq=seq.seq_id,
                )
                self.scheduler.on_preempted(victim)
                self.block_manager.free_sequence(victim)
                victim.fold_for_preemption()
                self.scheduler.waiting.appendleft(victim)

    def _preempt_for_priority(self) -> None:
        """TENANT_QOS priority preemption: when the highest-class waiting
        sequence cannot allocate its prefill pages, preempt ONE strictly
        lower-class active sequence (the shared recompute-fold machinery —
        its pages are freed, surviving prefix-cache pages make the
        re-prefill cheap, and it re-queues WAITING, never errored). One
        victim per step bounds the blast radius: a page-starved pool
        degrades the background class gradually instead of folding every
        low-class lane at once and thrashing."""
        sch = self.scheduler
        sch.qos_reorder_waiting()
        head = next((s for s in sch.waiting if not s.importing), None)
        if head is None or self.block_manager.can_allocate(head):
            return
        candidates = [
            cand
            for cand in list(reversed(sch.running))
            + list(reversed(sch.prefilling))
            if cand.priority > head.priority and not self._should_finish(cand)
        ]
        if not candidates:
            return
        # Worst class first; within it, most recently admitted (least
        # progress lost) — max() returns the first maximum, and the lists
        # above are already most-recent-first.
        victim = max(candidates, key=lambda c: c.priority)
        if self._inflight is not None and any(
            s is victim for s in self._inflight["active"]
        ):
            self._drain_inflight()
        log.warning(
            "priority preemption",
            victim=victim.seq_id,
            victim_tenant=victim.tenant,
            for_seq=head.seq_id,
            for_tenant=head.tenant,
        )
        sch.on_preempted(victim)
        self.block_manager.free_sequence(victim)
        victim.fold_for_preemption()
        sch.waiting.append(victim)  # reorder places it by class next walk
        # .get()-style bump: the key appears in lifecycle_stats (and thus
        # in the /stats admission block, which spreads this dict) only
        # once a preemption actually happened — i.e. only with TENANT_QOS
        # on, preserving knobs-off /stats parity.
        self.lifecycle_stats["priority_preempted"] = (
            self.lifecycle_stats.get("priority_preempted", 0) + 1
        )

    def _count_decode_dispatch(
        self, rows: int, temperature: np.ndarray,
        seq_lens: Optional[np.ndarray] = None, steps: int = 1,
        chained: bool = False, block_tables: Optional[np.ndarray] = None,
        window_tables: Optional[tuple] = None,
    ) -> None:
        """``step_stats``' counters of one decode dispatch: its real lanes,
        whether any of them samples (``temperature`` is the host-side
        array the dispatch was given), whether its input ids came from the
        burst in flight (``chained``) and the context rows its ``steps``
        fused steps read a layer that attends (``attn_ctx_tokens``; for a
        latent pool also under ``latent_ctx_tokens``) (``seq_lens``: the
        host-side lengths of the dispatch, 0 for a lane that is not real;
        a lane's context grows by one a step). ``decode_forwards`` grows by
        ``steps``: the forwards ``experts_touched`` is summed over.
        ``block_tables``: the dispatch's block table array (its width is
        ``decode_table_slots``' table); ``window_tables``: the window table
        array of a model with sliding layers and the position each row's
        first slot stands for. The decode kernels walk both themselves
        (``_count_ctx_pages``)."""
        if self.obs_step_timing:
            self.step_stats["decode_dispatches"] += 1
            self.step_stats["decode_forwards"] += steps
            self.step_stats["decode_rows"] += rows
            self.step_stats["decode_sampled_dispatches"] += bool(
                (temperature > 0).any()
            )
            self.step_stats["decode_chained_dispatches"] += chained
            if seq_lens is not None:
                ctx = int(
                    steps * seq_lens.sum()
                    + rows * steps * (steps - 1) // 2
                )
                self.step_stats["attn_ctx_tokens"] += ctx
                self.step_stats["decode_table_slots"] += (
                    rows * steps * block_tables.shape[1] * self.page_size
                )
                if self.model_cfg.kv_lora_rank:
                    self.step_stats["latent_ctx_tokens"] += ctx
                if self.window_pages is not None:
                    # a sliding layer reads at most a window of each context
                    w = self.model_cfg.sliding_window
                    self.step_stats["window_ctx_tokens"] += int(sum(
                        np.minimum(seq_lens[seq_lens > 0] + j, w).sum()
                        for j in range(steps)
                    ))
                self._count_ctx_pages(
                    seq_lens, block_tables, window_tables, steps
                )

    def _count_ctx_pages(
        self, seq_lens: np.ndarray, block_tables: np.ndarray,
        window_tables: Optional[tuple], steps: int,
    ) -> None:
        """The table pages a layer's call of a decode dispatch copies, and
        those of them it copies as part of a run (``ops/_page_copies.py``:
        the kernel's own rule, group size and alignment), counted as the
        dispatch's first step finds the tables, times its ``steps``. The
        kernels walk a lane's table over the ``seq_len - 1`` rows that lie
        in pages. ``step_stats["full_ctx_pages"]`` / ``["full_ctx_run_pages"]``:
        a full layer's ``paged_attention``, the block table from its first
        slot (a latent pool runs no such call and counts none).
        ``["ctx_pages"]`` / ``["ctx_run_pages"]``: the latent kernel over
        the block table from its first slot, or the sliding layers' call
        over the window table (whose first slot stands for the second of
        ``window_tables``) from the first page that holds a visible slot; a
        model that runs neither counts none."""
        from ..ops._page_copies import count_run_pages

        ps = self.page_size

        def count(key, tables, starts, window, step_pages, pool):
            width = tables.shape[1]
            hist = np.clip(seq_lens - starts - 1, 0, width * ps)
            first = (
                np.maximum(seq_lens - starts - window, 0) // ps if window else 0
            )
            pages, in_runs = count_run_pages(
                tables, first, -(-hist // ps) - first,
                step_pages(width, ps), pool.shape[1],
            )
            self.step_stats[key + "pages"] += steps * pages
            self.step_stats[key + "run_pages"] += steps * in_runs

        if self.model_cfg.kv_lora_rank:
            from ..ops.mla_attention import ctx_step_pages

            count("ctx_", block_tables, 0, 0, ctx_step_pages, self.k_pages)
            return
        from ..ops.paged_attention import walk_step_pages

        count("full_ctx_", block_tables, 0, 0, walk_step_pages, self.k_pages)
        if window_tables is not None:
            window = self.model_cfg.sliding_window
            count(
                "ctx_", *window_tables, window,
                functools.partial(walk_step_pages, window=window),
                self.window_pages[0],
            )

    def _sample(self, logits: jnp.ndarray, seqs: list[Sequence]) -> jax.Array:
        """First tokens of a prefill batch, on the device (decode samples
        there too, inside its own dispatch). The sampler's inputs go up
        while the prefill runs; ``_commit_prefill``'s fetch waits for both
        dispatches."""
        with self.phase("prefill_build"):
            b = logits.shape[0]
            temperature = np.zeros((b,), np.float32)
            top_k = np.zeros((b,), np.int32)
            top_p = np.ones((b,), np.float32)
            for i, seq in enumerate(seqs[:b]):
                temperature[i] = seq.sampling.temperature
                top_k[i] = seq.sampling.top_k
                top_p[i] = seq.sampling.top_p
        with self.phase("prefill_put"):
            key = self._draw_key(temperature)
            (sampling_d,) = self._stage(
                "prefill", pack_sampling_params(temperature, top_k, top_p)
            )
        with self.phase("prefill_dispatch"):
            return sample_tokens_packed(logits, sampling_d, key)
