"""Paged KV block allocator with prefix caching and KV-event emission.

The TPU-side counterpart of what vLLM's block manager does for the reference
ecosystem, designed so the routing indexer can track this engine's cache:

- Pages are fixed-size (``page_size`` tokens). Page 0 is reserved as the
  padding target for block tables (the decode kernel requires valid ids in
  padded slots) and never allocated.
- **Prefix caching**: a page holding a *full* block of tokens is registered
  under its chained sha256-CBOR block hash — computed by the same
  ``ChunkedTokenDatabase`` the indexer uses, so engine-emitted event hashes
  and indexer read-path hashes are identical by construction (the reference
  needed deployment-time seed alignment instead,
  ``token_processor.go:37-40``).
- Cached pages are ref-counted; freed pages with a hash go to an LRU of
  evictable pages and are only recycled (and their ``BlockRemoved`` emitted)
  when the free pool runs dry.
- Every transition emits KV events through ``on_events``:
  ``BlockStored`` when a full page is registered, ``BlockRemoved`` when an
  evictable page is recycled — the engine forwards them to the ZMQ
  publisher (write path of SURVEY §3.2).

A model with sliding-window layers keeps those layers' keys and values in
a second pool with page ids of its own (``WindowPool``, ``config.
window_pages``). Everything above speaks of the first, the CONTEXT pool: a
page there lives as long as its prefix does, and an event asserts that the
full layers' keys and values of a block are held. A window page lives while
some sequence stands less than a window past it, or while it is part of the
last window of something a sequence left behind; it publishes nothing.

A model with linear-attention layers keeps, a sequence, a STATE that is no
page's: a matrix a head a layer (``StatePool``, ``config.state_slots``). A
sequence holds one live slot; where its length passes a multiple of
``config.state_snapshot_tokens`` the slot it held becomes a SNAPSHOT, keyed by
the hash of the block that ends there, and the sequence goes on in a new one.
A prefix hit in the context pool is cut back to the last boundary whose
snapshot is still held, and the sequence starts by reading that snapshot.
Events keep speaking of pages: an event asserts that a block's context rows
are held, not that a request for its prefix is served from that length (a
scorer overstates a pod's warmth by up to a stride, by more once a snapshot
is gone: ``state_cutback_tokens`` / ``state_cutback_lost`` count it).

The allocator is *width-agnostic*: it tracks page identity, hashes, and
tier membership (HBM / host DRAM / remote) but never touches page bytes,
so the same lifecycle drives full-width bf16 pools and the int8 pools of
``KV_QUANT_HBM`` — storage width is the engine's concern (its movers ship
codes + scales between tiers; see ``Engine._flush_page_moves``).
"""

from __future__ import annotations

import bisect
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Optional, Sequence as Seq

from ..kvcache.kvblock import ChunkedTokenDatabase, TokenProcessorConfig
from ..kvcache.kvevents.events import BadBlock, BlockRemoved, BlockStored, Event
from ..utils import get_logger
from .phases import no_part
from .sequence import Sequence

log = get_logger("server.block_manager")


class AllocationError(RuntimeError):
    """Raised when the pool cannot satisfy an allocation even after evicting."""


@dataclass
class BlockManagerConfig:
    total_pages: int = 1024
    page_size: int = 16
    hash_seed: str = ""
    # Emit one BlockStored per batch of freshly-filled pages.
    emit_events: bool = True
    #: host-DRAM offload tier capacity in pages (0 = disabled). Evicted
    #: HBM pages spill here instead of vanishing; prefix hits restore them.
    host_pages: int = 0
    #: the window pool of a model with sliding-window layers: its pages
    #: (ids of their own, page 0 reserved) and the window in tokens. The
    #: engine sets both from the model; 0 = no such layers, no second pool.
    window_pages: int = 0
    sliding_window: int = 0
    #: the state pool of a model with linear-attention layers: its slots
    #: (slot 0 reserved; live slots and snapshots from one free list) and
    #: the tokens between two snapshots (a multiple of the page size). The
    #: engine sets the first from the model, its lanes and
    #: ``state_snapshot_slots``; 0 = no such layers, no state pool.
    state_slots: int = 0
    state_snapshot_tokens: int = 512
    state_snapshot_slots: int = 0


class WindowPool:
    """The sliding layers' pages: ids of their own, hashes shared with the
    context pool (a window page of block ``i`` is found under the chain hash
    the context page of block ``i`` has), reference counts, and an order in
    which pages nobody holds are used again.

    A query at position ``t`` of a sliding layer sees ``(t - W, t]``, so a
    sequence holds the window pages from ``first_block(t)`` on and GIVES
    BACK the ones before as it moves (``BlockManager.reserve_window``), and
    a prefix hit of length ``L`` needs the cached run of blocks
    ``first_block(L) .. L / page - 1`` whole (``longest_run``) and no window
    page before it.

    Pages nobody holds are used again in this order: never written or
    unhashed ones (``_free``); GIVEN BACK ones, oldest first (``_passed``: a
    sequence moved a window past them, or a hit took the run after them and
    passed them over; such a page can serve only a hit that ends less than a
    window after it, and the sequence that went on has just shown where the
    hits on its chain end); pages a finished sequence LEFT inside its last
    window, least recently left first (``_left``); last, pages that SERVED A
    HIT since they were last passed over, least recently used first
    (``_kept``). The last class is what keeps a shared document's last
    window through the gaps between the requests that ask of it: every one
    of them takes the run, moves on past its first pages and gives them
    back, and the next request needs them again; given back into ``_passed``
    they would be the first pages reused. A window page that is reused this
    way loses its hash and publishes nothing: the context page of its block
    stays, and the next hit there is cut back (``window_short_hits``)."""

    def __init__(self, n_pages: int, window: int, page_size: int):
        if n_pages < 2 or window < 1:
            raise ValueError("a window pool needs pages (page 0 is reserved) "
                             "and a window")
        self.n_pages, self.window, self.page_size = n_pages, window, page_size
        self._free: list[int] = list(range(n_pages - 1, 0, -1))
        self._ref: dict[int, int] = {}  # page -> holders, allocated pages
        self._hash: dict[int, int] = {}  # page -> chain hash, registered pages
        self._cached: dict[int, int] = {}  # chain hash -> page
        self._served: set[int] = set()  # took part in a hit since passed over
        self._passed: OrderedDict[int, None] = OrderedDict()
        self._left: OrderedDict[int, None] = OrderedDict()
        self._kept: OrderedDict[int, None] = OrderedDict()
        #: monotone: pages given back by sequences that moved on, cached
        #: pages that lost their hash (reused, or gone with their context
        #: page), hits ``longest_run`` cut and the tokens it cut off
        self.stats = {
            "window_pages_dropped": 0,
            "window_pages_evicted": 0,
            "window_short_hits": 0,
            "window_short_hit_tokens": 0,
        }

    def first_block(self, pos: int) -> int:
        """The first block the query at position ``pos`` sees a slot of."""
        return max(pos - self.window + 1, 0) // self.page_size

    @property
    def num_free(self) -> int:
        """Pages an allocation may take: every one no sequence holds."""
        return (len(self._free) + len(self._passed) + len(self._left)
                + len(self._kept))

    @property
    def num_held(self) -> int:
        """Pages a sequence holds or that are kept because they served a hit:
        the pool's, less the free, the given back and what finished
        sequences left that no hit has taken since (those fill whatever is
        spare, as any cache does, and are reused before a kept page is)."""
        return (self.n_pages - 1 - len(self._free) - len(self._passed)
                - len(self._left))

    def _idle(self, page: int) -> Optional[OrderedDict]:
        for order in (self._passed, self._left, self._kept):
            if page in order:
                return order
        return None

    def _forget(self, page: int) -> None:
        """A cached page loses its hash (it is reused, or its context page
        was evicted)."""
        del self._cached[self._hash.pop(page)]
        self._served.discard(page)
        self.stats["window_pages_evicted"] += 1

    def pop(self, spare: bool = False) -> int:
        """A page for a sequence to write (reference count 1). ``spare``: a
        page nothing is lost by taking (free or given back), or the reserved
        page 0 where there is none."""
        orders = (self._passed,) if spare else (
            self._passed, self._left, self._kept)
        if self._free:
            page = self._free.pop()
        else:
            for order in orders:
                if order:
                    page, _ = order.popitem(last=False)
                    self._forget(page)
                    break
            else:
                if spare:
                    return 0
                raise AllocationError("window page pool exhausted")
        self._ref[page] = 1
        return page

    def take(self, page: int) -> None:
        """One more holder of a cached page (a hit)."""
        order = self._idle(page)
        if order is not None:
            del order[page]
        self._ref[page] = self._ref.get(page, 0) + 1
        self._served.add(page)

    def release(self, page: int, given_back: bool) -> None:
        """One holder less. ``given_back``: the holder moved a window past
        the page (else it finished, or was preempted, inside it)."""
        self.stats["window_pages_dropped"] += given_back
        self._ref[page] -= 1
        if self._ref[page]:
            return
        del self._ref[page]
        if page not in self._hash:
            self._free.append(page)
        elif page in self._served:
            self._kept[page] = None
        elif given_back:
            self._passed[page] = None
        else:
            self._left[page] = None

    def register(self, page: int, h: int) -> None:
        """A full page's hash; a block some other page is cached under keeps
        that page (this one then frees like a partial one)."""
        if page not in self._hash and h not in self._cached:
            self._hash[page] = h
            self._cached[h] = page

    def longest_run(self, hashes: Seq[int], n: int) -> int:
        """The largest ``m <= n`` for which a hit of ``m`` blocks has its
        window: the blocks ``first_block(m * page) .. m - 1`` all cached."""
        missing = -1  # the last block before ``m`` that is not cached
        last_missing = []
        for i in range(n + 1):
            last_missing.append(missing)
            if i < n and hashes[i] not in self._cached:
                missing = i
        for m in range(n, 0, -1):
            if last_missing[m] < self.first_block(m * self.page_size):
                return m
        return 0

    def take_run(self, hashes: Seq[int], m: int) -> tuple[int, list[int]]:
        """The window of a hit of ``m`` blocks: (its first block, its pages,
        each with one more holder). Cached pages of the chain before it that
        nobody holds are passed over: given back."""
        first = self.first_block(m * self.page_size)
        for h in hashes[:first]:
            page = self._cached.get(h)
            order = None if page is None else self._idle(page)
            if order is not None and order is not self._passed:
                del order[page]
                self._served.discard(page)
                self._passed[page] = None
        pages = [self._cached[h] for h in hashes[first:m]]
        for page in pages:
            self.take(page)
        return first, pages

    def evict_hash(self, h: int) -> None:
        """The context page of block ``h`` is evicted: its window page, if
        it has one, goes with it (free, if nobody holds it)."""
        page = self._cached.get(h)
        if page is None:
            return
        order = self._idle(page)
        self._forget(page)
        if order is not None:
            del order[page]
            self._free.append(page)


class StatePool:
    """The linear layers' state slots: live slots (one a sequence, read and
    written by every step) and snapshots (the state after a whole number of
    ``stride`` tokens, keyed by the chain hash of the block that ends there)
    in the same arrays, from one free list. Slot 0 is reserved for the rows
    of a dispatch that hold no sequence.

    Nothing is ever copied slot to slot: a program reads a row's state from
    one slot and writes it to another (``ops/kda.py``). A snapshot is TAKEN by
    letting the sequence go on in a new slot (the old one, left behind, is
    the snapshot) and RESTORED by reading it into a new sequence's live slot.
    While a sequence has still to read a snapshot it is pinned. Snapshots
    nobody is about to read are reused, when the free list is dry, in this
    order: those that never SERVED A HIT (``_idle``: what a sequence left
    behind at a boundary inside its own turn, which only a request that
    repeats that turn could use), oldest first; then those that did
    (``_kept``), least recently hit first. That keeps a resident thread's
    last snapshot through the gaps between the requests that continue it,
    however many boundaries the turns in between pass. A snapshot goes with
    its block when the context page is evicted (``evict_hash``); a page may
    outlive its snapshot."""

    def __init__(self, n_slots: int, stride: int, page_size: int):
        if n_slots < 2 or stride < page_size or stride % page_size:
            raise ValueError(
                f"a state pool needs slots (slot 0 is reserved) and a stride "
                f"of whole pages: {n_slots} slots, {stride} tokens in pages "
                f"of {page_size}"
            )
        self.n_slots, self.stride = n_slots, stride
        self._free: list[int] = list(range(n_slots - 1, 0, -1))
        self._snap: dict[int, int] = {}  # chain hash -> snapshot slot
        self._hash: dict[int, int] = {}  # snapshot slot -> chain hash
        self._pins: dict[int, int] = {}  # snapshot slot -> readers to come
        self._served: set[int] = set()  # snapshot slots that served a hit
        self._idle: OrderedDict[int, None] = OrderedDict()  # unpinned, never hit
        self._kept: OrderedDict[int, None] = OrderedDict()  # unpinned, hit: LRU first
        #: monotone: admissions, snapshots taken / read by a new sequence /
        #: reused or gone with their page, tokens of a hit prefilled again
        #: because it was cut back to a snapshot, and admissions whose
        #: nearest boundary had lost its snapshot
        self.stats = {
            "state_admissions": 0,
            "state_snapshots_taken": 0,
            "state_restores": 0,
            "state_snapshots_evicted": 0,
            "state_cutback_tokens": 0,
            "state_cutback_lost": 0,
        }

    @property
    def num_snapshots(self) -> int:
        return len(self._snap)

    @property
    def num_available(self) -> int:
        """Slots an allocation may take: free ones and unpinned snapshots."""
        return len(self._free) + len(self._idle) + len(self._kept)

    def _forget(self, slot: int) -> None:
        del self._snap[self._hash.pop(slot)]
        self._served.discard(slot)
        self.stats["state_snapshots_evicted"] += 1

    def pop(self) -> int:
        """A slot for a sequence to write."""
        if self._free:
            return self._free.pop()
        for order in (self._idle, self._kept):
            if order:
                slot, _ = order.popitem(last=False)
                self._forget(slot)
                del self._pins[slot]
                return slot
        raise AllocationError("state slot pool exhausted")

    def free(self, slot: int) -> None:
        """A slot no snapshot is registered in goes back."""
        self._free.append(slot)

    def lookup(self, h: int) -> Optional[int]:
        return self._snap.get(h)

    def register(self, slot: int, h: int) -> bool:
        """``slot`` holds the state at the end of block ``h``: a snapshot
        from now on (unpinned). False where that block has one already."""
        if h in self._snap:
            return False
        self._snap[h], self._hash[slot], self._pins[slot] = slot, h, 0
        self._idle[slot] = None
        self.stats["state_snapshots_taken"] += 1
        return True

    def pin(self, slot: int, hit: bool = False) -> None:
        """One more sequence that has still to read snapshot ``slot``;
        ``hit``: a new sequence starts from it (else the sequence that left
        it behind goes on from it)."""
        self._idle.pop(slot, None)
        self._kept.pop(slot, None)
        self._pins[slot] += 1
        if hit:
            self._served.add(slot)

    def unpin(self, slot: int) -> None:
        """A reader has read (its dispatch is enqueued)."""
        self._pins[slot] -= 1
        if self._pins[slot]:
            return
        if slot not in self._hash:  # its block went while it was pinned
            del self._pins[slot]
            self._free.append(slot)
        elif slot in self._served:
            self._kept[slot] = None
        else:
            self._idle[slot] = None

    def evict_hash(self, h: int) -> None:
        """The context page of block ``h`` is evicted: its snapshot, if it
        has one, goes with it (free at once, or when its readers have read)."""
        slot = self._snap.get(h)
        if slot is None:
            return
        self._forget(slot)
        for order in (self._idle, self._kept):
            if slot in order:
                del order[slot], self._pins[slot]
                self._free.append(slot)


@dataclass
class _PageInfo:
    ref_count: int = 0
    chain_hash: Optional[int] = None
    #: token ids of the full block (kept for BlockStored events)
    token_ids: tuple[int, ...] = ()
    parent_hash: Optional[int] = None
    #: TENANT_QOS slice the allocating sequence was charged to ("" =
    #: knob off, or untenanted work like imports). Rides with the block
    #: across tiers so host-cached pages stay attributed.
    tenant: str = ""


class BlockManager:
    def __init__(
        self,
        config: BlockManagerConfig,
        on_events: Optional[Callable[[list[Event]], None]] = None,
    ):
        if config.total_pages < 2:
            raise ValueError("total_pages must be >= 2 (page 0 is reserved)")
        self.config = config
        self.token_db = ChunkedTokenDatabase(
            TokenProcessorConfig(block_size=config.page_size, hash_seed=config.hash_seed)
        )
        self.on_events = on_events
        #: the child spans and counts of an admission (``Engine.part``: the
        #: engine that owns this manager sets its own; alone, a no-op)
        self.part = no_part
        # page id -> info, for allocated pages only
        self._pages: dict[int, _PageInfo] = {}
        self._free: list[int] = list(range(config.total_pages - 1, 0, -1))  # pop() -> 1,2,..
        # chain_hash -> page id (live cached pages, referenced or evictable)
        self._cached: dict[int, int] = {}
        # evictable cached pages (ref_count == 0), LRU order
        self._evictable: OrderedDict[int, None] = OrderedDict()  # page ids
        self._pending_events: list[Event] = []
        #: the sliding layers' pages (None: the model has no such layer and
        #: nothing below reads it)
        self.window: Optional[WindowPool] = None
        if config.window_pages:
            if config.host_pages:
                raise ValueError(
                    "a window pool is incompatible with host_pages > 0 (the "
                    "host tier moves the context pool's pages alone)"
                )
            self.window = WindowPool(
                config.window_pages, config.sliding_window, config.page_size
            )
        #: the linear layers' state slots (None: the model has no such layer
        #: and nothing below reads it)
        self.state: Optional[StatePool] = None
        if config.state_slots:
            if config.host_pages or config.window_pages:
                raise ValueError(
                    "a state pool of slots is incompatible with host_pages > "
                    "0 and with a window pool (one second pool a model; the "
                    "host tier moves the context pool's pages alone)"
                )
            self.state = StatePool(
                config.state_slots, config.state_snapshot_tokens,
                config.page_size,
            )
        # -- host-DRAM tier (SURVEY §2.3 device-tier mapping) --------------
        # The engine attaches the actual KV movers via attach_host_pool();
        # this class only does the tiering bookkeeping.
        self._copy_out = None  # (device_page, host_slot) -> None
        self._copy_in = None  # (host_slot, device_page) -> None
        self._restore_policy = None  # (n_pages) -> bool; None = always
        #: remote-tier demotion hook (REMOTE_TIER): called when an
        #: eviction is about to destroy the LAST local copy of a block —
        #: ``(info, tier, idx)`` with tier "tpu_hbm" (idx = device page,
        #: contents intact until the next dispatch) or "host_dram" (idx =
        #: host slot, caller must snapshot NOW — the slot is reused
        #: immediately). None (default) = plain eviction, bit-identical
        #: legacy behavior.
        self._demote = None
        #: KV-capacity observability (OBS_LIFECYCLE, obs/lifecycle.py):
        #: ``_lifecycle`` records each cached block's tier transitions,
        #: ``_mrc`` samples reuse distances off the allocate-time prefix
        #: walk. Both None (default) = no extra work on any path.
        self._lifecycle = None
        self._mrc = None
        # -- TENANT_QOS (attach_qos; all None/empty = knob off, every
        # path below is bit-identical legacy). Engine-thread-only state,
        # like the page pool itself.
        self._qos = None
        #: tenant slice charged for allocations in flight (set at the top
        #: of allocate/append_slot/reserve_slots from the sequence).
        self._alloc_tenant = ""
        #: evictable HBM pages currently charged per tenant slice — the
        #: numerator of the cache_share cap.
        self._tenant_evictable: dict[str, int] = {}
        #: lazily-built per-tenant reuse-distance estimators (the /debug/
        #: mrc tenant slices); factory installed only when OBS_LIFECYCLE
        #: is also on.
        self._tenant_mrc_factory = None
        self._tenant_mrc: dict = {}
        #: per-tenant first-prefill hit accounting (requests /
        #: prompt_tokens / cached_tokens / capped_evictions), for /stats.
        self.tenant_stats: dict[str, dict[str, int]] = {}
        #: KV_INTEGRITY plane (attach_integrity; both None = knob off,
        #: every path below is bit-identical legacy). ``_integrity`` is
        #: the digest side table, ``_host_verify(slot, h, reason)`` the
        #: engine's host-slot digest check.
        self._integrity = None
        self._host_verify = None
        #: rotating scrub position (last host slot verified by the
        #: background scrubber; engine-thread-only like the pools)
        self._scrub_cursor = -1
        self._host_free: list[int] = list(range(config.host_pages - 1, -1, -1))
        self._host_cached: dict[int, int] = {}  # chain_hash -> host slot
        self._host_info: dict[int, _PageInfo] = {}  # host slot -> metadata
        self._host_lru: OrderedDict[int, None] = OrderedDict()  # host slots
        #: host-tier accounting (monotone; /stats + kvcache_host_* feed):
        #: spilled/restored = device↔host page moves, prefetched = the
        #: subset of restores issued AHEAD of allocate by the prefetch
        #: stage, host_evicted = host-LRU drops, spill_declined = spills
        #: the recompute-vs-restore cost model refused.
        self.host_stats = {
            "spilled": 0,
            "restored": 0,
            "prefetched": 0,
            "host_evicted": 0,
            "spill_declined": 0,
        }

    def attach_host_pool(self, copy_out, copy_in, restore_policy=None) -> None:
        """Install the engine's device↔host page movers, enabling the
        host-DRAM offload tier (``config.host_pages`` > 0).

        ``restore_policy(n_pages) -> bool``, when given, is the
        recompute-vs-restore cost model: consulted once per contiguous
        host-cached run during ``allocate``, it answers whether restoring
        ``n_pages`` beats recomputing their tokens (the engine answers
        from online-measured restore/prefill rates). ``None`` keeps the
        always-restore behavior."""
        self._copy_out = copy_out
        self._copy_in = copy_in
        self._restore_policy = restore_policy

    def attach_demoter(self, demote_fn) -> None:
        """Install the engine's remote-tier demotion hook (``REMOTE_TIER``
        knob): ``demote_fn(info, tier, idx)`` fires whenever eviction
        would destroy the last local copy of a cached block, BEFORE the
        ``BlockRemoved`` is emitted. The hook only queues (the engine
        batches payload builds with the page-move flush); it must never
        block or raise."""
        self._demote = demote_fn

    def attach_lifecycle(self, ledger=None, mrc=None) -> None:
        """Attach the ``OBS_LIFECYCLE`` instruments (obs/lifecycle.py):
        ``ledger`` (a ``BlockLifecycleLedger``) records tier transitions
        at every allocate/spill/restore/prefetch/demote/import/evict;
        ``mrc`` (a ``ReuseDistanceEstimator``) observes the full
        prefix-hash chain of every ``allocate`` lookup. Either may be
        None; unattached (the default) no path here changes."""
        self._lifecycle = ledger
        self._mrc = mrc

    def attach_qos(self, qos, mrc_factory=None) -> None:
        """Attach the TENANT_QOS policy (``server/qos.py``): evictable
        pages are charged to the allocating tenant, tenants over their
        ``cache_share`` recycle their OWN LRU page instead of other
        tenants' warm prefixes, and — when ``mrc_factory`` is given
        (OBS_LIFECYCLE also on) — each tenant slice feeds its own
        reuse-distance estimator for /debug/mrc."""
        self._qos = qos
        self._tenant_mrc_factory = mrc_factory

    def attach_integrity(self, integrity, host_verify) -> None:
        """Attach the ``KV_INTEGRITY`` plane (``kvcache/integrity.py``):
        ``integrity`` is the content-digest side table; ``host_verify(slot,
        h, reason) -> bool`` is the engine's check — it recomputes the
        digest over the host-tier arrays for ``slot``, records the outcome
        (``reason`` maps to the metric's path label), quarantines on
        mismatch, and returns False only for a CORRUPT copy (unverified
        passes — absence of evidence never truncates a chain). On a False
        return this class runs the recovery choreography: free the slot,
        emit ``BlockRemoved`` + ``BadBlock``, and let the caller's chain
        walk break — cold recompute IS the recovery. Unattached (the
        default) no path here changes."""
        self._integrity = integrity
        self._host_verify = host_verify

    def _quarantine_host_slot(self, slot: int, info: _PageInfo) -> None:
        """Destroy a host-tier copy that failed its digest check (the
        caller already removed the slot from cached/info/lru maps — or is
        about to; this finishes the choreography): the slot returns to the
        free list, the ledger records the quarantine, and the fleet learns
        via ``BlockRemoved`` (index entry) + ``BadBlock`` (revocation +
        replica purge). Deliberately NOT counted as ``host_evicted`` —
        that stat means capacity pressure, and a corruption storm must not
        masquerade as one."""
        h = info.chain_hash
        self._host_free.append(slot)
        self._record_lifecycle(h, "none", "quarantine", tenant=info.tenant)
        self._emit(BlockRemoved(block_hashes=[h], medium="host_dram"))
        self._emit(BadBlock(block_hashes=[h], medium="host_dram"))
        log.warning(
            "host KV copy failed digest check; quarantined",
            block=h,
            slot=slot,
        )

    def quarantine_host_block(self, h) -> bool:
        """Remove block ``h``'s host-tier copy through the quarantine
        choreography (engine loop only). Returns True when a copy was
        resident and has been destroyed; False when the host tier holds
        no copy (nothing to do)."""
        slot = self._host_cached.pop(h, None)
        if slot is None:
            return False
        info = self._host_info.pop(slot)
        self._host_lru.pop(slot, None)
        self._quarantine_host_slot(slot, info)
        return True

    def scrub_host_tier(self, max_pages: int) -> int:
        """Background integrity scrub: verify up to ``max_pages`` resident
        host-tier slots against their write-time digests, rotating through
        the tier across calls so every slot is eventually covered. Corrupt
        copies get the full quarantine choreography (slot freed,
        ``BlockRemoved`` + ``BadBlock`` emitted). Returns slots checked.
        Caller must be the engine loop (page-pool ownership rule)."""
        if self._host_verify is None or max_pages <= 0:
            return 0
        slots = sorted(self._host_info)
        if not slots:
            return 0
        start = bisect.bisect_right(slots, self._scrub_cursor)
        order = slots[start:] + slots[:start]
        checked = 0
        for slot in order[: max(max_pages, 0)]:
            info = self._host_info.get(slot)
            if info is None:
                continue
            self._scrub_cursor = slot
            checked += 1
            if not self._host_verify(slot, info.chain_hash, "scrub"):
                self.quarantine_host_block(info.chain_hash)
        if checked and self._integrity is not None:
            self._integrity.note_scrubbed(checked)
        return checked

    def _record_lifecycle(
        self, chain_hash, tier: str, reason: str, tenant: str = ""
    ) -> None:
        if self._lifecycle is not None and chain_hash is not None:
            self._lifecycle.record(chain_hash, tier, reason, tenant=tenant)

    def _evict_count(self, info: _PageInfo, delta: int) -> None:
        """Maintain the per-tenant evictable-page counts (no-op with the
        QoS knob off, and for untenanted pages)."""
        if self._qos is None or not info.tenant:
            return
        n = self._tenant_evictable.get(info.tenant, 0) + delta
        if n > 0:
            self._tenant_evictable[info.tenant] = n
        else:
            self._tenant_evictable.pop(info.tenant, None)

    def _qos_evict_victim(self) -> Optional[int]:
        """Cache-share cap (TENANT_QOS): when the allocating tenant's
        evictable pages already meet its configured share of the pool,
        the recycle victim is that tenant's own LRU evictable page — its
        churn cannot evict another tenant's hot prefix. Under the cap
        (or uncapped, or untenanted) returns None: global LRU applies."""
        t = self._alloc_tenant
        if not t:
            return None
        cap = self._qos.cache_cap_pages(t, self.config.total_pages - 1)
        if cap is None or self._tenant_evictable.get(t, 0) < cap:
            return None
        for page in self._evictable:  # LRU order
            if self._pages[page].tenant == t:
                st = self.tenant_stats.get(t)
                if st is not None:
                    st["capped_evictions"] += 1
                return page
        return None

    @property
    def num_host_cached_pages(self) -> int:
        return len(self._host_cached)

    def _host_alloc_slot(self) -> Optional[int]:
        """Free host slot, evicting the LRU host-cached page if needed.
        Returns None when every slot is in flight (e.g. the single slot is
        mid-restore) — the caller then simply skips the spill."""
        if self._host_free:
            return self._host_free.pop()
        if not self._host_lru:
            return None
        slot, _ = self._host_lru.popitem(last=False)
        info = self._host_info.pop(slot)
        del self._host_cached[info.chain_hash]
        self.host_stats["host_evicted"] += 1
        if self._demote is not None:
            # Host-LRU drop destroys the only copy (tiers are disjoint:
            # a host-cached block is never simultaneously HBM-cached) —
            # demote it instead of losing it. The hook snapshots the slot
            # NOW; the caller reuses it immediately after.
            self._demote(info, "host_dram", slot)
            self._record_lifecycle(
                info.chain_hash, "remote", "demote", tenant=info.tenant
            )
        else:
            if self._integrity is not None:
                # Plain capacity eviction destroys the stored bytes the
                # digest described; the demote path instead hands the
                # entry's fate to the engine's payload build (which
                # verifies against it before shipping).
                self._integrity.drop(info.chain_hash)
            self._record_lifecycle(
                info.chain_hash, "none", "evict", tenant=info.tenant
            )
        self._emit(BlockRemoved(block_hashes=[info.chain_hash], medium="host_dram"))
        return slot

    def _try_offload(self, page: int, info: _PageInfo) -> None:
        """Spill an HBM page being recycled into the host-DRAM tier."""
        if (
            self._copy_out is None
            or self.config.host_pages == 0
            or info.chain_hash in self._host_cached
        ):
            return
        # A spill only ever pays off as a later restore; when the cost
        # model says restoring loses to recompute on this link, the
        # device→host copy is pure waste — skip it (on a slow host link,
        # ungated spills alone collapse throughput under thrash even with
        # every restore declined). Optimistic until both rates have samples,
        # so the model can bootstrap from real early spills+restores.
        if self._restore_policy is not None and not self._restore_policy(1):
            self.host_stats["spill_declined"] += 1
            return
        slot = self._host_alloc_slot()
        if slot is None:
            return
        self.host_stats["spilled"] += 1
        self._copy_out(page, slot)
        self._host_cached[info.chain_hash] = slot
        self._host_info[slot] = info
        self._host_lru[slot] = None
        self._record_lifecycle(
            info.chain_hash, "host_dram", "spill", tenant=info.tenant
        )
        self._emit(
            BlockStored(
                block_hashes=[info.chain_hash],
                parent_block_hash=info.parent_hash,
                token_ids=list(info.token_ids),
                block_size=self.config.page_size,
                medium="host_dram",
            )
        )

    # -- introspection ------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free) + len(self._evictable)

    @property
    def num_cached_pages(self) -> int:
        return len(self._cached)

    # -- event plumbing -----------------------------------------------------
    def _emit(self, ev: Event) -> None:
        if self.config.emit_events:
            self._pending_events.append(ev)

    def flush_events(self) -> list[Event]:
        """Drain pending events (engine calls once per step and publishes)."""
        evs, self._pending_events = self._pending_events, []
        if evs and self.on_events is not None:
            self.on_events(evs)
        return evs

    # -- low-level page ops -------------------------------------------------
    def _pop_free_page(self) -> int:
        if self._free:
            page = self._free.pop()
            self._pages[page] = _PageInfo(ref_count=1, tenant=self._alloc_tenant)
            return page
        # Recycle the least-recently-used evictable cached page, spilling
        # it to the host-DRAM tier first when one is attached. With
        # TENANT_QOS cache-share caps, an over-cap tenant recycles its
        # own LRU page instead (see _qos_evict_victim).
        if self._evictable:
            page = self._qos_evict_victim() if self._qos is not None else None
            if page is None:
                page, _ = self._evictable.popitem(last=False)
            else:
                del self._evictable[page]
            info = self._pages[page]
            assert info.ref_count == 0 and info.chain_hash is not None
            self._evict_count(info, -1)
            del self._cached[info.chain_hash]
            self._try_offload(page, info)
            if info.chain_hash not in self._host_cached:
                if self._demote is not None:
                    # The host tier didn't keep a copy (absent, full, or
                    # the cost model declined the spill): this recycle
                    # destroys the last local copy — demote over the
                    # fabric instead. The hook queues a snapshot of the
                    # page, whose contents stay intact until the next
                    # device dispatch (the same window the host-tier
                    # offload gather relies on).
                    self._demote(info, "tpu_hbm", page)
                    self._record_lifecycle(
                        info.chain_hash, "remote", "demote", tenant=info.tenant
                    )
                else:
                    self._record_lifecycle(
                        info.chain_hash, "none", "evict", tenant=info.tenant
                    )
            self._emit(BlockRemoved(block_hashes=[info.chain_hash], medium="tpu_hbm"))
            if self.window is not None:
                self.window.evict_hash(info.chain_hash)
            if self.state is not None:
                self.state.evict_hash(info.chain_hash)
            self._pages[page] = _PageInfo(ref_count=1, tenant=self._alloc_tenant)
            return page
        raise AllocationError("KV page pool exhausted")

    def _incref(self, page: int) -> None:
        info = self._pages[page]
        if info.ref_count == 0 and page in self._evictable:
            del self._evictable[page]
            self._evict_count(info, -1)
        info.ref_count += 1

    def _decref(self, page: int) -> None:
        info = self._pages[page]
        info.ref_count -= 1
        assert info.ref_count >= 0
        if info.ref_count == 0:
            if info.chain_hash is not None:
                # Stays cached & evictable: warm for future prefix hits.
                self._evictable[page] = None
                self._evictable.move_to_end(page)
                self._evict_count(info, +1)
            else:
                del self._pages[page]
                self._free.append(page)

    def _try_restore(self, h: int, reason: str = "restore") -> Optional[int]:
        """Swap a host-DRAM-cached block back into an HBM page (prefix hit
        on the offload tier). Returns the device page, or None.
        ``reason`` labels the lifecycle record: "restore" (blocking, from
        allocate) or "prefetch" (ahead of the scheduler)."""
        slot = self._host_cached.get(h)
        if slot is None or self._copy_in is None:
            return None
        # Claim the slot before _pop_free_page: recycling an HBM page can
        # itself offload into the host tier and evict the host LRU — which
        # must never be the very slot being restored.
        del self._host_cached[h]
        info = self._host_info.pop(slot)
        self._host_lru.pop(slot, None)
        if self._host_verify is not None and not self._host_verify(
            slot, h, reason
        ):
            # Corrupt host copy caught BEFORE any byte reaches HBM: the
            # chain walk breaks here (the caller sees a plain miss) and
            # the suffix recomputes cold — greedy decode stays
            # token-identical because the recompute writes fresh correct
            # pages under the same hashes.
            self._quarantine_host_slot(slot, info)
            return None
        try:
            page = self._pop_free_page()
        except AllocationError:
            # No HBM page available: put the block back in the host tier
            # untouched (freeing the slot here would drop the KV copy while
            # the index still believes this replica holds it).
            self._host_cached[h] = slot
            self._host_info[slot] = info
            self._host_lru[slot] = None
            return None
        self._copy_in(slot, page)
        self._host_free.append(slot)
        if self._integrity is not None:
            # The digest described the host-slot representation, which is
            # gone (HBM is trusted); a later re-spill re-records.
            self._integrity.drop(h)
        self.host_stats["restored"] += 1
        info.ref_count = 0
        self._pages[page] = info
        self._cached[h] = page
        self._evictable[page] = None  # ref 0 until the caller increfs
        self._evict_count(info, +1)
        self._record_lifecycle(h, "tpu_hbm", reason, tenant=info.tenant)
        self._emit(BlockRemoved(block_hashes=[h], medium="host_dram"))
        self._emit(
            BlockStored(
                block_hashes=[h],
                parent_block_hash=info.parent_hash,
                token_ids=list(info.token_ids),
                block_size=self.config.page_size,
                medium="tpu_hbm",
            )
        )
        return page

    def prefetch_chain(self, hashes: Seq[int], max_pages: int) -> int:
        """Bring host-cached blocks of a prefix chain back into HBM AHEAD
        of allocate (the prefetch stage): walks ``hashes`` like ``allocate``
        does, restoring up to ``max_pages`` host hits into ref-0 evictable
        HBM pages so the device↔host copies overlap the current step and
        the later ``allocate`` sees plain warm pages. HBM-resident chain
        pages are touched to MRU while walking — a prefetch must never
        recycle an earlier page of the very chain it is warming. Restores
        respect the recompute-vs-restore cost model with the same
        run-at-a-time consultation as ``allocate`` (a declined run stops
        the walk: allocate will stop there too). Returns pages restored."""
        restored = 0
        restore_until = -1
        for i, h in enumerate(hashes):
            page = self._cached.get(h)
            if page is not None:
                if page in self._evictable:
                    self._evictable.move_to_end(page)
                continue
            if h not in self._host_cached:
                break
            if restored >= max_pages:
                break
            if self._restore_policy is not None and i > restore_until:
                run = 0
                while (
                    i + run < len(hashes)
                    and hashes[i + run] in self._host_cached
                ):
                    run += 1
                if not self._restore_policy(run):
                    break
                restore_until = i + run - 1
            if self._try_restore(h, reason="prefetch") is None:
                break  # no HBM page available: stop, allocate will block
            restored += 1
        if restored:
            self.host_stats["prefetched"] += restored
        return restored

    # -- fleet self-healing (kvcache/kvevents resync) -----------------------
    def block_digest(self) -> dict[str, list[int]]:
        """Resync digest: every chain hash currently resident, per tier —
        the ground truth an ``IndexSnapshot`` replaces the indexer's view
        with. Caller must be the engine loop (page-pool ownership rule)."""
        return {
            "tpu_hbm": list(self._cached.keys()),
            "host_dram": list(self._host_cached.keys()),
        }

    def hot_chains(self, limit: int) -> list[list[int]]:
        """The longest HBM-resident prefix chains, in chain (root→leaf)
        order — the donor-side warm sets fleet scale-up revival pulls onto
        a new pod. A chain is read leaf-back via ``parent_hash`` links and
        truncated at the first non-resident ancestor (the export path's
        consecutive-run rule would stop there anyway). Caller must be the
        engine loop (page-pool ownership rule)."""
        if limit <= 0:
            return []
        parents = {
            self._pages[p].parent_hash
            for p in self._cached.values()
            if self._pages[p].parent_hash is not None
        }
        chains: list[list[int]] = []
        for h, page in self._cached.items():
            if h in parents:
                continue  # interior block; its leaf's walk covers it
            chain: list[int] = []
            cur: Optional[int] = h
            while cur is not None:
                p = self._cached.get(cur)
                if p is None:
                    break  # ancestor evicted: the resident run starts here
                chain.append(cur)
                cur = self._pages[p].parent_hash
            chain.reverse()
            chains.append(chain)
        chains.sort(key=len, reverse=True)
        return chains[:limit]

    # -- cross-pod transfer (kvcache/transfer) ------------------------------
    def is_block_resident(self, h: int) -> bool:
        """True when ``h`` lives in either tier (HBM page or host slot)."""
        return h in self._cached or h in self._host_cached

    def lookup_chain(
        self, hashes: Seq[int], max_blocks: Optional[int] = None
    ) -> list[tuple[int, _PageInfo, str, int]]:
        """Export read path: walk a chained-hash prefix and return the
        longest consecutive resident run as ``(hash, info, tier, idx)``
        tuples — tier ``"tpu_hbm"`` (idx = device page) or ``"host_dram"``
        (idx = host slot). Stops at the first non-resident hash: a block
        behind a chain gap can never serve a prefix hit on the importer,
        so shipping it would be pure waste."""
        out: list[tuple[int, _PageInfo, str, int]] = []
        walk = hashes if max_blocks is None else hashes[:max_blocks]
        for h in walk:
            page = self._cached.get(h)
            if page is not None:
                out.append((h, self._pages[page], "tpu_hbm", page))
                continue
            slot = self._host_cached.get(h)
            if slot is not None:
                out.append((h, self._host_info[slot], "host_dram", slot))
                continue
            break
        return out

    def install_imported_block(
        self,
        h: int,
        parent_hash: Optional[int],
        token_ids: Seq[int],
        allow_evict: bool = False,
    ) -> Optional[int]:
        """Commit a transferred block as a prefix-cache page: allocate a
        page, register it under ``h`` (ref 0, evictable — imports are
        warmth, not work-in-flight) and emit ``BlockStored`` so the global
        index learns this replica now holds the block. Returns the device
        page the caller must write the KV bytes into, or ``None`` when the
        block is already resident in some tier (nothing to do).

        By default only genuinely FREE pages are used — an import never
        evicts locally-warm pages (raises ``AllocationError`` instead):
        evicting proven-warm state for speculative remote warmth would let
        a pull storm thrash the very cache the transfer plane exists to
        protect. ``allow_evict=True`` (the ``REMOTE_TIER`` import path)
        relaxes this to the normal eviction ladder: with a demoter
        attached, the recycled victim spills to host or demotes over the
        fabric, so making room for routed-for warmth is LOSSLESS — the
        original rationale no longer applies. Imported pages land at the
        evictable MRU end, so a multi-block import never recycles its own
        chain."""
        if self.is_block_resident(h):
            return None
        if self._free:
            page = self._free.pop()
        elif allow_evict:
            # Imports are fleet warmth, not tenant work: never charge
            # them to (or cap them by) whatever tenant allocated last.
            self._alloc_tenant = ""
            page = self._pop_free_page()  # recycles LRU; victim spills/demotes
        else:
            raise AllocationError("no free pages for imported KV block")
        info = _PageInfo(
            ref_count=0,
            chain_hash=h,
            token_ids=tuple(int(t) for t in token_ids),
            parent_hash=parent_hash,
        )
        self._pages[page] = info
        self._cached[h] = page
        self._evictable[page] = None
        self._evictable.move_to_end(page)
        self._evict_count(info, +1)
        self._record_lifecycle(h, "tpu_hbm", "import")
        self._emit(
            BlockStored(
                block_hashes=[h],
                parent_block_hash=parent_hash,
                token_ids=list(info.token_ids),
                block_size=self.config.page_size,
                medium="tpu_hbm",
            )
        )
        return page

    # -- sequence lifecycle -------------------------------------------------
    def allocate(self, seq: Sequence, ahead: bool = False) -> int:
        """Allocate pages for a sequence's prompt, reusing prefix-cached
        pages. Sets ``seq.block_table`` / ``seq.num_cached_prompt``; returns
        the number of prompt tokens served from cache. ``ahead``: what the
        span says of itself, an admission made while the burst that frees
        its lane is on the device (``Scheduler.schedule``'s ``leaving``)."""
        assert not seq.block_table, "sequence already allocated"
        tokens = seq.prompt_tokens
        with self.part(
            seq=seq.seq_id, tokens=len(tokens), ahead=int(ahead)
        ) as admit:
            admit.add(admit_attempts=1, admit_tokens=len(tokens))
            cached_tokens = self._allocate(seq, tokens)
            admit.add(admit_blocks_hit=cached_tokens // self.config.page_size)
        return cached_tokens

    def _allocate(self, seq: Sequence, tokens: Seq[int]) -> int:
        """``allocate`` inside its span: the parts are ``Engine.part``'s
        (``ADMIT_PARTS``), what lies between them the span's own time."""
        self._alloc_tenant = seq.tenant
        ps = self.config.page_size
        with self.part("hash"):
            hashes = self.token_db.prefix_hashes(tokens)
        observe_tenant = (
            self._tenant_mrc_factory is not None and bool(seq.tenant)
        )
        if (self._mrc is not None or observe_tenant) and not seq.mrc_observed:
            # The MRC's access stream: every full block this lookup walks
            # — hits AND misses (the misses register below and become
            # future reuse), in chain order. Once per REQUEST, not per
            # allocate call: rollback retries and preemption re-prefills
            # re-walk the same chain, and double-observing it would feed
            # tiny artificial reuse distances (the hit_stats
            # first-prefill-only rule, applied to the curve). The tenant
            # slices (TENANT_QOS + OBS_LIFECYCLE) see the same stream,
            # restricted to their own requests.
            seq.mrc_observed = True
            if self._mrc is not None:
                self._mrc.observe_chain(hashes)
            if observe_tenant:
                est = self._tenant_mrc.get(seq.tenant)
                if est is None:
                    est = self._tenant_mrc[seq.tenant] = self._tenant_mrc_factory()
                est.observe_chain(hashes)

        with self.part("walk"):
            block_table, cached_tokens = self._walk_cached(hashes, len(tokens))
        if self.window is not None:
            with self.part("window"):
                # A hit needs both: cut back to the longest prefix whose
                # last window the window pool still holds whole, and take
                # that run.
                n_hit = self.window.longest_run(hashes, len(block_table))
                if n_hit < len(block_table):
                    self.window.stats["window_short_hits"] += 1
                    self.window.stats["window_short_hit_tokens"] += (
                        len(block_table) - n_hit
                    ) * ps
                    for page in block_table[n_hit:]:
                        self._decref(page)
                    del block_table[n_hit:]
                    cached_tokens = n_hit * ps
                seq.window_first, seq.window_table = self.window.take_run(
                    hashes, n_hit
                )
        snapshot = None
        try:
            if self.state is not None:
                with self.part("state"):
                    cached_tokens, snapshot = self._cut_to_snapshot(
                        hashes, block_table, cached_tokens
                    )
                    try:
                        seq.state_slot = self.state.pop()
                    except AllocationError:
                        if snapshot is not None:
                            self.state.unpin(snapshot)
                        raise
                    seq.state_from = snapshot or seq.state_slot
            n_pages_needed = -(-len(tokens) // ps)
            with self.part("pages") as pages:
                n_hit, free = len(block_table), len(self._free)
                try:
                    while len(block_table) < n_pages_needed:
                        block_table.append(self._pop_free_page())
                finally:
                    # a pop that the free list did not serve evicted
                    popped = len(block_table) - n_hit
                    pages.add(
                        admit_pages=popped,
                        admit_evictions=popped - (free - len(self._free)),
                    )
        except AllocationError:
            with self.part("rollback", seq=seq.seq_id) as undo:
                undo.add(admit_rollbacks=1)
                for page in block_table:
                    self._decref(page)
                self._free_window(seq)
                self._free_state(seq)
            raise

        seq.block_table = block_table
        seq.num_cached_prompt = cached_tokens
        seq.num_computed = cached_tokens
        seq.num_prefilled = cached_tokens
        # Cache-hit pages are already registered; continue the hash chain
        # from the last reused page.
        n_reused = cached_tokens // ps
        seq.num_registered_pages = n_reused
        seq.last_chain_hash = (
            self._pages[block_table[n_reused - 1]].chain_hash if n_reused else None
        )
        if self._qos is not None and seq.tenant and not seq.qos_observed:
            # Per-tenant hit accounting, first successful prefill only
            # (the hit_stats rule): rollbacks raise above, preemption
            # re-prefills have qos_observed already set.
            seq.qos_observed = True
            st = self.tenant_stats.setdefault(
                seq.tenant,
                {
                    "requests": 0,
                    "prompt_tokens": 0,
                    "cached_tokens": 0,
                    "capped_evictions": 0,
                },
            )
            st["requests"] += 1
            st["prompt_tokens"] += len(tokens)
            st["cached_tokens"] += cached_tokens
        return cached_tokens

    def _walk_cached(
        self, hashes: Seq[int], n_tokens: int
    ) -> tuple[list[int], int]:
        """(the pages of the prompt's longest cached prefix, each with a
        reference taken; its tokens): ``allocate``'s walk."""
        ps = self.config.page_size
        block_table: list[int] = []
        cached_tokens = 0
        restore_until = -1  # hash index below which restores are approved
        for i, h in enumerate(hashes):
            page = self._cached.get(h)
            if (
                page is None
                and self._restore_policy is not None
                and i > restore_until
                and h in self._host_cached
            ):
                # First touch of a contiguous host-cached run: consult the
                # recompute-vs-restore cost model ONCE for the whole run.
                # (Modeled per-run, not per-prompt: declining only forces
                # recompute of these blocks — allocate stops here either
                # way, so anything beyond the run is recomputed regardless.)
                run = 0
                while (
                    i + run < len(hashes)
                    and hashes[i + run] in self._host_cached
                ):
                    run += 1
                if not self._restore_policy(run):
                    break  # cheaper to recompute than to DMA the run in
                restore_until = i + run - 1
            if page is None:
                page = self._try_restore(h)
            if page is None:
                break
            self._incref(page)
            block_table.append(page)
            cached_tokens += ps
        # Never serve the *entire* prompt from cache: the engine needs at
        # least one fresh position to produce first-token logits.
        if cached_tokens >= n_tokens and block_table:
            page = block_table.pop()
            self._decref(page)
            cached_tokens -= ps
        return block_table, cached_tokens

    def _cut_to_snapshot(
        self, hashes: Seq[int], block_table: list[int], cached_tokens: int
    ) -> tuple[int, Optional[int]]:
        """A hit needs the state at its end: cuts ``block_table`` back to
        the last boundary whose snapshot is still held (none: position 0,
        zero state) and pins that snapshot, which the sequence reads with
        its first chunk. (The tokens kept, the snapshot's slot or None.)"""
        st = self.state
        ps = self.config.page_size
        n_hit = cached_tokens // st.stride
        snapshot, lost = None, False
        while n_hit:
            snapshot = st.lookup(hashes[n_hit * st.stride // ps - 1])
            if snapshot is not None:
                break
            n_hit, lost = n_hit - 1, True
        kept = n_hit * st.stride
        st.stats["state_admissions"] += 1
        st.stats["state_cutback_lost"] += lost
        st.stats["state_cutback_tokens"] += cached_tokens - kept
        for page in block_table[kept // ps:]:
            self._decref(page)
        del block_table[kept // ps:]
        if snapshot is not None:
            # (before the pop, which may reuse snapshots)
            st.pin(snapshot, hit=True)
            st.stats["state_restores"] += 1
        return kept, snapshot

    def can_allocate(self, seq: Sequence) -> bool:
        # Conservative: ignores prefix-cache hits (which only reduce demand).
        ps = self.config.page_size
        need = -(-len(seq.prompt_tokens) // ps)
        if self.window is not None and (
            min(need, self.window.window // ps + 2) > self.window.num_free
        ):
            # the pages of a last window and its boundary, or of the prompt
            return False
        if self.state is not None and not self.state.num_available:
            return False
        return need <= self.num_free

    def reserve_window(
        self, seq: Sequence, query_pos: int, end: int, chunk: bool = False
    ) -> None:
        """The window pages of a dispatch whose first query stands at
        ``query_pos`` and which writes the positions up to ``end - 1``: the
        pages before ``first_block(query_pos)`` are GIVEN BACK (every
        position in them lies a window behind every query to come), pages
        are taken through ``end - 1``. ``chunk``: one prefill chunk, whose
        queries read the chunk's own keys from the dispatch and not from
        pages: a block of it that already lies a window behind ``end`` is
        stored only for the hits it may serve, so it takes a spare page
        (free or given back) or, where there is none, the reserved page 0
        (its keys are written there and never read); either way it is given
        back with the next call, and a chunk needs no more than a window's
        pages however long it is. On exhaustion the growth so far is
        kept, as ``reserve_slots`` keeps it. A model without sliding layers
        returns at once."""
        w = self.window
        if w is None:
            return
        ps = self.config.page_size
        # (a block goes once it has its hash: a long chunk's blocks, behind
        # the window before ``register_full_pages`` saw them, wait a call)
        drop = min(w.first_block(query_pos), seq.num_registered_pages) - (
            seq.window_first)
        drop = min(drop, len(seq.window_table))
        if drop > 0:
            for page in seq.window_table[:drop]:
                if page:
                    w.release(page, given_back=True)
            del seq.window_table[:drop]
            seq.window_first += drop
        if not seq.window_table:  # nothing held: start where the window does
            seq.window_first = max(seq.window_first, w.first_block(query_pos))
        written_from = w.first_block(end) if chunk else 0
        while (seq.window_first + len(seq.window_table)) * ps < end:
            block = seq.window_first + len(seq.window_table)
            seq.window_table.append(w.pop(spare=block < written_from))

    def _free_window(self, seq: Sequence) -> None:
        """Release ``seq``'s window pages: given back where they lie a window
        behind its end, left where they lie inside its last window."""
        if self.window is None:
            return
        kept_from = self.window.first_block(seq.num_computed) - seq.window_first
        for i, page in enumerate(seq.window_table):
            if page:
                self.window.release(page, given_back=i < kept_from)
        seq.window_table, seq.window_first = [], 0

    # -- the state pool of slots (a model with linear-attention layers) -----
    def _free_state(self, seq: Sequence) -> None:
        """Give back ``seq``'s live slot, what it left behind and had not yet
        registered, and its claim on a snapshot it had still to read."""
        st = self.state
        if st is None or not seq.state_slot:
            return
        if seq.state_from != seq.state_slot:
            st.unpin(seq.state_from)
        st.free(seq.state_slot)
        for _, slot in seq.state_due:
            st.free(slot)
        seq.state_slot = seq.state_from = 0
        seq.state_due, seq.state_hashes = [], {}

    def prefill_cut(self, seq: Sequence, n: int) -> int:
        """``n`` tokens of ``seq``'s prompt, cut where a snapshot is due: a
        chunk's final state is the only one a prefill emits, so a chunk ends
        at the next multiple of the stride. Every other model: ``n``."""
        if self.state is None:
            return n
        stride = self.state.stride
        return min(n, stride - seq.num_prefilled % stride)

    def state_prefill_done(self, seq: Sequence) -> None:
        """``seq``'s chunk is enqueued and its full pages registered: the
        snapshot it read, if any, is released, and where the chunk ended on a
        boundary the slot it wrote becomes that block's snapshot (if the
        block has none) and the sequence goes on in a new slot, reading the
        snapshot with its next dispatch."""
        st = self.state
        if st is None or not seq.state_slot:
            return
        self.state_release_reads([seq])
        n = seq.num_computed
        if not n or n % st.stride:
            return
        h = seq.state_hashes.pop(n, None)
        if h is None or st.lookup(h) is not None:
            return
        try:
            new = st.pop()
        except AllocationError:
            return  # no slot to go on in: no snapshot here
        st.register(seq.state_slot, h)
        st.pin(seq.state_slot)
        seq.state_from, seq.state_slot = seq.state_slot, new

    def state_decode_slots(self, seq: Sequence, pos: int, k: int) -> tuple:
        """``(slot a, slot b, switch)`` of ``seq``'s lane for a burst of ``k``
        steps whose first token stands at ``pos`` (``llama._decode_body``:
        the token at ``switch`` reads ``a`` and writes ``b``). A snapshot the
        sequence has still to read is ``a`` with ``switch = pos``; a burst
        that passes a boundary takes a new slot for what follows it and
        leaves the old one to be registered when the tokens are committed
        (``state_commit``). The caller releases the reads after the build
        (``state_release_reads``): a slot released earlier could be reused
        as another lane's ``b`` in the same dispatch."""
        st = self.state
        slot = seq.state_slot
        if seq.state_from != slot:
            return seq.state_from, slot, pos
        # the first position of the burst whose token finds a whole number
        # of strides before it (``pos`` itself, where it stands on one)
        boundary = -(-pos // st.stride) * st.stride
        if boundary > pos + k - 1:
            return slot, slot, 0
        try:
            new = st.pop()
        except AllocationError:
            return slot, slot, 0  # no slot to go on in: no snapshot here
        seq.state_due.append((boundary, slot))
        seq.state_slot = seq.state_from = new
        return slot, new, boundary

    def state_release_reads(self, seqs: Seq[Sequence]) -> None:
        """The dispatch that reads these sequences' snapshots is built."""
        for seq in seqs:
            if seq.state_slot and seq.state_from != seq.state_slot:
                self.state.unpin(seq.state_from)
                seq.state_from = seq.state_slot

    def state_commit(self, seq: Sequence) -> None:
        """``seq``'s tokens up to ``num_computed`` are committed and its full
        pages registered: the slots it left behind at the boundaries it has
        passed become those blocks' snapshots (or go back, where a block has
        one already)."""
        st = self.state
        if st is None:
            return
        while seq.state_due and seq.state_due[0][0] <= seq.num_computed:
            boundary, slot = seq.state_due.pop(0)
            h = seq.state_hashes.pop(boundary, None)
            if h is None or not st.register(slot, h):
                st.free(slot)

    def append_slot(self, seq: Sequence) -> None:
        """Ensure capacity for one more token during decode; allocates a new
        page when the sequence crosses a page boundary."""
        ps = self.config.page_size
        if seq.num_tokens > len(seq.block_table) * ps:
            self._alloc_tenant = seq.tenant
            seq.block_table.append(self._pop_free_page())
        self.reserve_window(seq, seq.num_tokens - 1, seq.num_tokens)

    def reserve_slots(self, seq: Sequence, n: int) -> None:
        """Ensure KV-slot capacity for a fused decode burst: positions up to
        ``num_tokens + n - 1`` (token ``num_tokens - 1`` is the burst input;
        step j writes KV at position ``num_tokens - 1 + j``). Allocates all
        crossing pages up front; on exhaustion mid-way the partial growth is
        kept (the caller's preempt-and-retry loop continues from it)."""
        ps = self.config.page_size
        needed = -(-(seq.num_tokens + n - 1) // ps)
        self._alloc_tenant = seq.tenant
        while len(seq.block_table) < needed:
            seq.block_table.append(self._pop_free_page())
        self.reserve_window(seq, seq.num_tokens - 1, seq.num_tokens + n - 1)

    def register_full_pages(self, seq: Sequence) -> None:
        """Hash + cache-register any newly-completed pages of ``seq`` and
        queue their BlockStored events. Called after compute has written the
        page contents. Incremental: only blocks completed since the last
        call are hashed (the chain parent rides on the sequence), keeping
        per-sequence total hashing O(tokens) rather than O(tokens²)."""
        from ..kvcache.kvblock.token_processor import hash_block

        ps = self.config.page_size
        n_full = seq.num_computed // ps
        if n_full <= seq.num_registered_pages:
            return
        tokens = seq.all_tokens
        parent = (
            seq.last_chain_hash
            if seq.last_chain_hash is not None
            else self.token_db.init_hash
        )
        for i in range(seq.num_registered_pages, n_full):
            block = tuple(int(t) for t in tokens[i * ps : (i + 1) * ps])
            h = hash_block(parent, block)
            if self.state is not None and (i + 1) * ps % self.state.stride == 0:
                seq.state_hashes[(i + 1) * ps] = h  # a snapshot's key
            if self.window is not None:
                held = i - seq.window_first
                if 0 <= held < len(seq.window_table) and seq.window_table[held]:
                    self.window.register(seq.window_table[held], h)
            page = seq.block_table[i]
            info = self._pages[page]
            if info.chain_hash is None:
                existing = self._cached.get(h)
                if existing is not None and existing != page:
                    # Another sequence registered this block concurrently;
                    # keep ours unhashed (it frees normally).
                    parent = h
                    continue
                info.chain_hash = h
                info.token_ids = block
                info.parent_hash = parent if i > 0 else None
                info.tenant = seq.tenant
                self._cached[h] = page
                self._record_lifecycle(h, "tpu_hbm", "allocate", tenant=seq.tenant)
                self._emit(
                    BlockStored(
                        block_hashes=[h],
                        parent_block_hash=info.parent_hash,
                        token_ids=list(block),
                        block_size=ps,
                        medium="tpu_hbm",
                    )
                )
            parent = h
        seq.num_registered_pages = n_full
        seq.last_chain_hash = parent

    def free_sequence(self, seq: Sequence) -> None:
        for page in seq.block_table:
            self._decref(page)
        seq.block_table = []
        self._free_window(seq)
        self._free_state(seq)
