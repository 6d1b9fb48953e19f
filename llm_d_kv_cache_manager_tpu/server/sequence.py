"""Request/sequence state for the serving engine."""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Optional

_id_counter = itertools.count()


class SequenceStatus(Enum):
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED = "finished"


@dataclass
class SamplingParams:
    max_new_tokens: int = 64
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0
    stop_token_ids: tuple[int, ...] = ()
    #: generation by diffusion over blocks (a model with ``block_length``
    #: > 0; the engine refuses either on another model). None = the
    #: default: ``block_length`` denoising steps a block, a confidence
    #: threshold of ``DEFAULT_CONFIDENCE_THRESHOLD``.
    denoising_steps: Optional[int] = None
    confidence_threshold: Optional[float] = None


#: the family's released generation script's (``generate.py``)
DEFAULT_CONFIDENCE_THRESHOLD = 0.9
REMASKING_STRATEGY = "low_confidence_dynamic"


def check_block_sampling(sampling: SamplingParams, block_length: int) -> None:
    """Raise ``ValueError`` (a 400 at the API) for block-diffusion
    parameters the model cannot honour: either of them on an autoregressive
    model, ``denoising_steps`` outside 1..``block_length``."""
    steps = sampling.denoising_steps
    if block_length <= 0:
        if (steps, sampling.confidence_threshold) != (None, None):
            raise ValueError(
                "denoising_steps and confidence_threshold need a model that "
                "generates by diffusion over blocks; this one is autoregressive"
            )
        return
    if steps is not None and not 1 <= steps <= block_length:
        raise ValueError(
            f"denoising_steps {steps} outside 1..{block_length} "
            "(the model's block_length)"
        )


def check_remasking_strategy(strategy, block_length: int) -> None:
    """The API's ``remasking_strategy``: one value is served, so nothing is
    kept of it; any other, or the key on an autoregressive model, raises
    ``ValueError`` (a 400)."""
    if strategy is None:
        return
    if block_length <= 0:
        raise ValueError(
            "remasking_strategy needs a model that generates by diffusion "
            "over blocks; this one is autoregressive"
        )
    if strategy != REMASKING_STRATEGY:
        raise ValueError(
            f"unknown remasking_strategy {strategy!r}: only "
            f"{REMASKING_STRATEGY!r} is served"
        )


@dataclass
class Sequence:
    prompt_tokens: list[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    seq_id: int = field(default_factory=lambda: next(_id_counter))
    request_id: Optional[str] = None

    # engine-managed state
    status: SequenceStatus = SequenceStatus.WAITING
    output_tokens: list[int] = field(default_factory=list)
    block_table: list[int] = field(default_factory=list)
    #: a model with sliding-window layers: the window pool's pages that hold
    #: the blocks ``window_first, window_first + 1, ...`` of this sequence
    #: (its last window and what it is computing; 0 for a block that is
    #: never read). The block manager gives the front back as the sequence
    #: moves on. Empty for every other model.
    window_table: list[int] = field(default_factory=list)
    window_first: int = 0
    #: a model with linear-attention layers: the state pool's slot this
    #: sequence's state is written to (its live slot; 0: none), the slot its
    #: next dispatch reads it from (the same, or a snapshot it starts from or
    #: has just left behind), the slots it left at boundaries whose tokens
    #: are not committed yet ``[(boundary, slot)]`` and the chain hashes of
    #: the blocks that end at boundaries it has registered ``{boundary:
    #: hash}`` (``BlockManager``'s ``state_*``). Untouched by other models.
    state_slot: int = 0
    state_from: int = 0
    state_due: list = field(default_factory=list)
    state_hashes: dict = field(default_factory=dict)
    #: tokens whose K/V are resident in pages (cached prefix + processed)
    num_computed: int = 0
    #: tokens of the prompt served from the prefix cache
    num_cached_prompt: int = 0
    #: prompt tokens whose K/V are resident (cached prefix + prefilled
    #: chunks). Equals num_cached_prompt right after allocation and
    #: len(prompt_tokens) once prefill completes; strictly between the two
    #: while a sequence is mid-prefill under chunked-prefill scheduling.
    num_prefilled: int = 0
    #: total generated tokens — survives preemption (output_tokens may be
    #: folded into prompt_tokens when a sequence is preempted and recomputed)
    num_generated: int = 0
    #: length of the user's original prompt, for reporting after preemption
    user_prompt_len: int = -1
    #: prefix-cache registration bookkeeping (incremental hashing)
    num_registered_pages: int = 0
    last_chain_hash: Optional[int] = None
    #: when the ENGINE LOOP made this Sequence (between two steps), which
    #: is later than when the request reached the pod: see ``submit_time``
    arrival_time: float = field(default_factory=time.monotonic)
    #: when the pod took the request (``PodServer.submit``'s one clock
    #: read, riding the staging tuple here); ``arrival_time`` for a
    #: sequence added to the engine directly. ``staged_s`` and ``queue_s``
    #: count from it; ``ttft`` still counts from ``arrival_time``.
    submit_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    #: when the first prefill chunk for this sequence dispatched — the
    #: queue→compute boundary the latency decomposition (queue span /
    #: kvcache_request_queue_seconds) is derived from. Always stamped
    #: (one clock read per prefill batch; no behavior change).
    prefill_start_time: Optional[float] = None
    #: the router's verdict that placed this request here ("route_warm" /
    #: "pull" / "cold"), when the serving layer knows it — labels the
    #: latency histograms; None = derived from num_cached_prompt.
    route_action: Optional[str] = None
    #: live ``obs.tracing.Span`` for the request (serving layer owns it;
    #: child queue/prefill/decode spans are reconstructed from the
    #: timestamps above when the request resolves). None = tracing off.
    trace_span: Optional[object] = None
    #: absolute monotonic deadline (``time.monotonic()`` scale). None
    #: (default) = no deadline — bit-identical legacy behavior. An expired
    #: waiting sequence is shed before prefill; an expired running sequence
    #: finishes at the next commit point with ``finish_reason="deadline"``.
    deadline: Optional[float] = None
    #: why the request ended early, when not a normal stop/length finish:
    #: "deadline" (expired) or "abort" (client gone / operator abort).
    #: None = the normal finish reasons apply.
    finish_reason: Optional[str] = None
    #: set when the engine had to abort the request (e.g. unschedulable)
    error: Optional[str] = None
    #: speculative-decode acceptance history (drives the engine's adaptive
    #: per-sequence gate; survives preemption with the sequence)
    spec_proposed: int = 0
    spec_accepted: int = 0
    #: memoized prompt prefix-chain hashes for the host-tier prefetch
    #: stage (hashing is O(prompt) sha256 work; a sequence may wait many
    #: steps). Invalidated when preemption folds output into the prompt.
    prefetch_hashes: Optional[list[int]] = None
    #: async KV-pull (``ASYNC_PULL``): True while a background transfer
    #: fetch is importing this sequence's warm prefix — the scheduler
    #: skips it (admitting later waiting sequences past it) until the
    #: import lands or fails, so a slow wire never stalls admission.
    #: False (default) = legacy behavior, the scheduler never checks it.
    importing: bool = False
    #: when the scheduler FIRST skipped this sequence because its import
    #: was still in flight — the hidden/exposed boundary of the pull
    #: overlap decomposition (pull time before this instant was hidden
    #: behind other work; time after it delayed this sequence's prefill).
    import_wanted_time: Optional[float] = None
    #: OBS_LIFECYCLE reuse-distance MRC: True once this request's prefix
    #: chain has been observed by the estimator. Allocation rollbacks
    #: (scheduler budget overflow) and preemption re-prefills call
    #: ``allocate`` again for the SAME request — re-observing would feed
    #: tiny artificial reuse distances and bias the curve upward, the
    #: same reason ``hit_stats`` snapshots only the first prefill.
    mrc_observed: bool = False
    #: TENANT_QOS slice key this request is charged to ("" = knob off,
    #: no tenant dimension anywhere). Unknown tenants are collapsed onto
    #: the "*" slice by the serving layer before the sequence is built.
    tenant: str = ""
    #: TENANT_QOS priority class (0 = highest). The scheduler orders the
    #: waiting queue by class and preemption only takes pages from a
    #: strictly lower class. 0 for every sequence when the knob is off,
    #: so ordering is a no-op.
    priority: int = 0
    #: TENANT_QOS weighted-fair share within the class (> 0).
    qos_weight: float = 1.0
    #: per-tenant hit-stats bookkeeping: True once this request's first
    #: successful allocation has been counted (same first-prefill-only
    #: rationale as ``mrc_observed``).
    qos_observed: bool = False
    #: the model's ``block_length`` (0 = autoregressive; the engine sets
    #: it). With B > 0 the prefill covers whole blocks of the prompt only,
    #: ``num_computed`` counts tokens of FINAL blocks (a multiple of B while
    #: the sequence runs) and ``output_tokens`` / ``num_generated`` /
    #: ``first_token_time`` advance when a block is final.
    block_length: int = 0
    #: the block in progress: its B tokens (mask ids where not fixed yet),
    #: which rows are still masked, and the denoising steps it has had.
    #: None = no block open (before the first decode dispatch, after a
    #: block was committed, after preemption: a block interrupted half way
    #: starts again from masks).
    block_tokens: Optional[list[int]] = None
    block_masked: Optional[list[bool]] = None
    block_step: int = 0

    def __post_init__(self):
        if self.user_prompt_len < 0:
            self.user_prompt_len = len(self.prompt_tokens)
        if self.submit_time is None:
            self.submit_time = self.arrival_time

    @property
    def all_tokens(self) -> list[int]:
        return self.prompt_tokens + self.output_tokens

    @property
    def last_token(self) -> int:
        """``all_tokens[-1]`` without building the list: the engine asks
        several times a lane a step, and a prompt may be 30k tokens."""
        return (self.output_tokens or self.prompt_tokens)[-1]

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_tokens) + len(self.output_tokens)

    @property
    def generated_tokens(self) -> list[int]:
        """User-visible output, stable across preemption."""
        return self.all_tokens[self.user_prompt_len :]

    @property
    def prompt_tail(self) -> int:
        """Prompt tokens the prefill leaves to the first generated block:
        ``len(prompt) % block_length`` (0 for an autoregressive model)."""
        if not self.block_length:
            return 0
        return len(self.prompt_tokens) % self.block_length

    @property
    def prompt_remaining(self) -> int:
        """Prompt tokens still to prefill (chunked-prefill progress)."""
        return len(self.prompt_tokens) - self.prompt_tail - self.num_prefilled

    def open_block(self, mask_token_id: int) -> None:
        """Open the block after the final ones: the tokens that already
        stand in it (the prompt's tail, for the first block) and masks."""
        tail = self.all_tokens[self.num_computed :]
        n_mask = self.block_length - len(tail)
        assert n_mask > 0, "a running sequence's context is whole blocks"
        self.block_tokens = tail + [mask_token_id] * n_mask
        self.block_masked = [False] * len(tail) + [True] * n_mask
        self.block_step = 0

    @property
    def block_fixed(self) -> bool:
        """A block is open and none of its rows is masked any more: its
        next forward is the committing one."""
        return self.block_masked is not None and not any(self.block_masked)

    def reset_allocation(self) -> None:
        """Clear all page/prefix-cache bookkeeping (single source of truth
        for rollback and preemption)."""
        self.num_computed = 0
        self.num_cached_prompt = 0
        self.num_prefilled = 0
        self.num_registered_pages = 0
        self.last_chain_hash = None
        self.block_tokens = None
        self.block_masked = None
        self.block_step = 0

    def fold_for_preemption(self) -> None:
        """Recompute-preemption: all tokens become the new 'prompt'; the
        re-prefill will cache-hit the pages that survived eviction."""
        self.prompt_tokens = self.all_tokens
        self.output_tokens = []
        self.prefetch_hashes = None  # prompt changed: memo is stale
        self.reset_allocation()
        self.status = SequenceStatus.WAITING

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    @property
    def staged_s(self) -> float:
        """Submitted -> taken by the engine loop: the wait behind the step
        in progress, which ``ttft`` leaves out."""
        return max(self.arrival_time - self.submit_time, 0.0)

    @property
    def queue_s(self) -> Optional[float]:
        """Submitted -> first prefill dispatch, staging included (what the
        ``pod.queue`` span covers); the whole life of a request that
        finished without reaching prefill; None while it still waits."""
        for end in (self.prefill_start_time, self.finish_time):
            if end is not None:
                return max(end - self.submit_time, 0.0)
        return None

    @property
    def mean_itl(self) -> Optional[float]:
        """Mean inter-token latency over the generated tokens; None when
        not measurable (unfinished, or <= 1 generated token). The one
        definition both the latency histograms and the SLO recorder feed
        from — they must never diverge."""
        if (
            self.finish_time is None
            or self.first_token_time is None
            or self.num_generated <= 1
        ):
            return None
        return max(self.finish_time - self.first_token_time, 0.0) / (
            self.num_generated - 1
        )

    def is_finished(self) -> bool:
        return self.status == SequenceStatus.FINISHED
