"""The one general traffic generator. A traffic mix is a data file under
``chipbench/traffic/`` whose ``kind`` names an arrival process here
(``open_poisson``, ``closed``); everything else in the file is parameters,
``request`` (what every request adds to its body) among them.

Every seed does the same work at the same times: lengths, prefix groups and
arrival times are drawn from the file's own ``sizes_seed``; the run's
``--seed`` draws the text (and, in ``run.py``, the weights). A first version
permuted the order by seed: between seeds the tail of the time to first
token then spread by 22 %, between two runs of one seed by 1-4 % (my chip
runs, PR 24) — the seed was changing the work. Tokens are code points of
seeded ASCII text, so scorer and pod hash the same ids.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Optional

import numpy as np

from chipbench.fleet import BenchFailure

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("open_poisson", "closed")


@dataclasses.dataclass
class Request:
    index: int
    due_s: Optional[float]  # None in a closed loop: sent when a caller is free
    group: Optional[int]
    prefix_len: int  # shared with the group (0 = nothing shared)
    prompt: str
    max_tokens: int
    params: Optional[dict] = None  # the mix's ``request``, where it carries it

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclasses.dataclass
class Schedule:
    kind: str
    rate_rps: Optional[float]
    callers: Optional[int]
    prefixes: list[str]  # one per group
    requests: list[Request]


def load_traffic(name: str, rehearse: bool = False) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no traffic file {path}")
    with open(path) as f:
        spec = json.load(f)
    if rehearse:
        spec = {**spec, **spec.get("rehearse", {})}
    if spec.get("kind") not in KINDS:
        raise ValueError(f"traffic {name!r}: kind must be one of {KINDS}")
    return spec


def ascii_text(rng: np.random.Generator, n: int) -> str:
    return rng.integers(33, 127, n, dtype=np.uint8).tobytes().decode("ascii")


def draw_lengths(rng: np.random.Generator, dist: dict, n: int) -> np.ndarray:
    """n whole lengths from {"dist": "lognormal"|"uniform", ...}, clipped."""
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        x = rng.lognormal(math.log(dist["median"]), dist["sigma"], n)
    elif dist["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(int)


#: keys of the body that belong to the schedule, never to a mix's ``request``
SCHEDULED_KEYS = frozenset({"prompt", "max_tokens"})


def request_params(spec: dict, n: int, stream: int) -> list:
    """What each of n requests adds to its ``POST /v1/completions`` body: the
    mix's ``request`` dict (``temperature``, ``top_k``, ``top_p``, whatever
    the pod's API reads), or None (the body as it is without the key). With
    ``request_share`` that fraction of the requests carries it, drawn from
    ``sizes_seed`` on a stream of its own, so every seed sends the same.
    ``prompt`` and ``max_tokens`` are the schedule's (the warmed shapes and
    ``out_tokens_per_s`` follow them): a mix that states either is refused."""
    params = spec.get("request")
    if not params:
        return [None] * n
    taken = sorted(SCHEDULED_KEYS & set(params))
    if taken:
        raise BenchFailure(f"traffic: request may not state {taken}: the "
                           "schedule draws them")
    rng = np.random.default_rng([int(spec["sizes_seed"]), stream])
    carries = rng.random(n) < float(spec.get("request_share", 1.0))
    return [params if c else None for c in carries]


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=float) ** s
    return w / w.sum()


def group_prefix_lens(spec: dict, pool_tokens: int) -> list[int]:
    """Prefix length of each group: the file's lengths in equal shares, as
    many whole rounds of them as fit ``pool_share`` of the fleet's pool."""
    g = spec.get("groups")
    if not g:
        return []
    lens = [int(x) for x in g["prefix_tokens"]]
    rounds = max(1, int(g["pool_share"] * pool_tokens // sum(lens)))
    return lens * rounds


def build_schedule(spec: dict, seed: int, seconds: float, *, pods: int,
                   pool_tokens_per_pod: int, lanes: int,
                   rate_rps: Optional[float] = None) -> Schedule:
    """The requests of one window. ``rate_rps`` overrides the file's (the
    sweep's only use)."""
    sizes = np.random.default_rng(int(spec["sizes_seed"]))
    text = np.random.default_rng([int(seed), 2])

    kind = spec["kind"]
    rate = callers = None
    if kind == "open_poisson":
        rate = rate_rps if rate_rps is not None else spec["rate_rps"]
        if rate is None:
            raise ValueError("the traffic file has no rate yet: its knee has "
                             "not been swept (give --rate to sweep it)")
        rate = float(rate)
        gaps, t = [], 0.0
        while True:
            gap = sizes.exponential(1.0 / rate)
            if t + gap >= seconds:
                break
            gaps.append(gap)
            t += gap
        gaps = np.asarray(gaps)
        n = len(gaps)
        due = np.cumsum(gaps)
    else:
        callers = int(spec["callers_per_lane"]) * lanes * pods
        n = int(spec["requests"])
        due = [None] * n

    prefix_lens = group_prefix_lens(spec, pool_tokens_per_pod * pods)
    unique = draw_lengths(sizes, spec["unique"], n)
    output = draw_lengths(sizes, spec["output"], n)
    if prefix_lens:
        groups = sizes.choice(
            len(prefix_lens), n,
            p=zipf_weights(len(prefix_lens), spec["groups"]["zipf_s"]),
        )
    else:
        groups = np.full(n, -1)
    prefixes = [ascii_text(text, m) for m in prefix_lens]
    params = request_params(spec, n, 6)
    requests = []
    for r in range(n):
        g = int(groups[r])
        head = prefixes[g] if g >= 0 else ""
        requests.append(Request(
            index=r,
            due_s=None if due[r] is None else float(due[r]),
            group=g if g >= 0 else None,
            prefix_len=len(head),
            prompt=head + ascii_text(text, int(unique[r])),
            max_tokens=int(output[r]),
            params=params[r],
        ))
    return Schedule(kind=kind, rate_rps=rate, callers=callers,
                    prefixes=prefixes, requests=requests)


# -- the shapes a schedule compiles -----------------------------------------
def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class Buckets:
    """The engine's shape bucketing (``EngineConfig`` fields of the same
    names), read from the live engine so the plan follows the program."""
    page: int
    prefill_bucket: int
    prefill_ctx_bucket: int
    decode_pages_bucket: int
    max_pages: int

    def prefill_shape(self, prompt_len: int, cached: int) -> tuple[int, int]:
        cached = cached // self.page * self.page
        return (round_up(prompt_len - cached, self.prefill_bucket),
                round_up(cached // self.page, self.prefill_ctx_bucket))

    def decode_width(self, context_tokens: int) -> int:
        pages = -(-context_tokens // self.page)
        return min(self.max_pages, round_up(pages, self.decode_pages_bucket))

    def first_tokens_of_width(self, width: int) -> int:
        """Smallest context (tokens) whose table has this width."""
        return (width - self.decode_pages_bucket) * self.page + 1


def shape_set(requests, b: Buckets) -> tuple[set, set]:
    """(prefill shapes, decode widths) the window can reach when every
    shared prefix is resident. Batches take the largest member's bucket,
    which is some member's own, so the per-request set is the whole set."""
    prefill, decode = set(), set()
    for r in requests:
        prefill.add(b.prefill_shape(r.prompt_len, r.prefix_len))
        # the engine reserves the next token's slot, so the table may be
        # one token ahead of the context
        lo = b.decode_width(r.prompt_len + 1)
        hi = b.decode_width(r.prompt_len + r.max_tokens + 1)
        decode.update(range(lo, hi + 1, b.decode_pages_bucket))
    return prefill, decode


def warmup_plan(schedule: Schedule, b: Buckets, seed: int,
                burst: int) -> tuple[list[Request], list[Request]]:
    """(singles, burst): one clone per prefill shape and per decode width
    (fresh text of the same length, so nothing of the window is cached by
    it; as few output tokens as reach the width), then ``burst`` clones of
    the window's first requests to run batched prefill and full lanes."""
    text = np.random.default_rng([int(seed), 3])

    def clone(r: Request, max_tokens: int) -> Request:
        head = r.prompt[: r.prefix_len]
        return dataclasses.replace(
            r, due_s=None, max_tokens=max_tokens,
            prompt=head + ascii_text(text, r.prompt_len - r.prefix_len),
        )

    singles, seen = [], set()
    for r in schedule.requests:
        s = b.prefill_shape(r.prompt_len, r.prefix_len)
        if s not in seen:
            seen.add(s)
            singles.append(clone(r, 2))
    _, widths = shape_set(schedule.requests, b)
    by_index = {r.index: r for r in schedule.requests}
    for w in sorted(widths):
        need = {
            r.index: max(2, b.first_tokens_of_width(w) - r.prompt_len + 2)
            for r in schedule.requests
            if b.decode_width(r.prompt_len + 1) <= w
            <= b.decode_width(r.prompt_len + r.max_tokens + 1)
        }
        best = min(need, key=lambda i: (need[i], i))
        singles.append(clone(by_index[best], need[best]))
    many = [clone(r, min(r.max_tokens, 8))
            for r in schedule.requests[:burst]]
    return singles, many


def fill_plan(schedule: Schedule, spec: dict, seed: int) -> list[list]:
    """Rounds of (group, prompt) that make every shared prefix resident in
    pieces of ``fill_piece_tokens``: round k sends each group's first k
    pieces plus a short unique tail, so every fill dispatch has one chunk
    shape whatever the prefix length (and a padded 8-row dispatch of it
    stays small)."""
    if not schedule.prefixes:
        return []
    text = np.random.default_rng([int(seed), 4])
    piece = int(spec["fill_piece_tokens"])
    tail = int(spec["fill_tail_tokens"])
    if any(len(p) % piece for p in schedule.prefixes):
        raise ValueError("every prefix length must be whole fill pieces")
    rounds = []
    for k in range(1, max(len(p) for p in schedule.prefixes) // piece + 1):
        rounds.append([
            (g, p[: k * piece] + ascii_text(text, tail))
            for g, p in enumerate(schedule.prefixes) if len(p) >= k * piece
        ])
    return rounds
