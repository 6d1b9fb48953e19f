"""Metric arithmetic on the client's records. One record per request:

``{"index", "due", "start", "scored", "sent", "done"`` (seconds on the
client's clock, window start = 0), ``"status"`` (HTTP status or None),
``"pod", "prompt_len", "prefix_len", "max_tokens", "body"`` (the pod's
JSON), ``"error"`` (text or None)``}``. A request still in flight when the
window closed has ``done`` None and counts neither way.
"""

from __future__ import annotations

import math

#: a tail is reported where at least this many samples lie beyond it
TAIL_SAMPLES_BEYOND = 10


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tail_supported(n: int, q: float) -> bool:
    """The ten-beyond rule: a q-th percentile over n samples is a tail and
    not a maximum only where ten or more samples lie beyond it."""
    return math.floor(n * (100.0 - q) / 100.0) >= TAIL_SAMPLES_BEYOND


def response_fault(rec: dict):
    """Why a finished request's response is not a correct one, or None."""
    if rec["status"] != 200:
        return f"status {rec['status']}: {rec.get('error')}"
    body = rec["body"]
    try:
        choice = body["choices"][0]
        usage = body["usage"]
        n_out = len(choice["token_ids"])
        if n_out != rec["max_tokens"]:
            return f"{n_out} tokens, wanted {rec['max_tokens']}"
        if choice["finish_reason"] != "length":
            return f"finish_reason {choice['finish_reason']!r}"
        if usage["prompt_tokens"] != rec["prompt_len"]:
            return f"prompt_tokens {usage['prompt_tokens']}"
        if not 0 <= usage["cached_prompt_tokens"] <= usage["prompt_tokens"]:
            return f"cached_prompt_tokens {usage['cached_prompt_tokens']}"
        ttft = body["ttft_s"]
        if ttft is None or not ttft >= 0:
            return f"ttft_s {ttft!r}"
        if ttft > rec["done"] - rec["sent"]:
            return "ttft_s above the client's whole-request time"
    except (KeyError, IndexError, TypeError) as e:
        return f"malformed response: {e!r}"
    return None


def split(records):
    """(good, failed, in_flight). A refused, failed, timed-out or
    malformed response is a failure."""
    good, failed, in_flight = [], [], []
    for r in records:
        if r["done"] is None:
            in_flight.append(r)
        elif response_fault(r) is None:
            good.append(r)
        else:
            failed.append(r)
    return good, failed, in_flight


def ttft_s(rec: dict) -> float:
    """Due time -> POST to the pod sent (generator lateness + scoring +
    routing, client's clock) + the pod's own arrival -> first token. Nothing
    streams, so no client can see a first token; this is the honest sum."""
    return (rec["sent"] - rec["due"]) + rec["body"]["ttft_s"]


def itl_s(rec: dict):
    """(whole request from its due time - time to first token) over the
    gaps between output tokens; None for a single output token."""
    n = rec["body"]["usage"]["completion_tokens"]
    if n < 2:
        return None
    return (rec["done"] - rec["due"] - ttft_s(rec)) / (n - 1)


def out_tokens(good) -> int:
    return sum(r["body"]["usage"]["completion_tokens"] for r in good)


def out_tokens_per_s(good, in_flight_tokens: int, window_s: float) -> float:
    """Every output token generated inside the window over the whole window:
    the tokens of the requests that completed in it (their responses) plus
    what the requests in flight at the close had generated by then (the
    schedulers' ``num_generated`` at that instant). Counting completed
    requests alone moved the rate by a request's whole size: 4-7 % between
    runs of the closed-loop cell (my chip runs, PR 24)."""
    return (out_tokens(good) + in_flight_tokens) / window_s


def end_to_end(good, in_flight_tokens: int, window_s: float) -> dict:
    """Every end-to-end metric the records support, by name."""
    out = {}
    if good:
        ttfts = [ttft_s(r) * 1e3 for r in good]
        out["ttft_ms_p50"] = percentile(ttfts, 50)
        out["ttft_ms_p95"] = percentile(ttfts, 95)
        itls = [x * 1e3 for x in map(itl_s, good) if x is not None]
        if itls:
            out["itl_ms_p50"] = percentile(itls, 50)
    out["out_tokens_per_s"] = out_tokens_per_s(good, in_flight_tokens, window_s)
    return out
