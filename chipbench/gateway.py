"""The served path of every cell, and the load generator that drives it.

    generator (due time) -> gateway: POST /score_completions on the scorer
      -> kvcache.router.BlendedRouter (index score -> routed affinity ->
         least load; no cost model, predictor, auditor or remote arm)
      -> POST /v1/completions on the picked pod (prompt, max_tokens,
         temperature 0, then the parameters the traffic mix states under
         ``request``, if any)

With one pod the pick is trivial and its cost is still paid. One asyncio
loop thread carries every in-flight request of the generator.
"""

from __future__ import annotations

import asyncio
import threading
import time


class Gateway:
    def __init__(self, scorer_url: str, pods, model: str,
                 capacity_blocks: int):
        from llm_d_kv_cache_manager_tpu.kvcache.router import (
            BlendedRouter,
            PrefixAffinityTracker,
        )

        self.scorer_url = scorer_url
        self.pods = list(pods)
        self.names = [p.name for p in self.pods]
        self.model = model
        self.outstanding = [0] * len(self.pods)
        self._scores: dict = {}
        self.router = BlendedRouter(
            score_fn=lambda tokens, pods: self._scores,
            affinity=PrefixAffinityTracker(len(self.pods), capacity_blocks),
            loads_fn=lambda pods: list(self.outstanding),
        )
        self._session = None

    async def open(self) -> None:
        import aiohttp

        self._session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=600),
        )

    async def close(self) -> None:
        if self._session is not None:
            await self._session.close()

    async def _post(self, url: str, body: dict):
        async with self._session.post(url, json=body) as resp:
            return resp.status, await resp.json(content_type=None)

    async def score(self, prompt: str) -> dict:
        status, body = await self._post(
            f"{self.scorer_url}/score_completions",
            {"prompt": prompt, "model": self.model},
        )
        if status != 200:
            raise RuntimeError(f"score -> {status} {body}")
        return body.get("scores") or {}

    async def complete(self, req, due: float, clock, pod: int = None) -> dict:
        """One request through the served path; never raises. ``due`` and
        every time in the record are on ``clock`` (window start = 0).
        ``pod`` forces the target (the fill's cold placements only)."""
        rec = {
            "index": req.index, "due": due, "start": clock(), "scored": None,
            "sent": None, "done": None, "status": None, "pod": None,
            "prompt_len": req.prompt_len, "prefix_len": req.prefix_len,
            "group": req.group, "max_tokens": req.max_tokens, "body": None,
            "error": None,
        }
        i = None
        try:
            scores = await self.score(req.prompt)
            rec["scored"] = clock()
            # route() is synchronous and the loop is one thread: the scores
            # it reads are this request's
            self._scores = scores
            tokens = [ord(c) for c in req.prompt]
            decision = self.router.route(tokens, self.names, now=rec["scored"])
            i = self.names.index(decision.pod) if pod is None else pod
            if pod is not None:
                self.router.affinity.record(
                    self.router.affinity.keys(tokens), pod, rec["scored"]
                )
            rec["pod"] = i
            self.outstanding[i] += 1
            rec["sent"] = clock()
            status, body = await self._post(
                f"{self.pods[i].url}/v1/completions",
                {"prompt": req.prompt, "max_tokens": req.max_tokens,
                 "temperature": 0.0, **(req.params or {})},
            )
            rec["status"], rec["body"] = status, body
            if status != 200:
                rec["error"] = str(body)[:500]
            rec["done"] = clock()
        except asyncio.CancelledError:
            raise  # window closed: in flight, counted neither way
        except Exception as e:  # a failed request is a result, not a crash
            rec["error"] = f"{type(e).__name__}: {e}"[:500]
            rec["done"] = clock()
        finally:
            if i is not None:
                self.outstanding[i] -= 1
        return rec


class ClientLoop:
    """The generator's own thread and asyncio loop."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self.loop.run_forever, name="chipbench-client", daemon=True
        )
        self._thread.start()

    def run(self, coro, timeout: float = 3600.0):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def close(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=10)


async def send_all(gateway: Gateway, requests, pods=None) -> list[dict]:
    """Set-up traffic: every request at once, all awaited."""
    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0  # noqa: E731
    pods = pods or [None] * len(requests)
    return list(await asyncio.gather(*[
        gateway.complete(r, 0.0, clock, pod=p) for r, p in zip(requests, pods)
    ]))


async def run_window(gateway: Gateway, schedule, seconds: float,
                     on_tick=None, tick_s: float = 0.1, on_close=None) -> dict:
    """Offer the schedule for ``seconds``; at the close abort what is in
    flight. Returns {"records", "in_flight", "late_s", "window_s",
    "at_close"}. ``on_tick()`` is called every ``tick_s`` (the 10 Hz
    sampler); ``on_close()`` at the instant the window closes, before
    anything is aborted, and what it returns is ``at_close``."""
    records, tasks, late = [], set(), []
    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0  # noqa: E731
    started = {}

    def launch(req, due):
        task = asyncio.ensure_future(gateway.complete(req, due, clock))
        started[task] = (req, due)
        tasks.add(task)

        def finished(t):
            tasks.discard(t)
            if not t.cancelled():
                records.append(t.result())

        task.add_done_callback(finished)
        return task

    async def ticker():
        while True:
            await asyncio.sleep(tick_s)
            on_tick()

    tick_task = asyncio.ensure_future(ticker()) if on_tick else None
    callers = []
    try:
        if schedule.kind == "open_poisson":
            for req in schedule.requests:
                wait = req.due_s - clock()
                if wait > 0:
                    await asyncio.sleep(wait)
                late.append(clock() - req.due_s)
                launch(req, req.due_s)
        else:
            pending = iter(schedule.requests)

            async def caller():
                for req in pending:  # shared iterator: next unsent request
                    if clock() >= seconds:
                        return
                    # wait() leaves the request running when the caller is
                    # cancelled at the close: it is then counted in flight
                    await asyncio.wait([launch(req, clock())])

            callers = [asyncio.ensure_future(caller())
                       for _ in range(schedule.callers)]
        await asyncio.sleep(max(0.0, seconds - clock()))
        window_s = clock()
        at_close = on_close() if on_close else None
        for c in callers:
            c.cancel()
        await asyncio.gather(*callers, return_exceptions=True)
    finally:
        if tick_task is not None:
            tick_task.cancel()
    in_flight = []
    for task in list(tasks):
        req, due = started[task]
        task.cancel()
        in_flight.append({
            "index": req.index, "due": due, "done": None,
            "prompt_len": req.prompt_len, "max_tokens": req.max_tokens,
        })
    await asyncio.gather(*list(tasks), return_exceptions=True)
    # a response that arrived in the moment between the close and the abort
    # was in flight at the close: ``at_close`` has its tokens
    for rec in [r for r in records if r["done"] > window_s]:
        records.remove(rec)
        in_flight.append({**rec, "done": None, "body": None})
    return {"records": records, "in_flight": in_flight, "late_s": late,
            "window_s": window_s, "at_close": at_close}
