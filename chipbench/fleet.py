"""The fleet one run drives: scorer + ZMQ event plane + 1 or 4 in-process pods.

A copy of the recipe ``chip_smoke.py`` proved on the chip in PR 21
(``Fleet``, ``make_tokenizer``, ``start_scorer``, ``make_pod``), kept here
so that later PRs may change the smoke and not the yardstick. One process,
one replica per chip; every feature switch stays at its ``from_env()``
default — only sizing, identity and addresses are set.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request


class BenchFailure(Exception):
    """The run cannot give a result. Never caught: it ends the run non-zero."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@contextlib.contextmanager
def environ(**values: str):
    """``from_env()`` reads the process environment — set it for one call."""
    saved = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def http(method: str, url: str, body=None, timeout: float = 600.0):
    """(status, parsed JSON body); error statuses are returned, not raised."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            return e.code, json.loads(raw)
        except ValueError:
            return e.code, {"raw": raw.decode(errors="replace")}


class Fleet:
    """The HTTP side: one asyncio loop thread serving every aiohttp app."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self.loop.run_forever, name="chipbench-http", daemon=True
        )
        self._thread.start()
        self._runners = []

    def serve(self, app, port: int) -> str:
        from aiohttp import web

        async def _up():
            runner = web.AppRunner(app)
            await runner.setup()
            await web.TCPSite(runner, "127.0.0.1", port).start()
            return runner

        fut = asyncio.run_coroutine_threadsafe(_up(), self.loop)
        self._runners.append(fut.result(timeout=60))
        return f"http://127.0.0.1:{port}"

    def close(self) -> None:
        async def _down():
            for r in self._runners:
                await r.cleanup()

        asyncio.run_coroutine_threadsafe(_down(), self.loop).result(timeout=60)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=10)


def make_tokenizer():
    from llm_d_kv_cache_manager_tpu.tokenization import Tokenizer

    class CharTokenizer(Tokenizer):
        """Offline (there is no network): token id = code point, so scorer
        and pod hash the same ids for the same text."""

        def encode(self, prompt, model_name):
            return (
                [ord(c) for c in prompt],
                [(i, i + 1) for i in range(len(prompt))],
            )

        def decode(self, token_ids, model_name):
            return "".join(chr(t) if 32 <= t < 127 else "?" for t in token_ids)

    return CharTokenizer()


def build_native() -> None:
    """``*.so`` is git-ignored, so a checkout has none; without them hash
    chain and index fall back to pure Python without a word. Built before
    the first ``available()``: that call remembers a miss for the process."""
    from llm_d_kv_cache_manager_tpu.native import build, hashcore, lruindex

    if not all(os.path.isfile(os.path.join(build.HERE, lib))
               for lib in build.LIBS.values()):
        build.build(verbose=False)
    if not (hashcore.available() and lruindex.available()):
        raise BenchFailure("native hash/index libraries did not build")


def start_scorer(fleet: Fleet, zmq_port: int, page_size: int):
    """ScoringService the way ``server.api``'s main builds it."""
    from llm_d_kv_cache_manager_tpu.server.api import (
        ScoringService,
        ServiceConfig,
    )

    with environ(ZMQ_ENDPOINT=f"tcp://*:{zmq_port}",
                 BLOCK_SIZE=str(page_size)):
        cfg = ServiceConfig.from_env()
    svc = ScoringService(cfg, tokenizer=make_tokenizer())
    svc.start()
    url = fleet.serve(svc.build_app(), free_port())
    return svc, url


@dataclasses.dataclass
class Pod:
    name: str
    url: str
    server: object  # the in-process PodServer
    device: object

    @property
    def engine(self):
        return self.server.engine


def make_params(model_cfg, seed: int, device):
    """All weights in ONE jitted call from the seed, on the pod's own
    device, in the dtype they are served in (``llama.init_params`` itself,
    so the tree is whatever the engine expects)."""
    import functools

    import jax

    from llm_d_kv_cache_manager_tpu.models import llama

    make = jax.jit(functools.partial(llama.init_params, cfg=model_cfg))
    with jax.default_device(device):
        params = make(jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def make_pod(i: int, config: dict, model_cfg, zmq_port: int, device,
             seed: int, fleet: Fleet, rehearse: bool) -> Pod:
    """One replica, configured the way ``serve.main()`` configures it:
    ``PodServerConfig.from_env()`` under the configuration's sizing
    environment, then the configuration's pinned shape buckets."""
    from llm_d_kv_cache_manager_tpu.parallel import MeshConfig, make_mesh
    from llm_d_kv_cache_manager_tpu.server import Engine
    from llm_d_kv_cache_manager_tpu.server.serve import (
        PodServer,
        PodServerConfig,
    )

    name = f"chip-pod-{i}"
    env = {k: str(v) for k, v in config["env"].items()}
    env.update(
        MODEL_NAME=config["model_name"],
        POD_IDENTIFIER=name,
        ZMQ_ENDPOINT=f"tcp://localhost:{zmq_port}",
    )
    if rehearse:
        env["INTERPRET"] = "1"
    with environ(**env):
        cfg = PodServerConfig.from_env()
    cfg.engine.model = model_cfg
    cfg.engine.seed = seed
    for key, value in config.get("engine", {}).items():
        if not hasattr(cfg.engine, key):
            raise BenchFailure(f"EngineConfig has no field {key!r}")
        setattr(cfg.engine, key, value)
    mesh = make_mesh(MeshConfig(), devices=[device])
    # every replica serves the same weights, as a deployment's replicas do
    params = make_params(model_cfg, seed, device)
    engine = Engine(cfg.engine, params=params, mesh=mesh)
    server = PodServer(cfg, engine=engine, tokenizer=make_tokenizer())
    server.start()
    url = fleet.serve(server.build_app(), free_port())
    return Pod(name=name, url=url, server=server, device=device)


def wait_visible(scorer_url: str, prompt: str, model: str, pod_name: str,
                 want_blocks: int, timeout: float = 60.0) -> int:
    """Poll the score until ``pod_name``'s blocks crossed ZMQ."""
    deadline = time.monotonic() + timeout
    while True:
        status, body = http(
            "POST", f"{scorer_url}/score_completions",
            {"prompt": prompt, "model": model}, timeout=60,
        )
        if status != 200:
            raise BenchFailure(f"score -> {status} {body}")
        score = (body.get("scores") or {}).get(pod_name, 0)
        if score >= want_blocks:
            return score
        if time.monotonic() > deadline:
            raise BenchFailure(
                f"{pod_name}: scorer sees {score} of {want_blocks} blocks"
            )
        time.sleep(0.02)
