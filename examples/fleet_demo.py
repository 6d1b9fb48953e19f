"""Fleet smoke: pod-server subprocess + scoring service, full wire protocol.

The executable analogue of the reference's cluster smoke script
(``tests/kind-vllm-cpu.sh``) without needing a cluster: a real pod server
(tiny model, Pallas interpreter mode, real ZMQ PUB) serves a completion over
HTTP; its BlockStored events cross a TCP ZMQ hop into the scoring service's
SUB-bound subscriber; the indexer then scores the pod for the same prompt —
the complete closed loop every deployment relies on.

Run (CPU only):
    JAX_PLATFORMS=cpu python examples/fleet_demo.py

NOT a pattern for a TPU host. This parent imports JAX and the ``server``
package before it starts the pod as a child; it pins itself (and, through
the inherited environment, the child) to the CPU, which is right for a
demo. On a machine with a chip, one process holds the chip: a parent that
has initialised a JAX backend takes it, and a child that needs it then
fails or hangs. There, run every replica in ONE process, each engine on
its own device (``PodServer(mesh=...)`` — see ``chip_smoke.py``), or keep
the launching parent off JAX entirely.
"""

import asyncio
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCORE_PORT = int(os.environ.get("DEMO_SCORE_PORT", 8287))
POD_PORT = int(os.environ.get("DEMO_POD_PORT", 8288))
ZMQ_PORT = int(os.environ.get("DEMO_ZMQ_PORT", 5701))
MODEL = "tiny-llama"
PROMPT = ("the quick brown fox jumps over the lazy dog; pack my box with " + "x" * 64)[:64]


def post(url, payload, timeout=300):
    req = urllib.request.Request(
        url, json.dumps(payload).encode(), {"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from aiohttp import web

    from llm_d_kv_cache_manager_tpu.server.api import ScoringService, ServiceConfig
    from llm_d_kv_cache_manager_tpu.tokenization import Tokenizer

    class CharTokenizer(Tokenizer):
        def encode(self, prompt, model_name):
            return [ord(c) for c in prompt], [(i, i + 1) for i in range(len(prompt))]

    svc = ScoringService(
        ServiceConfig(block_size=16, zmq_endpoint=f"tcp://*:{ZMQ_PORT}"),
        tokenizer=CharTokenizer(),
    )
    svc.start()

    # Serve the scoring app on a dedicated thread so this (main) thread's
    # blocking HTTP calls cannot deadlock it.
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    async def _serve():
        runner = web.AppRunner(svc.build_app())
        await runner.setup()
        await web.TCPSite(runner, "127.0.0.1", SCORE_PORT).start()
        return runner

    runner = asyncio.run_coroutine_threadsafe(_serve(), loop).result(timeout=30)
    print(f"[demo] scoring service on :{SCORE_PORT}, events SUB on :{ZMQ_PORT}")

    env = {
        **os.environ,
        "PYTHONPATH": REPO,
        "MODEL_NAME": MODEL,
        "POD_IDENTIFIER": "tpu-pod-A",
        "ZMQ_ENDPOINT": f"tcp://localhost:{ZMQ_PORT}",
        "BLOCK_SIZE": "16",
        "TOTAL_PAGES": "128",
        "MAX_MODEL_LEN": "128",
        "DECODE_BATCH_SIZE": "4",
        "HTTP_PORT": str(POD_PORT),
        # The child is a CPU pod whatever the parent's environment said.
        "JAX_PLATFORMS": "cpu",
        "INTERPRET": "1",
    }
    # Child output goes to a file, not a pipe: an undrained pipe fills at
    # ~64KB of chatty logging and blocks the child mid-write.
    import tempfile

    pod_log = tempfile.NamedTemporaryFile(
        prefix="fleet-demo-pod-", suffix=".log", delete=False
    )
    pod = subprocess.Popen(
        [sys.executable, "-m", "llm_d_kv_cache_manager_tpu.server.serve"],
        cwd=REPO,
        env=env,
        stdout=pod_log,
        stderr=subprocess.STDOUT,
    )
    try:
        deadline = time.time() + 120
        while True:
            try:
                assert get(f"http://127.0.0.1:{POD_PORT}/healthz")["status"] == "ok"
                break
            except Exception:
                if pod.poll() is not None:
                    print(open(pod_log.name).read())
                    raise RuntimeError("pod server died during startup")
                if time.time() > deadline:
                    raise RuntimeError("pod server never became healthy")
                time.sleep(0.5)
        print("[demo] pod server healthy")
        time.sleep(1.5)  # ZMQ slow-joiner: let the SUB see the PUB

        ids = [ord(c) for c in PROMPT]
        out = post(
            f"http://127.0.0.1:{POD_PORT}/v1/completions",
            {"prompt_token_ids": ids, "max_tokens": 4},
        )
        assert len(out["choices"][0]["token_ids"]) == 4, out
        print(f"[demo] completion ok: ttft={out['ttft_s']:.3f}s")

        expect = len(PROMPT) // 16
        deadline = time.time() + 30
        scores = {}
        while time.time() < deadline:
            scores = post(
                f"http://127.0.0.1:{SCORE_PORT}/score_completions",
                {"prompt": PROMPT, "model": MODEL},
                timeout=30,
            )["scores"]
            if scores.get("tpu-pod-A", 0) >= expect:
                break
            time.sleep(0.3)
        assert scores.get("tpu-pod-A", 0) >= expect, f"scores never warmed: {scores}"
        print(f"[demo] routing scores after serving: {scores}")
        print("[demo] PASSED")
        return 0
    finally:
        pod.send_signal(signal.SIGTERM)
        try:
            pod.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pod.kill()
        asyncio.run_coroutine_threadsafe(runner.cleanup(), loop).result(timeout=10)
        loop.call_soon_threadsafe(loop.stop)
        svc.shutdown()


if __name__ == "__main__":
    sys.exit(main())
