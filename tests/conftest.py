"""Test bootstrap.

Tests run on the CPU with a virtual 8-device mesh, so multi-chip sharding
and per-device placement are exercised without TPU hardware; engines say
``interpret=True`` (Pallas interpreter, XLA prefill). The machine with the
chip is reached through ``chip_smoke.py``, never through pytest.

The platform is pinned both ways — the environment for child processes,
``jax.config.update`` for this one — before the first backend
initialization, so a test run can never take (or wait for) a chip.
"""

import os
import sys

# Must precede the first jax backend initialization (not merely jax import).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llm_d_kv_cache_manager_tpu.tokenization import Tokenizer  # noqa: E402


class CharTokenizer(Tokenizer):
    """Shared offline test tokenizer: token id = ord(char), byte offsets."""

    def encode(self, prompt, model_name):
        return [ord(c) for c in prompt], [(i, i + 1) for i in range(len(prompt))]


def free_tcp_port() -> int:
    """An ephemeral TCP port — fixed test ports collide when suites run
    concurrently (two pytest processes, or pytest alongside a dev server)."""
    import socket

    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _build_native_once() -> None:
    """``*.so`` is git-ignored, so a fresh checkout has no native library:
    suites then decide ``skipif(not native_available())`` at collection and
    the loaders remember the miss for the life of a worker, and the count
    of passing tests depends on what was run in the tree before. Build
    them here, before any worker collects. No compiler: the skips stand."""
    import subprocess

    from llm_d_kv_cache_manager_tpu.native import build

    if all(os.path.isfile(os.path.join(build.HERE, lib))
           for lib in build.LIBS.values()):
        return
    try:
        build.build(verbose=False)
    except (OSError, subprocess.CalledProcessError) as e:
        print(f"native libraries not built ({e!r}): their tests skip",
              file=sys.stderr)


def pytest_configure(config):
    if not hasattr(config, "workerinput"):  # the controller, or no xdist
        _build_native_once()
    # Lock-order/race harness: LOCKTRACE=1 routes every lock created from
    # here on through utils.locktrace's TracingLock, so the concurrency
    # hammer and chaos suites run under cycle + guarded-attribute checking
    # (CI runs them that way; plain local runs are untouched).
    from llm_d_kv_cache_manager_tpu.utils import locktrace

    if locktrace.enabled():
        locktrace.activate()
    config.addinivalue_line(
        "markers",
        "network: needs a real HF tokenizer (network or populated HF cache); "
        "skips cleanly offline",
    )
    config.addinivalue_line(
        "markers",
        "slow: heavy fuzz matrices / multi-config sweeps — excluded from the "
        "fast pre-commit loop (`pytest -m 'not slow'`); CI's full job runs "
        "everything",
    )


#: Heavy suites (fuzz matrices, multi-config sweeps, cross-engine numerics
#: oracles) auto-marked ``slow`` — kept as one table instead of markers
#: scattered over seven files. Measured on the dev rig: the full suite is
#: ~12.5 min; `pytest -m "not slow"` keeps the per-commit loop under 5.
#: Coverage rationale: everything here is either randomized re-coverage of
#: paths the fast tests pin directly, or parity oracles that only move when
#: the model/ops layer changes.
_SLOW_CLASSES = {
    ("test_chunked_prefill.py", "TestChunkedInterference"),
    ("test_engine.py", "TestDecodePathParityFuzz"),
    ("test_engine.py", "TestMoEServing"),
    ("test_engine.py", "TestGemmaServing"),
    ("test_engine.py", "TestHostDramOffloadTier"),
    ("test_engine.py", "TestTensorParallelServing"),
    ("test_parallel.py", "TestMoEExpertParallel"),
    ("test_parallel.py", "TestShardedTraining"),
    ("test_parallel.py", "TestSharding"),
    ("test_parallel.py", "TestTrainForwardMatchesServing"),
    ("test_llama_model.py", "TestHFNumericsParity"),
    ("test_llama_model.py", "TestMixtralMoE"),
    ("test_llama_model.py", "TestPrefillDecodeConsistency"),
    ("test_gmm.py", "TestExpertParallelWithKernel"),
    ("test_gmm.py", "TestRoutedDispatchWithKernel"),
    ("test_ring_attention.py", "TestRingAttention"),
    ("test_ring_attention.py", "TestSpEngine"),
    ("test_checkpoint.py", "TestQuantizedCheckpoint"),
    ("test_checkpoint.py", "TestCheckpoint"),
}


#: per-test wall-clock cap (seconds) applied when pytest-timeout is
#: installed (CI installs it; local runs without it are unchanged). A
#: deadlocked drain/abort test then fails fast with a stack dump instead of
#: eating the whole tier-1 budget. Generous: the slowest legitimate tests
#: (fuzz matrices, multi-config sweeps) finish well under it.
_PER_TEST_TIMEOUT_S = 300


def pytest_unconfigure(config):
    from llm_d_kv_cache_manager_tpu.utils import locktrace

    if locktrace.enabled():
        locktrace.deactivate()


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _locktrace_gate():
    """Fail any test on lock-order cycles / unguarded mutations recorded
    while it ran (LOCKTRACE=1 only; zero-cost no-op otherwise). A test that
    intentionally seeds a violation consumes it and calls ``reset()``
    before returning, so it passes this gate clean."""
    yield
    from llm_d_kv_cache_manager_tpu.utils import locktrace

    if locktrace.enabled():
        try:
            locktrace.assert_clean()
        finally:
            locktrace.reset()


def pytest_collection_modifyitems(config, items):
    import pytest

    have_timeout = config.pluginmanager.hasplugin("timeout")
    for item in items:
        if have_timeout and item.get_closest_marker("timeout") is None:
            item.add_marker(pytest.mark.timeout(_PER_TEST_TIMEOUT_S))
        cls = getattr(item, "cls", None)
        if cls is None:
            continue
        key = (os.path.basename(str(item.fspath)), cls.__name__)
        if key in _SLOW_CLASSES:
            item.add_marker(pytest.mark.slow)
