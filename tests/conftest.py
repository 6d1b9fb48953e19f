"""Test bootstrap.

Tests run on the CPU with a virtual 8-device mesh, so multi-chip sharding
and per-device placement are exercised without TPU hardware; engines say
``interpret=True`` (Pallas interpreter, XLA prefill). The machine with the
chip is reached through ``chip_smoke.py``, never through pytest.

The platform is pinned both ways — the environment for child processes,
``jax.config.update`` for this one — before the first backend
initialization, so a test run can never take (or wait for) a chip.
"""

import contextlib
import faulthandler
import os
import signal
import sys
import threading
import time

# Must precede the first jax backend initialization (not merely jax import).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llm_d_kv_cache_manager_tpu.tokenization import Tokenizer  # noqa: E402


class CharTokenizer(Tokenizer):
    """Shared offline test tokenizer: token id = ord(char), byte offsets."""

    def encode(self, prompt, model_name):
        return [ord(c) for c in prompt], [(i, i + 1) for i in range(len(prompt))]


#: ``free_tcp_port`` hands each xdist worker a range of its own, below the
#: kernel's ephemeral range (32768 up: what a client socket of another test
#: may be given), and never the same port twice in a process.
_PORT_BASE, _PORTS_PER_WORKER, _PORT_RANGES = 20000, 1000, 12
_ports_given: set[int] = set()


def free_tcp_port() -> int:
    """A TCP port nobody holds — fixed test ports collide when suites run
    concurrently (two pytest processes, or pytest alongside a dev server),
    and a port the kernel picked is free only until the next caller asks: two
    workers, or one worker twice, were handed the same one before the first
    had bound it. Each worker draws from its own range, starting where its
    process id says (two runs side by side start apart), skips what it has
    given out before, and checks the rest free by a bind."""
    import socket

    worker = os.environ.get("PYTEST_XDIST_WORKER", "gw0")  # "gw3"
    base = _PORT_BASE + int(worker[2:]) % _PORT_RANGES * _PORTS_PER_WORKER
    for step in range(_PORTS_PER_WORKER):
        port = base + (os.getpid() + step) % _PORTS_PER_WORKER
        if port in _ports_given:
            continue
        _ports_given.add(port)
        with socket.socket() as s:
            try:
                s.bind(("", port))
            except OSError:
                continue  # somebody else's
        return port
    raise RuntimeError(f"no free port left in {base}..{base + _PORTS_PER_WORKER}")


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


@pytest.hookimpl(tryfirst=True, optionalhook=True)  # (no hook under no:xdist)
def pytest_xdist_make_scheduler(config, log):
    """A FILE is the unit a worker is dealt, whatever ``--dist`` says. The
    suite is laid out for that: a file's cases share module fixtures (a
    parameter tree, a built engine) and the programs the first of them
    compiled in its process, and ``longest_first`` sorts whole files. Under
    ``--dist load`` (the driver's command since PR 47) xdist deals the
    collection out in runs of consecutive tests, a 24th of it to each worker
    at the start: the first worker got the five longest files in one piece
    (1 700 cpu-seconds of the run's 5 900) and the run was cut at its 1 470 s
    with five workers idle. ``each`` is left alone: it is asked for on
    purpose."""
    if config.getvalue("dist") in ("load", "worksteal"):
        from xdist.scheduler import LoadFileScheduling

        return LoadFileScheduling(config, log)
    return None  # xdist's own choice


def pytest_configure(config):
    if not hasattr(config, "workerinput"):  # the controller, or no xdist
        _build_native_once()
    # ``--dist loadfile`` deals files out most tests first unless told not
    # to, and a file of few tests is a long one here (a cell's rehearsals, an
    # engine's cases): they came last and one worker ran the run's tail
    # alone. Collection order it is, and ``longest_first`` sets that.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
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
        "slow: run by nobody (the driver's tier-1 command is `-m 'not slow'` "
        "and there is no other job); the classes and what covers each are "
        "`_SLOW_CLASSES` in tests/conftest.py",
    )


#: The classes NO RUN EXECUTES: the driver's tier-1 command is ``-m 'not
#: slow'`` and this repository has no other job, so ``slow`` means "run by
#: nobody". This table is the whole list. A line a class: tests, cpu-seconds
#: of the class alone on the CPU (junit sums of PR 46's census: every class
#: run once, all passed), and the tier-1 test that covers the same path, or
#: "none". A class leaves the table when it passes three times in a row under
#: ``-n 6``, draws nothing at random without a fixed seed and costs under 10 s
#: a test; the tier-1 clock is what keeps the others here. ``ROADMAP.md`` D15
#: names the code that only these classes reach.
_SLOW_CLASSES = {
    # 5 tests, 32 s. Covered: test_chunked_prefill.py::TestChunkedPrefillParity
    ("test_chunked_prefill.py", "TestChunkedInterference"),
    # 5 tests, 79 s (seeded). Covered for fixed cases:
    # test_run_ahead_parity.py::test_greedy_parity_with_the_engine_that_waits
    ("test_engine.py", "TestDecodePathParityFuzz"),
    # 8 tests, 49 s. Covered for the expert-parallel dispatch: test_quant.py::
    # TestQuantizedSharding::test_quantized_moe_with_expert_parallel_dispatch;
    # for its two train steps: none
    ("test_parallel.py", "TestMoEExpertParallel"),
    # 2 tests, 18 s. Covered: none (parallel/train.py)
    ("test_parallel.py", "TestShardedTraining"),
    # 1 test, 17 s. Covered: none (parallel/train.py)
    ("test_parallel.py", "TestTrainForwardMatchesServing"),
    # 7 tests, 42 s (needs ``transformers``). Covered: none for the dense,
    # Gemma and Qwen3 loaders against ``transformers``; the four newer
    # architectures' loaders are held to the float32 references
    # (test_mla.py::test_a_saved_state_dict_loads_to_the_references_logits
    # and its like)
    ("test_llama_model.py", "TestHFNumericsParity"),
    # 3 tests, 39 s. Covered for the routed layer:
    # test_llama_model.py::TestRoutedDispatch; against ``transformers``: none
    ("test_llama_model.py", "TestMixtralMoE"),
    # 1 test, 22 s. Covered: none for the kernel under expert parallelism
    # (the XLA dispatch: test_quant.py::TestQuantizedSharding)
    ("test_gmm.py", "TestExpertParallelWithKernel"),
    # 3 tests, 19 s. Covered a layer at a time:
    # test_moe_padding.py::test_real_rows_read_what_they_read_alone[*-kernel-*]
    ("test_gmm.py", "TestRoutedDispatchWithKernel"),
    # 8 tests, 20 s. Covered through the prefill that calls it:
    # test_ring_attention.py::TestSpPrefill
    ("test_ring_attention.py", "TestRingAttention"),
    # 1 test, 12 s. Covered: none (``EngineConfig.sp`` end to end)
    ("test_ring_attention.py", "TestSpEngine"),
    # 3 tests, 16 s. Covered: none (parallel/checkpoint.py)
    ("test_checkpoint.py", "TestQuantizedCheckpoint"),
    # 4 tests, 24 s. Covered: none (parallel/checkpoint.py)
    ("test_checkpoint.py", "TestCheckpoint"),
    # 15 tests, 291 s (a whole served program a case, 10-41 s each). Covered
    # at the fewest layers that keep every kind of pool:
    # test_pool_layout.py::TestThePrefillLoopReadsThePools
    ("test_pool_layout.py", "TestServedPrograms"),
}


#: What each file cost the last whole tier-1 run this tree records, longest
#: first: the output of ``python tests/junit_costs.py <junit file>``, which
#: writes it (``docs/development.md``, "The tests").
_RECORDED_COSTS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "junit_costs.txt")


def recorded_order(path: str = _RECORDED_COSTS) -> list[str] | None:
    """The files of a recorded run (relative to ``tests/``), longest first;
    None where there is no such record or it cannot be read."""
    try:
        with open(path) as f:
            rows = [line.split() for line in f]
    except (OSError, UnicodeDecodeError):
        return None
    return [row[2] for row in rows if len(row) == 3 and row[2].endswith(".py")]


def longest_first(items: list, recorded: list[str] | None, file_of=str) -> list:
    """``items`` (files, or tests with ``file_of`` naming each one's file) in
    the order they are to be collected: what is of a file the record does not
    name FIRST (a new file's cost is not known, and a long one may not hide at
    the run's tail, where one worker would run it alone), then the recorded
    files longest first, so that a worker's last file is a short one. Stable: a
    file stays together and as it was. No record: as they were."""
    if recorded is None:
        return list(items)
    rank = {name: i for i, name in enumerate(recorded)}
    return sorted(items, key=lambda item: rank.get(file_of(item), -1))


#: per-test wall-clock cap (seconds): a deadlocked drain/abort test fails
#: with a dump of every thread's stack and the run goes on, where it would
#: take the rest of the tier-1 clock with it. ``pytest-timeout`` applies it
#: where it is installed (it is not here), ``_per_test_cap`` below where not.
#: What holds it up is the benchmark's directory: ``tests/chipbench_tests/
#: test_swa_cell.py::test_the_probes_controls_each_read_not_correct`` took
#: 488 s of PR 50's whole run of its parent (302 s at PR 45) and no test that
#: passes may fail by the cap. Outside that directory the slowest tests after
#: PR 50 are the two halves of ``chip_smoke.py --dry-run``
#: (``tests/test_chip_smoke_dry_run_kernels.py`` 47-83 s and
#: ``tests/test_chip_smoke_dry_run.py`` 59-99 s of a whole run; 165 s as the
#: one test they were), then ``tests/test_swa.py::test_the_windows_edge``,
#: 53-62 s: once a ``benchmark`` PR has split the cells' files (ROADMAP D9
#: (m)) the cap can come down to twice the slowest test that is left.
_PER_TEST_TIMEOUT_S = 600


def pytest_unconfigure(config):
    from llm_d_kv_cache_manager_tpu.utils import locktrace

    if locktrace.enabled():
        locktrace.deactivate()


@contextlib.contextmanager
def capped(seconds: float):
    """The body under a timer: when it rings, every thread's stack goes to
    stderr and ``pytest.fail`` is raised where the main thread stands (a
    lock's ``acquire``, a ``join`` and a ``sleep`` are woken by the signal),
    with the cap in its message. A timer that was running is set again on
    the way out."""

    def ring(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(
            f"over the per-test cap of {seconds:g} s (tests/conftest.py); "
            "the stacks of all threads are on stderr", pytrace=False)

    handler = signal.signal(signal.SIGALRM, ring)
    outer = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *outer)
        signal.signal(signal.SIGALRM, handler)


@pytest.fixture(autouse=True)
def _per_test_cap(request):
    """``_PER_TEST_TIMEOUT_S`` without the plugin. A signal's handler runs on
    the main thread, which is where pytest, and an xdist worker, run a test."""
    if (request.config.pluginmanager.hasplugin("timeout")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return
    with capped(_PER_TEST_TIMEOUT_S):
        yield


def _mappings_allowed() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530  # Linux's default


def _mappings() -> int:
    """The memory mappings this process holds (0 where ``/proc`` has none)."""
    try:
        with open("/proc/self/maps", "rb") as f:
            return f.read().count(b"\n")
    except OSError:
        return 0


#: A program XLA compiled for the CPU is three mappings of the process (its
#: code, its constants, its data: a page or two each), jax keeps every
#: program a worker compiled until the worker exits, and a process may hold
#: ``vm.max_map_count`` mappings, 65 530 unless a machine says otherwise.
#: One file of served programs adds 7-13 thousand (``test_experts_touched.py``
#: alone: 7 123 at PR 55's tree, 12 862 with every decode program walking its
#: pages under the interpreter); a worker of a whole run passes the limit,
#: ``mmap`` then fails inside LLVM, and the worker dies of a segmentation
#: fault in ``backend_compile_and_load``, under whatever test compiles next:
#: ROADMAP D13's deaths, three of eleven whole runs before PR 56 and five of
#: five on its tree. From a third of the limit on, the programs go
#: (``jax.clear_caches`` took 12 862 mappings to 711); one that is called
#: again compiles again. A third, because a single test of the cells'
#: rehearsals compiles for minutes and has to fit in what is left.
_MAPPINGS_KEPT = _mappings_allowed() // 3
_MAPPINGS_CHECK_EVERY_S = 5.0  # the count reads /proc/self/maps: 5-10 ms
_mappings_checked_at = 0.0


@pytest.fixture(autouse=True)
def _bounded_mappings():
    yield
    global _mappings_checked_at
    if time.monotonic() - _mappings_checked_at < _MAPPINGS_CHECK_EVERY_S:
        return
    _mappings_checked_at = time.monotonic()
    if _mappings() > _MAPPINGS_KEPT:
        jax.clear_caches()


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
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    items[:] = longest_first(
        items, recorded_order(),
        lambda item: os.path.relpath(str(item.fspath), tests_dir))
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
