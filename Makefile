# Dev entry points (parity with the reference's Makefile targets:
# build / unit-test / e2e-test / bench).

PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: all native test fast-test unit-test e2e-test demo bench bench-smoke bench-8b bench-pressure bench-tier bench-lag10 \
        routing-bench engine-bench engine-bench-8b moe-bench \
        chip-smoke chip-smoke-dry dryrun docker lint

all: native test

## Build the C++ kernels (hash chain + block index).
native:
	$(PY) -m llm_d_kv_cache_manager_tpu.native.build

## Full test suite (CPU, virtual 8-device mesh via tests/conftest.py).
test:
	$(PY) -m pytest tests/ -q

## Fast pre-commit loop (<5 min): heavy fuzz matrices / sweeps / numerics
## oracles are auto-marked `slow` (tests/conftest.py table).
fast-test:
	$(PY) -m pytest tests/ -q -m "not slow"

unit-test:
	$(PY) -m pytest tests/ -q -k "not e2e and not pod_server"

e2e-test:
	$(PY) -m pytest tests/test_e2e_redis.py tests/test_kvevents.py tests/test_pod_server.py -q

## End-to-end demos (no cluster needed).
demo:
	$(CPU_ENV) $(PY) examples/offline_events_demo.py
	$(CPU_ENV) $(PY) examples/kv_cache_index_demo.py
	$(CPU_ENV) $(PY) examples/kv_cache_aware_scorer.py
	$(CPU_ENV) $(PY) examples/fleet_demo.py

## Headline routing benchmark (needs the TPU; exits non-zero without one —
## the smoke variant is the explicit CPU run).
bench:
	$(PY) bench.py

bench-smoke:
	BENCH_SMOKE=1 $(PY) bench.py

## 8B-at-north-star-scale variant (real Llama-3-8B, int8, 2-pod fleet).
bench-8b:
	BENCH_MODEL=8b-int8 BENCH_POLICIES=round_robin,precise $(PY) bench.py

## Pool-pressure regime: precise (blended) vs the capacity-LRU comparator
## at a thrash-sized pool — where eviction-awareness and affinity matter.
## (The default `bench` now also runs this regime as its second pass.)
bench-pressure:
	BENCH_TOTAL_PAGES=1536 BENCH_POLICIES=precise,estimated $(PY) bench.py

## Host-DRAM tier A/B at the round-3 thrash config.
bench-tier:
	BENCH_TOTAL_PAGES=192 BENCH_GROUPS=8 BENCH_PREFIX_LEN=2048 \
	BENCH_HOST_PAGES=1024 BENCH_POLICIES=precise BENCH_PRESSURE=0 $(PY) bench.py

## Event-plane lag sweep endpoint (default lag is 2 ms; 0 = optimistic).
bench-lag10:
	BENCH_EVENT_LAG_MS=10 $(PY) bench.py

routing-bench:
	$(PY) benchmarking/bench_routing.py

engine-bench:
	$(PY) benchmarking/bench_engine.py

engine-bench-8b:
	BENCH_MODEL=8b-int8 $(PY) benchmarking/bench_engine.py

moe-bench:
	$(PY) benchmarking/bench_moe.py

## The one command that proves the main path runs on the chip: compiled
## kernels vs their references at served shapes, then a ScoringService and
## PodServer(s) answering real HTTP traffic (one replica per visible chip).
## Exits non-zero where JAX finds no TPU.
chip-smoke:
	$(PY) chip_smoke.py

## Same code on the CPU (tiny preset, interpreter, virtual devices) — for
## debugging the command itself; never a device result.
chip-smoke-dry:
	$(PY) chip_smoke.py --dry-run

## Multi-chip dry-run on a virtual 8-device CPU mesh.
dryrun:
	$(CPU_ENV) $(PY) __graft_entry__.py 8

docker:
	docker build -t kv-cache-manager-tpu:latest .
	docker build --build-arg JAX_SPEC='jax[tpu]' -t kv-cache-manager-tpu:tpu .

lint:
	$(PY) -m compileall -q llm_d_kv_cache_manager_tpu tests examples
