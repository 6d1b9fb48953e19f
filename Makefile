# Dev entry points (parity with the reference's Makefile targets:
# build / unit-test / e2e-test). The benchmark is `python3 chipbench/run.py`
# (BENCHMARK.json); it needs the chip and has no target here.

PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: all native test fast-test unit-test e2e-test demo \
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
