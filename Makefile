# Top-level convenience targets (parity: reference ./configure && make).
.PHONY: all native test test-quick test-native asan bench smoke chip-smoke \
	telemetry-check chaos stream lint sanitize recovery crash qos \
	paged timeline perfgate fleet fleet-chaos mesh help

all: native

native:
	$(MAKE) -C quiver_tpu/cpp

test:
	python -m pytest tests/ -q

test-native:
	$(MAKE) -C quiver_tpu/cpp test

asan:
	$(MAKE) -C quiver_tpu/cpp asan

# needs a TPU (exits 2 without one)
bench:
	python bench.py

# CPU rehearsal of bench.py's control flow: says nothing about the chip
smoke:
	JAX_PLATFORMS=cpu python bench.py --small --iters 5

# the quickest proof that the main path runs on the chip (needs a TPU;
# from a sandbox: chiprun -- python chip_smoke.py)
chip-smoke:
	python chip_smoke.py

test-quick:
	python -m pytest tests/ -m "not slow" -q

# telemetry suite + the no-HTTP-exporter-in-hot-paths guard
telemetry-check:
	python -m pytest tests/ -m telemetry -q

# deterministic fault-injection suite (docs/RESILIENCE.md)
chaos:
	python -m pytest tests/ -m chaos -q

# delta-CSR overlay / temporal sampling / ingestion suite (docs/STREAMING.md)
stream:
	python -m pytest tests/ -m stream -q

# quiverlint: hot-path + whole-program concurrency + staging-dataflow
# static analysis (docs/STATIC_ANALYSIS.md); --strict-baseline also
# fails on stale baseline entries, rule-hash mismatches, and stale
# sync-ok waivers so the debt ledger can only shrink.  benchmarks/ is
# report-only against its own committed baseline: harness code gets
# linted and diffed, but doesn't gate.
lint:
	python -m quiver_tpu.analysis --strict-baseline quiver_tpu bench.py
	python -m quiver_tpu.analysis --report-only \
		--baseline quiverlint.bench.baseline.json benchmarks

# quick suite + chaos + mesh harnesses under both runtime witnesses
# (QUIVER_SANITIZE=1 wraps threading.Lock/RLock AND the device->host
# coercion points; docs/STATIC_ANALYSIS.md)
sanitize:
	QUIVER_SANITIZE=1 python -m pytest tests/ -m "not slow" -q
	QUIVER_SANITIZE=1 python -m pytest tests/ -m chaos -q
	QUIVER_SANITIZE=1 python -m pytest tests/ -m mesh -q

# WAL / checkpoint / program-registry durability suite (docs/RECOVERY.md)
recovery:
	python -m pytest tests/ -m recovery -q

# kill -9 crash harness: real child processes SIGKILLed mid-ingest under
# a seeded chaos plan, then recovered — zero acked loss, monotone
# version, bit-identical sampling (docs/RECOVERY.md)
crash:
	python -m pytest tests/ -m crash -q

# multi-tenant QoS suite + the closed-loop burst harness in smoke mode
# (docs/RESILIENCE.md "QoS & degradation ladder")
qos:
	python -m pytest tests/ -m qos -q
	python benchmarks/qos_load.py --smoke

# paged feature store + ragged page-gather kernel suite: bit-identical
# equivalence vs the staged merge, retrace budget, page-residency
# recovery (docs/FEATURE_CACHE.md)
paged:
	python -m pytest tests/ -m paged -q

# unified timeline / perfgate suite
# (docs/OBSERVABILITY.md "Timeline")
timeline:
	python -m pytest tests/ -m timeline -q

# noise-aware perf-regression gate vs the committed baseline in
# .bench_state.json (docs/BENCHMARKS.md "Perfgate"); exit 1 = regression
perfgate:
	python benchmarks/perfgate.py

# elastic replicated serving fleet suite: router, membership, WAL
# shipping edge cases, drain/rejoin (docs/FLEET.md)
fleet:
	python -m pytest tests/ -m fleet -q

# replica-failover chaos harness: 3 real replica processes, kill -9 one
# mid-burst, prove zero lost answers + warm rejoin (docs/FLEET.md)
fleet-chaos:
	python -m pytest tests/ -m fleet -q
	python benchmarks/fleet_chaos.py --smoke --scenario all

# mesh-native sharded serving suite: 8-virtual-device CPU rehearsal,
# sharded gather/sampling bit-identity, shard-group failover, coherent
# group WAL (docs/SHARDING.md)
mesh:
	python -m pytest tests/ -m mesh -q

help:
	@echo "targets: native | test | test-quick | test-native | asan | bench | smoke | chip-smoke | telemetry-check | chaos | stream | lint | sanitize | recovery | crash | qos | paged | timeline | perfgate | fleet | fleet-chaos | mesh | help"
