# Developer entry points (role of the reference's root Makefile:103-214:
# build, split test targets, bench). The Python package itself needs no
# build step; `native` compiles the perf sampler shared object.

PYTHON ?= python

.PHONY: all native lint test test-live chaos fuzz bench bench-statics bench-close bench-hotspot bench-sinks bench-scale bench-feed bench-regress bench-zoo soak soak-smoke trace-smoke hotspot-smoke regress-smoke fixtures golden clean install

all: native

native:
	$(MAKE) -C parca_agent_tpu/native

# palint (docs/static-analysis.md): the AST-based invariant checker for
# the agent's concurrency / fail-open / crash-only contracts — lock
# discipline, fail-open hooks, crash-only IO, chaos-site coverage,
# no-host-sync-on-capture, bounded-call. Runs in a few seconds; exits
# non-zero on any finding not in tools/lint/baseline.json. `--json` for
# machine-readable output.
lint:
	$(PYTHON) -m parca_agent_tpu.tools.lint

# Everything that runs without perf_event permission (the reference's
# `make test` analog, Makefile:207-214). The split is by the registered
# `live` pytest marker, not by name matching.
test:
	$(PYTHON) -m pytest tests/ -q -m "not live"

# Kernel/permission-dependent capture tests (the reference runs these as
# root, Makefile:204-205).
test-live:
	$(PYTHON) -m pytest tests/ -q -m live

# Fault-injection suite under a fixed seed (docs/robustness.md): store
# outages, disk-full spill, actor crashes, device/fleet hangs —
# deterministic by design, so it also rides every unmarked run. palint
# preflights it: the chaos-site checker is what keeps this suite's
# coverage honest (every SITES entry exercised here, and vice versa),
# so drift fails fast before any test runs.
chaos: lint bench-zoo soak-smoke
	PARCA_FAULT_SEED=42 $(PYTHON) -m pytest tests/test_chaos.py tests/test_ingest_poison.py tests/test_device_health.py tests/test_statics_store.py tests/test_trace.py tests/test_close_overlap.py tests/test_hotspots_chaos.py tests/test_sinks.py tests/test_admission.py tests/test_regression.py tests/test_feed_coalesce.py tests/test_device_telemetry.py tests/test_identity.py tests/test_zoo.py tests/test_soak.py -q -m chaos

# The workload-zoo matrix (docs/robustness.md "workload zoo"): >= 6
# seeded hostile-world scenario rows — pid reuse under tenant
# migration, perf-map churn, fork storms, deep stacks, kernel-heavy
# mixes, tenant bursts — each driven through the REAL profiler window
# loop and scored against per-scenario bars, plus the pid-reuse control
# arm with the generation stamp pinned off (must REPRODUCE the
# misattribution). Host-bound, reduced scale, one JSON line.
bench-zoo:
	JAX_PLATFORMS=cpu PARCA_BENCH_ZOO_CHILD=1 $(PYTHON) bench.py

# Wall-clock endurance soak (docs/robustness.md "endurance matrix"):
# ONE persistent agent (carry aggregator + streaming feeder + the full
# registry stack) drives an endless interleave of zoo scenario
# schedules at 1 s registry cadence, sampling RSS + per-subsystem byte
# lanes every window. Fails on a post-warm-up RSS slope above bound,
# any unbounded cache/counter lane, a lost window, or non-conserved
# sample mass. Seeded and wall-bounded: SOAK_WALL / SOAK_SEED / SOAK_OUT
# override, and both are stamped into the JSON artifact. Honors
# PARCA_FAULTS (the soak.tick site is fail-open by contract).
# NOTE: `python -c` instead of `-m` — the module is imported by the
# bench_zoo package, and runpy would load it twice.
SOAK_WALL ?= 1800
SOAK_SEED ?= 1234
SOAK_OUT ?= soak.json
soak:
	JAX_PLATFORMS=cpu $(PYTHON) -c "import sys; \
		from parca_agent_tpu.bench_zoo.soak import main; \
		sys.exit(main())" --wall $(SOAK_WALL) --seed $(SOAK_SEED) \
		--out $(SOAK_OUT)

# The <=90 s soak gate that rides `make chaos`: same harness, same
# bars, 45 s wall — long enough to clear the warm-up and measure real
# slopes, short enough for a preflight.
soak-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -c "import sys; \
		from parca_agent_tpu.bench_zoo.soak import main; \
		sys.exit(main())" --wall 45 --seed $(SOAK_SEED)

# Parser mutation-fuzz gate (docs/robustness.md "ingest containment"):
# >=500 seeded mutations per ingest parser, nothing may escape the
# PoisonInput taxonomy. Same harness the bench ingest_poison phase runs.
fuzz:
	PARCA_FAULT_SEED=42 PARCA_FUZZ_N=500 $(PYTHON) -m pytest \
		tests/test_ingest_poison.py -q -m chaos -k fuzz

# The driver-scored benchmark: ONE JSON line on stdout.
bench:
	$(PYTHON) bench.py

# The statics-wall drill alone (docs/perf.md): cold vs snapshot-warm
# statics build + first encode, byte-identity + corrupt-snapshot
# degradation bars. Host-bound, so it pins the cpu backend.
bench-statics:
	JAX_PLATFORMS=cpu PARCA_BENCH_STATICS_CHILD=1 $(PYTHON) bench.py

# The sub-RTT close drill alone (docs/perf.md "sub-RTT close"):
# double-buffer overlap and delta-fetch byte accounting, gated on pprof
# byte identity. Host-bound, so it pins the cpu backend.
bench-close:
	JAX_PLATFORMS=cpu PARCA_BENCH_CLOSE_CHILD=1 $(PYTHON) bench.py

# Window flight-recorder smoke (docs/observability.md): a short traced
# session must expose >=3 complete traces with every mandatory span on
# /debug/windows, serve per-stage Prometheus histograms on /metrics,
# and turn one injected slow window into exactly one incident file with
# zero windows lost. Host-bound, so it pins the cpu backend.
trace-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m parca_agent_tpu.tools.trace_smoke

# Hotspot rollup acceptance drill (docs/hotspots.md): a multi-hour
# simulated window stream folded into the rollup hierarchy; top-K vs
# the exact aggregate >= 99%, query p50/p99 at dashboard rates, and the
# per-level byte caps held with oldest-eviction engaged. Numpy-only.
bench-hotspot:
	JAX_PLATFORMS=cpu PARCA_BENCH_HOTSPOT_CHILD=1 $(PYTHON) bench.py

# Output-backend sink drill (docs/sinks.md): the sha256 pprof-identity
# bar through the SinkRegistry vs the legacy direct ship, per-sink emit
# latency, autofdo flush bytes, and the injected-sink-fault zero-loss
# acceptance check. Host-bound, so it pins the cpu backend.
bench-sinks:
	JAX_PLATFORMS=cpu PARCA_BENCH_SINK_CHILD=1 $(PYTHON) bench.py

# Multi-tenant pid-axis sweep (docs/robustness.md "multi-tenant
# admission"): 50k -> 200k -> 500k pids through one dict aggregator
# with 32 tenants and ONE tenant 10x over quota at the top tier —
# close latency + registry RSS per tier, zero windows lost, zero
# in-quota tenants degraded, mid-tier close within 2x of the low tier.
# Host-bound, so it pins the cpu backend. PARCA_BENCH_SCALE_TIERS
# overrides the tier list for quick runs.
bench-scale:
	JAX_PLATFORMS=cpu PARCA_BENCH_SCALE_CHILD=1 $(PYTHON) bench.py

# Ingest-wall A/B (docs/perf.md "ingest wall" + "feed endgame"): the
# scale sweep's pid tiers fed through raw / coalesced / coalesced+
# native-hash / carry+fold arms over a dup>=2 stationary stream —
# per-window feed seconds reduced >= 3x at the top tier, coalesced+
# native saturation < 50% of the window, carry+fold saturation < 1%
# (steady-state windows dispatch ~nothing: the cross-drain carry cache
# absorbs repeat stacks host-side and flushes once at close), zero
# windows lost, counts + pprof identity held across every arm, and the
# drain-cache hit rate + carry counters land in the artifact.
# Host-bound, so it pins the cpu backend. PARCA_BENCH_FEED_TIERS
# overrides for quick runs.
bench-feed:
	JAX_PLATFORMS=cpu PARCA_BENCH_FEED_CHILD=1 $(PYTHON) bench.py

# Regression sentinel acceptance drill (docs/regression.md): a
# synthetic window stream through the REAL encode pipeline with a 2x
# hotspot shift injected on one build-id mid-run — detected within <= 2
# rollup intervals, zero false-positive verdicts across the clean
# control windows, windows_lost == 0 under regression.fold/baseline
# chaos, pprof sha256 byte-identity unchanged with the sentinel
# enabled. Host-bound, so it pins the cpu backend.
bench-regress:
	JAX_PLATFORMS=cpu PARCA_BENCH_REGRESS_CHILD=1 $(PYTHON) bench.py

# Hotspot end-to-end smoke (docs/hotspots.md): a short real profiler
# session (dict aggregator, encode pipeline) must serve human-readable
# top-K answers on /hotspots, reject bad parameters, expose the rollup
# gauges on /metrics, and report the hotspots /healthz section without
# turning readiness red. Host-bound, so it pins the cpu backend.
hotspot-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m parca_agent_tpu.tools.hotspot_smoke

# Regression sentinel end-to-end smoke (docs/regression.md): a short
# real profiler session (hotspots + sentinel + alerts sink + HTTP) must
# hold a clean control at zero verdicts, turn an injected 10x one-stack
# shift into exactly one `regressed` verdict on /diff and one JSONL
# alert record, serve bounded range diffs, reject bad parameters with
# 400s, and report the regression /metrics//healthz surfaces without
# turning readiness red. Host-bound, so it pins the cpu backend.
regress-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m parca_agent_tpu.tools.regress_smoke

# Rebuild the checked-in ELF/DWARF test fixtures and their golden
# unwind tables (the reference's write-dwarf-unwind-tables pattern,
# Makefile:133-137).
fixtures:
	$(MAKE) -C tests/fixtures

golden:
	$(MAKE) -C tests/fixtures golden

install:
	$(PYTHON) -m pip install .

clean:
	$(MAKE) -C parca_agent_tpu/native clean 2>/dev/null || true
	rm -rf build dist *.egg-info
