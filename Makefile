# Developer entry points (role of the reference's root Makefile:103-214:
# build, split test targets, bench). The Python package itself needs no
# build step; `native` compiles the perf sampler shared object.

PYTHON ?= python

.PHONY: all native lint test test-live chaos fuzz bench-zoo soak soak-smoke trace-smoke hotspot-smoke regress-smoke fixtures golden clean install

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
chaos: lint soak-smoke
	PARCA_FAULT_SEED=42 $(PYTHON) -m pytest tests/test_chaos.py tests/test_ingest_poison.py tests/test_device_health.py tests/test_statics_store.py tests/test_trace.py tests/test_close_overlap.py tests/test_hotspots_chaos.py tests/test_sinks.py tests/test_admission.py tests/test_regression.py tests/test_feed_coalesce.py tests/test_device_telemetry.py tests/test_identity.py tests/test_zoo.py tests/test_soak.py -q -m chaos

# The workload zoo at full scale (docs/robustness.md "workload zoo" and
# "endurance matrix"): the six-scenario sweep, the pid-reuse control
# arm with the generation stamp off, and the 60-row endurance matrix,
# each through the real window loop. Prints run_zoo / run_scenario /
# run_matrix's own results as one JSON line; exits 1 when a bar fails.
# tests/test_zoo.py runs the same code at test scale on every tier-1 run.
bench-zoo:
	JAX_PLATFORMS=cpu $(PYTHON) -m parca_agent_tpu.bench_zoo

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
# PoisonInput taxonomy.
fuzz:
	PARCA_FAULT_SEED=42 PARCA_FUZZ_N=500 $(PYTHON) -m pytest \
		tests/test_ingest_poison.py -q -m chaos -k fuzz

# Window flight-recorder smoke (docs/observability.md): a short traced
# session must expose >=3 complete traces with every mandatory span on
# /debug/windows, serve per-stage Prometheus histograms on /metrics,
# and turn one injected slow window into exactly one incident file with
# zero windows lost. Host-bound, so it pins the cpu backend.
trace-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m parca_agent_tpu.tools.trace_smoke

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
