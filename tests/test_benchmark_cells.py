"""The benchmark loads: every cell of ``BENCHMARK.json`` resolves through
``benchmarks/lib/cell.py`` to files and readers that import, and the
deployment no steady cell runs (``build-node`` under ``compile``) walks
every step of a run on XLA:CPU at a tiny size, reclaim included.

A rehearsal's timings are XLA:CPU's and mean nothing; what it shows is
that the run ends ``correct: true`` with nothing failed.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)


@pytest.fixture()
def bench_lib():
    """``benchmarks/`` importable as the harness imports itself
    (``from lib import ...``), for the length of one test."""
    sys.path.insert(0, BENCH)
    try:
        yield importlib.import_module("lib.cell")
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_resolves_to_files_and_readers(bench_lib, name):
    cell = bench_lib.load_cell(name)
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == name)
    assert cell.config["name"] == entry["config"]
    assert cell.chips in (1, 4)
    # The deployment states its sizes, its guarantees and documented
    # flags; the mix names a generator that imports.
    gen = importlib.import_module("lib.generate")
    pop = gen.Population.from_config(cell.config)
    assert pop.pids > 0 and pop.stacks >= pop.pids
    assert cell.config["guarantees"]["held_by"].endswith("every limit is 0")
    assert float(cell.config["replay"]["period_s"]) > 0
    mix = importlib.import_module("lib.mixes").Mix(cell.traffic)
    assert mix.distinct_windows(8) >= 1 and len(mix.replay_order(8)) == 8
    # Every metric the cell reports: its file, its reader, and for a
    # roofline share the function that counts its bytes.
    assert {"setup_s"} < {m.name for m in cell.end_to_end}
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        reader = importlib.import_module(f"lib.readers.{m.reader}")
        assert callable(reader.read)
        if m.reader == "trace_roofline":
            fn = getattr(importlib.import_module(
                f"lib.{m.args['bytes_module']}"), m.args["bytes_fn"])
            assert fn(cell.config) > 0


def test_every_metric_lists_cells_that_exist():
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    configs = {c["name"] for c in BENCHMARK["configs"]}
    assert {w["config"] for w in BENCHMARK["workloads"]} == configs
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        assert os.path.isfile(os.path.join(
            BENCH, "metrics", m["name"] + ".json")), m["name"]
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e, m["name"]


def test_build_node_under_compile_rehearses_correct_on_the_cpu():
    """The harness's own steps at a size where the 4,096-slot dictionary
    (2,048 ids) has to give ids back every window: warm-up ends (XLA is
    asked for nothing new two windows running), every limit of the
    comparison reads 0, nothing fails, and the traced line carries the
    miss path's counters."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"),
         "--config", "build-node", "--traffic", "compile",
         "--pids", "40", "--stacks", "400", "--samples", "2400",
         "--capacity", "4096", "--seconds", "8", "--trace", "1"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=540, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2 and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert "compared stack_mismatches = 0 (limit 0)" in out.stdout
    assert "in-window parca_agent_xla_compile_requests_total = 0" \
        in out.stdout
    metrics = line["metrics"]
    assert 300 <= metrics["misses_per_window"]["value"] <= 400
    assert metrics["dict_reclaimed_ids_per_window"]["value"] > 0
    assert metrics["feed_miss_ms.p50"]["value"] > 0
    assert "miss_scatter_roofline" not in metrics   # a device number


def test_node_streamed_under_rollout_rehearses_streamed_on_the_cpu():
    """The DaemonSet's flags through the harness at a tiny size: every
    window of the measured window is streamed (ten drains, fed while it
    is open), the comparison reads 0 on every number, nothing fails,
    nothing compiles inside the measured window, and the traced line
    carries the feed thread's metrics: every window's new stacks are
    dispatched by the drain that first holds them and settled from the
    feed thread, and the rest of its rows the carry cache folds."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"),
         "--config", "node-streamed", "--traffic", "rollout",
         "--pids", "40", "--stacks", "1024", "--samples", "8000",
         "--capacity", "16384", "--seconds", "5", "--trace", "1"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=540, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2 and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert "compared stack_mismatches = 0 (limit 0)" in out.stdout
    assert "in-window parca_agent_xla_compile_requests_total = 0" \
        in out.stdout
    metrics = line["metrics"]
    assert metrics["streamed_windows_per_window"]["value"] == 1.0
    assert metrics["stream_rows_fed_per_window"]["value"] > 0
    assert metrics["carry_matched_rows_per_window"]["value"] > 0
    assert metrics["misses_per_window"]["value"] > 0
    # Every per-layer metric the cell lists that is no device number
    # reads one here, and the feed thread's self time is no deficit.
    cell = next(w for w in BENCHMARK["workloads"]
                if w["name"] == "node-streamed-rollout")
    listed = {m["name"]: m for m in BENCHMARK["per_layer"]
              if cell["name"] in m.get("workloads", [])}
    assert len(listed) >= 40
    for name, m in listed.items():
        if m["source"] != "device_trace":
            assert name in metrics, name
    assert metrics["stream_feed_self_ms.p50"]["value"] >= 0
    assert metrics["stream_feed_ms.p50"]["value"] \
        >= metrics["drain_fold_ms.p50"]["value"] > 0
    assert "feed_probe_roofline" not in metrics     # a device number


def test_the_8_bit_control_is_not_correct_for_node_streamed_under_rollout():
    """The plain reference in the program's place, its counts carried
    in 8 bits: the comparison that holds the streamed cell has to say
    so (exit 0: the sound shipment read 0 everywhere, the control did
    not)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"),
         "--config", "node-streamed", "--traffic", "rollout",
         "--seeds", "3700000007"],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["cell"] == "node-streamed-rollout" and line["bits"] == 8
    assert line["sound"]["correct"] is True
    assert line["control"]["correct"] is False
    assert line["control"]["mass_gap"] > 0
