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
import signal
import subprocess
import sys
import time

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


def _rehearse(config: str, traffic: str, pids: int, stacks: int,
              samples: int, capacity: int, seconds: int):
    """One traced rehearsal on XLA:CPU in a process of its own, held to
    what every rehearsal has to show: it ends ``correct: true`` with
    nothing failed, every number of the comparison 0 and no compile
    request inside the measured window. Returns its line."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"),
         "--config", config, "--traffic", traffic,
         "--pids", str(pids), "--stacks", str(stacks),
         "--samples", str(samples), "--capacity", str(capacity),
         "--seconds", str(seconds), "--trace", "1"],
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
    return line


def test_build_node_under_compile_rehearses_correct_on_the_cpu():
    """The harness's own steps at a size where the 4,096-slot dictionary
    (2,048 ids) has to give ids back every window: warm-up ends (XLA is
    asked for nothing new two windows running), every limit of the
    comparison reads 0, nothing fails, and the traced line carries the
    miss path's counters."""
    line = _rehearse("build-node", "compile", pids=40, stacks=400,
                     samples=2400, capacity=4096, seconds=8)
    metrics = line["metrics"]
    assert 300 <= metrics["misses_per_window"]["value"] <= 400
    assert metrics["dict_reclaimed_ids_per_window"]["value"] > 0
    assert metrics["feed_miss_ms.p50"]["value"] > 0
    assert "miss_scatter_roofline" not in metrics   # a device number


def test_firehose_churn_under_redeploy_rehearses_a_reclaim_on_the_cpu():
    """The tier that fills its dictionary, at a size whose id space
    (4,096 ids for 2,400 stacks and ~200 new a window) fills once inside
    the rehearsal, at the first feed of the ninth window: the run holds
    its reclaim inside the measured window, the window behind it goes
    through the fast encoder like any other (nothing failed), the last
    measured window, which lies after the reclaim, reads 0 on every
    number of the comparison, and the traced line carries the reclaim's
    spans and what the encoder kept across the epoch: the static
    sections of every pid that stayed, so that the ship builds a gzip
    piece for the ten new pids of a window and for no other."""
    line = _rehearse("firehose-churn", "redeploy", pids=120, stacks=2400,
                     samples=12000, capacity=8192, seconds=26)
    metrics = line["metrics"]
    windows = line["attempted"]
    assert metrics["dict_reclaims_per_window"]["value"] * windows \
        == pytest.approx(1.0)
    assert metrics["dict_reclaimed_ids_per_window"]["value"] > 0
    assert metrics["dict_reclaim_ms.p50"]["value"] \
        >= metrics["reclaim_compact_ms.p50"]["value"] > 0
    assert metrics["epoch_remap_ms.p50"]["value"] > 0
    # Every pid the encoder knew but the ten the reclaim's window lost.
    assert 100 <= metrics["encoder_epoch_statics_kept_per_window"]["value"] \
        * windows <= 120
    assert metrics["ship_static_built_per_window"]["value"] \
        == pytest.approx(10.0)
    assert metrics["ship_static_reused_per_window"]["value"] \
        == pytest.approx(110.0)
    assert metrics["encode_order_rebuilds_per_window"]["value"] == 0.0
    # The statics build of the window's new pids is a span under encode;
    # the mix gives new stacks to new pids alone, so no known pid is
    # asked for an address and no look-up is built.
    assert 0 < metrics["encode_statics_ms.p50"]["value"] \
        <= metrics["encode_ms.p99"]["value"]
    assert metrics["registry_index_builds_per_window"]["value"] == 0.0
    for name in ("prepare_ms.p99", "encode_ms.p99", "handoff_wait_ms.p99"):
        assert metrics[name]["value"] > 0
    # Every per-layer metric the cell lists that is no device number.
    listed = {m["name"]: m for m in BENCHMARK["per_layer"]
              if "firehose-churn-redeploy" in m.get("workloads", [])}
    assert len(listed) >= 60
    assert set(listed) >= {m["name"] for m in BENCHMARK["per_layer"]
                           if "firehose-rollout" in m.get("workloads", [])}
    # (The row hash goes over several threads from 32,768 rows a batch:
    # its two metrics read at the population's size alone.)
    at_size = {"feed_hash_parallel_batches_per_window",
               "cpu_row_hash_workers_ms_per_window"}
    for name, m in listed.items():
        if m["source"] != "device_trace" and name not in at_size:
            assert name in metrics, name
    assert "miss_scatter_roofline" not in metrics   # a device number


def test_node_streamed_under_rollout_rehearses_streamed_on_the_cpu():
    """The DaemonSet's flags through the harness at a tiny size: every
    window of the measured window is streamed (ten drains, fed while it
    is open), the comparison reads 0 on every number, nothing fails,
    nothing compiles inside the measured window, and the traced line
    carries the feed thread's metrics: every window's new stacks are
    dispatched by the drain that first holds them and settled from the
    feed thread, and the rest of its rows the carry cache folds."""
    line = _rehearse("node-streamed", "rollout", pids=40, stacks=1024,
                     samples=8000, capacity=16384, seconds=5)
    metrics = line["metrics"]
    assert metrics["streamed_windows_per_window"]["value"] == 1.0
    assert metrics["stream_rows_fed_per_window"]["value"] > 0
    assert metrics["carry_matched_rows_per_window"]["value"] > 0
    assert metrics["misses_per_window"]["value"] > 0
    # A window's new pids are registered by the drain that first holds
    # them and come back, known, in its later drains: each is asked for
    # its addresses then, and builds its look-up once.
    assert metrics["registry_index_builds_per_window"]["value"] > 0
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


def test_firehose_streamed_under_rollout_rehearses_streamed_on_the_cpu():
    """The DaemonSet's flags on the firehose deployment, through the
    harness at a size whose first drain (8,000 samples) starts three
    feed shapes above the floor: the run's first feed runs 8,192 down to
    1,024, every window of the measured window is streamed and none
    falls back, no feed is slow or compiles, no row reaches the sketch,
    the comparison reads 0 on every number, and the traced line carries
    the carry flush (one row a stack the cache folded: nearly all of the
    window's, so more rows than the new stacks dispatched by far) and
    the fixture's own stand-in for the sampler's drain."""
    line = _rehearse("firehose-streamed", "rollout", pids=200, stacks=16384,
                     samples=80000, capacity=65536, seconds=9)
    metrics = line["metrics"]
    assert metrics["streamed_windows_per_window"]["value"] == 1.0
    assert metrics["stream_fallback_windows_per_window"]["value"] == 0.0
    assert metrics["stream_feeds_slow_per_window"]["value"] == 0.0
    assert metrics["sketch_rows_per_window"]["value"] == 0.0
    fed = metrics["stream_rows_fed_per_window"]["value"]
    assert 0 < fed == metrics["misses_per_window"]["value"]
    assert 16384 - fed <= metrics["carry_flush_rows_per_window"]["value"] \
        <= 16384 + fed
    assert metrics["carry_matched_rows_per_window"]["value"] \
        >= metrics["carry_flush_rows_per_window"]["value"]
    assert metrics["close_ms.p50"]["value"] \
        >= metrics["close_carry_flush_ms.p50"]["value"] > 0
    assert metrics["drain_chunk_ms.p50"]["value"] > 0
    # Every per-layer metric the cell lists that is no device number.
    listed = {m["name"]: m for m in BENCHMARK["per_layer"]
              if "firehose-streamed-rollout" in m.get("workloads", [])}
    assert len(listed) >= 46
    assert set(listed) >= {m["name"] for m in BENCHMARK["per_layer"]
                           if "node-streamed-rollout"
                           in m.get("workloads", [])}
    for name, m in listed.items():
        if m["source"] != "device_trace":
            assert name in metrics, name


@pytest.mark.parametrize("config, traffic, seed", [
    ("node-streamed", "rollout", "3700000007"),
    # Over the pool's pids: processes of this machine (node-live, below).
    ("node-live", "steady-live", "4200000011"),
    # At the configuration's own size: 262,144 stacks, ~1.5 minutes.
    ("firehose-streamed", "rollout", "4400000007"),
])
def test_the_8_bit_control_is_not_correct(config, traffic, seed):
    """The plain reference in the program's place, its counts carried
    in 8 bits: the comparison that holds the cell has to say so (exit
    0: the sound shipment read 0 everywhere, the control did not)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"),
         "--config", config, "--traffic", traffic, "--seeds", seed],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["cell"] == f"{config}-{traffic}" and line["bits"] == 8
    assert line["sound"]["correct"] is True
    assert line["control"]["correct"] is False
    assert line["control"]["mass_gap"] > 0


# -- node-live: the window's pids are processes of this machine ---------------

# The rehearsal in a process of its own, with the tracker's reads and
# the labels that ride every shipped profile recorded on the way (the
# result line carries neither) and said on stderr after the run, with
# the pool's pids, so that the test can see that none is left.
LIVE_REHEARSAL = '''
import json, sys
sys.path[:0] = [{repo!r}, {bench!r}]
from parca_agent_tpu.agent import writer
from parca_agent_tpu.process import identity

windows, comms = [], {{}}
sound_reads = identity.ProcessIdentityTracker._starttimes

def counted_reads(self, distinct):
    got = sound_reads(self, distinct)
    checked, _starts, n_reads, n_absent = got[:4]
    windows.append([sorted(distinct.tolist()), sorted(checked), n_reads,
                    n_absent])
    return got

identity.ProcessIdentityTracker._starttimes = counted_reads
sound_write = writer.RemoteProfileWriter.write

def seen_write(self, labels, *a, **kw):
    comms.setdefault(labels.get("comm"), set()).add(int(labels["pid"]))
    return sound_write(self, labels, *a, **kw)

writer.RemoteProfileWriter.write = seen_write
import rehearse
from lib.mixes import live_ring
code = rehearse.main({args!r})
print("LIVE " + json.dumps({{
    "pool": live_ring.pool({pids}).pids, "windows": windows,
    "comms": {{str(c): sorted(p) for c, p in comms.items()}}}}),
    file=sys.stderr, flush=True)
sys.exit(code)
'''


def _pool_processes_left(pids) -> list[int]:
    """Those of ``pids`` that are still processes of the idle pool
    (a number the kernel has handed to another process is not)."""
    left = []
    for pid in pids:
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if b"idle_pool.py" in f.read():
                    left.append(pid)
        except OSError:
            pass
    return left


def _gone_within(pids, seconds: float) -> list[int]:
    deadline = time.monotonic() + seconds
    while (left := _pool_processes_left(pids)) \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    return left


def _live_rehearsal():
    args = ["--config", "node-live", "--traffic", "steady-live",
            "--pids", "40", "--stacks", "1024", "--samples", "8000",
            "--capacity", "16384", "--seconds", "4", "--trace", "1"]
    code = LIVE_REHEARSAL.format(repo=REPO, bench=BENCH, args=args, pids=40)
    out = subprocess.run([sys.executable, "-c", code],
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=540,
                         cwd=REPO)
    said = [ln for ln in out.stderr.splitlines() if ln.startswith("LIVE ")]
    return out, (json.loads(said[-1][5:]) if said else None)


def test_node_live_rehearses_correct_with_every_pid_read_every_window():
    """The deployment whose pids live, through the harness at a tiny
    size with a pool of 40 real processes: the comparison reads 0 on
    every number and nothing fails; every window the agent opened read
    the ``stat`` of every one of its pids and settled none as absent;
    every shipped profile carries the pool's real ``comm`` (``cat``);
    the traced line carries the three spans' two metrics and the two
    counts; and when the run is over no process of the pool is left."""
    out, live = _live_rehearsal()
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2 and line["rehearsal"] is True
    assert "compared stack_mismatches = 0 (limit 0)" in out.stdout
    assert "in-window parca_agent_xla_compile_requests_total = 0" \
        in out.stdout
    pool = live["pool"]
    assert len(pool) == 40 and pool == sorted(pool)
    # The guarantee: every live pid of every window, read.
    assert len(live["windows"]) >= line["attempted"] + 3
    for distinct, checked, n_reads, n_absent in live["windows"]:
        assert distinct == pool and checked == pool
        assert (n_reads, n_absent) == (40, 0)
    # Real labels: the pool's one comm on every pid's profiles.
    assert live["comms"] == {"cat": pool}
    metrics = line["metrics"]
    assert metrics["identity_checks_per_window"]["value"] == 40.0
    assert metrics["identity_absent_per_window"]["value"] == 0.0
    # Read by the one native call a window, every one of them.
    assert metrics["identity_native_reads_per_window"]["value"] == 40.0
    assert metrics["identity_ms.p50"]["value"] \
        >= metrics["identity_read_ms.p50"]["value"] > 0
    assert metrics["identity_list_ms.p50"]["value"] > 0
    # Every per-layer metric the cell lists that is no device number.
    listed = {m["name"]: m for m in BENCHMARK["per_layer"]
              if "node-live-steady" in m.get("workloads", [])}
    assert len(listed) >= 40
    for name, m in listed.items():
        if m["source"] != "device_trace":
            assert name in metrics, name
    assert _gone_within(pool, 10.0) == []


@pytest.mark.parametrize("how", ["an_exception", "a_kill"])
def test_no_process_of_the_pool_outlives_its_harness(how):
    """The pool's processes end with the process that started them:
    when it raises out of its run, and when it is killed where it
    stands (SIGKILL: no handler, no ``atexit``; the end of file on the
    pipe it held is all there is)."""
    code = (f"import sys; sys.path.insert(0, {BENCH!r})\n"
            "from lib.mixes import live_ring\n"
            "print(*live_ring.pool(24).pids, flush=True)\n"
            + ("raise RuntimeError('the run failed')\n"
               if how == "an_exception" else
               "import time; time.sleep(600)\n"))
    proc = subprocess.Popen([sys.executable, "-c", code], cwd=REPO,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    try:
        pids = [int(p) for p in proc.stdout.readline().split()]
        assert len(pids) == 24
        if how == "a_kill":
            assert _pool_processes_left(pids) == pids
            proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=60)
        assert proc.returncode != 0
    finally:
        proc.kill()
        proc.communicate()
    assert _gone_within(pids, 10.0) == []
