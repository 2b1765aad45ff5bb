"""Window flight recorder (runtime/trace.py, docs/observability.md).

The contract under test: every window gets a trace with per-stage spans;
completed traces land in a bounded ring and feed per-stage log-bucket
histograms; a stage blowing its running-p99 budget auto-captures exactly
one rate-limited incident (trace + self-profile + runtime context) as a
crash-only JSON file; and the entire tracing path is FAIL-OPEN — an
injected fault at ``trace.record`` or ``incident.dump`` never stalls or
loses a window.
"""

from __future__ import annotations

import base64
import json
import os
import time

import numpy as np
import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.profiler.cpu import CPUProfiler
from parca_agent_tpu.runtime.trace import (
    MANDATORY_SPANS,
    NULL_TRACE,
    FlightRecorder,
    StageHistogram,
)
from parca_agent_tpu.runtime import trace as trace_mod
from parca_agent_tpu.utils import faults


def _snap(seed=7, n_pids=6, rows=200):
    return generate(SyntheticSpec(
        n_pids=n_pids, n_unique_stacks=rows, n_rows=rows,
        total_samples=rows * 4, mean_depth=8, kernel_fraction=0.25,
        seed=seed))


class ListSource:
    """Capture source over a fixed list of snapshots; None at the end."""

    def __init__(self, snaps):
        self._snaps = list(snaps)

    def poll(self):
        return self._snaps.pop(0) if self._snaps else None


class Collect:
    def __init__(self):
        self.got = []

    def write(self, labels, blob):
        self.got.append((labels, bytes(blob)))


@pytest.fixture(autouse=True)
def _no_global_state():
    yield
    faults.install(None)
    trace_mod.install(None)


# -- histogram ----------------------------------------------------------------


def test_histogram_quantiles_and_max():
    h = StageHistogram()
    for ms in range(1, 101):  # 1..100 ms uniform
        h.observe(ms / 1e3)
    assert h.count == 100
    assert h.max_s == pytest.approx(0.1)
    # Log-bucket interpolation: within one 2x bucket of the true value.
    assert 0.025 <= h.quantile(0.5) <= 0.1
    assert h.quantile(0.99) <= h.max_s + 1e-9
    assert h.quantile(0.99) >= h.quantile(0.5)
    exp = h.export()
    assert exp["buckets"][-1][1] == 100  # largest finite bucket holds all
    assert exp["sum_s"] == pytest.approx(sum(range(1, 101)) / 1e3)


def test_histogram_export_buckets_cumulative_monotone():
    h = StageHistogram()
    for d in (1e-6, 1e-3, 0.5, 10.0, 1e4):  # incl. one past the last bound
        h.observe(d)
    cum = [c for _, c in h.export()["buckets"]]
    assert cum == sorted(cum)
    assert h.export()["count"] == 5
    assert cum[-1] == 4  # the 1e4 s observation lives in +Inf only


# -- trace lifecycle ----------------------------------------------------------


def test_trace_spans_ring_and_percentiles():
    rec = FlightRecorder(ring=4)
    for i in range(6):
        tr = rec.begin(time_ns=1000 + i)
        with tr.span("drain"):
            pass
        tr.add_span("close", 0.002)
        tr.annotate(samples=10)
        tr.complete()
    traces = rec.traces()
    assert len(traces) == 4                      # ring bound
    assert traces[-1]["seq"] == 6                # trace id == window seq
    assert traces[0]["seq"] == 3
    stages = {s["stage"] for s in traces[-1]["spans"]}
    assert {"drain", "close", "total"} <= stages
    assert traces[-1]["meta"] == {"samples": 10}
    assert rec.trace(5) is not None
    assert rec.trace(1) is None                  # fell off the ring
    pct = rec.percentiles()
    assert pct["close"]["count"] == 6
    assert pct["close"]["max_ms"] >= 2.0
    assert rec.stats["traces_completed"] == 6


def test_complete_is_idempotent_and_discard_skips_ring():
    rec = FlightRecorder()
    tr = rec.begin()
    tr.complete()
    tr.complete()
    assert rec.stats["traces_completed"] == 1
    tr2 = rec.begin()
    tr2.discard()
    assert rec.stats["traces_discarded"] == 1
    assert len(rec.traces()) == 1


def test_detached_trace_ignores_profiler_side_finish():
    rec = FlightRecorder()
    tr = rec.begin()
    tr.detach()
    tr.finish()                   # profiler end-of-iteration: no-op
    assert rec.stats["traces_completed"] == 0
    # An iteration error co-occurring with a successful hand-off (e.g.
    # debuginfo upload failure) annotates — it must NOT complete the
    # trace out from under the worker that owns it.
    tr.finish(error="debuginfo upload failed")
    assert rec.stats["traces_completed"] == 0
    tr.complete(error="worker died")   # owner's completion still lands
    assert rec.stats["traces_completed"] == 1
    t = rec.traces()[0]
    assert t["error"] == "worker died"
    assert t["meta"]["iteration_error"] == "debuginfo upload failed"


def test_zero_duration_stage_reports_zero_percentiles():
    h = StageHistogram()
    for _ in range(10):
        h.observe(0.0)
    assert h.quantile(0.5) == 0.0
    assert h.quantile(0.99) == 0.0
    assert h.max_s == 0.0


def test_nohist_span_rides_the_trace_but_not_the_histogram():
    rec = FlightRecorder()
    tr = rec.begin()
    tr.add_span("statics", 0.02, histogram=False)
    tr.add_span("encode", 0.01)
    tr.complete()
    pct = rec.percentiles()
    assert "statics" not in pct          # histogram untouched
    assert pct["encode"]["count"] == 1
    stages = {s["stage"] for s in rec.traces()[0]["spans"]}
    assert "statics" in stages           # wide event keeps the span
    assert "nohist" not in rec.traces()[0]["spans"][0]


def test_span_context_manager_records_error_and_reraises():
    rec = FlightRecorder()
    tr = rec.begin()
    with pytest.raises(ValueError):
        with tr.span("drain"):
            raise ValueError("boom")
    tr.complete(error="boom")
    t = rec.traces()[0]
    drain = next(s for s in t["spans"] if s["stage"] == "drain")
    assert "boom" in drain["error"]
    assert t["error"] == "boom"


# -- fail-open tracing (chaos) ------------------------------------------------


@pytest.mark.chaos
def test_trace_record_fault_is_swallowed_and_counted():
    faults.install(faults.FaultInjector.from_spec("trace.record:error"))
    rec = FlightRecorder()
    tr = rec.begin()              # begin itself rides the site
    assert tr is NULL_TRACE
    rec.observe("batch_flush", 0.01)
    assert rec.stats["record_errors"] >= 2
    faults.install(None)
    tr = rec.begin()
    tr.complete()
    assert rec.stats["traces_completed"] == 1


@pytest.mark.chaos
def test_tracing_fault_never_stalls_or_loses_a_window():
    """The acceptance bar: with trace.record firing on EVERY recording,
    all windows still aggregate, encode, and ship (fail-open), and the
    faults are visible as counted record errors."""
    faults.install(faults.FaultInjector.from_spec("trace.record:error"))
    rec = FlightRecorder()
    snaps = [_snap(seed=i) for i in range(3)]
    sink = Collect()
    prof = CPUProfiler(
        source=ListSource(snaps), aggregator=DictAggregator(capacity=1 << 12),
        fallback_aggregator=CPUAggregator(), profile_writer=sink,
        duration_s=0.0, fast_encode=True, encode_pipeline=True,
        trace_recorder=rec)
    prof.run()
    assert prof.crashed is None
    assert prof.last_error is None
    assert prof.metrics.attempts_total == 3
    assert prof.metrics.profiles_written > 0
    assert prof._pipeline.stats["windows_lost"] == 0
    assert rec.stats["record_errors"] > 0
    assert faults.get().stats().get("trace.record", 0) > 0
    # Nothing could be recorded, so nothing ringed — but nothing lost.
    assert rec.stats["traces_completed"] == 0


# -- profiler integration -----------------------------------------------------


def test_profiler_pipelined_traces_carry_mandatory_spans():
    rec = FlightRecorder()
    snaps = [_snap(seed=i) for i in range(4)]
    sink = Collect()
    prof = CPUProfiler(
        source=ListSource(snaps), aggregator=DictAggregator(capacity=1 << 12),
        fallback_aggregator=CPUAggregator(), profile_writer=sink,
        duration_s=0.0, fast_encode=True, encode_pipeline=True,
        trace_recorder=rec)
    prof.run()
    assert prof.crashed is None and prof.last_error is None
    traces = rec.traces()
    assert len(traces) == 4
    for t in traces:
        assert t["complete"] and "error" not in t
        stages = {s["stage"] for s in t["spans"]}
        assert set(MANDATORY_SPANS) <= stages, (t["seq"], stages)
        assert t["meta"]["path"] == "pipeline"
        assert t["meta"]["samples"] > 0
    # The stage histograms exist for every mandatory stage + total.
    pct = rec.percentiles()
    for stage in (*MANDATORY_SPANS, "total"):
        assert pct[stage]["count"] == 4, stage


def test_gauges_and_histograms_agree():
    """Satellite contract: the last-value gauges are set FROM the same
    measurements the histograms record, so they cannot disagree."""
    rec = FlightRecorder()
    snaps = [_snap(seed=i) for i in range(2)]
    prof = CPUProfiler(
        source=ListSource(snaps), aggregator=DictAggregator(capacity=1 << 12),
        fallback_aggregator=CPUAggregator(), profile_writer=Collect(),
        duration_s=0.0, fast_encode=True, encode_pipeline=True,
        trace_recorder=rec)
    prof.run()
    last = rec.traces()[-1]
    by_stage = {s["stage"]: s for s in last["spans"]}
    assert by_stage["close"]["duration_s"] == pytest.approx(
        prof.metrics.last_aggregate_duration_s, abs=1e-6)
    assert by_stage["encode"]["duration_s"] == pytest.approx(
        prof._pipeline.stats["last_encode_s"], abs=1e-6)
    assert by_stage["ship"]["duration_s"] == pytest.approx(
        prof._pipeline.stats["last_ship_s"], abs=1e-6)


def test_profiler_scalar_path_traces():
    rec = FlightRecorder()
    prof = CPUProfiler(
        source=ListSource([_snap(seed=1)]), aggregator=CPUAggregator(),
        profile_writer=Collect(), duration_s=0.0, trace_recorder=rec)
    prof.run()
    t = rec.traces()[0]
    stages = {s["stage"] for s in t["spans"]}
    assert {"drain", "close", "ship", "total"} <= stages
    assert t["meta"]["path"] == "scalar"


def test_poll_failure_completes_trace_with_error():
    class BadSource:
        def __init__(self):
            self.polled = 0

        def poll(self):
            self.polled += 1
            if self.polled == 1:
                raise OSError("ring gone")
            return None

    rec = FlightRecorder()
    prof = CPUProfiler(source=BadSource(), aggregator=CPUAggregator(),
                       duration_s=0.0, trace_recorder=rec)
    prof.run()
    traces = rec.traces()
    assert len(traces) == 1
    assert "ring gone" in traces[0]["error"]
    assert rec.stats["traces_discarded"] == 1  # the end-of-source poll


# -- slow-window detection / incidents ---------------------------------------


def _primed_recorder(tmp_path, **kw):
    rec = FlightRecorder(
        incident_dir=str(tmp_path / "incidents"), min_count=4,
        # Production-scale floor: the real begin->complete wall time of
        # the synthetic windows feeds the 'total' histogram, so a floor
        # near the test's ~us scale turns any scheduler hiccup into a
        # false incident (load-flaky under the full suite).
        min_duration_s=0.05, slow_multiple=5.0,
        context=lambda: {"supervisor": {"profiler": "healthy"}},
        self_profile=lambda: b"\x1f\x8bFAKEPPROF", **kw)
    for i in range(6):
        tr = rec.begin()
        tr.add_span("close", 0.002)
        tr.complete()
    return rec


def _wait_incidents(rec, tmp_path, n, timeout=5.0):
    deadline = time.monotonic() + timeout
    d = str(tmp_path / "incidents")
    while time.monotonic() < deadline:
        done = rec.stats["incidents_written"] + rec.stats["incidents_failed"]
        if done >= n and not rec._dumping:
            break
        time.sleep(0.01)
    return sorted(os.listdir(d)) if os.path.isdir(d) else []


def test_slow_window_captures_exactly_one_incident(tmp_path):
    rec = _primed_recorder(tmp_path)
    tr = rec.begin()
    tr.add_span("close", 0.5)      # 250x the primed p99
    tr.complete()
    files = _wait_incidents(rec, tmp_path, 1)
    assert len(files) == 1
    assert rec.stats["incidents_written"] == 1
    assert rec.stats["slow_spans_total"] >= 1
    body = json.loads((tmp_path / "incidents" / files[0]).read_text())
    assert body["kind"] == "slow_window"
    assert body["stage"] == "close"
    assert body["trace"]["seq"] == tr.seq
    assert any(s.get("slow") for s in body["trace"]["spans"])
    assert body["duration_s"] == pytest.approx(0.5)
    assert body["budget_s"] > 0
    assert body["context"] == {"supervisor": {"profiler": "healthy"}}
    assert base64.b64decode(
        body["self_profile_pprof_gz_b64"]) == b"\x1f\x8bFAKEPPROF"
    assert "close" in body["stage_percentiles"]
    # The slow trace is still a normal ring citizen.
    assert rec.trace(tr.seq)["meta"]["slow_stage"] == "close"


def test_second_slow_window_is_rate_limited(tmp_path):
    rec = _primed_recorder(tmp_path, incident_interval_s=3600.0)
    # Escalating durations so the SECOND one still breaches the p99
    # budget the first one just inflated.
    for dur in (0.5, 30.0):
        tr = rec.begin()
        tr.add_span("close", dur)
        tr.complete()
    files = _wait_incidents(rec, tmp_path, 1)
    assert len(files) == 1
    assert rec.stats["incidents_suppressed"] >= 1


def test_global_stage_stall_captures_incident(tmp_path):
    """'Any traced stage': a transport stage observed via observe() (no
    per-window trace) rides the same detector and dump machinery."""
    rec = FlightRecorder(
        incident_dir=str(tmp_path / "incidents"), min_count=4,
        min_duration_s=0.001, context=lambda: {},
        self_profile=lambda: b"p")
    for _ in range(6):
        rec.observe("batch_flush", 0.002)
    rec.observe("batch_flush", 1.0)
    files = _wait_incidents(rec, tmp_path, 1)
    assert len(files) == 1
    body = json.loads((tmp_path / "incidents" / files[0]).read_text())
    assert body["stage"] == "batch_flush"
    assert body["trace"] is None


def test_fast_windows_capture_nothing(tmp_path):
    rec = _primed_recorder(tmp_path)
    for _ in range(10):
        tr = rec.begin()
        tr.add_span("close", 0.002)
        tr.complete()
    assert _wait_incidents(rec, tmp_path, 0, timeout=0.3) == []
    assert rec.stats["incidents_written"] == 0
    assert rec.stats["slow_spans_total"] == 0


@pytest.mark.chaos
def test_incident_dump_fault_costs_the_file_not_the_window(tmp_path):
    faults.install(faults.FaultInjector.from_spec("incident.dump:error"))
    rec = _primed_recorder(tmp_path)
    tr = rec.begin()
    tr.add_span("close", 0.5)
    tr.complete()
    _wait_incidents(rec, tmp_path, 1)
    assert rec.stats["incidents_failed"] == 1
    assert rec.stats["incidents_written"] == 0
    assert os.listdir(tmp_path / "incidents") == []
    # The window itself completed normally into the ring.
    assert rec.trace(tr.seq)["complete"]


def test_incident_files_pruned_to_cap(tmp_path):
    rec = _primed_recorder(tmp_path, incident_interval_s=0.0,
                           max_incidents=2)
    for _ in range(4):
        tr = rec.begin()
        tr.add_span("close", 0.5)
        tr.complete()
        _wait_incidents(rec, tmp_path, rec.stats["incidents_written"] + 1,
                        timeout=2.0)
        time.sleep(0.02)  # distinct timestamps keep prune order honest
    files = _wait_incidents(rec, tmp_path, 4)
    assert len(files) <= 2


# -- the module-global hook ---------------------------------------------------


def test_module_observe_is_free_without_recorder():
    trace_mod.install(None)
    trace_mod.observe("batch_flush", 1.0)  # no-op, no error
    rec = FlightRecorder()
    trace_mod.install(rec)
    trace_mod.observe("batch_flush", 0.5)
    assert rec.percentiles()["batch_flush"]["count"] == 1
    trace_mod.install(None)


@pytest.mark.chaos
def test_failed_spool_spill_is_still_observed(tmp_path):
    """A slow-then-failing disk is exactly the stall the spool_spill
    histogram exists to explain: the failure path observes too."""
    from parca_agent_tpu.agent.profilestore import RawSeries
    from parca_agent_tpu.agent.spool import SpoolDir

    rec = FlightRecorder()
    trace_mod.install(rec)
    try:
        faults.install(faults.FaultInjector.from_spec(
            "spool.write:disk_full"))
        spool = SpoolDir(str(tmp_path / "spool"))
        assert not spool.append([RawSeries({"a": "b"}, [b"x"])])
        assert rec.percentiles()["spool_spill"]["count"] == 1
    finally:
        trace_mod.install(None)


def test_encoder_statics_build_feeds_global_histogram():
    rec = FlightRecorder()
    trace_mod.install(rec)
    try:
        from parca_agent_tpu.pprof.window_encoder import WindowEncoder

        snap = _snap(seed=3)
        agg = DictAggregator(capacity=1 << 12)
        counts = np.asarray(agg.window_counts(snap))
        enc = WindowEncoder(agg)
        enc.build_statics(snap.period_ns)
        assert enc.stats["last_statics_build_s"] > 0
        assert enc.stats["statics_build_s_total"] >= \
            enc.stats["last_statics_build_s"]
        assert rec.percentiles()["statics"]["count"] >= 1
        enc.encode(counts, snap.time_ns, snap.window_ns, snap.period_ns)
    finally:
        trace_mod.install(None)


# -- one span tree on one clock (ISSUE 24) -----------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CLOSE_CHILDREN = ("feed_hash", "feed_coalesce", "feed_pack", "feed_dispatch",
                  "feed_settle", "close_dispatch", "buffer_flip",
                  "close_fetch", "close_unpack")
SHIP_CHILDREN = ("ship_labels", "ship_gzip", "ship_enqueue")


class RawSink:
    """The batch client's side of RemoteProfileWriter."""

    def __init__(self):
        self.n = 0

    def write_raw(self, labels, sample):
        self.n += 1


class _StubResolver:
    def tenant_of(self, pid):
        return "t0"


def _traced_windows(pipelined: bool, n: int = 3, admission=None,
                    identity=None, snaps=None, profilers=None):
    """n windows through the real profiler on XLA:CPU, one at a time (a
    pipelined window is shipped before the next is handed over). The
    profiler is appended to ``profilers`` for a test that reads it."""
    from parca_agent_tpu.agent.writer import RemoteProfileWriter

    rec = FlightRecorder()
    snaps = [_snap(seed=5) for _ in range(n)] if snaps is None else snaps
    n = len(snaps)
    prof = CPUProfiler(
        source=ListSource(snaps),
        aggregator=DictAggregator(capacity=1 << 12),
        fallback_aggregator=CPUAggregator(),
        profile_writer=RemoteProfileWriter(RawSink()), duration_s=0.0,
        fast_encode=True, encode_pipeline=pipelined, trace_recorder=rec,
        admission=admission, identity=identity)
    if profilers is not None:
        profilers.append(prof)
    for _ in range(n):
        assert prof.run_iteration()
        assert prof.last_error is None
        if pipelined:
            assert prof._pipeline.flush(30.0)
    if pipelined:
        assert prof._pipeline.close(30.0)
    traces = rec.traces()
    assert len(traces) == n
    assert {t["meta"]["path"] for t in traces} \
        == {"pipeline" if pipelined else "inline"}
    return rec, traces


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "inline"])
def test_every_span_is_recorded_by_one_add_span_on_the_class(
        pipelined, monkeypatch):
    """The benchmark takes the end-to-end metric's two edges by
    replacing ``WindowTrace.add_span`` on the class and reading
    ``(seq, stage)`` from the call (benchmarks/lib/harness.py
    EdgeClock). So every span of a window, context-managed or after
    the fact, child or not, has to go through that one method, looked
    up on the class when the span ends."""
    seen = []
    sound = trace_mod.WindowTrace.add_span

    def add_span(window_trace, stage, *args, **kwargs):
        seen.append((window_trace.seq, stage))
        return sound(window_trace, stage, *args, **kwargs)

    monkeypatch.setattr(trace_mod.WindowTrace, "add_span", add_span)
    _rec, traces = _traced_windows(pipelined)
    for t in traces:
        stages = [s["stage"] for s in t["spans"] if s["stage"] != "total"]
        assert {"drain", "close", "encode", "ship"} <= set(stages)
        for stage in stages:
            assert (t["seq"], stage) in seen, (t["seq"], stage)
    # The two edges of window_to_pprof, in the order the metric needs.
    last = traces[-1]["seq"]
    assert seen.index((last, "drain")) < seen.index((last, "encode"))


def test_the_span_tree_parents_intervals_and_honest_starts():
    from parca_agent_tpu.process.identity import ProcessIdentityTracker
    from parca_agent_tpu.runtime.admission import AdmissionController

    before = time.monotonic()
    rec, traces = _traced_windows(
        True, admission=AdmissionController(_StubResolver()),
        identity=ProcessIdentityTracker(starttime_of=lambda pid: 1))
    after = time.monotonic()
    for t in traces:
        assert before <= t["t0_monotonic_s"] <= after
        by_id = {s["id"]: s for s in t["spans"]}
        assert len(by_id) == len(t["spans"])           # ids are unique
        by_stage = {s["stage"]: s for s in t["spans"]}
        assert len(by_stage) == len(t["spans"])        # one span a stage
        for stage in ("drain", "identity", "admission", "close",
                      "handoff_wait", "prepare", "encode_wait", "encode",
                      "ship", "total"):
            assert by_stage[stage]["parent"] is None, stage
        for stage in CLOSE_CHILDREN:
            assert by_stage[stage]["parent"] == by_stage["close"]["id"], stage
        for stage in SHIP_CHILDREN:
            assert by_stage[stage]["parent"] == by_stage["ship"]["id"], stage
            assert by_stage[stage]["accumulated"] is True
        for s in t["spans"]:
            assert s["start_s"] >= 0 and s["duration_s"] >= 0
            # Every span ended before the trace was read.
            assert t["t0_monotonic_s"] + s["start_s"] + s["duration_s"] \
                <= after + 1e-3
            if s["parent"] is not None:
                p = by_id[s["parent"]]
                assert s["start_s"] >= p["start_s"] - 2e-6, s["stage"]
                assert s["start_s"] + s["duration_s"] \
                    <= p["start_s"] + p["duration_s"] + 2e-6, s["stage"]
        for parent, kids in (("close", CLOSE_CHILDREN),
                             ("ship", SHIP_CHILDREN)):
            assert by_stage[parent]["duration_s"] - sum(
                by_stage[k]["duration_s"] for k in kids) >= -1e-5, parent
        # The stages of the capture thread follow one another, and the
        # after-the-fact spans start where the work started: the wait
        # for the worker begins at the hand-off and ends where the
        # encode begins; the ship begins where the encode ended.
        order = ["drain", "identity", "admission", "close", "handoff_wait",
                 "prepare", "encode_wait", "encode", "ship"]
        for a, b in zip(order, order[1:]):
            assert by_stage[a]["start_s"] + by_stage[a]["duration_s"] \
                <= by_stage[b]["start_s"] + 2e-6, (a, b)
        wait, enc = by_stage["encode_wait"], by_stage["encode"]
        assert wait["start_s"] + wait["duration_s"] \
            == pytest.approx(enc["start_s"], abs=1e-4)
        assert wait["thread"] == enc["thread"] != by_stage["close"]["thread"]
        meta = t["meta"]
        assert meta["rows"] == 200 and 0 < meta["rows_fed"] <= 200
        assert meta["profiles"] == 6
        assert 0 < meta["gzip_bytes"] < meta["pprof_bytes"]
        # An injected reader is asked for every distinct pid.
        assert (meta["identity_pids"], meta["identity_stat_reads"],
                meta["identity_absent"]) == (6, 6, 0)
    # The first window met every stack for the first time.
    first = {s["stage"]: s for s in traces[0]["spans"]}
    assert first["feed_miss"]["parent"] == first["close"]["id"]
    assert traces[0]["meta"]["misses"] == traces[0]["meta"]["rows_fed"]
    assert traces[-1]["meta"]["misses"] == 0
    # Children are wide-event only: no histogram, so no /metrics series.
    hists = set(rec.export_histograms())
    assert {"identity", "admission", "encode_wait"} <= hists
    assert not hists & {*CLOSE_CHILDREN, *SHIP_CHILDREN, "handoff_wait",
                        "feed_miss"} - {"buffer_flip"}


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "inline"])
def test_identity_counts_on_the_window_meta(pipelined):
    """The bulk identity check says on the window's ``meta`` how many
    distinct pids it saw, how many it read and how many the listing
    settled without a read; the three add up in every window."""
    from parca_agent_tpu.process.identity import ProcessIdentityTracker
    from parca_agent_tpu.utils.vfs import FakeFS
    from parca_agent_tpu.web import render_metrics

    pids = sorted({int(p) for p in _snap(seed=5).pids})
    assert len(pids) == 6
    rest = " ".join(["R"] + ["0"] * 18 + ["4242", "0"])
    listed = pids[:4]   # the other two are not processes of this host
    fs = FakeFS({f"/proc/{p}/stat": f"{p} (x) {rest}".encode()
                 for p in listed})
    tracker = ProcessIdentityTracker(fs=fs, enabled=True)
    _rec, traces = _traced_windows(pipelined, identity=tracker)
    for t in traces:
        meta = t["meta"]
        assert meta["identity_pids"] == 6
        assert meta["identity_stat_reads"] == 4
        assert meta["identity_absent"] == 2
        assert meta["identity_stat_reads"] + meta["identity_absent"] \
            == meta["identity_pids"]
    m = tracker.metrics()
    assert (m["checks_total"], m["absent_total"]) == (12, 6)
    assert "parca_agent_pid_identity_absent_total 6" \
        in render_metrics([], identity=tracker)


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "inline"])
def test_coalesce_counts_on_the_window_meta_and_on_metrics(pipelined):
    """The fold says on the window's ``meta`` which way it went: a
    window whose rows are distinct was answered by the value sort alone
    (``coalesce_unique``), one with repeats says how many rows folded
    away (``coalesce_folded``); ``/metrics`` carries the two counters
    beside the fold's rows in / rows out."""
    from parca_agent_tpu.capture.formats import concat_snapshots
    from parca_agent_tpu.ops.hashing import native_hash_available
    from parca_agent_tpu.web import render_metrics

    assert native_hash_available()   # else the fold runs on raw rows
    snap = _snap(seed=5)
    dup = concat_snapshots([snap] * 3)   # every row three times
    profilers = []
    _rec, traces = _traced_windows(pipelined, snaps=[snap, dup, snap],
                                   profilers=profilers)
    metas = [t["meta"] for t in traces]
    assert [m.get("coalesce_unique", 0) for m in metas] == [1, 0, 1]
    assert [m.get("coalesce_folded", 0) for m in metas] == [0, 400, 0]
    assert [m["rows"] for m in metas] == [200, 600, 200]
    assert [m["rows_fed"] for m in metas] == [200, 200, 200]
    lines = [ln for ln in render_metrics(profilers).splitlines()
             if not ln.startswith("#")]
    at = lines.index(
        'parca_agent_feed_coalesce_rows_in_total{profiler="cpu"} 1000')
    assert lines[at + 1:at + 5] == [
        'parca_agent_feed_coalesce_rows_out_total{profiler="cpu"} 600',
        'parca_agent_feed_coalesce_fallbacks_total{profiler="cpu"} 0',
        'parca_agent_feed_coalesce_unique_batches_total{profiler="cpu"} 2',
        'parca_agent_feed_coalesce_wide_folds_total{profiler="cpu"} 0']


def test_a_stage_in_chunks_is_one_accumulated_span():
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("close") as close:
        for _ in range(3):
            with trace_mod.child("feed_hash") as sp:
                time.sleep(0.001)
            assert sp.duration_s >= 0.001
        trace_mod.count(rows_fed=5)
        trace_mod.count(rows_fed=7)
    tr.complete()
    t = rec.traces()[-1]
    (fh,) = [s for s in t["spans"] if s["stage"] == "feed_hash"]
    assert fh["accumulated"] is True and fh["parent"] == close.id
    assert 0.003 <= fh["duration_s"] <= close.duration_s
    assert t["meta"]["rows_fed"] == 12


def test_a_merged_stage_keeps_its_first_id_and_counts_its_intervals():
    """Three drains of one window: one stream_feed span under drain, the
    sum of the three with n = 3; what the later drains record inside it
    names that one span as parent and merges by key too."""
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("drain") as drain:
        for _ in range(3):
            with trace_mod.child("stream_feed") as feed:
                with trace_mod.child("drain_fold"):
                    time.sleep(0.001)
                trace_mod.note("feed_handoff", 0.002,
                               start_s=time.monotonic())
            assert feed.parent == drain.id
    tr.finish()
    spans = {s["stage"]: s for s in rec.traces()[-1]["spans"]}
    assert len(spans) == len(rec.traces()[-1]["spans"])
    feed = spans["stream_feed"]
    assert feed["accumulated"] is True and feed["n"] == 3
    assert feed["parent"] == spans["drain"]["id"]
    for stage in ("drain_fold", "feed_handoff"):
        assert spans[stage]["parent"] == feed["id"]
        assert spans[stage]["n"] == 3
    assert spans["feed_handoff"]["duration_s"] == pytest.approx(0.006)
    assert "n" not in spans["drain"]


def test_a_wait_inside_a_span_lies_beside_its_annotation(monkeypatch):
    """``trace.waiting``: the open span's ``pa/<stage>`` annotation is
    closed for the wait's length and opened again after it, so the
    profiler's trace never shows ``pa/sleep`` inside ``pa/drain``; a
    span adopted from another thread is left alone."""
    events = []

    class Ann:
        def __init__(self, stage):
            self.stage = stage

        def __enter__(self):
            events.append(("enter", self.stage))

        def __exit__(self, *exc):
            events.append(("exit", self.stage))

    monkeypatch.setattr(trace_mod, "annotation",
                        lambda stage, **kv: Ann(stage))
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("drain") as drain:
        with trace_mod.waiting("sleep"):
            events.append(("wait", None))
    assert events == [("enter", "drain"), ("exit", "drain"),
                      ("enter", "sleep"), ("wait", None), ("exit", "sleep"),
                      ("enter", "drain"), ("exit", "drain")]
    assert drain.duration_s > 0
    # From another thread's point of view the adopted span is not its
    # own: a wait there closes nothing.
    del events[:]
    import threading

    with tr.span("drain") as drain:
        def other():
            with trace_mod.adopt(drain), trace_mod.waiting("sleep"):
                pass

        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert events == [("enter", "drain"), ("enter", "sleep"),
                      ("exit", "sleep"), ("exit", "drain")]
    tr.finish()


def test_child_with_nothing_open_measures_and_records_nowhere():
    rec = FlightRecorder()
    trace_mod.install(rec)
    with trace_mod.child("feed_hash") as sp:
        time.sleep(0.002)
    assert sp.duration_s >= 0.002
    trace_mod.note("ship_gzip", 0.5, accumulated=True)
    trace_mod.count(rows_fed=3)
    assert rec.traces() == [] and rec.stats["record_errors"] == 0
    assert trace_mod.current() is None


def test_a_pending_span_parents_what_begins_before_it_is_recorded():
    """A span its caller records at its end through add_span (the encode
    worker's `encode`): adopted meanwhile, it is the parent of children
    begun on the thread; a raise inside records the children and no
    span of the stage; with tracing off nothing is recorded at all."""
    rec = FlightRecorder()
    tr = rec.begin()
    t0 = time.monotonic()
    sp = trace_mod.pending(tr, "encode")
    with trace_mod.adopt(sp):
        assert trace_mod.current() is sp
        with trace_mod.child("encode_statics"):
            time.sleep(0.001)
    assert trace_mod.current() is None and len(tr.spans) == 1
    tr.add_span("encode", time.monotonic() - t0, start_s=t0, span_id=sp.id)
    child, parent = tr.spans
    assert (child["stage"], parent["stage"]) == ("encode_statics", "encode")
    assert child["parent"] == parent["id"] == sp.id
    assert parent["parent"] is None
    with pytest.raises(RuntimeError):
        with trace_mod.adopt(trace_mod.pending(tr, "ship")):
            raise RuntimeError("boom")
    assert trace_mod.current() is None
    assert [s["stage"] for s in tr.spans] == ["encode_statics", "encode"]
    off = trace_mod.pending(NULL_TRACE, "encode")
    with trace_mod.adopt(off):
        with trace_mod.child("encode_statics") as c:
            pass
    assert off.id is None and c.duration_s >= 0
    assert trace_mod.current() is None and rec.stats["record_errors"] == 0


@pytest.mark.chaos
def test_child_is_fail_open_under_the_trace_record_chaos_site():
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("close"):
        faults.install(faults.FaultInjector.from_spec("trace.record:error"))
        with trace_mod.child("feed_hash") as sp:
            time.sleep(0.001)
        trace_mod.note("delta_fetch", 0.001)
        faults.install(None)
    assert sp.duration_s >= 0.001           # measured all the same
    assert rec.stats["record_errors"] >= 2  # counted, never raised
    tr.complete()
    assert [s["stage"] for s in rec.traces()[-1]["spans"]] \
        == ["close", "total"]
    assert trace_mod.current() is None      # nothing left on the stack


def test_traces_limit_builds_only_what_it_returns(monkeypatch):
    rec = FlightRecorder(ring=64)
    for _ in range(40):
        rec.begin().complete()
    built = []
    sound = trace_mod.WindowTrace.to_dict
    monkeypatch.setattr(trace_mod.WindowTrace, "to_dict",
                        lambda self: built.append(self.seq) or sound(self))
    assert [t["seq"] for t in rec.traces(limit=2)] == [39, 40]
    assert built == [39, 40]
    assert len(rec.traces()) == 40


def test_fallback_duration_sums_top_level_spans_only():
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("close"):
        with trace_mod.child("feed_hash"):
            time.sleep(0.002)
    d = tr.to_dict()                         # no total yet
    (close,) = [s for s in d["spans"] if s["stage"] == "close"]
    assert d["duration_s"] == close["duration_s"]


@pytest.mark.parametrize("metric, program", [
    ("feed_probe_roofline", "feed"),
    ("close_roofline", "close"),
    ("close_roofline", "close_delta"),
])
def test_device_program_names_match_the_benchmarks_patterns(metric, program):
    """``benchmarks/metrics/*_roofline.json`` find the device programs
    by the names XLA gives them; the named scopes inside the programs
    must not change those."""
    import re

    import jax
    import jax.numpy as jnp

    from parca_agent_tpu.aggregator import dict as dict_mod

    with open(os.path.join(REPO, "benchmarks", "metrics",
                           metric + ".json")) as f:
        pattern = json.load(f)["args"]["pattern"]
    u32 = jax.ShapeDtypeStruct
    if program == "feed":
        lowered = dict_mod._feed_program(1024, 512, 64, 4, 128).lower(
            u32((1024, 4), jnp.uint32), u32((512,), jnp.int32),
            u32((4,), jnp.int32), u32((4, 64), jnp.uint32),
            u32((), jnp.uint32))
    elif program == "close":
        lowered = dict_mod._close_program(512, 512, 8, 16).lower(
            u32((512,), jnp.int32))
    else:
        lowered = dict_mod._close_program_delta(512, 512, 8, 16, 2, 128) \
            .lower(u32((512,), jnp.int32), u32((4,), jnp.int32))
    text = lowered.as_text()
    name = re.search(r"module @(\S+)", text).group(1)
    assert re.search(pattern, name), (pattern, name)
    scope = {"feed": "probe", "close": "pack",
             "close_delta": "touched_blocks"}[program]
    assert scope in lowered.as_text(debug_info=True)


def test_a_traced_window_without_a_device_aggregator_never_imports_jax():
    """``--aggregator cpu`` stays JAX-free: the ``pa/*`` annotations are
    entered only when something else already imported JAX."""
    import subprocess
    import sys

    code = """
import sys
import parca_agent_tpu.runtime.trace as trace_mod
from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.profiler.cpu import CPUProfiler

class Source:
    def __init__(self):
        self.left = [generate(SyntheticSpec(n_pids=3, n_unique_stacks=40,
                                            n_rows=40, total_samples=160,
                                            seed=1))]
    def poll(self):
        return self.left.pop() if self.left else None

class Writer:
    def write(self, labels, blob):
        pass

rec = trace_mod.FlightRecorder()
trace_mod.install(rec)
prof = CPUProfiler(source=Source(), aggregator=CPUAggregator(),
                   profile_writer=Writer(), duration_s=0.0,
                   trace_recorder=rec)
prof.run()
(t,) = rec.traces()
assert {"drain", "close", "ship", "total"} <= {s["stage"] for s in t["spans"]}
assert "jax" not in sys.modules, "jax was imported"
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": REPO})
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_a_compile_is_put_down_to_the_window_it_fell_in():
    from parca_agent_tpu.runtime import device_telemetry as dtel

    tel = dtel.DeviceTelemetry()
    dtel.install(tel)
    try:
        rec = FlightRecorder()
        quiet = rec.begin()
        quiet.complete()
        busy = rec.begin()
        tel.note_xla("compile_requests_total")
        tel.note_xla("compile_requests_total")
        busy.complete()
    finally:
        dtel.install(None)
    first, second = rec.traces()
    assert "xla_compiles" not in first.get("meta", {})   # only when not 0
    assert second["meta"]["xla_compiles"] == 2


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "inline"])
def test_gzip_counts_on_the_window_meta_and_on_metrics(pipelined):
    """The ship says on the window's ``meta`` what its gzip did: a
    pipelined window's blobs carry their static span, so the first one
    builds a piece a profile and every later one reuses them and
    deflates a fraction of its bytes; an inline window ships plain
    copies, which are deflated whole. ``/metrics`` carries the sums."""
    from parca_agent_tpu.web import render_metrics

    profilers = []
    _rec, traces = _traced_windows(pipelined, profilers=profilers)
    metas = [t["meta"] for t in traces]
    assert all(m["profiles"] == 6 and m["gzip_fallbacks"] == 0
               for m in metas)
    if pipelined:
        assert [m["gzip_static_built"] for m in metas] == [6, 0, 0]
        assert [m["gzip_static_reused"] for m in metas] == [0, 6, 6]
        assert metas[0]["gzip_deflated_bytes"] < metas[0]["pprof_bytes"]
        assert all(m["gzip_deflated_bytes"] < m["pprof_bytes"] // 2
                   for m in metas[1:])
    else:
        assert all(m["gzip_static_built"] == m["gzip_static_reused"] == 0
                   and m["gzip_deflated_bytes"] == m["pprof_bytes"]
                   for m in metas)
    text = render_metrics(profilers)
    built, reused = (6, 12) if pipelined else (0, 0)
    assert ('parca_agent_ship_static_pieces_total{profiler="cpu",'
            f'outcome="reused"}} {reused}') in text
    assert ('parca_agent_ship_static_pieces_total{profiler="cpu",'
            f'outcome="built"}} {built}') in text
    assert ('parca_agent_ship_deflated_bytes_total{profiler="cpu"} '
            f'{sum(m["gzip_deflated_bytes"] for m in metas)}') in text
    assert 'parca_agent_ship_gzip_fallbacks_total{profiler="cpu"} 0' in text
    cache = [ln for ln in text.splitlines()
             if ln.startswith("parca_agent_ship_static_cache_bytes")]
    assert len(cache) == 1
    assert (float(cache[0].split()[-1]) > 0) == pipelined


# -- CPU accounting inside the program (ISSUE 39) -----------------------------


def _burn(cpu_s: float) -> None:
    t0 = time.thread_time()
    while time.thread_time() - t0 < cpu_s:
        pass


def _spans_of(rec: FlightRecorder, seq: int = -1) -> dict:
    return {s["stage"]: s for s in rec.traces()[seq]["spans"]}


class _CountedClock:
    """The ``time`` module as runtime/trace.py sees it, counting (or
    failing) its reads of the thread's CPU clock."""

    def __init__(self, fail: bool = False):
        self.reads, self.fail = 0, fail

    def __getattr__(self, name):
        return getattr(time, name)

    def thread_time(self):
        self.reads += 1
        if self.fail:
            raise OSError("no thread clock here")
        return time.thread_time()


@pytest.mark.parametrize("work,cpu_share", [
    (lambda: _burn(0.05), None),
    (lambda: time.sleep(0.05), 0.1),
], ids=["burns", "sleeps"])
def test_a_span_that_asks_records_its_threads_cpu_beside_its_wall(
        work, cpu_share):
    """A span that works has the CPU its thread's own clock saw go by
    (within one 10 ms tick; never a share of the wall, which on a loaded
    machine a busy loop gets any part of); one that waits has wall and
    next to no CPU."""
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("drain", usage=True):
        c0 = time.thread_time()
        work()
        burnt = time.thread_time() - c0
    tr.complete()
    drain = _spans_of(rec)["drain"]
    cpu = drain.get("cpu_s", 0.0)
    if cpu_share is None:
        assert cpu > 0.0 and abs(cpu - burnt) <= 0.010
    else:
        assert cpu <= cpu_share * drain["duration_s"]
    assert "cpu_s" not in _spans_of(rec)["total"]


def test_a_span_that_does_not_ask_reads_no_clock(monkeypatch):
    """A reading is a system call (dear on a sandboxed kernel), so the
    stages between a window's last sample and its pprof bytes take
    none: the call site decides, and this module names no stage."""
    import threading

    clock = _CountedClock()
    monkeypatch.setattr(trace_mod, "time", clock)
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("close"):
        with trace_mod.child("feed_hash"):
            pass
        t = threading.Thread(target=lambda ctx: trace_mod.adopt(ctx)
                             .__enter__().__exit__(None, None, None),
                             args=(trace_mod.current(),))
        t.start()
        t.join(10)
    tr.add_span("encode", 0.002)
    assert clock.reads == 0
    with tr.span("ship", usage=True):
        pass
    assert clock.reads == 2
    tr.complete()
    spans = _spans_of(rec)
    for stage in ("close", "feed_hash", "encode"):
        assert not {"cpu_s", "threads"} & set(spans[stage]), stage
        assert spans[stage]["duration_s"] >= 0
    assert set(rec.export_stage_cpu()) <= {"ship"}


def test_usage_fields_are_left_out_when_zero():
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("drain", usage=True):
        time.sleep(0.001)
    tr.add_span("ship", 0.002, used={"cpu_s": 0.0015})
    tr.complete()
    spans = _spans_of(rec)
    assert "threads" not in spans["drain"] and "threads" not in spans["ship"]
    assert spans["drain"].get("cpu_s", 1.0) > 0  # there, or left out
    assert spans["ship"]["cpu_s"] == 0.0015


def test_a_merged_span_sums_what_its_intervals_used():
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("drain", usage=True):
        for _ in range(3):
            with trace_mod.child("stream_feed", usage=True):
                _burn(0.01)
    tr.complete()
    spans = _spans_of(rec)
    assert spans["stream_feed"]["n"] == 3
    assert 0.03 <= spans["stream_feed"]["cpu_s"] <= spans["drain"]["cpu_s"]
    assert rec.export_stage_cpu()["stream_feed"] \
        == pytest.approx(spans["stream_feed"]["cpu_s"])


def test_a_spans_cpu_is_that_of_every_thread_that_worked_under_it():
    """The feeder's thread adopts the capture thread's ``stream_feed``:
    that span and the ``drain`` around it hold the thread's CPU beside
    the capture thread's wait, and what the thread records under it
    without asking has none of its own."""
    from parca_agent_tpu.utils.bounded import bounded_call

    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("drain", usage=True):
        for _ in range(2):
            with trace_mod.child("stream_feed", usage=True):
                open_span = trace_mod.current()

                def feed():
                    with trace_mod.adopt(open_span):
                        with trace_mod.child("feed_carry"):
                            _burn(0.02)

                assert bounded_call(feed, 10, "stream-feed")[0] == "ok"
    tr.complete()
    spans = _spans_of(rec)
    drain, feed = spans["drain"], spans["stream_feed"]
    assert spans["feed_carry"]["thread"] == "stream-feed"
    assert "cpu_s" not in spans["feed_carry"]
    assert feed["threads"] == 2 and drain["threads"] == 2
    # The capture thread only waited: what they hold is the others'.
    assert 0.04 <= feed["cpu_s"] <= drain["cpu_s"] < 0.5
    assert rec.export_stage_cpu()["drain"] == pytest.approx(drain["cpu_s"])


def test_adopting_a_span_on_its_own_thread_counts_nothing_twice():
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("ship", usage=True):
        with trace_mod.adopt(trace_mod.current()):
            _burn(0.02)
    tr.complete()
    ship = _spans_of(rec)["ship"]
    assert "threads" not in ship
    assert 0.02 <= ship["cpu_s"] <= 1.5 * ship["duration_s"]


def test_a_span_left_on_another_thread_records_no_usage():
    import threading

    rec = FlightRecorder()
    tr = rec.begin()
    sp = tr.span("ship", usage=True)
    sp.__enter__()
    _burn(0.01)
    t = threading.Thread(target=sp.__exit__, args=(None, None, None))
    t.start()
    t.join(10)
    assert not t.is_alive()
    tr.complete()
    ship = _spans_of(rec)["ship"]
    assert ship["duration_s"] >= 0.01
    assert "cpu_s" not in ship


def test_a_failing_usage_read_loses_the_reading_and_never_the_span(
        monkeypatch):
    rec = FlightRecorder()
    tr = rec.begin()
    monkeypatch.setattr(trace_mod, "time", _CountedClock(fail=True))
    with tr.span("drain", usage=True):
        _burn(0.005)
    with NULL_TRACE.span("ship", usage=True) as null_span:  # reads nothing
        pass
    assert trace_mod.time.reads == 1 and null_span.duration_s >= 0
    tr.complete()
    spans = _spans_of(rec)
    assert "cpu_s" not in spans["drain"]
    assert spans["drain"]["duration_s"] >= 0.005
    assert rec.stats["record_errors"] >= 1


@pytest.mark.chaos
def test_trace_record_chaos_costs_spans_and_never_the_window():
    """With every recorder entry point failing, the windows still ship
    and the per-stage CPU totals simply hold nothing."""
    faults.install(faults.FaultInjector.from_spec("trace.record:error"))
    rec = FlightRecorder()
    snaps = [_snap(seed=5) for _ in range(2)]
    from parca_agent_tpu.agent.writer import RemoteProfileWriter

    sink = RawSink()
    prof = CPUProfiler(
        source=ListSource(snaps),
        aggregator=DictAggregator(capacity=1 << 12),
        fallback_aggregator=CPUAggregator(),
        profile_writer=RemoteProfileWriter(sink), duration_s=0.0,
        fast_encode=True, encode_pipeline=True, trace_recorder=rec)
    for _ in snaps:
        assert prof.run_iteration()
        assert prof.last_error is None
        assert prof._pipeline.flush(30.0)
    assert prof._pipeline.close(30.0)
    faults.install(None)
    assert sink.n == 12  # two windows of six profiles, all shipped
    assert rec.stats["record_errors"] > 0
    assert rec.export_stage_cpu() == {}


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "inline"])
def test_only_the_stages_off_the_windows_path_carry_cpu(pipelined):
    """drain and ship ask for their threads' CPU; nothing from the end
    of drain to the end of encode does, child or stage."""
    rec, traces = _traced_windows(pipelined)
    totals = rec.export_stage_cpu()
    summed = {}
    for t in traces:
        for s in t["spans"]:
            if "cpu_s" in s:
                summed[s["stage"]] = summed.get(s["stage"], 0.0) + s["cpu_s"]
            assert "threads" not in s, s["stage"]  # close asks for none
    assert set(summed) <= {"drain", "ship"} and summed["ship"] > 0
    assert totals == pytest.approx(summed, abs=1e-5)


def test_a_streamed_windows_feed_threads_cpu_is_under_stream_feed():
    from span_scenarios import (
        run_windows,
        streamed_profiler,
        turnover_windows,
    )

    rec = FlightRecorder()
    snaps, _raw = turnover_windows(2, turnover=0.25)
    prof, _feeder, _agg, sink = streamed_profiler(snaps, recorder=rec)
    run_windows(prof, sink, 2)
    for t in rec.traces():
        by_stage = {s["stage"]: s for s in t["spans"]}
        assert t["meta"]["streamed"] == 1
        feed, drain = by_stage["stream_feed"], by_stage["drain"]
        # Ten drains, each fed on a `stream-feed` thread of its own.
        assert feed["threads"] == drain["threads"] == feed["n"] == 10
        assert 0 < feed["cpu_s"] <= drain["cpu_s"]
        assert {s["stage"] for s in t["spans"] if "cpu_s" in s} \
            <= {"drain", "stream_feed", "ship"}


def test_a_late_iteration_shows_on_the_next_windows_meta():
    import threading

    rec = FlightRecorder()
    prof = CPUProfiler(
        source=ListSource([_snap(seed=3) for _ in range(3)]),
        aggregator=CPUAggregator(), profile_writer=Collect(),
        duration_s=0.01, trace_recorder=rec)
    asked = []

    def oversleep(wait_s):
        asked.append(wait_s)
        time.sleep(wait_s + 0.05)
        return False

    prof._stop.wait = oversleep
    t = threading.Thread(target=prof.run)
    t.start()
    t.join(30)
    assert not t.is_alive() and prof.crashed is None
    metas = [tr["meta"] for tr in rec.traces()]
    assert len(metas) == 3
    assert "loop_overshoot_s" not in metas[0]  # nothing waited before it
    for m, wait_s in zip(metas[1:], asked):
        assert m["loop_wait_s"] == pytest.approx(wait_s, abs=1e-5)
        assert 0.05 <= m["loop_overshoot_s"] < 5.0
    # The window the source ended on was waited for too, and discarded.
    assert prof.metrics.loop_overshoot_seconds_total >= sum(
        m["loop_overshoot_s"] for m in metas[1:])
    from parca_agent_tpu.web import render_metrics

    line = next(ln for ln in render_metrics([prof]).splitlines() if ln.startswith(
        "parca_agent_profiler_loop_overshoot_seconds_total"))
    assert float(line.split()[-1]) >= 0.1


@pytest.mark.parametrize("name,label", [
    ("actor-profiler", "actor-profiler"),
    ("aggregate-device", "aggregate-device"),
    ("row-hash_3", "row-hash"),
    ("ThreadPoolExecutor-0_12", "ThreadPoolExecutor"),
    ("Thread-7 (channel_spin)", "channel_spin"),
    ("Thread-12", "Thread"),
    ("discovery-kubernetes", "discovery-kubernetes"),
    ("worker 4", "worker"),
    ("17", "thread"),
])
def test_a_threads_label_is_its_name_less_a_trailing_number(name, label):
    assert trace_mod.thread_label(name) == label
