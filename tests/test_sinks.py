"""Output-backend sinks: registry fan-out, pprof byte-identity, the
AutoFDO profdata emitter, and the series sink.

The contract under test (docs/sinks.md): the SinkRegistry fans each
shipped window out to N backends; pprof is primary and byte-identical
(sha256) to the pre-sink ship path on BOTH the pipelined and the
inline-fallback routes; secondary sinks are fail-open — an injected
``sink.emit`` fault costs that sink one window and never the pprof
ship (``windows_lost == 0``); the AutoFDO emitter accumulates
per-build-id leaf samples across windows in bounded memory, flushes
crash-only, and a restart adopts the flushed files without replaying
anything.
"""

from __future__ import annotations

import hashlib
import os
import threading

import numpy as np
import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.formats import (
    KERNEL_ADDR_START,
    STACK_SLOTS,
    MappingTable,
    WindowSnapshot,
)
from parca_agent_tpu.capture.replay import ReplaySource
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.pprof.window_encoder import WindowEncoder
from parca_agent_tpu.profiler.cpu import CPUProfiler
from parca_agent_tpu.profiler.encode_pipeline import EncodePipeline
from parca_agent_tpu.runtime.hotspots import RegistryView
from parca_agent_tpu.sinks import (
    AutoFDOSink,
    PprofSink,
    SeriesSink,
    SinkRegistry,
)
from parca_agent_tpu.sinks.base import SinkWindow
from parca_agent_tpu.utils import faults


def _snap(seed=7, n_pids=6, rows=200):
    return generate(SyntheticSpec(
        n_pids=n_pids, n_unique_stacks=rows, n_rows=rows,
        total_samples=rows * 4, mean_depth=8, kernel_fraction=0.25,
        seed=seed))


class Collect:
    def __init__(self):
        self.got = []

    def write(self, labels, blob):
        self.got.append((labels, bytes(blob)))

    def sha(self) -> str:
        h = hashlib.sha256()
        for _, b in self.got:
            h.update(b)
        return h.hexdigest()


class BoomSink:
    """A secondary sink that always fails — the fail-open probe."""

    name = "boom"

    def __init__(self):
        self.stats = {}

    def emit(self, win):
        raise RuntimeError("boom")

    def flush(self):
        pass

    def close(self):
        pass


def _run_pipeline(windows, registry=None, agg=None):
    """Drive N synthetic windows through a real EncodePipeline; returns
    (sha256-of-shipped-pprof-bytes, pipeline). With a registry, the ship
    hook is the registry fan-out (pprof primary bound to the hasher);
    without, the legacy direct ship."""
    agg = agg or DictAggregator(capacity=1 << 12)
    sha = hashlib.sha256()

    def hash_out(out):
        for _, b in out:
            sha.update(bytes(b))

    if registry is not None:
        registry.bind(ship=hash_out)
        ship = lambda out, prep: registry.emit_window(out, prep)  # noqa: E731
        pipe = EncodePipeline(
            WindowEncoder(agg), ship=ship,
            sink_capture=lambda prep: RegistryView(agg))
    else:
        pipe = EncodePipeline(WindowEncoder(agg),
                              ship=lambda out, prep: hash_out(out))
    for w in windows:
        counts = np.asarray(agg.window_counts(w))
        assert pipe.submit(counts, w.time_ns, w.window_ns,
                           w.period_ns) is not None
        assert pipe.flush(30)
    assert pipe.close()
    return sha.hexdigest(), pipe


# -- pprof byte-identity through the registry ---------------------------------


def test_pipelined_registry_pprof_sha256_matches_legacy(tmp_path):
    windows = [_snap(seed=s) for s in range(3)]
    legacy_sha, _ = _run_pipeline(windows)
    reg = SinkRegistry([PprofSink(), AutoFDOSink(str(tmp_path)),
                        SeriesSink()])
    sink_sha, pipe = _run_pipeline(windows, registry=reg)
    assert sink_sha == legacy_sha
    assert pipe.stats["windows_lost"] == 0
    m = reg.metrics()
    assert m["pprof"]["windows"] == 3
    assert m["autofdo"]["windows"] == 3 and m["autofdo"]["errors"] == 0
    assert m["series"]["windows"] == 3
    assert m["autofdo"]["samples"] > 0


def test_inline_fallback_registry_pprof_sha256_matches_legacy():
    """encode_pipeline=False forces the inline route: pprof ships
    through the classic path and the secondaries fan out on the
    profiler thread — same bytes as a sink-less run, and the series
    sink sees every window."""
    snap = _snap(seed=9)
    w_legacy = Collect()
    CPUProfiler(source=ReplaySource([snap, snap]),
                aggregator=DictAggregator(capacity=1 << 12),
                fallback_aggregator=CPUAggregator(),
                profile_writer=w_legacy, fast_encode=True,
                duration_s=0.01).run()

    series = SeriesSink()
    reg = SinkRegistry([PprofSink(), series])
    w_sink = Collect()
    p = CPUProfiler(source=ReplaySource([snap, snap]),
                    aggregator=DictAggregator(capacity=1 << 12),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w_sink, fast_encode=True,
                    duration_s=0.01, sinks=reg)
    p.run()
    assert p.crashed is None and p.last_error is None
    assert w_sink.sha() == w_legacy.sha()
    assert series.stats["windows"] == 2
    assert series.stats["samples"] == 2 * int(snap.total_samples())


def test_pipelined_profiler_with_sinks_loses_nothing(tmp_path):
    snap = _snap(seed=12)
    afdo = AutoFDOSink(str(tmp_path), flush_windows=1)
    reg = SinkRegistry([PprofSink(), afdo])
    w = Collect()
    p = CPUProfiler(source=ReplaySource([snap, snap]),
                    aggregator=DictAggregator(capacity=1 << 12),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True,
                    encode_pipeline=True, duration_s=0.1, sinks=reg)
    p.run()
    assert p.crashed is None and p.last_error is None
    assert p._pipeline.stats["windows_lost"] == 0
    assert afdo.stats["windows"] == 2
    assert len(os.listdir(tmp_path)) > 0
    m = reg.metrics()
    assert m["pprof"]["windows"] == 2 and m["pprof"]["errors"] == 0


# -- registry fail-open semantics ---------------------------------------------


def test_secondary_failure_never_touches_the_pprof_ship(tmp_path):
    windows = [_snap(seed=s) for s in range(2)]
    legacy_sha, _ = _run_pipeline(windows)
    reg = SinkRegistry([PprofSink(), BoomSink()])
    sink_sha, pipe = _run_pipeline(windows, registry=reg)
    assert sink_sha == legacy_sha
    assert pipe.stats["windows_lost"] == 0
    assert pipe.stats["ship_errors"] == 0
    assert not pipe.disabled
    m = reg.metrics()
    assert m["boom"]["errors"] == 2 and m["boom"]["windows"] == 0
    assert m["pprof"]["windows"] == 2


def test_primary_failure_still_fans_out_and_propagates():
    """A pprof writer outage is the pipeline's ship_error (pre-sink
    semantics, pipeline stays alive) — and the secondaries still get
    the window: a store outage must not starve the PGO loop."""
    snap = _snap(seed=3)
    agg = DictAggregator(capacity=1 << 12)
    series = SeriesSink()
    reg = SinkRegistry([PprofSink(), series])

    def bad_ship(out):
        raise OSError("store down")

    reg.bind(ship=bad_ship)
    pipe = EncodePipeline(
        WindowEncoder(agg),
        ship=lambda out, prep: reg.emit_window(out, prep),
        sink_capture=lambda prep: RegistryView(agg))
    counts = np.asarray(agg.window_counts(snap))
    assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns) is not None
    assert pipe.flush(30)
    assert pipe.close()
    assert pipe.stats["ship_errors"] == 1
    assert pipe.stats["windows_lost"] == 0
    assert not pipe.disabled
    m = reg.metrics()
    assert m["pprof"]["errors"] == 1
    assert series.stats["windows"] == 1  # fan-out survived the outage


def test_registry_requires_the_pprof_sink():
    with pytest.raises(ValueError):
        SinkRegistry([SeriesSink()])


def test_sink_capture_failure_counted_window_still_ships():
    snap = _snap(seed=4)
    agg = DictAggregator(capacity=1 << 12)
    afdo_like = SeriesSink()
    reg = SinkRegistry([PprofSink(), afdo_like])
    shipped = []
    reg.bind(ship=lambda out: shipped.append(len(out)))

    def bad_capture(prep):
        raise RuntimeError("capture boom")

    pipe = EncodePipeline(
        WindowEncoder(agg),
        ship=lambda out, prep: reg.emit_window(out, prep),
        sink_capture=bad_capture)
    counts = np.asarray(agg.window_counts(snap))
    assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns) is not None
    assert pipe.close()
    assert pipe.stats["sink_capture_errors"] == 1
    assert pipe.stats["windows_lost"] == 0
    assert shipped  # pprof shipped regardless
    # The series sink folded pids_live without a view; the frame-reading
    # autofdo sink would have counted windows_skipped instead — either
    # way the window was never lost.
    assert afdo_like.stats["windows"] == 1


# -- the AutoFDO emitter ------------------------------------------------------

_BID_APP = "aa" * 20
_BID_LIB = "bb" * 20


def _golden_snapshot(time_ns=1_000, counts=(5, 3, 2, 7)):
    """Two binaries + one kernel-leaf stack, fully deterministic: pid 1
    runs /bin/app (build-id aa..) mapped at 0x1000 and /lib/libfoo.so
    (bb..) at 0x100000; leaf offsets are addr - start (file-offset
    normalization, offsets 0)."""
    mt = MappingTable(
        pids=np.array([1, 1], np.int32),
        starts=np.array([0x1000, 0x100000], np.uint64),
        ends=np.array([0x2000, 0x200000], np.uint64),
        offsets=np.array([0, 0], np.uint64),
        objs=np.array([0, 1], np.int32),
        obj_paths=("/bin/app", "/lib/libfoo.so"),
        obj_buildids=(_BID_APP, _BID_LIB),
    )
    stacks = np.zeros((4, STACK_SLOTS), np.uint64)
    stacks[0, :2] = [0x1100, 0x1200]        # leaf app+0x100
    stacks[1, :1] = [0x1180]                # leaf app+0x180
    stacks[2, :2] = [0x100100, 0x1200]      # leaf libfoo+0x100
    stacks[3, :1] = [KERNEL_ADDR_START + 0x10]  # kernel leaf
    return WindowSnapshot(
        pids=np.array([1, 1, 1, 1], np.int32),
        tids=np.array([1, 1, 1, 1], np.int32),
        counts=np.array(counts, np.int64),
        user_len=np.array([2, 1, 2, 0], np.int32),
        kernel_len=np.array([0, 0, 0, 1], np.int32),
        stacks=stacks,
        mappings=mt,
        time_ns=time_ns,
    )


def _emit_window(sink, snap, agg=None):
    """One window through the real prepare path into a sink."""
    agg = agg or DictAggregator(capacity=1 << 10)
    enc = WindowEncoder(agg)
    counts = np.asarray(agg.window_counts(snap))
    prep = enc.prepare(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns)
    win = SinkWindow([], prep, view=RegistryView(agg))
    sink.emit(win)
    return agg


def test_autofdo_golden_profdata_text(tmp_path):
    sink = AutoFDOSink(str(tmp_path), flush_windows=1)
    _emit_window(sink, _golden_snapshot())
    app = (tmp_path / f"{_BID_APP}.afdo.txt").read_text()
    lib = (tmp_path / f"{_BID_LIB}.afdo.txt").read_text()
    assert app == "app:8:8\n 0x100: 5\n 0x180: 3\n"
    assert lib == "libfoo.so:2:2\n 0x100: 2\n"
    assert sink.stats["samples"] == 10
    assert sink.stats["samples_kernel"] == 7   # counted, not attributed
    assert sink.stats["binaries"] == 2


def test_autofdo_buildid_keying_splits_binaries(tmp_path):
    sink = AutoFDOSink(str(tmp_path), flush_windows=1)
    _emit_window(sink, _golden_snapshot())
    names = sorted(os.listdir(tmp_path))
    assert names == [f"{_BID_APP}.afdo.txt", f"{_BID_LIB}.afdo.txt"]


def test_autofdo_accumulates_across_windows_on_the_flush_cadence(tmp_path):
    sink = AutoFDOSink(str(tmp_path), flush_windows=2)
    agg = _emit_window(sink, _golden_snapshot(time_ns=1_000))
    assert sink.stats["flushes"] == 0
    assert os.listdir(tmp_path) == []          # cadence not reached
    _emit_window(sink, _golden_snapshot(time_ns=2_000), agg=agg)
    assert sink.stats["flushes"] == 1
    app = (tmp_path / f"{_BID_APP}.afdo.txt").read_text()
    assert app == "app:16:16\n 0x100: 10\n 0x180: 6\n"  # 2x accumulated


def test_autofdo_restart_adopts_without_replay(tmp_path):
    sink = AutoFDOSink(str(tmp_path), flush_windows=1)
    _emit_window(sink, _golden_snapshot())
    before = (tmp_path / f"{_BID_APP}.afdo.txt").read_bytes()

    # Restart: a fresh sink over the same directory adopts the flushed
    # totals; flushing with NO new windows must rewrite nothing (no
    # dirty state — adoption is not a replay)...
    sink2 = AutoFDOSink(str(tmp_path), flush_windows=1)
    assert sink2.stats["files_adopted"] == 2
    sink2.flush()
    assert (tmp_path / f"{_BID_APP}.afdo.txt").read_bytes() == before
    assert sink2.stats["flushes"] == 0  # nothing was dirty

    # ...and new windows accumulate ON TOP of the adopted totals,
    # exactly once.
    _emit_window(sink2, _golden_snapshot(time_ns=9_000))
    app = (tmp_path / f"{_BID_APP}.afdo.txt").read_text()
    assert app == "app:16:16\n 0x100: 10\n 0x180: 6\n"


def test_autofdo_corrupt_file_adoption_counted_and_overwritten(tmp_path):
    sink = AutoFDOSink(str(tmp_path), flush_windows=1)
    _emit_window(sink, _golden_snapshot())
    victim = tmp_path / f"{_BID_APP}.afdo.txt"
    victim.write_bytes(b"not a profile\xff")
    sink2 = AutoFDOSink(str(tmp_path), flush_windows=1)
    assert sink2.stats["adopt_errors"] == 1
    assert sink2.stats["files_adopted"] == 1   # the intact one
    # The corrupt key starts cold; the next flush overwrites it whole.
    _emit_window(sink2, _golden_snapshot(time_ns=9_000))
    assert victim.read_text() == "app:8:8\n 0x100: 5\n 0x180: 3\n"


def test_autofdo_bounded_memory_drops_are_counted(tmp_path):
    sink = AutoFDOSink(str(tmp_path), flush_windows=100,
                       max_binaries=1, max_offsets=1)
    _emit_window(sink, _golden_snapshot())
    # One binary admitted, one offset kept; everything else dropped.
    assert sink.stats["binaries"] == 1
    assert sink.stats["samples_dropped"] > 0
    assert (sink.stats["samples"] + sink.stats["samples_dropped"]
            + sink.stats["samples_kernel"]
            + sink.stats["samples_unmapped"]) == 17


def test_autofdo_flush_cadence_ticks_on_skipped_windows(tmp_path):
    """The flush clock ticks on EVERY emit — a workload that goes idle
    (or a persistently failing view capture) must not let dirty state
    out-wait the flush_windows crash-loss bound."""
    sink = AutoFDOSink(str(tmp_path), flush_windows=2)
    _emit_window(sink, _golden_snapshot())          # dirty, no flush yet
    assert os.listdir(tmp_path) == []
    agg = DictAggregator(capacity=1 << 10)
    snap = _golden_snapshot(time_ns=2_000)
    enc = WindowEncoder(agg)
    counts = np.asarray(agg.window_counts(snap))
    prep = enc.prepare(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns)
    sink.emit(SinkWindow([], prep, view=None))      # skipped window
    assert sink.stats["windows_skipped"] == 1
    # ...but it still advanced the cadence: the dirty state flushed.
    assert (tmp_path / f"{_BID_APP}.afdo.txt").read_text() \
        == "app:8:8\n 0x100: 5\n 0x180: 3\n"


def test_autofdo_skips_windows_without_a_view_counted(tmp_path):
    sink = AutoFDOSink(str(tmp_path), flush_windows=1)
    agg = DictAggregator(capacity=1 << 10)
    snap = _golden_snapshot()
    enc = WindowEncoder(agg)
    counts = np.asarray(agg.window_counts(snap))
    prep = enc.prepare(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns)
    sink.emit(SinkWindow([], prep, view=None))
    assert sink.stats["windows_skipped"] == 1
    assert sink.stats["samples"] == 0


# -- the series sink ----------------------------------------------------------


def test_series_accumulates_otlp_style_per_label_set():
    labels = {1: {"pod": "a", "__internal": "x"}, 2: {"pod": "b"}}
    sink = SeriesSink(labels_for=lambda pid: labels.get(pid))
    snap = _golden_snapshot(time_ns=1_000_000_000)
    _emit_window(sink, snap)
    pts = {tuple(sorted(p["labels"].items())): p for p in sink.series()}
    pt = pts[(("pod", "a"),)]
    assert pt["value"] == int(snap.total_samples())
    assert pt["start_time_ns"] == snap.time_ns
    assert pt["time_ns"] == snap.time_ns + snap.window_ns
    assert pt["windows"] == 1
    # Cumulative across windows: value grows, start_time_ns is pinned.
    snap2 = _golden_snapshot(time_ns=11_000_000_000)
    agg = DictAggregator(capacity=1 << 10)
    _emit_window(sink, snap2, agg=agg)
    pt = {tuple(sorted(p["labels"].items())): p
          for p in sink.series()}[(("pod", "a"),)]
    assert pt["value"] == 2 * int(snap.total_samples())
    assert pt["start_time_ns"] == snap.time_ns
    assert pt["windows"] == 2


def test_series_eviction_is_bounded_and_counted():
    sink = SeriesSink(max_sets=2,
                      labels_for=lambda pid: {"pid": str(pid)})
    mt = MappingTable(
        pids=np.array([1, 2, 3], np.int32),
        starts=np.array([0x1000, 0x1000, 0x1000], np.uint64),
        ends=np.array([0x2000, 0x2000, 0x2000], np.uint64),
        offsets=np.zeros(3, np.uint64),
        objs=np.zeros(3, np.int32),
        obj_paths=("/bin/app",), obj_buildids=(_BID_APP,))
    stacks = np.zeros((3, STACK_SLOTS), np.uint64)
    stacks[:, 0] = 0x1100
    snap = WindowSnapshot(
        pids=np.array([1, 2, 3], np.int32),
        tids=np.array([1, 2, 3], np.int32),
        counts=np.array([1, 2, 3], np.int64),
        user_len=np.ones(3, np.int32),
        kernel_len=np.zeros(3, np.int32),
        stacks=stacks, mappings=mt, time_ns=1_000)
    _emit_window(sink, snap)
    assert sink.stats["sets"] == 2
    assert sink.stats["sets_evicted"] == 1


def test_series_dropped_target_counted():
    sink = SeriesSink(labels_for=lambda pid: None)  # relabeling drops all
    _emit_window(sink, _golden_snapshot())
    assert sink.stats["targets_dropped"] == 1  # pid 1, once per window
    assert sink.series() == []


# -- chaos drills (make chaos; palint chaos-site coverage) --------------------


@pytest.mark.chaos
def test_chaos_injected_sink_emit_fault_loses_no_pprof_window(tmp_path):
    """The SITES drill for ``sink.emit``: an injected fault in the
    autofdo backend's emit is counted as that sink's error; the pprof
    ship is untouched and ``windows_lost == 0``."""
    faults.install(faults.FaultInjector.from_spec(
        "sink.emit:error:count=1"))
    try:
        windows = [_snap(seed=s) for s in range(3)]
        legacy_sha, _ = _run_pipeline(windows)
        afdo = AutoFDOSink(str(tmp_path), flush_windows=1)
        reg = SinkRegistry([PprofSink(), afdo])
        sink_sha, pipe = _run_pipeline(windows, registry=reg)
        assert sink_sha == legacy_sha          # pprof ship unaffected
        assert pipe.stats["windows_lost"] == 0
        assert pipe.stats["ship_errors"] == 0
        assert not pipe.disabled
        m = reg.metrics()
        assert m["autofdo"]["errors"] == 1     # counted fault
        assert m["autofdo"]["windows"] == 2    # the other two folded
        assert m["pprof"]["windows"] == 3
    finally:
        faults.install(None)


@pytest.mark.chaos
def test_chaos_injected_sink_flush_disk_full_retries(tmp_path):
    """The SITES drill for ``sink.flush``: an injected disk-full costs
    one flush attempt (counted, the file stays dirty); the next flush
    lands the complete profile — crash-only, never torn."""
    faults.install(faults.FaultInjector.from_spec(
        "sink.flush:disk_full:count=1"))
    try:
        sink = AutoFDOSink(str(tmp_path), flush_windows=100)
        _emit_window(sink, _golden_snapshot())
        with pytest.raises(OSError):
            sink.flush()
        assert sink.stats["flush_errors"] >= 1
        assert not os.path.exists(tmp_path / f"{_BID_APP}.afdo.txt") \
            or (tmp_path / f"{_BID_APP}.afdo.txt").read_text()  # never torn
        sink.flush()                           # injector exhausted
        assert (tmp_path / f"{_BID_APP}.afdo.txt").read_text() \
            == "app:8:8\n 0x100: 5\n 0x180: 3\n"
    finally:
        faults.install(None)


# -- observability surfaces ---------------------------------------------------


def test_metrics_and_healthz_surface_per_sink_stats(tmp_path):
    from parca_agent_tpu.web import render_metrics

    afdo = AutoFDOSink(str(tmp_path), flush_windows=1)
    series = SeriesSink(labels_for=lambda pid: {"pod": "a"})
    reg = SinkRegistry([PprofSink(), afdo, series])
    windows = [_snap(seed=1)]
    _, _ = _run_pipeline(windows, registry=reg)
    text = render_metrics([], sinks=reg)
    assert '# TYPE parca_agent_sink_windows_total counter' in text
    assert 'parca_agent_sink_windows_total{sink="autofdo"} 1' in text
    assert 'parca_agent_sink_errors_total{sink="pprof"} 0' in text
    assert 'parca_agent_sink_bytes_total{sink="autofdo"}' in text
    assert 'parca_agent_sink_series_samples_total{pod="a"}' in text
    assert 'parca_agent_sink_windows_skipped_total 0' in text
    snap = reg.snapshot()
    assert snap["sinks"]["pprof"]["windows"] == 1
    assert snap["sinks"]["autofdo"]["errors"] == 0
    assert "bytes" in snap["sinks"]["autofdo"]


def test_scalar_path_windows_counted_as_skipped():
    """A backpressure scalar fallback ships no prepared window: the
    registry counts the sink coverage gap."""
    import dataclasses

    # A 50 ms window: the hand-off waits that long for the busy worker
    # (the gate below holds it longer) before it takes the scalar way.
    snap = dataclasses.replace(_snap(seed=10), window_ns=50_000_000)
    series = SeriesSink()
    reg = SinkRegistry([PprofSink(), series])
    w = Collect()
    p = CPUProfiler(source=ReplaySource([snap, snap, snap]),
                    aggregator=DictAggregator(capacity=1 << 12),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True,
                    encode_pipeline=True, duration_s=0.01, sinks=reg)
    enc = p._encoder
    gate = threading.Event()
    real = enc.encode_prepared

    def slow(prep, views=False):
        assert gate.wait(10)
        return real(prep, views=views)

    assert p.run_iteration()      # window 1: the worker's cold first,
    assert p._pipeline.flush(10)  # which the next window would wait out
    enc.encode_prepared = slow
    assert p.run_iteration()      # window 2 pipelined, worker blocked
    assert p.run_iteration()      # window 3: backpressure -> scalar
    gate.set()
    assert p._pipeline.close()
    assert p.metrics.encode_backpressure_total == 1
    m = reg.metrics()
    assert m["_registry"]["windows_skipped"] == 1
    assert series.stats["windows"] == 2  # the pipelined windows folded
