"""Encode pipeline: overlap, backpressure, flush-on-shutdown, failure
recovery, and the amortized statics prebuild's byte identity.

The contract under test (profiler/encode_pipeline.py): window close hands
the aggregated counts to a dedicated encoder thread; capture of window
N+1 overlaps encode/ship of window N; a busy worker at the next close
forces the observable scalar fallback; a worker exception disables the
pipeline without losing the window; shutdown flushes the in-flight
window; and the drain-tick statics prebuild produces byte-identical
pprof output vs the synchronous path.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np
import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.replay import ReplaySource
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.pprof.builder import parse_pprof
from parca_agent_tpu.pprof.window_encoder import WindowEncoder
from parca_agent_tpu.profiler.cpu import CPUProfiler
from parca_agent_tpu.profiler.encode_pipeline import EncodePipeline
from streaming_sources import CacheSource


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


def _mass(got):
    return sum(sum(v[0] for _, v, _ in parse_pprof(b).samples)
               for _, b in got)


# -- pipeline unit behavior ---------------------------------------------------


def test_pipeline_ships_bytes_identical_to_sync_encode():
    snap = _snap(seed=1)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))

    sync = WindowEncoder(agg).encode(
        counts, snap.time_ns, snap.window_ns, snap.period_ns)

    shipped = []
    pipe = EncodePipeline(WindowEncoder(agg),
                          ship=lambda out, prep: shipped.extend(
                              (pid, bytes(b)) for pid, b in out))
    assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns) is not None
    assert pipe.close()
    assert shipped == [(pid, bytes(b)) for pid, b in sync]


def test_pipeline_overlap_and_backpressure():
    """While the worker encodes window N, the submitting thread returns
    immediately (overlap); a second close during that encode is refused
    and counted — the backpressure contract."""
    snap = _snap(seed=2)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))

    enc = WindowEncoder(agg)
    gate = threading.Event()
    entered = threading.Event()
    real = enc.encode_prepared

    def slow_encode(prep, views=False):
        entered.set()
        assert gate.wait(10)
        return real(prep, views=views)

    shipped = []
    pipe = EncodePipeline(enc, ship=lambda out, prep: shipped.append(out))
    # The worker's first window (the cold build) is waited out by the
    # window behind it; the contract below is the steady one.
    assert pipe.submit(counts, snap.time_ns - 1, snap.window_ns,
                       snap.period_ns) is not None
    assert pipe.flush(10)
    enc.encode_prepared = slow_encode
    t0 = time.perf_counter()
    assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns) is not None
    handoff = time.perf_counter() - t0
    assert entered.wait(10)
    assert handoff < 5.0          # submit did not wait for the encode
    assert pipe.busy
    # Next window closes while the worker is still busy and stays busy
    # past the window's own length (50 ms here): refused, counted.
    assert pipe.submit(counts, snap.time_ns + 1, 50_000_000,
                       snap.period_ns) is None
    assert pipe.stats["backpressure_fallbacks"] == 1
    assert pipe.stats["handoff_waits"] == 0
    gate.set()
    assert pipe.flush(10)
    assert len(shipped) == 2
    assert pipe.stats["windows_pipelined"] == 2
    assert pipe.close()


def _busy_pipeline(snap, agg, counts, hold_s, cold=False):
    """A pipeline whose worker is inside an encode that lasts
    ``hold_s`` (its first window if ``cold``, else its second), and the
    recorder its windows trace into."""
    from parca_agent_tpu.runtime.trace import FlightRecorder

    enc = WindowEncoder(agg)
    entered = threading.Event()
    real = enc.encode_prepared
    shipped = []
    pipe = EncodePipeline(enc, ship=lambda out, prep: shipped.append(out))
    if not cold:
        assert pipe.submit(counts, snap.time_ns - 1, snap.window_ns,
                           snap.period_ns) is not None
        assert pipe.flush(10)
        shipped.clear()

    def slow_encode(prep, views=False):
        entered.set()
        time.sleep(hold_s)
        return real(prep, views=views)

    enc.encode_prepared = slow_encode
    assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns) is not None
    assert entered.wait(10)
    enc.encode_prepared = real    # the window behind encodes at once
    return pipe, shipped, FlightRecorder(ring=8)


def test_submit_waits_for_a_worker_that_is_a_little_late():
    """A worker still busy for 50 ms at the next close: the window waits
    for it inside its handoff_wait span and is handed off; nothing goes
    the scalar way."""
    snap = _snap(seed=2)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))
    pipe, shipped, rec = _busy_pipeline(snap, agg, counts, hold_s=0.05)
    tr = rec.begin(snap.time_ns + 1)
    assert pipe.submit(counts, snap.time_ns + 1, snap.window_ns,
                       snap.period_ns, trace=tr) is not None
    assert pipe.stats["backpressure_fallbacks"] == 0
    assert pipe.stats["handoff_waits"] == 1
    assert pipe.close()
    assert len(shipped) == 2 and pipe.stats["windows_pipelined"] == 3
    d = rec.traces()[-1]
    wait = next(s for s in d["spans"] if s["stage"] == "handoff_wait")
    assert 0.02 < wait["duration_s"] < 5.0      # the wait is in the span
    assert d["meta"]["handoff_waited"] is True
    assert d["meta"]["encode"] == "patch"       # the template stood


def test_submit_gives_up_past_the_windows_own_length():
    """Busy past the bound (the window's duration_ns): today's refusal,
    counted as before, after no more than about that long."""
    snap = _snap(seed=2)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))
    pipe, shipped, _rec = _busy_pipeline(snap, agg, counts, hold_s=0.6)
    t0 = time.perf_counter()
    assert pipe.submit(counts, snap.time_ns + 1, 50_000_000,
                       snap.period_ns) is None
    assert 0.04 < time.perf_counter() - t0 < 0.5
    assert pipe.stats["backpressure_fallbacks"] == 1
    assert pipe.stats["handoff_waits"] == 0
    assert pipe.close() and len(shipped) == 1


def test_second_window_waits_out_the_workers_cold_first_window():
    """The worker's first window lays everything out and is no measure
    of a window: the window behind it waits past its own length (50 ms
    here against an encode of 0.3 s) and is handed off; the one after
    is held to its own length again."""
    snap = _snap(seed=2)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))
    pipe, shipped, _rec = _busy_pipeline(snap, agg, counts, hold_s=0.3,
                                         cold=True)
    assert pipe.submit(counts, snap.time_ns + 1, 50_000_000,
                       snap.period_ns) is not None
    assert pipe.stats["backpressure_fallbacks"] == 0
    assert pipe.stats["handoff_waits"] == 1
    assert pipe.close() and len(shipped) == 2


@pytest.mark.parametrize("how", ["disabled", "stopping"])
def test_submit_returns_at_once_when_disabled_or_stopping(how):
    snap = _snap(seed=2)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))
    pipe, _shipped, _rec = _busy_pipeline(snap, agg, counts, hold_s=0.4)
    if how == "disabled":
        pipe.disabled = True
    else:
        pipe._stopping = True
    t0 = time.perf_counter()
    assert pipe.submit(counts, snap.time_ns + 1, snap.window_ns,
                       snap.period_ns) is None
    assert time.perf_counter() - t0 < 0.2       # no wait, no count
    assert pipe.stats["backpressure_fallbacks"] == 0
    pipe._stopping = False
    pipe.disabled = False
    assert pipe.close()


def test_pipeline_flush_on_shutdown_ships_inflight_window():
    snap = _snap(seed=3)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))
    enc = WindowEncoder(agg)
    real = enc.encode_prepared
    enc.encode_prepared = lambda prep, views=False: (
        time.sleep(0.3), real(prep, views=views))[1]
    shipped = []
    pipe = EncodePipeline(enc, ship=lambda out, prep: shipped.append(out))
    assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns) is not None
    assert pipe.close()           # flushes the in-flight window
    assert len(shipped) == 1


def test_pipeline_worker_exception_disables_without_losing_window():
    snap = _snap(seed=4)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))
    enc = WindowEncoder(agg)
    enc.encode_prepared = lambda prep, views=False: (_ for _ in ()).throw(
        RuntimeError("encoder bug"))
    recovered = []
    pipe = EncodePipeline(enc, ship=lambda out, prep: None)
    assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns,
                       fallback=lambda: recovered.append(1)) is not None
    assert pipe.quiesce(10)       # failure handling (incl. fallback) done
    assert pipe.disabled
    assert recovered == [1]       # the window shipped via the fallback
    assert pipe.stats["encoder_exceptions"] == 1
    assert pipe.stats["windows_lost"] == 0
    # Disabled pipeline refuses further windows (profiler goes inline).
    assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns) is None


def test_pipeline_ship_error_does_not_disable_or_reship():
    """A writer failure during ship is NOT an encoder failure: no
    fallback re-ship (profiles already written would duplicate), no
    pipeline disable, no encoder reset — log + count, carry on."""
    snap = _snap(seed=14)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))
    boom = {"on": True}
    shipped = []

    def ship(out, prep):
        if boom["on"]:
            raise OSError("disk full")
        shipped.append(out)

    recovered = []
    pipe = EncodePipeline(WindowEncoder(agg), ship=ship)
    assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns,
                       fallback=lambda: recovered.append(1)) is not None
    assert pipe.quiesce(10)
    assert not pipe.disabled
    assert pipe.stats["ship_errors"] == 1
    assert recovered == []        # no duplicate re-ship via the fallback
    boom["on"] = False
    assert pipe.submit(counts, snap.time_ns + 1, snap.window_ns,
                       snap.period_ns) is not None
    assert pipe.close()
    assert len(shipped) == 1      # pipeline still alive and shipping


def test_pipeline_prebuild_runs_on_worker_and_yields_to_handoff():
    snap = _snap(seed=5, n_pids=10, rows=400)
    agg = DictAggregator(capacity=1 << 13)
    counts = np.asarray(agg.window_counts(snap))
    enc = WindowEncoder(agg)
    shipped = []
    pipe = EncodePipeline(enc, ship=lambda out, prep: shipped.append(out))
    for _ in range(3):            # drain ticks
        pipe.request_prebuild(snap.period_ns, budget_s=0.05)
    assert pipe.quiesce(10)
    assert pipe.stats["prebuilds"] >= 1
    assert enc.statics_backlog(snap.period_ns) == 0
    # A window submits cleanly right after (and through) prebuild traffic.
    pipe.request_prebuild(snap.period_ns, budget_s=0.05)
    assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                       snap.period_ns) is not None
    assert pipe.close()
    assert len(shipped) == 1


# -- statics prebuild byte identity ------------------------------------------


def test_drain_tick_prebuild_byte_identical_to_sync_path():
    """Statics built incrementally across budgeted drain-tick passes must
    yield byte-identical pprof output vs an encoder that builds them all
    inside the encode — the regression bar for the amortization."""
    snap = _snap(seed=6, n_pids=12, rows=500)
    agg = DictAggregator(capacity=1 << 13)
    counts = np.asarray(agg.window_counts(snap))

    enc_amortized = WindowEncoder(agg)
    ticks = 0
    while enc_amortized.statics_backlog(snap.period_ns) and ticks < 500:
        # Tiny budget: one batch per tick, forcing many partial passes.
        enc_amortized.build_statics(snap.period_ns, budget_s=1e-9, chunk=2,
                                    loc_chunk=64)
        ticks += 1
    assert ticks > 1              # the budget actually split the build
    out_a = enc_amortized.encode(counts, snap.time_ns, snap.window_ns,
                                 snap.period_ns)

    out_b = WindowEncoder(agg).encode(counts, snap.time_ns, snap.window_ns,
                                      snap.period_ns)
    assert [(p, bytes(b)) for p, b in out_a] \
        == [(p, bytes(b)) for p, b in out_b]


def test_prebuild_stop_event_aborts_between_batches():
    snap = _snap(seed=7, n_pids=10, rows=400)
    agg = DictAggregator(capacity=1 << 13)
    agg.window_counts(snap)
    enc = WindowEncoder(agg)
    stop = threading.Event()
    stop.set()
    done = enc.build_statics(snap.period_ns, chunk=2, loc_chunk=64,
                             stop=stop)
    assert done < len(agg._pids)  # parked early, work left behind
    assert enc.statics_backlog(snap.period_ns) > 0


def test_encoder_dead_row_stats():
    snap = _snap(seed=8)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))
    enc = WindowEncoder(agg)
    enc.encode(counts, snap.time_ns, snap.window_ns, snap.period_ns)
    assert enc.stats["dead_rows"] == 0
    c2 = counts.copy()
    c2[: len(c2) // 4] = 0        # a quarter of the stacks go cold
    enc.encode(c2, snap.time_ns + 1, snap.window_ns, snap.period_ns)
    assert enc.stats["windows_encoded"] == 2
    assert enc.stats["dead_rows"] > 0
    assert 0.0 < enc.stats["dead_row_fraction"] <= 0.5
    assert enc.stats["template_rows"] == enc._tmpl.n_rows


# -- profiler integration -----------------------------------------------------


def test_profiler_pipeline_run_matches_classic_and_flushes():
    snap = _snap(seed=9)
    w = Collect()
    # duration_s bounds the worker's slack before the next close: 0.01
    # flaked under loaded hosts (window 2 hit backpressure and scalar-
    # shipped, breaking the windows_pipelined == 2 assertion below).
    p = CPUProfiler(source=ReplaySource([snap, snap]),
                    aggregator=DictAggregator(capacity=1 << 12),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True,
                    encode_pipeline=True, duration_s=0.1)
    p.run()                       # exhausts the source, flushes, closes
    assert p.crashed is None and p.last_error is None
    assert p._pipeline.stats["windows_pipelined"] == 2

    w2 = Collect()
    CPUProfiler(source=ReplaySource([snap]), aggregator=CPUAggregator(),
                profile_writer=w2).run_iteration()
    classic = {l["pid"]: sum(v[0] for _, v, _ in parse_pprof(b).samples)
               for l, b in w2.got}
    piped = {l["pid"]: sum(v[0] for _, v, _ in parse_pprof(b).samples)
             for l, b in w.got[: len(classic)]}
    assert piped == classic
    assert p.metrics.profiles_written == len(w.got)


def test_profiler_backpressure_scalar_fallback_is_counted():
    """Worker still encoding window N at window N+1's close: N+1 ships
    inline through the scalar fallback, the counter increments, and no
    mass is lost."""
    # A 50 ms window bounds the hand-off's wait for the blocked worker.
    snap = dataclasses.replace(_snap(seed=10), window_ns=50_000_000)
    w = Collect()
    p = CPUProfiler(source=ReplaySource([snap, snap, snap]),
                    aggregator=DictAggregator(capacity=1 << 12),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True,
                    encode_pipeline=True, duration_s=0.01)
    enc = p._encoder
    gate = threading.Event()
    real = enc.encode_prepared

    def slow(prep, views=False):
        assert gate.wait(10)
        return real(prep, views=views)

    assert p.run_iteration()      # window 1: the worker's cold first
    assert p._pipeline.flush(10)
    enc.encode_prepared = slow
    assert p.run_iteration()      # window 2 pipelined, worker blocked
    assert p.run_iteration()      # window 3: backpressure -> scalar
    assert p.last_error is None
    assert p.metrics.encode_backpressure_total == 1
    assert _mass(w.got) == 2 * snap.total_samples()  # 1, and 3 by scalar
    gate.set()
    assert p._pipeline.close()
    assert _mass(w.got) == 3 * snap.total_samples()


def test_profiler_pipeline_failure_falls_back_then_inline():
    """An encoder exception on the worker ships that window via the
    scalar fallback, disables the pipeline, and later windows ride the
    inline path — nothing is lost."""
    snap = _snap(seed=11)
    w = Collect()
    p = CPUProfiler(source=ReplaySource([snap, snap]),
                    aggregator=DictAggregator(capacity=1 << 12),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True,
                    encode_pipeline=True, duration_s=0.01)
    boom = {"on": True}
    real = p._encoder.encode_prepared

    def maybe_boom(prep, views=False):
        if boom["on"]:
            raise RuntimeError("encoder bug")
        return real(prep, views=views)

    p._encoder.encode_prepared = maybe_boom
    assert p.run_iteration()
    assert p._pipeline.quiesce(10)  # failure handling (incl. fallback) done
    assert p._pipeline.disabled
    assert _mass(w.got) == snap.total_samples()   # fallback shipped it
    boom["on"] = False
    assert p.run_iteration()      # inline path now
    assert p.last_error is None
    assert _mass(w.got) == 2 * snap.total_samples()


def test_inline_soft_deadline_forces_scalar_fallback():
    """No pipeline: an encode slower than encode_deadline_s is abandoned
    (it keeps running on a daemon thread) and the window ships via the
    scalar path; while the abandoned encode is still running the next
    window also scalar-ships rather than touching the encoder."""
    snap = _snap(seed=12)
    w = Collect()
    p = CPUProfiler(source=ReplaySource([snap, snap, snap]),
                    aggregator=DictAggregator(capacity=1 << 12),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True,
                    encode_deadline_s=0.1, duration_s=0.01)
    release = threading.Event()
    real = p._encoder.encode
    calls = {"n": 0}

    def slow(*a, **kw):
        calls["n"] += 1
        assert release.wait(10)
        return real(*a, **kw)

    p._encoder.encode = slow
    assert p.run_iteration()      # deadline blown -> scalar fallback
    assert p.last_error is None
    assert p.metrics.encode_deadline_hits_total == 1
    assert p.metrics.last_encode_duration_s >= 0.1
    assert _mass(w.got) == snap.total_samples()
    assert p.run_iteration()      # abandoned encode still in flight
    assert p.last_error is None
    assert calls["n"] == 1        # encoder NOT touched while abandoned
    assert _mass(w.got) == 2 * snap.total_samples()
    release.set()
    for _ in range(100):
        if p._encode_inflight is None or p._encode_inflight.is_set():
            break
        time.sleep(0.02)
    assert p.run_iteration()      # encoder healthy again: fast path
    assert p.last_error is None
    assert calls["n"] == 2
    assert _mass(w.got) == 3 * snap.total_samples()


def test_abandoned_encode_failure_resets_encoder_before_reuse():
    """An abandoned inline-deadline encode that later RAISES leaves the
    template possibly half-mutated: the next window must reset the
    encoder's mirrors before touching it again (the inline twin of the
    pipeline's _fail_window reset)."""
    snap = _snap(seed=15)
    w = Collect()
    p = CPUProfiler(source=ReplaySource([snap, snap]),
                    aggregator=DictAggregator(capacity=1 << 12),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True,
                    encode_deadline_s=0.1, duration_s=0.01)
    release = threading.Event()
    boom = {"on": True}
    real_encode = p._encoder.encode
    resets = []
    real_reset = p._encoder.reset
    p._encoder.reset = lambda: (resets.append(1), real_reset())[1]

    def slow_then_boom(*a, **kw):
        if boom["on"]:
            assert release.wait(10)
            raise RuntimeError("died after abandonment")
        return real_encode(*a, **kw)

    p._encoder.encode = slow_then_boom
    assert p.run_iteration()      # deadline blown -> scalar fallback
    assert p.metrics.encode_deadline_hits_total == 1
    boom["on"] = False
    release.set()
    for _ in range(100):
        if p._encode_inflight.is_set():
            break
        time.sleep(0.02)
    assert p.run_iteration()      # gate sees the failure, resets, encodes
    assert p.last_error is None
    assert resets == [1]
    assert _mass(w.got) == 2 * snap.total_samples()


def test_pipeline_requires_fast_encode():
    with pytest.raises(ValueError):
        CPUProfiler(source=None, aggregator=CPUAggregator(),
                    encode_pipeline=True)


def test_streaming_feeder_routes_prebuild_through_pipeline():
    """With the pipeline attached, the feeder's drain tick only ENQUEUES
    the statics prebuild (the polling thread stays free); the budgeted
    build lands on the worker thread."""
    from parca_agent_tpu.profiler.streaming import StreamingWindowFeeder

    class FakeMaps:
        def executable_mappings(self, pid):
            return []

    class FakeObjs:
        def build_ids(self, per_pid):
            return {}

    snap = _snap(seed=13, n_pids=3, rows=60)
    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()),
                                   prebuild_period_ns=snap.period_ns
                                   or 10_000_000)
    enc = WindowEncoder(agg)
    calls = []

    def request_prebuild(period_ns, budget_s=0.25):
        calls.append((period_ns, budget_s, threading.get_ident()))

    feeder.attach_encoder(enc, prebuild=request_prebuild)
    n = len(snap)
    mid = n // 2
    feeder.on_drain((snap.pids[:mid], snap.tids[:mid], snap.user_len[:mid],
                     snap.kernel_len[:mid], snap.stacks[:mid],
                     snap.counts[:mid]))
    assert feeder.stats["drains_fed"] == 1
    assert feeder.stats["statics_prebuilt"] == 1
    assert len(calls) == 1        # enqueued, not built inline
    # Feed registration is deferred by one drain (the sub-RTT close's
    # async dispatch settles the previous feed's miss check at the NEXT
    # feed, docs/perf.md "sub-RTT close"): the second drain makes the
    # first drain's pids visible to the backlog.
    feeder.on_drain((snap.pids[mid:n], snap.tids[mid:n],
                     snap.user_len[mid:n], snap.kernel_len[mid:n],
                     snap.stacks[mid:n], snap.counts[mid:n]))
    assert feeder.stats["drains_fed"] == 2
    assert len(calls) == 2
    assert enc.statics_backlog(feeder._prebuild_period) > 0
