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


# -- what a shipped window leaves behind is disposed of on the worker ---------


@pytest.mark.parametrize("how", ["shipped", "ship-failed", "worker-death"])
def test_after_window_is_the_last_thing_the_worker_does_for_a_window(how):
    """after_window runs once per window handed over, whichever way the
    window went, behind everything else the worker does for it and while
    the worker still counts as busy; a hook that raises is counted and
    the worker goes on to the next window."""
    snap = _snap(seed=22)
    agg = DictAggregator(capacity=1 << 12)
    counts = np.asarray(agg.window_counts(snap))
    events = []

    def ship(out, prep):
        events.append("ship")
        if how == "ship-failed":
            raise OSError("store down")

    def after_window():
        events.append(("after", threading.current_thread().name,
                       pipe._state))
        raise RuntimeError("hook bug")

    enc = WindowEncoder(agg)
    pipe = EncodePipeline(
        enc, ship=ship, rollup=lambda prep, ctx: events.append("rollup"),
        snapshot=lambda period_ns: events.append("snapshot"),
        snapshot_every=1, after_window=after_window)
    if how == "worker-death":
        def boom(prep, views=False):
            raise RuntimeError("encoder bug")

        enc.encode_prepared = boom
    after = ("after", "encode-pipeline", "encode")
    want = {"shipped": ["ship", "rollup", "snapshot", after],
            "ship-failed": ["ship", after],
            "worker-death": ["fallback", after]}[how]
    try:
        for n in (1, 2):
            del events[:]
            assert pipe.submit(
                counts, snap.time_ns, snap.window_ns, snap.period_ns,
                fallback=lambda: events.append("fallback")) is not None
            assert pipe.quiesce(30)
            assert events == want
            assert pipe.stats["after_window_errors"] == n
            if how == "worker-death":
                assert pipe.disabled
                break
            assert not pipe.disabled
    finally:
        assert pipe.close()


def test_worker_lets_go_of_a_window_when_it_is_done_with_it():
    """What a handed-off window holds (its prepared arrays; through the
    fallback its snapshot, hundreds of MB at firehose size) is freed by
    the worker while it still counts as busy with that window and before
    after_window runs, not by the next window's pick-up inside that
    window's encode_wait."""
    import weakref

    class Snapshot:
        pass

    snap = _snap(seed=23)
    agg = DictAggregator(capacity=1 << 12)
    counts = agg.window_counts(snap)
    events = []
    dropped = threading.Event()   # the test's own reference is gone
    pipe = EncodePipeline(WindowEncoder(agg),
                          ship=lambda out, prep: dropped.wait(30),
                          after_window=lambda: events.append("after"))
    held = Snapshot()
    weakref.finalize(held, lambda: events.append(
        (threading.current_thread().name, pipe._state)))
    try:
        assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                           snap.period_ns,
                           fallback=lambda s=held: None) is not None
        del held
        dropped.set()
        assert pipe.quiesce(30)
        assert events == [("encode-pipeline", "encode"), "after"]
    finally:
        assert pipe.close()


class _GcWatch:
    """A manage_gc profiler over a pipeline whose encode blocks until
    released, with gc.collect, the ship and the after-ship hooks
    recording (what, thread name) in the order they were entered."""

    def __init__(self, monkeypatch, n_windows=3, **kw):
        import gc

        self.events = []
        self.gate = threading.Event()
        snap = dataclasses.replace(_snap(seed=21), window_ns=50_000_000)
        kw.setdefault("encode_pipeline", True)
        self.p = CPUProfiler(
            source=ReplaySource([snap] * n_windows),
            aggregator=DictAggregator(capacity=1 << 12),
            fallback_aggregator=CPUAggregator(), profile_writer=Collect(),
            fast_encode=True, duration_s=0.01, manage_gc=True, **kw)
        real_collect = gc.collect

        def collect(*a):
            self.note("collect")
            return real_collect(*a)

        monkeypatch.setattr(gc, "collect", collect)
        real_encode = self.p._encoder.encode_prepared

        def encode_prepared(prep, views=False):
            self.note("encode")
            assert self.gate.wait(30)
            out = real_encode(prep, views=views)
            self.note("encode_end")
            return out

        self.p._encoder.encode_prepared = encode_prepared
        pipe = self.p._pipeline
        if pipe is not None:
            real_ship = pipe._ship

            def ship(out, prep):
                real_ship(out, prep)
                self.note("ship_end")

            pipe._ship = ship
            pipe._rollup = lambda prep, ctx: self.note("rollup")
            pipe._snapshot = lambda period_ns: self.note("snapshot")
            pipe._snapshot_every = 1

    def note(self, what):
        self.events.append((what, threading.current_thread().name))

    def whats(self):
        return [w for w, _ in self.events]

    def close(self):
        import gc

        self.gate.set()
        if self.p._pipeline is not None:
            self.p._pipeline.close()
        self.p._restore_gc()
        assert gc.isenabled() and gc.get_freeze_count() == 0


def test_gc_collects_on_the_worker_after_the_ship_and_its_hooks(monkeypatch):
    """No thread enters gc.collect() between a window's hand-off and the
    end of its encode; the worker enters one after the ship, the rollup
    and the snapshot, as the last thing it does for the window."""
    w = _GcWatch(monkeypatch)
    try:
        assert threading.current_thread().name != "encode-pipeline"
        for _ in range(2):           # the cold first window, a warm one
            del w.events[:]
            w.gate.clear()
            assert w.p.run_iteration()  # handed off; the worker blocked
            t_end = time.monotonic() + 10
            while "encode" not in w.whats() and time.monotonic() < t_end:
                time.sleep(0.005)
            assert w.whats() == ["encode"]   # nobody collected
            w.gate.set()
            assert w.p._pipeline.quiesce(30)
            assert w.events == [(x, "encode-pipeline") for x in (
                "encode", "encode_end", "ship_end", "rollup", "snapshot",
                "collect")]
        m = w.p.metrics
        assert (m.gc_collections_worker_total,
                m.gc_collections_loop_total) == (2, 0)
        assert m.gc_collect_seconds_total > 0
    finally:
        w.close()


def test_first_freeze_takes_in_what_the_first_windows_ship_built(monkeypatch):
    """The run's first collect-and-freeze falls after the first window's
    encode and ship: a tracked object the ship creates is in the
    permanent generation once that window is over, and nothing was
    frozen while the window was still on the worker."""
    import gc

    w = _GcWatch(monkeypatch)
    made = []
    real_ship = w.p._pipeline._ship

    def ship(out, prep):
        real_ship(out, prep)
        made.append({"static": ["piece"]})     # a tracked container

    w.p._pipeline._ship = ship
    try:
        assert gc.get_freeze_count() == 0
        assert w.p.run_iteration()
        assert not gc.isenabled()      # the scheduler is off already,
        assert gc.get_freeze_count() == 0   # the freeze waits for the ship
        w.gate.set()
        assert w.p._pipeline.quiesce(30)
        assert gc.get_freeze_count() > 0
        # gc.get_objects() leaves the permanent generation out.
        assert made and not any(o is made[0] for o in gc.get_objects())
        frozen = gc.get_freeze_count()
        assert w.p.run_iteration()     # a steady window: collected, and
        assert w.p._pipeline.quiesce(30)    # nothing more frozen
        assert gc.get_freeze_count() <= frozen  # (frozen ones may die)
        assert w.p.metrics.gc_collections_worker_total == 2
    finally:
        w.close()


@pytest.mark.parametrize("how", ["backpressure", "inline", "worker-death"])
def test_gc_collects_at_the_end_of_the_iteration_off_the_pipeline(
        monkeypatch, how):
    """A window that did not go through the pipeline is collected where
    it ends: on the capture thread, at the end of run_iteration."""
    w = _GcWatch(monkeypatch, encode_pipeline=(how != "inline"))
    me = threading.current_thread().name
    p, m = w.p, w.p.metrics
    try:
        if how == "backpressure":
            w.gate.set()
            assert p.run_iteration()       # window 1, piped
            assert p._pipeline.flush(30)
            w.gate.clear()
            assert p.run_iteration()       # window 2 piped, worker blocked
            del w.events[:]
            assert p.run_iteration()       # window 3 refused -> scalar
            assert m.encode_backpressure_total == 1
            assert ("collect", me) in w.events
            assert ("collect", "encode-pipeline") not in w.events
            assert (m.gc_collections_worker_total,
                    m.gc_collections_loop_total) == (1, 1)
        elif how == "inline":
            w.gate.set()
            assert p.run_iteration() and p.run_iteration()
            assert [e for e in w.events if e[0] == "collect"] \
                == [("collect", me)] * 2
            assert (m.gc_collections_worker_total,
                    m.gc_collections_loop_total) == (0, 2)
        else:
            real = p._encoder.encode_prepared
            boom = {"on": True}

            def maybe_boom(prep, views=False):
                if boom["on"]:
                    raise RuntimeError("encoder bug")
                return real(prep, views=views)

            p._encoder.encode_prepared = maybe_boom
            w.gate.set()
            assert p.run_iteration()       # handed off; the worker dies
            assert p._pipeline.quiesce(30) and p._pipeline.disabled
            # ... and collects behind the scalar fallback's ship.
            assert w.events[-1] == ("collect", "encode-pipeline")
            boom["on"] = False
            del w.events[:]
            assert p.run_iteration()       # disabled: inline, on the loop
            assert w.events[-1] == ("collect", me)
            assert (m.gc_collections_worker_total,
                    m.gc_collections_loop_total) == (1, 1)
        assert p.last_error is None
    finally:
        w.close()


@pytest.mark.parametrize("how", ["clean", "crash"])
def test_run_exit_restores_the_collector_with_a_pipeline(monkeypatch, how):
    """run()'s exit gives the process its collector back (enabled,
    nothing frozen) behind a pipeline: after a clean stop, whose close
    joins the worker first, and after a crash, where a worker still on
    its window finds the run closed and freezes nothing."""
    import gc

    w = _GcWatch(monkeypatch, n_windows=2)
    p = w.p
    try:
        if how == "clean":
            w.gate.set()
            p.run()                        # exhausts the source, closes
            assert p.crashed is None
            assert p.metrics.gc_collections_worker_total == 2
        else:
            def bug(_n):
                raise RuntimeError("loop bug")

            p._on_iteration = bug
            with pytest.raises(RuntimeError):
                p.run()                    # window 1 is still on the worker
            assert p.crashed is not None
            assert gc.isenabled() and gc.get_freeze_count() == 0
            w.gate.set()
            assert p._pipeline.quiesce(30)
            assert p.metrics.gc_collections_worker_total == 0
            assert "collect" not in w.whats()
        assert gc.isenabled() and gc.get_freeze_count() == 0
        if how == "crash":
            # A supervised restart re-arms: the loop's first iteration
            # switches the scheduler off, the worker's first collection
            # freezes.
            p._on_iteration = None
            p.run()
            assert p.crashed is None
            assert p.metrics.gc_collections_worker_total == 1
            assert gc.isenabled() and gc.get_freeze_count() == 0
    finally:
        w.close()


def test_hourly_refreeze_counts_collections_and_rides_the_hook(monkeypatch):
    """Every _GC_REFREEZE-th collection of a run unfreezes, collects and
    freezes again, on whichever arm that collection runs."""
    import gc

    w = _GcWatch(monkeypatch, n_windows=3)
    monkeypatch.setattr(CPUProfiler, "_GC_REFREEZE", 2)
    for name in ("freeze", "unfreeze"):
        real = getattr(gc, name)
        monkeypatch.setattr(
            gc, name, lambda real=real, name=name: (w.note(name), real())[1])
    w.gate.set()
    try:
        for _ in range(3):
            assert w.p.run_iteration() and w.p._pipeline.quiesce(30)
        assert [e for e in w.events
                if e[0] in ("collect", "freeze", "unfreeze")] \
            == [(x, "encode-pipeline") for x in (
                "collect", "freeze", "collect",
                "unfreeze", "collect", "freeze")]
    finally:
        w.close()


def test_gc_counters_on_metrics(monkeypatch):
    """The three families of the boundary collection, by arm."""
    from parca_agent_tpu.web import render_metrics

    w = _GcWatch(monkeypatch)
    w.gate.set()
    p = w.p
    try:
        text = render_metrics([p])
        assert 'parca_agent_profiler_gc_collections_total{' \
            'profiler="cpu",where="worker"} 0' in text
        assert p.run_iteration() and p._pipeline.quiesce(30)
        p._pipeline.disabled = True
        assert p.run_iteration()           # inline: the loop's arm
        text = render_metrics([p])
        for where in ("worker", "loop"):
            assert 'parca_agent_profiler_gc_collections_total{' \
                f'profiler="cpu",where="{where}"}} 1' in text
        sec = [ln for ln in text.splitlines() if ln.startswith(
            "parca_agent_profiler_gc_collect_seconds_total")]
        assert len(sec) == 1 and float(sec[0].split()[-1]) > 0
        got = [ln for ln in text.splitlines() if ln.startswith(
            "parca_agent_profiler_gc_collected_objects_total")]
        assert len(got) == 1 and float(got[0].split()[-1]) \
            == p.metrics.gc_collected_objects_total
    finally:
        w.close()


def test_two_arms_and_a_restore_share_the_collector_without_a_lost_update():
    """The collector's state is written by the capture thread and the
    encode worker: under a shortened switch interval every collection of
    either arm is counted once, and one that meets a closed run leaves
    the collector alone."""
    import gc
    import sys

    p = CPUProfiler(source=ReplaySource([]), aggregator=CPUAggregator(),
                    manage_gc=True)
    n, arms = 150, ("worker", "loop", "worker", "loop")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(
            target=lambda a=a: [p._collect_gc(a) for _ in range(n)])
            for a in arms]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
        m = p.metrics
        assert (m.gc_collections_worker_total, m.gc_collections_loop_total,
                p._gc_collections) == (2 * n, 2 * n, 4 * n)
        assert gc.get_freeze_count() > 0
        p._restore_gc()
        p._collect_gc("worker")            # a late worker: the run is closed
        assert m.gc_collections_worker_total == 2 * n
    finally:
        sys.setswitchinterval(old)
        p._restore_gc()
    assert gc.isenabled() and gc.get_freeze_count() == 0


# -- the encoder's carried state, seen through the pipeline -------------------


def _traced_windows(kind):
    """Four windows through a pipeline, each with a trace: a cold one,
    two of the same population and a last one of `kind` ("steady": the
    same again; "rollout": new stacks and new pids). Returns (the
    traces' meta, what was shipped, what a sync encoder over a twin
    aggregator encodes, the pipeline's encoder, ids the last window
    brought)."""
    from parca_agent_tpu.runtime.trace import FlightRecorder

    snap = _snap(seed=31)
    grown = _snap(seed=31, n_pids=9, rows=260)   # more of both
    agg, twin = (DictAggregator(capacity=1 << 12) for _ in range(2))
    enc, sync = WindowEncoder(agg), WindowEncoder(twin)
    shipped, want = [], []
    pipe = EncodePipeline(enc, ship=lambda out, prep: shipped.append(
        [(pid, bytes(b)) for pid, b in out]))
    rec = FlightRecorder(ring=8)
    new_ids = 0
    try:
        for t, s in enumerate((snap, snap, snap,
                               grown if kind == "rollout" else snap)):
            s = dataclasses.replace(s, counts=s.counts + t)
            ids0 = agg._published
            counts = np.asarray(agg.window_counts(s))
            new_ids = agg._published - ids0
            tr = rec.begin(s.time_ns + t)
            assert pipe.submit(counts, s.time_ns + t, s.window_ns,
                               s.period_ns, trace=tr) is not None
            assert pipe.flush(30)
            want.append([(pid, bytes(b)) for pid, b in sync.encode(
                np.asarray(twin.window_counts(s)), s.time_ns + t,
                s.window_ns, s.period_ns, views=True)])
    finally:
        assert pipe.close()
    return [d["meta"] for d in rec.traces()], shipped, want, enc, new_ids


@pytest.mark.parametrize("kind", ["steady", "rollout"])
def test_a_windows_meta_says_what_the_encoder_redid(kind):
    """The counts a window's trace carries (docs/observability.md): the
    cold window reads every cap and sorts the order; a steady one reads
    none, merges nothing and hands out the views it has; a rollout
    window reads the caps of the pids it touched and merges its ids."""
    meta, shipped, want, enc, new_ids = _traced_windows(kind)
    assert shipped == want        # and none of it moves a byte
    cold, last = meta[0], meta[-1]
    assert cold["caps_rebuilds"] == 1 and cold["encode_order_rebuilds"] == 1
    assert cold["caps_refreshed"] == len(shipped[0])
    assert cold["encode_views_reused"] == 0
    for m in meta[1:3] + ([last] if kind == "steady" else []):
        assert m["encode_views_reused"] == 1 and m["caps_refreshed"] == 0
        assert "caps_rebuilds" not in m
        assert "encode_order_rebuilds" not in m
        assert "encode_order_merged_ids" not in m
    if kind == "rollout":
        assert new_ids > 0 and last["encode_order_merged_ids"] == new_ids
        assert 0 < last["caps_refreshed"] <= len(shipped[-1])
        assert last["encode_views_reused"] == 0
        assert "caps_rebuilds" not in last
    assert enc.stats["views_reused_total"] == (3 if kind == "steady" else 2)


def test_a_pipelined_windows_statics_build_is_a_span_under_its_encode():
    """The worker records `encode` at its end, as it always did; what
    the encoder records inside it, the statics build of a window that
    lays out or appends, names it as parent. A window that only patches
    counts builds no statics and has no such span."""
    from parca_agent_tpu.runtime.trace import FlightRecorder

    snap = _snap(seed=31)
    grown = _snap(seed=31, n_pids=9, rows=260)
    agg = DictAggregator(capacity=1 << 12)
    pipe = EncodePipeline(WindowEncoder(agg), ship=lambda out, prep: None)
    rec = FlightRecorder(ring=8)
    try:
        for t, s in enumerate((snap, snap, grown)):
            counts = np.asarray(agg.window_counts(s))
            tr = rec.begin(s.time_ns + t)
            assert pipe.submit(counts, s.time_ns + t, s.window_ns,
                               s.period_ns, trace=tr) is not None
            assert pipe.flush(30)
    finally:
        assert pipe.close()
    cold, steady, rollout = (
        {sp["stage"]: sp for sp in d["spans"]} for d in rec.traces())
    for spans in (cold, rollout):
        enc_sp, st_sp = spans["encode"], spans["encode_statics"]
        assert st_sp["parent"] == enc_sp["id"] and enc_sp["parent"] is None
        assert 0 < st_sp["duration_s"] <= enc_sp["duration_s"]
        assert enc_sp["start_s"] <= st_sp["start_s"]
        assert st_sp["thread"] == enc_sp["thread"] == "encode-pipeline"
    assert "encode" in steady and "encode_statics" not in steady


def test_encoder_carried_state_counters_on_metrics():
    """The five families of the encoder's carried state."""
    from parca_agent_tpu.web import render_metrics

    snap = dataclasses.replace(_snap(seed=33), window_ns=50_000_000)
    p = CPUProfiler(
        source=ReplaySource([snap] * 3),
        aggregator=DictAggregator(capacity=1 << 12),
        fallback_aggregator=CPUAggregator(), profile_writer=Collect(),
        fast_encode=True, duration_s=0.01, encode_pipeline=True)
    try:
        for _ in range(3):
            assert p.run_iteration() and p._pipeline.quiesce(30)
        text = render_metrics([p])
        n = len(np.unique(snap.pids))
        for family, value in (("caps_refreshed_total", n),
                              ("caps_rebuilds_total", 1),
                              ("order_merged_ids_total", 0),
                              ("order_rebuilds_total", 1),
                              ("views_reused_total", 2)):
            assert f'parca_agent_encoder_{family}{{profiler="cpu"}} ' \
                f'{value}\n' in text, family
        # Beside them, the aggregator's: no known pid was asked for an
        # address (the same stacks three times), so no look-up was built.
        assert 'parca_agent_dict_registry_index_builds_total' \
            '{profiler="cpu"} 0\n' in text
    finally:
        p._pipeline.close()
