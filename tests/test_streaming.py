"""Streaming window feeder: drains fed to the device during the window,
close at the boundary — with exactness guaranteed by construction (any
incomplete/failed stream falls back to the one-shot snapshot path)."""

from __future__ import annotations

import numpy as np
import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.profiler.cpu import CPUProfiler
from parca_agent_tpu.profiler.streaming import StreamingWindowFeeder
from streaming_sources import CacheSource


class FakeMaps:
    def executable_mappings(self, pid):
        return []


class FakeObjs:
    def build_ids(self, per_pid):
        return {}


def _snap(seed=1, n=300, pids=6):
    return generate(SyntheticSpec(n_pids=pids, n_unique_stacks=n, n_rows=n,
                                  total_samples=n * 4, mean_depth=8,
                                  seed=seed))


def _cols(snap, lo, hi):
    """A drain's columnar chunk (the sampler tee payload) for rows [lo,hi)."""
    return (snap.pids[lo:hi], snap.tids[lo:hi], snap.user_len[lo:hi],
            snap.kernel_len[lo:hi], snap.stacks[lo:hi], snap.counts[lo:hi])


def test_feeder_streams_a_complete_window():
    snap = _snap()
    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()))
    n = len(snap)
    for lo in range(0, n, 64):
        feeder.on_drain(_cols(snap, lo, min(lo + 64, n)))
    assert feeder.stats["drains_fed"] == -(-n // 64)
    counts = feeder.take_window_if_complete(snap)
    assert counts is not None
    assert int(counts.sum()) == snap.total_samples()
    assert feeder.stats["windows_streamed"] == 1
    # Per-(pid,stack) equality against the oracle (ids are registry
    # order; compare multisets per pid through the profile build).
    profiles = {p.pid: p for p in agg._build_profiles(snap, counts)}
    for op in CPUAggregator().aggregate(snap):
        assert profiles[op.pid].total() == op.total()
        assert np.array_equal(np.sort(profiles[op.pid].values),
                              np.sort(op.values))


def test_feeder_incomplete_window_falls_back():
    snap = _snap(seed=2)
    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()))
    feeder.on_drain(_cols(snap, 0, len(snap) // 2))  # half the window
    assert feeder.take_window_if_complete(snap) is None
    assert feeder.stats["windows_fallback"] == 1
    # The one-shot path still produces exact counts afterwards.
    counts = agg.window_counts(snap)
    assert int(counts.sum()) == snap.total_samples()
    # Next window streams cleanly again.
    for lo in range(0, len(snap), 128):
        feeder.on_drain(_cols(snap, lo, min(lo + 128, len(snap))))
    assert feeder.take_window_if_complete(snap) is not None


def test_fallback_window_timings_do_not_leak_into_next_stream():
    """A one-shot window_counts between two streamed windows writes its
    own feed_dispatch/feed_settle into the shared aggregator's timings;
    the next streamed window must not pop them into ITS overlap stats."""
    snap = _snap(seed=9)
    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()))
    feeder.on_drain(_cols(snap, 0, len(snap) // 2))  # half: falls back
    assert feeder.take_window_if_complete(snap) is None
    agg.window_counts(snap)  # the one-shot fallback window
    assert "feed_dispatch" in agg.timings  # the leak source exists
    # Sentinel values a leak would make unmissable in the next stats.
    agg.timings["feed_dispatch"] = 999.0
    agg.timings["feed_settle"] = 999.0
    for lo in range(0, len(snap), 128):
        feeder.on_drain(_cols(snap, lo, min(lo + 128, len(snap))))
    assert feeder.take_window_if_complete(snap) is not None
    assert feeder.stats["last_window_dispatch_s"] < 100.0
    assert feeder.stats["last_window_settle_s"] < 100.0
    # The pop sites consumed every settle/dispatch timing: nothing left
    # for the NEXT window's first drain to mis-attribute.
    assert "feed_dispatch" not in agg.timings
    assert "feed_settle" not in agg.timings


def test_feeder_disables_on_feed_failure():
    snap = _snap(seed=3)

    class Boom(DictAggregator):
        def feed(self, *a, **kw):
            raise RuntimeError("device gone")

    agg = Boom(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()))
    feeder.on_drain(_cols(snap, 0, len(snap)))
    assert feeder.disabled
    assert feeder.take_window_if_complete(snap) is None
    # Disabled for the cooldown: further drains are no-ops, no exception
    # escapes.
    feeder.on_drain(_cols(snap, 0, 10))
    assert feeder.stats["drains_fed"] == 0


def test_feeder_recovers_after_transient_failure():
    """A transient device hiccup costs a bounded number of one-shot
    windows, not streaming for the process lifetime: the feeder re-probes
    at a window boundary after a capped-exponential cooldown."""
    snap = _snap(seed=8)

    class Flaky(DictAggregator):
        fail = True

        def feed(self, *a, **kw):
            if self.fail:
                raise RuntimeError("transient device hiccup")
            return super().feed(*a, **kw)

    agg = Flaky(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()),
                                   reprobe_base_windows=2)
    feeder.on_drain(_cols(snap, 0, len(snap)))
    assert feeder.disabled
    # Device heals immediately; the feeder still waits out its cooldown.
    agg.fail = False
    assert feeder.take_window_if_complete(snap) is None   # cooldown 2 -> 1
    feeder.on_drain(_cols(snap, 0, 10))                   # still ignored
    assert feeder.stats["drains_fed"] == 0
    assert feeder.take_window_if_complete(snap) is None   # cooldown 1 -> 0
    assert not feeder.disabled                            # re-enabled
    # The next window streams end to end again, exactly.
    for lo in range(0, len(snap), 64):
        feeder.on_drain(_cols(snap, lo, min(lo + 64, len(snap))))
    counts = feeder.take_window_if_complete(snap)
    assert counts is not None
    assert int(counts.sum()) == snap.total_samples()
    assert feeder.stats["reprobes"] == 1
    # A healthy streamed window resets the backoff to its base.
    assert feeder._backoff == feeder._backoff_base


def test_feeder_prebuilds_statics_during_window():
    """With an encoder attached, each drain feed is followed by a budgeted
    statics prebuild, so by close the window's pid population is already
    warm and the close-time encode pays no cold statics transient."""
    from parca_agent_tpu.pprof.window_encoder import WindowEncoder

    snap = _snap(seed=10)
    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()),
                                   prebuild_period_ns=10_000_000)
    enc = WindowEncoder(agg)
    feeder.attach_encoder(enc)
    for lo in range(0, len(snap), 64):
        feeder.on_drain(_cols(snap, lo, min(lo + 64, len(snap))))
    assert feeder.stats["statics_prebuilt"] == feeder.stats["drains_fed"]
    # Every pid the aggregator knows is cached before close.
    assert set(enc._static) == set(agg._pids)
    assert all(st.period_ns == 10_000_000 for st in enc._static.values())
    counts = feeder.take_window_if_complete(snap)
    assert counts is not None
    # The close-time encode matches the scalar builder byte-for-byte even
    # though its statics were prebuilt incrementally mid-window.
    out = dict(enc.encode(counts, snap.time_ns, snap.window_ns,
                          snap.period_ns))
    from parca_agent_tpu.pprof.builder import parse_pprof

    totals = {pid: sum(v[0] for _, v, _ in parse_pprof(b).samples)
              for pid, b in out.items()}
    oracle = {p.pid: p.total() for p in CPUAggregator().aggregate(snap)}
    assert totals == oracle


def test_build_statics_budget_is_incremental():
    """A budgeted build makes bounded progress per call and converges:
    repeated calls leave nothing dirty, and the result is identical to an
    unbudgeted build."""
    from parca_agent_tpu.pprof.window_encoder import WindowEncoder

    snap = _snap(seed=11, n=900, pids=40)
    agg = DictAggregator(capacity=1 << 12)
    counts = agg.window_counts(snap)
    enc = WindowEncoder(agg)
    # chunk smaller than the pid count forces multiple batches; a zero
    # budget stops after the guaranteed first chunk of each call.
    built = enc.build_statics(snap.period_ns, budget_s=0.0, chunk=8)
    assert built < len(agg._pids)  # partial progress, not all-at-once
    for _ in range(200):
        built = enc.build_statics(snap.period_ns, budget_s=0.0, chunk=8)
        if built == len(agg._pids):
            break
    assert built == len(agg._pids)
    out = dict(enc.encode(counts, snap.time_ns, snap.window_ns,
                          snap.period_ns))
    enc2 = WindowEncoder(agg)
    enc2.build_statics(snap.period_ns)
    out2 = dict(enc2.encode(counts, snap.time_ns, snap.window_ns,
                            snap.period_ns))
    assert out == out2


def test_feeder_discards_residual_device_mass():
    """A one-shot window_counts that failed AFTER its feed dispatched
    leaves mass in the device accumulator with _needs_reset False; the
    feeder's close gate must catch the mismatch and fall back rather
    than emit inflated counts."""
    snap = _snap(seed=12)
    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()))
    # Simulate the partial one-shot: feed dispatched, close never ran.
    # The residue lives in BOTH the device accumulator and the host-side
    # _pending mirror (which an acc reset alone would not clear).
    agg._needs_reset = True
    agg.feed(snap)
    assert agg._fed_total > 0 or agg._pending
    # A fully-streamed window on top of the residue closes EXACTLY: the
    # first feed discards the stale open-window state wholesale.
    for lo in range(0, len(snap), 64):
        feeder.on_drain(_cols(snap, lo, min(lo + 64, len(snap))))
    counts = feeder.take_window_if_complete(snap)
    assert counts is not None
    assert int(counts.sum()) == snap.total_samples()  # not inflated


def test_feeder_reenable_resets_accumulator():
    """Re-enabling after cooldown forces a device-accumulator reset so the
    first streamed window never builds on residual mass."""
    snap = _snap(seed=13, n=100, pids=3)

    class Once(DictAggregator):
        fail = True

        def feed(self, *a, **kw):
            if self.fail:
                raise RuntimeError("hiccup")
            return super().feed(*a, **kw)

    agg = Once(capacity=1 << 10)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()),
                                   reprobe_base_windows=1)
    feeder.on_drain(_cols(snap, 0, len(snap)))
    assert feeder.disabled
    agg.fail = False
    # Mid-cooldown, a one-shot partially fails leaving device mass.
    agg._needs_reset = True
    agg.feed(snap)
    assert agg._fed_total > 0
    assert feeder.take_window_if_complete(snap) is None  # re-enables
    assert not feeder.disabled
    assert agg._needs_reset  # forced clean start for the next feed


def test_feeder_skips_while_externally_blocked():
    """While the profiler's hang watchdog reports an abandoned aggregation
    call possibly still executing, the polling thread must not touch the
    aggregator or encoder at all."""
    snap = _snap(seed=14, n=100, pids=3)
    agg = DictAggregator(capacity=1 << 10)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()))
    feeder.external_blocked = lambda: True
    feeder.on_drain(_cols(snap, 0, len(snap)))
    assert feeder.stats["drains_fed"] == 0
    assert not feeder.disabled  # a skip is not a failure
    feeder.external_blocked = lambda: False
    feeder.on_drain(_cols(snap, 0, len(snap)))
    assert feeder.stats["drains_fed"] == 1


def test_feeder_backoff_doubles_and_caps():
    snap = _snap(seed=9, n=50, pids=2)

    class Boom(DictAggregator):
        def feed(self, *a, **kw):
            raise RuntimeError("device gone")

    agg = Boom(capacity=1 << 10)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()),
                                   reprobe_base_windows=2,
                                   reprobe_max_windows=8)
    observed = []
    for _ in range(4):  # repeated failures: 2, 4, 8, 8 (capped)
        feeder.on_drain(_cols(snap, 0, len(snap)))
        assert feeder.disabled
        observed.append(feeder._cooldown)
        while feeder.disabled:
            feeder.take_window_if_complete(snap)
    assert observed == [2, 4, 8, 8]


def test_feeder_hang_is_bounded():
    import threading

    snap = _snap(seed=4, n=50, pids=2)
    release = threading.Event()

    class Wedge(DictAggregator):
        def feed(self, *a, **kw):
            release.wait(20)

    agg = Wedge(capacity=1 << 10)
    # first_feed_timeout_s pinned down too: the cold-start budget is
    # deliberately long in production (it covers the XLA compile), and
    # this test wedges the very first feed.
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()),
                                   feed_timeout_s=0.2,
                                   first_feed_timeout_s=0.2)
    import time

    t0 = time.monotonic()
    feeder.on_drain(_cols(snap, 0, len(snap)))
    assert time.monotonic() - t0 < 5
    assert feeder.disabled
    # While the abandoned call is in flight, the aggregator is off-limits
    # (the profiler's fast path raises into its fallback machinery).
    assert feeder.device_blocked()
    release.set()
    import time as _t

    for _ in range(100):
        if not feeder.device_blocked():
            break
        _t.sleep(0.05)
    assert not feeder.device_blocked()


def test_profiler_uses_streamed_close():
    """End to end: a source whose poll() tees drains to the feeder; the
    profiler writes the same profiles the classic path writes."""
    from parca_agent_tpu.pprof.builder import parse_pprof

    snap = _snap(seed=5)

    class StreamingSource:
        def __init__(self, feeder):
            self._feeder = feeder
            self._left = 2

        def poll(self):
            if not self._left:
                return None
            self._left -= 1
            n = len(snap)
            for lo in range(0, n, 100):
                self._feeder.on_drain(_cols(snap, lo, min(lo + 100, n)))
            return snap

    class Collect:
        def __init__(self):
            self.got = []

        def write(self, labels, blob):
            self.got.append((labels, blob))

    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()))
    w = Collect()
    p = CPUProfiler(source=StreamingSource(feeder), aggregator=agg,
                    profile_writer=w, fast_encode=True,
                    streaming_feeder=feeder)
    assert p.run_iteration()
    assert p.run_iteration()
    assert p.last_error is None
    assert feeder.stats["windows_streamed"] == 2

    w2 = Collect()
    from parca_agent_tpu.capture.replay import ReplaySource

    CPUProfiler(source=ReplaySource([snap]), aggregator=CPUAggregator(),
                profile_writer=w2).run_iteration()
    classic = {l["pid"]: sum(v[0] for _, v, _ in parse_pprof(b).samples)
               for l, b in w2.got}
    streamed = {l["pid"]: sum(v[0] for _, v, _ in parse_pprof(b).samples)
                for l, b in w.got[: len(classic)]}
    assert streamed == classic


def test_profiler_streaming_requires_fast_encode():
    with pytest.raises(ValueError):
        CPUProfiler(source=None, aggregator=CPUAggregator(),
                    streaming_feeder=object())


def test_feeder_with_sharded_aggregator():
    """Streaming inherits over the mesh-sharded dict (same feed/close
    protocol; the sub-tables and psum close are dispatch details)."""
    from parca_agent_tpu.aggregator.sharded import ShardedDictAggregator
    from parca_agent_tpu.parallel.mesh import fleet_mesh

    snap = _snap(seed=7, n=400, pids=8)
    agg = ShardedDictAggregator(capacity=1 << 12, mesh=fleet_mesh(8))
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()))
    for lo in range(0, len(snap), 96):
        feeder.on_drain(_cols(snap, lo, min(lo + 96, len(snap))))
    counts = feeder.take_window_if_complete(snap)
    assert counts is not None
    assert int(counts.sum()) == snap.total_samples()
    profiles = {p.pid: p.total() for p in agg._build_profiles(snap, counts)}
    oracle = {p.pid: p.total() for p in CPUAggregator().aggregate(snap)}
    assert profiles == oracle


def test_first_feed_gets_the_compile_budget_then_short_timeout():
    """The first feed of a cold process includes the XLA compile of the
    feed program, so it gets first_feed_timeout_s; once one feed has
    succeeded, the short feed_timeout_s guards every later feed."""
    import threading
    import time

    snap = _snap(seed=9, n=60, pids=2)
    slow_s = {"v": 0.5}

    class Slow(DictAggregator):
        def feed(self, *a, **kw):
            time.sleep(slow_s["v"])
            return super().feed(*a, **kw)

    agg = Slow(capacity=1 << 10)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()),
                                   feed_timeout_s=0.2,
                                   first_feed_timeout_s=5.0)
    # First feed: slower than feed_timeout_s but inside the first-feed
    # budget — must SUCCEED (this is the compile-on-first-feed case that
    # would otherwise disable streaming on every cold TPU start).
    feeder.on_drain(_cols(snap, 0, 30))
    assert not feeder.disabled
    assert feeder.stats["drains_fed"] == 1
    # Later feeds run under the short timeout: the same slowness now
    # trips the watchdog and starts the cooldown.
    feeder.on_drain(_cols(snap, 30, 60))
    assert feeder.disabled


def test_wedged_boot_pays_the_long_budget_exactly_once():
    """A device wedged from boot costs ONE long first-feed stall; every
    re-probe after the cooldown runs under the short timeout (the old
    behavior re-paid the long budget on each re-probe, stalling the
    capture loop and wrapping the perf rings repeatedly)."""
    import threading
    import time

    snap = _snap(seed=12, n=50, pids=2)
    release = threading.Event()

    class Wedge(DictAggregator):
        def feed(self, *a, **kw):
            release.wait(30)

    agg = Wedge(capacity=1 << 10)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()),
                                   feed_timeout_s=0.1,
                                   first_feed_timeout_s=0.5,
                                   reprobe_base_windows=1)
    t0 = time.monotonic()
    feeder.on_drain(_cols(snap, 0, 25))        # first attempt: long budget
    first_stall = time.monotonic() - t0
    assert feeder.disabled
    assert 0.4 < first_stall < 5
    release.set()                               # let the abandoned call die
    for _ in range(100):
        if not feeder.device_blocked():
            break
        time.sleep(0.05)
    release.clear()
    feeder.take_window_if_complete(snap)        # cooldown 1 -> re-enabled
    assert not feeder.disabled
    t0 = time.monotonic()
    feeder.on_drain(_cols(snap, 25, 50))        # re-probe: SHORT budget
    assert time.monotonic() - t0 < 0.4
    assert feeder.disabled
    release.set()


def test_encode_failure_falls_back_scalar_not_device_watchdog():
    """The encoder is host-side numpy: its failures (and slow transients,
    e.g. a post-rotation template rebuild) run OUTSIDE the device hang
    watchdog. A raising encoder costs one scalar-fallback window — it must
    not mark the device wedged."""
    from parca_agent_tpu.capture.replay import ReplaySource

    snap = _snap(seed=13)

    class Collect:
        def __init__(self):
            self.got = []

        def write(self, labels, blob):
            self.got.append((labels, blob))

    agg = DictAggregator(capacity=1 << 11)
    w = Collect()
    p = CPUProfiler(source=ReplaySource([snap, snap]), aggregator=agg,
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True)

    boom = {"on": True}
    real_encode = p._encoder.encode

    def maybe_boom(*a, **kw):
        if boom["on"]:
            raise RuntimeError("encoder bug")
        return real_encode(*a, **kw)

    p._encoder.encode = maybe_boom
    assert p.run_iteration()
    assert p.last_error is None          # window still shipped (scalar)
    assert len(w.got) > 0
    assert p._device_wedged_at is None   # device NOT blamed
    n_scalar = len(w.got)
    # Next window: encoder healthy again, fast path resumes seamlessly.
    boom["on"] = False
    assert p.run_iteration()
    assert len(w.got) > n_scalar


def test_slow_encode_does_not_trip_the_device_watchdog():
    """The new invariant of the fast path's structure: encode runs on the
    profiler thread OUTSIDE the device hang watchdog, so an encode slower
    than device_timeout_s (a post-rotation template rebuild is tens of
    seconds at 50k pids) ships fast-path profiles and never marks the
    device wedged. (With encode inside the guarded thunk, this test
    times out the watchdog and fails on _device_wedged_at.)"""
    import time as _t

    from parca_agent_tpu.capture.replay import ReplaySource

    snap = _snap(seed=14)

    class Collect:
        def __init__(self):
            self.got = []

        def write(self, labels, blob):
            self.got.append((labels, blob))

    agg = DictAggregator(capacity=1 << 11)
    w = Collect()
    p = CPUProfiler(source=ReplaySource([snap, snap]), aggregator=agg,
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True)
    # Warm iteration with the default device budget: the one-shot
    # window_counts XLA compile must not be what trips the tiny timeout
    # below — this test is about the ENCODE being outside the watchdog.
    assert p.run_iteration()
    assert p._device_wedged_at is None
    w.got.clear()

    real_encode = p._encoder.encode

    def slow_encode(*a, **kw):
        _t.sleep(0.5)                    # slower than device_timeout_s
        return real_encode(*a, **kw)

    p._encoder.encode = slow_encode
    p._device_timeout = 0.15
    assert p.run_iteration()
    assert p.last_error is None
    assert p._device_wedged_at is None   # slow ENCODE is not a wedged DEVICE
    assert len(w.got) > 0
    # Fast-path blobs, not scalar-fallback profiles: parseable bytes with
    # the window's full mass.
    from parca_agent_tpu.pprof.builder import parse_pprof

    total = sum(sum(v[0] for _, v, _ in parse_pprof(b).samples)
                for _, b in w.got)
    assert total == snap.total_samples()


@pytest.mark.parametrize("compiles", [True, False],
                         ids=["asks_xla_for_a_program", "asks_for_nothing"])
def test_a_later_feed_gets_the_long_budget_only_while_it_compiles(compiles):
    """A feed after a run's first that meets a shape the first did not
    compiles on the feed thread: XLA is asked for a program inside it
    (the stub raises the event JAX raises on the asking thread as a
    compile request begins, and sleeps in the compile's place), so the
    watchdog gives it the first feed's long budget and streaming stays
    on. The same slowness with no request is a hang: it trips at the
    short timeout, as ever."""
    import time

    from jax import monitoring

    snap = _snap(seed=21, n=60, pids=2)
    slow = {"on": False}

    class Slow(DictAggregator):
        def feed(self, *a, **kw):
            if slow["on"]:
                if compiles:
                    monitoring.record_event(
                        "/jax/compilation_cache/compile_requests_use_cache")
                time.sleep(0.8)
            return super().feed(*a, **kw)

    agg = Slow(capacity=1 << 10)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()),
                                   feed_timeout_s=0.2,
                                   first_feed_timeout_s=20.0)
    feeder.on_drain(_cols(snap, 0, 30))        # the run's first feed
    assert not feeder.disabled
    before = dict(feeder.stats)
    slow["on"] = True
    t0 = time.monotonic()
    feeder.on_drain(_cols(snap, 30, 60))
    took = time.monotonic() - t0
    if compiles:
        assert not feeder.disabled and took >= 0.8
        assert feeder.stats["drains_fed"] == 2
        assert feeder.stats["feed_compiles"] == before["feed_compiles"] + 1
        # 0.8 s of a 20 s budget: the watchdog had room.
        assert feeder.stats["feeds_slow"] == before["feeds_slow"]
        counts = feeder.take_window_if_complete(snap)
        assert int(counts.sum()) == snap.total_samples()
    else:
        assert feeder.disabled and took < 0.8
        assert feeder.device_blocked()
        assert feeder.stats["feed_compiles"] == before["feed_compiles"]
        assert feeder.stats["feeds_slow"] == before["feeds_slow"] + 1
        for _ in range(100):                   # let the abandoned feed end
            if not feeder.device_blocked():
                break
            time.sleep(0.05)
        assert not feeder.device_blocked()
    m = feeder.metrics()
    assert m["parca_agent_streaming_feed_compiles_total"] \
        == feeder.stats["feed_compiles"]
    assert m["parca_agent_streaming_feeds_slow_total"] \
        == feeder.stats["feeds_slow"]
    assert "parca_agent_streaming_feeds_slow" not in m
    assert "parca_agent_streaming_feed_compiles" not in m


def test_compile_requests_are_counted_for_the_asking_thread_alone():
    """``compile_requests_here`` counts what its own thread asks XLA
    for, while the block is open, and nothing another thread asks."""
    import threading

    from jax import monitoring

    from parca_agent_tpu.runtime import device_telemetry

    event = "/jax/compilation_cache/compile_requests_use_cache"
    mine, theirs = [0], [0]
    with device_telemetry.compile_requests_here(mine):
        monitoring.record_event(event)
        t = threading.Thread(target=monitoring.record_event, args=(event,))
        t.start()
        t.join()

        def other():
            with device_telemetry.compile_requests_here(theirs):
                monitoring.record_event(event)
                monitoring.record_event("/jax/compilation_cache/cache_hits")

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert (mine, theirs) == ([1], [1])
    monitoring.record_event(event)             # the block is closed
    assert mine == [1]
