"""Sub-RTT close (docs/perf.md "sub-RTT close"): the device-resident
double-buffered window accumulator and delta-fetch — the swap matrix.

Every arm is gated on exactness: identical counts or identical pprof
bytes against the full-fetch and CPU references.
"""

from __future__ import annotations

import numpy as np
import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.utils import faults
from streaming_sources import CacheSource


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faults.install(None)


def _snap(seed=1, rows=512, pids=8, per_row=3):
    return generate(SyntheticSpec(n_pids=pids, n_unique_stacks=rows,
                                  n_rows=rows, total_samples=rows * per_row,
                                  mean_depth=8, seed=seed))


# -- double-buffered close: the flip, the split API, delta-fetch --------------


def test_split_close_feeds_next_window_while_packing():
    """The tentpole contract: after close_dispatch, feeds belong to the
    next window and land in the flipped-in twin; close_collect fetches
    the closed buffer exactly."""
    snap = _snap(seed=17)
    a = DictAggregator(capacity=1 << 11, overflow="raise")
    h = a.hash_rows(snap)
    a.feed(snap, h)
    first = a.close_window()  # population window
    assert int(first.sum()) == snap.total_samples()

    a.feed(snap, h, 0, 256)
    handle = a.close_dispatch()
    # Mid-flip: the next window's feeds land in the other buffer while
    # window N's pack output is still uncollected.
    a.feed(snap, h, 256, 384)
    a.feed(snap, h, 384, 512)
    got = a.close_collect(handle)
    assert int(got.sum()) == int(snap.counts[:256].sum())
    # The interleaved feeds were not lost and were not double-counted.
    nxt = a.close_window()
    assert int(nxt.sum()) == int(snap.counts[256:512].sum())
    assert a.stats["buffer_flips"] == 3


def test_double_close_without_collect_is_refused():
    snap = _snap(seed=19, rows=64, pids=2)
    a = DictAggregator(capacity=1 << 10, overflow="raise")
    a.feed(snap, a.hash_rows(snap))
    h = a.close_dispatch()
    with pytest.raises(RuntimeError, match="not collected"):
        a.close_dispatch()
    a.close_collect(h)


def test_delta_fetch_engages_and_stays_exact():
    """Steady-state hot set: the delta arm must fetch only touched
    blocks (counted, fewer rows than the full close) with counts equal
    to the full-fetch arm, window by window."""
    snap = _snap(seed=23, rows=4096, pids=32)
    full = DictAggregator(capacity=1 << 14, overflow="raise",
                          delta_fetch=False)
    delt = DictAggregator(capacity=1 << 14, overflow="raise",
                          delta_fetch=True)
    h = full.hash_rows(snap)
    for a in (full, delt):
        a.feed(snap, h)
        a.close_window()  # population window (full fetch; learns flags)
    lo, hi = 512, 1024  # a contiguous ~12% hot set
    for w in range(3):
        full.feed(snap, h, lo, hi)
        delt.feed(snap, h, lo, hi)
        cf = full.close_window()
        cd = delt.close_window()
        assert np.array_equal(cf, cd), w
    assert delt.stats.get("delta_closes", 0) >= 2
    assert delt.stats["fetch_rows_last"] < full.stats["fetch_rows_last"]
    assert delt.stats["fetch_bytes_last"] < full.stats["fetch_bytes_last"]
    assert "delta_fetch" in delt.timings
    assert "delta_fetch" not in full.timings


def test_delta_arm_under_a_split_close_ships_the_full_arms_pprof_bytes():
    """The two arms a close can take, each through its own encoder, over
    a steady hot set fed in drain-sized chunks: the delta arm (from its
    second hot window under the split close, the next window's first
    drain fed between dispatch and collect) ships every window's pprof
    bytes as the full-fetch arm does, and its steady close moves under a
    quarter of the full close's bytes."""
    from parca_agent_tpu.pprof.window_encoder import WindowEncoder

    rows, chunk = 4096, 1024
    snap = _snap(seed=77, rows=rows, pids=32)
    lo, hi = rows // 8, rows // 4
    arms = {k: DictAggregator(capacity=1 << 14, overflow="raise",
                              delta_fetch=k == "delta")
            for k in ("full", "delta")}
    encs = {k: WindowEncoder(a) for k, a in arms.items()}
    h = arms["full"].hash_rows(snap)

    def feed(a, a0, a1):
        for c0 in range(a0, a1, chunk):
            a.feed(snap, h, c0, min(c0 + chunk, a1))

    def blobs(k, counts, w):
        return [(pid, bytes(b)) for pid, b in encs[k].encode(
            counts, 1_000 + w, 10**10, 10**7)]

    shipped = {k: [] for k in arms}
    for k, a in arms.items():
        feed(a, 0, rows)  # the population window: every stack a miss
        c = a.close_window()
        assert int(c.sum()) == snap.total_samples()
        shipped[k].append(blobs(k, c, 0))
    for w in range(1, 5):
        for k, a in arms.items():
            feed(a, lo, hi)
            if k == "delta" and w >= 2:
                handle = a.close_dispatch()
                a.feed(snap, h, lo, lo + chunk // 2)  # lands in the twin
                c = a.close_collect(handle)
                a.discard_open_window()
            else:
                c = a.close_window()
            assert int(c.sum()) == int(snap.counts[lo:hi].sum()), (k, w)
            shipped[k].append(blobs(k, c, w))
    assert shipped["delta"] == shipped["full"]
    assert arms["delta"].stats.get("delta_closes", 0) >= 2
    assert 4 * arms["delta"].stats["fetch_bytes_last"] \
        < arms["full"].stats["fetch_bytes_last"]


def test_delta_misprediction_grows_then_falls_back():
    """A window touching far more blocks than predicted must retry (grow
    to the reported population, or full-fetch once delta stops being a
    win) and still produce exact counts."""
    snap = _snap(seed=29, rows=4096, pids=32)
    a = DictAggregator(capacity=1 << 13, overflow="raise", delta_fetch=True)
    ref = DictAggregator(capacity=1 << 13, overflow="raise",
                         delta_fetch=False)
    h = a.hash_rows(snap)
    for x in (a, ref):
        x.feed(snap, h)
        x.close_window()
    # Train a tiny touched-block history (the population window's feeds
    # were all inserts — misses don't mark touch flags — so its full
    # close learns an empty history and the floor-sized delta engages
    # right away)...
    for _ in range(2):
        for x in (a, ref):
            x.feed(snap, h, 0, 128)
            c = x.close_window()
    assert a.stats.get("delta_closes", 0) == 2
    # ...then blow the prediction: the whole population in one window.
    a.feed(snap, h)
    ref.feed(snap, h)
    got = a.close_window()
    want = ref.close_window()
    assert np.array_equal(got, want)
    assert a.stats.get("delta_retries", 0) >= 1
    # 4096 rows touched vs a ~256-row plan: past _DELTA_MAX_FRAC the
    # retry must land on the exact full fetch.
    assert a.stats.get("delta_fallbacks", 0) >= 1
    assert a.stats.get("delta_guard_trips", 0) == 0


@pytest.mark.parametrize("n_fetch, touched, blocks", [
    # A firehose-sized fetch (4,096 blocks): a streamed window touches a
    # handful of blocks, one more every few windows; the buffer stays at
    # the floor, 1/64 of the full fetch, until 2x the count passes it.
    (1 << 19, 0, 64), (1 << 19, 4, 64), (1 << 19, 5, 64), (1 << 19, 9, 64),
    (1 << 19, 32, 64), (1 << 19, 33, 128), (1 << 19, 1100, 0),
    # A node-sized fetch (2,048 blocks) and a test-sized one (8).
    (1 << 18, 2, 32), (1 << 18, 17, 64), (1 << 11, 1, 8), (1 << 10, 1, 0),
])
def test_the_delta_block_buffer_has_a_floor_its_count_does_not_move(
        n_fetch, touched, blocks):
    """The block buffer is a shape of the close program: sized by the
    touched count alone it crossed a power of two whenever the count
    crept past one (5 blocks: 8 -> 16), a compile on the window's path
    each time. Under the floor (the full fetch's blocks over 64, at
    least 8) the count moves no shape; over it the plan is 2x the count
    as before, and 0 (a full fetch) once that is no saving."""
    import jax.numpy as jnp

    a = DictAggregator(capacity=1 << 12, overflow="raise", delta_fetch=True)
    a._touch = jnp.zeros(1, jnp.int32)         # tracking on
    a._prev_touched = touched
    assert a._delta_plan(n_fetch) == blocks


def test_empty_window_clears_stale_flip_and_delta_timings():
    snap = _snap(seed=31, rows=256, pids=4)
    a = DictAggregator(capacity=1 << 11, overflow="raise")
    h = a.hash_rows(snap)
    a.feed(snap, h)
    a.close_window()
    a.feed(snap, h, 0, 64)
    a.close_window()
    assert "buffer_flip" in a.timings
    a.close_window()  # empty: no flip, no fetch
    assert "buffer_flip" not in a.timings
    assert "delta_fetch" not in a.timings


def test_pending_only_close_clears_stale_delta_timing():
    """A close with host-pending corrections but nothing fed to the
    device runs no fetch: the previous delta close's timing must not
    survive into its trace spans."""
    snap = _snap(seed=33, rows=4096, pids=32)
    a = DictAggregator(capacity=1 << 14, overflow="raise",
                       delta_fetch=True)
    h = a.hash_rows(snap)
    a.feed(snap, h)
    a.close_window()  # full close: learns the touch flags
    a.feed(snap, h)
    a.close_window()  # delta close
    assert a.stats.get("delta_closes", 0) >= 1
    assert "delta_fetch" in a.timings
    a._pending.append((0, 5))  # host-settled correction, nothing fed
    c = a.close_window()
    assert "delta_fetch" not in a.timings
    assert int(c[0]) == 5


def test_unpack_buf_eviction_is_by_size_not_key_order():
    """The bounded unpack-buffer cache evicts the SMALLEST allocation;
    tuple-ordered min() would always victimize the full-close key
    ((0, ...) sorts before every delta (1, ...) key)."""
    a = DictAggregator(capacity=1 << 10, overflow="raise")
    a._unpack_bufs = {
        (0, 1 << 18, 8): np.empty(((1 << 18) // 4, 4), np.uint32),
        (1, 1024, 8): np.empty((256, 4), np.uint32),
        (1, 2048, 8): np.empty((512, 4), np.uint32),
        (1, 4096, 8): np.empty((1024, 4), np.uint32),
    }
    smallest = min(a._unpack_bufs, key=lambda k: a._unpack_bufs[k].nbytes)
    assert smallest == (1, 1024, 8)
    snap = _snap(seed=34, rows=256, pids=4)
    a.feed(snap, a.hash_rows(snap))
    a.close_window()  # inserts a 5th key -> one eviction
    assert len(a._unpack_bufs) == 4
    assert (0, 1 << 18, 8) in a._unpack_bufs  # the big buffer survived
    assert (1, 1024, 8) not in a._unpack_bufs


def test_rotation_drops_both_buffers_and_delta_history():
    """Cold-stack rotation remaps the id space: the spare accumulator
    and the touch flags index the OLD space and must not survive it."""
    a = DictAggregator(capacity=1 << 10, id_cap=256, rotate_min_age=1)
    s1 = _snap(seed=37, rows=200, pids=2)
    s2 = _snap(seed=38, rows=200, pids=2)
    h1 = a.hash_rows(s1)
    a.feed(s1, h1)
    a.close_window()
    assert a._prev_touched is not None  # full close learned the flags
    # Overflow the id space so a rotation is requested...
    a.feed(s2, a.hash_rows(s2))
    a.close_window()
    assert a._rotate_pending
    # ...and the boundary rotation (inside the next window's first feed)
    # must clear every flip-side buffer: the spare accumulator and the
    # delta history index the OLD id space.
    a.feed(s1, h1)
    assert a.stats.get("rotations", 0) == 1
    assert a._acc_spare is None and a._touch_spare is None
    assert a._prev_touched is None
    c = a.close_window()
    assert int(c.sum()) == s1.total_samples()


# -- the one-close counts validity contract under the flip --------------------


def test_counts_view_valid_through_next_close_then_reused():
    """close_window(copy=False) documents one-close validity: the view
    survives the NEXT close (double-buffered) and is overwritten by the
    one after."""
    snap = _snap(seed=41, rows=256, pids=4)
    a = DictAggregator(capacity=1 << 11, overflow="raise")
    h = a.hash_rows(snap)
    a.feed(snap, h)
    a.close_window()
    a.feed(snap, h, 0, 64)
    v1 = a.close_window(copy=False)
    frozen = v1.copy()
    a.feed(snap, h, 64, 128)
    a.close_window(copy=False)  # the OTHER buffer: v1 still intact
    assert np.array_equal(v1, frozen)
    a.feed(snap, h, 128, 256)
    a.close_window(copy=False)  # v1's buffer is recycled here
    assert not np.array_equal(v1, frozen)


def test_pin_counts_removes_buffer_from_reuse_rotation():
    snap = _snap(seed=43, rows=256, pids=4)
    a = DictAggregator(capacity=1 << 11, overflow="raise")
    h = a.hash_rows(snap)
    a.feed(snap, h)
    a.close_window()
    a.feed(snap, h, 0, 64)
    v1 = a.close_window(copy=False)
    frozen = v1.copy()
    a.pin_counts(v1)  # copy-on-hand-off: ownership transfers
    assert all(b is None or (b is not v1 and b.base is not v1)
               for b in a._counts_bufs)
    for lo in (64, 128, 192):
        a.feed(snap, h, lo, lo + 64)
        a.close_window(copy=False)
    assert np.array_equal(v1, frozen)


def test_pipeline_prepare_copies_counts_out_of_the_rotation():
    """The encode pipeline's hand-off (WindowEncoder.prepare on the
    profiler thread) must not retain the aggregator's one-close buffer:
    encoding the prepared window AFTER the buffer is recycled still
    produces the same bytes as an immediate inline encode."""
    from parca_agent_tpu.pprof.window_encoder import WindowEncoder

    snap = _snap(seed=47, rows=256, pids=4)
    a = DictAggregator(capacity=1 << 11, overflow="raise")
    h = a.hash_rows(snap)
    a.feed(snap, h)
    a.close_window()

    ref_enc = WindowEncoder(a)
    pipe_enc = WindowEncoder(a)
    a.feed(snap, h, 0, 64)
    v = a.close_window(copy=False)
    want = ref_enc.encode(v.copy(), 1, 10**10, 10**7)
    prep = pipe_enc.prepare(v, 1, 10**10, 10**7)
    # Recycle the buffer twice before the deferred encode runs (the
    # worker being slow by two whole windows).
    for lo in (64, 128):
        a.feed(snap, h, lo, lo + 64)
        a.close_window(copy=False)
    got = pipe_enc.encode_prepared(prep)
    assert [(p, bytes(b)) for p, b in got] == \
        [(p, bytes(b)) for p, b in want]


# -- feed-during-pack under chaos: zero windows lost --------------------------


@pytest.mark.chaos
def test_dispatch_hang_mid_flip_loses_zero_windows():
    """Chaos acceptance (ISSUE satellite): a device.dispatch hang lands
    on the streamed close — the abandoned call flips the buffers on its
    daemon thread while the profiler ships the window via the CPU
    fallback. Zero windows lost, and once the abandoned call returns the
    streamed path resumes exactly."""
    from parca_agent_tpu.capture.replay import ReplaySource  # noqa: F401
    from parca_agent_tpu.profiler.cpu import CPUProfiler
    from parca_agent_tpu.profiler.streaming import StreamingWindowFeeder

    faults.install(faults.FaultInjector.from_spec(
        "device.dispatch:hang:ms=400,count=1", seed=42))
    snap = _snap(seed=53, rows=200, pids=5)

    class FakeMaps:
        def executable_mappings(self, pid):
            return []

    class FakeObjs:
        def build_ids(self, per_pid):
            return {}

    def _cols(lo, hi):
        return (snap.pids[lo:hi], snap.tids[lo:hi], snap.user_len[lo:hi],
                snap.kernel_len[lo:hi], snap.stacks[lo:hi],
                snap.counts[lo:hi])

    class StreamingSource:
        def __init__(self, feeder, budget):
            self._feeder = feeder
            self._left = budget

        def poll(self):
            if not self._left:
                return None
            self._left -= 1
            for lo in range(0, len(snap), 64):
                self._feeder.on_drain(_cols(lo, min(lo + 64, len(snap))))
            return snap

    class Collect:
        def __init__(self):
            self.got = []

        def write(self, labels, blob):
            self.got.append((labels, blob))

    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()))
    w = Collect()
    p = CPUProfiler(source=StreamingSource(feeder, 6), aggregator=agg,
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True,
                    streaming_feeder=feeder, device_timeout_s=0.05,
                    device_retry_windows=1)
    shipped = 0
    for i in range(6):
        assert p.run_iteration(), i
        assert p.last_error is None, i
        # EVERY window ships — streamed, one-shot, or CPU fallback.
        assert len(w.got) > shipped, i
        shipped = len(w.got)
        if p._device_inflight is not None:
            # The abandoned close (mid-flip on its daemon thread) gates
            # device retry; wait it out like the real loop would.
            assert p._device_inflight.wait(10)
    # The hang cost fallback/one-shot windows, not profiles; the
    # abandoned close completed cleanly (mid-flip, on its daemon
    # thread) and streaming recovered.
    assert p.metrics.attempts_total == 6
    assert p.metrics.errors_total == 0
    assert p.metrics.device_abandoned_ok_total == 1
    assert feeder.stats["windows_streamed"] >= 2
    # Post-recovery exactness: a streamed window equals the oracle.
    per_pid = {}
    for op in CPUAggregator().aggregate(snap):
        per_pid[op.pid] = op.total()
    from parca_agent_tpu.pprof.builder import parse_pprof

    labels, blob = w.got[-1]
    pid = int(labels["pid"])
    got_total = sum(v[0] for _, v, _ in parse_pprof(blob).samples)
    assert got_total == per_pid[pid]


def test_streamed_windows_record_overlap_trace_spans():
    """Satellite wiring (ISSUE): the flight recorder sees the overlap —
    every streamed window carries its feeds' dispatch (feed_dispatch
    under stream_feed, recorded where it ran) and the buffer_flip of
    its close, alongside the PR 7 mandatory set."""
    from parca_agent_tpu.profiler.cpu import CPUProfiler
    from parca_agent_tpu.profiler.streaming import StreamingWindowFeeder
    from parca_agent_tpu.runtime.trace import FlightRecorder

    snap = _snap(seed=73, rows=128, pids=4)

    class FakeMaps:
        def executable_mappings(self, pid):
            return []

    class FakeObjs:
        def build_ids(self, per_pid):
            return {}

    def _cols(lo, hi):
        return (snap.pids[lo:hi], snap.tids[lo:hi], snap.user_len[lo:hi],
                snap.kernel_len[lo:hi], snap.stacks[lo:hi],
                snap.counts[lo:hi])

    class Src:
        def __init__(self, feeder, n):
            self._f, self._n = feeder, n

        def poll(self):
            if not self._n:
                return None
            self._n -= 1
            for lo in range(0, len(snap), 48):
                self._f.on_drain(_cols(lo, min(lo + 48, len(snap))))
            return snap

    class W:
        def write(self, labels, blob):
            pass

    rec = FlightRecorder()
    agg = DictAggregator(capacity=1 << 10)
    feeder = StreamingWindowFeeder(agg, CacheSource(FakeMaps(), FakeObjs()))
    p = CPUProfiler(source=Src(feeder, 3), aggregator=agg,
                    profile_writer=W(), fast_encode=True,
                    streaming_feeder=feeder, trace_recorder=rec)
    for _ in range(3):
        assert p.run_iteration()
        assert p.last_error is None
    streamed = rec.traces()[-1]
    stages = {s["stage"] for s in streamed["spans"]}
    assert {"stream_feed", "feed_dispatch", "buffer_flip"} <= stages
    assert not {"feed", "feed_dispatch_overlap", "fetch"} & stages
    pct = rec.percentiles()
    assert pct["stream_feed"]["count"] >= 1
    assert pct["buffer_flip"]["count"] >= 1


# -- shadow window: double-buffered dict vs the CPU aggregator ----------------


def test_shadow_compare_passes_with_double_buffering_on():
    """The PR 5 promotion gate must hold over the flip/delta machinery:
    profiles built from double-buffered, delta-fetch closes digest-match
    the CPU aggregator's, window after window."""
    from parca_agent_tpu.runtime.device_health import shadow_compare

    snap = _snap(seed=59, rows=1024, pids=16)
    a = DictAggregator(capacity=1 << 13, overflow="raise", delta_fetch=True)
    h = a.hash_rows(snap)
    cpu = CPUAggregator()
    want = cpu.aggregate(snap)
    a.feed(snap, h)
    got = a._build_profiles(snap, a.close_window())
    assert shadow_compare(got, want)
    # Steady-state (delta) windows keep matching a fresh CPU pass over
    # the same hot subset.
    lo, hi = 128, 256
    sub_cpu = CPUAggregator()
    import dataclasses as _dc

    sub = _dc.replace(
        snap, pids=snap.pids[lo:hi], tids=snap.tids[lo:hi],
        user_len=snap.user_len[lo:hi], kernel_len=snap.kernel_len[lo:hi],
        stacks=snap.stacks[lo:hi], counts=snap.counts[lo:hi])
    for w in range(2):
        a.feed(snap, h, lo, hi)
        got = a._build_profiles(snap, a.close_window())
        assert shadow_compare(got, sub_cpu.aggregate(sub)), w
    assert a.stats.get("delta_closes", 0) >= 1
