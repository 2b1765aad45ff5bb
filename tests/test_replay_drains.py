"""The streamed path on fixtures: a replay source that drains
(``--replay-drains``), the streaming feeder fed from it through the
capture-source protocol, and what a streamed window leaves in the
flight recorder and on ``/metrics``.

A window's answer does not depend on how it arrived: a streamed replay
equals the one-shot run and the benchmark's plain reference
(``benchmarks/lib/reference.py`` through ``lib/compare.py``, every limit
0), with ``dict`` and ``dict+cm``, under ``steady`` and under
``turnover``.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import importlib
import os
import re
import sys
import threading
import time

import numpy as np
import pytest
from span_scenarios import run_windows, streamed_profiler, turnover_windows

from parca_agent_tpu.aggregator import dict as dict_mod
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.formats import load_snapshot, save_snapshot
from parca_agent_tpu.capture.replay import ReplaySource, drain_shares
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.ops.hashing import row_hash_np
from parca_agent_tpu.profiler.streaming import (
    FALLBACK_REASONS,
    StreamingWindowFeeder,
)
from parca_agent_tpu.runtime import trace as trace_mod
from parca_agent_tpu.runtime.trace import FlightRecorder
from parca_agent_tpu.utils import faults
from parca_agent_tpu.web import render_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")


@pytest.fixture(autouse=True)
def _nothing_left_installed():
    yield
    faults.install(None)
    trace_mod.install(None)


def _snap(seed=1, rows=300, pids=6, per_row=4):
    return generate(SyntheticSpec(n_pids=pids, n_unique_stacks=rows,
                                  n_rows=rows, total_samples=rows * per_row,
                                  mean_depth=8, seed=seed))


def _with_counts(snap, counts):
    return dataclasses.replace(snap, counts=np.asarray(counts, np.int64))


# -- the split ---------------------------------------------------------------


def _counts_case(case: str, k: int, n: int = 40) -> np.ndarray:
    if case == "ones":
        return np.ones(n, np.int64)
    if case == "k_minus_1":
        return np.full(n, max(1, k - 1), np.int64)
    if case == "k":
        return np.full(n, k, np.int64)
    if case == "million":
        return np.full(n, 10**6, np.int64)
    # Every kind in one window, in a seeded order.
    return np.random.default_rng(11).permutation(np.concatenate([
        np.ones(n, np.int64), np.full(n, max(1, k - 1), np.int64),
        np.full(n, k, np.int64), np.full(3, 10**6, np.int64)]))


@pytest.mark.parametrize("case", ["ones", "k_minus_1", "k", "million",
                                  "mixed"])
@pytest.mark.parametrize("k", [1, 3, 10])
def test_sample_j_lands_in_drain_j_mod_k(k, case):
    counts = _counts_case(case, k)
    shares = drain_shares(counts, k)
    assert shares.shape == (k, len(counts)) and shares.dtype == np.int64
    assert (shares >= 0).all()
    assert np.array_equal(shares.sum(axis=0), counts)      # the sum per row
    total = int(counts.sum())
    # Drain d holds the samples j with j mod k == d, counted in row order.
    want = [len(range(d, total, k)) for d in range(k)]
    assert shares.sum(axis=1).tolist() == want
    assert max(want) - min(want) <= 1
    # A row of c samples is in min(c, k) drains; k or more, in every one.
    assert np.array_equal((shares > 0).sum(axis=0), np.minimum(counts, k))
    # And it is the plain definition, sample by sample (small cases).
    if total <= 10_000:
        owner = np.repeat(np.arange(len(counts)), counts)
        plain = np.zeros_like(shares)
        np.add.at(plain, (np.arange(total) % k, owner), 1)
        assert np.array_equal(shares, plain)


class _Tee:
    def __init__(self):
        self.chunks, self.at = [], []

    def __call__(self, cols):
        self.chunks.append(cols)
        self.at.append(time.monotonic())


@pytest.mark.parametrize("k", [1, 3, 10])
def test_a_replayed_window_arrives_as_k_drains_then_its_snapshot(k, tmp_path):
    snap = _with_counts(_snap(seed=3), _counts_case("mixed", k, n=99))
    path = str(tmp_path / "w.snap")
    save_snapshot(snap, path)
    src = ReplaySource([path], drains=k, period_s=0.2)
    t0 = time.monotonic()
    if k == 1:
        # Today's behaviour: the snapshot in one piece, at once, from a
        # source that has no streaming half.
        assert not hasattr(src, "on_drain")
        got = src.poll()
        assert time.monotonic() - t0 < 0.2
    else:
        assert src.on_drain is None
        tee = src.on_drain = _Tee()
        got = src.poll()
        assert time.monotonic() - t0 >= 0.2
    today = load_snapshot(path)
    for col in ("pids", "tids", "counts", "user_len", "kernel_len", "stacks"):
        assert np.array_equal(getattr(got, col), getattr(today, col)), col
    assert src.poll() is None
    if k == 1:
        return
    assert len(tee.chunks) == k
    # period / k apart, the last one at the end of the period.
    for d, at in enumerate(tee.at):
        assert at - t0 >= 0.2 * (d + 1) / k - 1e-3
    shares = drain_shares(snap.counts, k)
    for d, cols in enumerate(tee.chunks):
        assert len(cols) == 9                  # the v1h drain's columns
        pids, tids, ulen, klen, stacks, counts, h1, h2, h3 = cols
        rows = np.flatnonzero(shares[d])       # a zero share: absent
        assert np.array_equal(pids, snap.pids[rows])
        assert np.array_equal(tids, snap.tids[rows])
        assert np.array_equal(ulen, snap.user_len[rows])
        assert np.array_equal(klen, snap.kernel_len[rows])
        assert np.array_equal(stacks, snap.stacks[rows])
        assert np.array_equal(counts, shares[d][rows]) and (counts > 0).all()
        want = row_hash_np(snap.stacks[rows], snap.pids[rows],
                           snap.user_len[rows], snap.kernel_len[rows], 3)
        for a, b in zip((h1, h2, h3), want):
            assert a.dtype == np.uint32 and np.array_equal(a, b)
    assert sum(int(c[5].sum()) for c in tee.chunks) == snap.total_samples()


def test_a_failing_tee_is_dropped_and_the_window_still_arrives():
    src = ReplaySource([_snap(seed=5), _snap(seed=6)], drains=4)
    calls = []

    def tee(cols):
        calls.append(len(cols[0]))
        raise RuntimeError("boom")

    src.on_drain = tee
    assert src.poll() is not None and src.on_drain is None
    assert len(calls) == 1
    assert src.poll() is not None


# -- the capture-source protocol: the drain's table comes from the source -----


class _Maps:
    """Two mappings a pid, as ``/proc/<pid>/maps`` would list them."""

    def __init__(self):
        self.asked = []

    def executable_mappings(self, pid):
        from parca_agent_tpu.process.maps import ProcMapping

        self.asked.append(pid)
        return [ProcMapping(0x1000 * (i + 1), 0x1000 * (i + 1) + 0x800,
                            "r-xp", 0, "08:01", 7, f"/bin/app{i}")
                for i in range(2)]


class _Objs:
    def build_ids(self, per_pid):
        return {}

    def get(self, pid, mapping):
        return None                 # unreadable: the base is start - offset


def _perf_sampler_without_perf():
    """The perf sampler's own ``mapping_table`` over fake caches
    (``perf_event_open`` is refused in the sandbox)."""
    from parca_agent_tpu.capture.live import PerfEventSampler

    s = PerfEventSampler.__new__(PerfEventSampler)
    s._maps, s._objs, s._quarantine, s._tables = _Maps(), _Objs(), None, None
    s.on_drain, s.capture_stack = None, False
    return s


@pytest.mark.parametrize("kind", ["live", "replay"])
def test_the_feeder_takes_its_table_from_the_source(kind):
    snap = _snap(seed=7)
    source = _perf_sampler_without_perf() if kind == "live" \
        else ReplaySource([snap], drains=5)
    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, source)
    tables = []
    sound = source.mapping_table

    def mapping_table(pids):
        tables.append((list(pids), sound(pids)))
        return tables[-1][1]

    source.mapping_table = mapping_table
    if kind == "live":
        shares = drain_shares(snap.counts, 5)
        for d in range(5):
            rows = np.flatnonzero(shares[d])
            feeder.on_drain((snap.pids[rows], snap.tids[rows],
                             snap.user_len[rows], snap.kernel_len[rows],
                             snap.stacks[rows], shares[d][rows]))
    else:
        source.on_drain = feeder.on_drain
        assert source.poll() is snap
    assert len(tables) == 5
    for pids, table in tables:
        assert pids == sorted(set(pids))          # the drain's pids, once
        if kind == "live":
            assert sorted(set(table.pids.tolist())) == pids
        else:
            # The rows of the open window's own table, for those pids.
            want = snap.mappings
            keep = np.isin(want.pids, pids)
            assert np.array_equal(table.pids, want.pids[keep])
            assert np.array_equal(table.starts, want.starts[keep])
            assert table.obj_paths == want.obj_paths
    if kind == "live":
        assert sorted(set(source._maps.asked)) \
            == sorted(set(snap.pids.tolist()))
    counts = feeder.take_window_if_complete(snap)
    assert counts is not None and int(counts.sum()) == snap.total_samples()
    assert feeder.stats["drains_fed"] == 5
    assert agg.stats["rows_fed"] > 0
    # Outside a window the replay source has no table to answer from.
    if kind == "replay":
        assert len(sound([int(snap.pids[0])]).pids) == 0


# -- feed shapes --------------------------------------------------------------


@pytest.fixture()
def pads(monkeypatch):
    """Every feed dispatched: (rows padded to, the packed buffer's shape)."""
    seen = []
    dispatch = DictAggregator._feed_dispatch_async

    def spy(self, packed, n_pad, reset):
        seen.append((n_pad, packed.shape))
        return dispatch(self, packed, n_pad, reset)

    monkeypatch.setattr(DictAggregator, "_feed_dispatch_async", spy)
    return seen


@pytest.mark.parametrize("rows", [1, 17, 300, 1000])
def test_small_feeds_share_one_program_shape(rows, pads):
    """A streamed window's later drains ship only the stacks the carry
    cache has not met, a count that differs from drain to drain: up to
    the pad floor every one of them runs the same ``jit_feed`` program,
    so none compiles on the feed thread in a later window."""
    agg = DictAggregator(capacity=1 << 13)
    snap = _snap(seed=rows, rows=rows, pids=min(rows, 6))
    agg.feed(snap)
    assert int(agg.close_window().sum()) == snap.total_samples()
    floor = dict_mod._FEED_PAD_MIN
    assert pads and all(p == (floor, (4, floor)) for p in pads)


def test_a_feed_above_the_pad_floor_keeps_its_power_of_two(pads):
    agg = DictAggregator(capacity=1 << 14)
    snap = _snap(seed=3, rows=1500, pids=6)
    agg.feed(snap)
    assert int(agg.close_window().sum()) == snap.total_samples()
    assert pads[0] == (2048, (4, 2048))


@pytest.mark.parametrize("carry", [True, False])
def test_a_carrying_runs_first_feed_runs_every_shape_under_its_own(
        carry, pads):
    """With the carry cache every later feed is smaller than a run's
    first, by how much follows the draw: the first feed (the one the
    feeder gives its long budget) runs every power of two from its own
    shape down to the floor, each as an all-padding batch that counts
    nothing, so no later feed at or under its size compiles. Without
    the cache (one-shot windows) a feed runs its own shape alone."""
    agg = DictAggregator(capacity=1 << 15, carry=carry)
    snap = _snap(seed=5, rows=5000, pids=6)
    agg.feed(snap)
    assert [p[0] for p in pads] == ([8192, 4096, 2048, 1024] if carry
                                   else [8192])
    assert all(shape == (4, n) for n, shape in pads)
    n_first = len(pads)
    agg.feed(snap)                             # a run meets them once
    assert int(agg.close_window().sum()) == 2 * snap.total_samples()
    assert len(pads) == (n_first if carry else n_first + 1)


def test_a_runs_feeds_use_the_shapes_its_first_window_compiled(pads):
    """Under turnover every window brings stacks the dictionary has not
    met, a different number in every drain: the first window's first
    feed runs the shapes down to the floor, its drains fall through them
    as the carry cache fills, and no later feed asks for a shape the
    first window did not."""
    snaps, _raw = turnover_windows(6, pids=40, stacks=6000, turnover=0.05)
    prof, feeder, agg, sink = streamed_profiler(snaps)
    run_windows(prof, sink, 1)
    first = {p[0] for p in pads}
    assert dict_mod._FEED_PAD_MIN in first and len(first) >= 2
    n_first = len(pads)
    run_windows(prof, sink, 5)
    later = pads[n_first:]
    assert len(later) >= 5                     # every window dispatched
    assert {p[0] for p in later} == {dict_mod._FEED_PAD_MIN}
    assert feeder.stats["windows_streamed"] == 6


def _keys(w):
    """A raw window's rows as (pid, stack) keys."""
    return [(int(p), s[:int(u) + int(k)].tobytes()) for p, s, u, k
            in zip(w.pids, w.stacks, w.user_len, w.kernel_len)]


def test_every_live_key_is_in_the_carry_cache_by_the_end_of_its_window():
    """What the carry cache holds and what its flush gives the close,
    window by window under turnover, against a count made from the
    windows alone. A stack's first drain dispatches it and the settle
    admits it before the next drain is matched, so by the end of a
    window every live (pid, stack) key is in the cache (one entry a
    stack id); a drain row is matched unless it is a new stack's first,
    and the flush holds one row for every stack with a matched row: the
    stacks met before, and the new ones that more than one drain
    held."""
    rec = FlightRecorder()
    trace_mod.install(rec)
    snaps, raw = turnover_windows(5, pids=40, stacks=6000, turnover=0.05)
    prof, feeder, agg, sink = streamed_profiler(snaps, recorder=rec,
                                                capacity=1 << 15)
    run_windows(prof, sink, 5)
    met: set = set()
    flushed = 0
    for w, snap, t in zip(raw, snaps, rec.traces()):
        shares = drain_shares(snap.counts, 10) > 0
        new = np.array([k not in met for k in _keys(w)])
        met.update(_keys(w))
        assert t["meta"]["carry_matched_rows"] \
            == int(shares.sum()) - int(new.sum())
        want = int((~new).sum()) + int((shares[:, new].sum(axis=0) > 1).sum())
        assert t["meta"]["carry_flush_rows"] == want
        flushed += want
        by_stage = {s["stage"]: s for s in t["spans"]}
        assert by_stage["close_carry_flush"]["parent"] \
            == by_stage["close"]["id"]
    assert new.any() and not new.all()         # the last window churned
    assert agg.stats["carry_flush_rows"] == flushed
    assert agg.stats["carry_entries"] == len(agg._carry_h1) \
        == agg._next_id == len(met)
    assert agg.stats.get("sketch_rows", 0) == 0
    assert feeder.stats["windows_streamed"] == 5
    assert feeder.stats["windows_fallback"] == 0


# -- through the profiler: spans, meta, counters ------------------------------

# What a drain records directly under the window's stream_feed span, on
# the capture thread and on the feed thread, and what the close of a
# streamed window records for the last feed's settle.
CAPTURE_STAGES = {"drain_table", "drain_fold", "feed_handoff", "feed_return",
                  "statics_prebuild"}
FEED_THREAD_STAGES = {"feed_carry", "feed_coalesce", "feed_pack",
                      "feed_dispatch", "feed_settle", "feed_miss",
                      "carry_admit"}
CLOSE_SETTLE_STAGES = {"close_settle", "close_miss", "close_carry_admit"}


@pytest.mark.parametrize("traffic", ["steady", "turnover"])
def test_the_span_tree_of_a_streamed_window(traffic):
    """Ten drains are one stream_feed span under drain, the sum of the
    ten; what a drain does is inside it, each stage once, summed, from
    the thread it ran on; no stage stands under two parents; nothing is
    added a second time afterwards."""
    rec = FlightRecorder()
    trace_mod.install(rec)
    snaps, _raw = turnover_windows(
        4, pids=12, stacks=400, turnover=0.25 if traffic == "turnover" else 0)
    prof, feeder, agg, sink = streamed_profiler(snaps, recorder=rec)
    run_windows(prof, sink, 4)
    traces = rec.traces()
    for t in traces:
        spans = t["spans"]
        by_stage = {s["stage"]: s for s in spans}
        assert len(by_stage) == len(spans)      # a stage under one parent
        assert len({s["id"] for s in spans}) == len(spans)
        drain, feed, close = (by_stage[k] for k in
                              ("drain", "stream_feed", "close"))
        assert feed["parent"] == drain["id"]
        assert feed["accumulated"] is True and feed["n"] == 10  # ten drains
        assert feed["thread"] == drain["thread"]
        kids = {s["stage"]: s for s in spans if s["parent"] == feed["id"]}
        assert set(kids) <= CAPTURE_STAGES | FEED_THREAD_STAGES
        assert {"drain_table", "drain_fold", "feed_handoff",
                "feed_return"} <= set(kids)
        for stage, s in kids.items():
            assert s["thread"] == ("stream-feed" if stage
                                   in FEED_THREAD_STAGES else feed["thread"])
            assert s["n"] <= 10
        # stream_feed and close each hold at least their own children.
        assert sum(s["duration_s"] for s in kids.values()) \
            <= feed["duration_s"] + 1e-4
        assert sum(s["duration_s"] for s in spans
                   if s["parent"] == close["id"]
                   and s["stage"] != "delta_fetch") \
            <= close["duration_s"] + 1e-4      # (delta_fetch: close_fetch's)
        assert feed["duration_s"] <= drain["duration_s"] + 1e-5
        # What profiler/cpu.py used to add afterwards from feeder.stats.
        assert not {"feed", "feed_dispatch_overlap", "fetch"} & set(by_stage)
        # The close settles the last feed under names of its own.
        for stage in ("feed_settle", "feed_miss", "miss_plan",
                      "miss_register", "miss_scatter", "carry_admit"):
            if stage in by_stage:
                assert by_stage[stage]["thread"] == "stream-feed", stage
        for stage in CLOSE_SETTLE_STAGES & set(by_stage):
            assert by_stage[stage]["parent"] == close["id"]
        assert t["meta"]["streamed"] == 1 and t["meta"]["drains_fed"] == 10
        assert "stream_reason" not in t["meta"]
    first, last = traces[0], traces[-1]
    assert first["meta"]["rows_fed"] == 400
    assert 0 < first["meta"]["carry_matched_rows"]
    stages = {s["stage"] for s in last["spans"]}
    assert "feed_carry" in stages
    if traffic == "steady":
        # A stationary population's later windows are carried whole: no
        # row is dispatched, so the device has nothing to do.
        assert last["meta"]["rows_fed"] == 0
        assert last["meta"]["carry_matched_rows"] \
            == int((drain_shares(snaps[-1].counts, 10) > 0).sum())
        assert "feed_dispatch" not in stages and "feed_settle" not in stages
    else:
        assert last["meta"]["rows_fed"] > 0 and last["meta"]["misses"] > 0
        assert {"feed_dispatch", "feed_settle", "feed_miss", "miss_register",
                "close_settle"} <= stages
    assert feeder.stats["last_window_carry_s"] > 0
    assert feeder.stats["windows_streamed"] == 4


def test_replay_drains_1_leaves_the_one_shot_span_tree_as_it_is():
    """Handed over in one piece, a window's tree holds no streamed
    stage, and the one-shot settle keeps the feed's names under close."""
    rec = FlightRecorder()
    trace_mod.install(rec)
    snaps, _raw = turnover_windows(3, pids=12, stacks=400, turnover=0.25)
    prof, feeder, agg, sink = streamed_profiler(snaps, drains=1,
                                                recorder=rec)
    run_windows(prof, sink, 3)
    for t in rec.traces():
        by_stage = {s["stage"]: s for s in t["spans"]}
        assert len(by_stage) == len(t["spans"])
        assert not {"stream_feed", "drain_table", "feed_handoff",
                    "close_settle", "close_miss"} & set(by_stage)
        for stage in ("feed_hash", "feed_dispatch", "feed_settle",
                      "feed_miss"):
            assert by_stage[stage]["parent"] == by_stage["close"]["id"]
        assert by_stage["miss_register"]["parent"] \
            == by_stage["feed_miss"]["id"]
        assert all("n" not in s for s in t["spans"])
        assert t["meta"]["streamed"] == 0
        assert t["meta"]["stream_reason"] == "mass_mismatch"


@pytest.mark.parametrize("reason", FALLBACK_REASONS)
def test_a_failed_drain_gives_a_one_shot_window_with_the_same_bytes(reason):
    """The second window's third drain does not reach the device. The
    window is closed one-shot, its bytes are those of the run in which
    nothing failed, and its trace says it was not streamed and why."""
    snaps, _raw = turnover_windows(4, pids=8, stacks=256)  # stationary
    sound_prof, _f, _a, sound_sink = streamed_profiler(snaps, drains=5)
    want = run_windows(sound_prof, sound_sink, 4)

    rec = FlightRecorder()
    trace_mod.install(rec)
    prof, feeder, agg, sink = streamed_profiler(
        snaps, drains=5, recorder=rec, reprobe_base_windows=1,
        **({"feed_timeout_s": 0.05, "first_feed_timeout_s": 30.0}
           if reason == "blocked" else {}))
    source = prof._source
    seen = {"drains": 0}
    tee = source.on_drain
    release = threading.Event()

    def failing_tee(cols):
        seen["drains"] += 1
        if seen["drains"] != 8:                 # window 2, drain 3
            return tee(cols)
        if reason == "cooldown":
            faults.install(faults.FaultInjector.from_spec(
                "actor.feeder:error:count=1"))
            return tee(cols)
        if reason == "blocked":
            # The feed outlives its watchdog: abandoned, still in flight
            # at the boundary, so the window goes to the CPU fallback.
            sound_feed = agg.feed

            def slow_feed(*a, **kw):
                agg.feed = sound_feed
                release.wait(10)
                return sound_feed(*a, **kw)

            agg.feed = slow_feed
            return tee(cols)
        return None                             # a drain the tee never saw

    source.on_drain = failing_tee
    got = run_windows(prof, sink, 2)
    release.set()
    if reason == "blocked":
        deadline = time.monotonic() + 10
        while feeder.device_blocked() and time.monotonic() < deadline:
            time.sleep(0.01)
    got += run_windows(prof, sink, 2)
    traces = rec.traces()
    assert [t["meta"].get("streamed") for t in traces[:2]] == [1, 0]
    assert traces[1]["meta"]["stream_reason"] == reason
    assert feeder.fallback_reasons[reason] >= 1
    assert sum(feeder.fallback_reasons.values()) \
        == feeder.stats["windows_fallback"]
    if reason == "blocked":
        # The CPU fallback shipped it through the scalar builder: the
        # same profiles, not the same bytes.
        from parca_agent_tpu.pprof.builder import parse_pprof

        assert traces[1]["meta"]["path"] == "scalar-fallback"
        for pid, blob in want[1].items():
            a, b = parse_pprof(got[1][pid]), parse_pprof(blob)
            assert sum(v[0] for _l, v, _x in a.samples) \
                == sum(v[0] for _l, v, _x in b.samples)
    else:
        assert traces[1]["meta"]["path"] == "inline"
        assert got[1] == want[1]                # the same bytes
    assert got[0] == want[0]
    # Streaming comes back, and the windows after are the sound run's.
    assert traces[-1]["meta"]["streamed"] == 1
    assert got[3] == want[3]


def test_one_series_a_count_on_metrics():
    snaps, _raw = turnover_windows(3, pids=8, stacks=128, turnover=0.25)
    prof, feeder, agg, sink = streamed_profiler(snaps, drains=4)
    source = prof._source
    tee, seen = source.on_drain, {"n": 0}

    def skipping(cols):
        seen["n"] += 1
        return None if seen["n"] == 6 else tee(cols)

    source.on_drain = skipping
    run_windows(prof, sink, 3)
    lines = [ln for ln in render_metrics(
        [prof], extra=feeder.metrics()).splitlines()
        if not ln.startswith("#")]
    names = [re.split(r"[ {]", ln)[0] for ln in lines]

    def sample(name, labels=""):
        (ln,) = [ln for ln in lines if ln.startswith(name + labels + " ")]
        return float(ln.rsplit(" ", 1)[1])

    assert sample("parca_agent_streaming_windows_streamed_total") == 2
    for reason in FALLBACK_REASONS:
        assert sample("parca_agent_streaming_windows_fallback_total",
                      f'{{reason="{reason}"}}') \
            == (1 if reason == "mass_mismatch" else 0)
    # In place of the gauges that counted the same, not beside them; the
    # counts of rows come from the aggregator's own stats, once each.
    assert "parca_agent_streaming_windows_streamed" not in names
    assert "parca_agent_streaming_windows_fallback" not in names
    assert not [n for n in names if "rows_fed" in n
                and n != "parca_agent_dict_rows_fed_total"]
    assert "parca_agent_feed_carry_hits_total" not in names
    assert sample("parca_agent_streaming_drains_fed") == 11
    assert sample("parca_agent_dict_rows_fed_total", '{profiler="cpu"}') \
        == agg.stats["rows_fed"] > 0
    assert sample("parca_agent_dict_carry_matched_rows_total",
                  '{profiler="cpu"}') == agg.stats["carry_hits"] > 0
    # What the close applied of the cache's fold, the rows handed to the
    # sketch, and the feed watchdog's two counts.
    assert sample("parca_agent_dict_carry_flush_rows_total",
                  '{profiler="cpu"}') == agg.stats["carry_flush_rows"] > 0
    assert sample("parca_agent_dict_sketch_rows_total", '{profiler="cpu"}') \
        == agg.stats.get("sketch_rows", 0)
    assert sample("parca_agent_streaming_feeds_slow_total") == 0
    assert sample("parca_agent_streaming_feed_compiles_total") \
        == feeder.stats["feed_compiles"]
    for name in ("parca_agent_dict_rows_fed_total",
                 "parca_agent_dict_carry_matched_rows_total",
                 "parca_agent_dict_carry_flush_rows_total",
                 "parca_agent_dict_sketch_rows_total",
                 "parca_agent_streaming_feeds_slow_total",
                 "parca_agent_streaming_feed_compiles_total",
                 "parca_agent_streaming_windows_streamed_total"):
        assert names.count(name) == 1


# -- through cli.run(): equal to the one-shot run and to the reference --------


@pytest.fixture()
def bench_lib():
    sys.path.insert(0, BENCH)
    try:
        yield importlib.import_module("lib.compare")
    finally:
        sys.path.remove(BENCH)


def _agent(tmp_path, tag, files, aggregator, extra):
    from parca_agent_tpu.cli import run

    out = tmp_path / tag
    rc = run(["--capture", "replay", "--replay", *files,
              "--aggregator", aggregator, "--aggregator-capacity", "8192",
              "--fast-encode", "--local-store-directory", str(out),
              "--profiling-duration", "0.05", "--http-address",
              "127.0.0.1:0", "--debuginfo-upload-disable",
              "--device-probe-timeout", "0", "--log-level", "error",
              *extra])
    assert rc == 0
    traces = trace_mod.get().traces()
    by_pid: dict[int, list] = {}
    for path in sorted(glob.glob(str(out / "*.pb.gz")),
                       key=lambda p: int(p.rsplit(".", 3)[-3])):
        pid = int(re.search(r"pid=(\d+)\.\d+\.pb\.gz$", path).group(1))
        with open(path, "rb") as f:
            by_pid.setdefault(pid, []).append(gzip.decompress(f.read()))
    return traces, by_pid


@pytest.mark.parametrize("traffic", ["steady", "turnover"])
@pytest.mark.parametrize("aggregator", ["dict", "dict+cm"])
def test_a_streamed_replay_through_cli_run_equals_one_shot_and_reference(
        aggregator, traffic, tmp_path, bench_lib):
    compare = bench_lib
    seed = 2147483659
    _snaps, windows = turnover_windows(
        3, turnover=0.25 if traffic == "turnover" else 0, seed=seed)
    snapfile = importlib.import_module("lib.snapfile")
    files = []
    for i, w in enumerate(windows):
        files.append(str(tmp_path / f"w{i}.snap"))
        snapfile.write_snapshot(w, files[-1])
    _t, one_shot = _agent(tmp_path, "one-shot", files, aggregator, [])
    traces, streamed = _agent(
        tmp_path, "streamed", files, aggregator,
        ["--streaming-window", "--replay-drains", "10"])
    assert [t["meta"]["streamed"] for t in traces] == [1, 1, 1]
    assert [t["meta"]["drains_fed"] for t in traces] == [10, 10, 10]
    assert traces[0]["meta"]["rows_fed"] == 300
    assert traces[2]["meta"]["carry_matched_rows"] > 0
    if traffic == "steady":
        assert traces[2]["meta"]["rows_fed"] == 0     # carried whole
    else:
        assert traces[2]["meta"]["rows_fed"] > 0
    reference = importlib.import_module("lib.reference")
    pprof_write = importlib.import_module("lib.pprof_write")
    born: dict[int, int] = {}
    for i, w in enumerate(windows):
        for pid in np.unique(w.pids).tolist():
            born.setdefault(pid, i)
        blobs = {pid: streamed[pid][i - born[pid]] for pid in born
                 if pid in set(w.pids.tolist())}
        once = {pid: one_shot[pid][i - born[pid]] for pid in blobs}
        numbers = compare.compare_window(w, blobs, seed, 12)
        assert numbers == dict.fromkeys(compare.LIMITS, 0), (i, numbers)
        assert compare.compare_window(w, once, seed, 12) == numbers
        pids = sorted(blobs)
        assert compare.observed_stacks(blobs, pids) \
            == compare.observed_stacks(once, pids)
        # The 8-bit control is not correct here either.
        truth = reference.group_by(w, pids)
        narrowed = {pid: pprof_write.make_pprof(
            w, pid, reference.lower_precision(stacks, 8))
            for pid, stacks in truth.items()}
        assert not compare.verdict(
            compare.compare_window(w, narrowed, seed, 12))


def test_streaming_window_names_the_protocol_when_the_source_lacks_it(
        tmp_path, capsys):
    """Handed over in one piece (``--replay-drains 1``) the replay source
    has no streaming half: the agent says what the flag needs and runs
    one-shot."""
    _snaps, windows = turnover_windows(1)
    sys.path.insert(0, BENCH)
    try:
        snapfile = importlib.import_module("lib.snapfile")
    finally:
        sys.path.remove(BENCH)
    path = str(tmp_path / "w.snap")
    snapfile.write_snapshot(windows[0], path)
    from parca_agent_tpu.cli import run

    rc = run(["--capture", "replay", "--replay", path, "--aggregator",
              "dict", "--aggregator-capacity", "8192", "--fast-encode",
              "--streaming-window", "--local-store-directory",
              str(tmp_path / "out"), "--profiling-duration", "0.05",
              "--http-address", "127.0.0.1:0", "--debuginfo-upload-disable",
              "--device-probe-timeout", "0", "--log-level", "warn"])
    assert rc == 0
    err = capsys.readouterr().err
    assert "on_drain and mapping_table" in err
    (t,) = trace_mod.get().traces()
    assert "streamed" not in t["meta"] and "stream_feed" not in {
        s["stage"] for s in t["spans"]}
