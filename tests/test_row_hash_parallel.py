"""The row hash across cores (docs/perf.md): a large batch is hashed as
disjoint row ranges on several threads, a small one by the serial call
on the calling thread, and the bits are the same whichever ran: against
the serial native call (the plain twin, `pa_row_hash`) and against the
numpy lane-matrix twin (`PARCA_NO_NATIVE_HASH=1`).
"""

from __future__ import annotations

import functools
import sys
import threading

import numpy as np
import pytest

from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.ops import hashing
from parca_agent_tpu.runtime.trace import FlightRecorder
from parca_agent_tpu.utils import faults

R = hashing._HASH_RANGE_ROWS
SLOTS = 128


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Native path on, no injector left behind, and workers to hand
    whatever cores the test machine has."""
    monkeypatch.delenv("PARCA_NO_NATIVE_HASH", raising=False)
    monkeypatch.setattr(hashing, "_hash_workers", lambda: 3)
    yield
    faults.install(None)


@functools.lru_cache(maxsize=2)
def _rows(n: int, depth: str = "poisson"):
    """(stacks, pids, user_len, kernel_len) of `n` contract-valid rows:
    zero past the depth, no zero frame inside it."""
    rng = np.random.default_rng([n, len(depth)])
    if depth == "poisson":
        d = rng.poisson(24, n).clip(0, SLOTS)
    elif depth == "empty":
        d = np.zeros(n, np.int64)
    elif depth == "full":
        d = np.full(n, SLOTS, np.int64)
    else:  # rows of no frame and of every slot, side by side
        d = np.where(np.arange(n) % 2, SLOTS, 0)
    stacks = np.zeros((n, SLOTS), np.uint64)
    live = np.arange(SLOTS)[None, :] < d[:, None]
    stacks[live] = rng.integers(1, 1 << 63, int(live.sum()), dtype=np.uint64)
    ulen = (d * 4 // 5).astype(np.int32)
    klen = (d - ulen).astype(np.int32)
    pids = rng.integers(1, 50_000, n).astype(np.int32)
    return stacks, pids, ulen, klen


def _serial(rows, n_hashes):
    """The plain twin: one `pa_row_hash` call over every row."""
    stacks, pids, ulen, klen = rows
    lib = hashing._load_native()
    n = len(stacks)
    depth = (ulen.astype(np.int64) + klen).astype(np.int32)
    coefs, biases = hashing.hash_params(n_hashes, SLOTS)
    out = np.empty((n_hashes, n), np.uint32)
    assert lib.pa_row_hash(
        stacks.ctypes.data, n, SLOTS, pids.view(np.uint32).ctypes.data,
        ulen.view(np.uint32).ctypes.data, klen.view(np.uint32).ctypes.data,
        depth.ctypes.data, coefs.ctypes.data, coefs.shape[1],
        biases.ctypes.data, n_hashes, out.ctypes.data) == -1
    return tuple(out)


def _same(a, b):
    return len(a) == len(b) and all(
        x.dtype == np.uint32 and np.array_equal(x, y) for x, y in zip(a, b))


# Below, at and above the split: one row, a range less one, the last
# serial count, the first ranged one, a ragged last range, a firehose
# window.
COUNTS = (1, R - 1, 2 * R - 1, 2 * R, 3 * R + 17, 262_144)


@pytest.mark.parametrize("n_hashes", (1, 2, 3))
@pytest.mark.parametrize("n", COUNTS)
def test_ranged_hash_gives_the_serial_and_the_numpy_bits(n, n_hashes,
                                                         monkeypatch):
    rows = _rows(n)
    facts: dict = {}
    got = hashing.row_hash_np(*rows, n_hashes=n_hashes, facts=facts)
    ranges = n // R if n >= 2 * R else 1
    assert facts == {"ranges": ranges, "threads": min(3, ranges - 1) + 1}
    assert _same(got, _serial(rows, n_hashes))
    monkeypatch.setenv("PARCA_NO_NATIVE_HASH", "1")
    facts = {}
    twin = hashing.row_hash_np(*rows, n_hashes=n_hashes, facts=facts)
    assert facts == {}                  # the numpy path says nothing
    assert _same(got, twin)


@pytest.mark.parametrize("depth", ("empty", "full", "mixed"))
@pytest.mark.parametrize("n", (2 * R - 1, 2 * R + 5))
def test_rows_of_no_frame_and_of_every_slot(n, depth, monkeypatch):
    rows = _rows(n, depth)
    got = hashing.row_hash_np(*rows, n_hashes=3)
    assert _same(got, _serial(rows, 3))
    monkeypatch.setenv("PARCA_NO_NATIVE_HASH", "1")
    assert _same(got, hashing.row_hash_np(*rows, n_hashes=3))


@pytest.mark.parametrize("n", (R, 4 * R))
def test_non_contiguous_rows_are_declined_to_numpy_as_before(n):
    stacks, pids, ulen, klen = _rows(2 * n)
    every_other = stacks[::2]
    assert not every_other.flags.c_contiguous
    facts: dict = {}
    got = hashing.row_hash_np(every_other, pids[::2], ulen[::2], klen[::2],
                              n_hashes=3, facts=facts)
    assert facts == {}                  # the native kernel never ran
    copy = (np.ascontiguousarray(every_other), pids[::2].copy(),
            ulen[::2].copy(), klen[::2].copy())
    assert _same(got, _serial(copy, 3))


@pytest.mark.parametrize("columns", ("int32", "int64", "uint32", "lists",
                                     "strided"))
@pytest.mark.parametrize("n", (257, 2 * R + 5))
def test_columns_of_any_kind_hash_as_the_int32_columns_do(n, columns):
    """The wrapper views an int32 column in place and converts any
    other: the bits are the int32 columns'."""
    stacks, pids, ulen, klen = _rows(n)
    want = _serial((stacks, pids, ulen, klen), 3)
    if columns == "lists":
        cols = [c.tolist() for c in (pids, ulen, klen)]
    elif columns == "strided":
        cols = [np.repeat(c, 2)[::2] for c in (pids, ulen, klen)]
        assert not cols[0].flags.c_contiguous
    else:
        cols = [c.astype(columns) for c in (pids, ulen, klen)]
    assert _same(hashing.row_hash_np(stacks, *cols, n_hashes=3), want)


def test_ranged_entry_point_refuses_a_range_outside_the_rows():
    stacks, pids, ulen, klen = _rows(64)
    lib = hashing._load_native()
    depth = (ulen.astype(np.int64) + klen).astype(np.int32)
    coefs, biases = hashing.hash_params(3, SLOTS)
    out = np.full((3, 64), 7, np.uint32)
    args = (stacks.ctypes.data, 64, SLOTS, pids.ctypes.data,
            ulen.ctypes.data, klen.ctypes.data, depth.ctypes.data,
            coefs.ctypes.data, coefs.shape[1], biases.ctypes.data, 3,
            out.ctypes.data)
    for i0, i1 in ((-1, 4), (0, 65), (9, 8)):
        assert lib.pa_row_hash_range(*args, i0, i1) == 0
    assert lib.pa_row_hash_range(*args, 8, 8) == -1   # empty: nothing to do
    assert (out == 7).all()
    assert lib.pa_row_hash_range(*args, 8, 24) == -1
    want = np.stack(_serial((stacks, pids, ulen, klen), 3))
    assert np.array_equal(out[:, 8:24], want[:, 8:24])
    assert (out[:, :8] == 7).all() and (out[:, 24:] == 7).all()


# -- the size rule ------------------------------------------------------------


@functools.lru_cache(maxsize=4)
def _snap(rows: int, seed: int = 3):
    return generate(SyntheticSpec(n_pids=64, n_unique_stacks=rows,
                                  n_rows=rows, total_samples=rows * 3,
                                  mean_depth=8, seed=seed))


# A node's drain and window (capture/replay.py; benchmarks/configs/
# node.json), and the last count under the rule.
@pytest.mark.parametrize("n", (1, 6_400, 10_240, 2 * R - 1))
def test_a_batch_under_the_size_rule_makes_and_uses_no_worker(n, monkeypatch):
    monkeypatch.setattr(hashing, "_pool", None)
    monkeypatch.setattr(hashing, "_hash_workers",
                        lambda: pytest.fail("the rule is the row count"))
    before = set(threading.enumerate())
    agg = DictAggregator(capacity=1 << 12, overflow="raise")
    rec = FlightRecorder()
    tr = rec.begin()
    snap = _snap(n)
    with tr.span("close"):
        got = agg.hash_rows(snap)
    assert _same(got, _serial((snap.stacks, snap.pids, snap.user_len,
                               snap.kernel_len), 3))
    assert hashing._pool is None
    assert set(threading.enumerate()) == before
    assert agg.stats["hash_parallel_batches"] == 0
    assert agg.stats["hash_parallel_fallbacks"] == 0
    assert (tr.meta["hash_ranges"], tr.meta["hash_threads"]) == (1, 1)


@pytest.mark.parametrize("cores, workers", ((1, 0), (2, 0), (3, 1), (6, 4),
                                            (13, 4), (64, 4)))
def test_workers_follow_the_cores_the_process_may_run_on(cores, workers,
                                                         monkeypatch):
    monkeypatch.undo()                  # the real _hash_workers
    monkeypatch.setattr(hashing.os, "sched_getaffinity",
                        lambda _pid: set(range(cores)))
    assert hashing._hash_workers() == workers
    rows = _rows(2 * R)
    facts: dict = {}
    got = hashing.row_hash_np(*rows, n_hashes=3, facts=facts)
    # Two ranges: one worker beside the calling thread, where there is one.
    assert facts == {"ranges": 2 if workers else 1,
                     "threads": 2 if workers else 1}
    assert _same(got, _serial(rows, 3))


def test_a_large_batch_counts_on_the_window_and_on_metrics():
    from parca_agent_tpu.capture.replay import ReplaySource
    from parca_agent_tpu.profiler.cpu import CPUProfiler
    from parca_agent_tpu.web import render_metrics

    agg = DictAggregator(capacity=1 << 12, overflow="raise")
    snap = _snap(2 * R + 11)
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("close"):
        got = agg.hash_rows(snap)
    assert _same(got, _serial((snap.stacks, snap.pids, snap.user_len,
                               snap.kernel_len), 3))
    assert (tr.meta["hash_ranges"], tr.meta["hash_threads"]) == (2, 2)
    assert agg.stats["hash_parallel_batches"] == 1
    assert agg.stats["hash_parallel_fallbacks"] == 0
    assert any(t.name.startswith("row-hash") for t in threading.enumerate())
    text = render_metrics([CPUProfiler(source=ReplaySource([]),
                                       aggregator=agg)])
    lines = [ln for ln in text.splitlines() if not ln.startswith("#")]
    at = lines.index(
        'parca_agent_feed_hash_parallel_batches_total{profiler="cpu"} 1')
    assert lines[at + 1] == \
        'parca_agent_feed_hash_parallel_fallbacks_total{profiler="cpu"} 0'


# -- fail open, counted -------------------------------------------------------


@pytest.mark.chaos
def test_feed_hash_fault_gives_the_serial_bits_and_counts_one_fallback():
    """A range that raises (chaos site feed.hash) costs the batch its
    other cores and nothing else: the serial call hashes every row, the
    window closes exact, the next one is ranged again."""
    snap = _snap(2 * R + 11)
    want = _serial((snap.stacks, snap.pids, snap.user_len,
                    snap.kernel_len), 3)
    faults.install(faults.FaultInjector.from_spec(
        "feed.hash:error:count=1", seed=42))
    agg = DictAggregator(capacity=1 << 17, overflow="raise")
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("close"):
        assert _same(agg.hash_rows(snap), want)
    assert agg.stats["hash_parallel_fallbacks"] == 1
    assert agg.stats["hash_parallel_batches"] == 0
    assert (tr.meta["hash_ranges"], tr.meta["hash_threads"]) == (1, 1)
    assert faults.get().stats().get("feed.hash") == 1
    assert _same(agg.hash_rows(snap), want)   # rule exhausted: ranged again
    assert agg.stats["hash_parallel_batches"] == 1
    assert agg.stats["hash_parallel_fallbacks"] == 1
    # The whole window through the fallback, and through the ranges.
    faults.install(faults.FaultInjector.from_spec(
        "feed.hash:error:count=1", seed=42))
    first = agg.window_counts(snap)
    assert agg.stats["hash_parallel_fallbacks"] == 2
    assert int(first.sum()) == snap.total_samples()   # windows_lost == 0
    again = agg.window_counts(snap)
    assert agg.stats["hash_parallel_batches"] == 2
    assert np.array_equal(first, again)


@pytest.mark.chaos
@pytest.mark.parametrize("n", (2 * R, 8 * R))
def test_a_fault_in_every_range_still_gives_the_serial_bits(n):
    rows = _rows(n)
    faults.install(faults.FaultInjector.from_spec("feed.hash:error", seed=1))
    facts: dict = {}
    got = hashing.row_hash_np(*rows, n_hashes=3, facts=facts)
    assert facts == {"ranges": 1, "threads": 1, "fallback": 1}
    assert _same(got, _serial(rows, 3))


@pytest.mark.parametrize("broken", ("pool", "submit"))
def test_workers_that_cannot_start_leave_the_serial_call(broken, monkeypatch):
    def no_thread(*_a, **_kw):
        raise RuntimeError("can't start new thread")

    if broken == "pool":
        monkeypatch.setattr(hashing, "_hash_pool", no_thread)
    else:
        class _Pool:
            submit = staticmethod(no_thread)

        monkeypatch.setattr(hashing, "_hash_pool", lambda _w: _Pool())
    rows = _rows(3 * R + 17)
    facts: dict = {}
    got = hashing.row_hash_np(*rows, n_hashes=3, facts=facts)
    assert facts == {"ranges": 1, "threads": 1, "fallback": 1}
    assert _same(got, _serial(rows, 3))


def test_a_worker_that_never_wakes_costs_nothing(monkeypatch):
    """The calling thread drains the queue of ranges itself and cancels
    the tasks no worker took up."""
    class _Never:
        def __init__(self):
            self.cancelled = 0

        def submit(self, _fn):
            pool = self

            class _F:
                def cancel(self):
                    pool.cancelled += 1
                    return True

            return _F()

    pool = _Never()
    monkeypatch.setattr(hashing, "_hash_pool", lambda _w: pool)
    rows = _rows(8 * R)
    facts: dict = {}
    got = hashing.row_hash_np(*rows, n_hashes=3, facts=facts)
    assert facts == {"ranges": 8, "threads": 4}
    assert pool.cancelled == 3
    assert _same(got, _serial(rows, 3))


# -- shared workers -----------------------------------------------------------


@pytest.mark.parametrize("callers", (2, 3, 12))
def test_aggregators_hashing_at_once_share_the_workers(callers):
    """The capture thread and a fleet actor hash their own windows at
    the same time (and, as a stress, more callers than cores under a
    short switch interval): one pool, and each gets its own rows' bits."""
    snaps = [_snap(2 * R + 11), _snap(3 * R + 5, seed=4)]
    snaps = [snaps[i % 2] for i in range(callers)]
    want = [_serial((s.stacks, s.pids, s.user_len, s.kernel_len), 3)
            for s in snaps]
    aggs = [DictAggregator(capacity=1 << 12, overflow="raise")
            for _ in snaps]
    start = threading.Barrier(callers)
    rounds = 6 if callers < 4 else 3
    bad: list = []

    def hash_windows(i):
        try:
            start.wait(10)
            for _ in range(rounds):
                if not _same(aggs[i].hash_rows(snaps[i]), want[i]):
                    bad.append(i)
        except Exception as e:  # noqa: BLE001 - reported below
            bad.append(repr(e))

    threads = [threading.Thread(target=hash_windows, args=(i,))
               for i in range(callers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not bad
    assert [a.stats["hash_parallel_batches"] for a in aggs] \
        == [rounds] * callers
    assert [a.stats["hash_parallel_fallbacks"] for a in aggs] == [0] * callers
    hashers = [t for t in threading.enumerate()
               if t.name.startswith("row-hash")]
    assert 1 <= len(hashers) <= hashing._HASH_WORKERS_MAX
