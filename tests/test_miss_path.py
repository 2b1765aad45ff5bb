"""The miss path under churn: a device write that compiles nothing per
count of new stacks, an exact dictionary that gives ids back, and the
window encoder through both.

Everything is held to the plain reference (``aggregator/cpu.py`` and the
scalar ``pprof/builder.py``) on seeded windows, small, on the CPU. The
windows come from the benchmark's own ``turnover`` generator
(``benchmarks/lib/mixes/turnover.py``) through the snapshot container,
as a replayed run's do.
"""

from __future__ import annotations

import io
import os
import sys

import numpy as np
import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture import load_snapshot
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.pprof.builder import build_pprof, parse_pprof
from parca_agent_tpu.pprof.window_encoder import WindowEncoder
from parca_agent_tpu.profiler.encode_pipeline import EncodePipeline

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _turnover_windows(n: int, pids: int, stacks: int, seed: int = 5,
                      turnover: float = 0.9):
    """``n`` windows of the benchmark's ``turnover`` mix, as the agent's
    replay source would load them."""
    sys.path.insert(0, _BENCH)
    try:
        from lib import generate as bgen
        from lib import snapfile
        from lib.mixes import turnover as mix
    finally:
        sys.path.remove(_BENCH)
    pop = bgen.Population(pids=pids, stacks=stacks,
                          samples_per_window=6 * stacks, mean_depth=8)
    seq = mix.sequence(pop, {"turnover": turnover}, seed)
    return [load_snapshot(io.BytesIO(snapfile.snapshot_bytes(seq.next())))
            for _ in range(n)]


def _reference(snap) -> dict[int, dict]:
    """{pid: {address tuple: count}} and the rest of each profile, by
    the plain reference: the CPU aggregator and the scalar builder."""
    return {p.pid: parse_pprof(build_pprof(p, compress=False))
            for p in CPUAggregator().aggregate(snap)}


def _assert_window_is_the_references(snap, blobs: dict[int, bytes]):
    want = _reference(snap)
    assert set(blobs) == set(want)
    for pid, ref in want.items():
        have = parse_pprof(blobs[pid])
        # A template row with no samples this window is a zero-count
        # row (tests/test_window_encoder.py): compare the observed mass.
        assert {k: v for k, v in have.stacks_by_address().items()
                if v > 0} == ref.stacks_by_address()
        assert have.sample_types == ref.sample_types
        assert have.period == ref.period
        assert have.time_nanos == ref.time_nanos
        assert have.duration_nanos == ref.duration_nanos


# -- no compile per count -----------------------------------------------------

_COMPILES: list[str] = []


def _watch_compiles() -> None:
    """Every backend compile of this process, by name of event (the
    listener cannot be taken off again; it appends to one list)."""
    if not _COMPILES:
        from jax import monitoring

        _COMPILES.append("watching")
        monitoring.register_event_duration_secs_listener(
            lambda event, _s, **_kw: _COMPILES.append(event)
            if event == "/jax/core/compile/backend_compile_duration"
            else None)


def _count_compiles_in_the_miss_path(agg) -> list[int]:
    """Wrap ``agg._resolve_misses`` (on the instance): the list gains,
    per call, how many programs XLA compiled inside it."""
    _watch_compiles()
    seen: list[int] = []
    real = agg._resolve_misses

    def watched(*a, **kw):
        before = len(_COMPILES)
        out = real(*a, **kw)
        seen.append(len(_COMPILES) - before)
        return out

    agg._resolve_misses = watched
    return seen


def _grown(base, extra: int, seed: int):
    """``base`` plus ``extra`` rows no window has shown before."""
    import dataclasses

    more = generate(SyntheticSpec(
        n_pids=max(1, extra // 8), n_unique_stacks=extra, n_rows=extra,
        total_samples=extra * 3, mean_depth=8, kernel_fraction=0.2,
        seed=seed))
    cat = {f: np.concatenate([getattr(base, f), getattr(more, f)])
           for f in ("pids", "tids", "counts", "user_len", "kernel_len",
                     "stacks")}
    return dataclasses.replace(base, **cat)


@pytest.fixture(scope="module")
def warm_dictionary():
    """A dictionary behind its cold insert (one transfer of the whole
    mirror, no scatter) and one window that missed: by the end of that
    one the miss path has seen the one shape it can take."""
    _watch_compiles()
    base = generate(SyntheticSpec(
        n_pids=20, n_unique_stacks=600, n_rows=600, total_samples=2400,
        mean_depth=8, kernel_fraction=0.2, seed=11))
    agg = DictAggregator(capacity=1 << 16, overflow="raise")
    agg.window_counts(base)
    assert agg.stats["inserts"] == 600
    base = _grown(base, 5, seed=12)
    agg.window_counts(base)
    assert agg.stats["inserts"] == 605
    return agg, base


@pytest.mark.parametrize("count", [1, 17, 130, 131, 1000, 9216])
def test_a_miss_settles_without_a_compile_whatever_the_count(
        warm_dictionary, count):
    agg, base = warm_dictionary
    snap = _grown(base, count, seed=1000 + count)
    in_miss_path = _count_compiles_in_the_miss_path(agg)
    try:
        inserted = agg.stats["inserts"]
        counts = agg.window_counts(snap)
    finally:
        del agg._resolve_misses
    assert agg.stats["inserts"] - inserted == count
    assert in_miss_path == [0]           # settled, and XLA was not asked
    assert int(counts.sum()) == snap.total_samples()
    # The device twin holds what the host mirror holds: a second look
    # finds every row on the device.
    misses = agg.stats["misses"]
    agg.window_counts(snap)
    assert agg.stats["misses"] == misses


# -- the exact dictionary gives ids back --------------------------------------


def _run_sequence(windows, capacity: int, route: str):
    """Ten windows through the dictionary and the window encoder; what
    was shipped for each window, and the aggregator."""
    agg = DictAggregator(capacity=capacity, overflow="raise")
    enc = WindowEncoder(agg)
    shipped: list[dict[int, bytes]] = []
    pipe = None
    if route == "pipelined":
        pipe = EncodePipeline(
            enc, ship=lambda out, prep: shipped.append(
                {pid: bytes(b) for pid, b in out}))
    ids_after = []
    for snap in windows:
        counts = agg.window_counts(snap)
        ids_after.append(agg._next_id)
        if pipe is None:
            shipped.append(dict(enc.encode(
                counts, snap.time_ns, snap.window_ns, snap.period_ns)))
        else:
            # No flush between windows: the next window's reclaim may
            # run while the worker is still on this one.
            assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                               snap.period_ns) is not None
    if pipe is not None:
        assert pipe.close()
        assert pipe.stats["backpressure_fallbacks"] == 0
        assert pipe.stats["encoder_exceptions"] == 0
    return shipped, agg, ids_after


@pytest.mark.parametrize("pids, stacks, capacity", [
    (40, 400, 1 << 12),       # under _VEC_MISS_MIN: the scalar settle
    (120, 1200, 1 << 13)])    # over it: the vectorised settle
def test_ten_windows_of_turnover_reclaim_and_stay_exact(pids, stacks,
                                                        capacity):
    windows = _turnover_windows(10, pids, stacks)
    inline, agg, ids_after = _run_sequence(windows, capacity, "inline")
    assert agg.stats["reclaims"] >= 2
    assert agg.stats["reclaimed_ids"] > 0
    assert max(ids_after) <= capacity // 2   # never past the id space
    assert agg.registry_epoch == agg.stats["reclaims"]
    for snap, blobs in zip(windows, inline):
        _assert_window_is_the_references(snap, blobs)
    # Through the worker, with the next window's reclaim free to run
    # beside it: the same bytes, pid for pid and window for window.
    piped, agg_p, _ = _run_sequence(windows, capacity, "pipelined")
    assert agg_p.stats["reclaims"] == agg.stats["reclaims"]
    assert piped == inline


def test_a_reclaimed_stack_that_comes_back_counts_exactly():
    windows = _turnover_windows(8, 40, 400)
    agg = DictAggregator(capacity=1 << 12, overflow="raise")
    first = windows[0]
    agg.window_counts(first)
    for snap in windows[1:]:
        agg.window_counts(snap)
    assert agg.stats["reclaims"] >= 1
    inserts = agg.stats["inserts"]
    # The first window again: most of its stacks were reclaimed long
    # ago; they register anew and count as the reference counts them.
    enc = WindowEncoder(agg)
    counts = agg.window_counts(first)
    assert agg.stats["inserts"] > inserts
    blobs = dict(enc.encode(counts, first.time_ns, first.window_ns,
                            first.period_ns))
    _assert_window_is_the_references(first, blobs)


def test_a_live_set_over_capacity_still_raises_before_any_mutation():
    snap = generate(SyntheticSpec(
        n_pids=16, n_unique_stacks=700, n_rows=700, total_samples=2800,
        mean_depth=8, seed=3))
    agg = DictAggregator(capacity=1 << 10, overflow="raise")  # 512 ids
    with pytest.raises(RuntimeError, match="capacity exhausted"):
        agg.window_counts(snap)
    assert agg._next_id == 0 and not agg._key_to_id
    assert not agg._occ.any() and not agg._pids
    assert agg.stats.get("reclaims", 0) == 0


def test_the_sketch_mode_rotates_as_before_and_never_reclaims():
    windows = _turnover_windows(8, 40, 400)
    agg = DictAggregator(capacity=1 << 11, overflow="sketch",
                         rotate_min_age=2)
    for snap in windows:
        counts = agg.window_counts(snap)
        assert int(counts.sum()) <= snap.total_samples()
    assert agg.stats.get("reclaims", 0) == 0
    assert agg.stats.get("rotations", 0) >= 1
    assert agg.sketch_info()["sketch_rows"] > 0


# -- the sharded twin ---------------------------------------------------------

requires_shard_map = pytest.mark.skipif(
    not hasattr(__import__("jax"), "shard_map"),
    reason="this jax has no shard_map: the sharded aggregator cannot run")


@requires_shard_map
def test_the_sharded_twin_scatters_in_one_shape_and_reclaims():
    from parca_agent_tpu.aggregator.sharded import ShardedDictAggregator
    from parca_agent_tpu.parallel.mesh import fleet_mesh

    windows = _turnover_windows(8, 120, 1200)
    agg = ShardedDictAggregator(capacity=1 << 14, mesh=fleet_mesh(8),
                                overflow="raise")
    enc = WindowEncoder(agg)
    in_miss_path = _count_compiles_in_the_miss_path(agg)
    for snap in windows:
        counts = agg.window_counts(snap)
        blobs = dict(enc.encode(counts, snap.time_ns, snap.window_ns,
                                snap.period_ns))
        _assert_window_is_the_references(snap, blobs)
    assert agg.stats["reclaims"] >= 1
    assert len(in_miss_path) == len(windows)
    # The cold insert goes over in one transfer; the second window, the
    # first to miss on a dictionary that holds something, compiles the
    # one shape, and no window after it compiles anything.
    assert in_miss_path[1] >= 1 and not any(in_miss_path[2:])
