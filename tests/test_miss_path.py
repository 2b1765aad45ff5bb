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


# -- a compaction says where the ids went, and rebuilds its table in bulk ------


def _seeded_dictionary(agg, n: int, seed: int, cluster: int = 0):
    """``n`` seeded keys in ``agg``'s host mirror, ids in key order, as
    the one-by-one insert lays them; ``cluster`` of them share one home
    slot, so that some sit 16 or more steps out (beyond the device
    probe: the unreachable ones)."""
    rng = np.random.default_rng(seed)
    h = rng.integers(0, 1 << 32, size=(n, 3), dtype=np.uint64)
    if cluster:
        h[:cluster, 0] = (h[:cluster, 0] & ~np.uint64(agg._cap - 1)) | 7
        if hasattr(agg, "_n_shards"):
            h[:cluster, 1] -= h[:cluster, 1] % agg._n_shards
    keys = [tuple(int(v) & 0xFFFFFFFF for v in row) for row in h]
    assert len(set(keys)) == n
    agg._rebuild_table_scalar({key: i for i, key in enumerate(keys)})
    agg._key_to_id = {key: i for i, key in enumerate(keys)}
    agg._next_id = agg._published = n
    agg._id_pid = (np.arange(n) % 37).astype(np.int32)
    agg._id_h1 = np.array([k[0] for k in keys], np.uint32)
    agg._id_h2 = np.array([k[1] for k in keys], np.uint32)
    agg._loc_off = np.arange(n + 1, dtype=np.int64) * 3
    agg._loc_flat = np.arange(3 * n, dtype=np.int32)
    agg._last_seen[:n] = 1
    return keys


def _table_facts(agg) -> dict:
    """What a look-up can see of the host mirror: every key's id, found
    by walking its own chain from its home slot over occupied slots
    alone; the set of occupied slots; and the keys 16 or more steps
    out, which have to be exactly ``_unreachable``."""
    from parca_agent_tpu.aggregator.dict import _PROBES

    found, far = {}, {}
    for key, sid in agg._key_to_id.items():
        h1 = np.array([key[0]], np.uint32)
        h2 = np.array([key[1]], np.uint32)
        base, start, mask = agg._probe_geometry_vec(h1, h2)
        base, start = int(base[0]), int(start[0])
        for k in range(mask + 1):
            slot = base + ((start + k) & mask)
            assert agg._occ[slot], (key, "an empty slot before the key")
            if (int(agg._h1[slot]), int(agg._h2[slot]),
                    int(agg._h3[slot])) == key:
                break
        found[key] = int(agg._ids[slot])
        assert agg._chain_dist(key, slot) == k
        if k >= _PROBES:
            far[key] = sid
    assert far == agg._unreachable
    assert int(agg._occ.sum()) == len(found)
    assert set(agg._ids[agg._occ].tolist()) == set(found.values())
    return {"ids": found, "occupied": np.flatnonzero(agg._occ).tolist(),
            "n_far": len(far), "map": dict(agg._key_to_id)}


def _watch_the_scalar_rebuild(agg) -> list[int]:
    """The list gains, per one-by-one rebuild of ``agg``'s table, the
    number of keys it inserted."""
    seen: list[int] = []
    real = agg._rebuild_table_scalar
    agg._rebuild_table_scalar = lambda m: (seen.append(len(m)), real(m))
    return seen


def _make(kind: str, capacity: int):
    if kind == "sharded":
        from parca_agent_tpu.aggregator.sharded import ShardedDictAggregator
        from parca_agent_tpu.parallel.mesh import fleet_mesh

        return ShardedDictAggregator(capacity=capacity, mesh=fleet_mesh(8),
                                     overflow="raise")
    return DictAggregator(capacity=capacity, overflow="raise")


@pytest.mark.parametrize("kind, n, capacity, cluster, seed", [
    ("dict", 900, 1 << 11, 0, 11),        # load 0.44: long chains by chance
    ("dict", 400, 1 << 12, 40, 12),       # 40 keys on one home slot
    ("dict", 2000, 1 << 13, 64, 2147483659),
    pytest.param("sharded", 1200, 1 << 14, 40, 13,
                 marks=requires_shard_map),
])
def test_the_bulk_rebuild_of_the_key_map_and_probe_table_equals_the_scalar_one(
        kind, n, capacity, cluster, seed):
    """A compaction's rebuild as array operations against the
    one-by-one insert it replaced, on seeded keys, unreachable chains
    included: the same keys under the same ids, the same slots
    occupied, every key on its own chain with no gap before it, and
    ``_unreachable`` exactly the keys beyond the device probe's reach
    in the layout it describes. (Which key of a contested run sits in
    which slot is the arbitration's to say: the facts are those a
    look-up can see.)"""
    rng = np.random.default_rng(seed)
    keep = rng.random(n) < 0.6
    keep[:cluster] = True     # the long chain survives
    facts = {}
    for how in ("bulk", "scalar"):
        agg = _make(kind, capacity)
        keys = _seeded_dictionary(agg, n, seed, cluster)
        one_by_one = _watch_the_scalar_rebuild(agg)
        if how == "scalar":
            agg._rebuild_table_vec = lambda *a: False
        epoch = agg.registry_epoch
        agg._compact_ids(keep)
        assert one_by_one == ([] if how == "bulk" else [int(keep.sum())])
        agg.stats["reclaims"] = agg.stats.get("reclaims", 0) + 1
        facts[how] = _table_facts(agg)
        # Where the ids went: survivors in order, -1 for the rest.
        remap = agg.id_remap(epoch)
        want = np.full(n, -1, np.int64)
        want[keep] = np.arange(int(keep.sum()))
        assert np.array_equal(remap, want) and not remap.flags.writeable
        assert facts[how]["map"] == {
            key: int(want[i]) for i, key in enumerate(keys) if keep[i]}
    assert facts["bulk"]["ids"] == facts["scalar"]["ids"]
    assert facts["bulk"]["occupied"] == facts["scalar"]["occupied"]
    if cluster:
        assert facts["bulk"]["n_far"] >= cluster - 16 > 0
        assert facts["scalar"]["n_far"] >= cluster - 16


def test_a_table_that_does_not_hold_every_id_is_rebuilt_one_by_one():
    """The bulk rebuild reads the keys off the table; where the table
    and the key map disagree it gives up and the one-by-one rebuild,
    which reads the map, takes over."""
    agg = DictAggregator(capacity=1 << 10, overflow="raise")
    _seeded_dictionary(agg, 200, 5)
    slot = int(np.flatnonzero(agg._occ)[0])
    agg._occ[slot] = False                      # an id in no slot
    keep = np.ones(200, bool)
    keep[::3] = False
    one_by_one = _watch_the_scalar_rebuild(agg)
    agg._compact_ids(keep)
    assert one_by_one == [int(keep.sum())]
    assert len(_table_facts(agg)["ids"]) == int(keep.sum())


def test_one_remap_is_kept_and_a_mirror_further_behind_gets_none():
    windows = _turnover_windows(10, 40, 400)
    agg = DictAggregator(capacity=1 << 12, overflow="raise")
    assert agg.id_remap(-1) is None and agg.id_remap(0) is None
    seen = []
    for snap in windows:
        before, n_before = agg.registry_epoch, agg._next_id
        agg.window_counts(snap)
        if agg.registry_epoch != before:
            remap = agg.id_remap(before)
            assert len(remap) == n_before
            kept = remap[remap >= 0]
            assert np.array_equal(kept, np.arange(len(kept)))
            assert len(kept) == n_before - (
                agg.stats["reclaimed_ids"] - sum(seen))
            seen.append(n_before - len(kept))
            # Not for the epoch itself, nor for one further back.
            assert agg.id_remap(agg.registry_epoch) is None
            assert agg.id_remap(before - 1) is None
    assert len(seen) == agg.stats["reclaims"] >= 2


# -- the register step: one pass over a batch, held to the loop it replaced ---


def _register_loop(agg, snapshot, rows: np.ndarray) -> None:
    """The plain reference: ``_register_stacks_bulk`` as it stood until
    PR 33, a numpy pass per pid. It left the package with that PR and
    lives on here, as what the one pass has to leave behind, field for
    field. It keeps what the list registry kept, a dictionary from
    address to location id made from the address column, and hands the
    registry each pid's run through ``append_locs``."""
    from parca_agent_tpu.aggregator.base import ProfileMapping
    from parca_agent_tpu.aggregator.cpu import _pid_mappings
    from parca_agent_tpu.aggregator.dict import _PidRegistry
    from parca_agent_tpu.capture.formats import (KERNEL_ADDR_START,
                                                 STACK_SLOTS)
    from parca_agent_tpu.pprof.vec import ragged_gather

    pids = snapshot.pids[rows]
    depths = (snapshot.user_len + snapshot.kernel_len)[rows]
    table = snapshot.mappings
    nb = len(rows)
    depths64 = depths.astype(np.int64)
    boff = np.zeros(nb + 1, np.int64)
    np.cumsum(depths64, out=boff[1:])
    flat_vals = np.empty(int(boff[-1]), np.int32)

    for pid in np.unique(pids):
        sel = np.flatnonzero(pids == pid)
        reg = agg._pids.get(int(pid))
        if reg is None:
            mappings = _pid_mappings(table, int(pid))
            reg = _PidRegistry(
                np.zeros(0, np.uint64), np.zeros(0, np.uint64),
                np.zeros(0, np.int32), np.zeros(0, bool), mappings,
                {(m.start, m.end, m.offset): m.id for m in mappings},
            )
            agg._pids[int(pid)] = reg
        addr_to_loc = {a: k + 1
                       for k, a in enumerate(reg.loc_address.tolist())}

        prows = rows[sel]
        pdepths = depths[sel]
        stacks = snapshot.stacks[prows]
        live = np.arange(STACK_SLOTS)[None, :] < pdepths[:, None]
        addrs = stacks[live]
        uniq = np.unique(addrs)
        known = np.array([int(a) in addr_to_loc for a in uniq], bool)
        fresh = uniq[~known] if len(uniq) else uniq
        if len(fresh):
            is_kernel = fresh >= np.uint64(KERNEL_ADDR_START)
            mrows = table.rows_for_pid(int(pid))
            norm = fresh.copy()
            map_id = np.zeros(len(fresh), np.int32)
            if len(mrows):
                starts = table.starts[mrows]
                ends = table.ends[mrows]
                offsets = table.offsets[mrows]
                bases = table.bases[mrows]
                j = np.searchsorted(starts, fresh, "right").astype(
                    np.int64) - 1
                safe = np.clip(j, 0, len(mrows) - 1)
                hit = (j >= 0) & (fresh < ends[safe]) & ~is_kernel
                norm = np.where(hit, fresh - bases[safe], fresh)
                row_to_reg = np.zeros(len(mrows), np.int32)
                for row in np.unique(safe[hit]) if hit.any() else []:
                    r = int(row)
                    mkey = (int(starts[r]), int(ends[r]), int(offsets[r]))
                    rid = reg.mapping_index.get(mkey)
                    if rid is None:
                        obj = int(table.objs[mrows[r]])
                        rid = len(reg.mappings) + 1
                        reg.mappings.append(ProfileMapping(
                            id=rid, start=mkey[0], end=mkey[1],
                            offset=mkey[2],
                            path=(table.obj_paths[obj]
                                  if 0 <= obj < len(table.obj_paths)
                                  else ""),
                            build_id=(table.obj_buildids[obj]
                                      if 0 <= obj < len(table.obj_buildids)
                                      else ""),
                            base=int(table.bases[mrows[r]]),
                        ))
                        reg.mapping_index[mkey] = rid
                    row_to_reg[r] = rid
                map_id = np.where(hit, row_to_reg[safe], 0)
            base = len(reg.loc_address)
            reg.append_locs(fresh, norm, map_id, is_kernel)
            for k, a in enumerate(fresh.tolist()):
                addr_to_loc[a] = base + k + 1

        lut = np.array([addr_to_loc[int(a)] for a in uniq], np.int32)
        frame_ids = lut[np.searchsorted(uniq, stacks[live])]
        pd64 = pdepths.astype(np.int64)
        src_starts = np.zeros(len(sel), np.int64)
        np.cumsum(pd64[:-1], out=src_starts[1:])
        ragged_gather(frame_ids, src_starts, pd64,
                      out=flat_vals, out_starts=boff[sel])

    agg._append_id_meta(pids.astype(np.int32), depths64, flat_vals)
    agg._reg_version += 1


def _with_the_loop(agg):
    """``agg``, its register step replaced by the reference loop (both
    settle paths and the cold insert call it through the instance)."""
    agg._register_stacks_bulk = \
        lambda snapshot, rows: _register_loop(agg, snapshot, rows)
    return agg


def _registry_facts(reg) -> dict:
    """A registry, column for column: each location column's dtype,
    length and values, the one published length, the mappings and their
    index in insertion order."""
    cols = {name: getattr(reg, name) for name in (
        "loc_address", "loc_normalized", "loc_mapping_id", "loc_is_kernel")}
    return {
        "n_locs": reg.n_locs,
        "columns": {name: (col.dtype.str, col.tolist())
                    for name, col in cols.items()},
        "mappings": repr(reg.mappings),
        "mapping_index": list(reg.mapping_index.items()),
    }


def _registered_state(agg) -> dict:
    """Everything the register step writes, a registry by
    ``_registry_facts``."""
    n = agg._next_id
    return {
        "pids": list(agg._pids),
        "registries": {pid: _registry_facts(reg)
                       for pid, reg in agg._pids.items()},
        "id_pid": agg._id_pid[:n].tolist(),
        "loc_off": agg._loc_off[:n + 1].tolist(),
        "loc_flat": agg._loc_flat[:int(agg._loc_off[n])].tolist(),
        "published": agg._published,
        "reg_version": agg._reg_version,
    }


_K = 0xFFFF_8000_0000_0000  # KERNEL_ADDR_START


def _snap(rows, mappings, objs=("/bin/a", "/lib/b.so", "/lib/c.so")):
    """A window by hand: ``rows`` of (pid, [user addresses], [kernel
    addresses]); ``mappings`` of (pid, start, end, offset, obj), which
    the table wants sorted by (pid, start)."""
    from parca_agent_tpu.capture.formats import (STACK_SLOTS, MappingTable,
                                                 WindowSnapshot)

    n = len(rows)
    stacks = np.zeros((n, STACK_SLOTS), np.uint64)
    for i, (_pid, user, kern) in enumerate(rows):
        stacks[i, :len(user) + len(kern)] = np.array(
            list(user) + list(kern), np.uint64)
    cols = list(zip(*mappings)) if mappings else [[], [], [], [], []]
    table = MappingTable(
        np.array(cols[0], np.int32), np.array(cols[1], np.uint64),
        np.array(cols[2], np.uint64), np.array(cols[3], np.uint64),
        np.array(cols[4], np.int32), obj_paths=objs,
        obj_buildids=tuple(f"id{k}" for k in range(len(objs))))
    return WindowSnapshot(
        pids=[r[0] for r in rows], tids=[r[0] for r in rows],
        counts=np.ones(n, np.int64), user_len=[len(r[1]) for r in rows],
        kernel_len=[len(r[2]) for r in rows], stacks=stacks,
        mappings=table)


def _batches_one_row():
    snap = _snap([(7, [0x1010, 0x1020, 0x1010], [_K + 5])],
                 [(7, 0x1000, 0x2000, 0x0, 0)])
    return [(snap, [0])]


def _batches_known_pid_some_addresses_registered():
    maps = [(7, 0x1000, 0x2000, 0x0, 0), (7, 0x4000, 0x5000, 0x100, 1)]
    snap = _snap([(7, [0x1010, 0x4020], []),
                  (7, [0x1030, 0x1010, 0x4040], [_K + 1]),
                  (7, [0x4020, 0x1005, 0x9999], [_K + 1, _K]),
                  (7, [0x1030], [])], maps)
    return [(snap, [0, 1]), (snap, [3, 2])]


def _batches_synthetic(n_pids, per_pid, seed):
    """Every pid first seen, ``per_pid`` stacks each, in one batch whose
    rows come in the generator's order (pids interleaved)."""
    n = n_pids * per_pid
    snap = generate(SyntheticSpec(
        n_pids=n_pids, n_unique_stacks=n, n_rows=n, total_samples=3 * n,
        mean_depth=8, kernel_fraction=0.2, seed=seed))
    return [(snap, np.arange(n))]


def _batches_known_and_first_seen_at_21_a_pid():
    (snap, rows), = _batches_synthetic(24, 21, seed=21)
    rng = np.random.default_rng(21)
    pid_set = np.unique(snap.pids)
    early = np.isin(snap.pids, pid_set[::2])     # half the pids come first
    part = early & (rng.random(len(rows)) < 0.5)  # with half their stacks
    return [(snap, np.flatnonzero(part)),
            (snap, rng.permutation(np.flatnonzero(~part)))]


def _batches_mapping_table_gained_a_range():
    before = _snap([(7, [0x1010, 0x4020], []), (9, [0x1010], [])],
                   [(7, 0x1000, 0x2000, 0x0, 0),
                    (7, 0x4000, 0x5000, 0x100, 1),
                    (9, 0x1000, 0x2000, 0x0, 0)])
    # pid 7 has dlopen'ed two objects (one below, one between its old
    # ranges) and remapped the second range at another offset; its old
    # first range is still there, at another row of the table.
    after = _snap([(7, [0x1011, 0x0810, 0x3010, 0x4020, 0x4030], []),
                   (9, [0x1010, 0x1020], []),
                   (7, [0x3020, 0x1010, 0x0900], [_K + 3])],
                  [(7, 0x0800, 0x0a00, 0x0, 2),
                   (7, 0x1000, 0x2000, 0x0, 0),
                   (7, 0x3000, 0x3800, 0x40, 2),
                   (7, 0x4000, 0x5000, 0x900, 1),
                   (9, 0x1000, 0x2000, 0x0, 0)])
    return [(before, [0, 1]), (after, [0, 1, 2])]


def _batches_a_pid_with_no_mappings():
    snap = _snap([(5, [0x10, 0x20], []), (7, [0x1010, 0x10], [_K]),
                  (8, [0x30], []), (5, [0x20, 0x40], [])],
                 [(7, 0x1000, 0x2000, 0x0, 0)])
    return [(snap, [0, 1, 2, 3])]


def _batches_no_table_at_all():
    snap = _snap([(5, [0x10, 0x20], [_K]), (6, [0x10], [])], [])
    return [(snap, [1, 0])]


def _batches_kernel_only_frames():
    top = 0xFFFF_FFFF_FFFF_FFFF
    snap = _snap([(7, [], [_K, _K + 8, top]), (7, [], [_K + 8]),
                  (3, [], [top, _K]), (7, [0x1010], [top])],
                 [(3, 0x1000, 0x2000, 0x0, 0), (7, 0x1000, 0x2000, 0x0, 0)])
    return [(snap, [0, 1, 2]), (snap, [3])]


def _batches_edges_of_the_address_space():
    # Starts that equal an address, an end that equals one, an empty
    # range that shares its start with the next, user addresses with
    # the top bit set (below the kernel half), a mapping that reaches
    # into the kernel half, a row of depth 0, and a pid that has only it.
    hi = 0x8000_0000_0000_0000
    snap = _snap([(7, [0x1000, 0x1fff, 0x2000, 0x0fff], []),
                  (7, [hi, hi + 0x10, _K - 1, 0x3000], [_K + 1]),
                  (7, [], []), (4, [], []),
                  (2, [0x3000, 0x3000, 0x2fff, 0x30ff, 0x3100], [])],
                 [(2, 0x3000, 0x3000, 0x0, 1), (2, 0x3000, 0x3100, 0x10, 1),
                  (7, 0x1000, 0x2000, 0x0, 0), (7, 0x3000, 0x3000, 0x0, 5),
                  (7, hi, _K + 0x100, 0x20, -1)])
    return [(snap, [0, 2, 3]), (snap, [4, 1])]


def _batches_turnover(pids, stacks, turnover):
    """The benchmark's own mix through the whole settle (plan, register,
    scatter), a window a batch: ``None`` for the rows."""
    return [(snap, None) for snap in _turnover_windows(
        4, pids, stacks, seed=33, turnover=turnover)]


_REGISTER_CASES = {
    "one-row": (_batches_one_row, None),
    "known-pid-some-addresses-registered":
        (_batches_known_pid_some_addresses_registered, None),
    "all-first-seen-5-a-pid": (lambda: _batches_synthetic(60, 5, 5), None),
    "known-and-first-seen-21-a-pid":
        (_batches_known_and_first_seen_at_21_a_pid, None),
    "mapping-table-gained-a-range":
        (_batches_mapping_table_gained_a_range, None),
    "pid-with-no-mappings": (_batches_a_pid_with_no_mappings, None),
    "no-table-at-all": (_batches_no_table_at_all, None),
    "kernel-only-frames": (_batches_kernel_only_frames, None),
    "edges-of-the-address-space":
        (_batches_edges_of_the_address_space, None),
    # 504 rows of ~8 frames against a budget of 256: ~16 groups, and a
    # pid of 21 stacks (~170 frames) now and then a group of its own.
    "larger-than-the-frame-budget":
        (_batches_known_and_first_seen_at_21_a_pid, 256),
    "one-pid-a-group": (lambda: _batches_synthetic(60, 5, 6), 1),
    # Whole windows: the scalar settle (under _VEC_MISS_MIN misses after
    # the cold insert) and the vectorised one, nine pids in ten new.
    "turnover-windows-scalar-settle":
        (lambda: _batches_turnover(40, 400, 0.5), None),
    "turnover-windows-vectorised-settle":
        (lambda: _batches_turnover(160, 1600, 0.9), None),
    "turnover-windows-in-groups":
        (lambda: _batches_turnover(160, 1600, 0.9), 1000),
}


@pytest.mark.parametrize("case", list(_REGISTER_CASES))
def test_the_one_pass_leaves_what_the_loop_per_pid_left(case, monkeypatch):
    from parca_agent_tpu.aggregator import dict as dict_mod

    make, budget = _REGISTER_CASES[case]
    if budget is not None:
        monkeypatch.setattr(dict_mod, "_REGISTER_FRAME_BUDGET", budget)
    new = DictAggregator(capacity=1 << 14, overflow="raise")
    old = _with_the_loop(DictAggregator(capacity=1 << 14, overflow="raise"))
    for snap, rows in make():
        for agg in (new, old):
            if rows is None:
                agg.window_counts(snap)
                continue
            rows = np.asarray(rows, np.int64)
            agg._next_id += len(rows)   # the plan's part: ids handed out
            agg._register_stacks_bulk(snap, rows)
        have, want = _registered_state(new), _registered_state(old)
        for field in want:
            assert have[field] == want[field], field
    assert new._pids and new._published == new._next_id > 0


def _count_numpy_calls(monkeypatch, names):
    """Patch ``np.<name>`` for each name (the package calls numpy through
    the module, so the patch is what it calls); the dict gains a count
    per name."""
    seen = dict.fromkeys(names, 0)

    def counted(name, real):
        def call(*a, **kw):
            seen[name] += 1
            return real(*a, **kw)
        return call

    for name in names:
        monkeypatch.setattr(np, name, counted(name, getattr(np, name)))
    return seen


_NUMPY_PER_PID_SUSPECTS = (
    "unique", "searchsorted", "argsort", "lexsort", "sort", "flatnonzero",
    "cumsum", "where", "array", "repeat", "arange", "concatenate",
    "bincount", "isin")    # not zeros / empty: the id arrays grow by size


def test_the_register_step_makes_as_many_numpy_calls_for_1800_pids_as_for_18(
        monkeypatch):
    """The loop per pid cannot come back unnoticed: one call of the
    register step makes a number of numpy calls that does not grow with
    the number of pids in the batch. (What a first-seen pid does cost
    is the four ``.copy()`` that cut its run of the columns out of the
    group's arrays: meant to be there, methods, not counted.)"""
    calls = {}
    for n_pids in (18, 1800):
        (snap, rows), = _batches_synthetic(n_pids, 5, seed=n_pids)
        agg = DictAggregator(capacity=1 << 15, overflow="raise")
        agg._next_id += len(rows)
        with monkeypatch.context() as m:
            seen = _count_numpy_calls(m, _NUMPY_PER_PID_SUSPECTS)
            agg._register_stacks_bulk(snap, rows)
        # (The generator leaves a pid in a hundred without a row.)
        assert n_pids * 0.95 < len(agg._pids) == len(np.unique(snap.pids))
        calls[n_pids] = seen
    # (Equal but for the merge's bisection: two ``where`` a step, and
    # as many steps as the largest pid's address count has bits.)
    assert all(calls[1800][name] - calls[18][name] <= 4 for name in seen)
    assert 0 < sum(calls[1800].values()) < 100
    # The reference loop, for scale: several calls a pid.
    (snap, rows), = _batches_synthetic(18, 5, seed=18)
    agg = DictAggregator(capacity=1 << 15, overflow="raise")
    agg._next_id += len(rows)
    with monkeypatch.context() as m:
        seen = _count_numpy_calls(m, _NUMPY_PER_PID_SUSPECTS)
        _register_loop(agg, snap, rows)
    assert sum(seen.values()) > 18 * 10


@pytest.mark.parametrize("pids, stacks", [
    (40, 400),        # the scalar settle
    (160, 1600)])     # the vectorised one
def test_a_windows_meta_counts_the_pids_it_registered(pids, stacks):
    from parca_agent_tpu.runtime.trace import FlightRecorder

    windows = _turnover_windows(3, pids, stacks, seed=9, turnover=0.75)
    agg = DictAggregator(capacity=1 << 14, overflow="raise")
    rec = FlightRecorder()
    for snap in windows + [windows[-1]]:
        had = set(agg._pids)
        ids = agg._next_id
        tr = rec.begin(snap.time_ns)
        with tr.span("close"):
            agg.window_counts(snap)
        tr.complete()
        meta = rec.traces()[-1]["meta"]
        new_rows = agg._id_pid[ids:agg._next_id]
        held = set(new_rows.tolist())
        assert meta["misses"] == len(new_rows)
        assert meta["registered_pids"] == len(held)
        assert meta["registered_first_seen"] == len(held - had)
        assert set(agg._pids) == had | held
    # The last window came twice: the second time nothing missed, and
    # the counts are there and say so.
    assert (meta["misses"], meta["registered_pids"],
            meta["registered_first_seen"]) == (0, 0, 0)


# -- the registry's columns: arrays, written once ----------------------------


_COLUMNS = ("loc_address", "loc_normalized", "loc_mapping_id",
            "loc_is_kernel")


@pytest.mark.parametrize("pids, stacks", [
    (40, 400),        # the scalar settle (under 512 misses a window)
    (160, 1600)])     # the vectorised one
def test_a_registered_and_an_adopted_registry_are_equal_column_for_column(
        pids, stacks):
    """The two constructors of a registry, the register step (reached
    from both miss settles) and ``adopt_registry`` (fed, as the statics
    store feeds it, arrays over a record's read-only bytes), leave the
    same type with the same columns: dtype, length, values."""
    agg = DictAggregator(capacity=1 << 14, overflow="raise")
    for snap in _turnover_windows(3, pids, stacks, seed=12, turnover=0.75):
        agg.window_counts(snap)
    twin = DictAggregator(capacity=1 << 14, overflow="raise")
    for pid, reg in agg._pids.items():
        cols = [np.frombuffer(getattr(reg, c).tobytes(),
                              getattr(reg, c).dtype) for c in _COLUMNS]
        assert not cols[0].flags.writeable
        assert twin.adopt_registry(pid, reg.mappings, *cols)
        got = twin._pids[pid]
        assert type(got) is type(reg)
        assert _registry_facts(got) == _registry_facts(reg), pid
        assert [getattr(got, c).dtype.str for c in _COLUMNS] == [
            "<u8", "<u8", "<i4", "|b1"]
        assert twin.registry_digest(pid) == agg.registry_digest(pid)
        # Adopted columns are the registry's own, and writable: the
        # pid's next window appends to them.
        assert all(_owns_its_memory(got, c) for c in _COLUMNS)
    assert len(twin._pids) == len(agg._pids) > pids


def _owns_its_memory(reg, column: str) -> bool:
    """The column is a view of a buffer that is nobody's view and holds
    no more than the registry asked for (its rows, or the doubled room
    of a growth): a registry pins no array of the group it came in."""
    col = getattr(reg, column)
    buf = col.base if col.base is not None else col
    return buf.base is None and buf.flags.owndata \
        and len(buf) <= max(2 * len(col), 1)


def test_a_first_seen_pids_columns_own_their_memory():
    """A first-seen pid is handed a copy of its run of the group's four
    arrays: were it a slice, every pid of a group would keep the whole
    group's arrays alive for as long as it lives."""
    (snap, rows), = _batches_synthetic(60, 5, seed=5)
    agg = DictAggregator(capacity=1 << 14, overflow="raise")
    agg._next_id += len(rows)
    agg._register_stacks_bulk(snap, rows)
    regs = list(agg._pids.values())
    assert len(regs) > 50
    for reg in regs:
        assert all(_owns_its_memory(reg, c) for c in _COLUMNS)
    a, b = regs[0], regs[1]
    assert not any(np.shares_memory(getattr(a, c), getattr(b, c))
                   for c in _COLUMNS)


def test_a_known_pid_that_grows_gets_the_list_registrys_ids_and_one_look_up():
    """A pid the registry knows brings new stacks in later windows, some
    of their addresses registered and some fresh: the fresh ones take
    the location ids the list registry gave (ascending, after the
    locations the pid has), the profiles are the plain reference's, the
    address look-up is built the first time the pid is asked and kept
    from there (the counter rises by one, once), and a first-seen pid
    beside it is never asked."""
    maps = [(7, 0x1000, 0x2000, 0x0, 0), (7, 0x4000, 0x5000, 0x100, 1),
            (9, 0x1000, 0x2000, 0x0, 0)]
    windows = [
        _snap([(7, [0x1010, 0x4020], []), (7, [0x1030, 0x1010], [_K + 1])],
              maps),
        # Known addresses (0x1010, 0x4020, _K + 1) among fresh ones, the
        # fresh ones on both sides of what the pid has; pid 9 first seen.
        _snap([(7, [0x1010, 0x4020], []), (7, [0x1030, 0x1010], [_K + 1]),
               (7, [0x1005, 0x4020, 0x4fff, 0x9999], [_K + 1, _K]),
               (9, [0x1010, 0x1020], [])], maps),
        # And again, twice over: a growth past the doubled room.
        _snap([(7, [0x1010, 0x4020], []),
               (7, [0x1005, 0x4020, 0x4fff, 0x9999], [_K + 1, _K]),
               (7, [0x1000 + 8 * k for k in range(40)], [_K + 7]),
               (9, [0x1010, 0x1020], [])], maps),
        _snap([(7, [0x1000 + 8 * k for k in range(40)], [_K + 7]),
               (7, [0x4000 + 4 * k for k in range(60)] + [0x1005], []),
               (9, [0x1020, 0x1fff], [])], maps),
    ]
    new = DictAggregator(capacity=1 << 10, overflow="raise")
    old = _with_the_loop(DictAggregator(capacity=1 << 10, overflow="raise"))
    builds = []
    for snap in windows:
        counts = np.asarray(new.window_counts(snap))
        np.asarray(old.window_counts(snap))
        assert _registered_state(new) == _registered_state(old)
        have = {p.pid: parse_pprof(build_pprof(p, compress=False))
                for p in new._build_profiles(snap, counts)}
        want = _reference(snap)
        assert set(have) == set(want)
        for pid, ref in want.items():
            assert have[pid].stacks_by_address() == ref.stacks_by_address()
        builds.append(new.stats.get("registry_index_builds", 0))
    # Window 2 asks pid 7 for the first time; window 3 asks it again and
    # window 4 asks pid 9, each look-up built once.
    assert builds == [0, 1, 1, 2]
    reg = new._pids[7]
    assert reg._index == {a: k + 1 for k, a in
                          enumerate(reg.loc_address.tolist())}
    assert new._pids[9]._index is not None
    # The ids: each window's fresh addresses ascending, after the rest.
    first = [0x1010, 0x1030, 0x4020, _K + 1]
    second = [0x1005, 0x4fff, 0x9999, _K]
    assert reg.loc_address[:8].tolist() == first + second
    # (Window 3's forty hold 0x1010 and 0x1030, window 4's sixty 0x4020.)
    assert reg.n_locs == 8 + (38 + 1) + 59
    assert all(_owns_its_memory(reg, c) for c in _COLUMNS)
    assert old.stats.get("registry_index_builds", 0) == 0


def test_the_footprint_counts_a_registrys_arrays():
    (snap, rows), = _batches_synthetic(20, 5, seed=3)
    agg = DictAggregator(capacity=1 << 12, overflow="raise")
    before = agg.footprint_bytes()["pid_registry_bytes"]
    agg.window_counts(snap)
    regs = agg._pids.values()
    want = sum(r.nbytes + 120 * len(r.mappings) for r in regs)
    assert agg.footprint_bytes()["pid_registry_bytes"] == want > before == 0
    # 21 bytes a location row (8 + 8 + 4 + 1); no pid was asked for an
    # address, so none has a look-up.
    assert all(r._index is None for r in regs)
    assert sum(r.nbytes for r in regs) == 21 * sum(r.n_locs for r in regs)
