"""Device aggregator parity on hand-built windows: DictAggregator must
match the CPU oracle.

Backends may order samples/locations differently (both are deterministic,
but the dictionary numbers stacks in insertion order while the CPU path
sorts by byte view); pprof treats samples and location tables as sets, so
the tests compare canonicalized forms: stacks expanded back to address
tuples with counts.

Every case runs twice. ``cold`` aggregates the snapshot on a fresh
aggregator: every row misses the device table and is inserted by the
host. ``warm`` aggregates it a second time on the same aggregator and
judges that result: every row is a device hit, nothing is inserted — the
steady-state path.
"""

import dataclasses

import numpy as np
import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator, NaiveAggregator
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.formats import (
    KERNEL_ADDR_START,
    STACK_SLOTS,
    MappingTable,
    WindowSnapshot,
)
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate

pytestmark = pytest.mark.parametrize("window", ("cold", "warm"))


def aggregate(window, snap, warm_with=None):
    """The judged aggregation of ``snap``: the first on a fresh
    aggregator (``cold``), or the one after ``warm_with`` (default: the
    same snapshot) has put every stack into the dictionary (``warm``)."""
    agg = DictAggregator(capacity=1 << 14)
    if window == "cold":
        return agg.aggregate(snap)
    agg.aggregate(snap if warm_with is None else warm_with)
    inserts = agg.stats["inserts"]
    out = agg.aggregate(snap)
    assert agg.stats["inserts"] == inserts
    return out


def canonical(profiles):
    """Profile list -> {pid: (stack->count dict, loc table dict)}."""
    out = {}
    for p in profiles:
        p.check()
        stacks = {}
        for si in range(p.n_samples):
            d = int(p.stack_depths[si])
            ids = p.stack_loc_ids[si, :d]
            addrs = tuple(int(p.loc_address[i - 1]) for i in ids)
            stacks[addrs] = stacks.get(addrs, 0) + int(p.values[si])
        locs = {
            int(p.loc_address[i]): (
                int(p.loc_normalized[i]),
                int(p.loc_mapping_id[i]),
                bool(p.loc_is_kernel[i]),
            )
            for i in range(p.n_locations)
        }
        mappings = [(m.start, m.end, m.offset, m.path, m.build_id) for m in p.mappings]
        out[p.pid] = (stacks, locs, mappings)
    return out


@pytest.fixture(scope="module")
def small_snapshot():
    return generate(SyntheticSpec(n_pids=13, n_unique_stacks=300,
                                  total_samples=40_000, seed=7))


def test_matches_cpu_on_synthetic(small_snapshot, window):
    cpu = canonical(CPUAggregator().aggregate(small_snapshot))
    dev = canonical(aggregate(window, small_snapshot))
    assert dev == cpu


def test_matches_naive_on_tiny(window):
    snap = generate(SyntheticSpec(n_pids=3, n_unique_stacks=20,
                                  total_samples=500, seed=1))
    naive = canonical(NaiveAggregator().aggregate(snap))
    dev = canonical(aggregate(window, snap))
    assert dev == naive


def test_empty_snapshot(window):
    snap = WindowSnapshot(
        pids=np.zeros(0, np.int32), tids=np.zeros(0, np.int32),
        counts=np.zeros(0, np.int64), user_len=np.zeros(0, np.int32),
        kernel_len=np.zeros(0, np.int32),
        stacks=np.zeros((0, STACK_SLOTS), np.uint64),
        mappings=MappingTable.empty(),
    )
    assert aggregate(window, snap) == []


def test_duplicate_rows_merge(window):
    """Two snapshot rows with identical (pid, stack) must merge counts."""
    stack = np.zeros((1, STACK_SLOTS), np.uint64)
    stack[0, :3] = [0x1000, 0x2000, 0x3000]
    snap = WindowSnapshot(
        pids=np.array([42, 42], np.int32),
        tids=np.array([42, 43], np.int32),
        counts=np.array([5, 7], np.int64),
        user_len=np.array([3, 3], np.int32),
        kernel_len=np.array([0, 0], np.int32),
        stacks=np.repeat(stack, 2, axis=0),
        mappings=MappingTable.empty(),
    )
    (prof,) = aggregate(window, snap)
    assert prof.n_samples == 1
    assert prof.total() == 12
    assert prof.n_locations == 3


def test_user_kernel_boundary_distinguishes(window):
    """Same addresses, different user/kernel split -> distinct samples."""
    stack = np.zeros((2, STACK_SLOTS), np.uint64)
    stack[:, 0] = 0x1000
    stack[:, 1] = KERNEL_ADDR_START + 0x500
    snap = WindowSnapshot(
        pids=np.array([42, 42], np.int32),
        tids=np.array([42, 42], np.int32),
        counts=np.array([1, 1], np.int64),
        user_len=np.array([2, 1], np.int32),
        kernel_len=np.array([0, 1], np.int32),
        stacks=stack,
        mappings=MappingTable.empty(),
    )
    (prof,) = aggregate(window, snap)
    assert prof.n_samples == 2
    kern = prof.loc_is_kernel[prof.loc_address >= KERNEL_ADDR_START]
    assert kern.all() and len(kern) == 1


def test_mapping_join_and_normalization(window):
    table = MappingTable(
        pids=np.array([9, 9], np.int32),
        starts=np.array([0x400000, 0x7F0000000000], np.uint64),
        ends=np.array([0x500000, 0x7F0000100000], np.uint64),
        offsets=np.array([0, 0x2000], np.uint64),
        objs=np.array([0, 1], np.int32),
        obj_paths=("/bin/a", "/lib/b.so"),
        obj_buildids=("aa", "bb"),
    )
    stack = np.zeros((1, STACK_SLOTS), np.uint64)
    stack[0, :4] = [0x400123, 0x7F0000000ABC, 0x600000, KERNEL_ADDR_START + 1]
    snap = WindowSnapshot(
        pids=np.array([9], np.int32), tids=np.array([9], np.int32),
        counts=np.array([3], np.int64),
        user_len=np.array([3], np.int32), kernel_len=np.array([1], np.int32),
        stacks=stack, mappings=table,
    )
    for profiles in (CPUAggregator().aggregate(snap),
                     aggregate(window, snap)):
        (prof,) = profiles
        by_addr = {
            int(a): (int(n), int(m))
            for a, n, m in zip(
                prof.loc_address, prof.loc_normalized, prof.loc_mapping_id
            )
        }
        assert by_addr[0x400123] == (0x123, 1)
        assert by_addr[0x7F0000000ABC] == (0xABC + 0x2000, 2)
        assert by_addr[0x600000] == (0x600000, 0)  # unmapped gap
        assert by_addr[KERNEL_ADDR_START + 1] == (KERNEL_ADDR_START + 1, 0)


def test_larger_snapshot_roundtrip(window):
    snap = generate(SyntheticSpec(n_pids=50, n_unique_stacks=2_000,
                                  total_samples=200_000, kernel_fraction=0.35,
                                  seed=99))
    cpu = canonical(CPUAggregator().aggregate(snap))
    dev = canonical(aggregate(window, snap))
    assert dev == cpu


def test_window_total_overflow_rejected(window):
    stack = np.zeros((2, STACK_SLOTS), np.uint64)
    stack[:, 0] = 0x1000
    snap = WindowSnapshot(
        pids=np.array([1, 1], np.int32), tids=np.array([1, 1], np.int32),
        counts=np.array([1_500_000_000, 1_500_000_000], np.int64),
        user_len=np.array([1, 1], np.int32),
        kernel_len=np.array([0, 0], np.int32),
        stacks=stack, mappings=MappingTable.empty(),
    )
    # Warmed by the same two stacks at a mass that fits: the refused
    # window's rows are then hits, not inserts.
    fits = dataclasses.replace(snap, counts=np.array([1, 1], np.int64))
    with pytest.raises(ValueError, match="int32"):
        aggregate(window, snap, warm_with=fits)


def test_vsyscall_mapping_does_not_normalize_kernel_addr(window):
    """A mapping covering kernel text (e.g. [vsyscall]) must not claim
    kernel frames — parity with the CPU oracle's ~is_kernel exclusion."""
    table = MappingTable(
        pids=np.array([7], np.int32),
        starts=np.array([0xFFFFFFFFFF600000], np.uint64),
        ends=np.array([0xFFFFFFFFFF601000], np.uint64),
        offsets=np.array([0], np.uint64),
        objs=np.array([0], np.int32),
        obj_paths=("[vsyscall]",),
    )
    stack = np.zeros((1, STACK_SLOTS), np.uint64)
    stack[0, 0] = 0xFFFFFFFFFF600ABC
    snap = WindowSnapshot(
        pids=np.array([7], np.int32), tids=np.array([7], np.int32),
        counts=np.array([1], np.int64),
        user_len=np.array([0], np.int32), kernel_len=np.array([1], np.int32),
        stacks=stack, mappings=table,
    )
    profiles = aggregate(window, snap)
    assert canonical(CPUAggregator().aggregate(snap)) == canonical(profiles)
    (prof,) = profiles
    assert int(prof.loc_mapping_id[0]) == 0
    assert int(prof.loc_normalized[0]) == 0xFFFFFFFFFF600ABC
