"""Dictionary (incremental) aggregator tests: exactness vs the CPU oracle,
steady-state behavior, overflow handling."""

import numpy as np

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate


def _samples_by_stack(profiles):
    """(pid, loc-addr tuple) -> count, independent of loc-table layout."""
    out = {}
    for p in profiles:
        addr = p.loc_address
        for k in range(p.n_samples):
            d = int(p.stack_depths[k])
            key = (p.pid, tuple(int(addr[i - 1])
                                for i in p.stack_loc_ids[k, :d]))
            out[key] = out.get(key, 0) + int(p.values[k])
    return out


def test_dict_matches_cpu_oracle():
    snap = generate(SyntheticSpec(n_pids=20, n_unique_stacks=300,
                                  total_samples=5000, seed=3))
    d = DictAggregator(capacity=1 << 12)
    got = _samples_by_stack(d.aggregate(snap))
    want = _samples_by_stack(CPUAggregator().aggregate(snap))
    assert got == want


def test_dict_steady_state_no_inserts():
    snap = generate(SyntheticSpec(n_pids=10, n_unique_stacks=200,
                                  total_samples=2000, seed=5))
    d = DictAggregator(capacity=1 << 12)
    d.aggregate(snap)
    inserts_after_first = d.stats["inserts"]
    assert inserts_after_first == len(snap)
    # Same population again: pure lookups, zero inserts.
    p2 = d.aggregate(snap)
    assert d.stats["inserts"] == inserts_after_first
    assert sum(p.total() for p in p2) == snap.total_samples()


def test_dict_accumulates_new_stacks_across_windows():
    a = generate(SyntheticSpec(n_pids=5, n_unique_stacks=50,
                               total_samples=500, seed=1))
    b = generate(SyntheticSpec(n_pids=5, n_unique_stacks=50,
                               total_samples=500, seed=2))
    d = DictAggregator(capacity=1 << 10)
    pa = d.aggregate(a)
    pb = d.aggregate(b)
    assert sum(p.total() for p in pa) == a.total_samples()
    assert sum(p.total() for p in pb) == b.total_samples()
    # Window b got only b's counts even though the dict holds a's stacks.
    want_b = _samples_by_stack(CPUAggregator().aggregate(b))
    got_b = {k: v for k, v in _samples_by_stack(pb).items()}
    assert got_b == want_b


def test_dict_location_registry_is_superset():
    snap = generate(SyntheticSpec(n_pids=4, n_unique_stacks=60,
                                  total_samples=600, seed=7))
    d = DictAggregator(capacity=1 << 10)
    d.aggregate(snap)
    profiles = d.aggregate(snap)
    oracle = {p.pid: p for p in CPUAggregator().aggregate(snap)}
    for p in profiles:
        o = oracle[p.pid]
        # Same addresses (registry == this window here), same normalization.
        ours = dict(zip(p.loc_address.tolist(), p.loc_normalized.tolist()))
        for a, n in zip(o.loc_address.tolist(), o.loc_normalized.tolist()):
            assert ours[a] == n
        p.check()


def test_dict_probe_overflow_absorbed_by_host():
    """With a tiny device probe bound relative to fill, overflow misses
    must still aggregate exactly (host absorbs them)."""
    snap = generate(SyntheticSpec(n_pids=8, n_unique_stacks=400,
                                  total_samples=4000, seed=11))
    # Capacity close to 2x entries: probe chains beyond _PROBES happen.
    d = DictAggregator(capacity=1 << 10)
    d.aggregate(snap)
    got = _samples_by_stack(d.aggregate(snap))
    want = _samples_by_stack(CPUAggregator().aggregate(snap))
    assert got == want


def test_dict_mapping_change_keeps_registry_ids_valid():
    """A pid whose mapping table changes between windows (dlopen) must get
    registry-stable mapping ids; profiles stay internally consistent."""
    from parca_agent_tpu.capture.formats import (
        STACK_SLOTS,
        MappingTable,
        WindowSnapshot,
    )

    def snap_with(table, addr):
        stacks = np.zeros((1, STACK_SLOTS), np.uint64)
        stacks[0, 0] = addr
        return WindowSnapshot(
            pids=np.array([9], np.int32), tids=np.array([9], np.int32),
            counts=np.array([3], np.int64),
            user_len=np.array([1], np.int32),
            kernel_len=np.array([0], np.int32),
            stacks=stacks, mappings=table,
        )

    t1 = MappingTable(
        pids=np.array([9], np.int32),
        starts=np.array([0x400000], np.uint64),
        ends=np.array([0x500000], np.uint64),
        offsets=np.array([0], np.uint64),
        objs=np.array([0], np.int32),
        obj_paths=("/bin/app",), obj_buildids=("aa",),
    )
    # Window 2: a library mapped BELOW the exe shifts the pid's row order.
    t2 = MappingTable(
        pids=np.array([9, 9], np.int32),
        starts=np.array([0x200000, 0x400000], np.uint64),
        ends=np.array([0x300000, 0x500000], np.uint64),
        offsets=np.array([0, 0], np.uint64),
        objs=np.array([1, 0], np.int32),
        obj_paths=("/bin/app", "/lib/new.so"), obj_buildids=("aa", "bb"),
    )
    d = DictAggregator(capacity=1 << 8)
    (p1,) = d.aggregate(snap_with(t1, 0x400123))
    p1.check()
    (p2,) = d.aggregate(snap_with(t2, 0x200077))  # new stack in new lib
    p2.check()
    by_addr = dict(zip(p2.loc_address.tolist(), p2.loc_mapping_id.tolist()))
    # Old location keeps its original mapping id; the new lib was appended.
    assert p2.mappings[by_addr[0x400123] - 1].path == "/bin/app"
    assert p2.mappings[by_addr[0x200077] - 1].path == "/lib/new.so"
    assert [m.id for m in p2.mappings] == list(range(1, len(p2.mappings) + 1))


def test_dict_capacity_guard():
    snap = generate(SyntheticSpec(n_pids=4, n_unique_stacks=100,
                                  total_samples=1000, seed=2))
    d = DictAggregator(capacity=64, overflow="raise")
    try:
        d.aggregate(snap)
        assert False, "expected capacity error"
    except RuntimeError as e:
        assert "capacity" in str(e) or "half full" in str(e)


def test_dict_sketch_degradation_survives_capacity():
    """r2 VERDICT #3: at capacity the default mode must absorb overflow
    into the count-min sideband (with its overestimate-only bound) instead
    of raising, and no sample mass may be lost."""
    snap = generate(SyntheticSpec(n_pids=4, n_unique_stacks=100,
                                  total_samples=1000, seed=2))
    d = DictAggregator(capacity=64)  # id_cap 32 << 100 uniques
    h1, h2, h3 = d.hash_rows(snap)
    counts = d.window_counts(snap, (h1, h2, h3))
    info = d.sketch_info()
    # Conservation: exact ids + sketch-absorbed samples == window total.
    assert int(counts.sum()) + info["sketch_samples"] == snap.total_samples()
    assert info["sketch_rows"] > 0
    assert info["sketch_distinct_est"] > 0
    # CM never underestimates: absorbed rows' estimates >= their true count.
    est = d.sketch_estimate(h1)
    in_dict = np.array(
        [(int(h1[i]), int(h2[i]), int(h3[i])) in d._key_to_id
         for i in range(len(snap))])
    assert (~in_dict).sum() == info["sketch_rows"]
    assert np.all(est[~in_dict] >= snap.counts[~in_dict])
    # Profiles still build and validate for the exact part.
    for p in d._build_profiles(snap, counts):
        p.check()


def test_dict_rotation_recycles_cold_ids():
    """Cold stacks (unseen rotate_min_age windows) are evicted at a window
    boundary and their space recycled, so a stack-churny host runs in
    bounded memory (r2 VERDICT #3 'registry rotation')."""
    cap = 1 << 9  # id_cap 256
    d = DictAggregator(capacity=cap, rotate_min_age=2)
    prev_sketch = 0
    for w in range(6):
        # A fresh 200-unique population every window: permanent churn.
        snap = generate(SyntheticSpec(
            n_pids=3, n_unique_stacks=200, n_rows=200,
            total_samples=2000, seed=100 + w))
        counts = d.window_counts(snap)
        assert d._next_id <= d._id_cap  # memory stays bounded
        info = d.sketch_info()
        absorbed = info["sketch_samples"] - prev_sketch
        prev_sketch = info["sketch_samples"]
        # Per-window conservation: exact + sketch-absorbed == total.
        assert int(counts.sum()) + absorbed == snap.total_samples()
    assert d.sketch_info()["rotations"] >= 1

    # A stationary population becomes fully resident (exact again) within
    # a few windows as rotation clears the cold churn.
    snap = generate(SyntheticSpec(
        n_pids=3, n_unique_stacks=100, n_rows=100,
        total_samples=1000, seed=999))
    for _ in range(4):
        counts = d.window_counts(snap)
        if int(counts.sum()) == snap.total_samples():
            break
    assert int(counts.sum()) == snap.total_samples()
    for p in d._build_profiles(snap, counts):
        p.check()


def test_dict_streaming_feed_close_matches_batch():
    """feed() chunks + close_window() must equal the one-shot batch path,
    including mid-stream inserts of never-seen stacks."""
    snap = generate(SyntheticSpec(n_pids=12, n_unique_stacks=500,
                                  total_samples=6000, seed=21))
    batch = DictAggregator(capacity=1 << 12)
    want = batch.window_counts(snap)

    d = DictAggregator(capacity=1 << 12)
    h = d.hash_rows(snap)
    step = 97  # odd chunk size: exercises padding + chunk boundaries
    for lo in range(0, len(snap), step):
        d.feed(snap, h, lo, min(lo + step, len(snap)))
    got = d.close_window()
    assert np.array_equal(got, want)
    assert int(got.sum()) == snap.total_samples()

    # Steady state: same rows again through the stream, no inserts.
    inserts = d.stats["inserts"]
    for lo in range(0, len(snap), 173):
        d.feed(snap, h, lo, min(lo + 173, len(snap)))
    got2 = d.close_window()
    assert np.array_equal(got2, want)
    assert d.stats["inserts"] == inserts


def test_dict_streaming_overflow_sideband():
    """Counts above the uint8 pack sentinel must come back exact via the
    overflow sideband."""
    from parca_agent_tpu.capture.formats import (
        STACK_SLOTS,
        MappingTable,
        WindowSnapshot,
    )

    table = MappingTable(
        pids=np.zeros(0, np.int32), starts=np.zeros(0, np.uint64),
        ends=np.zeros(0, np.uint64), offsets=np.zeros(0, np.uint64),
        objs=np.zeros(0, np.int32), obj_paths=(), obj_buildids=(),
    )
    n = 8
    stacks = np.zeros((n, STACK_SLOTS), np.uint64)
    stacks[:, 0] = np.arange(1, n + 1, dtype=np.uint64) * 4096
    counts = np.array([1, 254, 255, 256, 300, 70000, 2, 99999], np.int64)
    snap = WindowSnapshot(
        pids=np.full(n, 7, np.int32), tids=np.full(n, 7, np.int32),
        counts=counts, user_len=np.ones(n, np.int32),
        kernel_len=np.zeros(n, np.int32), stacks=stacks, mappings=table,
    )
    d = DictAggregator(capacity=1 << 8)
    d.window_counts(snap)  # stage population
    d.feed(snap)
    got = d.close_window()
    assert got.tolist() == counts.tolist()


def test_dict_streaming_width_misprediction_retries_lossless():
    """A window whose count distribution shifts hard (many ids crossing the
    4-bit sentinel) must overrun the narrow sideband, retry wider against
    the intact accumulator, and still return exact counts."""
    import dataclasses

    n = 40_960
    snap1 = generate(SyntheticSpec(n_pids=16, n_unique_stacks=n, n_rows=n,
                                   total_samples=n, mean_depth=8, seed=31))
    # Every stack exactly once: close picks width 4, predicts 4 again.
    snap1 = dataclasses.replace(snap1, counts=np.ones(n, np.int64))
    snap2 = dataclasses.replace(snap1, counts=np.full(n, 20, np.int64))

    d = DictAggregator(capacity=1 << 17)
    d.feed(snap1)
    c1 = d.close_window()
    assert c1.sum() == n
    d.feed(snap2)
    c2 = d.close_window()
    assert d.stats.get("close_retries", 0) >= 1
    assert int(c2.sum()) == 20 * n
    assert set(np.unique(c2).tolist()) == {20}


def test_dict_streaming_sideband_growth_retries_lossless():
    """First close of a heavy-overflow window: the predictive sideband
    starts at its floor (no history), the overflow population exceeds it,
    and the retry grows the buffer (same width) against the intact
    accumulator — exact counts, one retry, and the next window predicts
    large enough to close in one fetch."""
    import dataclasses

    from parca_agent_tpu.aggregator.dict import _OVER_MIN

    n = _OVER_MIN + 2048  # overflow population > the floor sideband
    snap = generate(SyntheticSpec(n_pids=8, n_unique_stacks=n, n_rows=n,
                                  total_samples=n, mean_depth=8, seed=33))
    snap = dataclasses.replace(snap, counts=np.full(n, 16, np.int64))

    d = DictAggregator(capacity=1 << 16)
    d.window_counts(snap)  # stage population (inserts ride the host path)
    d.feed(snap)
    got = d.close_window()
    assert d.stats.get("close_retries", 0) == 1
    assert int(got.sum()) == 16 * n
    assert set(np.unique(got).tolist()) == {16}
    assert d._prev_n_over == n  # history: next close fetches once
    d.feed(snap)
    retries_before = d.stats["close_retries"]
    got2 = d.close_window()
    assert d.stats["close_retries"] == retries_before
    assert int(got2.sum()) == 16 * n


def test_dict_unreachable_chain_short_circuits_host_side():
    """Keys whose probe chain lands beyond the device bound would miss on
    EVERY window (a fixed extra fetch per feed, forever). The host knows
    the chain position at insert time, so later windows must settle those
    rows pre-ship: exact counts, no recurring device misses."""
    from parca_agent_tpu.aggregator.dict import _PROBES
    from parca_agent_tpu.capture.formats import (
        STACK_SLOTS,
        MappingTable,
        WindowSnapshot,
    )

    n = _PROBES + 8  # probe chain longer than the device bound
    table = MappingTable(
        pids=np.zeros(0, np.int32), starts=np.zeros(0, np.uint64),
        ends=np.zeros(0, np.uint64), offsets=np.zeros(0, np.uint64),
        objs=np.zeros(0, np.int32), obj_paths=(), obj_buildids=(),
    )
    stacks = np.zeros((n, STACK_SLOTS), np.uint64)
    stacks[:, 0] = np.arange(1, n + 1, dtype=np.uint64) * 4096
    counts = np.arange(1, n + 1, dtype=np.int64)
    snap = WindowSnapshot(
        pids=np.full(n, 3, np.int32), tids=np.full(n, 3, np.int32),
        counts=counts, user_len=np.ones(n, np.int32),
        kernel_len=np.zeros(n, np.int32), stacks=stacks, mappings=table,
    )
    # All keys collide on the table index: one linear chain of length n.
    hashes = (np.full(n, 7, np.uint32),
              np.arange(n, dtype=np.uint32),          # distinct identities
              np.arange(100, 100 + n, dtype=np.uint32))

    d = DictAggregator(capacity=1 << 10)
    first = d.window_counts(snap, hashes)  # inserts; marks the deep tail
    assert first.tolist() == counts.tolist()
    assert len(d._unreachable) == n - _PROBES

    # Steady state: the one-shot path and the streaming path both settle
    # the deep tail host-side with exact counts and no device misses.
    before = d.stats["overflow_misses"]
    second = d.window_counts(snap, hashes)
    assert second.tolist() == counts.tolist()
    assert d.stats["overflow_misses"] == before
    assert d.stats["unreachable_rows"] >= n - _PROBES

    d.feed(snap, hashes)
    got = d.close_window()
    assert got.tolist() == counts.tolist()
    assert d.stats["overflow_misses"] == before


def test_dict_streaming_empty_close():
    d = DictAggregator(capacity=1 << 8)
    assert d.close_window().tolist() == []


def test_dict_empty_window():
    d = DictAggregator(capacity=1 << 8)
    empty = generate(SyntheticSpec(n_pids=2, n_unique_stacks=4, n_rows=0,
                                   total_samples=10, seed=1))
    assert d.aggregate(empty) == []


def test_prefix_sum_equals_cumsum_at_every_branch():
    """The blocked two-level prefix sum (which exists for XLA:TPU's
    compile time, aggregator/dict.py) must give cumsum's integers at
    the plain branch, the blocked branch, the recursive branch and
    lengths that do not divide into blocks."""
    import jax
    import jax.numpy as jnp

    from parca_agent_tpu.aggregator.dict import _SCAN_BLOCK, prefix_sum

    rng = np.random.default_rng(5)
    for n in (16, 4 * _SCAN_BLOCK, 8 * _SCAN_BLOCK, 5 * (1 << 18),
              8 * _SCAN_BLOCK * _SCAN_BLOCK, 8 * _SCAN_BLOCK + 7):
        x = rng.integers(0, 3, n).astype(np.int32)
        got = np.asarray(jax.jit(prefix_sum)(jnp.asarray(x)))
        assert got.dtype == np.int32
        assert np.array_equal(got, np.cumsum(x, dtype=np.int32)), n


# -- the per-pid registry's columns ------------------------------------------


def _registry(n: int):
    from parca_agent_tpu.aggregator.dict import _PidRegistry

    a = np.arange(1, n + 1, dtype=np.uint64) * 16
    return _PidRegistry(a.copy(), a + 1, np.full(n, 3, np.int32),
                        a % 32 == 0, [], {})


def _run(lo: int, hi: int):
    a = np.arange(lo + 1, hi + 1, dtype=np.uint64) * 16
    return a, a + 1, np.full(hi - lo, 3, np.int32), a % 32 == 0


def test_registry_columns_read_like_the_lists_they_were():
    reg = _registry(5)
    assert reg.n_locs == len(reg.loc_address) == 5
    assert int(reg.loc_address[1]) == 32 and bool(reg.loc_is_kernel[1])
    assert reg.loc_normalized[1:3].tolist() == [33, 49]
    part = reg.loc_mapping_id[:4]
    assert part.base is not None and part.dtype == np.int32   # a view
    # Appends with room and past it: the length grows, the rows below
    # a length read before stay what they were, in whichever buffer.
    held = reg.loc_address[:5]
    want = held.tolist()
    for lo, hi in ((5, 6), (6, 40), (40, 41), (41, 200)):
        reg.append_locs(*_run(lo, hi))
        assert reg.n_locs == len(reg.loc_is_kernel) == hi
        assert held.tolist() == reg.loc_address[:5].tolist() == want
    assert reg.loc_address.tolist() == (
        np.arange(1, 201, dtype=np.uint64) * 16).tolist()
    assert reg.loc_normalized.tolist() == [a + 1 for a in
                                           reg.loc_address.tolist()]
    # Amortised: 195 rows arrived in four appends and three growths.
    assert len(reg._address) >= 200 and reg.nbytes == 21 * len(reg._address)


def test_registry_look_up_is_built_when_asked_and_follows_appends():
    reg = _registry(0)
    reg.append_locs(*_run(0, 0))      # nothing, and no growth
    assert reg._index is None and reg.nbytes == 0
    index, built = reg.index()
    assert built and index == {}
    # Runs arrive ascending within themselves, not among each other.
    hi, lo = _run(100, 103), _run(0, 2)
    reg.append_locs(*hi)
    reg.append_locs(*lo)
    again, built = reg.index()
    assert again is index and not built
    assert index == {1616: 1, 1632: 2, 1648: 3, 16: 4, 32: 5}
    assert all(type(a) is int for a in index)     # never an np.uint64
    assert [int(reg.loc_address[i - 1]) for i in index.values()] \
        == list(index)
    assert reg.nbytes == 21 * len(reg._address) + 96 * 5


def test_a_reader_thread_never_sees_a_length_ahead_of_its_rows():
    """The encode worker's side of the contract: while the owner thread
    appends (through growths), a reader that takes the length and then a
    column always finds that many finished rows, equal to the rows any
    earlier read gave."""
    import threading

    reg = _registry(1)
    stop, bad = threading.Event(), []

    def reader():
        while not stop.is_set():
            n = reg.n_locs
            addr, norm = reg.loc_address[:n], reg.loc_normalized[:n]
            kern = reg.loc_is_kernel[:n]
            if not (len(addr) == len(norm) == len(kern) == n
                    and int(addr[-1]) == 16 * n
                    and int(norm[n // 2]) == 16 * (n // 2 + 1) + 1
                    and bool(kern[-1]) == (n % 2 == 0)):
                bad.append(n)
                return

    import sys

    readers = [threading.Thread(target=reader) for _ in range(3)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # hand the interpreter over often
    try:
        for t in readers:
            t.start()
        n = 1
        for k in range(3000):
            step = 1 + k % 7
            reg.append_locs(*_run(n, n + step))
            n += step
    finally:
        stop.set()
        for t in readers:
            t.join(30)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in readers)
    assert not bad and reg.n_locs == n


def test_a_profiles_location_columns_are_read_only_views():
    """_build_profiles hands out views of the registry's columns (no
    copy a pid a window); a consumer cannot write through them."""
    snap = generate(SyntheticSpec(n_pids=4, n_unique_stacks=40,
                                  total_samples=400, seed=9))
    d = DictAggregator(capacity=1 << 10)
    prof = d.aggregate(snap)[0]
    reg = d._pids[prof.pid]
    for col, own in ((prof.loc_address, reg.loc_address),
                     (prof.loc_normalized, reg.loc_normalized),
                     (prof.loc_mapping_id, reg.loc_mapping_id),
                     (prof.loc_is_kernel, reg.loc_is_kernel)):
        assert np.shares_memory(col, own) and not col.flags.writeable
        with np.testing.assert_raises(ValueError):
            col[:1] = 0
