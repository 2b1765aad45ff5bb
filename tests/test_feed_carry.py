"""The feed endgame (docs/perf.md "feed endgame"): capture-side hash
carry (the sampler's dedup drain stamps each unique record with the
aggregator's h1/h2/h3 triple) and the cross-drain carry cache (a stack
dispatches once per window — or once per population under a stationary
load — and accumulates host-side after that). Every arm is gated on
exactness: identical counts, identical pprof bytes, zero windows lost.
"""

from __future__ import annotations

import ctypes
import dataclasses
import hashlib
import struct

import numpy as np
import pytest

from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.formats import STACK_SLOTS, MappingTable
from parca_agent_tpu.capture.live import load_native
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.ops import hashing
from parca_agent_tpu.utils import faults


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faults.install(None)


def _snap(seed=1, rows=512, pids=8, per_row=3):
    return generate(SyntheticSpec(n_pids=pids, n_unique_stacks=rows,
                                  n_rows=rows, total_samples=rows * per_row,
                                  mean_depth=8, seed=seed))


def _dup(snap, dup=2):
    n = len(snap)
    idx = np.repeat(np.arange(n), dup)
    return dataclasses.replace(
        snap, pids=snap.pids[idx],
        tids=np.arange(len(idx), dtype=np.int32),
        counts=snap.counts[idx], user_len=snap.user_len[idx],
        kernel_len=snap.kernel_len[idx], stacks=snap.stacks[idx])


def _encode_digest(enc, counts, w):
    out = enc.encode(counts, 1_000 + w, 10**10, 10**7)
    h = hashlib.sha256()
    for pid, blob in out:
        h.update(str(pid).encode())
        h.update(blob)
    return h.hexdigest()


# -- capture-side hash: bit identity ------------------------------------------


def _native_hash_lib():
    lib = load_native()
    if not hasattr(lib, "pa_stack_hash"):
        pytest.skip("native library predates pa_stack_hash")
    return lib


def test_stack_hash_bit_identical_to_numpy_triple():
    """pa_stack_hash (the helper the v1h dedup drain stamps records
    with) over arbitrary (kernel, user) splits — including zero-depth
    rows — is bit-identical to row_hash_np's triple, on BOTH the native
    batch kernel and the numpy lane-matrix fallback."""
    import os

    lib = _native_hash_lib()
    coefs, biases = hashing.hash_params(3, STACK_SLOTS)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)

    rng = np.random.default_rng(97)
    n = 256
    pids = rng.integers(1, 1 << 21, n).astype(np.int32)
    ulen = rng.integers(0, 30, n).astype(np.int32)
    klen = rng.integers(0, 4, n).astype(np.int32)
    ulen[:8] = 0  # zero-depth rows: pid/len lanes only
    klen[:8] = 0
    klen[8:16] = 0  # user-only
    ulen[16:24] = 0  # kernel-only
    stacks = np.zeros((n, STACK_SLOTS), np.uint64)
    for i in range(n):
        d = int(ulen[i] + klen[i])
        stacks[i, :d] = rng.integers(1, 1 << 62, d, dtype=np.uint64)

    got = np.zeros((n, 3), np.uint32)
    for i in range(n):
        nu, nk = int(ulen[i]), int(klen[i])
        urow = np.ascontiguousarray(stacks[i, :nu])
        krow = np.ascontiguousarray(stacks[i, nu:nu + nk])
        out = np.zeros(3, np.uint32)
        rc = lib.pa_stack_hash(
            krow.ctypes.data_as(u64p) if nk else None, nk,
            urow.ctypes.data_as(u64p) if nu else None, nu,
            ctypes.c_uint32(int(pids[i])),
            coefs.ctypes.data_as(u32p), coefs.shape[1],
            biases.ctypes.data_as(u32p), 3, STACK_SLOTS,
            out.ctypes.data_as(u32p))
        assert rc == 0
        got[i] = out

    for pin_numpy in (False, True):
        if pin_numpy:
            os.environ["PARCA_NO_NATIVE_HASH"] = "1"
        else:
            os.environ.pop("PARCA_NO_NATIVE_HASH", None)
        try:
            ref = hashing.row_hash_np(stacks, pids, ulen, klen, 3)
        finally:
            os.environ.pop("PARCA_NO_NATIVE_HASH", None)
        for fam in range(3):
            assert np.array_equal(got[:, fam], ref[fam]), fam


def _pack_v1h(pid, tid, kframes, uframes, count, triple):
    out = struct.pack("<IIIIIIII", pid, tid, len(kframes), len(uframes),
                      count, *triple)
    for f in list(kframes) + list(uframes):
        out += struct.pack("<Q", f)
    return out


def test_v1h_decode_and_hash_gather():
    """The v1h record format decodes its count + carried triple, keeps
    a corrupt tail's prefix, and columns_to_snapshot gathers the triple
    onto the deduped rows — equal to hashing the snapshot itself."""
    from parca_agent_tpu.capture.live import (
        columns_to_snapshot,
        decode_records_columnar_v1h,
    )

    lib = _native_hash_lib()
    buf = (_pack_v1h(7, 8, [0xFFFF800000000010], [0x401000], 5,
                     (11, 12, 13))
           + _pack_v1h(9, 9, [], [0x55000], 2, (21, 22, 23))
           + _pack_v1h(7, 8, [0xFFFF800000000010], [0x401000], 3,
                       (11, 12, 13)))
    cols = decode_records_columnar_v1h(lib, buf, len(buf))
    pids, tids, ulen, klen, stacks, counts, h1, h2, h3 = cols
    assert pids.tolist() == [7, 9, 7]
    assert counts.tolist() == [5, 2, 3]
    assert ulen.tolist() == [1, 1, 1] and klen.tolist() == [1, 0, 1]
    assert h1.tolist() == [11, 21, 11]
    assert h2.tolist() == [12, 22, 12]
    assert h3.tolist() == [13, 23, 13]
    np.testing.assert_array_equal(stacks[0, :2],
                                  [0x401000, 0xFFFF800000000010])
    # Corrupt tail: prefix kept (same contract as v1/v1d).
    p2, *_ = decode_records_columnar_v1h(lib, buf + b"\x01\x02",
                                         len(buf) + 2)
    assert p2.tolist() == [7, 9, 7]

    snap, (g1, g2, g3) = columns_to_snapshot(
        pids, tids, ulen, klen, stacks, MappingTable.empty(),
        10**7, 10**10, weights=counts, hashes=(h1, h2, h3))
    # Rows 0 and 2 merged (5 + 3); the gathered triple is the merged
    # row's triple.
    assert len(snap) == 2
    assert sorted(snap.counts.tolist()) == [2, 8]
    by_pid = {int(p): (int(a), int(b), int(c))
              for p, a, b, c in zip(snap.pids, g1, g2, g3)}
    assert by_pid[7] == (11, 12, 13)
    assert by_pid[9] == (21, 22, 23)


def test_snapshot_carried_triple_matches_row_hash():
    """End to end: a real triple stamped per record (pa_stack_hash, the
    drain's helper) survives decode + snapshot dedup bit-identical to
    row_hash_np over the final snapshot rows — the property that lets
    feed() trust capture-carried hashes without re-hashing."""
    from parca_agent_tpu.capture.live import (
        columns_to_snapshot,
        decode_records_columnar_v1h,
    )

    lib = _native_hash_lib()
    coefs, biases = hashing.hash_params(3, STACK_SLOTS)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    rng = np.random.default_rng(31)
    buf = b""
    for _ in range(100):
        pid = int(rng.integers(1, 1 << 20))
        nk = int(rng.integers(0, 3))
        nu = int(rng.integers(0, 20))
        if nk + nu == 0:
            nu = 1
        kf = np.ascontiguousarray(
            rng.integers(1, 1 << 62, nk, dtype=np.uint64))
        uf = np.ascontiguousarray(
            rng.integers(1, 1 << 62, nu, dtype=np.uint64))
        out = np.zeros(3, np.uint32)
        assert lib.pa_stack_hash(
            kf.ctypes.data_as(u64p) if nk else None, nk,
            uf.ctypes.data_as(u64p) if nu else None, nu,
            ctypes.c_uint32(pid),
            coefs.ctypes.data_as(u32p), coefs.shape[1],
            biases.ctypes.data_as(u32p), 3, STACK_SLOTS,
            out.ctypes.data_as(u32p)) == 0
        buf += _pack_v1h(pid, pid, kf.tolist(), uf.tolist(),
                         int(rng.integers(1, 9)), tuple(out.tolist()))
    cols = decode_records_columnar_v1h(lib, buf, len(buf))
    snap, carried = columns_to_snapshot(
        *cols[:5], MappingTable.empty(), 10**7, 10**10,
        weights=cols[5], hashes=cols[6:9])
    ref = hashing.row_hash_np(snap.stacks, snap.pids, snap.user_len,
                              snap.kernel_len, 3)
    for a, b in zip(carried, ref):
        assert np.array_equal(a, b)


# -- cross-drain carry cache: exactness ---------------------------------------


def test_carry_counts_identical_and_steady_state_carries():
    """carry on/off count bit-identity across windows with several
    drains each — and the stationary population's steady-state windows
    ride the cache (every row a hit, dispatch-free closes)."""
    dup = _dup(_snap(seed=3, rows=512, pids=8), dup=2)
    ref = DictAggregator(capacity=1 << 12, overflow="raise",
                         coalesce=True)
    car = DictAggregator(capacity=1 << 12, overflow="raise",
                         coalesce=True, carry=True)
    for w in range(3):
        for agg in (ref, car):
            agg.feed(dup)  # drain 1: window 1 dispatches + admits
            agg.feed(dup)  # drain 2: same stacks, fully carried
        cr = ref.close_window(copy=True)
        cc = car.close_window(copy=True)
        assert np.array_equal(cc, cr), w
        assert int(cc.sum()) == 2 * dup.total_samples()
    assert ref._key_to_id == car._key_to_id
    s = car.stats
    assert s["carry_flushes"] == 3
    assert s.get("carry_fallbacks", 0) == 0
    # Window 1's second drain and every window-2/3 drain: all hits.
    assert s["carry_hits"] == s["carry_rows_in"] == 5 * 512
    assert s["carry_mass"] > 0
    assert s["carry_entries"] == 512


def test_carry_identical_with_capture_carried_hashes():
    """The hashes-given feed path (capture-side carry) matches and
    folds exactly like the self-hash path."""
    dup = _dup(_snap(seed=5, rows=400, pids=8), dup=2)
    ref = DictAggregator(capacity=1 << 12, overflow="raise",
                         coalesce=True)
    car = DictAggregator(capacity=1 << 12, overflow="raise",
                         coalesce=True, carry=True)
    hashes = ref.hash_rows(dup)
    for _ in range(3):
        ref.feed(dup, hashes=hashes)
        car.feed(dup, hashes=hashes)
        assert np.array_equal(car.close_window(copy=True),
                              ref.close_window(copy=True))
    assert car.stats["carry_hits"] > 0


def test_carry_discard_drops_open_mass_only():
    """discard_open_window forgets carried mass with the window (no
    leak into the next flush) but keeps the cache entries."""
    dup = _dup(_snap(seed=7, rows=300, pids=4), dup=2)
    ref = DictAggregator(capacity=1 << 12, overflow="raise",
                         coalesce=True)
    want = ref.window_counts(dup)
    car = DictAggregator(capacity=1 << 12, overflow="raise",
                         coalesce=True, carry=True)
    assert np.array_equal(car.window_counts(dup), want)
    car.feed(dup)  # fully carried: open mass accumulates host-side
    car.discard_open_window()
    assert car.stats["carry_discards"] == 1
    assert car._carry_open_mass == 0
    assert len(car._carry_h1) > 0  # entries survive, weights do not
    # The discarded window's mass must NOT surface here.
    assert np.array_equal(car.window_counts(dup), want)
    assert int(car.window_counts(dup).sum()) == dup.total_samples()


def test_carry_exact_across_cm_rotation():
    """Cold-stack rotation remints the id space: the carry cache must
    drop wholesale (stale sids would credit the wrong stacks) and
    counts stay byte-equal to the carry-off arm through the rotation.
    Sketch-absorbed overflow keys are never admitted, so every flush
    stays exact."""
    s1 = _dup(_snap(seed=17, rows=200, pids=4), dup=2)
    s2 = _dup(_snap(seed=18, rows=200, pids=4), dup=2)
    ref = DictAggregator(capacity=1 << 9, id_cap=256, rotate_min_age=1,
                         coalesce=True)
    car = DictAggregator(capacity=1 << 9, id_cap=256, rotate_min_age=1,
                         coalesce=True, carry=True)
    for snap in (s1, s2, s1, s2):
        cr = ref.window_counts(snap)
        cc = car.window_counts(snap)
        assert np.array_equal(cc, cr)
    assert car.stats.get("rotations", 0) >= 1
    assert car.stats.get("rotations", 0) == ref.stats.get("rotations", 0)
    assert car.stats.get("sketch_samples", 0) == \
        ref.stats.get("sketch_samples", 0)


def test_carry_pprof_byte_identity_matrix():
    """pprof sha256 identity across carry on/off x fold on/off x the
    numpy-fallback hash (fold-first order) x capture-carried hashes —
    every arm must publish the same bytes."""
    import os

    from parca_agent_tpu.pprof.window_encoder import WindowEncoder

    dup = _dup(_snap(seed=13, rows=384, pids=8), dup=2)
    arms = {
        "raw": dict(coalesce=False, carry=False),
        "fold": dict(coalesce=True, carry=False),
        "carry+fold": dict(coalesce=True, carry=True),
        "carry-no-fold": dict(coalesce=False, carry=True),
        "carry+fold-numpy": dict(coalesce=True, carry=True, numpy=True),
        "carry+fold-hashes": dict(coalesce=True, carry=True, given=True),
    }
    digests = {}
    for name, cfg in arms.items():
        if cfg.get("numpy"):
            os.environ["PARCA_NO_NATIVE_HASH"] = "1"
        try:
            agg = DictAggregator(capacity=1 << 12, overflow="raise",
                                 coalesce=cfg["coalesce"],
                                 carry=cfg["carry"])
            enc = WindowEncoder(agg)
            hashes = agg.hash_rows(dup) if cfg.get("given") else None
            out = []
            for w in range(3):
                agg.feed(dup, hashes=hashes)
                out.append(_encode_digest(
                    enc, agg.close_window(copy=True), w))
            digests[name] = out
        finally:
            os.environ.pop("PARCA_NO_NATIVE_HASH", None)
    for name, d in digests.items():
        assert d == digests["raw"], name


# -- chaos: feed.carry fails open to per-drain dispatch -----------------------


@pytest.mark.chaos
def test_feed_carry_fault_falls_back_per_drain_dispatch():
    """An injected fault mid-carry costs NOTHING but the cross-drain
    fold: the batch dispatches per drain (counted fallback), matching
    stays off until the window boundary, mass already carried still
    flushes, the window closes exact (windows_lost == 0), and the next
    window carries again."""
    dup = _dup(_snap(seed=47, rows=512, pids=8), dup=2)
    ref = DictAggregator(capacity=1 << 12, overflow="raise",
                         coalesce=True)
    want = [ref.window_counts(dup) for _ in range(3)]

    faults.install(faults.FaultInjector.from_spec(
        "feed.carry:error:count=1", seed=42))
    d = DictAggregator(capacity=1 << 12, overflow="raise",
                       coalesce=True, carry=True)
    got = [d.window_counts(dup) for _ in range(3)]
    # Window 1 admits (empty cache, no match attempted); window 2's
    # match faults and the window dispatches per drain.
    assert d.stats.get("carry_fallbacks", 0) == 1
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
        assert int(g.sum()) == dup.total_samples()  # windows_lost == 0
    # Rule exhausted + boundary re-arm: window 3 fully carried.
    assert d.stats["carry_hits"] == len(_snap(seed=47, rows=512, pids=8))
    assert faults.get().stats().get("feed.carry") == 1


# -- the carry cache at a population's size -----------------------------------


@pytest.mark.parametrize("n_keys, n_rows", [(1_024, 5_000),
                                           (65_536, 100_000)])
def test_a_carry_cache_answers_a_drain_as_a_dictionary_of_its_keys_does(
        n_keys, n_rows):
    """A carry cache of N keys, admitted over ten settles as a first
    window's drains bring them, answers a drain of M rows as a Python
    dictionary of the same keys does: a row is folded exactly when its
    (h1, h2, h3) is a key of the cache, its weight lands on that key's
    stack id, and a key that shares its h1 with an earlier one is never
    admitted (h1 stays unique: such a stack keeps dispatching, exact
    either way). The sorted arrays, the prefix index rebuilt by every
    admit and the bucket walk are what a firehose-sized population
    leans on ten times a window."""
    rng = np.random.default_rng(n_keys)
    keys = rng.integers(0, 1 << 32, (n_keys, 3), dtype=np.uint64).astype(
        np.uint32)
    # One key in 64 takes the h1 of the key before it.
    twin = np.arange(1, n_keys, 64)
    keys[twin, 0] = keys[twin - 1, 0]
    agg = DictAggregator(capacity=1 << 10, carry=True)
    ids_of: dict[tuple, int] = {}

    def classify(h1, h2, h3):
        # Every key offered is live in the host mirror; ids in the
        # order the keys were first offered.
        ids = np.array([ids_of.setdefault(k, len(ids_of)) for k in
                        zip(h1.tolist(), h2.tolist(), h3.tolist())], np.int64)
        return ids, None, False

    agg._classify_keys_vec = classify
    want: dict[tuple, int] = {}                # the dictionary: key -> sid
    seen_h1: set = set()
    for part in np.array_split(rng.permutation(n_keys), 10):
        batch = keys[np.concatenate([part, part[:7]])]   # with repeats
        agg._carry_admit(batch[:, 0], batch[:, 1], batch[:, 2])
        fresh: dict[int, tuple] = {}           # per h1 its first key
        for k in map(tuple, batch.tolist()):
            if k[0] not in seen_h1:
                fresh.setdefault(k[0], k)
        for k in fresh.values():
            want[k] = ids_of[k]
        seen_h1.update(fresh)
    assert len(agg._carry_h1) == len(want) == agg.stats["carry_entries"]
    assert np.all(np.diff(agg._carry_h1.astype(np.int64)) > 0)
    got = dict(zip(zip(agg._carry_h1.tolist(), agg._carry_h2.tolist(),
                       agg._carry_h3.tolist()), agg._carry_sid.tolist()))
    assert got == want

    # The drain: rows of the cache's keys (hot ones many times), of the
    # twins it refused, and of keys it never met, some on a cached h1.
    rows = keys[rng.integers(0, n_keys, n_rows)]
    strangers = rng.integers(0, 1 << 32, (n_rows // 10, 3),
                             dtype=np.uint64).astype(np.uint32)
    strangers[::2, 0] = keys[rng.integers(0, n_keys, len(strangers[::2])), 0]
    rows = rng.permutation(np.concatenate([rows, strangers]))
    w64 = rng.integers(1, 50, len(rows)).astype(np.int64)
    keep = agg._carry_match_rows(rows[:, 0], rows[:, 1], rows[:, 2], w64)
    folded = np.array([tuple(r) in want for r in rows.tolist()])
    assert 0 < folded.sum() < len(rows)
    assert np.array_equal(keep, ~folded)
    by_sid = np.zeros(len(ids_of), np.int64)
    for r, w in zip(rows[folded].tolist(), w64[folded].tolist()):
        by_sid[want[tuple(r)]] += w
    sids, cnts = agg._carry_take()
    flushed = np.zeros(len(ids_of), np.int64)
    flushed[sids] = cnts
    assert np.array_equal(flushed, by_sid)
    assert len(sids) == len(np.unique(sids)) == int((by_sid > 0).sum())
    assert agg.stats["carry_hits"] == int(folded.sum())
    assert agg._carry_open_mass == 0 and not agg._carry_w.any()
