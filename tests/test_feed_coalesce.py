"""The ingest wall (docs/perf.md "ingest wall"): host-side feed
coalescing to (stack, weight) pairs, the native batch row-hash kernel,
and the vectorized miss settle — every arm gated on exactness (identical
counts, identical registries, identical pprof bytes) against the raw /
numpy / scalar references.
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np
import pytest

from parca_agent_tpu.aggregator.dict import DictAggregator, _PROBES
from parca_agent_tpu.capture.formats import fold_rows_first_seen
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.ops import hashing
from parca_agent_tpu.utils import faults
from streaming_sources import CacheSource


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faults.install(None)


@pytest.fixture()
def numpy_hash(monkeypatch):
    """Pin the numpy lane-matrix hash path for one test."""
    monkeypatch.setenv("PARCA_NO_NATIVE_HASH", "1")


def _snap(seed=1, rows=512, pids=8, per_row=3):
    return generate(SyntheticSpec(n_pids=pids, n_unique_stacks=rows,
                                  n_rows=rows, total_samples=rows * per_row,
                                  mean_depth=8, seed=seed))


def _dup(snap, dup=3):
    """Repeat every row `dup` times under distinct tids — the cross-
    thread repetition the coalescer folds (columns_to_snapshot keys on
    (pid, tid, stack), so these rows survive the capture-side dedup)."""
    n = len(snap)
    idx = np.repeat(np.arange(n), dup)
    return dataclasses.replace(
        snap, pids=snap.pids[idx],
        tids=np.arange(len(idx), dtype=np.int32),
        counts=snap.counts[idx], user_len=snap.user_len[idx],
        kernel_len=snap.kernel_len[idx], stacks=snap.stacks[idx])


def _hash_pair(snap, n_hashes=3):
    """(native, numpy) hash tuples for one snapshot."""
    import os

    os.environ.pop("PARCA_NO_NATIVE_HASH", None)
    native = hashing.row_hash_np(snap.stacks, snap.pids, snap.user_len,
                                 snap.kernel_len, n_hashes)
    os.environ["PARCA_NO_NATIVE_HASH"] = "1"
    try:
        ref = hashing.row_hash_np(snap.stacks, snap.pids, snap.user_len,
                                  snap.kernel_len, n_hashes)
    finally:
        os.environ.pop("PARCA_NO_NATIVE_HASH", None)
    return native, ref


def _encode_digest(enc, counts, w):
    out = enc.encode(counts, 1_000 + w, 10**10, 10**7)
    h = hashlib.sha256()
    for pid, blob in out:
        h.update(str(pid).encode())
        h.update(blob)
    return h.hexdigest()


# -- the fold primitive -------------------------------------------------------


def test_fold_rows_first_seen_property():
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 40, 300, dtype=np.uint64)
    counts = rng.integers(1, 100, 300).astype(np.int64)
    folded = fold_rows_first_seen(keys, counts)
    assert folded is not None
    rep, inv, weights = folded
    # Exact mass, key-for-key.
    assert int(weights.sum()) == int(counts.sum())
    seen: dict = {}
    for i, k in enumerate(keys.tolist()):
        j = seen.setdefault(k, len(seen))
        assert inv[i] == j  # first-occurrence order
    for k, j in seen.items():
        assert int(keys[rep[j]]) == k
        assert rep[j] == min(i for i, kk in enumerate(keys.tolist())
                             if kk == k)
        assert int(weights[j]) == int(counts[keys == k].sum())
    # All-unique input: None (callers skip the rebuild).
    assert fold_rows_first_seen(np.arange(16, dtype=np.uint64),
                                np.ones(16, np.int64)) is None


def _triple_case(name):
    """(h1, h2, h3, weights) for one case of the integer-fold property
    test, each lane uint32."""
    rng = np.random.default_rng(29)

    def lanes(n):
        return [rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
                for _ in range(3)]

    if name == "n=2-same":
        return (*[x[[0, 0]] for x in lanes(1)], np.array([3, 4], np.int64))
    if name.startswith("n="):
        n = int(name[2:])
        return (*lanes(n), rng.integers(1, 100, n).astype(np.int64))
    if name == "distinct":
        # Distinct on (h1, h2) by construction, whatever the draw.
        h1, h2, h3 = lanes(4096)
        h2 = rng.permutation(4096).astype(np.uint32)
        return h1, h2, h3, rng.integers(1, 100, 4096).astype(np.int64)
    if name == "h3-alone-repeats":
        # Rows that share h3 and nothing else are distinct triples.
        h1, h2, h3 = lanes(512)
        h2 = rng.permutation(512).astype(np.uint32)
        h3[:] = 7
        return h1, h2, h3, rng.integers(1, 100, 512).astype(np.int64)
    if name == "heavy-repeats":
        idx = rng.integers(0, 40, 3000)
        return (*[x[idx] for x in lanes(40)],
                rng.integers(1, 100, 3000).astype(np.int64))
    if name == "one-triple":
        return (*[x[np.zeros(257, np.int64)] for x in lanes(1)],
                rng.integers(1, 100, 257).astype(np.int64))
    if name in ("collision-two-h3", "collision-no-triple-repeats"):
        # A forced 64-bit collision: one (h1, h2) under two h3 values,
        # interleaved so the run is not grouped by h3 after a stable
        # argsort of the 64-bit key (A, B, A).
        idx = rng.integers(0, 64, 600)
        h1, h2, h3 = [x[idx] for x in lanes(64)]
        if name == "collision-no-triple-repeats":
            h1, h2, h3 = lanes(600)
            h2 = rng.permutation(600).astype(np.uint32)
        h1[[5, 9, 300]] = h1[5]
        h2[[5, 9, 300]] = h2[5]
        h3[[5, 9, 300]] = (11, 12, 11)
        if name == "collision-no-triple-repeats":
            h3[300] = 13
        return h1, h2, h3, rng.integers(1, 100, 600).astype(np.int64)
    if name in ("mass=2^53", "mass>2^53"):
        idx = rng.integers(0, 16, 200)
        w = np.ones(200, np.int64)
        # Odd masses above 2^53 are where a float64 sum goes wrong.
        w[:3] = (2**53 - 199, 1, 2) if name == "mass=2^53" \
            else (2**53 + 1, 2**60 + 1, 2**61 + 3)
        idx[:3] = (0, 0, 1)
        return (*[x[idx] for x in lanes(16)], w)
    raise AssertionError(name)


@pytest.mark.parametrize("name,how", [
    ("n=0", "unique"), ("n=1", "unique"), ("n=2", "unique"),
    ("n=2-same", "folded"), ("distinct", "unique"),
    ("h3-alone-repeats", "unique"), ("heavy-repeats", "folded"),
    ("one-triple", "folded"), ("collision-two-h3", "wide"),
    ("collision-no-triple-repeats", "wide-unique"),
    ("mass=2^53", "folded"), ("mass>2^53", "folded")])
def test_integer_triple_fold_is_the_record_fold(name, how, monkeypatch):
    """The feed folds on 64-bit integer keys; what comes out is what
    fold_rows_first_seen gives for the same rows as 12-byte records, to
    the element: representatives, folded weights, first-occurrence
    order. The counters say which way the batch went."""
    import parca_agent_tpu.aggregator.dict as D

    h1, h2, h3, w = _triple_case(name)
    n = len(h1)
    key = np.stack([h1, h2, h3], axis=1).astype(np.uint32)
    want = fold_rows_first_seen(
        np.ascontiguousarray(key).view(np.dtype((np.void, 12))).ravel(), w)
    record_folds = []
    sound = D.fold_rows_first_seen
    monkeypatch.setattr(D, "fold_rows_first_seen",
                        lambda *a: record_folds.append(1) or sound(*a))
    agg = DictAggregator(capacity=1 << 10)
    rows_map = np.arange(100, 100 + n, dtype=np.int64)
    g1, g2, g3, gw, gmap = agg._coalesce_triples(h1, h2, h3, w, rows_map)
    if want is None:
        assert how in ("unique", "wide-unique")
        # The inputs come back as they are: nothing was rebuilt.
        assert (g1 is h1 and g2 is h2 and g3 is h3 and gw is w
                and gmap is rows_map)
        rep = np.arange(n)
    else:
        assert how in ("folded", "wide")
        rep, _inv, fw = want
        assert np.array_equal(gmap, rows_map[rep])
        assert gw.dtype == np.int64 and np.array_equal(gw, fw)
        triples = [tuple(t) for t in key.tolist()]
        assert [int(x) for x in gw] == [
            sum(int(c) for c, t in zip(w, triples) if t == triples[r])
            for r in rep]   # exact, in Python
        assert np.array_equal(np.stack([g1, g2, g3], axis=1), key[rep])
        assert np.array_equal(rep, np.sort(rep))  # first-occurrence order
    st = agg.stats
    assert st.get("coalesce_fallbacks", 0) == 0
    assert (st["coalesce_rows_in"], st["coalesce_rows_out"]) == (n, len(rep))
    assert st.get("coalesce_unique_batches", 0) == (how == "unique")
    assert st.get("coalesce_wide_folds", 0) == how.startswith("wide")
    # Only a 64-bit collision pays the record sort.
    assert len(record_folds) == how.startswith("wide")


# -- native batch hash kernel -------------------------------------------------


def test_native_hash_bit_identical_to_numpy():
    for seed in (1, 2, 3):
        snap = _snap(seed=seed, rows=1024, pids=16)
        for n_hashes in (2, 3):
            native, ref = _hash_pair(snap, n_hashes)
            assert len(native) == n_hashes
            for a, b in zip(native, ref):
                assert a.dtype == np.uint32
                assert np.array_equal(a, b)


def test_native_hash_zero_rows_and_depth_edge():
    snap = _snap(seed=5, rows=64, pids=4)
    empty = dataclasses.replace(
        snap, pids=snap.pids[:0], tids=snap.tids[:0],
        counts=snap.counts[:0], user_len=snap.user_len[:0],
        kernel_len=snap.kernel_len[:0], stacks=snap.stacks[:0])
    native, ref = _hash_pair(empty)
    for a, b in zip(native, ref):
        assert len(a) == 0 and len(b) == 0
    # Zero-depth rows (scalar-ladder degraded pids) hash from the
    # pid/len lanes alone — identical either way.
    flat = dataclasses.replace(
        snap, user_len=np.zeros(len(snap), np.int32),
        kernel_len=np.zeros(len(snap), np.int32),
        stacks=np.zeros_like(snap.stacks))
    native, ref = _hash_pair(flat)
    for a, b in zip(native, ref):
        assert np.array_equal(a, b)


# -- coalesced feed exactness -------------------------------------------------


def test_coalesced_feed_counts_and_registry_identical_to_raw():
    dup = _dup(_snap(seed=7, rows=1024, pids=16), dup=3)
    a = DictAggregator(capacity=1 << 13, overflow="raise", coalesce=True)
    b = DictAggregator(capacity=1 << 13, overflow="raise", coalesce=False)
    for w in range(3):
        ca = a.window_counts(dup)
        cb = b.window_counts(dup)
        assert np.array_equal(ca, cb)
        assert int(ca.sum()) == dup.total_samples()
    # Identical id assignment and per-pid registries (pprof inputs).
    assert a._key_to_id == b._key_to_id
    assert np.array_equal(a._id_pid[:a._next_id], b._id_pid[:b._next_id])
    for pid in a._pids:
        assert a.registry_digest(pid) == b.registry_digest(pid)
    # The fold did real work and the stats say so.
    assert a.stats["coalesce_rows_out"] * 3 == a.stats["coalesce_rows_in"]
    assert "coalesce_rows_in" not in b.stats


def test_coalesced_miss_corrections_carry_folded_weights():
    """Every duplicate's mass must reach its stack id through the miss
    path (first window: all misses) — a representative-count bug would
    drop (dup-1)/dup of the window."""
    base = _snap(seed=11, rows=600, pids=8)
    dup = _dup(base, dup=4)
    a = DictAggregator(capacity=1 << 12, overflow="raise", coalesce=True)
    counts = a.window_counts(dup)
    assert int(counts.sum()) == dup.total_samples()
    # Per-key: 4x the base row's count.
    h1, h2, h3 = a.hash_rows(base)
    for i in range(0, len(base), 37):
        sid = a._key_to_id[(int(h1[i]), int(h2[i]), int(h3[i]))]
        assert int(counts[sid]) == 4 * int(base.counts[i])


def test_pprof_byte_identity_coalesced_vs_raw_dict():
    from parca_agent_tpu.pprof.window_encoder import WindowEncoder

    dup = _dup(_snap(seed=13, rows=512, pids=8), dup=3)
    arms = {
        "raw": DictAggregator(capacity=1 << 12, overflow="raise",
                              coalesce=False),
        "coalesced": DictAggregator(capacity=1 << 12, overflow="raise",
                                    coalesce=True),
    }
    encs = {k: WindowEncoder(v) for k, v in arms.items()}
    digests = {k: [] for k in arms}
    for w in range(3):
        for k, agg in arms.items():
            c = agg.window_counts(dup)
            digests[k].append(_encode_digest(encs[k], c, w))
    assert digests["coalesced"] == digests["raw"]


def test_pprof_byte_identity_across_cm_rotation():
    """dict+cm arm: overflow into the sketch plus a cold-stack rotation
    mid-stream — the coalesced arm must ride the identical degrade/
    rotate schedule, byte for byte."""
    from parca_agent_tpu.pprof.window_encoder import WindowEncoder

    s1 = _dup(_snap(seed=17, rows=200, pids=4), dup=3)
    s2 = _dup(_snap(seed=18, rows=200, pids=4), dup=3)
    arms = {
        "raw": DictAggregator(capacity=1 << 9, id_cap=256,
                              rotate_min_age=1, coalesce=False),
        "coalesced": DictAggregator(capacity=1 << 9, id_cap=256,
                                    rotate_min_age=1, coalesce=True),
    }
    encs = {k: WindowEncoder(v) for k, v in arms.items()}
    digests = {k: [] for k in arms}
    for w, snap in enumerate((s1, s2, s1, s2)):
        for k, agg in arms.items():
            c = agg.window_counts(snap)
            digests[k].append(_encode_digest(encs[k], c, w))
    assert digests["coalesced"] == digests["raw"]
    assert arms["coalesced"].stats.get("rotations", 0) >= 1
    assert arms["coalesced"].stats.get("rotations", 0) == \
        arms["raw"].stats.get("rotations", 0)
    # Absorbed MASS is identical (sketch_rows naturally differs: the
    # raw arm absorbs each duplicate as its own row, the coalesced arm
    # absorbs one folded row carrying the same weight).
    assert arms["coalesced"].stats.get("sketch_samples", 0) == \
        arms["raw"].stats.get("sketch_samples", 0)
    h1, _h2, _h3 = arms["raw"].hash_rows(s1)
    assert np.array_equal(arms["coalesced"].sketch_estimate(h1[:64]),
                          arms["raw"].sketch_estimate(h1[:64]))


def test_pprof_byte_identity_native_vs_numpy_hash(monkeypatch):
    from parca_agent_tpu.pprof.window_encoder import WindowEncoder

    dup = _dup(_snap(seed=19, rows=512, pids=8), dup=2)
    digests = {}
    for arm in ("native", "numpy"):
        if arm == "numpy":
            monkeypatch.setenv("PARCA_NO_NATIVE_HASH", "1")
        else:
            monkeypatch.delenv("PARCA_NO_NATIVE_HASH", raising=False)
        agg = DictAggregator(capacity=1 << 12, overflow="raise")
        enc = WindowEncoder(agg)
        digests[arm] = [_encode_digest(enc, agg.window_counts(dup), w)
                        for w in range(2)]
    assert digests["native"] == digests["numpy"]


def test_coalesced_overflow_sideband_and_widen_retry_identical():
    """The grow-then-widen close retry ladder under coalescing: a hard
    count-distribution shift overruns the narrow sideband in BOTH arms,
    and the retried closes stay byte-equal."""
    n = 40_960
    snap1 = generate(SyntheticSpec(n_pids=16, n_unique_stacks=n, n_rows=n,
                                   total_samples=n, mean_depth=8, seed=31))
    snap1 = dataclasses.replace(snap1, counts=np.ones(n, np.int64))
    # dup=2 with per-row count 10: folded weight 20 crosses the 4-bit
    # sentinel for every id, exactly the misprediction the ladder eats.
    dup1 = _dup(snap1, dup=2)
    dup2 = dataclasses.replace(dup1, counts=np.full(len(dup1), 10,
                                                    np.int64))
    arms = {
        "raw": DictAggregator(capacity=1 << 17, coalesce=False),
        "coalesced": DictAggregator(capacity=1 << 17, coalesce=True),
    }
    got = {}
    for k, d in arms.items():
        d.feed(dup1)
        c1 = d.close_window()
        assert int(c1.sum()) == 2 * n
        d.feed(dup2)
        got[k] = d.close_window()
        assert d.stats.get("close_retries", 0) >= 1
    assert np.array_equal(got["coalesced"], got["raw"])
    assert set(np.unique(got["raw"]).tolist()) == {20}


# -- vectorized miss settle ---------------------------------------------------


def _assert_valid_probe_layout(agg):
    """Every key must be findable by the linear probe from its home
    slot (chain prefix fully occupied), and the unreachable set must be
    exactly the keys past the device probe bound."""
    for key, sid in agg._key_to_id.items():
        mask = agg._cap - 1
        idx = key[0] & mask
        dist = 0
        while True:
            assert agg._occ[idx], f"hole in chain for {key}"
            if (int(agg._h1[idx]), int(agg._h2[idx]),
                    int(agg._h3[idx])) == key:
                assert int(agg._ids[idx]) == sid
                break
            idx = (idx + 1) & mask
            dist += 1
        assert (dist >= _PROBES) == (key in agg._unreachable)


def test_vec_miss_settle_matches_scalar():
    import parca_agent_tpu.aggregator.dict as D

    dup = _dup(_snap(seed=23, rows=2048, pids=16), dup=2)
    vec = DictAggregator(capacity=1 << 13, overflow="raise")
    cv = vec.window_counts(dup)
    assert vec.stats.get("miss_vec_inserts", 0) == 2048
    assert vec.stats.get("miss_vec_fallbacks", 0) == 0
    old = D._VEC_MISS_MIN
    D._VEC_MISS_MIN = 10**9
    try:
        sca = DictAggregator(capacity=1 << 13, overflow="raise")
        cs = sca.window_counts(dup)
    finally:
        D._VEC_MISS_MIN = old
    # Same ids, same counts, same registries; the slot layout may
    # differ (placement arbitration vs sequential order) but both must
    # be valid linear-probe tables.
    assert np.array_equal(cv, cs)
    assert vec._key_to_id == sca._key_to_id
    assert np.array_equal(vec._occ, sca._occ)
    _assert_valid_probe_layout(vec)
    _assert_valid_probe_layout(sca)
    # Steady state: no further inserts, still exact.
    assert np.array_equal(vec.window_counts(dup), sca.window_counts(dup))


def test_vec_miss_settle_overflow_stat_parity_with_scalar():
    """overflow_misses must keep ONE unit (per miss row) regardless of
    which settle path the batch size picked: the fold collapses
    duplicate rows, so the vec path counts their multiplicity back."""
    import parca_agent_tpu.aggregator.dict as D

    dup = _dup(_snap(seed=67, rows=1500, pids=8), dup=2)
    vec = DictAggregator(capacity=1 << 13, overflow="raise",
                         coalesce=False)
    vec.window_counts(dup)
    old = D._VEC_MISS_MIN
    D._VEC_MISS_MIN = 10**9
    try:
        sca = DictAggregator(capacity=1 << 13, overflow="raise",
                             coalesce=False)
        sca.window_counts(dup)
    finally:
        D._VEC_MISS_MIN = old
    assert vec.stats["overflow_misses"] == sca.stats["overflow_misses"]
    assert vec.stats["overflow_misses"] == 1500  # one dup row per key


def test_vec_miss_settle_falls_back_on_capacity_pressure():
    """Near the id cap the vectorized path must hand the batch to the
    scalar loop (which owns the sketch degrade + rotation request) —
    never degrade on its own."""
    snap = _snap(seed=29, rows=1024, pids=8)
    d = DictAggregator(capacity=1 << 11, id_cap=600, rotate_min_age=1)
    d.window_counts(snap)
    assert d.stats.get("miss_vec_fallbacks", 0) >= 1
    assert d.stats.get("miss_vec_inserts", 0) == 0
    assert d.stats.get("sketch_rows", 0) > 0  # degraded, never lost
    assert d._rotate_pending


def test_vec_and_scalar_prefix_reuse_mixed_batches():
    """A second population fed after the first exercises the existing-
    key classification (overflow corrections) beside fresh inserts."""
    s1 = _snap(seed=41, rows=1024, pids=8)
    s2 = _snap(seed=42, rows=1024, pids=8)
    from parca_agent_tpu.capture.formats import concat_snapshots

    both = concat_snapshots([s1, s1, s2])  # s1 rows duplicated
    vec = DictAggregator(capacity=1 << 13, overflow="raise")
    vec.window_counts(s1)
    c = vec.window_counts(both)
    assert int(c.sum()) == both.total_samples()
    _assert_valid_probe_layout(vec)


def test_sharded_coalesced_counts_identical_to_raw():
    """The mesh-sharded aggregator inherits the fold through the base
    feed: partitioned dispatch rows shrink to uniques per shard and the
    counts stay byte-equal to the uncoalesced arm."""
    from parca_agent_tpu.aggregator.sharded import ShardedDictAggregator

    dup = _dup(_snap(seed=37, rows=512, pids=8), dup=3)
    a = ShardedDictAggregator(capacity=1 << 12, n_shards=1, coalesce=True)
    b = ShardedDictAggregator(capacity=1 << 12, n_shards=1,
                              coalesce=False)
    for _ in range(2):
        ca = a.window_counts(dup)
        cb = b.window_counts(dup)
        assert np.array_equal(ca, cb)
        assert int(ca.sum()) == dup.total_samples()
    assert a._key_to_id == b._key_to_id
    assert a.stats["coalesce_rows_out"] * 3 == a.stats["coalesce_rows_in"]


# -- chaos: feed.coalesce degrades to the uncoalesced path --------------------


@pytest.mark.chaos
def test_feed_coalesce_fault_falls_back_uncoalesced():
    """An injected fault mid-coalesce costs NOTHING but the fold: the
    batch dispatches uncoalesced, the window closes exact
    (windows_lost == 0), and the next window coalesces again."""
    dup = _dup(_snap(seed=43, rows=512, pids=8), dup=3)
    ref = DictAggregator(capacity=1 << 12, overflow="raise",
                         coalesce=False)
    want = ref.window_counts(dup)

    faults.install(faults.FaultInjector.from_spec(
        "feed.coalesce:error:count=1", seed=42))
    d = DictAggregator(capacity=1 << 12, overflow="raise", coalesce=True)
    got = d.window_counts(dup)  # fold faulted: dispatched uncoalesced
    assert d.stats.get("coalesce_fallbacks", 0) == 1
    assert d.stats.get("coalesce_rows_out", 0) == 0
    assert np.array_equal(got, want)
    assert int(got.sum()) == dup.total_samples()  # windows_lost == 0
    got2 = d.window_counts(dup)  # rule exhausted: folding again
    assert np.array_equal(got2, want)
    assert d.stats["coalesce_rows_out"] == len(dup) // 3
    assert faults.get().stats().get("feed.coalesce") == 1


# -- trace/feeder hygiene -----------------------------------------------------


class _FakeMaps:
    def executable_mappings(self, pid):
        return []


class _FakeObjs:
    def build_ids(self, per_pid):
        return {}


def _cols(snap, lo, hi):
    return (snap.pids[lo:hi], snap.tids[lo:hi], snap.user_len[lo:hi],
            snap.kernel_len[lo:hi], snap.stacks[lo:hi], snap.counts[lo:hi])


@pytest.fixture(params=[0.0, 1.0], ids=["no-period", "1s-period"])
def window_period(request):
    """The stale-timing-pop cases run twice: bare, and under a 1 s
    window period with the device flight recorder installed — the
    sub-second-window regime the SLO layer judges
    (docs/observability.md "device flight recorder"). The pop contract
    must hold identically; the 1 s arm additionally exercises the
    telemetry record path under the feeder's dispatch cadence."""
    from parca_agent_tpu.runtime import device_telemetry as dtel_mod

    period = request.param
    if period:
        dtel_mod.install(dtel_mod.DeviceTelemetry(period_s=period))
    yield period
    dtel_mod.install(None)


def test_feeder_tracks_hash_and_coalesce_seconds(window_period):
    from parca_agent_tpu.profiler.streaming import StreamingWindowFeeder

    dup = _dup(_snap(seed=47, rows=256, pids=4), dup=3)
    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(_FakeMaps(), _FakeObjs()))
    for lo in range(0, len(dup), 128):
        feeder.on_drain(_cols(dup, lo, min(lo + 128, len(dup))))
    counts = feeder.take_window_if_complete(dup)
    assert counts is not None
    assert feeder.stats["last_window_hash_s"] > 0.0
    assert feeder.stats["last_window_coalesce_s"] > 0.0
    # Empty window: the per-window numbers reset — nothing stale.
    empty = dataclasses.replace(
        dup, pids=dup.pids[:0], tids=dup.tids[:0], counts=dup.counts[:0],
        user_len=dup.user_len[:0], kernel_len=dup.kernel_len[:0],
        stacks=dup.stacks[:0])
    assert feeder.take_window_if_complete(empty) is not None
    assert feeder.stats["last_window_hash_s"] == 0.0
    assert feeder.stats["last_window_coalesce_s"] == 0.0


def test_fallback_window_hash_timings_do_not_leak_into_next_stream(
        window_period):
    """A one-shot window_counts between streamed windows leaves its own
    feed_hash/feed_coalesce in the shared aggregator's timings; the next
    streamed window's first drain must discard them, not absorb them."""
    from parca_agent_tpu.profiler.streaming import StreamingWindowFeeder

    dup = _dup(_snap(seed=53, rows=256, pids=4), dup=3)
    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(_FakeMaps(), _FakeObjs()))
    agg.window_counts(dup)  # one-shot fallback window
    assert "feed_hash" in agg.timings or "feed_coalesce" in agg.timings
    sentinel = 99.0
    agg.timings["feed_hash"] = sentinel
    agg.timings["feed_coalesce"] = sentinel
    for lo in range(0, len(dup), 128):
        feeder.on_drain(_cols(dup, lo, min(lo + 128, len(dup))))
    assert feeder.take_window_if_complete(dup) is not None
    assert feeder.stats["last_window_hash_s"] < sentinel
    assert feeder.stats["last_window_coalesce_s"] < sentinel


def test_streamed_window_records_hash_and_coalesce_spans(window_period):
    """A streamed window's hash and coalesce are the aggregator's own
    spans, recorded on the feed thread where they ran: under the
    window's one stream_feed span, itself under drain, summed over the
    drains (the feeder's per-window split stays on its stats)."""
    from parca_agent_tpu.profiler.cpu import CPUProfiler
    from parca_agent_tpu.profiler.streaming import StreamingWindowFeeder
    from parca_agent_tpu.runtime.trace import FlightRecorder

    dup = _dup(_snap(seed=59, rows=128, pids=4), dup=3)
    agg = DictAggregator(capacity=1 << 11)
    feeder = StreamingWindowFeeder(agg, CacheSource(_FakeMaps(), _FakeObjs()))

    class Src:
        def __init__(self, n):
            self._n = n

        def poll(self):
            if not self._n:
                return None
            self._n -= 1
            for lo in range(0, len(dup), 128):
                feeder.on_drain(_cols(dup, lo, min(lo + 128, len(dup))))
            return dup

    class W:
        def write(self, labels, blob):
            pass

    rec = FlightRecorder()
    prof = CPUProfiler(source=Src(3), aggregator=agg, profile_writer=W(),
                       fast_encode=True, streaming_feeder=feeder,
                       duration_s=window_period,
                       trace_recorder=rec)
    for _ in range(3):
        assert prof.run_iteration()
        assert prof.last_error is None
    streamed = rec.traces()[-1]
    by_stage = {s["stage"]: s for s in streamed["spans"]}
    # No stage under two parents: a streamed window's last settle
    # records under the close's own names.
    assert len(by_stage) == len(streamed["spans"])
    feed = by_stage["stream_feed"]
    assert feed["parent"] == by_stage["drain"]["id"]
    assert feed["accumulated"] is True and feed["n"] == 3   # three drains
    for stage in ("feed_hash", "feed_coalesce"):
        assert by_stage[stage]["parent"] == feed["id"], stage
        assert by_stage[stage]["accumulated"] is True
        assert by_stage[stage]["thread"] == "stream-feed"
    assert streamed["meta"]["drains_fed"] == 3
    assert streamed["meta"]["streamed"] == 1
    assert rec.percentiles()["stream_feed"]["count"] >= 1
    if window_period:
        # The 1 s-period arm: every streamed window rolled into the
        # window-SLO layer, well under budget.
        from parca_agent_tpu.runtime import device_telemetry as dtel_mod

        tel = dtel_mod.get()
        assert tel.window_stats["windows_total"] == 3
        assert tel.window_stats["windows_over_budget_total"] == 0
        assert 0.0 < tel.window_stats["budget_used_last"] < 1.0
        assert tel.stats["record_errors"] == 0


# -- partition vectorization + one-shot kernel fold ---------------------------


def test_sharded_partition_vectorized_matches_reference():
    """_partition_packed's one-scatter-per-channel rewrite against a
    per-shard reference loop, plus the double-buffer contract (the
    previous drain's buffer is not overwritten by the next pack)."""
    from types import SimpleNamespace

    from parca_agent_tpu.aggregator.sharded import ShardedDictAggregator

    rng = np.random.default_rng(5)
    n_shards, n_pad = 4, 256
    packed = np.zeros((4, n_pad), np.uint32)
    n = 200
    for c in range(3):
        packed[c, :n] = rng.integers(0, 2**32, n, dtype=np.uint64)
    packed[3, :n] = rng.integers(1, 50, n)
    packed[3, 160:180] = 0  # dead lanes inside the live prefix
    fake = SimpleNamespace(_n_shards=n_shards, _cap_s=64, _part_bufs={},
                           stats={})
    out = ShardedDictAggregator._partition_packed(fake, packed)
    # Reference: the old serial per-shard loop.
    cnt = packed[3]
    live = np.flatnonzero(cnt > 0)
    shard = (packed[1, live] % np.uint32(n_shards)).astype(np.int64)
    order = np.argsort(shard, kind="stable")
    rows = live[order]
    per = np.bincount(shard, minlength=n_shards)
    bounds = np.zeros(n_shards + 1, np.int64)
    np.cumsum(per, out=bounds[1:])
    ref = np.zeros_like(out)
    for s in range(n_shards):
        mine = rows[bounds[s]: bounds[s + 1]]
        ref[s, :4, : len(mine)] = packed[:, mine]
        ref[s, 4, : len(mine)] = mine.astype(np.uint32)
    assert np.array_equal(out, ref)
    # Double buffer: the next pack must land in the OTHER buffer.
    out2 = ShardedDictAggregator._partition_packed(fake, packed)
    assert out2 is not out
    assert np.array_equal(out2, ref)
    out3 = ShardedDictAggregator._partition_packed(fake, packed)
    assert out3 is out  # alternation wraps
