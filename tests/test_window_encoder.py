"""Differential tests: vectorized window encoder vs the scalar builder.

The encoder's contract is byte-level freedom but message-level equality:
for every pid in a window, parse_pprof(encoder bytes) must describe exactly
the same profile as parse_pprof(build_pprof(PidProfile)) from the same
aggregation — samples (as address stacks with counts), mappings, locations,
string table, period/time metadata.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest
import span_scenarios

from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.pprof import proto
from parca_agent_tpu.pprof.builder import build_pprof, parse_pprof
from parca_agent_tpu.pprof.vec import (
    encode_varint_stream,
    put_varints,
    ragged_gather,
    varint_len,
)
from parca_agent_tpu.pprof.window_encoder import WindowEncoder


# -- vec primitives ----------------------------------------------------------


def _scalar_varint(v: int) -> bytes:
    out = bytearray()
    proto.put_varint(out, v)
    return bytes(out)


def test_varint_len_matches_scalar_encoder():
    rng = np.random.default_rng(0)
    vals = np.concatenate([
        np.array([0, 1, 127, 128, 16383, 16384, 2**32 - 1, 2**63, 2**64 - 1],
                 np.uint64),
        rng.integers(0, 2**63, 200, dtype=np.uint64),
    ])
    lens = varint_len(vals)
    for v, l in zip(vals.tolist(), lens.tolist()):
        assert l == len(_scalar_varint(v)), v


def test_encode_varint_stream_roundtrip():
    rng = np.random.default_rng(1)
    vals = rng.integers(0, 2**62, 500, dtype=np.uint64)
    flat, offs = encode_varint_stream(vals)
    blob = flat.tobytes()
    pos = 0
    for i, v in enumerate(vals.tolist()):
        got, pos2 = proto.get_varint(blob, pos)
        assert got == v
        assert pos2 - pos == offs[i + 1] - offs[i]
        pos = pos2
    assert pos == len(blob)


def test_put_varints_scatter_positions():
    vals = np.array([5, 300, 2**21, 1], np.uint64)
    lens = varint_len(vals)
    pos = np.array([3, 10, 20, 30], np.int64)
    out = np.zeros(40, np.uint8)
    put_varints(out, pos, vals, lens)
    blob = out.tobytes()
    for p, v in zip(pos.tolist(), vals.tolist()):
        got, _ = proto.get_varint(blob, p)
        assert got == v


def test_ragged_gather_packed_and_scattered():
    rng = np.random.default_rng(2)
    flat = rng.integers(0, 255, 1000, dtype=np.int64)
    starts = np.array([0, 100, 50, 990], np.int64)
    lens = np.array([10, 0, 25, 10], np.int64)
    out, offs = ragged_gather(flat, starts, lens)
    assert offs.tolist() == [0, 10, 10, 35, 45]
    for i in range(4):
        np.testing.assert_array_equal(
            out[offs[i]:offs[i + 1]],
            flat[starts[i]:starts[i] + lens[i]])
    # Scatter form with caller-chosen destinations.
    dst = np.array([5, 50, 60, 100], np.int64)
    out2 = np.zeros(120, np.int64)
    ragged_gather(flat, starts, lens, out=out2, out_starts=dst)
    for i in range(4):
        np.testing.assert_array_equal(
            out2[dst[i]:dst[i] + lens[i]],
            flat[starts[i]:starts[i] + lens[i]])


# -- encoder vs builder ------------------------------------------------------


def _spec(seed=7, n_pids=12, rows=400):
    return SyntheticSpec(
        n_pids=n_pids, n_unique_stacks=rows, n_rows=rows,
        total_samples=rows * 4, mean_depth=10, kernel_fraction=0.25,
        seed=seed)


def _assert_same_profiles(agg, snap, counts, encoded):
    profiles = {p.pid: p for p in agg._build_profiles(snap, counts)}
    got = dict(encoded)
    assert set(got) == set(profiles)
    for pid, prof in profiles.items():
        want = parse_pprof(build_pprof(prof, compress=False))
        have = parse_pprof(got[pid])
        # The churn-tolerant template represents stacks that got no
        # samples this window as zero-count rows (same profile
        # semantics); the scalar builder omits them. Compare the
        # observed mass.
        have_stacks = {k: v for k, v in have.stacks_by_address().items()
                       if v > 0}
        assert have_stacks == want.stacks_by_address()
        assert have.sample_types == want.sample_types
        assert have.period_type == want.period_type
        assert have.period == want.period
        assert have.time_nanos == want.time_nanos
        assert have.duration_nanos == want.duration_nanos
        assert have.mappings == want.mappings
        # Location tables: same (address, mapping) rows under the same ids.
        assert have.locations == want.locations
        assert sorted(have.strings) == sorted(want.strings)


def test_encoder_matches_builder_single_window():
    snap = generate(_spec())
    agg = DictAggregator(capacity=1 << 12)
    enc = WindowEncoder(agg)
    counts = agg.window_counts(snap)
    # Route statics through the BATCH build (the first-window warm path;
    # one vectorized mapping pass) so the differential covers it too.
    enc.build_statics(snap.period_ns)
    out = enc.encode(counts, snap.time_ns, snap.window_ns, snap.period_ns)
    assert len(out) > 1
    _assert_same_profiles(agg, snap, counts, out)

    # The straggler path (_ensure_static, scalar build) must produce the
    # same bytes as the batch build for the same registry state.
    enc2 = WindowEncoder(agg)
    out2 = enc2.encode(counts, snap.time_ns, snap.window_ns, snap.period_ns)
    assert len(out) == len(out2)
    for (p1, b1), (p2, b2) in zip(out, out2):
        assert p1 == p2 and b1 == b2


@pytest.mark.parametrize("second", ["first_seen_pids", "known_pids_grow"])
def test_the_batch_build_ships_the_stragglers_bytes(second):
    """A window that brings first-seen pids, and one in which known pids
    grow new locations: the encoder whose statics come from the batch
    build (_build_locs_batch: one concatenate of the registries' column
    views) ships the bytes of one whose build_statics does nothing, so
    that every static is built, and later extended by its delta, by the
    scalar _ensure_static."""
    snap = generate(_spec(seed=77, n_pids=14, rows=700))
    rng = np.random.default_rng(3)
    late = np.isin(snap.pids, np.unique(snap.pids)[-3:])
    half = rng.random(len(snap)) < 0.5
    first = ~late & half
    masks = [first, first | (late if second == "first_seen_pids"
                             else ~late)]
    aggs = [DictAggregator(capacity=1 << 13) for _ in range(2)]
    batch, scalar = (WindowEncoder(a) for a in aggs)
    scalar.build_statics = lambda *a, **kw: 0
    for t, mask in enumerate(masks):
        sub = span_scenarios.rows_of(snap, mask)
        outs = []
        for agg, enc in zip(aggs, (batch, scalar)):
            had = {p: r.n_locs for p, r in agg._pids.items()}
            c = np.asarray(agg.window_counts(sub)).copy()
            outs.append(_bytes_of(enc.encode(
                c, snap.time_ns + t, snap.window_ns, snap.period_ns)))
        assert outs[0] == outs[1]
        _assert_same_profiles(aggs[0], dataclasses.replace(
            sub, time_ns=snap.time_ns + t), c, outs[0])
    now = {p: r.n_locs for p, r in aggs[0]._pids.items()}
    if second == "first_seen_pids":
        assert len(now) == len(had) + 3
        assert all(now[p] == n for p, n in had.items())
    else:
        assert now.keys() == had.keys()
        assert sum(now[p] > n for p, n in had.items()) >= 5
    assert batch.stats["statics_build_s_total"] > 0
    assert scalar.stats["statics_build_s_total"] == 0
    assert scalar.stats["statics_bytes_built"] \
        == batch.stats["statics_bytes_built"] > 0


def test_the_location_key_of_array_columns_is_the_list_registrys():
    """_loc_key digests a registry's (mapping id, normalised address)
    rows as little-endian uint64, which is what np.asarray(list,
    np.uint64).tobytes() gave when the columns were lists: content-cache
    keys, and statics an older agent persisted, stay valid. The
    constants are the list registry's keys for these values (from the
    tree before PR 51)."""
    import hashlib

    from parca_agent_tpu.aggregator.dict import _PidRegistry
    from parca_agent_tpu.pprof.window_encoder import _loc_key

    k = 0xFFFF_8000_0000_0000
    addr = [0x1010, 0x4020, k + 1, 0x7FFF_FFFF_F000, 0xFFFF_FFFF_FFFF_FFFF]
    norm = [0x10, 0x120, k + 1, 0x7FFF_FFFF_F000, 0xFFFF_FFFF_FFFF_FFFF]
    mid = [1, 2, 0, 0, 0]
    reg = _PidRegistry(
        np.array(addr, np.uint64), np.array(norm, np.uint64),
        np.array(mid, np.int32),
        np.array([False, False, True, False, True]), [], {})
    pinned = {5: "4c38fec5f38d945b13fb252a2a9f400ed8",
              3: "4cbd4b1aec71ab309525cf0e14386ac131",
              0: "4cc804ce198ec337e3dc762bdd1a09aece"}
    for n, want in pinned.items():
        assert _loc_key(reg, n).hex() == want
        h = hashlib.blake2b(digest_size=16)
        h.update(n.to_bytes(8, "little"))
        h.update(np.asarray(mid[:n], np.uint64).tobytes())
        h.update(np.asarray(norm[:n], np.uint64).tobytes())
        assert (b"L" + h.digest()).hex() == want


def test_encoder_incremental_new_stacks_and_pids():
    snap1 = generate(_spec(seed=1))
    snap2 = generate(_spec(seed=2, n_pids=20, rows=600))
    agg = DictAggregator(capacity=1 << 13)
    enc = WindowEncoder(agg)
    c1 = agg.window_counts(snap1)
    out1 = enc.encode(c1, snap1.time_ns, snap1.window_ns, snap1.period_ns)
    _assert_same_profiles(agg, snap1, c1, out1)
    # Window 2 brings new stacks, new pids, and registry growth for old
    # pids; cached prefixes and static sections must update incrementally.
    c2 = agg.window_counts(snap2)
    out2 = enc.encode(c2, snap2.time_ns, snap2.window_ns, snap2.period_ns)
    _assert_same_profiles(agg, snap2, c2, out2)
    # Re-encoding window 1's counts (shorter id space) still works.
    out1b = enc.encode(c1, snap1.time_ns, snap1.window_ns, snap1.period_ns)
    assert {p for p, _ in out1b} == {p for p, _ in out1}


def test_encoder_streaming_close_path():
    snap = generate(_spec(seed=3))
    agg = DictAggregator(capacity=1 << 12)
    enc = WindowEncoder(agg)
    h = agg.hash_rows(snap)
    n = len(snap)
    agg.feed(snap, h, 0, n // 2)
    agg.feed(snap, h, n // 2, n)
    counts = agg.close_window()
    assert int(counts.sum()) == snap.total_samples()
    out = enc.encode(counts, snap.time_ns, snap.window_ns, snap.period_ns)
    _assert_same_profiles(agg, snap, counts, out)


def test_encoder_survives_rotation():
    snap1 = generate(_spec(seed=4))
    agg = DictAggregator(capacity=1 << 12, rotate_min_age=1)
    enc = WindowEncoder(agg)
    c1 = agg.window_counts(snap1)
    enc.encode(c1, snap1.time_ns, snap1.window_ns, snap1.period_ns)
    # Age window 1's ids out: a window of different stacks, then a forced
    # rotation at the next boundary evicts them and remaps every id.
    snap2 = generate(_spec(seed=5))
    agg.window_counts(snap2)
    agg._rotate_pending = True
    c2 = agg.window_counts(snap2)
    assert agg.stats.get("rotations", 0) == 1
    assert len(c2) < len(c1) + len(snap2)  # something was evicted
    out2 = enc.encode(c2, snap2.time_ns, snap2.window_ns, snap2.period_ns)
    _assert_same_profiles(agg, snap2, c2, out2)


def test_encoder_gzip_roundtrip():
    snap = generate(_spec(seed=6, n_pids=3, rows=50))
    agg = DictAggregator(capacity=1 << 10)
    enc = WindowEncoder(agg, compress=True)
    counts = agg.window_counts(snap)
    out = enc.encode(counts, snap.time_ns, snap.window_ns, snap.period_ns)
    for pid, blob in out:
        assert blob[:2] == b"\x1f\x8b"
        parsed = parse_pprof(blob)
        assert sum(v[0] for _, v, _ in parsed.samples) > 0


def test_encoder_rejects_stale_longer_counts():
    snap = generate(_spec(seed=8, n_pids=3, rows=50))
    agg = DictAggregator(capacity=1 << 10)
    enc = WindowEncoder(agg)
    counts = agg.window_counts(snap)
    with pytest.raises(ValueError):
        enc.encode(np.concatenate([counts, [1]]), 0, 0, 1)


def test_encoder_period_change_invalidates_template():
    snap = generate(_spec(seed=9, n_pids=4, rows=80))
    agg = DictAggregator(capacity=1 << 10)
    enc = WindowEncoder(agg)
    c = agg.window_counts(snap)
    enc.encode(c, snap.time_ns, snap.window_ns, snap.period_ns)
    # Same live set → template hit territory; a period change must still
    # re-emit (the period is embedded in the cached static tails).
    out = enc.encode(c, snap.time_ns, snap.window_ns, 999_999)
    for _, blob in out:
        assert parse_pprof(blob).period == 999_999
    # And with the period unchanged, the next encode is a pure patch.
    enc.encode(c, snap.time_ns + 1, snap.window_ns, 999_999)
    assert "encode_patch" in enc.timings


def test_encoder_empty_window():
    agg = DictAggregator(capacity=1 << 10)
    enc = WindowEncoder(agg)
    assert enc.encode(np.zeros(0, np.int64), 0, 0, 1) == []


# -- churn-tolerant template -------------------------------------------------


def _churn_setup(seed=21, n_pids=10, rows=500):
    """One registry-complete aggregator + encoder + full counts vector."""
    snap = generate(_spec(seed=seed, n_pids=n_pids, rows=rows))
    agg = DictAggregator(capacity=1 << 13)
    enc = WindowEncoder(agg)
    c_full = agg.window_counts(snap)
    return snap, agg, enc, np.asarray(c_full)


def test_encoder_count_churn_is_a_patch_not_a_relayout():
    """A window whose live set shrank a little (stacks went cold) must ride
    the patch path — dead template rows become zero-count samples — and
    still parse to exactly the oracle's profiles."""
    snap, agg, enc, c_full = _churn_setup()
    enc.encode(c_full, snap.time_ns, snap.window_ns, snap.period_ns)
    rng = np.random.default_rng(5)
    c2 = c_full.copy()
    c2[rng.random(len(c2)) < 0.2] = 0
    c2[c2 > 0] += 3
    enc.timings.clear()
    out = enc.encode(c2, snap.time_ns, snap.window_ns, snap.period_ns)
    assert "encode_build" not in enc.timings      # no relayout
    assert "encode_patch" in enc.timings
    _assert_same_profiles(agg, snap, c2, out)


def test_encoder_new_stacks_append_into_slack():
    """Stacks (and whole pids) the template has never seen are APPENDED —
    per-pid slack, relocation, or a fresh blob — without a full rebuild."""
    snap, agg, enc, c_full = _churn_setup()
    pids_of_id = agg._id_pid[: len(c_full)]
    victim = int(pids_of_id[0])
    c1 = c_full.copy()
    rng = np.random.default_rng(6)
    # Hide a slice of stacks and one ENTIRE pid from the first window.
    c1[rng.random(len(c1)) < 0.15] = 0
    c1[pids_of_id == victim] = 0
    out1 = enc.encode(c1, snap.time_ns, snap.window_ns, snap.period_ns)
    assert victim not in {p for p, _ in out1}
    # Full window: the hidden stacks are new template rows, the hidden
    # pid is a brand-new blob. Must stay on the append path.
    enc.timings.clear()
    out2 = enc.encode(c_full, snap.time_ns, snap.window_ns, snap.period_ns)
    assert "encode_build" not in enc.timings
    assert victim in {p for p, _ in out2}
    _assert_same_profiles(agg, snap, c_full, out2)
    # And the shrunken window again: pure zero-patch, oracle equality.
    enc.timings.clear()
    out1b = enc.encode(c1, snap.time_ns, snap.window_ns, snap.period_ns)
    assert "encode_build" not in enc.timings
    _assert_same_profiles(agg, snap, c1, out1b)


def test_encoder_slack_exhaustion_relocates_blob():
    """A pid whose appends outgrow its slack gets relocated to the end of
    the buffer; bytes stay correct and waste is accounted."""
    snap, agg, enc, c_full = _churn_setup(rows=800)
    pids_of_id = agg._id_pid[: len(c_full)]
    big = int(np.bincount(pids_of_id.astype(np.int64)).argmax())
    mask_big = pids_of_id == big
    c1 = c_full.copy()
    # First window: the big pid shows only a couple of stacks, so its blob
    # (and slack) is tiny; every other pid is fully live.
    hide = np.flatnonzero(mask_big)[2:]
    c1[hide] = 0
    enc.encode(c1, snap.time_ns, snap.window_ns, snap.period_ns)
    waste0 = enc._tmpl.waste
    enc.timings.clear()
    out = enc.encode(c_full, snap.time_ns, snap.window_ns, snap.period_ns)
    assert "encode_build" not in enc.timings
    assert enc._tmpl.waste > waste0               # relocation happened
    _assert_same_profiles(agg, snap, c_full, out)


def test_encoder_heavy_churn_rebuilds():
    """Mostly-dead template (wire bloat) forces a full relayout."""
    snap, agg, enc, c_full = _churn_setup()
    enc.encode(c_full, snap.time_ns, snap.window_ns, snap.period_ns)
    c2 = c_full.copy()
    c2[np.arange(len(c2)) % 3 != 0] = 0           # ~67% dead
    enc.timings.clear()
    out = enc.encode(c2, snap.time_ns, snap.window_ns, snap.period_ns)
    assert "encode_build" in enc.timings
    _assert_same_profiles(agg, snap, c2, out)


def _fuzz_agg(kind: str):
    if kind == "sharded":
        from parca_agent_tpu.aggregator.sharded import ShardedDictAggregator

        return ShardedDictAggregator(capacity=1 << 13)
    return DictAggregator(capacity=1 << 13)


@pytest.mark.parametrize("agg_kind", ["dict", "sharded"])
@pytest.mark.parametrize("seed", [31, 32, 33, 34, 35])
def test_encoder_churn_fuzz_multi_window(seed, agg_kind):
    """Window-sequence fuzz of the churn-tolerant template: random live
    fractions (patch/append/relocate/rebuild all get exercised), count
    perturbations, registry growth mid-sequence, and an all-dead pid now
    and then — every window must parse to exactly the oracle's profiles.
    Runs over both the single-chip dict and the mesh-sharded variant
    (same registry mirrors, different placement)."""
    rng = np.random.default_rng(seed)
    snap_a = generate(_spec(seed=seed, n_pids=8, rows=300))
    snap_b = generate(_spec(seed=seed + 100, n_pids=14, rows=500))
    agg = _fuzz_agg(agg_kind)
    enc = WindowEncoder(agg)
    c_a = np.asarray(agg.window_counts(snap_a))
    snap, c_full = snap_a, c_a
    paths_seen: set[str] = set()
    for w in range(10):
        if w == 5:
            # Registry growth: new stacks, new pids, old pids' new locs.
            c_b = np.asarray(agg.window_counts(snap_b))
            snap, c_full = snap_b, c_b
        c = c_full.copy()
        frac = rng.uniform(0.2, 1.0)
        c[rng.random(len(c)) < 1 - frac] = 0
        if rng.random() < 0.5:
            c[c > 0] += rng.integers(1, 5)
        if rng.random() < 0.4 and len(np.unique(agg._id_pid[:len(c)])) > 2:
            # Kill one whole pid this window.
            victim = int(rng.choice(agg._id_pid[:len(c)]))
            c[agg._id_pid[:len(c)] == victim] = 0
        if not int((c > 0).sum()):
            # All-dead window on a warm template: nothing to ship, and
            # the stale template must not leak.
            assert enc.encode(c, snap.time_ns, snap.window_ns,
                              snap.period_ns) == []
            continue
        enc.timings.clear()
        out = enc.encode(c, snap.time_ns, snap.window_ns, snap.period_ns)
        paths_seen.add("build" if "encode_build" in enc.timings
                       else "patch")
        _assert_same_profiles(agg, snap, c, out)
    # The fuzz must have exercised the incremental machinery, not routed
    # every window through the full rebuild.
    assert "patch" in paths_seen


def test_encoder_views_are_invalidated_by_the_next_encode():
    """views=True returns zero-copy memoryviews into the template buffer,
    valid only until the next encode() — which patches counts in place.
    Consumers must finish within their window; this pins
    the aliasing so nobody 'optimizes' the default copy path away."""
    snap, agg, enc, c_full = _churn_setup(seed=41, n_pids=4, rows=80)
    out1 = enc.encode(c_full, snap.time_ns, snap.window_ns, snap.period_ns,
                      views=True)
    pid0, view0 = out1[0]
    before = bytes(view0)
    c2 = c_full.copy()
    c2[c2 > 0] += 1000            # move every count
    enc.encode(c2, snap.time_ns, snap.window_ns, snap.period_ns, views=True)
    after = bytes(view0)
    assert before != after        # the old view aliases patched memory
    # The default (views=False) hands out stable copies instead.
    out3 = enc.encode(c_full, snap.time_ns, snap.window_ns, snap.period_ns)
    _, blob = out3[0]
    stable = bytes(blob)
    enc.encode(c2, snap.time_ns, snap.window_ns, snap.period_ns)
    assert bytes(blob) == stable


# -- content-addressed statics ------------------------------------------------


@pytest.mark.parametrize("remap", ["followed", "missed"])
def test_rotation_rebuild_is_served_from_what_was_built(remap):
    """A registry rotation remaps every id. An encoder that follows the
    aggregator's remap keeps the statics of the pids that stay, and one
    that missed it (the aggregator keeps one remap, the last
    boundary's) loses the per-pid statics map and is served by the
    content cache (keyed by build inputs, not pids): either way bytes
    identical to a fresh cold encoder, with zero re-encoding for the
    surviving content."""
    snap1 = generate(_spec(seed=51))
    snap2 = generate(_spec(seed=52))
    agg = DictAggregator(capacity=1 << 13, rotate_min_age=1)
    enc = WindowEncoder(agg)
    c1 = agg.window_counts(snap1)
    enc.encode(c1, snap1.time_ns, snap1.window_ns, snap1.period_ns)
    # Register snap2's stacks and encode once so the POST-growth statics
    # content is what the cache holds; then rotate snap1's ids out.
    c2a = agg.window_counts(snap2)
    enc.encode(c2a, snap2.time_ns, snap2.window_ns, snap2.period_ns)
    agg._rotate_pending = True
    c2 = agg.window_counts(snap2)
    assert agg.stats.get("rotations", 0) == 1
    assert agg.id_remap(0) is not None and agg.id_remap(1) is None
    if remap == "missed":
        agg._remap = None
    built_before = enc.stats["statics_bytes_built"]
    hits_before = enc.stats["statics_cache_hits"]
    out = enc.encode(c2, snap2.time_ns, snap2.window_ns, snap2.period_ns)
    assert enc.stats["epoch_changes_total"] == 1
    if remap == "followed":
        assert enc.stats["epoch_statics_kept_total"] == len(enc._static) > 0
        assert enc.stats["epoch_statics_dropped_total"] == 0
        assert enc.stats["statics_cache_hits"] == hits_before
        assert enc.stats["order_rebuilds_total"] == 1   # the cold one
    else:
        assert enc.stats["epoch_statics_kept_total"] == 0
        assert enc.stats["epoch_statics_dropped_total"] > 0
        assert enc.stats["statics_cache_hits"] > hits_before
        # Surviving pids' sections were not re-encoded, only looked up.
        assert enc.stats["statics_bytes_reused"] > 0
        assert enc.stats["order_rebuilds_total"] == 2
    ref = WindowEncoder(agg).encode(c2, snap2.time_ns, snap2.window_ns,
                                    snap2.period_ns)
    assert [(p, bytes(b)) for p, b in out] \
        == [(p, bytes(b)) for p, b in ref]
    assert enc.stats["statics_bytes_built"] == built_before


def test_cross_pid_dedup_shares_identical_statics():
    """Two pids with byte-identical layouts (same mappings, same stacks
    — forks, same-image containers) must share ONE head/tail pair and
    ONE location blob via the content cache."""
    from parca_agent_tpu.capture.formats import (
        STACK_SLOTS,
        MappingTable,
        WindowSnapshot,
    )

    table = MappingTable(
        pids=[1, 2], starts=[0x1000, 0x1000], ends=[0x9000, 0x9000],
        offsets=[0, 0], objs=[0, 0], obj_paths=("/bin/app",),
        obj_buildids=("ab" * 20,))
    stacks = np.zeros((4, STACK_SLOTS), np.uint64)
    for i, pid in enumerate((1, 1, 2, 2)):
        stacks[i, :2] = [0x1000 + 0x10 * (i % 2 + 1),
                         0x1000 + 0x100 * (i % 2 + 1)]
    snap = WindowSnapshot(
        pids=[1, 1, 2, 2], tids=[1, 1, 2, 2], counts=[3, 4, 3, 4],
        user_len=[2] * 4, kernel_len=[0] * 4, stacks=stacks,
        mappings=table)
    agg = DictAggregator(capacity=1 << 10)
    enc = WindowEncoder(agg)
    c = agg.window_counts(snap)
    enc.build_statics(snap.period_ns)
    st1, st2 = enc._static[1], enc._static[2]
    assert st1.head is st2.head          # one interned blob, two pids
    assert st1.tail is st2.tail
    assert st1.loc_bytes is st2.loc_bytes
    assert enc.stats["statics_bytes_reused"] > 0
    out = enc.encode(c, snap.time_ns, snap.window_ns, snap.period_ns)
    _assert_same_profiles(agg, snap, c, out)


def test_churn_append_rides_the_vectorized_fast_path():
    """The churn regime — known stacks reappearing across many pids with
    unchanged statics — must take the vectorized append (one scatter for
    all groups), not the per-group walk, and still match the oracle."""
    snap, agg, enc, c_full = _churn_setup(seed=53, n_pids=12, rows=600)
    rng = np.random.default_rng(8)
    c1 = c_full.copy()
    c1[rng.random(len(c1)) < 0.3] = 0   # hide stacks across every pid
    enc.encode(c1, snap.time_ns, snap.window_ns, snap.period_ns)
    enc.timings.clear()
    out = enc.encode(c_full, snap.time_ns, snap.window_ns, snap.period_ns)
    assert "encode_build" not in enc.timings      # append, not relayout
    assert enc.stats["append_fast_groups"] > 0
    assert enc.stats["append_fast_groups"] >= enc.stats["append_slow_groups"]
    _assert_same_profiles(agg, snap, c_full, out)


def test_adopt_statics_short_circuits_build():
    """adopt_statics + adopt_registry (the statics store's path) leave
    nothing to build: statics_backlog is zero and the first encode
    re-encodes no statics bytes."""
    snap = generate(_spec(seed=54, n_pids=6, rows=150))
    agg1 = DictAggregator(capacity=1 << 12)
    enc1 = WindowEncoder(agg1)
    c1 = agg1.window_counts(snap)
    enc1.encode(c1, snap.time_ns, snap.window_ns, snap.period_ns)

    agg2 = DictAggregator(capacity=1 << 12)
    enc2 = WindowEncoder(agg2)
    for pid, reg in agg1._pids.items():
        assert agg2.adopt_registry(
            pid, list(reg.mappings), list(reg.loc_address),
            list(reg.loc_normalized), list(reg.loc_mapping_id),
            list(reg.loc_is_kernel))
        st = enc1._static[pid]
        enc2.adopt_statics(pid, st.head, st.tail, bytes(st.loc_bytes),
                           st.n_mappings, st.n_locs, st.period_ns)
    assert enc2.statics_backlog(snap.period_ns) == 0
    c2 = agg2.window_counts(snap)
    out = enc2.encode(c2, snap.time_ns, snap.window_ns, snap.period_ns)
    assert enc2.stats["statics_bytes_built"] == 0
    assert [(p, bytes(b)) for p, b in out] == [
        (p, bytes(b)) for p, b in enc1.encode(
            c1, snap.time_ns, snap.window_ns, snap.period_ns)]


# -- the static span handed to the ship path ----------------------------------
# (tests/span_scenarios.py; the gzip member spliced from it is held by
# tests/test_agent_transport.py)


def _span_bytes(blob) -> bytes:
    off, ln = blob.static_span
    return bytes(blob)[off: off + ln]


def _assert_span_is_the_static_block(enc, out) -> None:
    """Every blob's span is [head][locations as laid down][tail] of its
    pid, and what follows it begins with the 22-byte time tail: the span
    never reaches into bytes that change every window."""
    from parca_agent_tpu.pprof.builder import P_DURATION_NANOS, P_TIME_NANOS

    for pid, blob in out.span_blobs():
        raw = bytes(blob)
        off, ln = blob.static_span
        st = enc._static[pid]
        n_loc = ln - len(st.head) - len(st.tail)
        assert 0 <= n_loc <= len(st.loc_bytes)
        assert raw[off: off + ln] \
            == st.head + bytes(st.loc_bytes)[:n_loc] + st.tail
        tail = raw[off + ln: off + ln + 22]
        assert len(tail) == 22
        assert tail[0] == P_TIME_NANOS << 3
        assert tail[11] == P_DURATION_NANOS << 3


@pytest.mark.parametrize("shape", sorted(span_scenarios.SHAPES))
def test_span_blob_is_a_bytes_like_of_the_same_bytes(shape):
    """A views=True output knows where each blob's static block lies;
    to every consumer its blobs are the bytes views=False copies."""
    import hashlib

    snap = span_scenarios.shape_snapshot(shape)
    agg = DictAggregator(capacity=1 << 12)
    enc = WindowEncoder(agg)
    c = agg.window_counts(snap)
    out = enc.encode(c, snap.time_ns, snap.window_ns, snap.period_ns,
                     views=True)
    _assert_same_profiles(agg, snap, c, [(p, bytes(b)) for p, b in out])
    _assert_span_is_the_static_block(enc, out)
    # To whoever iterates it the output is the [(pid, memoryview)] it
    # was; the ship path's wrapped blobs are the same bytes.
    assert all(type(view) is memoryview for _, view in out)
    assert [(p, bytes(b)) for p, b in out.span_blobs()] \
        == [(p, bytes(v)) for p, v in out]
    for pid, blob in out.span_blobs():
        raw = bytes(blob)
        assert len(blob) == len(raw)
        assert hashlib.sha256(blob).digest() == hashlib.sha256(raw).digest()
        assert np.frombuffer(blob, np.uint8).tobytes() == raw
        off, ln = blob.static_span
        assert off > 0 and ln > 0 and off + ln + 22 == len(raw)
        assert blob.static_piece() is None
    if shape == "no_locations":
        bare = dict(out.span_blobs())[999_999]
        st = enc._static[999_999]
        assert st.n_locs == 0 and _span_bytes(bare) == st.head + st.tail
    copies = enc.encode(c, snap.time_ns, snap.window_ns, snap.period_ns)
    assert [(p, bytes(b)) for p, b in out] == copies
    assert all(type(b) is bytes for _, b in copies)


@pytest.mark.parametrize("site", span_scenarios.SITES)
def test_static_span_revision_follows_every_rewrite(site):
    """A span's revision changes when, and only when, the bytes inside it
    are written; a move keeps it, and so keeps the group's compressed
    piece; a reset and a rotation that grew every static start an empty
    piece cache, and a relayout a new one that takes over the piece of
    every span it lays down again with the bytes it held."""
    seen: dict[int, tuple[bytes, int, bytes]] = {}  # pid -> span, rev, piece
    cache = None
    for enc, out, rewritten, note in span_scenarios.run(site):
        _assert_span_is_the_static_block(enc, out)
        tmpl = enc._tmpl
        relaid = note == span_scenarios.RELAID
        if rewritten is None:
            assert tmpl.pieces is not cache, note
            assert tmpl.pieces.nbytes == 0
            seen.clear()
        elif relaid:
            assert tmpl.pieces is not cache, note
            assert tmpl.pieces.nbytes == cache.nbytes > 0
        else:
            assert tmpl.pieces is cache, note
        cache = tmpl.pieces
        assert len(cache.slots) == len(tmpl.pids) == len(tmpl.span_rev)
        for pid, blob in out.span_blobs():
            rev = int(tmpl.span_rev[tmpl.group_of[pid]])
            span = _span_bytes(blob)
            if pid in seen and pid not in (rewritten or ()):
                was, old_rev, piece = seen[pid]
                assert span == was, (note, pid)
                assert (rev != old_rev) == relaid, (note, pid)
                # The piece made from these very bytes.
                assert blob.static_piece() == piece, (note, pid)
            else:
                assert pid not in seen or rev != seen[pid][1], (note, pid)
                # No piece made from other bytes is handed out.
                assert blob.static_piece() is None, (note, pid)
                piece = b"piece-%d" % rev
                blob.keep_static_piece(piece)
            seen[pid] = (span, rev, piece)
        assert enc.static_piece_bytes() == sum(
            len(s[1]) for s in cache.slots if s is not None)


# -- state carried from window to window --------------------------------------
#
# One scripted sequence of windows on ONE encoder, each step a way a
# window can differ from the one before. Beside it the same windows go
# through a second aggregator and an encoder that is made to forget,
# before every window, everything the encoder carries (the caps, the
# order, the template's kept emit state): that is the encoder without
# the carried state, on the same template history, so the two must ship
# the same bytes, profile for profile.

# step -> (views kept, caps dictionary kept, full caps loops, order
# argsorts): what each kind of window may reuse and what it must redo.
_CARRY_EXPECT = {
    "cold": (False, False, 1, 1),
    "steady_1": (True, True, 0, 0),
    "steady_2": (True, True, 0, 0),
    "steady_3": (True, True, 0, 0),
    "new_stacks_known_pids": (False, False, 0, 0),
    "steady_after_new_stacks": (True, True, 0, 0),
    "brand_new_pids": (False, False, 0, 0),
    # The counts cover fewer ids: the full loop, and nothing is carried
    # out of such a window, so the next one loops in full as well.
    "short_counts": (False, False, 1, 0),
    "after_short_counts": (False, False, 1, 0),
    "steady_after_short": (True, True, 0, 0),
    # Other groups are live: other blobs go out. The returning pid is
    # the one cap read.
    "pid_dies": (False, False, 0, 0),
    "pid_returns": (False, False, 0, 0),
    # Other ids, the same pids on the same layout: the list stands.
    "partly_dead_same_pids": (True, True, 0, 0),
    # A compaction bumps registry_epoch: the mirrors follow its remap
    # (the order and the caps of the pids that stay stand), the template
    # is laid out again.
    "pid_invalidated_and_reused": (False, False, 0, 0),
    "steady_after_invalidation": (True, True, 0, 0),
    "ids_go_cold": (True, True, 0, 0),
    # The same pids with no registry touched: their caps stand whole.
    "rotation": (False, True, 0, 0),
    "steady_after_rotation": (True, True, 0, 0),
    "steady_after_rotation_2": (True, True, 0, 0),
}


_CARRY_STEPS = tuple(_CARRY_EXPECT)


def _forget(enc) -> None:
    enc._caps = None
    enc._order = None
    enc._tmpl.kept = None


def _todays_caps(agg, prep) -> dict:
    """What the loop prepare() always ran builds for this window."""
    from parca_agent_tpu.pprof.window_encoder import _reg_cap

    return {int(p): _reg_cap(agg._pids[int(p)])
            for p in np.unique(prep.pids_live).tolist()
            if int(p) in agg._pids}


def _cap_rows(cap) -> tuple:
    """The bytes a (registry, n_mappings, n_locs) cap lets a reader on
    another thread read: every location column below n_locs, and the
    mappings below n_mappings."""
    reg, n_mappings, n_locs = cap
    return (reg.loc_address[:n_locs].tobytes(),
            reg.loc_normalized[:n_locs].tobytes(),
            reg.loc_mapping_id[:n_locs].tobytes(),
            reg.loc_is_kernel[:n_locs].tobytes(),
            repr(reg.mappings[:n_mappings]))


def _same_caps(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(
        a[p][0] is b[p][0] and a[p][1:] == b[p][1:] for p in a)


_COUNTERS = ("caps_refreshed_total", "caps_rebuilds_total",
             "order_merged_ids_total", "order_rebuilds_total",
             "views_reused_total", "layouts_built")


@pytest.fixture(scope="module")
def carried():
    """{step: facts} of the scripted sequence (recorded, not asserted:
    each case below judges its own step)."""
    rows_of = span_scenarios.rows_of
    snap = generate(_spec(seed=77, n_pids=14, rows=700))
    rng = np.random.default_rng(3)
    pids = np.unique(snap.pids)
    late = np.isin(snap.pids, pids[-3:])            # pids that arrive late
    half = rng.random(len(snap)) < 0.5
    victim, reused = int(pids[2]), int(pids[4])
    m_base = ~late & half
    # A few more stacks, for five of the known pids alone.
    m_more = m_base | (~late & np.isin(snap.pids, pids[:5])
                       & (rng.random(len(snap)) < 0.2))
    m_all = m_more | (late & half)                   # and the late pids
    m_dead = m_all & (snap.pids != victim)
    m_cold = m_all & (rng.random(len(snap)) < 0.5)   # half the ids rest

    aggs = [DictAggregator(capacity=1 << 13, rotate_min_age=1)
            for _ in range(2)]
    enc, ref = (WindowEncoder(a) for a in aggs)
    facts: dict[str, dict] = {}
    frozen: list[tuple[dict, dict]] = []
    rows_then: list[list[tuple]] = []   # each window's caps, as read then
    state = {"t": 0, "prev_out": None, "prev_caps": None, "seen": None,
             "evicted": False}

    def window(step, mask=None, counts=None, thin=None, before=None):
        """One window through both sides. `mask`: the snapshot's rows
        that are fed; `counts`: encode these (older) counts instead;
        `thin`: zero every thin-th id's count after the feed;
        `before(agg)`: run on each aggregator ahead of the feed."""
        state["t"] += 1
        t = state["t"]
        outs, preps, cs = [], [], []
        stats0 = {k: enc.stats[k] for k in _COUNTERS}
        ids0, epoch0 = enc._synced, aggs[0].registry_epoch
        for agg, e in zip(aggs, (enc, ref)):
            if before is not None:
                before(agg)
            if counts is None:
                sub = rows_of(snap, mask)
                sub = dataclasses.replace(sub, counts=sub.counts + t)
                c = np.asarray(agg.window_counts(sub)).copy()
                if thin:
                    c[::thin] = 0
            else:
                c = counts
            if e is ref:
                _forget(e)
            prep = e.prepare(c, snap.time_ns + t, snap.window_ns,
                             snap.period_ns)
            outs.append(e.encode_prepared(prep, views=True))
            preps.append(prep)
            cs.append(c)
        out, prep = outs[0], preps[0]
        if aggs[0].registry_epoch != epoch0:
            # The ids the compaction left are those the window began on.
            ids0 = int((aggs[0].id_remap(epoch0) >= 0).sum())
        frozen.append((prep.caps, dict(prep.caps)))
        rows_then.append([(cap, cap[0]._address, _cap_rows(cap))
                          for cap in prep.caps.values()])
        try:
            _assert_same_profiles(
                aggs[0], dataclasses.replace(snap, time_ns=snap.time_ns + t),
                cs[0], [(p, bytes(b)) for p, b in out])
            oracle = None
        except AssertionError as e:  # judged by the step's own case
            oracle = str(e) or "differs"
        new_rows = None
        if mask is not None and not state["evicted"]:
            seen = state["seen"]
            fresh = mask if seen is None else mask & ~seen
            new_rows = snap.pids[fresh]
            state["seen"] = mask if seen is None else seen | mask
        facts[step] = {
            "same_bytes": [(p, bytes(b)) for p, b in out]
            == [(p, bytes(b)) for p, b in outs[1]],
            "n_profiles": len(out),
            "oracle": oracle,
            "caps_ok": _same_caps(prep.caps, _todays_caps(aggs[0], prep)),
            "caps_kept": prep.caps is state["prev_caps"],
            "order_ok": np.array_equal(enc._order, np.argsort(
                aggs[0]._id_pid[:enc._synced], kind="stable")),
            "views_kept": out is state["prev_out"],
            "all_live": prep.idx is enc._order,
            "new_ids": enc._synced - ids0,
            "new_rows_pids": new_rows,
            "live_pids": len(np.unique(prep.pids_live)),
            **{k: enc.stats[k] - stats0[k] for k in _COUNTERS},
        }
        state["prev_out"], state["prev_caps"] = out, prep.caps
        return cs[0]

    window("cold", m_base)
    window("steady_1", m_base)
    window("steady_2", m_base)
    c_old = window("steady_3", m_base)
    window("new_stacks_known_pids", m_more)
    window("steady_after_new_stacks", m_more)
    window("brand_new_pids", m_all)
    # An older window's counts: they stop short of the id space.
    assert len(c_old) < enc._synced
    window("short_counts", counts=c_old)
    window("after_short_counts", m_all)
    window("steady_after_short", m_all)
    window("pid_dies", m_dead)
    window("pid_returns", m_all)
    window("partly_dead_same_pids", m_all, thin=7)
    state["evicted"] = True
    window("pid_invalidated_and_reused", m_all,
           before=lambda agg: agg.invalidate_pid(reused))
    window("steady_after_invalidation", m_all)
    window("ids_go_cold", m_cold)

    def rotate(agg):
        agg._rotate_pending = True

    window("rotation", m_cold, before=rotate)
    window("steady_after_rotation", m_cold)
    window("steady_after_rotation_2", m_cold)
    facts["_frozen"] = all(now == then for now, then in frozen)
    # What a cap frozen in prepare() reads below its lengths, read again
    # now that later windows appended to its registry; and how many of
    # those registries meanwhile moved to a grown buffer.
    caps_then = [c for window_caps in rows_then for c in window_caps]
    facts["_rows_frozen"] = all(
        _cap_rows(cap) == rows for cap, _buf, rows in caps_then)
    facts["_caps_outgrown"] = sum(
        cap[0]._address is not buf and cap[0].n_locs > cap[2]
        for cap, buf, _rows in caps_then)
    facts["_epochs"] = [a.registry_epoch for a in aggs]
    return facts


@pytest.mark.parametrize("step", _CARRY_STEPS)
def test_carried_state_ships_the_bytes_of_an_encoder_without_it(
        carried, step):
    f = carried[step]
    assert f["n_profiles"] > 0
    assert f["same_bytes"]
    assert f["oracle"] is None, f["oracle"]
    assert f["caps_ok"]       # same keys, same tuples as today's loop
    assert f["order_ok"]      # the merged order is the stable argsort


@pytest.mark.parametrize("step", _CARRY_STEPS)
def test_a_window_redoes_only_what_it_changed(carried, step):
    f = carried[step]
    views_kept, caps_kept, rebuilds, argsorts = _CARRY_EXPECT[step]
    assert f["views_kept"] is views_kept
    assert f["views_reused_total"] == int(views_kept)
    assert f["caps_kept"] is caps_kept
    assert f["caps_rebuilds_total"] == rebuilds
    assert f["order_rebuilds_total"] == argsorts
    if rebuilds:
        assert f["caps_refreshed_total"] == f["live_pids"]
    elif caps_kept:
        assert f["caps_refreshed_total"] == 0
    if not argsorts:
        # Every id the window brought was merged into the order.
        assert f["order_merged_ids_total"] == f["new_ids"]
    if step.startswith("steady"):
        # The table of docs/perf.md for a steady window.
        assert f["all_live"] and f["new_ids"] == 0
        assert f["layouts_built"] == 0


def test_a_rollout_window_reads_the_caps_of_the_pids_it_touched(carried):
    """New stacks for known pids, then brand-new pids: the caps read are
    those of the pids that own a new stack (every fed row that was never
    fed before misses and registers), never the population's; the ids
    are merged; no list of views is handed out twice."""
    for step in ("new_stacks_known_pids", "brand_new_pids"):
        f = carried[step]
        owners = len(np.unique(f["new_rows_pids"]))
        assert 0 < owners and f["caps_refreshed_total"] == owners
        assert f["new_ids"] == len(f["new_rows_pids"]) > 0
        assert f["order_merged_ids_total"] == f["new_ids"]
        assert f["caps_rebuilds_total"] == f["order_rebuilds_total"] == 0
    assert carried["new_stacks_known_pids"]["caps_refreshed_total"] \
        < carried["new_stacks_known_pids"]["live_pids"]
    assert carried["pid_returns"]["caps_refreshed_total"] == 1
    assert carried["pid_dies"]["caps_refreshed_total"] == 0


def test_prepared_caps_are_never_mutated_after_the_hand_off(carried):
    assert carried["_frozen"]
    assert carried["_epochs"][0] == carried["_epochs"][1] == 2
    # Nor are the rows a cap covers: `[:n]` of every column reads what it
    # read at the hand-off, also where the capture thread has since
    # appended past a growth (the registry's columns are another buffer
    # by now, with an equal prefix).
    assert carried["_rows_frozen"]
    assert carried["_caps_outgrown"] > 0


def test_reused_views_read_the_new_counts_and_times():
    snap, agg, enc, c = _churn_setup(seed=43, n_pids=5, rows=120)
    out1 = enc.encode(c, snap.time_ns, snap.window_ns, snap.period_ns,
                      views=True)
    c2 = c + 11
    out2 = enc.encode(c2, snap.time_ns + 5, snap.window_ns, snap.period_ns,
                      views=True)
    assert out2 is out1 and enc.stats["views_reused_total"] == 1
    _assert_same_profiles(
        agg, dataclasses.replace(snap, time_ns=snap.time_ns + 5), c2,
        [(p, bytes(b)) for p, b in out2])
    # An append lays rows down: the list is built again, over the layout
    # as it now is.
    snap_b = generate(_spec(seed=44, n_pids=5, rows=60))
    c3 = np.asarray(agg.window_counts(snap_b))
    out3 = enc.encode(c3, snap_b.time_ns, snap_b.window_ns,
                      snap_b.period_ns, views=True)
    assert out3 is not out1 and enc.stats["views_reused_total"] == 1
    _assert_same_profiles(agg, snap_b, c3,
                          [(p, bytes(b)) for p, b in out3])


def test_an_aggregator_without_the_touched_pid_report_takes_the_full_loop():
    class Silent(DictAggregator):
        take_touched_pids = None     # as an aggregator that has none

    snap = generate(_spec(seed=45, n_pids=6, rows=150))
    agg = Silent(capacity=1 << 12)
    enc = WindowEncoder(agg)
    for t in range(3):
        c = np.asarray(agg.window_counts(snap))
        prep = enc.prepare(c, snap.time_ns + t, snap.window_ns,
                           snap.period_ns)
        assert _same_caps(prep.caps, _todays_caps(agg, prep))
        _assert_same_profiles(
            agg, dataclasses.replace(snap, time_ns=snap.time_ns + t), c,
            enc.encode_prepared(prep))
    assert enc.stats["caps_rebuilds_total"] == 3
    assert enc.stats["caps_refreshed_total"] == 3 * len(prep.caps)


def test_a_second_reader_of_the_touched_pids_costs_a_full_loop():
    """Two encoders over one aggregator take each other's report: the
    token says so, and each falls back to the full loop rather than
    trust a report with a hole in it."""
    snap = generate(_spec(seed=46, n_pids=6, rows=150))
    agg = DictAggregator(capacity=1 << 12)
    a, b = WindowEncoder(agg), WindowEncoder(agg)
    c = np.asarray(agg.window_counts(snap))
    for enc in (a, b, a, b):
        prep = enc.prepare(c, snap.time_ns, snap.window_ns, snap.period_ns)
        assert _same_caps(prep.caps, _todays_caps(agg, prep))
    assert a.stats["caps_rebuilds_total"] == 2
    assert b.stats["caps_rebuilds_total"] == 2
    # Left alone, one reader is told of nothing and reads nothing.
    a.prepare(c, snap.time_ns, snap.window_ns, snap.period_ns)
    a.prepare(c, snap.time_ns, snap.window_ns, snap.period_ns)
    assert a.stats["caps_rebuilds_total"] == 3


# -- across a compaction of the id space ---------------------------------------
#
# The aggregator says where a compaction put every id (id_remap) and the
# encoder follows: the prefixes, the pid order, the statics and the caps
# of the pids that stay are kept under their new ids, and the template is
# laid out again from them. Beside it the same windows go through a
# second aggregator whose remap is taken away before the encoder sees
# it: the encoder that drops every mirror, as every encoder did.


def _bytes_of(out) -> list[tuple[int, bytes]]:
    return [(p, bytes(b)) for p, b in out]


@pytest.mark.parametrize("pids, stacks, capacity, turnover, n", [
    (40, 800, 1 << 12, 0.5, 10),     # a reclaim every other window
    (120, 1200, 1 << 13, 0.25, 14),  # one, with nine pids in ten staying
])
def test_after_a_reclaim_every_window_ships_a_fresh_encoders_bytes(
        pids, stacks, capacity, turnover, n):
    windows, _ = span_scenarios.turnover_windows(
        n, pids=pids, stacks=stacks, turnover=turnover)
    aggs = [DictAggregator(capacity=capacity, overflow="raise")
            for _ in range(2)]
    enc, ref = (WindowEncoder(a) for a in aggs)
    reclaims = 0
    for snap in windows:
        at = (snap.time_ns, snap.window_ns, snap.period_ns)
        outs = []
        for agg, e in zip(aggs, (enc, ref)):
            epoch, known = agg.registry_epoch, len(e._static)
            c = agg.window_counts(snap)
            changed = agg.registry_epoch != epoch
            if e is ref and changed:
                agg._remap = None         # the mirror that missed it
            kept0 = e.stats["epoch_statics_kept_total"]
            dropped0 = e.stats["epoch_statics_dropped_total"]
            built0 = e.stats["layouts_built"]
            outs.append(_bytes_of(e.encode(c, *at, views=True)))
            kept = e.stats["epoch_statics_kept_total"] - kept0
            dropped = e.stats["epoch_statics_dropped_total"] - dropped0
            if changed:
                # The template is keyed by id: laid out again either way.
                assert e.stats["layouts_built"] == built0 + 1
                assert kept + dropped == known
                if e is enc:
                    # k of n pids stay: theirs are kept, the rest dropped.
                    stay = set(agg._pids) - set(
                        np.unique(snap.pids).tolist())
                    assert kept >= len(stay) and 0 < kept < known
                else:
                    assert kept == 0
                    # A cold encoder on the same aggregator: these bytes.
                    assert _bytes_of(WindowEncoder(agg).encode(c, *at)) \
                        == outs[-1]
            else:
                assert kept == dropped == 0
        reclaims += changed
        assert outs[0] == outs[1]
        _assert_same_profiles(aggs[0], snap, c, outs[0])
    assert reclaims >= 1 and aggs[0].stats["reclaims"] == reclaims
    assert enc.stats["epoch_changes_total"] == reclaims
    # Following the remap, the order is never sorted whole again.
    assert enc.stats["order_rebuilds_total"] == 1
    assert ref.stats["order_rebuilds_total"] == 1 + reclaims


def test_a_pid_that_loses_some_stacks_to_a_reclaim_keeps_its_static():
    """Built by hand (the turnover generator never makes one): a pid
    shows half its stacks from the second window on, the other half
    goes cold and a reclaim gives those ids away. The encoder keeps the
    pid's static sections (the very object) and the compressed piece of
    its span, sheds its dead rows (the bytes are a cold encoder's) and
    sorts nothing again."""
    rows_of = span_scenarios.rows_of
    snap = generate(_spec(seed=88, n_pids=10, rows=1300))
    victim = int(np.bincount(snap.pids.astype(np.int64)).argmax())
    mask = np.ones(len(snap), bool)
    rows_v = np.flatnonzero(snap.pids == victim)
    mask[rows_v[::2]] = False
    part = rows_of(snap, mask)
    agg = DictAggregator(capacity=1 << 12, overflow="raise")   # 2,048 ids
    enc = WindowEncoder(agg)

    def window(s, t):
        c = agg.window_counts(s)
        out = enc.encode(c, s.time_ns + t, s.window_ns, s.period_ns,
                         views=True)
        for _pid, blob in out.span_blobs():
            if blob.static_piece() is None:
                blob.keep_static_piece(b"piece of %d" % _pid)
        return c, out

    window(snap, 0)
    window(part, 1)
    static, n_rows = enc._static[victim], enc._tmpl.n_rows
    assert n_rows == len(snap) and agg.stats.get("reclaims", 0) == 0
    # The window just closed is made to look like one that brought 400
    # new stacks: 1,300 ids and twice that churn are over the id space.
    agg._inserts_mark -= 400
    c, out = window(part, 2)
    assert agg.stats["reclaims"] == 1
    assert agg.stats["reclaimed_ids"] == len(rows_v[::2])
    assert enc.stats["epoch_statics_kept_total"] == 10
    assert enc.stats["epoch_statics_dropped_total"] == 0
    assert enc._static[victim] is static
    assert enc._tmpl.n_rows == len(part) < n_rows          # dead rows shed
    assert enc.stats["order_rebuilds_total"] == 1
    got = dict(out.span_blobs())
    assert all(got[p].static_piece() == b"piece of %d" % p for p in got)
    assert _bytes_of(out) == _bytes_of(WindowEncoder(agg).encode(
        c, part.time_ns + 2, part.window_ns, part.period_ns))
    _assert_same_profiles(
        agg, dataclasses.replace(part, time_ns=part.time_ns + 2), c,
        _bytes_of(out))
