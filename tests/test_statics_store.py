"""Warm statics + registry snapshot (pprof/statics_store.py).

The contract under test: a snapshot-warmed aggregator+encoder produce
pprof output BYTE-IDENTICAL to a cold-built pair over the same windows —
across registry rotation and pid churn — while any stale, corrupt, or
torn snapshot state degrades to a cold build for exactly the records it
touches, never crashing and never double-counting a window.
"""

from __future__ import annotations

import os
import struct
import threading
import zlib

import numpy as np
import pytest

from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.pprof import statics_store as ss
from parca_agent_tpu.pprof.statics_store import StaticsStore
from parca_agent_tpu.pprof.window_encoder import WindowEncoder
from parca_agent_tpu.profiler.encode_pipeline import EncodePipeline
from parca_agent_tpu.utils import faults


def _spec(seed=7, n_pids=10, rows=300):
    return SyntheticSpec(
        n_pids=n_pids, n_unique_stacks=rows, n_rows=rows,
        total_samples=rows * 4, mean_depth=8, kernel_fraction=0.25,
        seed=seed)


def _warm_pair(tmp_path, seed=7, n_pids=10, rows=300):
    """One aggregated+encoded window, snapshotted to disk. Returns
    (snapshot window, store, path)."""
    snap = generate(_spec(seed=seed, n_pids=n_pids, rows=rows))
    agg = DictAggregator(capacity=1 << 12)
    enc = WindowEncoder(agg)
    counts = np.asarray(agg.window_counts(snap))
    enc.encode(counts, snap.time_ns, snap.window_ns, snap.period_ns)
    path = str(tmp_path / "statics.snap")
    store = StaticsStore(path)
    assert store.save(agg, enc, snap.period_ns)
    return snap, store, path


def _blobs(out):
    return [(pid, bytes(b)) for pid, b in out]


_FHEAD = len(ss._FMARK) + ss._FRAME.size  # marker + len/crc header


def _frames(data: bytes):
    """(frame offset, payload length) of every frame after the magic.
    Payload bytes start at offset + _FHEAD."""
    out = []
    off = len(ss._MAGIC)
    while off < len(data):
        assert data[off: off + len(ss._FMARK)] == ss._FMARK
        length, _crc = ss._FRAME.unpack_from(data, off + len(ss._FMARK))
        out.append((off, length))
        off += _FHEAD + length
    return out


# -- warm-restart byte identity ----------------------------------------------


def test_adoption_outcomes_all_adopted(tmp_path):
    snap, store, _ = _warm_pair(tmp_path)
    agg2 = DictAggregator(capacity=1 << 12)
    enc2 = WindowEncoder(agg2)
    out = store.adopt(agg2, enc2, snap.period_ns)
    n_pids = len({int(p) for p in snap.pids})
    assert out == {"adopted": n_pids, "stale": 0, "corrupt": 0,
                   "outcome": "adopted"}
    assert enc2.stats["statics_adopted_pids"] == n_pids
    assert store.stats["snapshot_adopt_ms"] >= 0.0


def test_warm_encoder_byte_identical_to_cold(tmp_path):
    """The acceptance bar: replay the same window into a snapshot-warmed
    restart; the warmed encoder's bytes must equal both a cold-built
    encoder on the same state AND the pre-restart output."""
    snap, store, _ = _warm_pair(tmp_path)
    agg1 = DictAggregator(capacity=1 << 12)
    c1 = np.asarray(agg1.window_counts(snap))
    ref = _blobs(WindowEncoder(agg1).encode(
        c1, snap.time_ns, snap.window_ns, snap.period_ns))

    agg2 = DictAggregator(capacity=1 << 12)
    enc2 = WindowEncoder(agg2)
    store.adopt(agg2, enc2, snap.period_ns)
    c2 = np.asarray(agg2.window_counts(snap))
    warm = _blobs(enc2.encode(c2, snap.time_ns, snap.window_ns,
                              snap.period_ns))
    cold = _blobs(WindowEncoder(agg2).encode(
        c2, snap.time_ns, snap.window_ns, snap.period_ns))
    assert warm == cold
    assert warm == ref
    # And the warm path really was warm: nothing was re-encoded.
    assert enc2.stats["statics_bytes_built"] == 0


def test_warm_byte_identity_across_rotation_and_churn(tmp_path):
    """Warm vs cold must stay byte-identical through the two events the
    snapshot is supposed to survive: a registry rotation (statics map
    wiped, content cache serves the rebuild) and pid churn (a pid dead
    one window, back the next)."""
    snap, store, _ = _warm_pair(tmp_path, seed=9, n_pids=8, rows=250)
    aggs, encs = [], []
    for warm in (True, False):
        agg = DictAggregator(capacity=1 << 12, rotate_min_age=1)
        enc = WindowEncoder(agg)
        if warm:
            assert store.adopt(agg, enc, snap.period_ns)["adopted"] > 0
        aggs.append(agg)
        encs.append(enc)

    snap2 = generate(_spec(seed=10, n_pids=8, rows=250))
    for w in range(4):
        outs = []
        for agg, enc in zip(aggs, encs):
            if w == 1:
                agg.window_counts(snap2)  # age snap's ids
                agg._rotate_pending = True
            c = np.asarray(agg.window_counts(snap))
            if w == 2:  # pid churn: kill one whole pid this window
                c[agg._id_pid[: len(c)] == int(snap.pids[0])] = 0
            if not c.any():
                continue
            outs.append(_blobs(enc.encode(
                c, snap.time_ns + w, snap.window_ns, snap.period_ns)))
        assert outs[0] == outs[1], f"window {w} diverged"
    assert aggs[0].stats.get("rotations", 0) == 1


def test_period_mismatch_adopts_registry_counts_stale(tmp_path):
    """A snapshot taken at another sampling period still warms the
    registry and location blobs; head/tail rebuild via the encoder's
    staleness guard, and the output matches a cold build exactly."""
    snap, store, _ = _warm_pair(tmp_path)
    other_period = snap.period_ns + 12345
    agg2 = DictAggregator(capacity=1 << 12)
    enc2 = WindowEncoder(agg2)
    out = store.adopt(agg2, enc2, other_period)
    assert out["adopted"] > 0
    assert out["stale"] == out["adopted"]  # every record: old period
    c2 = np.asarray(agg2.window_counts(snap))
    warm = _blobs(enc2.encode(c2, snap.time_ns, snap.window_ns,
                              other_period))
    cold = _blobs(WindowEncoder(agg2).encode(
        c2, snap.time_ns, snap.window_ns, other_period))
    assert warm == cold


# -- corruption / staleness property ------------------------------------------


def test_any_single_corrupt_record_is_discarded_rest_adopt(tmp_path):
    """Property over every record: flip one byte inside record k's
    payload — exactly one record reads corrupt, all others adopt, and
    the replayed window still encodes (cold for the victim pid)."""
    snap, store, path = _warm_pair(tmp_path)
    data = open(path, "rb").read()
    frames = _frames(data)
    records = frames[1:]  # frame 0 is the json header
    n = len(records)
    assert n == len({int(p) for p in snap.pids})
    for k, (off, length) in enumerate(records):
        mut = bytearray(data)
        mut[off + _FHEAD + length // 2] ^= 0xFF
        open(path, "wb").write(bytes(mut))
        agg = DictAggregator(capacity=1 << 12)
        enc = WindowEncoder(agg)
        out = StaticsStore(path).adopt(agg, enc, snap.period_ns)
        assert out["corrupt"] == 1, f"record {k}"
        assert out["adopted"] == n - 1, f"record {k}"
        c = np.asarray(agg.window_counts(snap))
        warm = _blobs(enc.encode(c, snap.time_ns, snap.window_ns,
                                 snap.period_ns))
        cold = _blobs(WindowEncoder(agg).encode(
            c, snap.time_ns, snap.window_ns, snap.period_ns))
        assert warm == cold, f"record {k}"
    open(path, "wb").write(data)  # restore


def test_digest_mismatch_with_valid_crc_is_corrupt(tmp_path):
    """Corruption that re-frames correctly (payload mutated AND its CRC
    recomputed) is still caught — by the registry content digest."""
    snap, store, path = _warm_pair(tmp_path)
    data = bytearray(open(path, "rb").read())
    off, length = _frames(bytes(data))[1]
    payload = bytearray(data[off + _FHEAD:
                             off + _FHEAD + length])
    payload[ss._REC_HEAD.size - 1] ^= 0xFF  # flip a digest byte
    ss._FRAME.pack_into(data, off + len(ss._FMARK), length,
                        zlib.crc32(bytes(payload)))
    data[off + _FHEAD: off + _FHEAD + length] = payload
    open(path, "wb").write(bytes(data))
    out = StaticsStore(path).adopt(DictAggregator(capacity=1 << 12),
                                   WindowEncoder(DictAggregator(
                                       capacity=1 << 12)), snap.period_ns)
    assert out["corrupt"] == 1


def test_truncated_snapshot_salvages_prefix(tmp_path):
    snap, store, path = _warm_pair(tmp_path)
    data = open(path, "rb").read()
    frames = _frames(data)
    # Cut mid-way through the LAST record: everything before it adopts.
    off, length = frames[-1]
    open(path, "wb").write(data[: off + _FHEAD + length // 2])
    agg = DictAggregator(capacity=1 << 12)
    out = StaticsStore(path).adopt(agg, WindowEncoder(agg), snap.period_ns)
    assert out["adopted"] == len(frames) - 2
    assert out["corrupt"] == 1
    # Sanity: the salvaged state still closes and encodes the window.
    c = np.asarray(agg.window_counts(snap))
    assert int(c.sum()) == snap.total_samples()


def test_garbage_and_missing_snapshot(tmp_path):
    agg = DictAggregator(capacity=1 << 10)
    enc = WindowEncoder(agg)
    missing = StaticsStore(str(tmp_path / "nope.snap"))
    assert missing.adopt(agg, enc, 1)["outcome"] == "absent"
    bad = str(tmp_path / "bad.snap")
    open(bad, "wb").write(b"not a snapshot at all")
    assert StaticsStore(bad).adopt(agg, enc, 1)["outcome"] == "corrupt"


def test_old_snapshot_is_stale(tmp_path):
    snap, _, path = _warm_pair(tmp_path)
    clk = {"t": 1e9}
    store = StaticsStore(path, max_age_s=60.0, clock=lambda: clk["t"])
    # Re-save with the injectable clock so created_at is deterministic;
    # pin the mtime to the same virtual instant (adoption ages by
    # max(header, mtime), and the real write just stamped real time).
    agg = DictAggregator(capacity=1 << 12)
    enc = WindowEncoder(agg)
    np.asarray(agg.window_counts(snap))
    assert store.save(agg, enc, snap.period_ns)
    os.utime(path, times=(clk["t"], clk["t"]))
    clk["t"] += 61.0
    out = store.adopt(DictAggregator(capacity=1 << 12),
                      WindowEncoder(DictAggregator(capacity=1 << 12)),
                      snap.period_ns)
    assert out["outcome"] == "stale"
    assert out["adopted"] == 0


def test_clean_skip_keeps_snapshot_fresh(tmp_path):
    """A long stationary run (every interval clean-skipped) must keep
    the snapshot adoptable: the skip refreshes the mtime, so the age bar
    measures liveness, not time-since-last-content-change."""
    snap = generate(_spec(seed=18, n_pids=4, rows=80))
    path = str(tmp_path / "fresh.snap")
    clk = {"t": 1e9}
    store = StaticsStore(path, max_age_s=60.0, clock=lambda: clk["t"])
    agg = DictAggregator(capacity=1 << 11)
    enc = WindowEncoder(agg)
    np.asarray(agg.window_counts(snap))
    enc.build_statics(snap.period_ns)       # clean marker -> skippable
    assert store.save(agg, enc, snap.period_ns)
    os.utime(path, times=(clk["t"], clk["t"]))
    # Stationary for far longer than max_age, skipping each interval.
    for _ in range(5):
        clk["t"] += 50.0
        assert store.save(agg, enc, snap.period_ns) == "skipped"
    clk["t"] += 30.0                         # 280 s since content write
    out = store.adopt(DictAggregator(capacity=1 << 11),
                      WindowEncoder(DictAggregator(capacity=1 << 11)),
                      snap.period_ns)
    assert out["outcome"] == "adopted"
    assert out["adopted"] == 4


def test_adopt_into_live_pid_refused_as_stale(tmp_path):
    snap, store, _ = _warm_pair(tmp_path)
    agg = DictAggregator(capacity=1 << 12)
    np.asarray(agg.window_counts(snap))  # registries already live
    enc = WindowEncoder(agg)
    out = store.adopt(agg, enc, snap.period_ns)
    assert out["adopted"] == 0
    assert out["stale"] == len({int(p) for p in snap.pids})


def test_snapshot_byte_cap_drops_records_counted(tmp_path):
    snap = generate(_spec(seed=11, n_pids=6, rows=150))
    agg = DictAggregator(capacity=1 << 12)
    enc = WindowEncoder(agg)
    c = np.asarray(agg.window_counts(snap))
    enc.encode(c, snap.time_ns, snap.window_ns, snap.period_ns)
    store = StaticsStore(str(tmp_path / "tiny.snap"), max_bytes=4096)
    assert store.save(agg, enc, snap.period_ns)
    assert store.stats["records_dropped_cap"] > 0
    assert store.stats["snapshot_records"] < 6
    # Whatever made it in still adopts cleanly.
    agg2 = DictAggregator(capacity=1 << 12)
    out = store.adopt(agg2, WindowEncoder(agg2), snap.period_ns)
    assert out["corrupt"] == 0


# -- chaos: injected snapshot faults (make chaos) ------------------------------


@pytest.mark.chaos
def test_injected_write_failure_counted_not_fatal(tmp_path):
    snap = generate(_spec(seed=12, n_pids=4, rows=80))
    agg = DictAggregator(capacity=1 << 11)
    enc = WindowEncoder(agg)
    np.asarray(agg.window_counts(snap))
    path = str(tmp_path / "statics.snap")
    store = StaticsStore(path)
    prev = faults.get()
    faults.install(faults.FaultInjector.from_spec(
        "statics.snapshot:disk_full"))
    try:
        assert store.save(agg, enc, snap.period_ns) is False
    finally:
        faults.install(prev)
    assert store.stats["snapshot_write_errors"] == 1
    assert not os.path.exists(path)
    # Recovery: with the fault gone the next save lands.
    assert store.save(agg, enc, snap.period_ns)
    assert store.stats["snapshots_written"] == 1


@pytest.mark.chaos
def test_pipeline_snapshot_fault_no_disable_no_double_ship(tmp_path):
    """An injected snapshot crash on the encode worker must not disable
    the pipeline, must not re-ship the window (no double-count), and the
    next interval's snapshot must succeed."""
    snap = generate(_spec(seed=13, n_pids=4, rows=80))
    agg = DictAggregator(capacity=1 << 11)
    counts = np.asarray(agg.window_counts(snap))
    enc = WindowEncoder(agg)
    store = StaticsStore(str(tmp_path / "statics.snap"))
    shipped = []
    pipe = EncodePipeline(
        enc, ship=lambda out, prep: shipped.append(len(out)),
        snapshot=lambda period_ns: store.save(agg, enc, period_ns),
        snapshot_every=1)
    prev = faults.get()
    faults.install(faults.FaultInjector.from_spec(
        "statics.snapshot:error:count=1"))
    try:
        assert pipe.submit(counts, snap.time_ns, snap.window_ns,
                           snap.period_ns) is not None
        assert pipe.quiesce(10)
        assert not pipe.disabled
        assert pipe.stats["snapshot_errors"] == 1
        assert pipe.stats["snapshots_written"] == 0
        assert shipped == [4]          # shipped exactly once
        # Next window: fault exhausted, snapshot lands.
        assert pipe.submit(counts, snap.time_ns + 1, snap.window_ns,
                           snap.period_ns) is not None
        assert pipe.close()
    finally:
        faults.install(prev)
    assert pipe.stats["snapshots_written"] == 1
    assert shipped == [4, 4]
    assert store.snapshot_info()["present"]


def _flip_every_seventh(data: bytes) -> bytes:
    data = bytearray(data)
    for i in range(len(ss._MAGIC), len(data), 7):
        data[i] ^= 0xA5
    return bytes(data)


@pytest.mark.chaos
@pytest.mark.parametrize("corrupt", [
    _flip_every_seventh,
    lambda data: data[: len(ss._MAGIC) + _FHEAD // 2],
    lambda data: b"not a snapshot" * 64,
], ids=["bit-flipped", "torn-in-the-first-frame", "garbage"])
def test_corrupt_snapshot_degrades_to_cold_zero_windows_lost(tmp_path,
                                                             corrupt):
    """The acceptance drill: a snapshot no record of which survives
    adopts nothing at startup, and the first window still aggregates,
    encodes, and ships the bytes a cold start ships — zero windows
    lost, just cold."""
    snap, store, path = _warm_pair(tmp_path, seed=14, n_pids=5, rows=100)
    data = open(path, "rb").read()
    open(path, "wb").write(corrupt(data))
    cold = DictAggregator(capacity=1 << 12)
    want = _blobs(WindowEncoder(cold).encode(
        np.asarray(cold.window_counts(snap)), snap.time_ns, snap.window_ns,
        snap.period_ns))
    agg = DictAggregator(capacity=1 << 12)
    enc = WindowEncoder(agg)
    out = StaticsStore(path).adopt(agg, enc, snap.period_ns)
    assert out["adopted"] == 0
    shipped = []
    pipe = EncodePipeline(enc, ship=lambda o, p: shipped.append(_blobs(o)))
    c = np.asarray(agg.window_counts(snap))
    assert int(c.sum()) == snap.total_samples()
    assert pipe.submit(c, snap.time_ns, snap.window_ns,
                       snap.period_ns) is not None
    assert pipe.close()
    assert shipped == [want]
    assert pipe.stats["windows_lost"] == 0


# -- pipeline scheduling -------------------------------------------------------


def test_pipeline_writes_snapshot_on_worker_thread(tmp_path):
    snap = generate(_spec(seed=15, n_pids=4, rows=80))
    agg = DictAggregator(capacity=1 << 11)
    counts = np.asarray(agg.window_counts(snap))
    enc = WindowEncoder(agg)
    calls = []

    def snapshot(period_ns):
        calls.append((period_ns, threading.get_ident()))

    pipe = EncodePipeline(enc, ship=lambda o, p: None,
                          snapshot=snapshot, snapshot_every=2)
    for k in range(4):
        assert pipe.submit(counts, snap.time_ns + k, snap.window_ns,
                           snap.period_ns) is not None
        assert pipe.flush(10)
    assert pipe.close()
    assert len(calls) == 2                       # every 2nd window
    assert all(p == snap.period_ns for p, _ in calls)
    assert all(t != threading.get_ident() for _, t in calls)
    assert pipe.stats["snapshots_written"] == 2


def test_header_corruption_never_skips_records_silently(tmp_path):
    """A lost header must not demote a data record into the header slot:
    with an age bar the (now-unknowable-age) snapshot rejects as stale,
    without one every record still adopts — in neither case is a valid
    record silently dropped."""
    snap, store, path = _warm_pair(tmp_path)
    data = bytearray(open(path, "rb").read())
    off, _length = _frames(bytes(data))[0]     # the json header frame
    data[off + _FHEAD] ^= 0xFF
    open(path, "wb").write(bytes(data))
    n = len({int(p) for p in snap.pids})
    agg = DictAggregator(capacity=1 << 12)
    out = StaticsStore(path).adopt(agg, WindowEncoder(agg),
                                   snap.period_ns)
    assert out["outcome"] == "stale"
    assert out["adopted"] == 0
    assert out["stale"] == n
    assert out["corrupt"] == 1
    agg2 = DictAggregator(capacity=1 << 12)
    out2 = StaticsStore(path, max_age_s=None).adopt(
        agg2, WindowEncoder(agg2), snap.period_ns)
    assert out2["adopted"] == n
    assert out2["corrupt"] == 1
    assert out2["stale"] == 0


def test_registry_digest_identity_after_adoption(tmp_path):
    """The aggregator's public digest exposure: an adopted registry is
    content-identical to one rebuilt by replaying the same window, and
    the digest says so (this is the identity the snapshot's statics
    validity rests on)."""
    snap, store, _ = _warm_pair(tmp_path)
    replayed = DictAggregator(capacity=1 << 12)
    np.asarray(replayed.window_counts(snap))
    adopted = DictAggregator(capacity=1 << 12)
    store.adopt(adopted, WindowEncoder(adopted), snap.period_ns)
    assert adopted.registry_epoch == 0
    pids = set(replayed._pids)
    assert pids == set(adopted._pids)
    for pid in pids:
        d1, d2 = replayed.registry_digest(pid), adopted.registry_digest(pid)
        assert d1 is not None and d1 == d2, pid
    assert replayed.registry_digest(999999) is None


def test_save_skips_when_nothing_changed(tmp_path):
    """Steady state (no registry mutation, statics fully built) must not
    re-serialize the world every interval: the save is skipped, counted,
    and re-armed by the next registry mutation."""
    snap = generate(_spec(seed=16, n_pids=4, rows=80))
    agg = DictAggregator(capacity=1 << 11)
    enc = WindowEncoder(agg)
    np.asarray(agg.window_counts(snap))
    enc.build_statics(snap.period_ns)      # full scan -> clean marker
    store = StaticsStore(str(tmp_path / "s.snap"))
    assert store.save(agg, enc, snap.period_ns)
    assert store.save(agg, enc, snap.period_ns)
    assert store.stats["snapshots_written"] == 1
    assert store.stats["snapshots_skipped_clean"] == 1
    snap2 = generate(_spec(seed=17, n_pids=6, rows=120))
    np.asarray(agg.window_counts(snap2))   # registry mutation re-arms
    enc.build_statics(snap.period_ns)
    assert store.save(agg, enc, snap.period_ns)
    assert store.stats["snapshots_written"] == 2


def test_adopt_bounds_the_read_itself(tmp_path):
    """A snapshot file over the byte cap is rejected before it is ever
    materialized past the cap (the PR4 bounded-read discipline)."""
    path = str(tmp_path / "big.snap")
    open(path, "wb").write(ss._MAGIC + b"\xa5" * 4096)
    agg = DictAggregator(capacity=1 << 10)
    out = StaticsStore(path, max_bytes=1024).adopt(
        agg, WindowEncoder(agg), 1)
    assert out["outcome"] == "corrupt"
    assert out["adopted"] == 0


def test_header_only_snapshot_is_empty_not_corrupt(tmp_path):
    """A snapshot written before any pid registered is a legal empty
    file: adoption reports 'empty', never a false corruption signal."""
    agg = DictAggregator(capacity=1 << 10)
    enc = WindowEncoder(agg)
    store = StaticsStore(str(tmp_path / "empty.snap"))
    assert store.save(agg, enc, 10_000_000)
    out = store.adopt(DictAggregator(capacity=1 << 10),
                      WindowEncoder(DictAggregator(capacity=1 << 10)),
                      10_000_000)
    assert out == {"adopted": 0, "stale": 0, "corrupt": 0,
                   "outcome": "empty"}


def test_corrupt_length_field_resyncs_to_next_record(tmp_path):
    """A bit flip in a frame's LENGTH field must cost that record only:
    the per-frame marker re-anchors the scan, so the remaining records
    still adopt (the documented per-record discard property holds for
    frame headers, not just payloads)."""
    snap, store, path = _warm_pair(tmp_path)
    data = bytearray(open(path, "rb").read())
    frames = _frames(bytes(data))
    n = len(frames) - 1
    victim, _length = frames[2]            # a middle pid record
    ss._FRAME.pack_into(data, victim + len(ss._FMARK), 0x7FFFFFFF, 0)
    open(path, "wb").write(bytes(data))
    agg = DictAggregator(capacity=1 << 12)
    out = StaticsStore(path).adopt(agg, WindowEncoder(agg),
                                   snap.period_ns)
    assert out["adopted"] == n - 1
    assert out["corrupt"] >= 1


# -- a snapshot written when the registry's columns were Python lists ---------

# `StaticsStore.save` of the tree before PR 51 (columns as lists, each
# dumped through np.asarray(list, dtype).tobytes()): three pids of one
# synthetic window (seed 51), their registries and built statics;
# zlib-compressed, base64. Period 10,000,000 ns.
_LIST_LAYOUT_SNAPSHOT = (
    "eNrtlm1sU1UYx2/f1q7r2s6xcYcipfihJHPtvXup48vuAhESNUzKmyZkbF2RKbRLu/ESddzN"
    "oQTnRgDJEnVWTSbGsJCJBgwv/aAocc6ZEFREsxghOlG3JYouYn3OeZ5rt7olm1/gw05y+v+f"
    "c27Pufeec3/PU1UZWFu5NiBVBdYs3ygIwqfu7v4n3TtC0Vh9JOxe5pIKXe5gNFTTGKqrrmms"
    "bgrX72K9/nJJuq+stFgqksqlMslfCpc1hKL1kbrqcIxd4MMC3aGGSHArdPmeZmv0mARhhfec"
    "5weDIKhHugRWjFBzBCy5Jfbvb3S5Pmsb/WBj4R+Z3+hYZ1W0VljHNchVK9TvAOutaWjw1taH"
    "vTsj0SdCUQ90+WZWJL023R7+q+PqpL6RZDLZLAj5bImmWNS7rb6W1djWmmiozlcUi8xiJdmA"
    "czpppRyuLlppKOlg7alXkma3UrER53TRSou5+milweSi6VeSZ7dSiXLsKb4JiqUZVSSNq6hl"
    "+GaVr22o67NRrzpQ/Tr+PpQTJtQ/M1Bf0/G7V66QurLxaZoK4OQkk6rbxVUwLuaqbLLyh1NO"
    "ZqF+TFplR70fH175yYa6JRv1NwfqBh3qRRNqgRn1c+q36Wk+/N9096HjRyhV9WnVMKEaJ9Tp"
    "iw5K2lcSgWo1WnRO/YJ8+FXjFw49G88TQV8C9Rh8xgUFFr1TZWVokQi/+0Bdqstj8mXAkIEP"
    "tdBQCxtSPGafBYaMfKiVhlrZ0GZPps/qg3uQBdkcq9nesC0Uk03BSFO4UXakfXiyZ6afnZw/"
    "9Qc14xnkqWaQZjND8VQzyLOZoUQ2BBua5KxwTTgSCwUj4brYo0ZLltO2WT32nfEUbZfbApuk"
    "ExPvLwWnZ26kCJyBuQNecEbm4uXgTE69mGjXgctg7lIxODNz/aXgLMwNV4DLdBrERK8JnJW5"
    "o/PBZTHXdxc4m9MoJl43g8tmrssCzs5cvNSdbXGI6vBVdjLgtELTKaofqi3/NnNEdV8bNRmx"
    "/4KDV1bwQu+PacS20cPZokc2PXPi2/fWF1Q1e959O5t/LoPLRzmpB5ePTSI29c8R+1YS++Yq"
    "vgnKFw+i+h9GjRqQyJvMqNeyUFcTuXv1SOYeIvSIFXWdHQn9lgP1XiKoORP1MSJtK75NZTuR"
    "9XcidDUReYAIez4D1Unje4ngXzlou2dH10ksTTu8/wFp5/5ODtLOg51zIL1tQLorDaSX52kg"
    "7bhHA+lAKQcpALLNroH08kINpNe9GkjPShykgMqXszSQtt+pgTReyEEKqNy/TANpXwXj4CE4"
    "le9sqTw1nMZBke5t/MyeitrR+hU7Bh5f80mGdIlz8PjKXzn/jq8cmcRB6p/j4K3kYGQV3wTl"
    "Aqn/AdQA6dKHUFesRt0ZQH1zA+oBPXLxFdIAcfNnynBjNuTjI3bU0/hUyhILarWNq+pwYCZZ"
    "R9qKKmTmoV5zc01Yl2Cme8WLeo4y2gLKUJ8jvr5Bups4fJh4+xHx9AZlvHHi7THSIF13k3h9"
    "iLi8m/h7ndYLWFA7cfz/3n96pjzTzHlmWfM0ifSEZFqcJgYMdY93sBgw1N06FwNunxiQq5sc"
    "A/7WazGgz6jFgA67lkyfxxgAbiyPxwBwR+/mMYBd5+UxANwvmExDXEhYeQxgUcPKYwC48wt5"
    "DAA37ufJNESIHpkn0+DGWMLuYBHiSwM4J3M9i8HlMNctQzJ9h6i+ejqVaueK6oUzqeY8Ud17"
    "NtWEM3fwYqqZL6r9z6fy8vli4sX2VFMUEyfPUfMfCwHROg==")
_LIST_LAYOUT_PERIOD_NS = 10_000_000
# registry_digest / _loc_key of each pid, as that tree computed them.
_LIST_LAYOUT_DIGESTS = {
    1000: ("13340ee4f79820cf84f1c4582cf809dd", 17,
           "4c2ce89d4363ca55568aaea264a3115494"),
    1001: ("0c72965d83b4deb65619507e28b5ab0d", 12,
           "4c96b871de0f1f4eec35ef7c40f2bb2ecb"),
    1002: ("fabd7f3f62f1694476ce6a52cb0631d6", 24,
           "4c4353ee5cf72350468b71249e81d4c2f7"),
}


def test_a_snapshot_of_the_list_layout_restores_as_array_columns(tmp_path):
    """An agent that kept its registries as lists wrote this file; the
    agent that keeps them as arrays adopts every record of it (the
    record's digest is recomputed from the arrays' bytes), ends with
    equal content digests and equal location-blob keys, and writes the
    same records back."""
    import base64

    from parca_agent_tpu.aggregator.dict import _PidRegistry
    from parca_agent_tpu.pprof.window_encoder import _loc_key

    data = zlib.decompress(base64.b64decode(_LIST_LAYOUT_SNAPSHOT))
    path = str(tmp_path / "old.snap")
    open(path, "wb").write(data)
    agg = DictAggregator(capacity=1 << 8)
    enc = WindowEncoder(agg)
    out = StaticsStore(path, max_age_s=None).adopt(
        agg, enc, _LIST_LAYOUT_PERIOD_NS)
    assert (out["adopted"], out["corrupt"], out["stale"]) == (3, 0, 0)
    assert list(agg._pids) == list(_LIST_LAYOUT_DIGESTS)
    for pid, (digest, n_locs, loc_key) in _LIST_LAYOUT_DIGESTS.items():
        reg = agg._pids[pid]
        assert type(reg) is _PidRegistry and reg.n_locs == n_locs
        assert [c.dtype.str for c in (
            reg.loc_address, reg.loc_normalized, reg.loc_mapping_id,
            reg.loc_is_kernel)] == ["<u8", "<u8", "<i4", "|b1"]
        assert reg.loc_address.flags.writeable   # its own, not the file's
        assert agg.registry_digest(pid).hex() == digest
        assert _loc_key(reg, n_locs).hex() == loc_key
        assert enc._static[pid].n_locs == n_locs
    # And back: the records (every frame after the json header) are the
    # old file's, byte for byte.
    back = str(tmp_path / "new.snap")
    assert StaticsStore(back).save(agg, enc, _LIST_LAYOUT_PERIOD_NS)
    new = open(back, "rb").read()
    old_frames, new_frames = _frames(data), _frames(new)
    assert len(old_frames) == len(new_frames) >= 1 + 3
    for (o_off, o_len), (n_off, n_len) in zip(old_frames[1:],
                                              new_frames[1:]):
        assert data[o_off: o_off + _FHEAD + o_len] \
            == new[n_off: n_off + _FHEAD + n_len]
