"""Native sampler tests: build, decode path (always), live capture (gated
on perf_event permission)."""

import ctypes
import struct

import numpy as np
import pytest

from parca_agent_tpu.capture.formats import MappingTable
from parca_agent_tpu.capture.live import (
    PerfEventSampler,
    SamplerUnavailable,
    build_native,
    decode_records,
    load_native,
    records_to_snapshot,
)


def test_native_builds():
    path = build_native()
    lib = ctypes.CDLL(path)
    assert lib  # symbols resolve
    assert hasattr(lib, "pa_sampler_create")


def _pack(pid, tid, kframes, uframes):
    out = struct.pack("<IIII", pid, tid, len(kframes), len(uframes))
    for f in list(kframes) + list(uframes):
        out += struct.pack("<Q", f)
    return out


def test_decode_records():
    buf = _pack(7, 8, [0xFFFF800000000010], [0x401000, 0x401100]) + \
        _pack(9, 9, [], [0x55000])
    recs = decode_records(buf)
    assert len(recs) == 2
    pid, tid, kf, uf = recs[0]
    assert (pid, tid) == (7, 8)
    assert list(kf) == [0xFFFF800000000010]
    assert list(uf) == [0x401000, 0x401100]
    # truncated tail is dropped, prefix kept
    recs = decode_records(buf + b"\x01\x02")
    assert len(recs) == 2


def test_native_columnar_decode_matches_python():
    """pa_decode_v1 (one native pass into columnar arrays) agrees with the
    Python reference decoder, including user-first row layout, prefix-keep
    on a corrupt tail, and randomized record streams."""
    import numpy as np

    from parca_agent_tpu.capture.formats import STACK_SLOTS
    from parca_agent_tpu.capture.live import (
        decode_records_columnar,
        load_native,
    )

    lib = load_native()
    rng = np.random.default_rng(11)
    bufs = [
        _pack(7, 8, [0xFFFF800000000010], [0x401000, 0x401100]) +
        _pack(9, 9, [], [0x55000]),
        b"",
    ]
    # Random stream of 200 records with varied depths (incl. empty).
    blob = b""
    for _ in range(200):
        nk = int(rng.integers(0, 4))
        nu = int(rng.integers(0, 30))
        blob += _pack(int(rng.integers(1, 1 << 21)),
                      int(rng.integers(1, 1 << 21)),
                      rng.integers(1, 1 << 62, nk).tolist(),
                      rng.integers(1, 1 << 62, nu).tolist())
    bufs.append(blob)
    bufs.append(blob + b"\x05\x00\x00\x00")  # corrupt tail: prefix kept

    for buf in bufs:
        recs = decode_records(buf)
        pids, tids, ulen, klen, stacks = decode_records_columnar(
            lib, buf, len(buf))
        assert len(pids) == len(recs)
        for i, (pid, tid, kf, uf) in enumerate(recs):
            assert (pids[i], tids[i]) == (pid, tid)
            assert (ulen[i], klen[i]) == (len(uf), len(kf))
            np.testing.assert_array_equal(stacks[i, :len(uf)], uf)
            np.testing.assert_array_equal(
                stacks[i, len(uf):len(uf) + len(kf)], kf)
            assert not stacks[i, len(uf) + len(kf):].any()
        assert stacks.shape[1] == STACK_SLOTS if len(recs) else True


def _pack_v1d(pid, tid, kframes, uframes, count):
    out = struct.pack("<IIIIII", pid, tid, len(kframes), len(uframes),
                      count, 0)
    for f in list(kframes) + list(uframes):
        out += struct.pack("<Q", f)
    return out


def test_v1d_decode_and_weighted_snapshot():
    """The dedup-drain record format decodes with its count column, and
    columns_to_snapshot sums weights across residual duplicate rows."""
    from parca_agent_tpu.capture.live import (
        columns_to_snapshot,
        decode_records_columnar_v1d,
    )

    lib = load_native()
    buf = (_pack_v1d(7, 8, [0xFFFF800000000010], [0x401000], 5)
           + _pack_v1d(9, 9, [], [0x55000], 2)
           + _pack_v1d(7, 8, [0xFFFF800000000010], [0x401000], 3))
    pids, tids, ulen, klen, stacks, counts = decode_records_columnar_v1d(
        lib, buf, len(buf))
    assert pids.tolist() == [7, 9, 7]
    assert counts.tolist() == [5, 2, 3]
    assert ulen.tolist() == [1, 1, 1] and klen.tolist() == [1, 0, 1]
    np.testing.assert_array_equal(stacks[0, :2],
                                  [0x401000, 0xFFFF800000000010])
    # Corrupt tail: prefix kept (same contract as v1).
    p2, *_ = decode_records_columnar_v1d(lib, buf + b"\x01\x02", len(buf) + 2)
    assert p2.tolist() == [7, 9, 7]

    snap = columns_to_snapshot(
        pids, tids, ulen, klen, stacks,
        MappingTable.empty(), 10**7, 10**10, weights=counts)
    # Rows 0 and 2 are identical (cross-pass residual): merged, 5 + 3.
    assert len(snap) == 2
    assert sorted(snap.counts.tolist()) == [2, 8]
    assert snap.total_samples() == 10


def test_records_to_snapshot_dedups():
    recs = decode_records(
        _pack(7, 7, [0xFFFF800000000010], [0x401000]) * 3
        + _pack(7, 7, [], [0x401000])
        + _pack(8, 8, [], [0x55000]) * 2
    )
    snap = records_to_snapshot(recs, MappingTable.empty(), 10_000_000,
                               10_000_000_000)
    assert len(snap) == 3
    assert snap.total_samples() == 6
    by_key = {(int(p), int(u), int(k)): int(c)
              for p, u, k, c in zip(snap.pids, snap.user_len,
                                    snap.kernel_len, snap.counts)}
    assert by_key[(7, 1, 1)] == 3
    assert by_key[(7, 1, 0)] == 1
    assert by_key[(8, 1, 0)] == 2
    # user frames first, kernel tail after (formats contract)
    row = np.flatnonzero((snap.pids == 7) & (snap.kernel_len == 1))[0]
    assert int(snap.stacks[row, 0]) == 0x401000
    assert int(snap.stacks[row, 1]) == 0xFFFF800000000010
    snap.validate_padding()


def test_unattributable_records_dropped():
    """perf's pid -1 (idle/unattributable context) records carry no
    process to profile and would alias the device kernels' dead-row
    sentinel after the uint32 cast: dropped record-by-record, never
    failing the window."""
    recs = decode_records(
        _pack(7, 7, [], [0x401000]) * 2
        + _pack(0xFFFFFFFF, 0xFFFFFFFF, [0xFFFF800000000010], []) * 3
    )
    snap = records_to_snapshot(recs, MappingTable.empty(), 10_000_000,
                               10_000_000_000)
    assert len(snap) == 1
    assert snap.total_samples() == 2
    assert int(snap.pids[0]) == 7

    # An all-unattributable window degrades to an empty snapshot.
    recs = decode_records(_pack(0xFFFFFFFF, 0, [], [0x1]) * 2)
    snap = records_to_snapshot(recs, MappingTable.empty(), 10_000_000,
                               10_000_000_000)
    assert len(snap) == 0


def test_empty_records():
    snap = records_to_snapshot([], MappingTable.empty(), 1, 1)
    assert len(snap) == 0


class _ScriptedSamplerLib:
    """The native library with its sampler calls scripted (no perf
    events are opened); the decoders are the real ones. ``refuse`` says
    where the hash carry is turned down: "tables" (pa_sampler_set_hash
    answers non-zero), "symbol" (a build from before the carry has no
    such call) or "drain" (the hashed drain fails mid-session)."""

    def __init__(self, refuse: str, record: bytes):
        self._real, self._refuse, self._record = load_native(), refuse, record
        self.drains: list[str] = []

    def __getattr__(self, name):
        if name == "pa_sampler_set_hash":
            if self._refuse == "symbol":
                raise AttributeError(name)
            return lambda *args: -1 if self._refuse == "tables" else 0
        return getattr(self._real, name)

    def pa_sampler_create2(self, hz, flags, dump_bytes):
        return 1

    def pa_sampler_drain_dedup2(self, handle, buf, cap):
        self.drains.append("hashed")
        return -1

    def pa_sampler_drain_dedup(self, handle, buf, cap):
        self.drains.append("hashless")
        ctypes.memmove(buf, self._record, len(self._record))
        return len(self._record)

    pa_sampler_start = pa_sampler_lost = pa_sampler_truncated = \
        pa_sampler_dedup_hits = pa_sampler_dedup_overflow = \
        staticmethod(lambda handle: 0)
    pa_sampler_n_cpus = staticmethod(lambda handle: 1)
    pa_sampler_destroy = staticmethod(lambda handle: None)


@pytest.mark.parametrize("refuse", ["tables", "symbol", "drain"])
def test_a_sampler_that_refuses_the_hash_carry_drains_hashless(
        refuse, monkeypatch):
    """The hash carry's off-state is entered by what the sampler does,
    not by a switch: whichever call turns the carry down, the drain is
    the hashless v1d one (six columns, the feeder hashes host-side), the
    sampler says so through ``hash_carry`` (the
    ``parca_agent_capture_hash_carry`` gauge), and it stays so."""
    from parca_agent_tpu.capture import live

    lib = _ScriptedSamplerLib(
        refuse, _pack_v1d(7, 8, [0xFFFF800000000010], [0x401000], 5))
    monkeypatch.setattr(live, "load_native", lambda: lib)
    sampler = PerfEventSampler(frequency_hz=99, window_s=0.01)
    try:
        assert sampler.hash_carry == (refuse == "drain")
        for _ in range(2):
            (chunk,) = sampler._drain_columnar()
            pids, _tids, _ulen, _klen, _stacks, counts = chunk
            assert (pids.tolist(), counts.tolist()) == ([7], [5])
        assert not sampler.hash_carry
        # A refused hashed drain is tried once and never again.
        assert lib.drains == (["hashed"] if refuse == "drain" else []) \
            + ["hashless", "hashless"]
    finally:
        sampler.close()


@pytest.fixture(scope="session")
def live_sampler():
    try:
        s = PerfEventSampler(frequency_hz=99, window_s=1.0)
    except SamplerUnavailable as e:
        pytest.skip(f"perf_event not permitted here: {e}")
    yield s
    s.close()


@pytest.mark.live
def test_live_capture_smoke(live_sampler):
    """Real sampling: burn CPU for a window and expect our own samples."""

    import threading

    stop = threading.Event()

    def burn():
        x = 0
        while not stop.is_set():
            x += 1
        return x

    t = threading.Thread(target=burn, daemon=True)
    t.start()
    try:
        snap = live_sampler.poll()
    finally:
        stop.set()
    assert live_sampler.n_cpus >= 1
    assert snap.total_samples() > 0
    import os

    assert os.getpid() in set(int(p) for p in snap.pids)
    # Aggregation over live data works end to end.
    from parca_agent_tpu.aggregator.cpu import CPUAggregator

    profiles = CPUAggregator().aggregate(snap)
    assert sum(p.total() for p in profiles) == snap.total_samples()


def test_load_native_symbols():
    lib = load_native()
    # create may fail without permissions, but the symbol table is complete.
    for sym in ("pa_sampler_create", "pa_sampler_drain", "pa_sampler_stop",
                "pa_sampler_destroy", "pa_sampler_n_cpus", "pa_sampler_lost"):
        assert hasattr(lib, sym)


def _pack_v2(pid, tid, kframes, uframes, rip, rsp, rbp, stack: bytes):
    dyn = len(stack)
    pad = (-dyn) % 8
    out = struct.pack("<IIII", pid, tid, len(kframes), len(uframes))
    out += struct.pack("<QQQII", rip, rsp, rbp, dyn, 0)
    for f in list(kframes) + list(uframes):
        out += struct.pack("<Q", f)
    return out + stack + b"\x00" * pad


def test_decode_records_v2():
    from parca_agent_tpu.capture.live import decode_records_v2

    buf = _pack_v2(7, 8, [0xFFFF800000000010], [0x401000],
                   0x401000, 0x7FFF0000, 0x7FFF0040, b"\xAA" * 19) + \
        _pack_v2(9, 9, [], [], 0x55000, 0x1000, 0, b"")
    recs = decode_records_v2(buf)
    assert len(recs) == 2
    pid, tid, kf, uf, rip, rsp, rbp, stack = recs[0]
    assert (pid, tid, rip, rsp, rbp) == (7, 8, 0x401000, 0x7FFF0000,
                                         0x7FFF0040)
    assert list(kf) == [0xFFFF800000000010] and list(uf) == [0x401000]
    assert len(stack) == 19 and (stack == 0xAA).all()
    assert recs[1][4] == 0x55000 and len(recs[1][7]) == 0
    # truncated tail dropped, prefix kept
    assert len(decode_records_v2(buf + b"\x01" * 50)) == 2


@pytest.mark.live
def test_drain_overflow_is_lossless():
    """A drain buffer too small for the backlog must return what fits,
    keep the rest in the rings, and recover it on subsequent drains
    (r1 VERDICT weak #5 / ADVICE medium #2)."""
    import os
    import subprocess
    import time

    fix = os.path.join(os.path.dirname(__file__), "fixtures",
                       "fixture_pie_nofp")
    try:
        sampler = PerfEventSampler(frequency_hz=1997, window_s=1.0)
    except SamplerUnavailable as e:
        pytest.skip(f"perf_event not permitted here: {e}")
    try:
        proc = subprocess.Popen([fix, "spin", "1"],
                                stdout=subprocess.DEVNULL)
        time.sleep(1.1)
        proc.wait(timeout=10)
        sampler._lib.pa_sampler_stop(sampler._handle)  # freeze the corpus

        tiny = 4096
        chunks = []
        for _ in range(10_000):
            buf = (ctypes.c_uint8 * tiny)()
            n = sampler._lib.pa_sampler_drain(
                sampler._handle, buf, ctypes.c_long(tiny))
            assert n >= 0
            if n == 0:
                break
            chunks.append(bytes(buf[:n]))
        total = b"".join(chunks)
        if len(total) <= tiny:
            pytest.skip("not enough samples to overflow the tiny buffer")
        assert sampler.truncated_drains >= 1
        # Every recovered byte decodes into whole records: nothing was torn.
        recs = decode_records(total)
        assert sum(16 + 8 * (len(r[2]) + len(r[3])) for r in recs) \
            == len(total)
    finally:
        sampler.close()


def test_comm_filter_source_keeps_matching_pids():
    """--debug-process-names analog: rows whose pid's comm doesn't match
    are dropped at the window boundary; matching rows are untouched."""
    import numpy as np

    from parca_agent_tpu.capture.live import CommFilterSource
    from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate

    snap = generate(SyntheticSpec(n_pids=6, n_unique_stacks=120,
                                  n_rows=120, total_samples=480, seed=3))
    comms = {int(p): ("keepme" if i % 2 else "other")
             for i, p in enumerate(np.unique(snap.pids))}

    class Once:
        def __init__(self):
            self._left = [snap]

        def poll(self):
            return self._left.pop() if self._left else None

        def close(self):
            pass

    src = CommFilterSource(Once(), ["keep"],
                           read_comm=lambda pid: comms.get(pid, ""))
    got = src.poll()
    kept = {p for p, c in comms.items() if c == "keepme"}
    assert set(np.unique(got.pids).tolist()) == kept
    # Counts for kept pids are byte-identical to the unfiltered window.
    for p in kept:
        assert (got.counts[got.pids == p].sum()
                == snap.counts[snap.pids == p].sum())
    assert src.poll() is None


def test_comm_filter_source_passthrough_when_all_match():
    from parca_agent_tpu.capture.live import CommFilterSource
    from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate

    snap = generate(SyntheticSpec(n_pids=3, n_unique_stacks=30,
                                  n_rows=30, total_samples=90, seed=4))

    class Once:
        def __init__(self):
            self._snap = snap

        def poll(self):
            return self._snap

        def close(self):
            pass

    src = CommFilterSource(Once(), [".*"], read_comm=lambda pid: "anything")
    assert src.poll() is snap          # zero-copy passthrough


def test_comm_filter_verdict_is_a_lease_not_a_fact():
    """Kernel pid reuse / exec() comm changes: a cached match verdict
    expires after the TTL and the comm is re-read."""
    import numpy as np

    from parca_agent_tpu.capture.live import CommFilterSource
    from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate

    snap = generate(SyntheticSpec(n_pids=2, n_unique_stacks=40,
                                  n_rows=40, total_samples=120, seed=5))
    pids = sorted(int(p) for p in np.unique(snap.pids))
    comms = {pids[0]: "keepme", pids[1]: "other"}

    class Repeat:
        def poll(self):
            return snap

        def close(self):
            pass

    now = {"t": 100.0}
    src = CommFilterSource(Repeat(), ["keep"],
                           read_comm=lambda pid: comms[pid],
                           cache_ttl_s=30.0, clock=lambda: now["t"])
    got = src.poll()
    assert set(np.unique(got.pids)) == {pids[0]}
    # The kernel reuses pids[1] for a matching process. Within the TTL
    # the stale verdict holds; past it, the re-read flips the verdict.
    comms[pids[1]] = "keepme2"
    assert set(np.unique(src.poll().pids)) == {pids[0]}
    now["t"] += 31.0
    assert set(np.unique(src.poll().pids)) == {pids[0], pids[1]}


@pytest.mark.live
def test_cli_streaming_window_live(tmp_path):
    """The flagship production mode end to end on real capture: perf FP
    sampling + dict aggregator + --fast-encode + --streaming-window
    through the actual CLI. Windows must STREAM (drains fed during the
    window, close = one packed fetch), profiles must parse with mass,
    and the streaming gauges must be live on /metrics."""
    import gzip
    import os
    import subprocess
    import sys
    import threading
    import time
    import urllib.request

    from parca_agent_tpu.capture.live import (
        PerfEventSampler,
        SamplerUnavailable,
    )
    from parca_agent_tpu.cli import run
    from parca_agent_tpu.pprof.builder import parse_pprof

    try:
        PerfEventSampler(frequency_hz=99, window_s=0.1).close()
    except SamplerUnavailable as e:
        pytest.skip(f"perf_event not permitted here: {e}")

    burn = subprocess.Popen(
        [sys.executable, "-c", "while True:\n sum(i*i for i in range(4000))"])
    out = tmp_path / "profiles"
    # Ephemeral port (bind-release): the suite convention is :0, but this
    # test must scrape /metrics mid-run and so needs to know the number.
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    # The scraped dict keeps the high-water values: an increment from
    # window N is observed during window N+1's polls, so with three
    # windows the assertions don't race the post-final-window shutdown.
    scraped: dict = {}

    def scrape():
        while not scraped.get("_stop"):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=1) as r:
                    for line in r.read().decode().splitlines():
                        if line.startswith("parca_agent_streaming"):
                            k, _, v = line.partition(" ")
                            scraped[k] = float(v)
            except Exception:
                pass
            time.sleep(0.25)

    t = threading.Thread(target=scrape, daemon=True)
    t.start()
    try:
        rc = run(["--capture", "perf",
                  "--aggregator", "dict", "--fast-encode",
                  "--streaming-window",
                  "--profiling-duration", "3", "--windows", "3",
                  "--local-store-directory", str(out),
                  "--http-address", f"127.0.0.1:{port}",
                  "--debuginfo-upload-disable", "--node", "streamlive"])
    finally:
        scraped["_stop"] = True
        burn.kill()
        burn.wait()
    assert rc == 0
    assert scraped.get("parca_agent_streaming_windows_streamed_total", 0) >= 1
    assert scraped.get("parca_agent_streaming_drains_fed", 0) >= 1
    total = 0
    for f in os.listdir(out):
        p = parse_pprof(gzip.decompress((out / f).read_bytes()))
        total += sum(v[0] for _, v, _ in p.samples)
    assert total > 0
