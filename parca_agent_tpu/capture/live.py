"""Live capture source over the native perf_event sampler.

Python side of parca_agent_tpu/native/sampler.cc (the capture role of the
reference's pkg/profiler/cpu/cpu.go:234-275 perf_event_open + attach): the
shared library is built on demand with the local toolchain, loaded via
ctypes, and drained once per window. Raw records are decoded with numpy,
deduplicated into (pid, stack) -> count rows (the aggregation the
reference's BPF map does kernel-side happens here, vectorized), and joined
with the live /proc mapping table.

Two capture modes:

  FP mode (default): kernel + frame-pointer user chains via
  PERF_SAMPLE_CALLCHAIN (v1 record: u32 pid | u32 tid | u32 n_kernel |
  u32 n_user | u64 frames[...], kernel-first).

  DWARF mode (capture_stack=True): additionally snapshots user registers
  and a stack slice per sample (v2 record, see sampler.cc header); at
  drain time the batched walker (unwind/walker.py) unwinds frameless user
  stacks against .eh_frame tables built by the watch-processes loop —
  the role of the reference's debug_pids + in-kernel DWARF walker
  (pkg/profiler/cpu/cpu.go:390-459, bpf/cpu/cpu.bpf.c:464-674).

Drain overflow is lossless: the native side returns the records that fit
and keeps the rest in the rings (truncation counter incremented); poll()
immediately drains again.
"""

from __future__ import annotations

import ctypes
import re
import struct
import threading
import time

import numpy as np

from parca_agent_tpu.capture.formats import (
    MAX_STACK_DEPTH,
    STACK_SLOTS,
    MappingTable,
    WindowSnapshot,
    filter_snapshot_rows,
)
from parca_agent_tpu.process.maps import ProcessMapCache, build_mapping_table
from parca_agent_tpu.process.objectfile import ObjectFileCache
from parca_agent_tpu.utils.log import get_logger

_log = get_logger("capture")

PA_CAPTURE_USER_STACK = 1


class SamplerUnavailable(RuntimeError):
    pass


def build_native(force: bool = False) -> str:
    """Compile libpasampler.so if missing or stale; returns its path
    (shared build-on-demand policy: native.ensure_built)."""
    from parca_agent_tpu.native import ensure_built

    try:
        return ensure_built("libpasampler.so", "sampler.cc", force=force)
    except RuntimeError as e:
        raise SamplerUnavailable(str(e)) from None


def load_native():
    lib = ctypes.CDLL(build_native(), use_errno=True)
    lib.pa_sampler_create.restype = ctypes.c_void_p
    lib.pa_sampler_create.argtypes = [ctypes.c_int]
    lib.pa_sampler_create2.restype = ctypes.c_void_p
    lib.pa_sampler_create2.argtypes = [ctypes.c_int, ctypes.c_int,
                                       ctypes.c_uint32]
    lib.pa_sampler_n_cpus.restype = ctypes.c_int
    lib.pa_sampler_n_cpus.argtypes = [ctypes.c_void_p]
    lib.pa_sampler_lost.restype = ctypes.c_uint64
    lib.pa_sampler_lost.argtypes = [ctypes.c_void_p]
    lib.pa_sampler_truncated.restype = ctypes.c_uint64
    lib.pa_sampler_truncated.argtypes = [ctypes.c_void_p]
    lib.pa_sampler_start.restype = ctypes.c_int
    lib.pa_sampler_start.argtypes = [ctypes.c_void_p]
    lib.pa_sampler_stop.restype = ctypes.c_int
    lib.pa_sampler_stop.argtypes = [ctypes.c_void_p]
    lib.pa_sampler_drain.restype = ctypes.c_long
    lib.pa_sampler_drain.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_uint8),
                                     ctypes.c_long]
    lib.pa_sampler_destroy.restype = None
    lib.pa_sampler_destroy.argtypes = [ctypes.c_void_p]
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.pa_decode_v1_count.restype = ctypes.c_long
    lib.pa_decode_v1_count.argtypes = [u8p, ctypes.c_long, ctypes.c_long]
    lib.pa_decode_v1.restype = ctypes.c_long
    lib.pa_decode_v1.argtypes = [
        u8p, ctypes.c_long, i32p, i32p, i32p, i32p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_long, ctypes.c_long]
    lib.pa_sampler_drain_dedup.restype = ctypes.c_long
    lib.pa_sampler_drain_dedup.argtypes = [ctypes.c_void_p, u8p,
                                           ctypes.c_long]
    lib.pa_sampler_dedup_hits.restype = ctypes.c_uint64
    lib.pa_sampler_dedup_hits.argtypes = [ctypes.c_void_p]
    lib.pa_sampler_dedup_overflow.restype = ctypes.c_uint64
    lib.pa_sampler_dedup_overflow.argtypes = [ctypes.c_void_p]
    lib.pa_decode_v1d_count.restype = ctypes.c_long
    lib.pa_decode_v1d_count.argtypes = [u8p, ctypes.c_long, ctypes.c_long]
    lib.pa_decode_v1d.restype = ctypes.c_long
    lib.pa_decode_v1d.argtypes = [
        u8p, ctypes.c_long, i32p, i32p, i32p, i32p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_long, ctypes.c_long]
    # v1h (hash-carrying dedup drain) entry points — guarded so a stale
    # pre-carry .so still loads; the sampler then simply runs hashless
    # (PerfEventSampler checks hash_carry before using them).
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    try:
        lib.pa_sampler_set_hash.restype = ctypes.c_int
        lib.pa_sampler_set_hash.argtypes = [
            ctypes.c_void_p, u32p, ctypes.c_long, u32p, ctypes.c_int,
            ctypes.c_long]
        lib.pa_sampler_drain_dedup2.restype = ctypes.c_long
        lib.pa_sampler_drain_dedup2.argtypes = [ctypes.c_void_p, u8p,
                                                ctypes.c_long]
        lib.pa_decode_v1h_count.restype = ctypes.c_long
        lib.pa_decode_v1h_count.argtypes = [u8p, ctypes.c_long,
                                            ctypes.c_long]
        lib.pa_decode_v1h.restype = ctypes.c_long
        lib.pa_decode_v1h.argtypes = [
            u8p, ctypes.c_long, i32p, i32p, i32p, i32p,
            ctypes.POINTER(ctypes.c_int64), u32p, u32p, u32p,
            u64p, ctypes.c_long, ctypes.c_long]
        lib.pa_stack_hash.restype = ctypes.c_int
        lib.pa_stack_hash.argtypes = [
            u64p, ctypes.c_long, u64p, ctypes.c_long, ctypes.c_uint32,
            u32p, ctypes.c_long, u32p, ctypes.c_long, ctypes.c_long,
            u32p]
    except AttributeError:
        pass
    return lib


def decode_records(buf: bytes) -> list[tuple[int, int, np.ndarray, np.ndarray]]:
    """Packed v1 drain buffer -> [(pid, tid, kernel_frames, user_frames)]."""
    out = []
    pos = 0
    n = len(buf)
    while pos + 16 <= n:
        pid, tid, nk, nu = struct.unpack_from("<IIII", buf, pos)
        pos += 16
        if nk + nu > MAX_STACK_DEPTH or pos + 8 * (nk + nu) > n:
            break  # corrupt/truncated tail
        frames = np.frombuffer(buf, np.uint64, nk + nu, pos)
        pos += 8 * (nk + nu)
        out.append((pid, tid, frames[:nk], frames[nk:]))
    return out


def decode_records_v2(buf: bytes) -> list[
        tuple[int, int, np.ndarray, np.ndarray, int, int, int, np.ndarray]]:
    """Packed v2 drain buffer ->
    [(pid, tid, kframes, uframes, rip, rsp, rbp, stack_bytes)]."""
    out = []
    pos = 0
    n = len(buf)
    while pos + 48 <= n:
        pid, tid, nk, nu = struct.unpack_from("<IIII", buf, pos)
        rip, rsp, rbp, dyn, _pad = struct.unpack_from(
            "<QQQII", buf, pos + 16)
        pos += 48
        dyn_pad = (dyn + 7) & ~7
        if nk + nu > MAX_STACK_DEPTH or pos + 8 * (nk + nu) + dyn_pad > n:
            break  # corrupt/truncated tail
        frames = np.frombuffer(buf, np.uint64, nk + nu, pos)
        pos += 8 * (nk + nu)
        stack = np.frombuffer(buf, np.uint8, dyn, pos)
        pos += dyn_pad
        out.append((pid, tid, frames[:nk], frames[nk:], rip, rsp, rbp,
                    stack))
    return out


def decode_records_columnar(lib, buf, nbytes: int) -> tuple:
    """Native one-pass v1 decode straight into the columnar arrays
    columns_to_snapshot needs — replaces two Python per-record loops on
    the once-a-second capture path. `buf` is a ctypes uint8 buffer (or
    bytes) whose first `nbytes` bytes are valid.

    Returns (pids, tids, ulen, klen, stacks) with user frames first per
    row (the WindowSnapshot contract; the native decoder reorders from
    the drain's kernel-first packing).
    """
    if isinstance(buf, (bytes, bytearray)):
        buf = (ctypes.c_uint8 * nbytes).from_buffer_copy(buf[:nbytes])
    p = ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8))
    n = int(lib.pa_decode_v1_count(p, nbytes, STACK_SLOTS))
    pids = np.zeros(n, np.int32)
    tids = np.zeros(n, np.int32)
    ulen = np.zeros(n, np.int32)
    klen = np.zeros(n, np.int32)
    stacks = np.zeros((n, STACK_SLOTS), np.uint64)
    if n:
        i32p = ctypes.POINTER(ctypes.c_int32)
        got = int(lib.pa_decode_v1(
            p, nbytes,
            pids.ctypes.data_as(i32p),
            tids.ctypes.data_as(i32p),
            ulen.ctypes.data_as(i32p),
            klen.ctypes.data_as(i32p),
            stacks.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            STACK_SLOTS, n))
        assert got == n, (got, n)
    return pids, tids, ulen, klen, stacks


def decode_records_columnar_v1d(lib, buf, nbytes: int) -> tuple:
    """Native one-pass v1d decode (dedup-drain records, 24-byte header
    with a count field) into columnar arrays. Returns (pids, tids, ulen,
    klen, stacks, counts) with user frames first per row."""
    if isinstance(buf, (bytes, bytearray)):
        buf = (ctypes.c_uint8 * nbytes).from_buffer_copy(buf[:nbytes])
    p = ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8))
    n = int(lib.pa_decode_v1d_count(p, nbytes, STACK_SLOTS))
    pids = np.zeros(n, np.int32)
    tids = np.zeros(n, np.int32)
    ulen = np.zeros(n, np.int32)
    klen = np.zeros(n, np.int32)
    counts = np.zeros(n, np.int64)
    stacks = np.zeros((n, STACK_SLOTS), np.uint64)
    if n:
        i32p = ctypes.POINTER(ctypes.c_int32)
        got = int(lib.pa_decode_v1d(
            p, nbytes,
            pids.ctypes.data_as(i32p),
            tids.ctypes.data_as(i32p),
            ulen.ctypes.data_as(i32p),
            klen.ctypes.data_as(i32p),
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            stacks.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            STACK_SLOTS, n))
        assert got == n, (got, n)
    return pids, tids, ulen, klen, stacks, counts


def decode_records_columnar_v1h(lib, buf, nbytes: int) -> tuple:
    """Native one-pass v1h decode (hash-carrying dedup-drain records,
    32-byte header with count + h1/h2/h3) into columnar arrays. Returns
    (pids, tids, ulen, klen, stacks, counts, h1, h2, h3) with user frames
    first per row; the hash triple is bit-identical to row_hash_np over
    the decoded row (the drain computed it with the same installed
    coefficient tables)."""
    if isinstance(buf, (bytes, bytearray)):
        buf = (ctypes.c_uint8 * nbytes).from_buffer_copy(buf[:nbytes])
    p = ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8))
    n = int(lib.pa_decode_v1h_count(p, nbytes, STACK_SLOTS))
    pids = np.zeros(n, np.int32)
    tids = np.zeros(n, np.int32)
    ulen = np.zeros(n, np.int32)
    klen = np.zeros(n, np.int32)
    counts = np.zeros(n, np.int64)
    h1 = np.zeros(n, np.uint32)
    h2 = np.zeros(n, np.uint32)
    h3 = np.zeros(n, np.uint32)
    stacks = np.zeros((n, STACK_SLOTS), np.uint64)
    if n:
        i32p = ctypes.POINTER(ctypes.c_int32)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        got = int(lib.pa_decode_v1h(
            p, nbytes,
            pids.ctypes.data_as(i32p),
            tids.ctypes.data_as(i32p),
            ulen.ctypes.data_as(i32p),
            klen.ctypes.data_as(i32p),
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            h1.ctypes.data_as(u32p),
            h2.ctypes.data_as(u32p),
            h3.ctypes.data_as(u32p),
            stacks.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            STACK_SLOTS, n))
        assert got == n, (got, n)
    return pids, tids, ulen, klen, stacks, counts, h1, h2, h3


def mapping_table_for_pids(maps_cache, objs_cache, pids,
                           quarantine=None) -> MappingTable:
    """MappingTable for a set of pids via the shared caches; pids that
    exited (maps unreadable) or are unattributable (< 0) are skipped —
    their rows keep raw addresses. Shared by the window-end snapshot
    build and the streaming feeder's per-drain mini-snapshots so the two
    paths cannot drift.

    Ingest containment (docs/robustness.md): with a quarantine registry,
    a pid whose maps file is poison (PoisonInput) or whose processing
    blows the per-pid deadline is charged against its error budget and
    skipped — its samples stay unmapped and ride the degradation ladder —
    instead of aborting the table build for every pid in the window.
    Without a registry, PoisonInput propagates (the pre-containment
    drop-on-error behavior).
    Scalar-level pids skip maps parsing entirely; address-level pids
    keep maps (normalized addresses must travel) but skip ELF opens
    (build_mapping_table's degraded path — the ELF is the suspect)."""
    from parca_agent_tpu.utils.poison import PoisonInput

    per_pid = {}
    healthy = {}
    for pid in pids:
        pid = int(pid)
        if pid < 0:
            continue
        level = quarantine.level(pid) if quarantine is not None else 0
        if level >= 2:
            continue  # scalar ladder level: counts only, no mapping work
        t0 = quarantine.clock() if quarantine is not None else 0.0
        try:
            per_pid[pid] = maps_cache.executable_mappings(pid)
        except OSError:
            continue
        except PoisonInput as e:
            if quarantine is None:
                raise
            quarantine.record_error(pid, getattr(e, "site", "maps.parse"),
                                    e)
            continue
        if quarantine is not None:
            quarantine.check_deadline(pid, t0)
            if quarantine.level(pid) == 0:
                healthy[pid] = per_pid[pid]
        else:
            healthy[pid] = per_pid[pid]
    # Build ids come from opening mapped ELFs — only healthy pids pay
    # (and risk) that; a shared path mapped by any healthy pid still
    # contributes its id for everyone.
    return build_mapping_table(per_pid, objs_cache.build_ids(healthy),
                               objcache=objs_cache, quarantine=quarantine)


def columns_to_snapshot(
    pids, tids, ulen, klen, stacks,
    mappings: MappingTable, period_ns: int, window_ns: int,
    weights=None, hashes=None,
) -> WindowSnapshot:
    """Dedup identical (pid, tid, stack) rows into counted rows (the role
    the BPF stack_counts map plays in the reference). Columnar input from
    the native decoder or from records_to_snapshot's packing. `weights`
    carries per-row pre-aggregated counts (the native dedup drain emits
    them); rows still merge here — drain passes and table overflows leave
    best-effort duplicates — with counts summed.

    `hashes` is an optional capture-carried (h1, h2, h3) uint32 triple
    aligned with the input rows (the v1h drain). When given, the return
    is (snapshot, (h1, h2, h3)) with the triple gathered onto the
    snapshot's deduped rows — exact, because dedup-equal rows hash to
    equal triples (the hash is a function of pid/ulen/klen/stack only)."""
    pids = np.asarray(pids, np.int32)
    if weights is not None:
        weights = np.asarray(weights, np.int64)
    if hashes is not None:
        hashes = tuple(np.asarray(h, np.uint32) for h in hashes)
    if len(pids) and int(pids.min()) < 0:
        # perf delivers unattributable/idle-context samples as pid -1;
        # they carry no process to profile, and downstream a uint32
        # cast would turn them into pid 4294967295. Drop the records,
        # not the window.
        keep = pids >= 0
        pids, tids = pids[keep], np.asarray(tids)[keep]
        ulen, klen = np.asarray(ulen)[keep], np.asarray(klen)[keep]
        stacks = np.asarray(stacks)[keep]
        if weights is not None:
            weights = weights[keep]
        if hashes is not None:
            hashes = tuple(h[keep] for h in hashes)
    n = len(pids)
    if n == 0:
        snap = WindowSnapshot(
            pids=np.zeros(0, np.int32), tids=np.zeros(0, np.int32),
            counts=np.zeros(0, np.int64), user_len=np.zeros(0, np.int32),
            kernel_len=np.zeros(0, np.int32),
            stacks=np.zeros((0, STACK_SLOTS), np.uint64),
            mappings=mappings, period_ns=period_ns, window_ns=window_ns,
            time_ns=time.time_ns(),
        )
        if hashes is not None:
            return snap, tuple(np.zeros(0, np.uint32) for _ in range(3))
        return snap
    # Vectorized row dedup (same byte-view trick as CPUAggregator),
    # comparing only up to the window's deepest stack: slots past it are
    # zero in every row, so the result is identical and the sort compares
    # ~3x less data at typical depths.
    max_depth = int((ulen + klen).max())
    rec = np.zeros((n, max_depth + 4), np.uint64)
    rec[:, 0] = pids.astype(np.uint64)
    rec[:, 1] = tids.astype(np.uint64)
    rec[:, 2] = ulen.astype(np.uint64)
    rec[:, 3] = klen.astype(np.uint64)
    rec[:, 4:] = stacks[:, :max_depth]
    void = np.ascontiguousarray(rec).view(
        np.dtype((np.void, rec.shape[1] * 8))).ravel()
    _, first, inverse = np.unique(void, return_index=True, return_inverse=True)
    if weights is None:
        # Unweighted bincount accumulates in exact integers already.
        counts = np.bincount(inverse, minlength=len(first)).astype(np.int64)
    else:
        # Weighted bincount sums in float64 — exact only below 2^53 per
        # key. Window mass is bounded far under that in practice (the
        # aggregator raises at 2^31), so take the fast path and fall
        # back to the integral-but-~10-30x-slower scatter-add on the
        # pathological mass, keeping "counts are exact either way"
        # unconditional rather than resting on float precision.
        if int(weights.sum(dtype=np.int64)) < 2**53:
            counts = np.bincount(
                inverse, weights=weights, minlength=len(first)).astype(
                    np.int64)
        else:
            counts = np.zeros(len(first), np.int64)
            np.add.at(counts, inverse, weights.astype(np.int64))
    snap = WindowSnapshot(
        pids=pids[first], tids=tids[first], counts=counts,
        user_len=ulen[first], kernel_len=klen[first], stacks=stacks[first],
        mappings=mappings, period_ns=period_ns, window_ns=window_ns,
        time_ns=time.time_ns(),
    )
    if hashes is not None:
        return snap, tuple(h[first] for h in hashes)
    return snap


def records_to_snapshot(
    records, mappings: MappingTable, period_ns: int, window_ns: int,
) -> WindowSnapshot:
    """Tuple-record variant of columns_to_snapshot (the DWARF path's
    walker rewrites per-record user chains, so it stays tuple-shaped)."""
    n = len(records)
    pids = np.zeros(n, np.int32)
    tids = np.zeros(n, np.int32)
    ulen = np.zeros(n, np.int32)
    klen = np.zeros(n, np.int32)
    stacks = np.zeros((n, STACK_SLOTS), np.uint64)
    for i, (pid, tid, kframes, uframes) in enumerate(records):
        # perf carries pid/tid as u32 (-1 = unattributable); store with
        # int32 wraparound semantics like the native columnar decoder,
        # so columns_to_snapshot's negative-pid drop sees them as -1.
        pids[i] = pid if pid < 2**31 else pid - 2**32
        tids[i] = tid if tid < 2**31 else tid - 2**32
        nu, nk = len(uframes), len(kframes)
        ulen[i] = nu
        klen[i] = nk
        # formats.py contract: user frames first, then kernel tail.
        stacks[i, :nu] = uframes
        stacks[i, nu:nu + nk] = kframes
    return columns_to_snapshot(pids, tids, ulen, klen, stacks,
                               mappings, period_ns, window_ns)


class UnwindTableCache:
    """Per-pid merged compact unwind tables with background builds and 5 s
    refresh (the role of the reference's watchProcesses loop,
    pkg/profiler/cpu/cpu.go:390-459: match processes, build/refresh their
    unwind tables off the hot path)."""

    def __init__(self, map_cache: ProcessMapCache,
                 comm_regex: str | None = None,
                 refresh_s: float = 5.0, fs=None):
        from parca_agent_tpu.unwind.table import UnwindTableBuilder
        from parca_agent_tpu.utils.vfs import RealFS

        self._fs = fs or RealFS()
        self._builder = UnwindTableBuilder(fs=self._fs)
        # Ingest containment: set (post-construction, by the sampler's
        # quarantine property) to the shared per-pid registry; builds
        # charge poison to the owning pid and skip laddered pids.
        self.quarantine = None
        self._maps = map_cache
        self._regex = re.compile(comm_regex) if comm_regex else None
        self._refresh = refresh_s
        self._tables: dict[int, np.ndarray] = {}
        self._built_at: dict[int, float] = {}
        self._lock = threading.Lock()
        self._queue: list[int] = []
        self._qset: set[int] = set()
        self._cv = threading.Condition(self._lock)
        self._stop = False
        self._worker: threading.Thread | None = None
        self._last_evict = 0.0
        self.stats = {"builds": 0, "build_errors": 0}

    def _comm(self, pid: int) -> str:
        try:
            return self._fs.read_bytes(
                f"/proc/{pid}/comm").decode().strip()
        except OSError:
            return ""

    def matches(self, pid: int) -> bool:
        if self._regex is None:
            return True
        return bool(self._regex.search(self._comm(pid)))

    def table_for(self, pid: int) -> "ShardedTable | None":
        """The pid's table if built; queues a (re)build when missing or
        stale. Never blocks the drain path."""
        now = time.monotonic()
        with self._lock:
            t = self._tables.get(pid)
            fresh = now - self._built_at.get(pid, 0) < self._refresh
            if (t is None or not fresh) and pid not in self._qset:
                self._qset.add(pid)
                self._queue.append(pid)
                self._cv.notify()
                self._ensure_worker()
            return t

    def evict(self, pid: int) -> None:
        """Drop a pid's table immediately (generation-stamped identity
        invalidation, process/identity.py: a recycled pid must not
        unwind through its dead predecessor's tables). A queued rebuild
        may stay queued — it reads the pid's CURRENT maps, which is
        exactly the fresh state we want."""
        with self._lock:
            self._tables.pop(pid, None)
            self._built_at.pop(pid, None)

    def _ensure_worker(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._run, name="unwind-table-builder", daemon=True)
            self._worker.start()

    def _run(self) -> None:
        while True:
            pid = None
            with self._cv:
                if not self._queue and not self._stop:
                    self._cv.wait(timeout=1.0)
                if self._stop:
                    return
                if self._queue:
                    pid = self._queue.pop(0)
            if pid is None:
                # Idle tick: matched processes may ALL have exited, in
                # which case no build ever requeues and the per-build
                # sweep below would never run. _evict_dead self-rate-
                # limits, so idle ticks cost one monotonic read.
                self._evict_dead()
                continue
            from parca_agent_tpu.unwind.table import ShardedTable

            try:
                self._builder.quarantine = self.quarantine
                maps = self._maps.executable_mappings(pid)
                # Store range-partitioned (the reference's (pid, shard)
                # layout, maps.go:286-395): the walker's two-level lookup
                # consumes shards directly, and huge processes keep full
                # coverage (no 3-shard truncation; see shard_table).
                table = ShardedTable.from_table(
                    self._builder.table_for_pid(pid, maps))
                with self._lock:
                    self._tables[pid] = table
                    self._built_at[pid] = time.monotonic()
                self.stats["builds"] += 1
            except Exception as e:
                # table_for_pid contains the PoisonInput taxonomy itself
                # (charging the pid's budget), but a maps read can raise
                # MapsError here and defense-in-depth still wants the
                # blanket guard (MemoryError from a hostile allocation).
                # Record built_at so the poison pid is not re-queued every
                # drain, and keep the worker alive for the other pids.
                from parca_agent_tpu.utils.poison import PoisonInput

                if self.quarantine is not None \
                        and isinstance(e, PoisonInput):
                    self.quarantine.record_error(
                        pid, getattr(e, "site", "unwind.build"), e)
                with self._lock:
                    self._built_at[pid] = time.monotonic()
                self.stats["build_errors"] += 1
                _log.warn("unwind table build failed", pid=pid,
                          error=repr(e))
            finally:
                with self._lock:
                    self._qset.discard(pid)
                self._evict_dead()

    def _evict_dead(self) -> None:
        """Drop tables for exited pids so an always-on agent's table
        memory tracks the LIVE process set instead of growing forever
        under pid churn (same bounded-memory stance as the aggregator's
        cold-id rotation). Runs opportunistically after builds, at most
        once per refresh interval."""
        now = time.monotonic()
        if now - self._last_evict < self._refresh:
            return
        self._last_evict = now
        with self._lock:
            pids = list(self._tables)
        dead = [p for p in pids
                if not self._fs.exists(f"/proc/{p}/comm")]
        if not dead:
            return
        with self._lock:
            for p in dead:
                self._tables.pop(p, None)
                self._built_at.pop(p, None)
        self.stats["evicted"] = self.stats.get("evicted", 0) + len(dead)
        _log.debug("evicted unwind tables for exited pids", count=len(dead))

    def build_now(self, pid: int) -> "ShardedTable | None":
        """Synchronous build (tests / tools)."""
        from parca_agent_tpu.unwind.table import ShardedTable
        from parca_agent_tpu.utils.poison import PoisonInput

        try:
            self._builder.quarantine = self.quarantine
            maps = self._maps.executable_mappings(pid)
        except OSError:
            return None
        except PoisonInput as e:
            if self.quarantine is not None:
                self.quarantine.record_error(
                    pid, getattr(e, "site", "maps.parse"), e)
            return None
        table = ShardedTable.from_table(
            self._builder.table_for_pid(pid, maps))
        with self._lock:
            self._tables[pid] = table
            self._built_at[pid] = time.monotonic()
        self.stats["builds"] += 1
        return table

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()


def unwind_records(records_v2, tables: UnwindTableCache,
                   trust_fp_frames: int | None = None, stats=None):
    """v2 records -> v1-shaped records with DWARF-walked user stacks.

    Every register-carrying sample of a table-matched pid is batch-walked
    and the LONGER of the walked vs frame-pointer chain wins — the
    reference likewise runs its DWARF walker instead of the FP path for
    every sample of a targeted process (cpu.bpf.c:724-757); walking only
    short FP chains would keep truncated mixed stacks (an FP-built leaf
    over a frameless caller stops the FP chain early yet still has >= 2
    frames). trust_fp_frames is a throughput knob: samples whose FP chain
    already has that many frames skip the walk (None = walk all).
    """
    from parca_agent_tpu.unwind.walker import WalkStats, walk_batch

    by_pid: dict[int, list[int]] = {}
    for i, r in enumerate(records_v2):
        by_pid.setdefault(r[0], []).append(i)

    out = [(r[0], r[1], r[2], r[3]) for r in records_v2]
    total_stats = stats if stats is not None else WalkStats()
    for pid, idxs in by_pid.items():
        need = [i for i in idxs
                if records_v2[i][4] != 0
                and (trust_fp_frames is None
                     or len(records_v2[i][3]) < trust_fp_frames)]
        if not need or not tables.matches(pid):
            continue
        table = tables.table_for(pid)
        if table is None or len(table) == 0:
            continue
        m = len(need)
        dmax = max(len(records_v2[i][7]) for i in need)
        rip = np.zeros(m, np.uint64)
        rsp = np.zeros(m, np.uint64)
        rbp = np.zeros(m, np.uint64)
        dyn = np.zeros(m, np.int64)
        stacks = np.zeros((m, max(dmax, 8)), np.uint8)
        for k, i in enumerate(need):
            _, _, _, _, ip, sp, bp, stk = records_v2[i]
            rip[k], rsp[k], rbp[k] = ip, sp, bp
            dyn[k] = len(stk)
            stacks[k, : len(stk)] = stk
        frames, depth, st = walk_batch(table, rip, rsp, rbp, stacks, dyn)
        total_stats.add(st)
        for k, i in enumerate(need):
            # The record's kernel frames stay on the row; the walked user
            # chain must fit the remaining depth budget or the combined
            # stack would overflow records_to_snapshot's STACK_SLOTS rows.
            budget = MAX_STACK_DEPTH - len(records_v2[i][2])
            d = min(int(depth[k]), budget)
            # Only adopt the walk when it beats the FP chain.
            if d > len(records_v2[i][3]):
                pid_, tid_, kf, _uf = out[i]
                out[i] = (pid_, tid_, kf, frames[k, :d].copy())
    return out


class PerfEventSampler:
    """Capture source: poll() blocks one window then drains the rings."""

    def __init__(self, frequency_hz: int = 100, window_s: float = 10.0,
                 drain_cap_mb: int = 64, capture_stack: bool = False,
                 stack_dump_bytes: int = 16 * 1024,
                 dwarf_comm_regex: str | None = None,
                 trust_fp_frames: int | None = None):
        self._lib = load_native()
        self._freq = frequency_hz
        self._window = window_s
        self._cap = drain_cap_mb << 20
        self._maps = ProcessMapCache()
        self._objs = ObjectFileCache()
        # Ingest containment: the CLI wires the shared per-pid quarantine
        # registry here (via the `quarantine` property) so the window-end
        # mapping build AND the DWARF unwind-table cache charge poisoned
        # pids instead of failing the snapshot (runtime/quarantine.py).
        self._quarantine = None
        # One reusable drain buffer: allocating + zeroing drain_cap_mb per
        # drain pass is pure churn on the capture path; only the n written
        # bytes are ever read back.
        self._drainbuf = (ctypes.c_uint8 * self._cap)()
        # (lost, truncated, dedup, dd_overflow) snapshotted at close
        self._final_counters = (0, 0, 0, 0)
        # Optional per-drain tee (FP mode): called on the polling thread
        # with each drain's columnar chunk so a streaming consumer (the
        # window feeder) can ship it to the aggregation device DURING the
        # window. A failing tee disables itself for the agent's lifetime
        # (the window-end snapshot path is unaffected either way).
        self.on_drain = None
        self.capture_stack = capture_stack
        flags = PA_CAPTURE_USER_STACK if capture_stack else 0
        self._handle = self._lib.pa_sampler_create2(
            frequency_hz, flags, stack_dump_bytes)
        if not self._handle:
            err = ctypes.get_errno()
            raise SamplerUnavailable(
                f"perf_event_open failed (errno {err}): needs CAP_PERFMON or "
                f"kernel.perf_event_paranoid <= 0"
            )
        if self._lib.pa_sampler_start(self._handle) != 0:
            # Free the per-CPU perf fds before raising: the caller
            # degrades to another capture source and this object is
            # discarded unclosed.
            self._lib.pa_sampler_destroy(self._handle)
            self._handle = None
            raise SamplerUnavailable("failed to enable perf events")
        self.n_cpus = self._lib.pa_sampler_n_cpus(self._handle)
        # Capture-side hash carry (docs/perf.md "feed endgame"): install
        # the Python-seeded multilinear coefficient tables so the dedup
        # drain can stamp each unique record with its h1/h2/h3 triple
        # while the frames are hot in cache. FP mode only (the DWARF
        # walker rewrites user chains after the drain, invalidating any
        # drain-time hash). A sampler that refuses the tables drains
        # hashless (v1d) and the feeder hashes host-side: exact either way.
        self.hash_carry = False
        if not capture_stack:
            try:
                from parca_agent_tpu.ops.hashing import hash_params

                coefs, biases = hash_params(3, STACK_SLOTS)
                u32p = ctypes.POINTER(ctypes.c_uint32)
                ok = self._lib.pa_sampler_set_hash(
                    self._handle, coefs.ctypes.data_as(u32p),
                    coefs.shape[1], biases.ctypes.data_as(u32p), 3,
                    STACK_SLOTS)
                self.hash_carry = ok == 0
            except AttributeError:
                # Stale pre-carry .so: run hashless; the feeder hashes
                # host-side exactly as before.
                pass
        self._tables = UnwindTableCache(
            self._maps, comm_regex=dwarf_comm_regex) if capture_stack \
            else None
        self._trust_fp_frames = trust_fp_frames
        from parca_agent_tpu.unwind.walker import WalkStats

        self.walk_stats = WalkStats()

    @property
    def quarantine(self):
        return self._quarantine

    @quarantine.setter
    def quarantine(self, registry) -> None:
        self._quarantine = registry
        if self._tables is not None:
            self._tables.quarantine = registry

    # Counter properties stay truthful after close(): the native handle
    # is gone then (the C getters would see NULL and answer 0), so close
    # snapshots the final values.
    @property
    def lost_samples(self) -> int:
        if self._handle:
            return int(self._lib.pa_sampler_lost(self._handle))
        return self._final_counters[0]

    @property
    def truncated_drains(self) -> int:
        if self._handle:
            return int(self._lib.pa_sampler_truncated(self._handle))
        return self._final_counters[1]

    @property
    def dedup_hits(self) -> int:
        """Samples merged into an existing row at the drain boundary
        (capture-side pre-aggregation effectiveness; measured ~92% of
        samples on a steady synthetic load)."""
        if self._handle:
            return int(self._lib.pa_sampler_dedup_hits(self._handle))
        return self._final_counters[2]

    @property
    def dedup_overflow(self) -> int:
        """Records emitted without table registration because the dedup
        probe chain saturated — distinguishes hash-table overflow from
        genuine stack uniqueness when the dedup rate drops."""
        if self._handle:
            return int(self._lib.pa_sampler_dedup_overflow(self._handle))
        return self._final_counters[3]

    def _drain_passes(self, consume, dedup: bool = False,
                      hashed: bool = False) -> None:
        """Lossless drain: loops while the native side reports records
        left behind for lack of buffer space, handing each pass's
        (buffer, n_bytes) to `consume` before the buffer is reused."""
        if hashed:
            drain = self._lib.pa_sampler_drain_dedup2
        else:
            drain = (self._lib.pa_sampler_drain_dedup if dedup
                     else self._lib.pa_sampler_drain)
        for _ in range(64):  # safety bound; one pass is the norm
            before = self.truncated_drains
            n = drain(
                self._handle, self._drainbuf, ctypes.c_long(self._cap))
            if n < 0:
                raise SamplerUnavailable("sampler drain failed")
            if n:
                consume(self._drainbuf, int(n))
            if self.truncated_drains == before:
                break

    def _drain(self) -> bytes:
        chunks = []
        self._drain_passes(
            lambda buf, n: chunks.append(ctypes.string_at(buf, n)))
        return b"".join(chunks)

    def _drain_columnar(self) -> list[tuple]:
        """Lossless DEDUP drain with the native columnar decoder applied
        per pass, straight off the reusable drain buffer (no bytes copy).
        The native side pre-aggregates repeats to (row, count) so Python
        decodes ~unique rows (the reference's in-kernel envelope). With
        hash carry installed the chunks additionally tail the h1/h2/h3
        triple (9 columns instead of 6); a refused v1h drain permanently
        falls back to the hashless v1d drain mid-session."""
        cols = []
        if self.hash_carry:
            try:
                self._drain_passes(
                    lambda buf, n: cols.append(
                        decode_records_columnar_v1h(self._lib, buf, n)),
                    hashed=True)
                return cols
            except SamplerUnavailable:
                _log.warn("v1h drain refused; disabling capture-side "
                          "hash carry for this sampler")
                self.hash_carry = False
                cols = []
        self._drain_passes(
            lambda buf, n: cols.append(
                decode_records_columnar_v1d(self._lib, buf, n)),
            dedup=True)
        return cols

    def mapping_table(self, pids) -> MappingTable:
        """The capture-source protocol's table of a list of pids, from
        this sampler's caches of ``/proc/<pid>/maps`` and object files
        (the streaming feeder asks once a drain, ``poll()`` once a
        window); a poisoned pid is charged to the quarantine registry."""
        return mapping_table_for_pids(self._maps, self._objs, pids,
                                      quarantine=self.quarantine)

    def poll(self) -> WindowSnapshot:
        deadline = time.monotonic() + self._window
        # Drain mid-window too so a ring never wraps (the reference sizes
        # BPF maps for a full window; perf rings are smaller).
        records = []
        col_chunks: list[tuple] = []
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            time.sleep(min(1.0, remaining))
            if self.capture_stack:
                raw = self._drain()
                v2 = decode_records_v2(raw)
                # Queue table builds early so they're ready within the
                # window (matches the 5 s watch cadence).
                for pid in {r[0] for r in v2}:
                    if self._tables.matches(pid):
                        self._tables.table_for(pid)
                records.extend(
                    unwind_records(v2, self._tables,
                                   trust_fp_frames=self._trust_fp_frames,
                                   stats=self.walk_stats))
            else:
                chunks = self._drain_columnar()
                col_chunks.extend(chunks)
                if self.on_drain is not None:
                    for c in chunks:
                        try:
                            self.on_drain(c)
                        except Exception as e:  # noqa: BLE001 - tee only
                            _log.warn("on_drain tee failed; disabling "
                                      "streaming for this agent",
                                      error=repr(e))
                            self.on_drain = None
                            break

        if self.capture_stack:
            pid_iter = sorted({r[0] for r in records})
        else:
            cols = [np.concatenate([c[i] for c in col_chunks])
                    if col_chunks else z
                    for i, z in enumerate((
                        np.zeros(0, np.int32), np.zeros(0, np.int32),
                        np.zeros(0, np.int32), np.zeros(0, np.int32),
                        np.zeros((0, STACK_SLOTS), np.uint64),
                        np.zeros(0, np.int64)))]
            pid_iter = np.unique(cols[0]).tolist()
        table = self.mapping_table(pid_iter)
        period_ns = int(1e9 / self._freq)
        window_ns = int(self._window * 1e9)
        if self.capture_stack:
            return records_to_snapshot(records, table, period_ns, window_ns)
        return columns_to_snapshot(*cols[:5], table, period_ns, window_ns,
                                   weights=cols[5])

    def close(self) -> None:
        if self._handle:
            self._final_counters = (self.lost_samples,
                                    self.truncated_drains, self.dedup_hits,
                                    self.dedup_overflow)
            self._lib.pa_sampler_destroy(self._handle)
            self._handle = None
        if self._tables is not None:
            self._tables.close()


class CommFilterSource:
    """Snapshot-source wrapper keeping only rows whose pid's comm matches
    one of the given regexes — the reference's hidden --debug-process-names
    debug flag (main.go DebugProcessNames: 'Only attach profilers to
    specified processes', matched against comm). Whole-machine capture
    stays on; rows are dropped at the window boundary, so the filter
    composes with any source. Comm verdicts are cached per pid with a
    TTL: pids get reused by the kernel and processes exec() into new
    comms, so a verdict is a lease, not a fact (and the TTL also bounds
    the cache under pid churn).

    NOTE: drains tee'd mid-window (streaming) bypass this filter; the CLI
    therefore runs debug-filtered sessions one-shot.
    """

    def __init__(self, source, patterns, read_comm=None,
                 cache_ttl_s: float = 60.0, clock=time.monotonic):
        self._source = source
        self._regexes = [re.compile(p) for p in patterns if p]
        self._cache: dict[int, tuple[bool, float]] = {}
        self._ttl = cache_ttl_s
        self._clock = clock

        def _default_read(pid: int) -> str:
            try:
                with open(f"/proc/{pid}/comm", "rb") as f:
                    return f.read().decode().strip()
            except OSError:
                return ""

        self._read_comm = read_comm or _default_read

    def __getattr__(self, name):
        return getattr(self._source, name)

    def _keep(self, pid: int, now: float) -> bool:
        got = self._cache.get(pid)
        if got is not None and now - got[1] < self._ttl:
            return got[0]
        comm = self._read_comm(pid)
        verdict = any(r.search(comm) for r in self._regexes)
        self._cache[pid] = (verdict, now)
        return verdict

    def poll(self):
        snap = self._source.poll()
        if snap is None or not len(snap) or not self._regexes:
            return snap
        now = self._clock()
        uniq = np.unique(snap.pids)
        if len(self._cache) > 4 * len(uniq) + 1024:
            # Bound the cache under pid churn: drop expired leases.
            self._cache = {p: v for p, v in self._cache.items()
                           if now - v[1] < self._ttl}
        kept = np.array([p for p in uniq.tolist()
                         if self._keep(int(p), now)], np.int32)
        if len(kept) == len(uniq):
            return snap
        return filter_snapshot_rows(snap, np.isin(snap.pids, kept))

    def close(self) -> None:
        self._source.close()
