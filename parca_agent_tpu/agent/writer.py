"""Profile writers: local files and remote (via listener/batcher).

Role of the reference's pkg/profiler/profile_writer.go:32-97:
FileProfileWriter stores each window's profile as a .pb.gz under a
directory (--local-store-directory mode); RemoteProfileWriter gzips the
encoded pprof and hands it to the write path (listener -> batch client).

Thread contract: in fast-encode mode write() is called from the encode
pipeline's worker thread (ship overlaps the next window's capture), and
may be called CONCURRENTLY from the profiler thread on the scalar
fallback path — both writers must (and do) tolerate that:
FileProfileWriter does one self-contained open/write per profile under a
nanosecond-stamped filename, RemoteProfileWriter's gzip is pure and its
downstream batch buffer is lock-protected. `pprof_bytes` may be any bytes-like (the pipeline
ships zero-copy memoryviews into the encoder's template buffer; the gzip
pass here materializes them before the view is recycled).
"""

from __future__ import annotations

import gzip
import os
import threading
import time

from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.vfs import atomic_write_bytes

# palint: persistence-root — local profile store writes survive restarts.


def _series_filename(labels: dict[str, str], now_ns: int) -> str:
    parts = [f"{k}={labels[k]}" for k in sorted(labels)
             if not k.startswith("__")]
    safe = "_".join(parts).replace("/", "-") or "profile"
    return f"{safe}.{now_ns}.pb.gz"


class _ShipClocks:
    """What a writer's two halves cost over one ship, summed where the
    work is: gzip seconds, seconds handing the bytes on, bytes in and
    out. Per thread, because the encode worker and the profiler thread's
    scalar fallback may write at once; the thread that shipped takes its
    own sums (the profiler records them as the ship span's children,
    profiler/cpu.py _write_all)."""

    def __init__(self):
        self._tls = threading.local()

    def _tally(self, t0: float, t1: float, n_in: int, n_out: int) -> None:
        """One write: gzip ran from t0 to t1, the hand-on from t1 to now."""
        acc = getattr(self._tls, "acc", None)
        if acc is None:
            acc = self._tls.acc = [0.0, 0.0, 0, 0]
        acc[0] += t1 - t0
        acc[1] += time.monotonic() - t1
        acc[2] += n_in
        acc[3] += n_out

    def take_ship_clocks(self) -> dict:
        """The calling thread's sums since it last took them."""
        acc = getattr(self._tls, "acc", None) or [0.0, 0.0, 0, 0]
        self._tls.acc = None
        return {"gzip_s": acc[0], "enqueue_s": acc[1],
                "pprof_bytes": acc[2], "gzip_bytes": acc[3]}


class FileProfileWriter(_ShipClocks):
    def __init__(self, directory: str):
        super().__init__()
        self._dir = directory
        os.makedirs(directory, exist_ok=True)

    def write_raw(self, labels: dict[str, str], sample: bytes) -> None:
        """`sample` is already a gzipped pprof proto. Written through a
        tmp file + os.replace so a crash (or injected disk-full) mid-write
        never leaves a truncated .pb.gz in the local-store directory —
        readers of the directory only ever see whole profiles."""
        path = os.path.join(self._dir, _series_filename(labels, time.time_ns()))
        faults.inject("writer.write")
        atomic_write_bytes(path, sample)

    def write(self, labels: dict[str, str],
              pprof_bytes: bytes | memoryview) -> None:
        """Profile-writer interface: encode side handles gzip."""
        t0 = time.monotonic()
        sample = gzip.compress(pprof_bytes, 1)
        t1 = time.monotonic()
        self.write_raw(labels, sample)
        self._tally(t0, t1, len(pprof_bytes), len(sample))


class RemoteProfileWriter(_ShipClocks):
    """pprof bytes -> gzip -> downstream write_raw sink."""

    def __init__(self, sink):
        super().__init__()
        self._sink = sink

    def write(self, labels: dict[str, str],
              pprof_bytes: bytes | memoryview) -> None:
        t0 = time.monotonic()
        sample = gzip.compress(pprof_bytes, 1)
        t1 = time.monotonic()
        self._sink.write_raw(labels, sample)
        self._tally(t0, t1, len(pprof_bytes), len(sample))


class TeeProfileWriter:
    """Fan one profile write to several writers (--local-store-directory
    plus the remote path). Arms are constructed ONCE, here — the old CLI
    closure built a fresh RemoteProfileWriter per write. A failing arm
    aborts the remaining arms, like the single-writer path: the caller's
    per-profile error handling owns the failure either way."""

    def __init__(self, *writers):
        self._writers = writers

    def take_ship_clocks(self) -> dict:
        """The arms' sums added up (every arm gzips for itself)."""
        total = {"gzip_s": 0.0, "enqueue_s": 0.0, "pprof_bytes": 0,
                 "gzip_bytes": 0}
        for w in self._writers:
            take = getattr(w, "take_ship_clocks", None)
            for k, v in (take() if take is not None else {}).items():
                total[k] += v
        return total

    def write(self, labels: dict[str, str],
              pprof_bytes: bytes | memoryview) -> None:
        for w in self._writers:
            w.write(labels, pprof_bytes)
