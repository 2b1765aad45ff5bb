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
nanosecond-stamped filename, the gzip's compressor is the writing
thread's own and RemoteProfileWriter's downstream batch buffer is
lock-protected. `pprof_bytes` may be any bytes-like (the pipeline ships
zero-copy views into the encoder's template buffer; the gzip pass here
materializes them before the view is recycled).

The gzip member (`_ShipClocks._gzip`): a payload that says where its
static block lies (`static_span`; the fast encoder's blobs do,
pprof/window_encoder.py `_SpanBlob`) is not deflated whole. A raw
deflate stream cut with Z_FULL_FLUSH ends on a byte boundary and refers
to nothing before the cut, so pieces made at different times concatenate
into one valid stream:

    10-byte header (mtime 0)
    deflate(bytes before the span), full flush     every window
    deflate(the span), full flush                  made once, kept by the
                                                   payload's owner
    the bytes behind the span: one final stored block where they are
      few (a steady blob's 22-byte time tail), else deflated like the
      first piece and ended by an empty final block
    CRC32 and length of the whole payload, from the live bytes

Any inflater reads it (RFC 1951 blocks in an RFC 1952 member); it
inflates to exactly the payload. Everything that is deflated is
deflated at level 1 with zlib's default strategy and window, as
`gzip.compress(.., 1)` does, which is also what a payload with no span
gets, and what a splice that raises falls back to (counted).
"""

from __future__ import annotations

import gzip
import os
import struct
import threading
import time
import zlib

from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.vfs import atomic_write_bytes

# palint: persistence-root — local profile store writes survive restarts.


def _series_filename(labels: dict[str, str], now_ns: int) -> str:
    parts = [f"{k}={labels[k]}" for k in sorted(labels)
             if not k.startswith("__")]
    safe = "_".join(parts).replace("/", "-") or "profile"
    return f"{safe}.{now_ns}.pb.gz"


# RFC 1952 header as gzip.compress(.., 1) writes it (deflate, no flags,
# XFL 4 = fastest, OS 255 = unknown) with mtime 0: equal payloads give
# equal members.
_GZIP_HEADER = b"\x1f\x8b\x08\x00\x00\x00\x00\x00\x04\xff"
# An empty final block (BFINAL 1, fixed Huffman, end-of-block): ends a
# stream whose last piece was deflated and flushed.
_EMPTY_FINAL = b"\x03\x00"
# Bytes behind the span up to this many go out as one stored block
# (5 bytes of framing): a block of their own with its Huffman trees and
# its flush marker would cost more than it could save, on the wire and
# in time.
_STORED_MAX = 64

_SUMS = ("gzip_s", "enqueue_s", "pprof_bytes", "gzip_bytes",
         "gzip_static_reused", "gzip_static_built", "gzip_deflated_bytes",
         "gzip_fallbacks")


def _splice(payload, span, co) -> tuple[bytes, int, int]:
    """The gzip member of a payload whose static block is `span` (offset,
    length), through the calling thread's raw-deflate compressor `co`.
    Returns (member, pieces built: 0 or 1, bytes that went through
    deflate)."""
    off, length = span
    mv = memoryview(payload)
    end = off + length
    parts = [_GZIP_HEADER]
    deflated = built = 0
    if off:
        parts.append(co.compress(mv[:off]))
        parts.append(co.flush(zlib.Z_FULL_FLUSH))
        deflated += off
    piece = payload.static_piece()
    if piece is None:
        piece = co.compress(mv[off:end]) + co.flush(zlib.Z_FULL_FLUSH)
        payload.keep_static_piece(piece)
        built = 1
        deflated += length
    parts.append(piece)
    rest = mv[end:]
    n = len(rest)
    if n <= _STORED_MAX:
        parts.append(struct.pack("<BHH", 1, n, n ^ 0xFFFF))
        parts.append(rest)
    else:
        parts.append(co.compress(rest))
        parts.append(co.flush(zlib.Z_FULL_FLUSH))
        parts.append(_EMPTY_FINAL)
        deflated += n
    parts.append(struct.pack("<II", zlib.crc32(mv), len(mv) & 0xFFFFFFFF))
    return b"".join(parts), built, deflated


class _ShipClocks:
    """The gzip every writer shares, and what a writer's two halves cost
    over one ship, summed where the work is: gzip seconds, seconds
    handing the bytes on, bytes in and out, static pieces reused and
    built, bytes that went through deflate, splices that fell back. Per
    thread, compressor and sums alike, because the encode worker and the
    profiler thread's scalar fallback may write at once; the thread that
    shipped takes its own sums (the profiler records them as the ship
    span's children and the window's counts, profiler/cpu.py
    _write_all)."""

    def __init__(self):
        self._tls = threading.local()

    def _acc(self) -> list:
        acc = getattr(self._tls, "acc", None)
        if acc is None:
            acc = self._tls.acc = [0.0, 0.0, 0, 0, 0, 0, 0, 0]
        return acc

    def _gzip(self, payload) -> bytes:
        """One payload's gzip member. The path follows what the payload
        carries: a `static_span` is spliced (module docstring), anything
        else is `gzip.compress(.., 1)`."""
        span = getattr(payload, "static_span", None)
        acc = self._acc()
        if span is not None:
            tls = self._tls
            try:
                faults.inject("writer.splice")
                co = getattr(tls, "deflater", None)
                if co is None:
                    co = tls.deflater = zlib.compressobj(
                        1, zlib.DEFLATED, -zlib.MAX_WBITS)
                member, built, deflated = _splice(payload, span, co)
                acc[4] += 1 - built
                acc[5] += built
                acc[6] += deflated
                return member
            except Exception:  # noqa: BLE001 - this profile still ships
                # The compressor may hold half a piece: the next splice
                # starts from a fresh one.
                tls.deflater = None
                acc[7] += 1
        acc[6] += len(payload)
        return gzip.compress(payload, 1)

    def _tally(self, t0: float, t1: float, n_in: int, n_out: int) -> None:
        """One write: gzip ran from t0 to t1, the hand-on from t1 to now."""
        acc = self._acc()
        acc[0] += t1 - t0
        acc[1] += time.monotonic() - t1
        acc[2] += n_in
        acc[3] += n_out

    def take_ship_clocks(self) -> dict:
        """The calling thread's sums since it last took them."""
        acc = self._acc()
        self._tls.acc = None
        return dict(zip(_SUMS, acc))


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
        sample = self._gzip(pprof_bytes)
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
        sample = self._gzip(pprof_bytes)
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
        total = dict.fromkeys(_SUMS, 0)
        for w in self._writers:
            take = getattr(w, "take_ship_clocks", None)
            for k, v in (take() if take is not None else {}).items():
                total[k] += v
        return total

    def write(self, labels: dict[str, str],
              pprof_bytes: bytes | memoryview) -> None:
        for w in self._writers:
            w.write(labels, pprof_bytes)
