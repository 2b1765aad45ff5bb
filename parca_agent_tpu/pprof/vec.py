"""Vectorized protobuf varint primitives (numpy, no per-value Python).

The pprof encode path is the agent's second hot loop: a 10 s window at
north-star scale carries ~1M deduplicated stacks x ~24 frames, i.e. tens of
millions of varints per window. The scalar encoder in
parca_agent_tpu/pprof/proto.py costs ~1 us per varint in CPython — minutes
per window at scale — so the window encoder batch-encodes with whole-array
numpy passes instead: compute every varint's byte length, cumsum to
positions, then write byte k of every value in pass k (at most 10 passes,
and the selection shrinks geometrically because most varints are short).

These helpers implement exactly the proto wire contract of proto.put_varint
(unsigned LEB128; int64 negatives are encoded by the caller pre-masking to
two's-complement uint64, as proto.put_varint does).
"""

from __future__ import annotations

import ctypes

import numpy as np

# varint byte-length thresholds: value >= 2^(7k) needs more than k bytes.
_THRESHOLDS = np.array([1 << (7 * k) for k in range(1, 10)], np.uint64)

# Native emission kernel (native/vecenc.cc): the numpy byte-plane passes
# are whole-array vectorized but go memory-system-superlinear at
# north-star scale (measured 1.67 s for 25M varints vs 0.15 s for 3.1M —
# 11x for 8x); one sequential native pass holds ~linear. Loaded lazily,
# built on demand like the sampler; every helper keeps its numpy path as
# the build-less fallback.
_native: ctypes.CDLL | None | bool = False  # False = not yet attempted


def _load_native() -> ctypes.CDLL | None:
    global _native
    if _native is False:
        _native = None
        try:
            from parca_agent_tpu.native import ensure_built

            lib = ctypes.CDLL(ensure_built("libpavecenc.so", "vecenc.cc"))
            lib.pa_varint_lens.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
            lib.pa_put_varints.restype = ctypes.c_int64
            lib.pa_put_varints.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64]
            lib.pa_put_varints_padded.restype = ctypes.c_int64
            lib.pa_put_varints_padded.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32]
            lib.pa_ragged_copy.restype = ctypes.c_int64
            lib.pa_ragged_copy.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64]
            _native = lib
        except Exception as e:  # noqa: BLE001 - fallback is numpy
            _native = None
            # One warning, not silence: the numpy byte-plane path is
            # ~1.7x slower per window at north-star scale
            # (docs/perf.md), and a host missing g++ would otherwise
            # regress invisibly.
            from parca_agent_tpu.utils.log import get_logger

            get_logger("pprof.vec").warn(
                "native varint kernel unavailable; falling back to "
                "the numpy encode path", error=repr(e))
    return _native


def varint_len(vals: np.ndarray) -> np.ndarray:
    """int32 [N] byte length of each value's varint encoding (1..10)."""
    vals = np.ascontiguousarray(vals, np.uint64)
    lib = _load_native()
    if lib is not None:
        lens = np.empty(len(vals), np.int32)
        lib.pa_varint_lens(vals.ctypes.data, len(vals), lens.ctypes.data)
        return lens
    lens = np.ones(len(vals), np.int32)
    for t in _THRESHOLDS:
        # Cheap early exit: thresholds are increasing, so once nothing
        # clears one, nothing clears the rest.
        more = vals >= t
        n_more = int(more.sum())
        if n_more == 0:
            break
        lens += more.astype(np.int32)
    return lens


def _dispatch_native(fn, out: np.ndarray, pos: np.ndarray,
                     vals: np.ndarray, *extra) -> bool:
    """Shared gate for the native scatter kernels. The C loops trust
    len(pos) == len(vals) and index `out` only after a bounds check, so
    the length agreement MUST be validated here: the numpy fallback
    raises IndexError on a short `pos` via fancy indexing, and the native
    path reading past `pos` could fabricate an in-bounds position and
    corrupt `out` silently (vecenc.cc: 'silent heap corruption here would
    be strictly worse'). Returns True when the native kernel ran."""
    if len(pos) != len(vals):
        raise IndexError(
            f"pos has {len(pos)} entries for {len(vals)} values")
    if fn is None or not (out.flags.c_contiguous and out.flags.writeable
                          and out.dtype == np.uint8):
        return False
    bad = fn(out.ctypes.data, len(out), pos.ctypes.data, vals.ctypes.data,
             len(vals), *extra)
    if bad >= 0:
        raise IndexError(
            f"varint region for value {bad} (pos {int(pos[bad])}) "
            f"leaves the {len(out)}-byte buffer")
    return True


def put_varints(out: np.ndarray, pos: np.ndarray, vals: np.ndarray,
                lens: np.ndarray | None = None) -> None:
    """Scatter varint encodings of vals into uint8 buffer `out` at byte
    positions `pos` (each value's encoding occupies pos[i]..pos[i]+len-1).

    Caller guarantees the regions were sized with varint_len and do not
    overlap. Native: one sequential emission pass. Numpy fallback: byte k
    of every encoding is written in one vectorized pass.
    """
    vals = np.ascontiguousarray(vals, np.uint64)
    pos = np.ascontiguousarray(pos, np.int64)
    lib = _load_native()
    if _dispatch_native(lib.pa_put_varints if lib is not None else None,
                        out, pos, vals):
        return
    if lens is None:
        lens = varint_len(vals)
    if len(pos) and int(np.min(pos)) < 0:
        raise IndexError("negative varint position")  # wrap = corruption
    sel = np.arange(len(vals))
    k = 0
    while len(sel):
        v = vals[sel]
        b = ((v >> np.uint64(7 * k)) & np.uint64(0x7F)).astype(np.uint8)
        cont = (lens[sel] > k + 1)
        out[pos[sel] + k] = b | (cont.astype(np.uint8) << 7)
        sel = sel[cont]
        k += 1


def put_varints_padded(out: np.ndarray, pos: np.ndarray, vals: np.ndarray,
                       width: int) -> None:
    """Scatter FIXED-WIDTH varint encodings: every value occupies exactly
    `width` bytes via non-minimal encoding (continuation bit set on all but
    the last byte; trailing zero septets are legal protobuf and decode to
    the same value). A fixed width makes a serialized message's layout
    independent of the values, which is what lets the window encoder patch
    counts into a cached template instead of re-serializing. Caller must
    pick width >= varint_len(max value) (5 covers uint32, 10 covers any
    uint64)."""
    # Both paths reject a bad width identically (the native kernel's own
    # width<1 check would surface as a misleading bounds IndexError, and
    # the numpy loop would silently write nothing); >10 would emit
    # continuation bytes beyond the longest legal protobuf varint.
    if not 1 <= width <= 10:
        raise ValueError(f"padded varint width must be in 1..10, got {width}")
    vals = np.ascontiguousarray(vals, np.uint64)
    pos = np.ascontiguousarray(pos, np.int64)
    lib = _load_native()
    if _dispatch_native(
            lib.pa_put_varints_padded if lib is not None else None,
            out, pos, vals, width):
        return
    if len(pos) and int(np.min(pos)) < 0:
        raise IndexError("negative varint position")  # wrap = corruption
    for k in range(width):
        b = ((vals >> np.uint64(7 * k)) & np.uint64(0x7F)).astype(np.uint8)
        if k < width - 1:
            b |= np.uint8(0x80)
        out[pos + k] = b


def encode_varint_stream(vals: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Encode values back-to-back: (flat uint8 buffer, int64 offsets[N+1])."""
    lens = varint_len(vals)
    offs = np.zeros(len(vals) + 1, np.int64)
    np.cumsum(lens, out=offs[1:])
    out = np.empty(int(offs[-1]), np.uint8)
    put_varints(out, offs[:-1], vals, lens)
    return out, offs


def ragged_gather(flat: np.ndarray, starts: np.ndarray, lens: np.ndarray,
                  out: np.ndarray | None = None,
                  out_starts: np.ndarray | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Gather variable-length runs flat[starts[i] : starts[i]+lens[i]] into
    one contiguous buffer (or scatter them to caller-chosen out_starts).

    Returns (out, out_offsets[N+1]) where out_offsets is the packed layout
    (exclusive cumsum of lens); when out_starts is given the runs land
    there instead and out_offsets is out_starts re-returned unchanged.
    """
    lens = np.ascontiguousarray(lens, np.int64)
    starts = np.ascontiguousarray(starts, np.int64)
    packed = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=packed[1:])
    n_total = int(packed[-1])
    if out_starts is None:
        offs = packed
        dst = packed[:-1]
        total = n_total
    else:
        offs = out_starts
        dst = np.ascontiguousarray(out_starts, np.int64)
        total = int((dst + lens).max(initial=0))
    if out is None:
        out = np.empty(total, flat.dtype)
    if n_total:
        lib = _load_native()
        if (lib is not None and flat.flags.c_contiguous
                and out.flags.c_contiguous and out.flags.writeable
                and out.dtype == flat.dtype):
            # Native path: one bounds-checked memcpy per run (positions
            # scaled to BYTES) — per-element fancy indexing costs ~3
            # int64 index ops per byte and dominates the template
            # layout's multi-MB splices.
            isz = flat.itemsize
            # Bind the scaled arrays to locals: .ctypes.data is a bare
            # int, and an inline temporary could be collected before the
            # C call reads through it.
            src_b = np.ascontiguousarray(starts * isz)
            dst_b = np.ascontiguousarray(dst * isz)
            len_b = np.ascontiguousarray(lens * isz)
            bad = lib.pa_ragged_copy(
                out.ctypes.data, out.nbytes, flat.ctypes.data,
                flat.nbytes, src_b.ctypes.data, dst_b.ctypes.data,
                len_b.ctypes.data, len(lens))
            if bad >= 0:
                raise IndexError(
                    f"ragged run {bad} (src {int(starts[bad])}, dst "
                    f"{int(dst[bad])}, len {int(lens[bad])}) leaves a "
                    f"buffer")
            return out, offs
        # within-run index for every output byte, then one fancy gather.
        within = np.arange(n_total, dtype=np.int64) - np.repeat(
            packed[:-1], lens)
        src = np.repeat(starts, lens) + within
        if out_starts is None:
            out[:n_total] = flat[src]
        else:
            out[np.repeat(dst, lens) + within] = flat[src]
    return out, offs
