"""Hashing primitives for the device aggregation path.

Everything here works on uint32 lanes because TPUs have no native 64-bit
integer datapath (JAX runs with x64 disabled); 64-bit addresses travel as
(hi, lo) uint32 pairs. The workhorse is a multilinear hash family
h(x) = b + sum_i a_i * x_i (mod 2^32) with fixed random odd coefficients:
pairwise collision probability <= 2^-32 per independent hash, fully
vectorizable as a multiply + lane reduction, which XLA fuses into the
surrounding sort pipeline.

The role MurmurHash2 plays on the reference capture side (hashing the
127-slot DWARF stack buffer into a stack id, reference bpf/cpu/cpu.bpf.c:
438-448 and bpf/cpu/hash.h:6) is played here by two independent multilinear
hashes over the padded stack row; unlike the reference we never trust the
hash alone — the dedup pipeline compares full rows before merging.
"""

from __future__ import annotations

import collections
import ctypes
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.log import get_logger

# Enough coefficient lanes for [hi | lo | pid | user_len | kernel_len].
_MAX_LANES = 2 * 128 + 8
# Independent hash families: 2 for the batch kernel's sort keys, 3 for the
# dictionary aggregator's 96-bit identity (its bucket index is family 0),
# one spare. Each family draws from its OWN seeded stream so adding
# families can never shift another family's constants — hashes must be
# stable across processes and versions, or fleet-merged sketches built on
# different hosts stop agreeing bucket-for-bucket.
N_FAMILIES = 4


def _family_rng(k: int) -> np.random.Generator:
    return np.random.default_rng([0x9E3779B9, k])


# Odd coefficients make x -> a*x a bijection mod 2^32.
_COEFS = np.stack([
    _family_rng(k).integers(0, 1 << 32, _MAX_LANES, dtype=np.uint64)
    .astype(np.uint32) | np.uint32(1)
    for k in range(N_FAMILIES)
])
_BIASES = np.array([
    int(np.random.default_rng([0x2545F491, k]).integers(
        0, 1 << 32, dtype=np.uint64))
    for k in range(N_FAMILIES)
], np.uint32)


def _np_or_jnp(x):
    return np if isinstance(x, np.ndarray) else _jnp()


def _jnp():
    import jax.numpy as jnp

    return jnp


def mix32(x, seed: int = 0):
    """fmix32 finalizer (murmur3-style): avalanche a uint32 lane."""
    xp = _np_or_jnp(x)
    x = x.astype(xp.uint32) ^ xp.uint32(seed & 0xFFFFFFFF)
    x = x ^ (x >> xp.uint32(16))
    x = x * xp.uint32(0x85EBCA6B)
    x = x ^ (x >> xp.uint32(13))
    x = x * xp.uint32(0xC2B2AE35)
    x = x ^ (x >> xp.uint32(16))
    return x


def multilinear_hash_u32(lanes, which: int):
    """Hash uint32 lane matrix [N, K] -> uint32 [N] with hash family `which`.

    Modular arithmetic wraps naturally in uint32; the final mix decorrelates
    the low bits so the result can be truncated for sketch bucket indices.
    """
    xp = _np_or_jnp(lanes)
    k = lanes.shape[-1]
    if k > _MAX_LANES:
        raise ValueError(f"too many lanes to hash: {k} > {_MAX_LANES}")
    coefs = xp.asarray(_COEFS[which, :k])
    acc = (lanes.astype(xp.uint32) * coefs[None, :]).sum(axis=-1, dtype=xp.uint32)
    return mix32(acc + xp.asarray(_BIASES[which]))


def fold_u64_rows(hi, lo, extra=None):
    """Interleave (hi, lo) uint32 matrices [N, S] (+ optional scalar columns
    [N] each) into one lane matrix for multilinear_hash_u32."""
    xp = _np_or_jnp(hi)
    cols = [hi.astype(xp.uint32), lo.astype(xp.uint32)]
    if extra:
        cols.append(xp.stack([c.astype(xp.uint32) for c in extra], axis=-1))
    return xp.concatenate(cols, axis=-1)


# Native batch row-hash kernel (native/vecenc.cc pa_row_hash): the numpy
# path below materializes the full [N, 2*128+3] uint32 lane matrix —
# ~1 GB of transient traffic per 1M-row window, almost all zero padding —
# while the C pass walks only each row's live depth. Loaded lazily and
# built on demand like the varint kernel; PARCA_NO_NATIVE_HASH=1 forces
# the numpy path (which is how tests pin the bit-identity of both).
_native: ctypes.CDLL | None | bool = False  # False = not yet attempted


def _load_native() -> ctypes.CDLL | None:
    global _native
    if _native is False:
        _native = None
        try:
            from parca_agent_tpu.native import ensure_built

            lib = ctypes.CDLL(ensure_built("libpavecenc.so", "vecenc.cc"))
            lib.pa_row_hash.restype = ctypes.c_int64
            lib.pa_row_hash.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
            lib.pa_row_hash_range.restype = ctypes.c_int64
            lib.pa_row_hash_range.argtypes = lib.pa_row_hash.argtypes + [
                ctypes.c_int64, ctypes.c_int64]
            _native = lib
        except Exception as e:  # noqa: BLE001 - fallback is numpy
            _native = None
            # One warning, not silence: the lane-matrix numpy path is
            # several times slower per window at scale (docs/perf.md
            # "ingest wall") and a host missing g++ would otherwise
            # regress invisibly.
            get_logger("ops.hashing").warn(
                "native row-hash kernel unavailable; falling back to the "
                "numpy lane-matrix path", error=repr(e))
    return _native


# The row hash across cores (docs/perf.md "The row hash across cores").
# Rows are independent and a row writes its own output column only, so a
# large batch is hashed as disjoint row ranges on several threads: the
# calling thread and a few workers that are made once, on the first
# large batch, and stay parked on the pool's queue between windows (the
# chip's host is slow at system calls: no thread is made per window).
# ctypes releases the GIL round each call. The degree follows the one
# thing the input shows, its row count: under two ranges' worth of rows
# the call is the serial pa_row_hash on the calling thread and nothing
# here is touched. Constants, not switches: a node's window (10,240 rows;
# 6,400 a drain) stays serial, a firehose window (262,144) is 16 ranges.
_HASH_RANGE_ROWS = 16_384  # the least rows a range holds
_HASH_WORKERS_MAX = 4  # parked workers beside the calling thread
_pool: ThreadPoolExecutor | None = None
_pool_lock = threading.Lock()


def _hash_workers() -> int:
    """Workers beside the calling thread: the cores this process may
    run on, less one for the calling (capture) thread and one for the
    encode worker, capped."""
    return max(0, min(_HASH_WORKERS_MAX, len(os.sched_getaffinity(0)) - 2))


def _hash_pool(workers: int) -> ThreadPoolExecutor:
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(workers,
                                       thread_name_prefix="row-hash")
        return _pool


def _hash_ranged(lib, args, n: int, workers: int) -> dict:
    """Hash rows [0, n) as n // _HASH_RANGE_ROWS even ranges, each a
    pa_row_hash_range call: `workers` pool threads and the calling
    thread take ranges off one queue until it is empty, so a worker
    that wakes late does less and one that never wakes costs nothing
    (its task is cancelled). Returns the split's facts; raises what a
    range raised, after every thread has left the arrays."""
    r = n // _HASH_RANGE_ROWS
    pending = collections.deque(
        (n * j // r, n * (j + 1) // r) for j in range(r))

    def run():
        while True:
            try:
                i0, i1 = pending.popleft()
            except IndexError:
                return
            faults.inject("feed.hash")
            if lib.pa_row_hash_range(*args, i0, i1) != -1:
                raise ValueError("row-hash layout guard tripped")

    futures = []
    err = None
    try:
        pool = _hash_pool(workers)
        for _ in range(min(workers, r - 1)):
            futures.append(pool.submit(run))
        run()
    except Exception as e:  # noqa: BLE001 - re-raised below
        err = e
        pending.clear()
    for f in futures:
        if not f.cancel():  # running or done: wait, it holds the arrays
            err = err or f.exception()
    if err is not None:
        raise err
    return {"ranges": r, "threads": len(futures) + 1}


def _u32(col) -> np.ndarray:
    """A column as contiguous uint32: the snapshot's own int32 column
    viewed in place (the bits a cast gives), anything else converted."""
    col = np.asarray(col)
    if col.dtype == np.int32 and col.flags.c_contiguous:
        return col.view(np.uint32)
    return np.ascontiguousarray(col, np.uint32)


def _row_hash_native(stacks_u64, pids, user_len, kernel_len,
                     n_hashes: int, facts: dict | None = None):
    """Native dispatch, or None when the kernel cannot take this input
    (unavailable, non-contiguous, or too many lanes). Bit-identical to
    the numpy twin for contract-valid rows (zero-padded past depth —
    zero lanes contribute coef*0 to a multilinear hash either way).
    `facts`, when given, learns how the batch was hashed: `ranges`,
    `threads`, and `fallback` when the ranged form raised and the
    serial call hashed the whole batch instead."""
    lib = _load_native()
    if lib is None or n_hashes < 1 or n_hashes > N_FAMILIES:
        return None
    stacks = stacks_u64
    if stacks.dtype != np.uint64 or stacks.ndim != 2 \
            or not stacks.flags.c_contiguous:
        return None
    n, slots = stacks.shape
    k = 2 * slots + 3
    if k > _MAX_LANES:
        raise ValueError(f"too many lanes to hash: {k} > {_MAX_LANES}")
    pids_u, ulen_u, klen_u = _u32(pids), _u32(user_len), _u32(kernel_len)
    # The same bits an int64 sum cast to int32 has, from one array: the
    # chip's host pays every fresh page of a temporary (PERF.md, PR 38).
    depth = (ulen_u + klen_u).view(np.int32)
    coefs = np.ascontiguousarray(_COEFS[:n_hashes, :k])
    biases = np.ascontiguousarray(_BIASES[:n_hashes])
    out = np.empty((n_hashes, n), np.uint32)
    args = (stacks.ctypes.data, n, slots, pids_u.ctypes.data,
            ulen_u.ctypes.data, klen_u.ctypes.data, depth.ctypes.data,
            coefs.ctypes.data, coefs.shape[1], biases.ctypes.data, n_hashes,
            out.ctypes.data)
    how = {"ranges": 1, "threads": 1}
    workers = _hash_workers() if n >= 2 * _HASH_RANGE_ROWS else 0
    if workers:
        try:
            how = _hash_ranged(lib, args, n, workers)
        except Exception as e:  # noqa: BLE001 - counted fallback
            # Fail-open to the serial call over the whole batch: the
            # same bits, whatever the ranges had written already.
            how["fallback"] = 1
            get_logger("ops.hashing").warn(
                "ranged row hash failed; hashing the batch serially",
                error=repr(e)[:200])
    if how["ranges"] == 1 and lib.pa_row_hash(*args) != -1:
        return None  # layout guard tripped (cannot happen from here)
    if facts is not None:
        facts.update(how)
    return tuple(out)


def native_hash_available() -> bool:
    """Whether the native batch row-hash kernel is loadable. The feed
    path orders its work on this: with the native kernel (walks only
    live depth) it hashes every row then folds by triple; without it the
    numpy lane-matrix fallback pays O(rows x lanes) per hash, so the
    fold runs first and only representatives get hashed."""
    return _load_native() is not None


def hash_params(n_hashes: int, slots: int):
    """Contiguous (coefs [n_hashes, 2*slots+3], biases [n_hashes]) slices
    of the seeded multilinear family — what the capture sampler installs
    via pa_sampler_set_hash so its drain-time h1/h2/h3 carry matches
    row_hash_np bit-for-bit. The C side cannot regenerate numpy-seeded
    streams; these tables are the single source of truth."""
    if not 1 <= n_hashes <= N_FAMILIES:
        raise ValueError(f"n_hashes out of range: {n_hashes}")
    k = 2 * slots + 3
    if k > _MAX_LANES:
        raise ValueError(f"too many lanes to hash: {k} > {_MAX_LANES}")
    return (np.ascontiguousarray(_COEFS[:n_hashes, :k]),
            np.ascontiguousarray(_BIASES[:n_hashes]))


def row_hash_np(stacks_u64: np.ndarray, pids, user_len, kernel_len,
                n_hashes: int = 2, facts: dict | None = None):
    """Host-side (numpy) twin of the device row hash; used by sketches, the
    dictionary aggregator, and tests to confirm host/device agreement.

    Dispatches to the native batch kernel when available (bit-identical
    output — the dict aggregator's probe path and every cross-node join
    key on these exact values); PARCA_NO_NATIVE_HASH=1 pins the numpy
    lane-matrix fallback. A `facts` dict learns how the native kernel
    hashed the batch (`_row_hash_native`); the numpy path leaves it
    empty."""
    stacks_u64 = np.asarray(stacks_u64, np.uint64)
    if not os.environ.get("PARCA_NO_NATIVE_HASH") and len(stacks_u64):
        got = _row_hash_native(stacks_u64, pids, user_len, kernel_len,
                               n_hashes, facts)
        if got is not None:
            return got
    hi = (stacks_u64 >> np.uint64(32)).astype(np.uint32)
    lo = stacks_u64.astype(np.uint32)
    lanes = fold_u64_rows(
        hi,
        lo,
        extra=[
            np.asarray(pids, np.uint32),
            np.asarray(user_len, np.uint32),
            np.asarray(kernel_len, np.uint32),
        ],
    )
    return tuple(multilinear_hash_u32(lanes, k) for k in range(n_hashes))
