"""Native varint kernel (native/vecenc.cc) vs the numpy fallback.

vec.py dispatches to the native emission kernel when it builds/loads, and
keeps the numpy byte-plane path as the build-less fallback — these tests
pin byte-identical output between the two and the bounds-check contract
(a bad caller must get IndexError from either path, never a silent
out-of-bounds write; the reference leans on Go's memory safety for the
equivalent encode path, pkg/profiler/pprof.go).
"""

from __future__ import annotations

import numpy as np
import pytest

from parca_agent_tpu.pprof import vec


@pytest.fixture()
def native_lib():
    lib = vec._load_native()
    if lib is None:
        pytest.skip("native vecenc unavailable (no toolchain?)")
    return lib


def _numpy_only(monkeypatch):
    monkeypatch.setattr(vec, "_native", None)


@pytest.mark.parametrize("maxv", [2, 128, 4000, 1 << 40, None])
def test_native_matches_numpy(native_lib, monkeypatch, maxv):
    rng = np.random.default_rng(3)
    hi = np.iinfo(np.uint64).max if maxv is None else maxv
    vals = rng.integers(0, hi, 4096, dtype=np.uint64)

    lens_nat = vec.varint_len(vals)
    pos = np.zeros(len(vals), np.int64)
    np.cumsum(lens_nat[:-1], out=pos[1:])
    total = int(pos[-1] + lens_nat[-1])

    out_nat = np.zeros(total, np.uint8)
    vec.put_varints(out_nat, pos, vals, lens_nat)
    pad_nat = np.zeros(len(vals) * 10, np.uint8)
    vec.put_varints_padded(pad_nat, np.arange(len(vals), dtype=np.int64) * 10,
                           vals, 10)

    _numpy_only(monkeypatch)
    lens_np = vec.varint_len(vals)
    out_np = np.zeros(total, np.uint8)
    vec.put_varints(out_np, pos, vals, lens_np)
    pad_np = np.zeros(len(vals) * 10, np.uint8)
    vec.put_varints_padded(pad_np, np.arange(len(vals), dtype=np.int64) * 10,
                           vals, 10)

    np.testing.assert_array_equal(lens_nat, lens_np)
    np.testing.assert_array_equal(out_nat, out_np)
    np.testing.assert_array_equal(pad_nat, pad_np)


@pytest.mark.parametrize("scattered", [False, True],
                         ids=["packed", "scattered"])
def test_ragged_gather_native_matches_numpy(native_lib, monkeypatch,
                                            scattered):
    rng = np.random.default_rng(5)
    flat = rng.integers(0, 1 << 62, 5000, dtype=np.uint64)
    lens = rng.integers(0, 40, 300).astype(np.int64)  # empty runs too
    starts = rng.integers(0, len(flat) - 40, 300).astype(np.int64)
    kw = {}
    if scattered:  # runs land at caller-chosen, gapped, shuffled places
        slots = rng.permutation(300).astype(np.int64) * 48
        kw = dict(out_starts=slots)

    def gather():
        out = np.zeros(300 * 48, np.uint64) if scattered else None
        return vec.ragged_gather(flat, starts, lens, out=out, **kw)

    out_nat, offs_nat = gather()
    _numpy_only(monkeypatch)
    out_np, offs_np = gather()
    np.testing.assert_array_equal(out_nat, out_np)
    np.testing.assert_array_equal(offs_nat, offs_np)


def test_bounds_check_raises_both_paths(native_lib, monkeypatch):
    """A region leaving the buffer raises IndexError — native checks
    before writing; numpy's fancy indexing raises on its own."""
    vals = np.array([1, 300], np.uint64)   # lens 1, 2
    pos = np.array([0, 2], np.int64)       # needs 4 bytes; give 3
    out = np.zeros(3, np.uint8)
    with pytest.raises(IndexError):
        vec.put_varints(out, pos, vals)
    with pytest.raises(IndexError):
        vec.put_varints_padded(out, np.array([0], np.int64),
                               np.array([7], np.uint64), 5)
    _numpy_only(monkeypatch)
    with pytest.raises(IndexError):
        vec.put_varints(out, pos, vals)
    with pytest.raises(IndexError):
        vec.put_varints_padded(out, np.array([0], np.int64),
                               np.array([7], np.uint64), 5)


def test_negative_position_rejected_both_paths(native_lib, monkeypatch):
    """Numpy fancy indexing would WRAP a negative position to the end of
    the buffer (silent corruption); both paths must reject instead."""
    out = np.zeros(8, np.uint8)
    neg = np.array([-1], np.int64)
    five = np.array([5], np.uint64)
    with pytest.raises(IndexError):
        vec.put_varints(out, neg, five)
    with pytest.raises(IndexError):
        vec.put_varints_padded(out, neg, five, 3)
    _numpy_only(monkeypatch)
    with pytest.raises(IndexError):
        vec.put_varints(out, neg, five)
    with pytest.raises(IndexError):
        vec.put_varints_padded(out, neg, five, 3)
    assert not out.any()  # nothing was written by any rejected call


def test_readonly_output_rejected_not_corrupted(native_lib):
    """A read-only buffer must not be written through the raw pointer:
    the native gate falls through to numpy, which raises."""
    out = np.zeros(8, np.uint8)
    out.flags.writeable = False
    with pytest.raises((ValueError, IndexError)):
        vec.put_varints(out, np.array([0], np.int64),
                        np.array([5], np.uint64))
    with pytest.raises((ValueError, IndexError)):
        vec.put_varints_padded(out, np.array([0], np.int64),
                               np.array([5], np.uint64), 3)
    assert not out.any()


def test_length_mismatch_rejected_both_paths(native_lib, monkeypatch):
    """pos/vals length disagreement raises IndexError from BOTH paths: the
    native loop would otherwise read past `pos` and could fabricate an
    in-bounds position — a silent write at an arbitrary offset."""
    out = np.zeros(64, np.uint8)
    short_pos = np.array([0, 2], np.int64)
    vals = np.array([1, 2, 3], np.uint64)
    with pytest.raises(IndexError):
        vec.put_varints(out, short_pos, vals)
    with pytest.raises(IndexError):
        vec.put_varints_padded(out, short_pos, vals, 5)
    _numpy_only(monkeypatch)
    with pytest.raises(IndexError):
        vec.put_varints(out, short_pos, vals)
    with pytest.raises(IndexError):
        vec.put_varints_padded(out, short_pos, vals, 5)
    assert not out.any()


def test_padded_width_out_of_range_rejected_both_paths(native_lib,
                                                       monkeypatch):
    """width<1 (would write nothing / trip the kernel's bounds return) and
    width>10 (longer than any legal protobuf varint) raise ValueError
    identically on both paths, before anything is written."""
    out = np.zeros(64, np.uint8)
    pos = np.array([0], np.int64)
    vals = np.array([7], np.uint64)
    for width in (0, -1, 11):
        with pytest.raises(ValueError):
            vec.put_varints_padded(out, pos, vals, width)
    _numpy_only(monkeypatch)
    for width in (0, -1, 11):
        with pytest.raises(ValueError):
            vec.put_varints_padded(out, pos, vals, width)
    assert not out.any()


def test_native_build_failure_falls_back_with_a_warning(monkeypatch):
    """A failed native build must land on the numpy path (with one log
    warning), never raise out of the varint helpers mid-encode."""
    from parca_agent_tpu import native as native_mod

    def boom(*a, **kw):
        raise RuntimeError("no toolchain")

    monkeypatch.setattr(vec, "_native", False)   # force a fresh load
    monkeypatch.setattr(native_mod, "ensure_built", boom)
    try:
        vals = np.array([1, 300, 1 << 40], np.uint64)
        lens = vec.varint_len(vals)              # first call hits the except
        out = np.zeros(int(lens.sum()), np.uint8)
        pos = np.zeros(3, np.int64)
        np.cumsum(lens[:-1], out=pos[1:])
        vec.put_varints(out, pos, vals)
        assert out.any()
        assert vec._load_native() is None        # pinned to the fallback
    finally:
        monkeypatch.setattr(vec, "_native", False)  # don't poison others


def test_a_window_encodes_to_the_same_bytes_without_the_library(
        native_lib, monkeypatch):
    """The numpy arm is entered by a library that fails to build or
    load, and by nothing else: a window encoded that way (first layout,
    then a patched window with new rows) ships the bytes the native arm
    ships."""
    from parca_agent_tpu import native as native_mod
    from parca_agent_tpu.aggregator.dict import DictAggregator
    from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
    from parca_agent_tpu.pprof.window_encoder import WindowEncoder

    snap = generate(SyntheticSpec(n_pids=12, n_unique_stacks=600,
                                  n_rows=600, total_samples=5000,
                                  mean_depth=14, kernel_fraction=0.2,
                                  seed=21))

    def windows():
        agg = DictAggregator(capacity=1 << 12)
        enc = WindowEncoder(agg)
        counts = np.asarray(agg.window_counts(snap)).copy()
        first = counts.copy()
        first[::7] = 0  # these stacks appear in the second window
        return [[(pid, bytes(b)) for pid, b in enc.encode(
            c, snap.time_ns + w, snap.window_ns, snap.period_ns)]
            for w, c in enumerate((first, counts))]

    want = windows()

    def boom(*a, **kw):
        raise OSError("libpavecenc.so: cannot open shared object file")

    monkeypatch.setattr(vec, "_native", False)   # force a fresh load
    monkeypatch.setattr(native_mod, "ensure_built", boom)
    try:
        got = windows()
        assert vec._native is None               # the load was tried, failed
    finally:
        monkeypatch.setattr(vec, "_native", False)  # don't poison others
    assert got == want
