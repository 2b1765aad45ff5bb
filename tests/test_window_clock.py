"""Cadence invariance of the window-clocked registries.

Every window-denominated knob in runtime/ is authored at the 10 s
reference window and converted through runtime/window_clock.py at
construction, so the robustness contract is a wall-clock contract:
"3 windows of cooldown" means ~30 seconds at ANY --profiling-duration.
These tests parameterize the four window-clocked state machines the
endurance matrix leans on — admission token refill, quarantine strike
decay, sentinel rollup sealing, identity sweep — over
``window_s in {10.0, 1.0, 0.5}`` and pin that per-second semantics,
wall-clock patience, and per-event counters do not move with cadence.
"""

from __future__ import annotations

import numpy as np
import pytest

from parca_agent_tpu.aggregator.base import ProfileMapping
from parca_agent_tpu.ops.sketch import CountMinSpec
from parca_agent_tpu.process.identity import ProcessIdentityTracker
from parca_agent_tpu.runtime.admission import AdmissionController
from parca_agent_tpu.runtime.quarantine import (
    LEVEL_FULL,
    QuarantineRegistry,
)
from parca_agent_tpu.runtime.regression import (
    RegressionSentinel,
    RegressionSpec,
)
from parca_agent_tpu.runtime.window_clock import (
    REFERENCE_WINDOW_S,
    check_window_s,
    per_window,
    windows_for,
)

# The cadence axis the endurance matrix runs (docs/robustness.md):
# reference, the 10x sub-second target, and one uglier non-divisor.
CADENCES = [10.0, 1.0, 0.5]

cadence = pytest.mark.parametrize("window_s", CADENCES)


# -- the conversion primitives ----------------------------------------------

def test_reference_cadence_conversions_are_exact_identities():
    for n in (1, 2, 3, 6, 30, 60):
        assert windows_for(n, REFERENCE_WINDOW_S) == n
    for r in (0, 1, 100, 5000):
        assert per_window(r, REFERENCE_WINDOW_S) == float(r)


@cadence
def test_conversions_preserve_wall_time_and_rate(window_s):
    # Window-count knobs: same seconds of patience at any cadence.
    for n in (1, 3, 6, 30):
        assert windows_for(n, window_s) * window_s == pytest.approx(
            n * REFERENCE_WINDOW_S)
    # Rate knobs: same per-second budget at any cadence.
    for r in (50, 1000):
        assert per_window(r, window_s) / window_s == pytest.approx(
            r / REFERENCE_WINDOW_S)


def test_check_window_s_rejects_nonpositive():
    for bad in (0.0, -1.0, -0.5):
        with pytest.raises(ValueError):
            check_window_s(bad)
    assert check_window_s(0.25) == 0.25


def test_windows_for_floor_is_one_window():
    # A sub-window commitment still costs at least one window.
    assert windows_for(1, 60.0) == 1


# -- admission: token refill is a per-second budget --------------------------

class _StubResolver:
    def resolve(self, pid: int) -> str:
        return "noisy" if pid == 1 else "calm"


def _run_admission(window_s: float, wall_s: float = 120.0):
    """One noisy tenant at 200 samples/s against a 100/s quota, one calm
    tenant at 50/s, fed for ``wall_s`` seconds of windows. Returns the
    wall time at which the noisy tenant first degraded."""
    adm = AdmissionController(
        _StubResolver(), quota_samples=1000, burst_windows=3,
        degrade_after=2, window_s=window_s)
    onset_wall = None
    n = windows_for(wall_s / REFERENCE_WINDOW_S * 10, window_s)
    noisy = int(200 * window_s)
    calm = int(50 * window_s)
    for i in range(n):
        adm.account_window(np.array([1, 2]), np.array([noisy, calm]))
        adm.tick_window()
        assert adm.level_for(2) == LEVEL_FULL, \
            f"in-quota tenant degraded at window {i} ({window_s=})"
        if onset_wall is None and adm.level_for(1) > LEVEL_FULL:
            onset_wall = (i + 1) * window_s
    return onset_wall


@cadence
def test_admission_refill_degrades_overquota_tenant_only(window_s):
    onset = _run_admission(window_s)
    assert onset is not None, "2x-over tenant never degraded"


def test_admission_degrade_onset_holds_wall_time_across_cadences():
    # The wall-clock arc is fixed: the burst bank (3 ref-windows of
    # quota) drains at the same per-second overdraft at every cadence,
    # then the over-quota streak must cover degrade_after ref-windows.
    # The only cadence-dependent term is discretization — the window in
    # which the bank first goes dry counts as over-window #1 — so
    # onsets may differ by at most one window of the coarsest cadence.
    onsets = {w: _run_admission(w) for w in CADENCES}
    assert all(v is not None for v in onsets.values()), onsets
    spread = max(onsets.values()) - min(onsets.values())
    assert spread < max(CADENCES), onsets


# -- quarantine: strike decay is a wall-time cooldown ------------------------

@cadence
def test_quarantine_cooldown_holds_wall_time(window_s):
    reg = QuarantineRegistry(max_strikes=1, quarantine_windows=3,
                             window_s=window_s)
    for _ in range(2):  # strikes must EXCEED max_strikes to trip
        reg.record_error(7, "maps.parse", ValueError("boom"))
    assert reg.is_quarantined(7)
    ticks = 0
    while reg.is_quarantined(7):
        reg.tick_window()
        ticks += 1
        assert ticks < 10_000, "cooldown never decayed"
    # "3 windows of quarantine" is a 30 s sentence at every cadence.
    assert ticks * window_s == pytest.approx(3 * REFERENCE_WINDOW_S)


# -- sentinel: rollup sealing rides the wall clock, not the tick rate --------

T0_NS = 1_700_000_000_000_000_000


def _Reg(mappings, n_locs):
    """A registry as the aggregator keeps one: array columns."""
    from parca_agent_tpu.aggregator.dict import _PidRegistry

    normalized = 0x100 * np.arange(1, n_locs + 1, dtype=np.uint64)
    return _PidRegistry(
        normalized.copy(), normalized,
        (1 + np.arange(n_locs) % len(mappings)).astype(np.int32),
        np.zeros(n_locs, bool), mappings, {})


class _View:
    """RegistryView duck-type: sid i has hashes (i+1, 2*(i+1)), pid
    1000, and leaf location id i+1 (1-based)."""

    def __init__(self, n):
        self._loc_off = np.arange(n + 1, dtype=np.int64)
        self._loc_flat = np.arange(1, n + 1, dtype=np.int64)
        self._id_pid = np.full(n, 1000, np.int64)
        self._h1 = np.arange(1, n + 1, dtype=np.uint32)
        self._h2 = (2 * np.arange(1, n + 1)).astype(np.uint32)

    def id_hashes(self, n=None):
        return self._h1, self._h2


class _Prep:
    def __init__(self, idx, vals, time_ns, caps, duration_ns):
        self.idx = np.asarray(idx, np.int64)
        self.vals = np.asarray(vals, np.int64)
        self.pids_live = np.full(len(self.idx), 1000, np.int64)
        self.time_ns = time_ns
        self.duration_ns = duration_ns
        self.caps = caps


@cadence
def test_sentinel_seals_per_rollup_interval_not_per_window(window_s):
    n_stacks = 4
    sent = RegressionSentinel(spec=RegressionSpec(
        interval_s=10.0, baseline_rollups=3, min_count=4,
        cm=CountMinSpec(depth=4, width=1 << 10)))
    maps = [ProfileMapping(id=1, start=0, end=0, offset=0,
                           path="/bin/b1", build_id="b1", base=0)]
    reg = _Reg(maps, n_stacks)
    view = _View(n_stacks)
    caps = {1000: (reg, len(maps), n_stacks)}
    dur_ns = int(window_s * 1e9)
    wall_s = 60.0
    for w in range(int(round(wall_s / window_s))):
        prep = _Prep(np.arange(n_stacks), [10] * n_stacks,
                     T0_NS + int(w * window_s * 1e9), caps, dur_ns)
        sent.fold_from_prepared(view, prep)
    # One final empty window exactly at the wall so the last bucket
    # seals at every cadence.
    sent.fold_from_prepared(
        view, _Prep([], [], T0_NS + int(wall_s * 1e9), caps, dur_ns))
    # 60 s at a 10 s rollup interval is 6 sealed rollups whether the
    # window clock ticked 6 times or 120.
    assert sent.stats["rollups_sealed"] == 6


# -- identity: reuse detection is per-event, not per-tick --------------------

@cadence
def test_identity_sweep_counts_events_not_windows(window_s):
    world = {7: 100, 8: 200}
    tracker = ProcessIdentityTracker(starttime_of=world.__getitem__,
                                     enabled=True)
    dropped: list[int] = []
    tracker.add_invalidator("test", dropped.append)
    wall_s = 60.0
    n = int(round(wall_s / window_s))
    reused_windows = 0
    for i in range(n):
        if (i + 1) * window_s > 30.0 and world[7] == 100:
            world[7] = 101  # pid 7 recycled once, at wall t=30s
        if tracker.observe_window([7, 8]):
            reused_windows += 1
    # Per-window bookkeeping scales with the tick rate...
    assert tracker.stats["checks_total"] == 2 * n
    # ...but the EVENT counters count the one recycle at any cadence.
    assert reused_windows == 1
    assert tracker.stats["reuse_detected_total"] == 1
    assert tracker.stats["invalidations_total"] == 1
    assert dropped == [7]
