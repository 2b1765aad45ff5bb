"""The comparison that decides ``correct``.

For one window the agent shipped: every pid's profile arrived, every
pid's total equals the reference's, the shipped mass equals the window's
samples, every profile carries the window's time, and for a sample of
pids every stack's address tuple and count equal the reference's.
Equality is exact: every limit is 0.
"""

from __future__ import annotations

import numpy as np

from . import reference
from .pprof_read import read_profile

LIMITS = {"profiles_missing": 0, "profiles_unexpected": 0,
          "pid_total_mismatches": 0, "mass_gap": 0, "time_mismatches": 0,
          "stack_mismatches": 0}


def sample_pids(window, seed: int, n: int) -> list[int]:
    """``n`` of the window's pids, drawn from the seed."""
    pids = np.unique(window.pids)
    rng = np.random.default_rng([int(seed), int(window.index), 3])
    take = min(n, len(pids))
    return sorted(int(p) for p in rng.choice(pids, take, replace=False))


def observed_stacks(blobs: dict[int, bytes], pids) -> dict[int, dict]:
    return {p: read_profile(blobs[p]).stacks_by_raw_address()
            for p in pids if p in blobs}


def stack_mismatches(want: dict[int, dict], got: dict[int, dict]) -> int:
    """Stacks (over the sampled pids) that are missing, extra, or carry
    another count than the reference's."""
    bad = 0
    for pid, ref in want.items():
        obs = got.get(pid, {})
        bad += sum(1 for k, v in ref.items() if obs.get(k) != v)
        bad += sum(1 for k in obs if k not in ref)
    return bad


def compare_window(window, blobs: dict[int, bytes], seed: int,
                   n_sampled: int) -> dict[str, int]:
    """The numbers compared, for one shipped window. ``blobs`` is
    {pid: the pprof the sink holds for this window}."""
    want_totals = reference.pid_totals(window)
    missing = [p for p in want_totals if p not in blobs]
    unexpected = [p for p in blobs if p not in want_totals]
    got_totals = {}
    time_bad = 0
    for p, blob in blobs.items():
        prof = read_profile(blob, totals_only=True)
        got_totals[p] = prof.total()
        time_bad += prof.time_nanos != window.time_ns
    mismatched = sum(1 for p, t in want_totals.items()
                     if p in got_totals and got_totals[p] != t)
    sampled = sample_pids(window, seed, n_sampled)
    want = reference.group_by(window, sampled)
    got = observed_stacks(blobs, sampled)
    return {
        "profiles_missing": len(missing),
        "profiles_unexpected": len(unexpected),
        "pid_total_mismatches": mismatched,
        "mass_gap": abs(sum(got_totals.values()) - window.total_samples()),
        "time_mismatches": int(time_bad),
        "stack_mismatches": stack_mismatches(want, got),
    }


def verdict(numbers: dict[str, int]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
