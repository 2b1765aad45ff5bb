"""The plain reference: what one window aggregates to.

A numpy group-by of a generated window, ``(pid, stack bytes) -> count``,
written for the benchmark; it imports nothing of the program and takes
nothing the program made. Counts are int64 throughout.

``lower_precision`` is the control of the output check: the same
group-by with every count carried in ``bits`` bits, saturating, which is
what the close's packed fetch would give without its exact overflow
sideband. It must come out as not correct.
"""

from __future__ import annotations

import numpy as np


def pid_totals(w) -> dict[int, int]:
    """{pid: samples in the window}."""
    pids, inv = np.unique(w.pids, return_inverse=True)
    sums = np.zeros(len(pids), np.int64)
    np.add.at(sums, inv, w.counts.astype(np.int64))
    return dict(zip(pids.tolist(), sums.tolist()))


def group_by(w, pids) -> dict[int, dict[tuple, int]]:
    """{pid: {leaf-first stack of addresses: count}} for the given pids:
    rows with equal (pid, stack bytes) are one stack and their counts
    add. A row's stack is its user frames then its kernel frames."""
    want = np.asarray(sorted(pids), np.int32)
    rows = np.flatnonzero(np.isin(w.pids, want))
    key = np.zeros((len(rows), 1 + w.stacks.shape[1]), np.uint64)
    key[:, 0] = w.pids[rows].astype(np.uint64)
    key[:, 1:] = w.stacks[rows]
    void = np.ascontiguousarray(key).view(
        np.dtype((np.void, key.shape[1] * 8))).ravel()
    _uniq, first, inv = np.unique(void, return_index=True,
                                  return_inverse=True)
    sums = np.zeros(len(first), np.int64)
    np.add.at(sums, inv.reshape(-1), w.counts[rows].astype(np.int64))
    depth = (w.user_len[rows] + w.kernel_len[rows])[first]
    out: dict[int, dict[tuple, int]] = {int(p): {} for p in want}
    for j, r in enumerate(first):
        row = rows[r]
        stack = tuple(w.stacks[row, :int(depth[j])].tolist())
        out[int(w.pids[row])][stack] = int(sums[j])
    return out


def lower_precision(counts_by_stack: dict[tuple, int], bits: int) -> dict:
    """The control: each stack's count carried in ``bits`` bits."""
    cap = (1 << bits) - 1
    return {k: min(v, cap) for k, v in counts_by_stack.items()}
