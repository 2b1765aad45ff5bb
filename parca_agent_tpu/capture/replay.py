"""Replay source: feed saved WindowSnapshot fixtures through the agent.

The reference has no replay path — its aggregation can only be exercised
against live BPF maps (SURVEY.md section 4 closing note). ReplaySource is the
fixture seam that lets every downstream layer run kernel-free.

With ``drains`` > 1 a window does not arrive in one piece: ``poll()``
hands its rows to ``on_drain`` as that many columnar chunks over the
period, in the form of the native sampler's hash-carrying dedup drain
(capture/live.py ``decode_records_columnar_v1h``), and then returns the
window's snapshot. That is how the perf sampler's ``poll()`` delivers a
window, so the streaming feeder (profiler/streaming.py) runs from
fixtures on a machine where ``perf_event_open`` is refused.
"""

from __future__ import annotations

import os
import time
from typing import Iterator, Sequence

import numpy as np

from parca_agent_tpu.capture.formats import (
    MappingTable,
    WindowSnapshot,
    load_snapshot,
)
from parca_agent_tpu.ops.hashing import row_hash_np
from parca_agent_tpu.runtime import trace
from parca_agent_tpu.utils.log import get_logger

_log = get_logger("replay")


def drain_shares(counts: np.ndarray, drains: int) -> np.ndarray:
    """How a window's samples fall into its drains: ``[drains, N]``
    int64, ``shares[d, r]`` the samples of row ``r`` that drain ``d``
    carries. Sample ``j`` of the window, counted in row order, lands in
    drain ``j mod drains``: every drain carries its ``1 / drains`` of the
    mass to within one sample, a hot stack is in every drain and a stack
    sampled once is in one. Exact, and draws no random number."""
    counts = np.asarray(counts, np.int64)
    ends = np.cumsum(counts)
    starts = ends - counts
    d = np.arange(drains, dtype=np.int64)[:, None]

    def upto(x):
        # Samples j < x with j mod drains == d: ceil((x - d) / drains);
        # x - d + drains - 1 is never negative.
        return (x[None, :] - d + drains - 1) // drains

    return upto(ends) - upto(starts)


class ReplaySource:
    """Iterates snapshots from files or in-memory values.

    Implements the capture-source protocol: ``poll()`` returns the next
    window's snapshot or None when exhausted. With ``drains`` > 1 it
    also implements the protocol's streaming half, as the perf sampler
    does: ``on_drain``, once a consumer has set it, is called on the
    polling thread with each drain's columnar chunk while the window is
    open, and ``mapping_table(pids)`` answers with the mappings of a
    drain's pids. A source that hands its windows over in one piece has
    no ``on_drain``.
    """

    def __init__(self, items: Sequence[WindowSnapshot | str | os.PathLike],
                 drains: int = 1, period_s: float = 0.0):
        self._items = list(items)
        self._pos = 0
        self._drains = max(1, int(drains))
        self._period = max(0.0, float(period_s))
        self._open: WindowSnapshot | None = None
        if self._drains > 1:
            self.on_drain = None

    def poll(self) -> WindowSnapshot | None:
        if self._pos >= len(self._items):
            return None
        t0 = time.monotonic()
        item = self._items[self._pos]
        self._pos += 1
        snap = item if isinstance(item, WindowSnapshot) \
            else load_snapshot(item)
        if self._drains > 1:
            self._drain_window(snap, t0)
        return snap

    def _drain_window(self, snap: WindowSnapshot, t0: float) -> None:
        """The window's rows as ``drains`` chunks, ``period / drains``
        apart from ``t0`` on (the poll's start: the load of the file is
        inside the period), the last one at the end of the period."""
        k = self._drains
        self._open = snap
        # drain_chunk: what stands here for the sampler's own drain (the
        # split, the gather of a drain's rows, their hash), a stage of
        # its own beside the feeder's stream_feed.
        with trace.child("drain_chunk"):
            shares = drain_shares(snap.counts, k) \
                if self.on_drain is not None else None
        try:
            for d in range(k):
                # The wait for the drain's moment, on the device trace's
                # clock: the chip's idle time under it is headroom.
                with trace.waiting("sleep"):
                    time.sleep(max(0.0, t0 + self._period * (d + 1) / k
                                   - time.monotonic()))
                if self.on_drain is None:  # no consumer, or a tee dropped
                    continue
                try:
                    with trace.child("drain_chunk"):
                        chunk = self._chunk(snap, shares[d])
                    self.on_drain(chunk)
                except Exception as e:  # noqa: BLE001 - tee only
                    _log.warn("on_drain tee failed; disabling streaming "
                              "for this agent", error=repr(e))
                    self.on_drain = None
        finally:
            self._open = None

    @staticmethod
    def _chunk(snap: WindowSnapshot, share: np.ndarray) -> tuple:
        """One drain: the rows with a share, as the v1h drain's columns
        ``(pids, tids, ulen, klen, stacks, counts, h1, h2, h3)``. The
        triple is computed here, at drain time and once a drain for
        every row the drain holds, where the sampler pays for it (with
        the coefficients the native sampler installs: ops/hashing.py)."""
        rows = np.flatnonzero(share)
        cols = (snap.pids[rows], snap.tids[rows], snap.user_len[rows],
                snap.kernel_len[rows], snap.stacks[rows], share[rows])
        return cols + tuple(row_hash_np(cols[4], cols[0], cols[2], cols[3],
                                        n_hashes=3))

    def mapping_table(self, pids) -> MappingTable:
        """The open window's mappings of ``pids``: the rows of its own
        table (a fixture's pids are not processes of this machine)."""
        if self._open is None:
            return MappingTable.empty()
        t = self._open.mappings
        keep = np.isin(t.pids, np.asarray(pids, np.int32))
        return MappingTable(t.pids[keep], t.starts[keep], t.ends[keep],
                            t.offsets[keep], t.objs[keep], t.obj_paths,
                            t.obj_buildids, t.bases[keep])

    def __iter__(self) -> Iterator[WindowSnapshot]:
        while (snap := self.poll()) is not None:
            yield snap
