"""Whose time the chip's idle time is: the share of the device's idle
intervals (between its first and its last operation of the trace) that
lies under the program's ``pa/<stage>`` annotation on the capture
thread, percent. The program writes those annotations into the same
``.xplane.pb`` as the device's operations (``runtime/trace.py``), so
both are on one clock; the capture thread is the line that holds
``pa/sleep``, the profiler loop's wait for its next period. With
``unattributed_by`` the share under none of the listed stages.

Reads the raw trace the harness keeps (``chiprun_out/bench/<cell>-<seed>
.xplane.pb``, the newest of the cell). A program without annotations
(the parent of the PR that added them) gives nothing."""

import functools
import glob
import os

from .. import trace_reduce
from ..cell import CHECKOUT

PREFIX, CAPTURE_MARK = "pa/", "pa/sleep"


def _overlap(a: list, b: list) -> float:
    """Total length of the intersection of two lists of disjoint,
    sorted [start, end) intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_shares(planes) -> dict[str, float] | None:
    """{stage: percent of the device's idle time under ``pa/<stage>`` of
    the capture thread}, from planes as ``trace_reduce.read_xplane``
    returns them; None without a device plane, idle time, or a capture
    thread."""
    idle: list[tuple[float, float]] = []
    lines = []
    for pn, plane_lines in planes:
        if trace_reduce.DEVICE_PLANE.match(pn):
            by = dict(plane_lines)
            ops = by.get(trace_reduce.OPS_LINE) \
                or by.get(trace_reduce.MODULES_LINE) or []
            _busy, merged = trace_reduce._union(
                [(s, s + d) for _n, s, d in ops])
            idle += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        else:
            lines += [ev for _ln, ev in plane_lines]
    capture = max((ev for ev in lines
                   if any(n == CAPTURE_MARK for n, _s, _d in ev)),
                  key=len, default=None)
    idle.sort()
    total = sum(b - a for a, b in idle)
    if capture is None or total <= 0:
        return None
    by_stage: dict[str, list] = {}
    for n, s, d in capture:
        if n.startswith(PREFIX):
            by_stage.setdefault(n[len(PREFIX):], []).append((s, s + d))
    return {stage: 100.0 * _overlap(idle, trace_reduce._union(iv)[1]) / total
            for stage, iv in by_stage.items()}


@functools.lru_cache(maxsize=2)
def _shares_of(path: str, _mtime: float):
    return idle_shares(trace_reduce.read_xplane(path))


def read(ctx, stage: str | None = None,
         unattributed_by: list[str] | None = None):
    found = sorted(glob.glob(os.path.join(
        CHECKOUT, "chiprun_out", "bench", f"{ctx.cell.name}-*.xplane.pb")),
        key=os.path.getmtime)
    if not ctx.trace or not found:
        return None
    shares = _shares_of(found[-1], os.path.getmtime(found[-1]))
    if shares is None:
        return None
    if unattributed_by is not None:
        return 100.0 - sum(shares.get(s, 0.0) for s in unattributed_by)
    return shares.get(stage, 0.0)
