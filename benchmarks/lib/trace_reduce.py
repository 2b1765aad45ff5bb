"""From a profiler trace (``.xplane.pb``) to device numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU
trace has one plane per chip (``/device:TPU:<n>``) whose lines hold the
executed programs (``XLA Modules``) and their operations (``XLA Ops``),
each event with a start and a duration in nanoseconds. Busy time is the
union of the intervals in which an operation ran on the chip, averaged
over the chips; ``window_s`` here is the extent of all events of all
planes, host threads included (the harness replaces it with its own
clock's start-to-stop). A trace with no device plane (a CPU
rehearsal) reduces to ``None``: there is no device number in it.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def _union(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """(covered length, the merged intervals) of [start, end) pairs."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def _op(name: str) -> str:
    """An operation's event is named by its whole HLO line: keep the
    instruction's name, ``%fusion.16 = u32[...] fusion(...)`` ->
    ``%fusion.16``."""
    return name.split(" = ", 1)[0][:64]


def _program(name: str) -> str:
    """``jit_feed(1234567)`` -> ``jit_feed``: the program without the
    fingerprint XLA appends."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_planes(planes) -> dict | None:
    """``planes``: [(plane name, [(line name, [(event name, start_ns,
    duration_ns), ...]), ...]), ...]."""
    lo, hi = float("inf"), float("-inf")
    for _pn, lines in planes:
        for _ln, events in lines:
            for _n, s, d in events:
                lo, hi = min(lo, s), max(hi, s + d)
    devices = [(pn, dict(lines)) for pn, lines in planes
               if DEVICE_PLANE.match(pn)]
    if not devices or hi <= lo:
        return None
    busy = []
    ops: dict[str, float] = {}
    programs: dict[str, list] = {}
    gaps: list[tuple[float, str]] = []
    for _pn, lines in devices:
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        covered, merged = _union([(s, s + d) for _n, s, d in op_events])
        busy.append(covered)
        for n, _s, d in op_events:
            ops[_op(n)] = ops.get(_op(n), 0.0) + d
        mods = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        for n, _s, d in mods:
            rec = programs.setdefault(_program(n), [0, 0.0])
            rec[0] += 1
            rec[1] += d
        # A gap is named by the programs on either side of it: the
        # program's spans are not on the trace's clock, so what the host
        # did meanwhile is not known here.
        prev_end, prev_name = lo, "trace-start"
        for n, s, d in mods:
            if s > prev_end:
                gaps.append((s - prev_end,
                             f"after:{_program(prev_name)}"
                             f"-before:{_program(n)}"))
            if s + d > prev_end:
                prev_end, prev_name = s + d, n
        if hi > prev_end:
            gaps.append((hi - prev_end,
                         f"after:{_program(prev_name)}-before:trace-end"))
    by_gap: dict[str, float] = {}
    for length, name in gaps:
        by_gap[name] = max(by_gap.get(name, 0.0), length)
    return {
        "planes": [[pn, [[ln, len(ev)] for ln, ev in lines]]
                   for pn, lines in planes],
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "chips": len(devices),
        "programs": {n: {"calls": c, "seconds": t / 1e9}
                     for n, (c, t) in programs.items()},
        "device_ops": [[n, t / 1e9 / len(devices)] for n, t in
                       sorted(ops.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[n, t / 1e9] for n, t in
                      sorted(by_gap.items(), key=lambda kv: -kv[1])],
    }


def read_xplane(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(plane.name,
             [(line.name, [(ev.name, float(ev.start_ns),
                            float(ev.duration_ns)) for ev in line.events])
              for line in plane.lines])
            for plane in data.planes]


def newest_xplane(trace_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return found[-1] if found else None


def reduce_dir(trace_dir: str) -> dict | None:
    path = newest_xplane(trace_dir)
    return reduce_planes(read_xplane(path)) if path else None
