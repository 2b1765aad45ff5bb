"""The reference put in the program's place: a window's aggregate
written as the pprofs the agent would ship.

Used by the control of the output check (``control.py``) and by the
tests of the comparison; it imports nothing of the program. One mapping
per mapped object of the pid, locations normalised against their mapping
(address - (start - offset)), kernel locations unmapped, leaf-first
samples, the window's time, duration and period.
"""

import gzip

import numpy as np


def _put_varint(out, v):
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | 0x80 if v else b)
        if not v:
            return


def _field(out, num, wt, payload):
    _put_varint(out, (num << 3) | wt)
    if wt == 0:
        _put_varint(out, payload)
    else:
        _put_varint(out, len(payload))
        out.extend(payload)


def _msg(pairs):
    out = bytearray()
    for num, wt, payload in pairs:
        _field(out, num, wt, payload)
    return bytes(out)


def _packed(values):
    out = bytearray()
    for v in values:
        _put_varint(out, v)
    return bytes(out)


def make_pprof(w, pid, counts_by_stack) -> bytes:
    """One pid's gzipped pprof for window ``w``."""
    rows = np.flatnonzero(w.map_pids == pid)
    maps = [(int(w.map_starts[r]), int(w.map_ends[r]), int(w.map_offsets[r]))
            for r in rows]
    loc_ids: dict[int, int] = {}
    body = []
    for stack, count in counts_by_stack.items():
        ids = []
        for addr in stack:
            ids.append(loc_ids.setdefault(addr, len(loc_ids) + 1))
        body.append((2, 2, _msg([(1, 2, _packed(ids)),
                                 (2, 2, _packed([count]))])))
    for i, (start, end, off) in enumerate(maps, 1):
        body.append((3, 2, _msg([(1, 0, i), (2, 0, start), (3, 0, end),
                                 (4, 0, off)])))
    for addr, lid in loc_ids.items():
        mid = next((i for i, (s, e, _o) in enumerate(maps, 1)
                    if s <= addr < e), 0)
        norm = addr - (maps[mid - 1][0] - maps[mid - 1][2]) if mid else addr
        body.append((4, 2, _msg([(1, 0, lid), (2, 0, mid), (3, 0, norm)])))
    body.append((9, 0, w.time_ns))
    body.append((10, 0, w.window_ns))
    body.append((12, 0, w.period_ns))
    return gzip.compress(_msg(body), 1)
