"""Writer for the agent's window snapshot container (``--capture replay``).

The benchmark's own copy of the container ``capture/formats.py``
``save_snapshot`` writes: ``MAGIC | version u32 | zlib(payload)``, the
payload being the row columns and the mapping table, each array
length-prefixed. Deflate level 1 instead of the program's 6: the same
container, a fifth of the set-up time at the firehose's size (the program's
level is listed in PERF.md for a later PR).
"""

from __future__ import annotations

import os
import zlib

import numpy as np

_MAGIC = b"PATPSNAP"
_VERSION = 2
_LEVEL = 1


def _arr(a: np.ndarray, dtype) -> list[bytes]:
    data = np.ascontiguousarray(a, dtype=dtype)
    return [int(data.nbytes).to_bytes(8, "little"), memoryview(data).cast("B")]


def _strs(strs) -> list[bytes]:
    blob = b"\x00".join(s.encode() for s in strs)
    return [len(strs).to_bytes(8, "little"), len(blob).to_bytes(8, "little"),
            blob]


def snapshot_bytes(w) -> bytes:
    """Serialise one ``generate.Window``."""
    n, m = len(w.pids), len(w.map_pids)
    parts: list = [n.to_bytes(8, "little"), m.to_bytes(8, "little")]
    for v in (w.period_ns, w.window_ns, w.time_ns):
        parts.append(int(v).to_bytes(8, "little"))
    # tids are the pids: the sampled thread is the main thread.
    for a, dt in ((w.pids, np.int32), (w.pids, np.int32),
                  (w.counts, np.int64), (w.user_len, np.int32),
                  (w.kernel_len, np.int32), (w.stacks, np.uint64)):
        parts += _arr(a, dt)
    bases = w.map_starts - w.map_offsets
    for a, dt in ((w.map_pids, np.int32), (w.map_starts, np.uint64),
                  (w.map_ends, np.uint64), (w.map_offsets, np.uint64),
                  (w.map_objs, np.int32), (bases, np.uint64)):
        parts += _arr(a, dt)
    parts += _strs(w.obj_paths)
    parts += _strs(w.obj_buildids)
    z = zlib.compressobj(_LEVEL)
    out = [_MAGIC + _VERSION.to_bytes(4, "little")]
    for p in parts:
        out.append(z.compress(p))
    out.append(z.flush())
    return b"".join(out)


def write_snapshot(w, path: str) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(snapshot_bytes(w))
    os.replace(tmp, path)
