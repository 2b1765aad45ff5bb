"""Native (C++) runtime pieces shipped as source and built on demand.

`sampler.cc` is the perf_event ring drainer (role of the reference's
bpf/cpu/cpu.bpf.c capture program); capture/live.py compiles it with the
adjacent Makefile on first use and loads it via ctypes. `vecenc.cc` is
the varint emission kernel behind pprof/vec.py. `procstat.cc` is the
batch read of `/proc/<pid>/stat` behind process/identity.py. All share
the build-on-demand policy below; what differs per caller is only what a
build failure means (the sampler raises SamplerUnavailable, the varint
kernel falls back to its numpy path, the identity check to its Python
read loop).
"""

from __future__ import annotations

import os
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))


def ensure_built(target: str, source: str, force: bool = False) -> str:
    """Compile `target` (.so) from `source` (.cc) via the adjacent
    Makefile if missing or stale; returns the .so path.

    Shared objects are never checked in (gitignored): a fresh checkout
    always compiles from the reviewed source. Raises RuntimeError with
    the compiler output on failure — callers decide whether that is
    fatal (sampler) or a fallback trigger (varint kernel)."""
    lib = os.path.join(_DIR, target)
    src = os.path.join(_DIR, source)
    if force or not os.path.exists(lib) or \
            os.path.getmtime(lib) < os.path.getmtime(src):
        r = subprocess.run(["make", "-C", _DIR, target],
                           capture_output=True, text=True)
        if r.returncode != 0:
            raise RuntimeError(f"native build failed:\n{r.stderr}")
    return lib
