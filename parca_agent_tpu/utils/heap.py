"""Hold the heap: glibc's allocator told to keep what the agent frees.

Left to its defaults glibc moves its thresholds with what the process
frees (``M_MMAP_THRESHOLD`` follows the largest mmapped chunk freed, up
to 32 MiB, ``M_TRIM_THRESHOLD`` twice that) and gives free heap back to
the kernel past the trim threshold. A window here builds and drops tens
of megabytes of numpy arrays and, under churn, ~16 KB of per-pid
registry for every first-seen pid, which a reclaim then drops two
windows' worth at a time; what was given back is faulted in again by
the next windows, and where a page fault is dear (the chip tool's
sandboxed host) the same work then runs at one speed or at half of it,
for stretches of many windows. Measured there: ``miss_register`` read
~0.5 s or ~1.1 s for the same 9,216 new stacks, window after window,
and 0.50-0.71 s in every measured window of 13 runs with the heap held
(PERF.md section 6, PR 32). The repair that would make this module
unnecessary is a smaller registry (ROADMAP A13).

So the agent fixes three of glibc's settings for the whole process,
whatever the deployment: arrays under 32 MiB come from the heap (the
ceiling glibc itself gives that threshold once a process has freed a
chunk as large), the heap is not trimmed below 1 GiB of free top, and
it grows 64 MiB at a time. Memory the agent has once used stays with
it, as a long-running process's high-water mark mostly does anyway:
what that costs in resident memory is measured in PERF.md section 6
(PR 32). ``mallopt`` overrides ``MALLOC_*`` variables of the
environment; there is no switch.
"""

from __future__ import annotations

# <malloc.h>
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3
_SETTINGS = ((_M_MMAP_THRESHOLD, 32 << 20), (_M_TRIM_THRESHOLD, 1 << 30),
             (_M_TOP_PAD, 64 << 20))


def hold_heap() -> bool:
    """Apply the settings above; True when glibc took all of them,
    False where the C library has no ``mallopt``."""
    import ctypes

    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes, mallopt.restype = (ctypes.c_int, ctypes.c_int), \
        ctypes.c_int
    return all(mallopt(param, value) == 1 for param, value in _SETTINGS)
