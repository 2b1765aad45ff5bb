"""Generation-stamped process identity: detect pid reuse, invalidate
stale per-pid state.

Linux recycles pids; a profiler keyed on bare pid will hand a recycled
pid its dead predecessor's everything — mapping tables, perf-map and
unwind-table caches, tenant resolution, quarantine strikes, and (worst)
the aggregator's per-pid location registry, which silently attributes
the NEW process's samples to the OLD binary (the workload zoo's
pid-reuse scenario reproduces this end to end). The reference agent is
immune by construction: its BPF stack maps are keyed per-attach and
torn down with the process, so reuse can't alias (see the parity note
in docs/parity.md). A procfs sampler has no such teardown signal, so we
stamp identity the way the kernel does — ``(pid, starttime)``, where
starttime is field 22 of ``/proc/<pid>/stat`` (clock ticks since boot
at fork, unique per pid incarnation).

The tracker observes each window's pid set once per window-loop
iteration (profiler/cpu.py run_iteration, BEFORE admission accounting
and aggregation), remembers each pid's starttime, and on a mismatch
fires registered invalidators — aggregator.invalidate_pid,
quarantine.forget_pid, resolver.forget, map/perf/unwind cache evicts —
so every layer drops the dead generation's state before the new
generation's first sample resolves. Everything is fail-open: an
unreadable stat, a raising invalidator, or an injected fault
(``process.identity``) is counted and the window proceeds unhardened
rather than lost.

The check is made once per DISTINCT pid, in bulk. The profiler hands
over the per-row pid column (262,144 rows at firehose size); it is
reduced with ``np.unique``, so no Python statement runs per row. With
the procfs reader, one ``listdir("/proc")`` a window then says which of
the window's pids exist: a pid that is not listed takes the "exited
mid-window" branch (an error counted, the remembered generation kept,
``absent_total``) without an open, and only a listed pid gets its
bounded stat read. The table and the counters are updated under one
lock acquisition a window, and ``reused`` comes back in ascending pid
order. The three steps are three spans under the profiler's
``identity`` (``trace.child``, wall clock only): ``identity_list`` (the
listing and the ``isin``), ``identity_read`` (the reads),
``identity_settle`` (the table update under the lock and the
invalidators); what is left of ``identity`` is the ``np.unique``.

Why a pid absent from the listing needs no read: a recycled pid's stale
state must be invalidated before any sample of the NEW generation
resolves. The samples in hand were drained before ``observe_window``
runs, so they belong to whatever held the pid during the window. A pid
missing from the listing had exited by then; if it is forked again
between the listing and the read the per-pid code would have made, its
first samples can only arrive with the NEXT drain, when it is listed,
read, and found to differ from the remembered generation. The evidence
stays ``(pid, starttime)`` for every live pid in every window: no
watermark on ``ns_last_pid``, no cache of "checked recently", no
sampling of pids — those would be a weaker check, not a faster one.
What the check costs was measured where it runs (the chip tool's host,
gVisor, 400 live pids; PERF.md sections 5 and 6): a read through
``read_starttime`` is a buffered ``open``, about seven system calls,
each of which drops and retakes the GIL: ~88 us a pid inside the agent,
~35 ms of a window, the largest stage between a node window's last
sample and its pprof bytes. So the reads of a window are ONE call of
``native/procstat.cc`` (``pa_read_starttimes``, through ctypes, which
releases the GIL round it): per pid ``open``, ``read``, ``close`` and
the parse of field 22 in C, ~48 us a pid there (~19 ms of a window). It reads what the loop
read (every listed pid, every window: ``identity_stat_reads`` on the
window's ``meta``; ``identity_native_reads`` says how many of them the
call read), by ``read_starttime``'s rule, and that function stays the
reference its tests hold it to. A cheaper check is a cheaper READ;
the number of pids read stays where it is. When the call is made is
decided by what ``_starttimes`` can observe, not by a switch: the
reader is the procfs default, the filesystem is the host's (``RealFS``
itself: a fake or a test's subclass is a world of its own and is asked
pid by pid), the library loads (a host with no compiler runs the loop,
with one warning) and there is a pid to read. A call that reports
failure hands that window to the loop
(``parca_agent_pid_identity_native_fallbacks_total``). A
listing that fails falls back to a read per pid (fail-open, same
result), and an injected ``starttime_of`` is the world: it is asked for
every distinct pid and no listing is made. (``/proc/<n>/stat`` opens
for a number that is only a THREAD id although ``/proc`` does not list
it; such a number is settled as absent. The capture's pid column holds
thread-group ids, listed while any thread lives.)

``PARCA_NO_PID_GENERATION=1`` pins the hardening off — the bench zoo's
misattribution control arm.
"""

from __future__ import annotations

import ctypes
import functools
import os
import threading
from typing import Callable, Iterable

import numpy as np

from parca_agent_tpu.runtime import trace
from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.log import get_logger
from parca_agent_tpu.utils.poison import read_bounded
from parca_agent_tpu.utils.vfs import VFS, RealFS

# /proc/<pid>/stat is one short line; anything larger is not procfs.
_STAT_CAP = 1 << 16
# Bound on remembered generations: entries for pids absent from the
# current window are trimmed once the table grows past this (a dead,
# never-reused pid must not leak memory forever).
_MAX_TRACKED = 1 << 20


# native/procstat.cc, loaded on the first window that has a pid of the
# host's /proc to read. False: not tried yet; None: cannot be built or
# loaded here, and every window runs the Python loop.
_native: ctypes.CDLL | None | bool = False


def _load_native() -> ctypes.CDLL | None:
    global _native
    if _native is False:
        _native = None
        try:
            from parca_agent_tpu.native import ensure_built

            lib = ctypes.CDLL(
                ensure_built("libpaprocstat.so", "procstat.cc"))
            lib.pa_read_starttimes.restype = ctypes.c_int64
            lib.pa_read_starttimes.argtypes = [
                ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p]
            _native = lib
        except Exception as e:  # noqa: BLE001 - fallback is the loop
            # One warning, not silence: the loop costs nearly twice the
            # native call a live pid (PERF.md section 5), and a host
            # missing g++ would otherwise regress invisibly.
            get_logger("process.identity").warn(
                "native stat reader unavailable; reading each pid's stat "
                "from Python", error=repr(e))
    return _native


def native_starttimes(lib: ctypes.CDLL, pids: np.ndarray,
                      root: str = "/proc") -> np.ndarray | None:
    """One call of native/procstat.cc over ``pids``: per pid the
    starttime ``read_starttime`` gives for ``<root>/<pid>/stat``, or a
    negative code where it raises (-1 absent or unreadable, -2 over
    ``_STAT_CAP`` bytes, -3 garbled). ctypes releases the GIL round the
    call. None when the call reports that it could not run. ``root`` is
    a parameter so that tests can hold the call to ``read_starttime``
    over a tree of files."""
    pids = np.ascontiguousarray(pids, np.int64)
    out = np.empty(len(pids), np.int64)
    rc = lib.pa_read_starttimes(os.fsencode(root), pids.ctypes.data,
                                len(pids), _STAT_CAP, out.ctypes.data)
    return out if rc >= 0 else None


def read_starttime(fs: VFS, pid: int) -> int:
    """Starttime (field 22 of /proc/<pid>/stat) in clock ticks since
    boot. Raises on unreadable/absent/garbled stat — callers own the
    fail-open. Parsed after the last ``)`` (comm may embed spaces and
    parens), same as capture/procfs.py's cpu-tick read: field N of the
    stat line is index N-3 of the post-comm split."""
    data = read_bounded(fs, f"/proc/{int(pid)}/stat", _STAT_CAP,
                        site="process.identity")
    rp = data.rfind(b")")
    if rp < 0:
        raise ValueError(f"garbled stat for pid {pid}")
    fields = data[rp + 1:].split()
    return int(fields[19])


class ProcessIdentityTracker:
    """Per-window pid-generation check with pluggable invalidation.

    ``starttime_of`` defaults to the procfs reader (one listing of
    ``/proc`` a window, then a bounded stat read per listed pid); tests
    and the bench zoo inject a callable backed by their scenario's
    world state, which is asked for every distinct pid.
    Invalidators are ``(name, fn(pid))`` pairs registered by the wiring
    layer (cli.py / the zoo runner); each fires under its own guard so
    one raising layer never blocks the others from dropping stale
    state."""

    def __init__(self, starttime_of: Callable[[int], int] | None = None,
                 fs: VFS | None = None, enabled: bool | None = None):
        self._fs = fs if fs is not None else RealFS()
        # None: procfs, asked which pids exist before any is opened.
        self._start_of = starttime_of
        if enabled is None:
            enabled = os.environ.get("PARCA_NO_PID_GENERATION", "") != "1"
        self.enabled = enabled
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._gens: dict[int, int] = {}   # pid -> last observed starttime
        self._invalidators: list[tuple[str, Callable[[int], None]]] = []
        # guarded-by: _lock
        self.stats = {
            "checks_total": 0,
            "reuse_detected_total": 0,
            "invalidations_total": 0,
            "invalidation_errors_total": 0,
            "errors_total": 0,
            "absent_total": 0,
            "trims_total": 0,
        }
        # guarded-by: _lock — the native reader's counters: pids it read
        # and parsed, windows it handed back to the loop.
        self.native_stats = {"reads_total": 0, "fallbacks_total": 0}
        # guarded-by: _lock — last detected reuse, for /healthz.
        self._last_reuse: dict | None = None

    def add_invalidator(self, name: str,
                        fn: Callable[[int], None]) -> None:
        with self._lock:
            self._invalidators.append((name, fn))

    def forget(self, pid: int) -> None:
        """Drop a pid's remembered generation (process exit observed by
        a layer with better signal, e.g. cache eviction sweeps)."""
        with self._lock:
            self._gens.pop(int(pid), None)

    # palint: fail-open
    def observe_window(self, pids: Iterable[int]) -> list[int]:
        """Check every distinct pid in this window's capture against
        its remembered starttime; fire invalidators for recycled pids.
        ``pids`` may be the per-row column: it is reduced as an array,
        nothing here runs once per row. Returns the reused pids in
        ascending order. Fail-open end to end: any error — including
        the injected ``process.identity`` fault — is counted and the
        window proceeds with whatever hardening landed."""
        reused: list[int] = []
        try:
            if not self.enabled:
                return reused
            faults.inject("process.identity")
            arr = (pids if isinstance(pids, np.ndarray)
                   else np.fromiter(pids, np.int64))
            distinct = np.unique(arr)
            # Kernel pseudo-pids have no /proc identity.
            distinct = distinct[distinct >= 0]
            checked, starts, n_reads, n_absent, n_native, fell_back = \
                self._starttimes(distinct)
            with trace.child("identity_settle"):
                with self._lock:
                    prevs = list(map(self._gens.get, checked))
                    self._gens.update(zip(checked, starts))
                    hits = [(pid, prev, start) for pid, prev, start
                            in zip(checked, prevs, starts)
                            if prev is not None and prev != start]
                    reused = [pid for pid, _prev, _start in hits]
                    if hits:
                        pid, prev, start = hits[-1]
                        self._last_reuse = {
                            "pid": pid, "old_starttime": prev,
                            "new_starttime": start}
                    st = self.stats
                    st["checks_total"] += len(checked)
                    # Exited mid-window (or unreadable): the remembered
                    # generation is kept — if the pid comes back it is
                    # BY DEFINITION a new incarnation and the stale
                    # entry is what lets us detect it.
                    st["errors_total"] += len(distinct) - len(checked)
                    st["absent_total"] += n_absent
                    self.native_stats["reads_total"] += n_native
                    self.native_stats["fallbacks_total"] += fell_back
                    st["reuse_detected_total"] += len(reused)
                    self._trim(distinct)
                    hooks = list(self._invalidators) if reused else ()
                trace.count(identity_pids=len(distinct),
                            identity_stat_reads=n_reads,
                            identity_absent=n_absent,
                            identity_native_reads=n_native)
                if hooks:
                    self._invalidate(reused, hooks)
        except Exception:
            with self._lock:
                self.stats["errors_total"] += 1
        return reused

    def _starttimes(self, distinct: np.ndarray
                    ) -> tuple[list[int], list[int], int, int, int, int]:
        """(pids whose starttime was read, their starttimes, reads
        attempted, pids settled absent by the listing with no read, pids
        the native call read and parsed, 1 if the native call was made
        and reported failure)."""
        start_of = self._start_of
        n_absent = 0
        lib = None
        if start_of is None:
            start_of = functools.partial(read_starttime, self._fs)
            with trace.child("identity_list"):
                # palint: fail-open
                try:
                    live = np.fromiter(
                        (int(n) for n in self._fs.listdir("/proc")
                         if n.isdigit()), np.int64)
                    listed = distinct[np.isin(distinct, live)]
                    n_absent = len(distinct) - len(listed)
                    distinct = listed
                except Exception:
                    pass  # no listing: a read per pid, the same result
            # The native call reads the host's /proc and nothing else: an
            # injected reader, a fake filesystem or a test's subclass is
            # the world and is asked pid by pid, below.
            if type(self._fs) is RealFS and len(distinct):
                lib = _load_native()
        fell_back = 0
        with trace.child("identity_read"):
            if lib is not None:
                out = native_starttimes(lib, distinct)
                if out is not None:
                    ok = out >= 0
                    checked = distinct[ok].tolist()
                    return (checked, out[ok].tolist(), len(distinct),
                            n_absent, len(checked), 0)
                fell_back = 1  # fail-open: this window by the loop
            checked: list[int] = []
            starts: list[int] = []
            for pid in distinct.tolist():
                try:
                    start = int(start_of(pid))
                except Exception:
                    continue
                checked.append(pid)
                starts.append(start)
        return checked, starts, len(distinct), n_absent, 0, fell_back

    def _invalidate(self, reused: list[int], hooks) -> None:
        fired = failed = 0
        for pid in reused:
            for _name, fn in hooks:
                # palint: fail-open
                try:
                    fn(pid)
                    fired += 1
                except Exception:
                    failed += 1
        with self._lock:
            self.stats["invalidations_total"] += fired
            self.stats["invalidation_errors_total"] += failed

    def _trim(self, distinct: np.ndarray) -> None:  # palint: holds=_lock
        """Bound the generation table: past _MAX_TRACKED, keep only the
        pids seen in the current window. Called with the lock held — the
        table swap must not interleave with a concurrent forget."""
        if len(self._gens) <= max(_MAX_TRACKED, 4 * len(distinct)):
            return
        live = set(distinct.tolist())
        self._gens = {p: s for p, s in self._gens.items() if p in live}
        self.stats["trims_total"] += 1

    def metrics(self) -> dict:
        with self._lock:
            return dict(self.stats)

    def native_metrics(self) -> dict:
        with self._lock:
            return dict(self.native_stats)

    def snapshot(self) -> dict:
        """Observability view for /healthz (never turns readiness red)."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "tracked_pids": len(self._gens),
                "invalidators": [n for n, _ in self._invalidators],
                "last_reuse": dict(self._last_reuse)
                               if self._last_reuse else None,
                "stats": dict(self.stats),
                "native": dict(self.native_stats),
            }
