"""Device-side flight recorder: kernel, compile, and transfer truth.

PR 7's window flight recorder (runtime/trace.py) explains the agent's
host-side tail — but the hardware arc (ROADMAP item 1) is blind exactly
where its truth lives: kernel dispatch cost is folded into whatever
stage span happens to contain it, the first call's XLA compile (seconds)
is indistinguishable from steady-state execution (microseconds), a
demotion to the CPU fallback latches silently behind a one-shot log
line, and nothing accounts the H2D/D2H bytes each kernel moves. This
module is the device-side twin: a process-global
:class:`DeviceTelemetry` registry that every kernel dispatch site
reports into —

  * per-kernel streaming latency histograms discriminating
    ``event=compile|execute`` via a shape-signature first-call latch
    (the first observation of a new signature on a kernel IS the call
    that paid tracing+compilation; JAX caches by shape, so a signature
    seen before executes from cache);
  * a recompile-storm detector: a NEW signature on a previously-latched
    kernel increments a counter and routes a rate-limited incident
    through PR 7's incident machinery (``FlightRecorder.capture_event``)
    — a workload whose shapes churn recompiles forever, and that must
    be an incident, not a vibe;
  * H2D/D2H transfer-byte accounting per kernel, derived from the
    packed buffer sizes the sites already compute — no extra syncs;
  * a latched backend-identity record (platform, device_kind, device
    count, jax / jaxlib / libtpu versions) that the device's OWNER
    learns once and hands in (:func:`collect_identity`,
    :meth:`DeviceTelemetry.set_identity`) — reading it never
    initialises a backend — plus the device/cpu_fallback resolution
    the device-health registry latches, exported as info-style gauges
    so a node that landed on the wrong platform or runs its windows on
    the CPU fallback is visible from /metrics, not just logs;
  * a window-SLO layer rolling capture-thread busy time plus off-thread
    kernel seconds into a per-window budget-used ratio and a
    windows-over-budget burn counter keyed to the configured period —
    the instrument the sub-second-window work is measured against.

Reporting sites (aggregator/{dict,sharded}.py) call the module-level
hooks (:func:`record`, :func:`transfer`, :func:`note_backend`,
:func:`tick_window`) — the faults.py pattern: one module-attribute read
when telemetry is off. Several sites sit on the CAPTURE PATH (palint's
host-sync walk reaches them), so every hook is observation-only: wall
clocks and byte counts already on the host, never a device sync.

Fail-open discipline mirrors trace.py exactly: every entry point is
annotated ``# palint: fail-open``, swallows its own errors into
``stats["record_errors"]``, and carries the ``device.telemetry`` chaos
site — telemetry must never cost a window or change a pprof byte
(docs/observability.md "device flight recorder").
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque

from parca_agent_tpu.runtime import trace as trace_mod
from parca_agent_tpu.runtime.trace import StageHistogram
from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.log import get_logger

_log = get_logger("device_telemetry")

# The kernel names the dispatch sites report under (the registry is
# dynamic — these are documentation, not a closed set):
#   feed_probe   dict feed probe dispatch (aggregator/dict.py)
#   miss_settle  vectorized miss plan-then-commit (aggregator/dict.py)
#   close_pack   full close pack dispatch (aggregator/dict.py)
#   close_delta  delta close pack dispatch (aggregator/dict.py)
#   close_fetch  the packed close D2H collect (aggregator/dict.py)
#   shard_put    per-device sharded feed puts (aggregator/sharded.py)
EVENTS = ("compile", "execute")


def _dist_version(name: str) -> str:
    """Installed distribution version without importing the package."""
    from importlib import metadata

    try:
        return metadata.version(name)
    except metadata.PackageNotFoundError:
        return "none"


def backend_initialized() -> bool:
    """Whether THIS process already holds an initialised JAX backend.
    Reads only: jax is never imported here and no backend is created —
    a process that has not touched JAX answers False."""
    import sys

    xb = sys.modules.get("jax._src.xla_bridge")
    return bool(xb is not None and xb.backends_are_initialized())


def collect_identity() -> dict:
    """The backend-identity record: platform, device kind and count as
    JAX reports them, plus the installed versions. DELIBERATE: calling
    this initialises the JAX backend if nothing has yet (and on a TPU
    host takes the chip), so it belongs to whoever owns the device —
    the device-health claim after bring-up, a bench child — never to a
    scrape. A backend that fails to initialise raises: an identity of
    "unknown" would hide exactly the landing this record exists to
    show."""
    import socket

    import jax

    devs = jax.devices()
    platform = str(devs[0].platform)
    return {
        "platform": platform,
        "device_kind": str(devs[0].device_kind),
        "device_count": len(devs),
        "jax_version": str(jax.__version__),
        "jaxlib_version": _dist_version("jaxlib"),
        "libtpu_version": _dist_version("libtpu"),
        "hostname": socket.gethostname(),
    }


class DeviceTelemetry:
    """Process-global device flight recorder (one per agent, installed
    via :func:`install`). Thread-safe; every write path is fail-open."""

    def __init__(self, period_s: float = 0.0, ring: int = 256,
                 incident_interval_s: float = 300.0,
                 clock=time.monotonic):
        self._lock = threading.Lock()
        self._clock = clock
        self._t0 = clock()
        self.period_s = float(period_s)
        self._incident_interval = incident_interval_s
        self._hists: dict[tuple[str, str], StageHistogram] = {}  # guarded-by: _lock
        self._shapes: dict[str, set] = {}  # guarded-by: _lock
        self._transfers: dict[tuple[str, str], list[int]] = {}  # guarded-by: _lock
        self._backends: dict[str, dict] = {}  # guarded-by: _lock
        self._identity: dict | None = None  # guarded-by: _lock
        self._budget_hist = StageHistogram()  # guarded-by: _lock
        self._events = deque(maxlen=max(16, ring))  # guarded-by: _lock
        self._windows = deque(maxlen=max(16, ring))  # guarded-by: _lock
        self._win_kernel_s: dict[int, float] = {}  # guarded-by: _lock
        self._last_recompile_at: float | None = None  # guarded-by: _lock
        self.stats = {  # guarded-by: _lock
            "record_errors": 0,
            "events_total": 0,
            "compiles_total": 0,
            "recompiles_total": 0,
            "recompile_incidents": 0,
            "recompile_incidents_suppressed": 0,
        }
        self.window_stats = {  # guarded-by: _lock
            "windows_total": 0,
            "windows_over_budget_total": 0,
            "budget_used_last": 0.0,
        }
        # What XLA itself reports (watch_xla_compiles): the host-clock
        # "compile" events above time a kernel's first call per shape,
        # which for miss_settle is mostly the host-side insert; these
        # are the compiler's own seconds and the persistent cache's
        # hit/miss counts (runtime/compile_cache.py).
        self.xla = {  # guarded-by: _lock
            "compile_requests_total": 0,
            "cache_hits_total": 0,
            "cache_misses_total": 0,
            "backend_compile_seconds_total": 0.0,
        }

    # -- write side (dispatch sites; capture path) ---------------------------

    # palint: fail-open
    def record(self, kernel: str, duration_s: float, shape=None,
               h2d_bytes: int = 0, d2h_bytes: int = 0) -> None:
        """Record one kernel observation: latency histogram keyed
        (kernel, event), shape-signature compile latch, transfer bytes,
        per-window kernel-seconds, and the bounded event timeline.
        ``shape`` is the site's compiled-program signature (its jit
        cache key, or the padded shape class for eager dispatches);
        None records an execute event with no latch. Fail-open."""
        try:
            faults.inject("device.telemetry")
            storm = None
            with self._lock:
                event = "execute"
                if shape is not None:
                    seen = self._shapes.get(kernel)
                    if seen is None:
                        seen = self._shapes[kernel] = set()
                    if shape not in seen:
                        event = "compile"
                        self.stats["compiles_total"] += 1
                        if seen:
                            self.stats["recompiles_total"] += 1
                            storm = (kernel, shape, len(seen) + 1)
                        seen.add(shape)
                self._hists.setdefault(
                    (kernel, event), StageHistogram()).observe(duration_s)
                self.stats["events_total"] += 1
                if h2d_bytes:
                    t = self._transfers.setdefault((kernel, "h2d"), [0, 0])
                    t[0] += int(h2d_bytes)
                    t[1] += 1
                if d2h_bytes:
                    t = self._transfers.setdefault((kernel, "d2h"), [0, 0])
                    t[0] += int(d2h_bytes)
                    t[1] += 1
                tid = threading.get_ident()
                self._win_kernel_s[tid] = \
                    self._win_kernel_s.get(tid, 0.0) + duration_s
                self._events.append({
                    "t_s": round(self._clock() - self._t0, 6),
                    "kernel": kernel,
                    "event": event,
                    "duration_s": round(duration_s, 6),
                    "h2d_bytes": int(h2d_bytes),
                    "d2h_bytes": int(d2h_bytes),
                    "shape": repr(shape) if shape is not None else None,
                })
            if storm is not None:
                self._recompile_incident(*storm)
        except Exception as e:  # noqa: BLE001 - telemetry is fail-open
            self._record_error(e)

    # palint: fail-open
    def record_transfer(self, kernel: str, direction: str,
                        nbytes: int) -> None:
        """Account a transfer with no latency observation (eager device
        writes whose dispatch rides another kernel's clock). Fail-open."""
        try:
            faults.inject("device.telemetry")
            with self._lock:
                t = self._transfers.setdefault((kernel, direction), [0, 0])
                t[0] += int(nbytes)
                t[1] += 1
        except Exception as e:  # noqa: BLE001 - telemetry is fail-open
            self._record_error(e)

    # palint: fail-open
    def note_backend(self, kernel: str, requested: str | None = None,
                     resolved: str | None = None,
                     fallback: bool | None = None) -> None:
        """Latch one kernel's backend resolution (requested vs resolved
        backend, fallback one-hot). Fields are
        sticky per call — last write wins, None leaves a field alone.
        Fail-open."""
        try:
            faults.inject("device.telemetry")
            with self._lock:
                rec = self._backends.setdefault(kernel, {
                    "requested": None, "resolved": None,
                    "fallback": False})
                if requested is not None:
                    rec["requested"] = requested
                if resolved is not None:
                    rec["resolved"] = resolved
                if fallback is not None:
                    rec["fallback"] = bool(fallback)
        except Exception as e:  # noqa: BLE001 - telemetry is fail-open
            self._record_error(e)

    # palint: fail-open
    def tick_window(self, used_s: float) -> None:
        """Roll one window into the SLO layer. ``used_s`` is the capture
        thread's busy wall for the window; kernel seconds recorded from
        OTHER threads this window (streaming feed tees, encode-side
        fetches) are added on top — same-thread kernel time is already
        inside ``used_s``. Judged against the configured period; a
        period of 0 (tests, bench micro-phases) counts windows without
        a budget. Fail-open."""
        try:
            faults.inject("device.telemetry")
            with self._lock:
                me = threading.get_ident()
                other = sum(s for tid, s in self._win_kernel_s.items()
                            if tid != me)
                kernel_s = sum(self._win_kernel_s.values())
                self._win_kernel_s.clear()
                used = float(used_s) + other
                self.window_stats["windows_total"] += 1
                entry = {
                    "seq": self.window_stats["windows_total"],
                    "used_s": round(used, 6),
                    "kernel_s": round(kernel_s, 6),
                    "period_s": self.period_s,
                }
                if self.period_s > 0:
                    ratio = used / self.period_s
                    self.window_stats["budget_used_last"] = ratio
                    self._budget_hist.observe(ratio)
                    over = ratio > 1.0
                    if over:
                        self.window_stats["windows_over_budget_total"] += 1
                    entry["ratio"] = round(ratio, 6)
                    entry["over"] = over
                self._windows.append(entry)
        except Exception as e:  # noqa: BLE001 - telemetry is fail-open
            self._record_error(e)

    # palint: fail-open
    def note_xla(self, key: str, amount: float = 1) -> None:
        """One XLA compile/cache event (watch_xla_compiles). Fail-open."""
        try:
            with self._lock:
                self.xla[key] += amount
        except Exception as e:  # noqa: BLE001 - telemetry is fail-open
            self._record_error(e)

    # palint: fail-open
    def set_identity(self, ident: dict) -> None:
        """Latch the backend-identity record the device owner learned
        (:func:`collect_identity`). First write wins. Fail-open."""
        try:
            with self._lock:
                if self._identity is None:
                    self._identity = dict(ident)
        except Exception as e:  # noqa: BLE001 - telemetry is fail-open
            self._record_error(e)

    # palint: fail-open
    def ensure_identity(self) -> dict:
        """The latched backend-identity record, or ``{}`` while nobody
        has learned it. PASSIVE — safe on the HTTP thread: it never
        initialises a backend (a scrape landing while a bring-up probe
        child holds the chip must not pin this process to the CPU for
        life) and never latches a placeholder. A process that already
        runs on an initialised backend (bench children, tests, library
        embedders) latches from it on first read, since reading an
        existing backend creates nothing. Fail-open: ``{}`` on error."""
        try:
            with self._lock:
                if self._identity is not None:
                    return dict(self._identity)
            if not backend_initialized():
                return {}
            self.set_identity(collect_identity())
            with self._lock:
                return dict(self._identity or {})
        except Exception as e:  # noqa: BLE001 - telemetry is fail-open
            self._record_error(e)
            return {}

    def _recompile_incident(self, kernel: str, shape, n_shapes: int) -> None:
        """Rate-limited recompile-storm incident routed through the
        window flight recorder's machinery (called inside record()'s
        fail-open guard — its own errors are counted there)."""
        with self._lock:
            now = self._clock()
            if (self._last_recompile_at is not None
                    and now - self._last_recompile_at
                    < self._incident_interval):
                self.stats["recompile_incidents_suppressed"] += 1
                return
            self._last_recompile_at = now
            recompiles = self.stats["recompiles_total"]
        rec = trace_mod.get()
        captured = rec is not None and rec.capture_event(
            "recompile_storm", stage="recompile",
            detail={
                "kernel": kernel,
                "shape": repr(shape),
                "shapes_latched": n_shapes,
                "recompiles_total": recompiles,
                "kernel_percentiles": self.percentiles(),
                "backends": self.backends(),
            })
        with self._lock:
            if captured:
                self.stats["recompile_incidents"] += 1
            else:
                self.stats["recompile_incidents_suppressed"] += 1
        _log.warn("kernel recompile detected", kernel=kernel,
                  shape=repr(shape)[:120], shapes_latched=n_shapes,
                  incident=captured)

    def _record_error(self, e: Exception) -> None:
        try:
            with self._lock:
                self.stats["record_errors"] += 1
            _log.debug("device telemetry recording failed (fail-open)",
                       error=repr(e))
        except Exception:  # noqa: BLE001 - never escalate from here
            pass

    # -- read side (HTTP thread, bench, incident bundles) --------------------

    def export_kernel_histograms(self) -> list[tuple[str, str, dict]]:
        """[(kernel, event, StageHistogram.export())] for /metrics."""
        with self._lock:
            return [(k, e, h.export())
                    for (k, e), h in sorted(self._hists.items())]

    def transfers(self) -> list[tuple[str, str, int, int]]:
        """[(kernel, direction, bytes_total, ops_total)] for /metrics."""
        with self._lock:
            return [(k, d, t[0], t[1])
                    for (k, d), t in sorted(self._transfers.items())]

    def backends(self) -> dict[str, dict]:
        """{kernel: {requested, resolved, fallback}}."""
        with self._lock:
            return {k: dict(v) for k, v in sorted(self._backends.items())}

    def percentiles(self) -> dict[str, dict]:
        """{kernel: {event: {p50_ms, p99_ms, max_ms, count}}} — the
        compact per-kernel stamp (bench JSON, incident files)."""
        out: dict[str, dict] = {}
        with self._lock:
            for (kernel, event), h in sorted(self._hists.items()):
                out.setdefault(kernel, {})[event] = {
                    "p50_ms": round(h.quantile(0.50) * 1e3, 4),
                    "p99_ms": round(h.quantile(0.99) * 1e3, 4),
                    "max_ms": round(h.max_s * 1e3, 4),
                    "count": h.count,
                }
        return out

    def shape_counts(self) -> dict[str, int]:
        """{kernel: latched shape signatures} (recompiles = count - 1)."""
        with self._lock:
            return {k: len(v) for k, v in sorted(self._shapes.items())}

    def budget_export(self) -> dict:
        """The window-SLO block: ratio histogram + burn counters."""
        with self._lock:
            return {
                "period_s": self.period_s,
                "hist": self._budget_hist.export(),
                **dict(self.window_stats),
            }

    def snapshot(self) -> dict:
        """The full JSON-able telemetry stamp (bench artifacts,
        /debug/device): identity, per-kernel events/percentiles/shape
        latches, backends, transfers, window budget, self-accounting."""
        ident = self.ensure_identity()
        shapes = self.shape_counts()
        kernels: dict[str, dict] = {}
        for kernel, events in self.percentiles().items():
            kernels[kernel] = {
                "events": events,
                "compiles": events.get("compile", {}).get("count", 0),
                "executes": events.get("execute", {}).get("count", 0),
                "shapes_latched": shapes.get(kernel, 0),
                "recompiles": max(0, shapes.get(kernel, 0) - 1),
            }
        transfers: dict[str, dict] = {}
        for kernel, direction, nbytes, ops in self.transfers():
            transfers.setdefault(kernel, {})[direction] = {
                "bytes": nbytes, "ops": ops}
        with self._lock:
            stats = dict(self.stats)
            xla = dict(self.xla)
        return {
            "identity": ident,
            "kernels": kernels,
            "backends": self.backends(),
            "transfers": transfers,
            "window_budget": self.budget_export(),
            "xla": xla,
            "stats": stats,
        }

    def timeline(self, limit: int | None = None) -> dict:
        """The bounded rings for /debug/device: recent kernel events and
        per-window SLO entries, oldest first."""
        with self._lock:
            events = list(self._events)
            windows = list(self._windows)
        if limit:
            events = events[-limit:]
            windows = windows[-limit:]
        return {"events": events, "windows": windows}


# -- process-global installation (the faults.py pattern) ----------------------

_active: DeviceTelemetry | None = None


def install(telemetry: DeviceTelemetry | None) -> None:
    """Install (or with None, remove) the process-wide device telemetry.
    The CLI calls this once at startup; tests install/uninstall around
    cases."""
    global _active
    _active = telemetry


def get() -> DeviceTelemetry | None:
    return _active


def record(kernel: str, duration_s: float, shape=None,
           h2d_bytes: int = 0, d2h_bytes: int = 0) -> None:
    """Dispatch-site hook: free when no telemetry is installed."""
    if _active is not None:
        _active.record(kernel, duration_s, shape, h2d_bytes, d2h_bytes)


def transfer(kernel: str, direction: str, nbytes: int) -> None:
    """Transfer-only site hook (eager device writes)."""
    if _active is not None:
        _active.record_transfer(kernel, direction, nbytes)


def note_backend(kernel: str, **fields) -> None:
    """Backend-resolution latch hook (requested/resolved/fallback)."""
    if _active is not None:
        _active.note_backend(kernel, **fields)


def tick_window(used_s: float) -> None:
    """Window-SLO hook, called once per profiler iteration."""
    if _active is not None:
        _active.tick_window(used_s)


_XLA_COMPILE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_XLA_EVENTS = {
    _XLA_COMPILE_REQUEST: "compile_requests_total",
    "/jax/compilation_cache/cache_hits": "cache_hits_total",
    "/jax/compilation_cache/cache_misses": "cache_misses_total",
}
_XLA_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_xla_watched = False
# Threads whose compile requests someone counts (compile_requests_here):
# thread ident -> [requests so far]. JAX raises the request event on the
# thread that asks, as the request begins: before the persistent cache is
# read and before the backend compiles.
_asking: dict[int, list[int]] = {}


@contextlib.contextmanager
def compile_requests_here(asked: list):
    """Count into ``asked[0]`` the XLA compile requests the calling
    thread makes inside the block (what
    ``parca_agent_xla_compile_requests_total`` counts for the process).
    The list is the caller's, so another thread may read it while the
    block runs: the streaming feeder's watchdog tells a feed that is
    compiling from one that hangs by it. With JAX's compilation cache
    off no request event is raised and the count stays where it was."""
    watch_xla_compiles()
    ident = threading.get_ident()
    _asking[ident] = asked
    try:
        yield
    finally:
        _asking.pop(ident, None)


def watch_xla_compiles() -> None:
    """Route JAX's own compile and persistent-cache events into whatever
    telemetry is installed. Imports jax (no backend is initialised), so
    it is called only by entry points that are about to use a device —
    a numpy-only agent never loads jax. Idempotent; the listeners live
    for the process."""
    global _xla_watched
    if _xla_watched:
        return
    from jax import monitoring

    def on_event(event: str, **_kw) -> None:
        if event == _XLA_COMPILE_REQUEST:
            asked = _asking.get(threading.get_ident())
            if asked is not None:
                asked[0] += 1
        key = _XLA_EVENTS.get(event)
        if key is not None and _active is not None:
            _active.note_xla(key)

    def on_duration(event: str, duration_s: float, **_kw) -> None:
        if event == _XLA_COMPILE_EVENT and _active is not None:
            _active.note_xla("backend_compile_seconds_total", duration_s)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _xla_watched = True
