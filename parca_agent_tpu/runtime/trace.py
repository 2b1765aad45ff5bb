"""Window flight recorder: per-window lifecycle traces, streaming stage
histograms, and slow-window auto-capture.

The agent is a profiler that could not explain its own tail latency:
`/metrics` exposed only last-value gauges, so a median close headline
hid the distribution, and a stalled window (a device runtime wedged for
minutes, a multi-second statics rebuild) had to be reconstructed from
logs after the fact. This module is the always-on
instrumentation substrate (docs/observability.md):

  * ``WindowTrace`` — one trace per window, trace id = window seq: a
    tree of spans on one clock (``time.monotonic()``; the trace exports
    its ``t0_monotonic_s``). The stages of the window (drain, identity,
    close, prepare, encode_wait, encode, ship, total, ...) are recorded
    by the profiler loop and the encode pipeline's worker; what happens
    inside a stage (the aggregator's hash, pack, dispatch, fetch and
    unpack under ``close``; labels, gzip and enqueue under ``ship``) is
    recorded where the work is through :func:`child`, with the stage
    open on that thread as parent. While a ``jax.profiler`` session
    runs, the same spans show in its trace as ``pa/<stage>``.
  * ``FlightRecorder`` — a bounded ring of completed traces (the flight
    recorder `/debug/windows` serves as wide-event JSON) plus one
    streaming log-bucket histogram per stage (p50/p90/p99/max), exported
    in real Prometheus histogram format from `/metrics`. Transport
    stages that are not per-window (batch_flush, store_ack, store_rpc,
    spool_spill, spool_replay) feed the same histograms through
    :func:`observe`.
  * A slow-window detector: a span whose duration exceeds
    ``slow_multiple`` x the stage's RUNNING p99 (with a sample-count
    gate and an absolute floor) auto-captures an incident — the
    offending trace, a self-pprof (profiler/selfprofile.py), and the
    current supervisor/device/quarantine state — into a crash-only
    tmp+rename JSON file, rate-limited and counted.

Tracing is FAIL-OPEN by contract: every recorder entry point swallows
its own errors (counted in ``stats["record_errors"]``), so a broken or
chaos-injected tracing path can never stall or lose a window. The chaos
sites ``trace.record`` and ``incident.dump`` (utils/faults.py) exist to
prove exactly that.

Like ``utils/faults.py``, a process-global recorder can be installed so
deep components (batch client, spool, gRPC client, encoder) observe
stage durations without plumbing: production pays one module-attribute
read per site when tracing is off. :func:`child`, :func:`note` and
:func:`count` follow the same pattern through a per-thread stack of the
open spans.
"""

from __future__ import annotations

import base64
import collections
import contextlib
import itertools
import json
import os
import re
import sys
import threading
import time
import weakref

from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.log import get_logger
from parca_agent_tpu.utils.vfs import atomic_write_bytes

_log = get_logger("trace")

# palint: persistence-root — incident files are read by operators post-crash.

# Log-spaced bucket upper bounds in seconds: 10 us doubling to ~671 s.
# 27 finite buckets + the implicit +Inf bucket cover everything from a
# sub-ms host-side stage to a device runtime wedged for minutes.
BUCKET_BOUNDS = tuple(1e-5 * (2.0 ** i) for i in range(27))

# The spans every complete fast-path (dict aggregator + fast encode)
# window trace carries; `make trace-smoke` and the integration tests
# assert these. Scalar-path traces replace prepare/encode with
# symbolize-less builder work and still carry drain/close/ship.
MANDATORY_SPANS = ("drain", "close", "prepare", "encode", "ship")


class StageHistogram:
    """One streaming log-bucket histogram: fixed bounds, cumulative-free
    per-bucket counts (cumulated at export), running sum/count/max.
    Mutation is serialized by the owning recorder's lock."""

    __slots__ = ("counts", "count", "sum_s", "max_s")

    def __init__(self):
        self.counts = [0] * (len(BUCKET_BOUNDS) + 1)
        self.count = 0
        self.sum_s = 0.0
        self.max_s = 0.0

    def observe(self, dur_s: float) -> None:
        dur_s = max(0.0, float(dur_s))
        lo, hi = 0, len(BUCKET_BOUNDS)
        while lo < hi:  # first bound >= dur_s (inlined bisect: no import)
            mid = (lo + hi) // 2
            if BUCKET_BOUNDS[mid] < dur_s:
                lo = mid + 1
            else:
                hi = mid
        self.counts[lo] += 1
        self.count += 1
        self.sum_s += dur_s
        if dur_s > self.max_s:
            self.max_s = dur_s

    def quantile(self, q: float) -> float:
        """Bucket-interpolated quantile estimate. With log-spaced
        buckets the true value is within one bucket ratio (2x) of the
        estimate — good enough for budgets and dashboards, and the max
        is tracked exactly alongside."""
        if self.count == 0:
            return 0.0
        rank = q * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= rank and c:
                if i >= len(BUCKET_BOUNDS):
                    return self.max_s
                lo = BUCKET_BOUNDS[i - 1] if i else 0.0
                # Cap at the exact max (all-zero stages report 0, not
                # half a bucket bound); observations in bucket i are
                # strictly above lo, so max(hi, lo) only guards the
                # zero-bucket case.
                hi = min(BUCKET_BOUNDS[i], self.max_s)
                frac = (rank - (seen - c)) / c
                return lo + (max(hi, lo) - lo) * frac
        return self.max_s

    def export(self) -> dict:
        """Cumulative buckets + summary stats (the /metrics shape)."""
        cum, acc = [], 0
        for i, c in enumerate(self.counts[:-1]):
            acc += c
            cum.append((BUCKET_BOUNDS[i], acc))
        return {
            "buckets": cum,             # [(le_seconds, cumulative_count)]
            "count": self.count,        # == the +Inf cumulative bucket
            "sum_s": self.sum_s,
            "max_s": self.max_s,
            "p50_s": self.quantile(0.50),
            "p90_s": self.quantile(0.90),
            "p99_s": self.quantile(0.99),
        }


# One clock for every span of every window: ``time.monotonic()``. It is
# the machine's, so a span's ``t0_monotonic_s + start_s`` lies on one
# line with another window's, with an outside reader's own stamps, and
# (through the ``pa/*`` annotations below) with the device trace.
_clock = time.monotonic

# Per-thread stack of the spans open on this thread (innermost last):
# a span's parent is the top of its thread's stack when it begins.
_tls = threading.local()


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


# What a span that asks for it (``usage=True``) records of its threads
# beside its wall time: their CPU (user + system) from each thread's own
# CPU clock, ``time.thread_time()``, read at both edges. It is the clock
# the per-thread counters read too, exact at the moment of the call. A
# reading is a system call (~0.4 us on Linux, ~50 us inside the agent on
# a sandboxed kernel such as gVisor), so the call site decides: the
# stages that lie off the stretch from a window's last sample to its
# pprof bytes ask, and nothing between those two edges does (PERF.md
# section 6, PR 39).
def _usage_fields(cpu_s: float, threads: int = 0) -> dict:
    out = {"cpu_s": round(cpu_s, 6)} if cpu_s > 0 else {}
    if threads:
        out["threads"] = threads
    return out


# Serializes what adopting threads add to a span open elsewhere (an
# abandoned feed thread may leave beside the next one).
_adopt_lock = threading.Lock()

_NO_ANNOTATION = contextlib.nullcontext()


def annotation(stage: str, **kv):
    """``jax.profiler.TraceAnnotation("pa/<stage>", **kv)`` when JAX is
    already imported, else a context manager that does nothing: the
    annotation lands on the host plane of the same ``.xplane.pb`` as the
    device's programs, so a profiler session shows what the host did
    while the chip idled. Never imports JAX (``--aggregator cpu`` stays
    JAX-free) and costs a no-op TraceMe while no profiler session runs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_ANNOTATION
    try:
        return jax.profiler.TraceAnnotation("pa/" + stage, **kv)
    except Exception:  # noqa: BLE001 - tracing is fail-open
        return _NO_ANNOTATION


class waiting:
    """``annotation(stage)`` around a wait inside an open span (a
    source's wait for its next drain, inside the profiler's ``drain``),
    with the annotations of the spans open on this thread closed for its
    length and opened again after it: in the profiler's trace the wait
    then lies beside ``pa/drain``, not inside it, and the chip's idle
    time under it is counted once. The spans themselves stay open and
    go on measuring."""

    __slots__ = ("_stage", "_open", "_ann")

    def __init__(self, stage: str):
        self._stage = stage
        self._open: list = []
        self._ann = _NO_ANNOTATION

    def __enter__(self):
        try:
            me = threading.get_ident()  # not a span adopted from another
            self._open = [c for c in _stack() if c._tid == me
                          and c._ann is not _NO_ANNOTATION]
            for c in reversed(self._open):
                c._ann.__exit__(None, None, None)
            self._ann = annotation(self._stage)
            self._ann.__enter__()
        except Exception:  # noqa: BLE001 - tracing is fail-open
            pass
        return self

    def __exit__(self, et, ev, tb):
        try:
            self._ann.__exit__(et, ev, tb)
            for c in self._open:
                c._ann = annotation(c._stage, window=c._trace.seq)
                c._ann.__enter__()
        except Exception:  # noqa: BLE001 - tracing is fail-open
            pass
        return False


class _SpanCtx:
    """Context manager for one timed span. Always measures (the gauges
    that must stay in lockstep with the histograms read .duration_s even
    when tracing is disabled); recording is the trace's problem and is
    fail-open there. User exceptions are recorded and re-raised.

    While open it sits on its thread's stack, so spans begun inside it
    (``trace.span`` of the same window, ``child`` from deep components)
    name it as their parent. ``merge`` marks a child: a second span of
    the same stage under the same parent adds to the first.

    With ``usage`` a recorded span also reads its thread's CPU clock at
    both edges, and holds what the threads that adopted it
    (:class:`adopt`) used under it: such a span's CPU is that of every
    thread that worked under it."""

    __slots__ = ("_trace", "_stage", "_hist", "_merge", "_ann", "_tid",
                 "_usage", "_up", "_u0", "_adopted", "_threads",
                 "id", "parent", "start_s", "duration_s")

    def __init__(self, trace, stage: str, histogram: bool = True,
                 merge: bool = False, usage: bool = False):
        self._trace = trace
        self._stage = stage
        self._hist = histogram
        self._merge = merge
        self._usage = usage
        self._ann = _NO_ANNOTATION
        self._tid = None  # the thread whose profiler line holds _ann
        self._up = None  # the span open around this one, adopted or not
        self._u0 = None  # the thread's CPU clock at the start
        self._adopted = 0.0  # CPU the adopting threads added
        self._threads = 0  # how many did
        self.id = self.parent = None
        self.start_s = self.duration_s = 0.0

    def __enter__(self):
        tr = self._trace
        if tr is not NULL_TRACE:
            try:
                stack = _stack()
                top = stack[-1] if stack else None
                if top is not None and top._trace is tr:
                    self.parent = top.id
                    self._up = top
                # A merged stage keeps the id of its first interval: what
                # a later interval records inside it (a drain's
                # stream_feed and the feed stages under it) names the one
                # span the window keeps as its parent.
                first = tr._by_key.get((self._stage, self.parent)) \
                    if self._merge else None
                self.id = first["id"] if first is not None else tr.new_id()
                stack.append(self)
                self._ann = annotation(self._stage, window=tr.seq)
                self._tid = threading.get_ident()
                self._ann.__enter__()
                if self._usage:
                    self._u0 = time.thread_time()
            except Exception as e:  # noqa: BLE001 - tracing is fail-open
                tr._rec._record_error(e)
        self.start_s = _clock()
        return self

    def _used(self) -> dict | None:
        """This span's usage fields at its end: the thread's own CPU
        since the start (none when the span is left on another thread
        than it was entered on) plus what adopting threads added."""
        if self._u0 is None:
            return None
        cpu_s = 0.0
        if self._tid == threading.get_ident():
            cpu_s = time.thread_time() - self._u0
        with _adopt_lock:
            return _usage_fields(cpu_s + self._adopted, self._threads)

    def __exit__(self, et, ev, tb):
        self.duration_s = _clock() - self.start_s
        tr = self._trace
        if tr is NULL_TRACE:
            return False
        used = None
        try:
            used = self._used()
        except Exception as e:  # noqa: BLE001 - the span keeps its wall
            tr._rec._record_error(e)
        try:
            self._ann.__exit__(et, ev, tb)
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:
                stack.remove(self)
        except Exception as e:  # noqa: BLE001 - tracing is fail-open
            tr._rec._record_error(e)
        tr.add_span(
            self._stage, self.duration_s,
            error=(repr(ev)[:200] if ev is not None else None),
            histogram=self._hist, start_s=self.start_s,
            parent=self.parent, span_id=self.id, merge=self._merge,
            used=used)
        return False


class _NullTrace:
    """The do-nothing trace: call sites never branch on whether tracing
    is enabled. Spans still measure (see _SpanCtx) but record nowhere."""

    seq = 0
    completed = True
    detached = False

    def span(self, stage: str, histogram: bool = True,
             usage: bool = False) -> _SpanCtx:
        return _SpanCtx(self, stage)

    def add_span(self, stage, duration_s, error=None,
                 histogram=True, **_kw) -> None:
        pass

    def annotate(self, **kv) -> None:
        pass

    def count(self, **kv) -> None:
        pass

    def detach(self) -> None:
        pass

    def finish(self, error: str | None = None) -> None:
        pass

    def complete(self, error: str | None = None) -> None:
        pass

    def discard(self) -> None:
        pass


NULL_TRACE = _NullTrace()


class WindowTrace:
    """One window's lifecycle. Created by FlightRecorder.begin on the
    profiler thread; ownership may transfer to the encode pipeline's
    worker (detach) — the hand-off lock gives the happens-before edge,
    so spans never need their own lock. complete() is idempotent and
    routes through the recorder (ring + histograms + slow detector).

    Every span is a dict ``{id, parent, stage, start_s, duration_s,
    thread}``: ``start_s`` counts from ``t0_monotonic_s`` (the trace's
    birth on ``time.monotonic()``), ``parent`` is the id of the span of
    this window that was open on the same thread when this one began
    (None at the top level), and ``accumulated`` marks a duration
    summed over ``n`` intervals. A span that asked for it
    (``span(..., usage=True)``) has beside the wall time, each left out
    when zero, ``cpu_s`` (user + system CPU of every thread that worked
    under the span) and ``threads`` (how many threads adopted it)."""

    __slots__ = ("seq", "time_ns", "t0_monotonic_s", "spans", "meta",
                 "error", "completed", "detached", "_rec", "_ids",
                 "_compiles0", "_by_key")

    def __init__(self, rec, seq: int, time_ns: int):
        self._rec = rec
        self.seq = seq
        self.time_ns = time_ns
        self.t0_monotonic_s = _clock()
        self.spans: list[dict] = []
        # (stage, parent) -> the first span recorded under that key: what
        # a merged stage adds to, looked up and not searched for.
        self._by_key: dict[tuple, dict] = {}
        self.meta: dict = {}
        self.error: str | None = None
        self.completed = False
        self.detached = False
        self._ids = itertools.count(1)
        self._compiles0 = _xla_compile_requests()

    def new_id(self) -> int:
        return next(self._ids)  # atomic under the GIL: threads share it

    def span(self, stage: str, histogram: bool = True,
             usage: bool = False) -> _SpanCtx:
        """A top-level stage. ``usage`` asks for the CPU of the threads
        that work under it (``cpu_s``): two system calls, so a stage
        between a window's last sample and its pprof bytes does not."""
        return _SpanCtx(self, stage, histogram, usage=usage)

    # palint: fail-open
    def add_span(self, stage: str, duration_s: float,
                 error: str | None = None,
                 histogram: bool = True, *,
                 start_s: float | None = None,
                 parent: int | None = None,
                 accumulated: bool = False,
                 span_id: int | None = None,
                 merge: bool = False,
                 used: dict | None = None) -> None:
        """Record one span, at its end; fail-open (a tracing fault must
        never cost the window — the trace.record chaos site injects
        exactly here). EVERY span of a window goes through this one
        method, looked up on the class at call time: an outside reader
        that wants its own clock at a stage's end wraps it.

        ``histogram=False`` keeps the span out of the stage histograms
        at completion: for stages whose histogram is fed elsewhere
        (the encoder observes each statics build per call; the worker's
        per-window statics span would double-count it) and for children,
        which are wide-event only. ``start_s`` is the span's start on
        ``time.monotonic()``; without it the span is taken to end now.
        ``accumulated`` says the duration is a sum of several intervals
        that began at ``start_s``. ``merge`` adds the duration to a span
        of the same stage and parent if the window already has one (a
        feed in chunks is one ``feed_hash`` span, a window's ten drains
        one ``stream_feed``: then ``accumulated``, with the number of
        intervals as ``n``). ``used`` is what the span's threads used
        (``cpu_s``, ``threads``); a merged span sums it as it sums the
        duration."""
        try:
            faults.inject("trace.record")
            if parent is not None and self.completed:
                return  # a child of an abandoned call, after the fact
            dur = float(duration_s)
            if merge:
                s = self._by_key.get((stage, parent))
                if s is not None:
                    s["duration_s"] = round(s["duration_s"] + dur, 6)
                    s["accumulated"] = True
                    s["n"] = s.get("n", 1) + 1
                    for k, v in (used or {}).items():
                        s[k] = round(s.get(k, 0) + v, 6)
                    return
            if start_s is None:
                start_s = _clock() - dur
            span = {
                "id": span_id if span_id is not None else self.new_id(),
                "parent": parent,
                "stage": stage,
                "start_s": round(max(0.0, start_s - self.t0_monotonic_s), 6),
                "duration_s": round(dur, 6),
                "thread": threading.current_thread().name,
                **({"accumulated": True} if accumulated else {}),
                **({} if histogram else {"nohist": True}),
                **({"error": error} if error else {}),
                **(used or {}),
            }
            self.spans.append(span)
            self._by_key.setdefault((stage, parent), span)
        except Exception as e:  # noqa: BLE001 - tracing is fail-open
            self._rec._record_error(e)

    # palint: fail-open
    def annotate(self, **kv) -> None:
        try:
            # Rebind, don't mutate: a detached trace may already be in
            # the ring (the worker completed it) while the profiler
            # thread annotates a late iteration error — a concurrent
            # /debug/windows json.dumps must see the old dict or the
            # new one, never one resizing mid-iteration. The recorder
            # lock serializes against complete()'s slow_stage rebind —
            # two unlocked rebinds would lose one writer's keys.
            with self._rec._lock:
                self.meta = {**self.meta, **kv}
        except Exception as e:  # noqa: BLE001 - tracing is fail-open
            self._rec._record_error(e)

    # palint: fail-open
    def count(self, **kv) -> None:
        """Add to the window's counts in ``meta`` (rows fed, misses,
        bytes shipped): counts taken where the work happens, summed
        over a window that does the work in several calls."""
        try:
            with self._rec._lock:
                self.meta = {**self.meta,
                             **{k: self.meta.get(k, 0) + v
                                for k, v in kv.items()}}
        except Exception as e:  # noqa: BLE001 - tracing is fail-open
            self._rec._record_error(e)

    def detach(self) -> None:
        """Ownership moved to another thread (the encode worker): the
        profiler loop's end-of-iteration complete() becomes a no-op."""
        self.detached = True

    def finish(self, error: str | None = None) -> None:
        """The profiler loop's end-of-iteration completion. Detached
        traces are NEVER completed from here — the encode worker owns
        them (completing one early would race the worker's span writes
        and drop its encode/ship samples from the histograms); an
        iteration error that co-occurs with a successful hand-off (e.g.
        a debuginfo upload failure) is annotated instead, so it still
        shows on /debug/windows without stealing the completion."""
        if self.detached:
            if error is not None:
                self.annotate(iteration_error=error)
            return
        self._rec.complete(self, error=error)

    def complete(self, error: str | None = None) -> None:
        self._rec.complete(self, error=error)

    def discard(self) -> None:
        self._rec.discard(self)

    def to_dict(self) -> dict:
        total = next((s["duration_s"] for s in self.spans
                      if s["stage"] == "total"), None)
        d = {
            "seq": self.seq,
            "time_ns": self.time_ns,
            "t0_monotonic_s": round(self.t0_monotonic_s, 6),
            "complete": self.completed,
            # No total yet: the top-level spans (children lie inside).
            "duration_s": total if total is not None else round(
                sum(s["duration_s"] for s in self.spans
                    if s["parent"] is None), 6),
            "spans": list(self.spans),
        }
        if self.meta:
            d["meta"] = dict(self.meta)
        if self.error:
            d["error"] = self.error
        return d


def _xla_compile_requests() -> float:
    """``parca_agent_xla_compile_requests_total`` as the installed
    device telemetry counts it; 0 without one (a numpy-only agent never
    loads that module, and this never imports it)."""
    dtel = sys.modules.get("parca_agent_tpu.runtime.device_telemetry")
    tel = dtel.get() if dtel is not None else None
    return tel.xla["compile_requests_total"] if tel is not None else 0


class FlightRecorder:
    """The per-process window flight recorder (module docs above).

    ``context`` is a zero-arg callable returning a JSON-able dict of
    runtime state for incident files (the CLI wires supervisor/device/
    quarantine snapshots via set_context after those exist);
    ``self_profile`` a zero-arg callable returning gzipped pprof bytes
    (defaults to a 1 s profiler/selfprofile.py wall-clock sample).
    ``incident_dir`` empty disables incident files (slow windows are
    still detected and counted)."""

    def __init__(self, ring: int = 512, slow_multiple: float = 5.0,
                 min_count: int = 8, min_duration_s: float = 0.05,
                 incident_dir: str = "", incident_interval_s: float = 300.0,
                 max_incidents: int = 64, self_profile_s: float = 1.0,
                 context=None, self_profile=None, clock=time.monotonic):
        self._lock = threading.Lock()
        # guarded-by: _lock (the next three + stats below): profiler
        # thread, encode worker, batch/flush threads, and the HTTP read
        # side all meet here — the PR 7 review round's two-writer
        # lost-update is exactly what the annotation now machine-checks.
        self._ring: collections.deque = collections.deque(  # guarded-by: _lock
            maxlen=max(1, ring))
        self._hists: dict[str, StageHistogram] = {}  # guarded-by: _lock
        # stage -> cpu_s summed over every completed window's spans of
        # that stage, children too.
        self._stage_cpu_s: dict[str, float] = {}  # guarded-by: _lock
        self._seq = 0  # guarded-by: _lock
        self._slow_multiple = slow_multiple
        self._min_count = max(1, min_count)
        self._min_duration = min_duration_s
        self._incident_dir = incident_dir
        self._incident_interval = incident_interval_s
        self._max_incidents = max(1, max_incidents)
        self._last_incident_at: float | None = None
        self._dumping = False
        self._clock = clock
        self._context = context
        self._self_profile = self_profile
        self._self_profile_s = self_profile_s
        if incident_dir:
            os.makedirs(incident_dir, exist_ok=True)
        self.stats = {  # guarded-by: _lock
            "traces_started": 0,
            "traces_completed": 0,
            "traces_discarded": 0,
            "record_errors": 0,
            "slow_spans_total": 0,
            "incidents_written": 0,
            "incidents_suppressed": 0,
            "incidents_failed": 0,
        }

    # -- configuration -------------------------------------------------------

    def set_context(self, context) -> None:
        """Late-bind the incident context provider (the CLI builds the
        recorder before the supervisor exists)."""
        self._context = context

    # -- trace lifecycle -----------------------------------------------------

    # palint: fail-open
    def begin(self, time_ns: int | None = None):
        """Start the next window's trace. Fail-open: any internal error
        returns the NULL trace so the window proceeds untraced."""
        try:
            faults.inject("trace.record")
            with self._lock:
                self._seq += 1
                seq = self._seq
                self.stats["traces_started"] += 1
            return WindowTrace(self, seq,
                               time_ns if time_ns is not None
                               else time.time_ns())
        except Exception as e:  # noqa: BLE001 - tracing is fail-open
            self._record_error(e)
            return NULL_TRACE

    # palint: fail-open
    def complete(self, trace: WindowTrace, error: str | None = None) -> None:
        """Finish a trace: total span, ring append, histogram feed, slow
        detection. Idempotent; fail-open."""
        try:
            faults.inject("trace.record")
            with self._lock:
                if trace.completed:
                    return
                trace.completed = True
            if error:
                trace.error = error
            total_s = _clock() - trace.t0_monotonic_s
            trace.spans.append({
                "id": trace.new_id(),
                "parent": None,
                "stage": "total",
                "start_s": 0.0,
                "duration_s": round(total_s, 6),
                "thread": threading.current_thread().name,
            })
            compiles = _xla_compile_requests() - trace._compiles0
            if compiles:
                # Put a compile down to the window it fell in (a
                # pipelined window lasts through its ship, so a compile
                # in the next window's close shows in both).
                trace.count(xla_compiles=int(compiles))
            worst = None  # (ratio, stage, duration, budget)
            with self._lock:
                for s in trace.spans:
                    stage, dur = s["stage"], s["duration_s"]
                    if "cpu_s" in s:
                        self._stage_cpu_s[stage] = \
                            self._stage_cpu_s.get(stage, 0.0) + s["cpu_s"]
                    if s.pop("nohist", False):
                        # This stage's histogram AND slow detection are
                        # fed per-call elsewhere (encoder statics via
                        # observe()); the per-window aggregate span is
                        # display-only — a churn window summing N fast
                        # builds must not trip a budget derived from
                        # per-call samples.
                        continue
                    budget = self._budget_locked(stage)
                    if budget is not None and dur > budget:
                        self.stats["slow_spans_total"] += 1
                        s["slow"] = True
                        if worst is None or dur / budget > worst[0]:
                            worst = (dur / budget, stage, dur, budget)
                    self._hists.setdefault(
                        stage, StageHistogram()).observe(dur)
                if worst is not None:
                    # Rebind, don't mutate: the trace is already visible
                    # to /debug/windows serialization (see annotate(),
                    # which shares this lock so neither rebind is lost).
                    trace.meta = {**trace.meta, "slow_stage": worst[1]}
                self._ring.append(trace)
                self.stats["traces_completed"] += 1
            if worst is not None:
                self._capture_incident(trace, worst)
        except Exception as e:  # noqa: BLE001 - tracing is fail-open
            self._record_error(e)

    # palint: fail-open
    def discard(self, trace) -> None:
        """Drop a trace that never became a window (source exhausted):
        not ringed, not histogrammed."""
        try:
            with self._lock:
                if not getattr(trace, "completed", True):
                    trace.completed = True
                    self.stats["traces_discarded"] += 1
        except Exception as e:  # noqa: BLE001 - tracing is fail-open
            self._record_error(e)

    # palint: fail-open
    def observe(self, stage: str, duration_s: float) -> None:
        """Feed one non-per-window stage observation (batch flush, store
        ack, spool spill/replay) into its histogram + the slow detector.
        Fail-open."""
        try:
            faults.inject("trace.record")
            slow = None
            with self._lock:
                budget = self._budget_locked(stage)
                if budget is not None and duration_s > budget:
                    self.stats["slow_spans_total"] += 1
                    slow = (duration_s / budget, stage, duration_s, budget)
                self._hists.setdefault(
                    stage, StageHistogram()).observe(duration_s)
            if slow is not None:
                self._capture_incident(None, slow)
        except Exception as e:  # noqa: BLE001 - tracing is fail-open
            self._record_error(e)

    def _record_error(self, e: Exception) -> None:
        try:
            with self._lock:
                self.stats["record_errors"] += 1
            _log.debug("trace recording failed (fail-open)", error=repr(e))
        except Exception:  # noqa: BLE001 - never escalate from here
            pass

    # -- slow-window detection / incidents -----------------------------------

    def _budget_locked(self, stage: str) -> float | None:  # palint: holds=_lock
        """Stage budget = slow_multiple x running p99, floored at
        min_duration_s; None until min_count samples exist (a budget
        computed from two observations is noise, not a contract)."""
        h = self._hists.get(stage)
        if h is None or h.count < self._min_count:
            return None
        return max(self._slow_multiple * h.quantile(0.99),
                   self._min_duration)

    def _capture_incident(self, trace, worst) -> None:
        """Rate-limited, single-flight incident capture on a daemon
        thread (the self-profile samples for self_profile_s seconds —
        never on the window path)."""
        _ratio, stage, dur, budget = worst
        with self._lock:
            now = self._clock()
            if self._dumping or (
                    self._last_incident_at is not None
                    and now - self._last_incident_at
                    < self._incident_interval):
                self.stats["incidents_suppressed"] += 1
                return
            self._last_incident_at = now
            if not self._incident_dir:
                self.stats["incidents_suppressed"] += 1
                return
            self._dumping = True
        _log.warn("slow window detected; capturing incident",
                  stage=stage, duration_s=round(dur, 3),
                  budget_s=round(budget, 3),
                  seq=getattr(trace, "seq", None))
        threading.Thread(
            target=self._dump_incident, args=(trace, stage, dur, budget),
            name="trace-incident", daemon=True).start()

    def capture_event(self, kind: str, stage: str, detail: dict) -> bool:
        """External incident capture — the device flight recorder routes
        recompile storms here (runtime/device_telemetry.py). Same rate
        limiter, single-flight daemon thread, context/self-profile
        bundle, and pruning as slow-window capture; the incident file
        carries the caller's ``kind`` and ``detail`` payload. Returns
        False when suppressed (rate limit, capture in flight, no
        incident dir)."""
        with self._lock:
            now = self._clock()
            if self._dumping or (
                    self._last_incident_at is not None
                    and now - self._last_incident_at
                    < self._incident_interval):
                self.stats["incidents_suppressed"] += 1
                return False
            self._last_incident_at = now
            if not self._incident_dir:
                self.stats["incidents_suppressed"] += 1
                return False
            self._dumping = True
        _log.warn("external incident; capturing", kind=kind, stage=stage)
        threading.Thread(
            target=self._dump_incident, args=(None, stage, 0.0, 0.0),
            kwargs={"kind": kind, "detail": detail},
            name="trace-incident", daemon=True).start()
        return True

    def _dump_incident(self, trace, stage: str, dur: float,
                       budget: float, kind: str = "slow_window",
                       detail: dict | None = None) -> None:
        try:
            faults.inject("incident.dump")
            body = {
                "kind": kind,
                "stage": stage,
                "duration_s": round(dur, 6),
                "budget_s": round(budget, 6),
                "slow_multiple": self._slow_multiple,
                "captured_at_ns": time.time_ns(),
                "trace": trace.to_dict() if trace is not None else None,
                "stage_percentiles": self.percentiles(),
            }
            if detail is not None:
                body["detail"] = detail
            if self._context is not None:
                try:
                    body["context"] = self._context()
                except Exception as e:  # noqa: BLE001 - partial > none
                    body["context_error"] = repr(e)[:200]
            try:
                prof = self._self_profile_bytes()
                body["self_profile_pprof_gz_b64"] = \
                    base64.b64encode(prof).decode()
            except Exception as e:  # noqa: BLE001 - partial > none
                body["self_profile_error"] = repr(e)[:200]
            seq = getattr(trace, "seq", 0) or 0
            path = os.path.join(
                self._incident_dir,
                f"incident-{time.strftime('%Y%m%dT%H%M%S')}"
                f"-w{seq:06d}-{stage}.json")
            atomic_write_bytes(
                path, json.dumps(body, indent=1).encode())
            self._prune_incidents()
            with self._lock:
                self.stats["incidents_written"] += 1
            _log.warn("incident captured", path=path)
        except Exception as e:  # noqa: BLE001 - incidents are best-effort
            with self._lock:
                self.stats["incidents_failed"] += 1
            _log.warn("incident capture failed", error=repr(e))
        finally:
            with self._lock:
                self._dumping = False
            thread_ended()  # a thread of its own, gone before any scrape

    def _self_profile_bytes(self) -> bytes:
        if self._self_profile is not None:
            return self._self_profile()
        from parca_agent_tpu.profiler.selfprofile import profile_self

        return profile_self(self._self_profile_s)

    def _prune_incidents(self) -> None:
        """Keep the newest max_incidents files: an agent stuck slow must
        not fill the disk with its own forensics."""
        try:
            names = sorted(n for n in os.listdir(self._incident_dir)
                           if n.startswith("incident-")
                           and n.endswith(".json"))
            for n in names[:-self._max_incidents]:
                os.unlink(os.path.join(self._incident_dir, n))
        except OSError:  # pragma: no cover - prune is best-effort
            pass

    # -- read side (HTTP thread) ---------------------------------------------

    def traces(self, limit: int | None = None) -> list[dict]:
        """The ring, oldest first, as wide-event dicts (/debug/windows)."""
        with self._lock:
            # Slice before building dicts: the benchmark's harness asks
            # for ?limit=1 fifty times a second over a ring of thousands.
            ring = self._ring if not limit else reversed(
                list(itertools.islice(reversed(self._ring), limit)))
            return [t.to_dict() for t in ring]

    def trace(self, seq: int) -> dict | None:
        with self._lock:
            for t in self._ring:
                if t.seq == seq:
                    return t.to_dict()
        return None

    def export_histograms(self) -> dict[str, dict]:
        """{stage: StageHistogram.export()} for /metrics rendering."""
        with self._lock:
            return {stage: h.export()
                    for stage, h in sorted(self._hists.items())}

    def export_stage_cpu(self) -> dict[str, float]:
        """{stage: cpu_s}: the per-stage totals /metrics serves, for the
        stages whose spans ask for their threads' CPU."""
        with self._lock:
            return dict(sorted(self._stage_cpu_s.items()))

    def percentiles(self) -> dict[str, dict]:
        """{stage: {p50_ms, p90_ms, p99_ms, max_ms, count}} — the compact
        distribution stamp (bench JSON, incident files)."""
        with self._lock:
            return {
                stage: {
                    "p50_ms": round(h.quantile(0.50) * 1e3, 3),
                    "p90_ms": round(h.quantile(0.90) * 1e3, 3),
                    "p99_ms": round(h.quantile(0.99) * 1e3, 3),
                    "max_ms": round(h.max_s * 1e3, 3),
                    "count": h.count,
                }
                for stage, h in sorted(self._hists.items())
            }


# -- process-global installation (the faults.py pattern) ----------------------

_active: FlightRecorder | None = None


def install(recorder: FlightRecorder | None) -> None:
    """Install (or with None, remove) the process-wide recorder. The CLI
    calls this once at startup; tests install/uninstall around cases."""
    global _active
    _active = recorder


def get() -> FlightRecorder | None:
    return _active


def observe(stage: str, duration_s: float) -> None:
    """The deep-component hook (batch client, spool, gRPC client,
    encoder): free when no recorder is installed."""
    if _active is not None:
        _active.observe(stage, duration_s)


# -- every thread's CPU ---------------------------------------------------------


_THREAD_SERIAL = re.compile(r"(?:[-_ ]?\d+)+$")
_DEFAULT_NAME = re.compile(r"Thread-\d+ \((.+)\)$")
_COMM_DIGITS = re.compile(r"\d+")


def thread_label(name: str) -> str:
    """A thread's name with any trailing number taken off (`row-hash_3`
    -> `row-hash`); a thread nobody named goes by its target
    (`Thread-7 (channel_spin)` -> `channel_spin`)."""
    m = _DEFAULT_NAME.match(name)
    return _THREAD_SERIAL.sub("", m.group(1) if m else name) or "thread"


def _task_stat(tid) -> tuple[bytes, int] | None:
    """``(comm, utime + stime in clock ticks)`` of one of this process's
    threads from ``/proc/self/task/<tid>/stat``; None for a thread that
    is gone (no such file) or a line that does not parse."""
    try:
        with open(f"/proc/self/task/{tid}/stat", "rb") as f:
            raw = f.read()
        # "<tid> (<comm>) <state> ...": utime and stime are the 12th
        # and 13th fields after the name's closing bracket.
        close = raw.rindex(b")")
        rest = raw[close + 2:].split()
        return raw[raw.index(b"(") + 1:close], int(rest[11]) + int(rest[12])
    except (OSError, ValueError, IndexError):
        return None


class ThreadCpu:
    """The process's CPU by thread, as monotone counters
    (``parca_agent_thread_cpu_seconds_total{thread}``; docs/
    observability.md lists the labels).

    Most of the agent's work runs on threads that are gone when anyone
    looks (``bounded_call`` starts one for every device call), so the
    counters are fed from two sides: a thread that ends credits its own
    ``time.thread_time()`` (:meth:`ended`, its last act), and a scrape
    credits every live thread with the rise of what the kernel's
    ``/proc/self/task/<tid>/stat`` says it has used (clock ticks) since
    the scrape before. No thread asks for another thread's CPU clock: a
    thread that ended under the scrape has no file, where its clock id
    would be a dangling one. What one side has credited the other
    leaves out, so each label only rises. ``native`` is the remainder:
    the process's CPU less every Python thread's, which is XLA's,
    libtpu's and gRPC's own threads (and a Python thread that ended
    without saying so). Nothing here runs on a window's path: a scrape
    pays it."""

    _MAX_LABELS = 32  # thread names are the code's, not the input's
    _MAX_COMMS = 16
    # The native threads are ~100 files of ~100 us each on some hosts.
    _NATIVE_EVERY_S = 1.0

    def __init__(self):
        self._tick_s = 1.0 / os.sysconf("SC_CLK_TCK")
        self._lock = threading.Lock()
        self._total: dict[str, float] = {}  # guarded-by: _lock
        # Thread -> the CPU already credited for it; and the threads
        # that said they were ending, which a scrape credits no more.
        self._seen: weakref.WeakKeyDictionary = \
            weakref.WeakKeyDictionary()  # guarded-by: _lock
        self._ended: weakref.WeakSet = weakref.WeakSet()  # guarded-by: _lock
        self._native = 0.0  # guarded-by: _lock
        # The walk of the native threads has a lock of its own: a thread
        # that reports its end never waits for file reads.
        self._proc_lock = threading.Lock()
        self._comm_total: dict[str, float] = {}  # guarded-by: _proc_lock
        self._tid_ticks: dict[int, int] = {}  # guarded-by: _proc_lock
        self._proc_at: float | None = None  # guarded-by: _proc_lock

    def _credit(self, name: str, cpu_s: float) -> None:  # palint: holds=_lock
        label = thread_label(name)
        if label not in self._total and len(self._total) >= self._MAX_LABELS:
            label = "other"
        self._total[label] = self._total.get(label, 0.0) + cpu_s

    # palint: fail-open
    def ended(self) -> None:
        """The calling thread is about to end: credit what it used and
        no scrape has added yet. A scrape credits it no more."""
        try:
            t = threading.current_thread()
            now = time.thread_time()
            with self._lock:
                self._credit(t.name, max(0.0, now - self._seen.get(t, 0.0)))
                self._seen[t] = now
                self._ended.add(t)
        except Exception as e:  # noqa: BLE001 - accounting never fails a call
            _log.debug("thread CPU credit failed (fail-open)", error=repr(e))

    def scrape(self) -> dict:
        """``{"threads": {label: s}, "native": s, "process": s,
        "native_comm": {comm: s}}`` as of now."""
        python_tids, live = set(), []
        for t in threading.enumerate():
            if isinstance(t, threading._DummyThread):
                # A native thread that once called into Python: it may
                # be long gone, and it is `native` anyway.
                continue
            python_tids.add(t.native_id)
            stat = _task_stat(t.native_id)
            # Alive after the read, so the file read was this thread's
            # (a thread id is handed out again once its thread is gone).
            if stat is not None and t.is_alive():
                live.append((t, stat[1] * self._tick_s))
        with self._lock:
            for t, now in live:
                seen = self._seen.get(t, 0.0)
                if t not in self._ended and now > seen:
                    self._credit(t.name, now - seen)
                    self._seen[t] = now
            process = time.process_time()
            self._native = max(self._native,
                               process - sum(self._total.values()))
            out = {"threads": dict(sorted(self._total.items())),
                   "native": self._native, "process": process}
        with self._proc_lock:
            self._scrape_native(python_tids)
            out["native_comm"] = dict(sorted(self._comm_total.items()))
        return out

    def _scrape_native(self, python_tids: set) -> None:  # palint: holds=_proc_lock
        """What `native` is made of, by the kernel's name for each
        thread that is not Python's (information only; digits stripped
        from the name, at most ``_MAX_COMMS`` names and the rest
        ``other``), read at most once in ``_NATIVE_EVERY_S``."""
        now = _clock()
        if self._proc_at is not None \
                and now - self._proc_at < self._NATIVE_EVERY_S:
            return
        self._proc_at = now
        try:
            tids = set(map(int, os.listdir("/proc/self/task")))
        except (OSError, ValueError):
            return
        for tid in tids - python_tids:
            stat = _task_stat(tid)
            if stat is None:
                continue
            comm, ticks = stat
            before = self._tid_ticks.get(tid, 0)
            self._tid_ticks[tid] = ticks
            if ticks <= before:
                continue
            label = _COMM_DIGITS.sub("", comm.decode("ascii", "replace")) \
                or "thread"
            if label not in self._comm_total \
                    and len(self._comm_total) >= self._MAX_COMMS:
                label = "other"
            self._comm_total[label] = self._comm_total.get(label, 0.0) \
                + (ticks - before) * self._tick_s
        for tid in set(self._tid_ticks) - tids:
            del self._tid_ticks[tid]


THREAD_CPU = ThreadCpu()


def thread_ended() -> None:
    """The last act of a short-lived thread's target (``bounded_call``'s
    threads, an HTTP request's): :meth:`ThreadCpu.ended` on the
    process's accounting."""
    THREAD_CPU.ended()


# -- deep components: children of whatever span is open on this thread --------


def current() -> _SpanCtx | None:
    """The innermost span open on the calling thread, or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def child(stage: str, histogram: bool = False,
          usage: bool = False) -> _SpanCtx:
    """A context manager that times ``stage`` as a child of the
    innermost span open on the calling thread (the aggregator's hash,
    pack, dispatch, fetch and unpack inside the profiler's ``close``).
    It always measures ``.duration_s``, so the site's other consumers
    (``timings[...]``, ``dtel.record``) take that number and the clock
    is read once; with nothing open (library use, a disabled recorder)
    it records nowhere and costs one attribute read more than the
    clock. Children are wide-event only unless ``histogram`` is set:
    no ``/metrics`` series, no slow-window budget. ``usage`` as
    :meth:`WindowTrace.span` takes it."""
    stack = getattr(_tls, "stack", None)
    if not stack:
        return _SpanCtx(NULL_TRACE, stage)
    return _SpanCtx(stack[-1]._trace, stage, histogram, merge=True,
                    usage=usage)


def pending(tr, stage: str) -> _SpanCtx:
    """A span of ``tr`` that its caller records at its end itself,
    through ``add_span(..., span_id=ctx.id)`` (the encode worker's
    ``encode``: recorded where it always was, and not at all when the
    encode raises). ``adopt`` it for the length of the work, and what
    begins on the thread meanwhile names it as parent."""
    ctx = _SpanCtx(tr, stage)
    if tr is not NULL_TRACE:
        ctx.id = tr.new_id()
    return ctx


def note(stage: str, duration_s: float, start_s: float | None = None,
         accumulated: bool = False, histogram: bool = False) -> None:
    """Record an interval measured elsewhere as a child of the innermost
    span open on the calling thread. An ``accumulated`` one (a sum kept
    where the work is, over the parent's many calls) starts where its
    parent starts; any other is taken to end now unless ``start_s``
    says when it began."""
    top = current()
    if top is None or top._trace is NULL_TRACE:
        return
    if start_s is None and accumulated:
        start_s = top.start_s
    top._trace.add_span(stage, duration_s, histogram=histogram,
                        start_s=start_s, parent=top.id,
                        accumulated=accumulated, merge=True)


def count(**kv) -> None:
    """Add to the counts of the window whose span is open on the
    calling thread (``WindowTrace.count``); nothing open, nothing done."""
    top = current()
    if top is not None:
        top._trace.count(**kv)


def annotate(**kv) -> None:
    """Set keys in the ``meta`` of the window whose span is open on the
    calling thread (``WindowTrace.annotate``); nothing open, nothing
    done."""
    top = current()
    if top is not None:
        top._trace.annotate(**kv)


class adopt:
    """Make ``ctx`` (a span open on another thread) the innermost open
    span of this thread for the length of a ``with`` block: the device
    watchdog runs the close on an abandonable thread of its own and the
    streaming feeder each drain's feed, and the aggregator's children
    belong under the profiler's ``close`` and the feeder's
    ``stream_feed``. The caller of an abandonable call wraps its thunk
    in this (utils/bounded.py knows no tracer).

    Where the adopted span reads its thread's CPU (``usage=True``), so
    does this thread at both ends of the block, and the difference is
    added to the adopted span and to every span open around it:
    ``stream_feed`` and the ``drain`` it lies in hold their
    ``stream-feed`` threads' CPU. A block that outlives its span adds
    to nothing."""

    __slots__ = ("_ctx", "_u0")

    def __init__(self, ctx: _SpanCtx | None):
        self._ctx = ctx
        self._u0 = None

    def __enter__(self):
        ctx = self._ctx
        if ctx is not None:
            _stack().append(ctx)
            try:
                if ctx._u0 is not None \
                        and ctx._tid != threading.get_ident():
                    self._u0 = time.thread_time()
            except Exception as e:  # noqa: BLE001 - tracing is fail-open
                ctx._trace._rec._record_error(e)
        return self

    def __exit__(self, et, ev, tb):
        ctx = self._ctx
        if ctx is not None:
            if self._u0 is not None:
                try:
                    cpu_s = time.thread_time() - self._u0
                    with _adopt_lock:
                        up = ctx
                        while up is not None:
                            up._adopted += cpu_s
                            up._threads += 1
                            up = up._up
                except Exception as e:  # noqa: BLE001 - fail-open
                    ctx._trace._rec._record_error(e)
            stack = _stack()
            if ctx in stack:
                stack.remove(ctx)
        return False
