"""The CPU profiler actor: the 10-second iteration loop.

Role of the reference's pkg/profiler/cpu/cpu.go Run + obtainProfiles
(cpu.go:189-384): every profiling duration, drain the capture source into
a WindowSnapshot, aggregate (pluggable backend — the north-star seam),
symbolize kernel/JIT frames, label, encode pprof, write, and kick off
debuginfo uploads. An iteration failure is non-fatal: logged, surfaced via
last_error, and the loop continues (cpu.go:326-330, SURVEY.md section 5.3).

The capture source protocol is `poll() -> WindowSnapshot | None` (replay,
synthetic, or live sampler); `None` ends the run loop — the replay-driven
agent exits cleanly after the last window, the live sampler never returns
None while running.
"""

from __future__ import annotations

import dataclasses
import gc
import threading
import time
from typing import Callable, Protocol

from parca_agent_tpu.aggregator.base import Aggregator, PidProfile
from parca_agent_tpu.capture.formats import WindowSnapshot
from parca_agent_tpu.pprof.builder import build_pprof
from parca_agent_tpu.runtime import device_telemetry as dtel
from parca_agent_tpu.runtime.quarantine import apply_ladder
from parca_agent_tpu.runtime import trace as trace_mod
from parca_agent_tpu.runtime.trace import NULL_TRACE
from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.log import get_logger

_log = get_logger("profiler")


class CaptureSource(Protocol):
    def poll(self) -> WindowSnapshot | None: ...


@dataclasses.dataclass
class ProfilerMetrics:
    """Counter names mirror the reference's observable metric contract
    (pkg/profiler/cpu/metrics.go:22-65, SURVEY.md section 5.5)."""

    attempts_total: int = 0
    errors_total: int = 0
    profiles_written: int = 0
    samples_aggregated: int = 0
    last_attempt_duration_s: float = 0.0
    last_symbolize_duration_s: float = 0.0
    last_aggregate_duration_s: float = 0.0
    # Encode-path observability (fast_encode mode): how long the last
    # window's pprof encode took (on whichever thread ran it), how many
    # windows hit the pipeline's backpressure fallback, and how many
    # inline encodes were abandoned at the soft deadline.
    last_encode_duration_s: float = 0.0
    encode_backpressure_total: int = 0
    encode_deadline_hits_total: int = 0
    # Abandoned-device-call accounting: how many watchdogged calls that
    # were abandoned at their deadline eventually RETURNED, and how they
    # ended. An abandoned call that later fails used to set box["err"]
    # into the void — now it is logged and counted here.
    device_abandoned_ok_total: int = 0
    device_abandoned_err_total: int = 0
    # The loop's wait between windows, measured: how much later than
    # asked the next window began, summed over the windows of run().
    loop_overshoot_seconds_total: float = 0.0
    # The boundary collection (manage_gc, _collect_gc): wall time inside
    # it, how many ran on the encode worker after their window's ship
    # and how many on the capture loop, and the objects they collected.
    gc_collect_seconds_total: float = 0.0
    gc_collections_worker_total: int = 0
    gc_collections_loop_total: int = 0
    gc_collected_objects_total: int = 0
    # What the ship's gzip did (agent/writer.py): static pieces spliced
    # from the encoder's cache or built anew, bytes that went through
    # deflate, and splices that fell back to a plain gzip.compress.
    ship_static_reused_total: int = 0
    ship_static_built_total: int = 0
    ship_deflated_bytes_total: int = 0
    ship_gzip_fallbacks_total: int = 0


class CPUProfiler:
    name = "cpu"

    def __init__(
        self,
        source: CaptureSource,
        aggregator: Aggregator,
        symbolizer=None,
        labels_manager=None,
        profile_writer=None,
        debuginfo=None,
        duration_s: float = 10.0,
        fallback_aggregator: Aggregator | None = None,
        on_iteration: Callable[[int], None] | None = None,
        device_timeout_s: float = 60.0,
        first_device_timeout_s: float | None = None,
        device_retry_windows: int = 30,
        manage_gc: bool = False,
        window_sink: Callable[[WindowSnapshot], None] | None = None,
        fast_encode: bool = False,
        streaming_feeder=None,
        encode_pipeline: bool = False,
        encode_deadline_s: float | None = None,
        quarantine=None,
        admission=None,
        identity=None,
        device_health=None,
        statics_store=None,
        statics_snapshot_every: int = 6,
        statics_cache_bytes: int = 256 << 20,
        trace_recorder=None,
        hotspot_store=None,
        sinks=None,
        regression=None,
    ):
        self._source = source
        self._aggregator = aggregator
        # Window flight recorder (runtime/trace.py): one trace per
        # window, spans recorded here, in the encode pipeline's worker,
        # and in the encoder. Tracing is fail-open by contract — every
        # recorder entry point swallows its own errors — so nothing in
        # this file guards a tracing call with anything heavier than the
        # NULL_TRACE default.
        self._recorder = trace_recorder
        # Ingest containment (runtime/quarantine.py): the profiler owns
        # the window clock, so it ticks the registry once per iteration
        # and routes aggregated profiles down the degradation ladder
        # before symbolize/write. The same registry instance is shared
        # with the capture source, the feeder, the symbolizer, and the
        # unwind builder — one budget per pid across every ingest site.
        self._quarantine = quarantine
        # Multi-tenant admission (runtime/admission.py): the profiler
        # owns the window clock here too — each window's snapshot usage
        # is charged to its tenants at the top of the iteration, the
        # controller ticks beside the quarantine registry, and the
        # governor reads this loop's own overload signals (close
        # latency, registry rows, encode backpressure). Both entry
        # points are fail-open by the controller's own contract, so the
        # calls ride unguarded.
        self._admission = admission
        # Generation-stamped process identity (process/identity.py):
        # observed once per window, before accounting/aggregation, so a
        # recycled pid invalidates its dead predecessor's state instead
        # of inheriting it.
        self._identity = identity
        # Fast write path: aggregate counts + vectorized template encoder,
        # no per-pid PidProfile objects or scalar pprof serialization on
        # the hot loop. Profiles ship unsymbolized (the reference agent's
        # contract too — the server symbolizes), so it excludes a local
        # symbolizer.
        self._encoder = None
        if fast_encode:
            if symbolizer is not None:
                raise ValueError(
                    "fast_encode ships unsymbolized profiles; it cannot be "
                    "combined with a local symbolizer")
            if not hasattr(aggregator, "window_counts"):
                raise ValueError(
                    "fast_encode requires a dict-style aggregator "
                    "(window_counts/close_window protocol)")
            from parca_agent_tpu.pprof.window_encoder import WindowEncoder

            self._encoder = WindowEncoder(
                aggregator, statics_cache_bytes=statics_cache_bytes)
        # Encode pipeline: window close hands the aggregated counts to a
        # dedicated encoder thread, so capture of window N+1 overlaps
        # encoding/shipping of window N and the encoder's slow transients
        # (cold statics, post-rotation rebuilds) never stall the capture
        # loop. Inline soft deadline: without the pipeline, an encode
        # slower than encode_deadline_s is abandoned to a daemon thread
        # and the window ships via the scalar fallback.
        self._pipeline = None
        # Warm statics + registry snapshot (pprof/statics_store.py): the
        # encode worker persists the statics state on the window clock so
        # a restart adopts instead of cold-building; the capture thread
        # never touches the file. Snapshotting therefore requires the
        # pipeline — without a worker there is no thread that may safely
        # serialize the encoder's statics map off the capture path.
        self._statics_store = statics_store
        # Hotspot rollups (runtime/hotspots.py): each shipped window is
        # folded into mergeable sketch+top-K summaries ON THE ENCODE
        # WORKER — the read path (/hotspots) must add zero work to the
        # capture/close thread, so without the pipeline there is no
        # thread the fold may ride and the store stays unfed.
        self._hotspots = hotspot_store
        if hotspot_store is not None and labels_manager is not None \
                and hotspot_store.labels_for is None:
            hotspot_store.labels_for = self._locked_labels_for
        # Regression sentinel (runtime/regression.py): the judgment
        # rider on the same worker-thread fold clock — each shipped
        # window is attributed by (leaf build-id, tenant) and diffed
        # against frozen baselines. Fail-open inside the sentinel
        # itself (counted fold_errors), so it shares the rollup hook
        # without changing the hotspot fold's re-raise contract.
        self._regression = regression
        if regression is not None and labels_manager is not None \
                and regression.labels_for is None:
            regression.labels_for = self._locked_labels_for
        # Output-backend sinks (sinks/, docs/sinks.md): the registry
        # replaces the hardwired pprof ship with a fan-out whose primary
        # (pprof) IS the pre-sink write path bound below — bytes stay
        # identical — and whose secondaries (autofdo/series) consume the
        # prepared window under the counted fail-open contract. Pipelined
        # windows fan out on the encode worker (emit_window); inline-
        # fallback windows fan out on this thread (_emit_sinks_inline);
        # scalar-path windows are counted as skipped — no prepared rows
        # exist for a sink to read.
        self._sinks = sinks
        if sinks is not None:
            if self._encoder is None:
                raise ValueError("sinks require fast_encode (the sink "
                                 "fan-out reads prepared windows)")
            sinks.bind(ship=self._write_encoded,
                       labels_for=(self._locked_labels_for
                                   if labels_manager is not None else None))
            # Opt the encoder into the inline-path prep stash only when
            # someone will read it — without secondaries it would just
            # pin each window's prepared arrays for nothing.
            self._encoder.track_prep = sinks.has_secondary
        if encode_pipeline:
            if self._encoder is None:
                raise ValueError("encode_pipeline requires fast_encode")
            from parca_agent_tpu.profiler.encode_pipeline import (
                EncodePipeline,
            )

            snapshot = None
            if statics_store is not None:
                snapshot = (lambda period_ns: statics_store.save(
                    self._aggregator, self._encoder, period_ns))
            self._pipeline = EncodePipeline(
                self._encoder, ship=self._ship_encoded,
                snapshot=snapshot,
                snapshot_every=(statics_snapshot_every
                                if statics_store is not None else 0),
                rollup=(self._rollup_window
                        if hotspot_store is not None
                        or regression is not None else None),
                rollup_capture=(self._rollup_capture
                                if hotspot_store is not None
                                or regression is not None else None),
                # The sink context is the same rotation-consistent
                # RegistryView the rollup capture produces; reusing the
                # hook keeps one definition of "safe to read off-thread".
                sink_capture=(self._rollup_capture
                              if sinks is not None
                              and sinks.has_secondary else None),
                # A window handed over here is collected where it ends:
                # on the worker, after its ship and the hooks above
                # (_manage_gc).
                after_window=((lambda: self._collect_gc("worker"))
                              if manage_gc else None))
        else:
            if statics_store is not None:
                _log.warn("statics snapshotting needs the encode pipeline; "
                          "snapshots disabled (adoption still works)")
            if hotspot_store is not None:
                _log.warn("hotspot rollups need the encode pipeline; "
                          "windows will not be folded")
            if regression is not None:
                _log.warn("the regression sentinel needs the encode "
                          "pipeline; windows will not be judged")
        self._encode_deadline = encode_deadline_s
        self._encode_inflight = None   # abandoned inline deadline encode
        self._encode_abandoned = None  # its result box (error inspection)
        # Writes can come from the profiler thread (inline/scalar paths)
        # AND the pipeline's worker (shipping window N while window N+1
        # falls back inline): one lock serializes writer + label lookups
        # + the written-profiles counter.
        self._write_mu = threading.Lock()
        # Streaming mode: drains were fed to the device during the window
        # (profiler/streaming.py); close replaces the one-shot aggregate
        # when the feeder confirms it saw the whole window.
        if streaming_feeder is not None and self._encoder is None:
            raise ValueError("streaming_feeder requires fast_encode")
        if streaming_feeder is not None \
                and hasattr(streaming_feeder, "attach_encoder"):
            # Statics amortization: the feeder prebuilds pprof static
            # sections (budgeted) after each drain feed, so the close-time
            # encode's statics transient is bounded even on a cold first
            # window at large pid populations. With the pipeline on, the
            # budgeted build runs on the ENCODER thread (the encoder's
            # thread-ownership contract); inline it runs on the polling
            # thread as before.
            if self._pipeline is not None:
                streaming_feeder.attach_encoder(
                    self._encoder, prebuild=self._pipeline.request_prebuild)
            else:
                streaming_feeder.attach_encoder(self._encoder)
            # While an abandoned AGGREGATION call (hang watchdog, below)
            # may still be executing inside take_window_if_complete() /
            # window_counts(), it shares registry state the encoder
            # reads; gate the feeder's polling-thread touches on it.
            # Likewise an inline encode abandoned at its soft deadline
            # still owns the encoder's mirrors until it returns.
            # And while the device is not trusted (bring-up probe in
            # flight, demoted, shadow pending) a drain must not feed at
            # all: the feed would be the process's first JAX touch, and
            # a probe child may be holding the chip at that very moment.
            streaming_feeder.external_blocked = (
                lambda: (self._device_inflight is not None
                         and not self._device_inflight.is_set())
                or (self._encode_inflight is not None
                    and not self._encode_inflight.is_set())
                or (self._health is not None
                    and self._health.window_mode() != "device"))
        self._feeder = streaming_feeder
        self._fallback = fallback_aggregator
        self._device_timeout = device_timeout_s
        # The process's FIRST guarded device call is not a device wait:
        # it is XLA compiling the feed/close programs plus the host
        # inserting the whole stack population (aggregator/dict.py
        # _resolve_misses) — at the flagship size that alone outlasts
        # the steady-state bound, and tripping it demotes a healthy
        # device on every cold start. Same repair as the streaming
        # feeder's first feed (profiler/streaming.py): the long budget
        # applies exactly once; if that attempt still overruns, every
        # later call runs under the steady-state bound, which is never
        # raised. None (embedders, tests) = no separate first budget.
        self._next_device_timeout = max(
            device_timeout_s, first_device_timeout_s or device_timeout_s)
        self._device_timeout_used = device_timeout_s  # for the hang logs
        # Device lifecycle state lives in ONE place: the health registry
        # (runtime/device_health.py) owns wedge accounting, cooldowns,
        # the probing/healthy/degraded/dead machine, and the shadow-
        # window promotion gate. The CLI passes a probe-armed registry;
        # embedders get a probe-less default that reproduces the old
        # retry-after-N-windows semantics (cooldown expiry goes straight
        # to the shadow window).
        self._health = device_health
        if self._health is None and fallback_aggregator is not None:
            from parca_agent_tpu.runtime.device_health import (
                STATE_HEALTHY,
                DeviceHealthRegistry,
            )

            self._health = DeviceHealthRegistry(
                probe=None, promote_after=0,
                cooldown_windows=device_retry_windows,
                start_state=STATE_HEALTHY)
        # The abandoned in-flight device call (a wedged call may still be
        # executing inside the aggregator — nothing touches it until the
        # event fires) and its result box, inspected once on completion.
        self._device_inflight = None
        self._device_abandoned: dict | None = None
        self._windows_seen = 0
        self._symbolizer = symbolizer
        self._labels = labels_manager
        self._writer = profile_writer
        self._debuginfo = debuginfo
        self._duration = duration_s
        # Process-global GC stewardship (freeze + explicit boundary
        # collects): only the process owner (the agent CLI) should turn
        # this on; embedders keep CPython's default scheduler.
        self._manage_gc_enabled = manage_gc
        # The collector's state is written by the capture thread (the
        # loop arm, the disable, the restore) and by the encode worker
        # (a pipelined window's collection): one lock, held through a
        # collection, so a restore never lands inside one.
        self._gc_mu = threading.Lock()
        # Did this iteration's window go to the encode pipeline.
        self._window_piped = False
        # Optional tee of each window's snapshot (the fleet merger feeds
        # on it); failures there must not fail the iteration.
        self._window_sink = window_sink
        self._on_iteration = on_iteration
        self._stop = threading.Event()
        self.metrics = ProfilerMetrics()
        self.last_error: Exception | None = None
        self.last_profile_started_at: float = 0.0
        # pid -> profiled-ok flag for the status page (reference
        # processLastErrors, cpu.go:461-471).
        self.process_last_errors: dict[int, Exception | None] = {}

    # -- one iteration ------------------------------------------------------

    def obtain_profiles(self, snapshot: WindowSnapshot) -> list[PidProfile]:
        """Aggregate with the configured backend; fall back to the CPU path
        when the device backend fails OR HANGS (SURVEY.md section 7 hard
        part #5: device trouble must not stall the capture loop — and a
        wedged device runtime blocks inside a C call no exception ever
        leaves, observed as multi-minute backend-init hangs on real
        hardware). With a fallback configured, device aggregation runs on
        a watchdog thread bounded by device_timeout_s; on timeout the
        window is aggregated on the CPU and the device-health registry
        demotes the backend — re-trusted only after its cooldown, its
        probe gate, AND one shadow window whose device result matches
        the CPU fallback (and never while the abandoned call may still
        be executing inside the aggregator)."""
        t0 = time.perf_counter()
        self._windows_seen += 1
        # Device failures are handled (and logged as such) inside
        # _aggregate_guarded; an exception escaping it is a FALLBACK (or
        # no-fallback) failure and must propagate as an iteration error —
        # re-running the fallback here would double the work and blame
        # the wrong backend in the log.
        profiles = self._aggregate_guarded(snapshot)
        self.metrics.last_aggregate_duration_s = time.perf_counter() - t0
        return profiles

    def _aggregate_guarded(self, snapshot: WindowSnapshot):
        return self._guarded(lambda: self._aggregator.aggregate(snapshot),
                             lambda: self._fallback.aggregate(snapshot))

    @property
    def _device_wedged_at(self):
        """Window index of the hang the device is currently demoted for
        (None while trusted) — kept for tests and the status page; the
        registry is the single owner of the state."""
        return self._health.wedged_at if self._health is not None else None

    def _inspect_abandoned(self) -> None:
        """An abandoned device call that finally RETURNED: its outcome
        used to be silently discarded (an error set into box["err"] after
        the timeout went nowhere). Inspect it exactly once — log the
        late failure, count ok/err — and release the inflight gate."""
        done = self._device_inflight
        if done is None or not done.is_set():
            return
        box = self._device_abandoned or {}
        if "err" in box:
            self.metrics.device_abandoned_err_total += 1
            _log.warn("abandoned device call completed with an error",
                      aggregator=type(self._aggregator).__name__,
                      error=repr(box["err"]))
        else:
            self.metrics.device_abandoned_ok_total += 1
            _log.info("abandoned device call completed",
                      aggregator=type(self._aggregator).__name__)
        self._device_inflight = None
        self._device_abandoned = None

    def _device_call_clear(self) -> bool:
        return self._device_inflight is None \
            or self._device_inflight.is_set()

    def _watchdog_call(self, thunk):
        """Run thunk under the abandonable bounded-call guard
        (utils/bounded.py) with the device timeout. Returns
        ("ok", out) | ("err", exc) | ("hang", None); a hang leaves the
        call registered as in-flight (the aggregator's state is not
        touched while it may still be executing inside it)."""
        from parca_agent_tpu.utils.bounded import bounded_call

        # The call runs on a thread of its own; the aggregator's child
        # spans belong under the span open here (the window's close).
        open_span = trace_mod.current()

        def site():
            with trace_mod.adopt(open_span):
                faults.inject("device.dispatch")
                # First device touch after a bring-up that had failed:
                # learn the backend here, inside the guard (no-op once
                # claimed).
                self._health.claim_backend()
                return thunk()

        self._device_timeout_used = self._next_device_timeout
        self._next_device_timeout = self._device_timeout
        status, out, done, box = bounded_call(
            site, self._device_timeout_used, thread_name="aggregate-device")
        if status == "hang":
            self._device_inflight = done
            self._device_abandoned = box
        return status, out

    @staticmethod
    def _shadow_match(dev_out, cpu_out) -> bool:
        """Promotion-gate A/B: does the device result agree with the CPU
        fallback's? Profile lists compare per-pid (mass, unique-stack
        count) digests; the fast path's raw counts compare total window
        mass."""
        def norm(o):
            if isinstance(o, tuple) and len(o) == 2 \
                    and isinstance(o[0], str):
                kind, payload = o
                if kind == "counts":
                    import numpy as np

                    return int(np.asarray(payload).astype(np.int64).sum())
                return payload
            return o

        a, b = norm(dev_out), norm(cpu_out)
        if isinstance(a, int) or isinstance(b, int):
            def mass(x):
                return x if isinstance(x, int) \
                    else sum(int(p.total()) for p in x)

            return mass(a) == mass(b)
        from parca_agent_tpu.runtime.device_health import shadow_compare

        return shadow_compare(a, b)

    def _guarded(self, thunk, fallback_thunk):
        """Run thunk on the device backend under the hang watchdog and
        the health registry's demote/promote supervision; fallback_thunk
        while degraded or on failure/hang (see _aggregate_guarded docs).
        Promotion back to the device passes through one SHADOW window:
        both backends aggregate, the results must match, and the window
        ships the CPU result either way."""
        if self._fallback is None:
            return thunk()
        self._inspect_abandoned()
        mode = self._health.window_mode()
        if mode != "fallback" and not self._device_call_clear():
            # The abandoned call still owns the aggregator's state: no
            # device touch (not even a shadow) until it returns.
            mode = "fallback"
        if mode == "fallback":
            self._health.record_fallback_window()
            return fallback_thunk()

        status, out = self._watchdog_call(thunk)

        if mode == "shadow":
            cpu_out = fallback_thunk()
            if status == "hang":
                _log.error("device hung during its shadow window; "
                           "re-demoting",
                           timeout_s=self._device_timeout_used)
                self._health.record_hang()
            else:
                matched = status == "ok" \
                    and self._shadow_match(out, cpu_out)
                err = repr(out)[:200] if status == "err" else ""
                self._health.record_shadow(matched, error=err)
            return cpu_out

        if status == "ok":
            self._health.record_dispatch_ok()
            return out
        if status == "err":
            _log.warn("device aggregation failed; using CPU fallback",
                      aggregator=type(self._aggregator).__name__,
                      error=repr(out))
            self._health.record_dispatch_error(out)
        else:
            _log.error(
                "device aggregation hung; abandoning call and using the "
                "CPU fallback",
                aggregator=type(self._aggregator).__name__,
                timeout_s=self._device_timeout_used)
            self._health.record_hang()
        # Counted like a planned fallback window: the window ships from
        # the CPU either way, and "0 fallback windows" has to mean it.
        self._health.record_fallback_window()
        return fallback_thunk()

    def run_iteration(self) -> bool:
        """Returns False when the source is exhausted."""
        t_iter0 = time.perf_counter()
        self._window_piped = False
        tr = (self._recorder.begin() if self._recorder is not None
              else NULL_TRACE)
        if self._loop_waited is not None:
            # run() waited before this window: from the end of the
            # iteration before to this trace's begin, less what the wait
            # was asked for, is how late the loop came back (a thread
            # that was not scheduled, a pause of the whole process).
            t_end, wait_s = self._loop_waited
            self._loop_waited = None
            began = tr.t0_monotonic_s if tr is not NULL_TRACE \
                else time.monotonic()
            late = max(0.0, began - t_end - wait_s)
            self.metrics.loop_overshoot_seconds_total += late
            tr.annotate(loop_wait_s=round(wait_s, 6),
                        loop_overshoot_s=round(late, 6))
        try:
            with tr.span("drain", usage=True):
                snapshot = self._source.poll()
        except Exception as e:
            # Capture trouble is non-fatal, like any other iteration error
            # (cpu.go:326-330): a transient drain failure must not kill the
            # agent. run() waits out the rest of the window, a natural
            # backoff before the retry.
            self.last_error = e
            self.metrics.errors_total += 1
            _log.warn("capture poll failed; retrying next window",
                      error=repr(e))
            tr.finish(error=repr(e)[:200])
            return True
        if snapshot is None:
            tr.discard()  # never a window: not ringed, not histogrammed
            return False
        self.last_profile_started_at = time.time()
        self.metrics.attempts_total += 1
        if self._identity is not None:
            # Generation-stamped identity check BEFORE accounting and
            # aggregation: a recycled pid's stale tenant/quarantine/
            # registry state must be invalidated before any of the new
            # generation's samples resolve through it (fail-open by the
            # tracker's own contract — see process/identity.py).
            with tr.span("identity"):
                self._identity.observe_window(snapshot.pids)
        if self._admission is not None:
            # Per-tenant usage accounting BEFORE the close: the ladder
            # levels this window's profiles ride were set by last tick
            # (admission reacts on the window clock, one window behind —
            # the same cadence as quarantine cooldowns).
            with tr.span("admission"):
                self._admission.account_window(snapshot.pids,
                                               snapshot.counts)
        tr.annotate(time_ns=snapshot.time_ns,
                    samples=int(snapshot.total_samples()),
                    rows=len(snapshot))
        t_start = time.perf_counter()
        try:
            if self._encoder is not None:
                n_pids = self._aggregate_encode_write(snapshot, tr)
            else:
                # Scalar path spans: close (aggregate), symbolize, ship.
                # The close gauge is set FROM the span duration so the
                # last-value gauge and the histogram can never disagree.
                with tr.span("close") as sp_close:
                    profiles = self.obtain_profiles(snapshot)
                self.metrics.last_aggregate_duration_s = sp_close.duration_s
                self.metrics.samples_aggregated += snapshot.total_samples()

                # Degradation ladder first (level-1 pids lose local
                # symbols, level-2 pids collapse to scalar counts), then
                # symbolize — which itself skips laddered pids, so a
                # degraded profile can never be re-symbolized.
                profiles = apply_ladder(profiles, self._quarantine,
                                        self._admission)

                if self._symbolizer is not None:
                    with tr.span("symbolize") as sp_sym:
                        self._symbolizer.symbolize(profiles)
                    self.metrics.last_symbolize_duration_s = \
                        sp_sym.duration_s

                with tr.span("ship", usage=True):
                    self._write_profiles(profiles)
                n_pids = len(profiles)
                tr.annotate(pids=n_pids, path="scalar")

            if self._debuginfo is not None:
                objs = []
                mt = snapshot.mappings
                for i, path in enumerate(mt.obj_paths):
                    bid = mt.obj_buildids[i] if i < len(mt.obj_buildids) else ""
                    rows = (mt.objs == i).nonzero()[0]
                    if len(rows) and path:
                        pid = int(mt.pids[rows[0]])
                        objs.append((pid, path, bid))
                self._debuginfo.ensure_uploaded(objs)
            if self._window_sink is not None:
                try:
                    self._window_sink(snapshot)
                except Exception as e:  # noqa: BLE001 - tee must not fail us
                    _log.warn("window sink failed", error=repr(e))
            self.last_error = None
            _log.debug("window aggregated",
                       pids=n_pids,
                       samples=int(snapshot.total_samples()))
        except Exception as e:  # non-fatal (cpu.go:326-330)
            self.last_error = e
            self.metrics.errors_total += 1
            _log.warn("profile iteration failed", error=repr(e))
            tr.finish(error=repr(e)[:200])
        # Pipelined windows detached their trace (the encode worker
        # completes it after the ship); everything else finishes here.
        tr.finish()
        if self._quarantine is not None:
            # Quarantine time is window time: cooldown/probation advance
            # once per iteration, whether or not the window shipped.
            self._quarantine.tick_window()
        if self._admission is not None:
            # Admission rides the same clock: buckets refill, ladder
            # levels adjust, and the overload governor judges THIS
            # window's close latency / registry growth / encode
            # backpressure (tick_window is fail-open by contract).
            self._admission.tick_window(
                close_latency_s=self.metrics.last_aggregate_duration_s,
                registry_rows=int(
                    getattr(self._aggregator, "_next_id", 0) or 0),
                backlog=(self._pipeline.stats["backpressure_fallbacks"]
                         if self._pipeline is not None else 0))
        if self._health is not None:
            # Same clock for the device-backend state machine: demote
            # cooldowns and re-probe scheduling advance per window.
            self._health.tick_window()
        self.metrics.last_attempt_duration_s = time.perf_counter() - t_start
        # Window-SLO accounting (runtime/device_telemetry.py): the
        # capture thread's busy wall for this window — drain through
        # hand-off plus the per-window ticks above — judged against the
        # configured period. run() sleeps out the remainder, so this is
        # the window's whole non-idle cost on this thread; off-thread
        # kernel seconds are folded in by the telemetry layer itself.
        dtel.tick_window(time.perf_counter() - t_iter0)
        self._manage_gc()
        if self._on_iteration is not None:
            self._on_iteration(self.metrics.attempts_total)
        return True

    # CPython gen-2 collections scan every tracked object; the aggregator
    # mirror holds millions of long-lived ones (stack-key tuples, per-id
    # location lists), so an automatic pass costs hundreds of ms and can
    # land in the middle of a window close (the Go reference never has
    # this problem — its GC is concurrent). Policy: DISABLE the automatic
    # scheduler at the end of the first iteration and collect explicitly,
    # once a window, where that window ends. A full collection is one C
    # call that keeps the interpreter lock from its first object to its
    # last, so "where the window ends" is where nothing of it is in
    # flight any more. For a window handed to the encode pipeline (path
    # "pipeline") that is the encode worker, after the window's ship and
    # after-ship hooks (EncodePipeline after_window): at the end of
    # run_iteration its encode has only just been handed over, and a
    # collection there is time the prepared window waits for the lock
    # (the encode_wait span) or its encode stands still for. For every
    # other window (no pipeline, pipeline disabled, inline, scalar
    # fallback or backpressure, an iteration error) encode and ship are
    # over when run_iteration ends, and it is collected there. The run's
    # first collection also freezes the warm state into the permanent
    # generation (excluded from all collection): with a pipeline that is
    # after the first window's cold encode and ship, so the templates,
    # the per-pid statics, the label sets and the gzip pieces are in it,
    # and a later collection walks only what its window allocated plus
    # registry growth since the last refreeze. Every _GC_REFREEZE
    # collections (~1 h), unfreeze + full-collect + refreeze so garbage
    # that slipped into the frozen set is reclaimed.
    _GC_REFREEZE = 360

    _gc_modified = False    # the collector is disabled and/or frozen by us
    _gc_closed = False      # run() ended: a late worker leaves it alone
    _gc_collections = 0     # since this run's first collect-and-freeze

    def _restore_gc(self) -> None:
        """Undo the stewardship on shutdown: the process may outlive the
        profiler (embedding tests, supervised restarts) and must get the
        default collector back. After a crash the pipeline's worker may
        still be on a window: its collection then finds the run closed."""
        with self._gc_mu:
            self._gc_closed = True
            self._gc_collections = 0
            if not self._gc_modified:
                return
            self._gc_modified = False
            gc.unfreeze()
            gc.enable()

    def _manage_gc(self) -> None:
        """End of an iteration, capture thread: collect for a window that
        ended here, and keep the automatic scheduler off."""
        if not self._manage_gc_enabled:
            return
        if not self._window_piped:
            self._collect_gc("loop")
        with self._gc_mu:
            if not self._gc_closed:
                # From the end of the first managed iteration of THIS
                # run (not of the process): a supervised restart
                # re-enters run() after the crash path restored the
                # default collector, and re-arms here.
                gc.disable()
                self._gc_modified = True

    def _collect_gc(self, where: str) -> None:
        """One boundary collection: on the capture thread ("loop") or on
        the encode worker after a pipelined window's ship ("worker")."""
        with self._gc_mu:
            if self._gc_closed:
                return
            t0 = time.perf_counter()
            refreeze = self._gc_collections % self._GC_REFREEZE == 0
            if refreeze and self._gc_collections:
                gc.unfreeze()
            n = gc.collect()
            if refreeze:
                gc.freeze()
                self._gc_modified = True
            self._gc_collections += 1
            m = self.metrics
            m.gc_collect_seconds_total += time.perf_counter() - t0
            m.gc_collected_objects_total += n
            if where == "worker":
                m.gc_collections_worker_total += 1
            else:
                m.gc_collections_loop_total += 1

    def _labels_for(self, pid: int) -> dict | None:
        """Label set for a pid; None when relabeling dropped the target."""
        if self._labels is not None:
            return self._labels.label_set("parca_agent_cpu", pid)
        return {"__name__": "parca_agent_cpu", "pid": str(pid)}

    def _locked_labels_for(self, pid: int) -> dict | None:
        """Label lookup under the write lock — the same serialization
        _write_one uses, so the rollup fold (encode worker) and the ship
        paths never race the labels manager's caches."""
        with self._write_mu:
            return self._labels_for(pid)

    # palint: fail-open=caller — the pipeline's hand-off guard counts
    # rollup_errors and ships the window unfolded; swallowing here would
    # leave that exported counter dark.
    def _rollup_capture(self, prep):
        """EncodePipeline rollup-capture hook (PROFILER thread, at window
        hand-off): snapshot the per-id mirror references the fold will
        read, before the next window's first feed can rotate them."""
        from parca_agent_tpu.runtime.hotspots import RegistryView

        return RegistryView(self._aggregator)

    # palint: fail-open=caller — fold_from_aggregator counts fold_errors
    # and RE-RAISES by contract, for the pipeline's worker guard to
    # count rollup_errors; both counters are exported on /metrics.
    def _rollup_window(self, prep, ctx) -> None:
        """EncodePipeline rollup hook (worker thread): fold the shipped
        window's live (id, count) rows into the hotspot store, reading
        per-id state only through the hand-off-time registry view; then
        hand the same view to the regression sentinel. The sentinel
        rides in the finally arm (its fold is internally fail-open and
        never raises), so a hotspot fold failure — which must propagate
        for the pipeline's rollup_errors counter — cannot starve the
        window's judgment."""
        try:
            if self._hotspots is not None:
                self._hotspots.fold_from_aggregator(
                    ctx, prep.idx, prep.vals, prep.time_ns,
                    prep.duration_ns)
        finally:
            if self._regression is not None:
                self._regression.fold_from_prepared(ctx, prep)

    def _write_one(self, pid: int, payload, tally: list) -> bool:
        """Labels lookup + write + bookkeeping for one profile; False when
        relabeling dropped the target. `payload` is a zero-arg callable so
        dropped targets never pay the serialization. `tally[0]` gathers
        the seconds of the label lookup with its lock (the ship's
        ``ship_labels`` child, _write_all). Called from the
        profiler thread (inline/scalar paths) or the pipeline's worker;
        the write lock covers only the shared mutable state (label-cache
        lookup, written counter) — serialization/gzip and writer.write
        run outside it, so a worker-side ship never stalls the capture
        thread's fallback writes behind a multi-MB gzip (writers tolerate
        concurrent write(): FileProfileWriter is one open/write per call,
        RemoteProfileWriter's gzip is pure and its sink buffer locked)."""
        try:
            t0 = time.monotonic()
            with self._write_mu:
                labels = self._labels_for(pid)
            tally[0] += time.monotonic() - t0
            if labels is None:
                self.process_last_errors[pid] = None
                return False  # relabeling dropped this target
            if self._writer is not None:
                self._writer.write(labels, payload())
            with self._write_mu:
                self.metrics.profiles_written += 1
            self.process_last_errors[pid] = None
            return True
        except Exception as e:
            self.process_last_errors[pid] = e
            raise

    def _write_profiles(self, profiles) -> int:
        """Ship PidProfiles through the scalar builder."""
        # compress=False: the writer owns gzip framing (gzipping here too
        # double-compressed every profile).
        return self._write_all(
            (p.pid, lambda p=p: build_pprof(p, compress=False))
            for p in profiles)

    def _write_encoded(self, out) -> int:
        """Ship [(pid, blob)] from the fast encoder through the writer.
        Where the encoder's output knows its blobs' static spans (a
        pipelined window's views: window_encoder._SpanViews), each blob
        goes to the writer with its span, so that the writer's gzip can
        splice what did not change (agent/writer.py)."""
        span_blobs = getattr(out, "span_blobs", None)
        return self._write_all(
            (pid, lambda b=blob: b)
            for pid, blob in (out if span_blobs is None else span_blobs()))

    def _write_all(self, items) -> int:
        """One window's ship: every (pid, payload) through _write_one.
        What the ship is made of is summed where the work is (the label
        lookup here, gzip and enqueue in the writer) and recorded once,
        as children of the ship span open on this thread: 12,500 spans
        a window would cost more than they tell."""
        n = 0
        tally = [0.0]
        take = getattr(self._writer, "take_ship_clocks", None)
        try:
            for pid, payload in items:
                if self._write_one(pid, payload, tally):
                    n += 1
        finally:
            trace_mod.note("ship_labels", tally[0], accumulated=True)
            trace_mod.count(profiles=n)
            if take is not None:
                c = take()
                trace_mod.note("ship_gzip", c["gzip_s"], accumulated=True)
                trace_mod.note("ship_enqueue", c["enqueue_s"],
                               accumulated=True)
                counts = {k: c[k] for k in (
                    "pprof_bytes", "gzip_bytes", "gzip_static_reused",
                    "gzip_static_built", "gzip_deflated_bytes",
                    "gzip_fallbacks")}
                trace_mod.count(**counts)
                m = self.metrics
                with self._write_mu:
                    m.ship_static_reused_total += counts["gzip_static_reused"]
                    m.ship_static_built_total += counts["gzip_static_built"]
                    m.ship_deflated_bytes_total += \
                        counts["gzip_deflated_bytes"]
                    m.ship_gzip_fallbacks_total += counts["gzip_fallbacks"]
        return n

    def _ship_encoded(self, out, prep) -> None:
        """EncodePipeline ship hook (worker thread): with sinks
        configured, the registry runs the primary pprof ship (the same
        _write_encoded bound at construction — identical bytes) and
        fans the window out to the secondaries; a secondary failure is
        counted there and never reaches the pipeline's ship guard."""
        if self._sinks is not None:
            self._sinks.emit_window(out, prep)
        else:
            self._write_encoded(out)
        if self._pipeline is not None:
            self.metrics.last_encode_duration_s = \
                self._pipeline.stats["last_encode_s"]

    def _ship_scalar(self, snapshot: WindowSnapshot) -> int:
        """Aggregate + write one window through the scalar path (the
        encode fallback: pipeline backpressure, encoder exceptions, or a
        blown inline deadline)."""
        if self._sinks is not None:
            # No prepared window exists on this path; sinks (secondaries
            # included) cannot see it — counted, so PGO/series coverage
            # gaps during fallback storms are observable.
            self._sinks.count_skipped()
        profiles = self._fallback.aggregate(snapshot)
        self._write_profiles(profiles)
        return len(profiles)

    # palint: fail-open
    def _emit_sinks_inline(self, out, snapshot: WindowSnapshot) -> None:
        """Secondary-sink fan-out for an INLINE-encoded window (profiler
        thread: no pipeline, pipeline disabled, or hand-off refused).
        The pprof bytes already shipped through _write_encoded; here the
        secondaries consume the same prepared rows, with a registry view
        captured on this thread — the thread that runs rotation, so the
        capture cannot race it. Fail-open: a sink bug costs sinks one
        window, never the iteration."""
        try:
            if self._sinks is None or not self._sinks.has_secondary:
                return
            prep = getattr(self._encoder, "last_prep", None)
            if prep is None or prep.time_ns != snapshot.time_ns:
                # The encoder did not stash THIS window (e.g. a custom
                # encode path): skip rather than misattribute.
                self._sinks.count_skipped()
                return
            from parca_agent_tpu.runtime.hotspots import RegistryView

            prep.sink_ctx = RegistryView(self._aggregator)
            self._sinks.emit_secondary(out, prep)
        except Exception as e:  # noqa: BLE001 - sinks are best-effort
            self._sinks.count_capture_error()
            _log.warn("inline sink fan-out failed; window skipped for "
                      "secondary sinks", error=repr(e))

    def _aggregate_encode_write(self, snapshot: WindowSnapshot,
                                tr=NULL_TRACE) -> int:
        """Fast path: counts -> vectorized encoder -> writer, no PidProfile
        materialization. ONLY the device call rides the hang watchdog (on
        failure/hang the CPU fallback aggregates and writes through the
        scalar builder); the encoder is host-side numpy — it cannot hang
        on the device, and its slow transients (a post-rotation template
        rebuild is tens of seconds at 50k pids) must not eat the device
        watchdog's budget and read as a wedged device. An encoder FAILURE
        still falls back to the scalar path for that window."""
        self._windows_seen += 1  # hang-cooldown clock (obtain_profiles' twin)

        def fast():
            if self._feeder is not None and self._feeder.device_blocked():
                # An abandoned streaming feed may still be executing
                # inside the aggregator; touching it now would race the
                # donation contract. Raise into the watchdog machinery:
                # the CPU fallback shares no state with the dict.
                self._feeder.count_fallback("blocked")
                raise RuntimeError(
                    "abandoned streaming feed still in flight")
            counts = None
            if self._feeder is not None:
                counts = self._feeder.take_window_if_complete(snapshot)
            if counts is None:  # not streamed (or incomplete): one-shot
                counts = self._aggregator.window_counts(snapshot)
            return "counts", counts

        def fallback():
            return "prof", self._fallback.aggregate(snapshot)

        # The close span is the guarded device call (streaming: the
        # packed close fetch rides inside take_window_if_complete); its
        # duration also sets the aggregate gauge, so gauge and histogram
        # are the same measurement.
        with tr.span("close") as sp_close:
            kind, out = self._guarded(fast, fallback)
        self.metrics.last_aggregate_duration_s = sp_close.duration_s
        # Why a window left the fast path, for the trace: "device" (the
        # registry planned a fallback window, or the device call failed
        # or hung) vs "encode" (the device answered; the encoder did not).
        fallback_reason = "device" if kind == "prof" else None
        # (A streamed window's feeds are spans of their own, recorded
        # where they ran: stream_feed under drain, once a drain, with the
        # aggregator's feed stages inside it — profiler/streaming.py; its
        # close is this close span.)
        if kind == "counts":
            # (buffer_flip and delta_fetch are children of the close,
            # recorded where they run: dict.py close_dispatch/collect.)
            n_piped = self._submit_to_pipeline(out, snapshot, tr)
            if n_piped is not None:
                self.metrics.samples_aggregated += snapshot.total_samples()
                return n_piped
            try:
                out = self._encode_inline(out, snapshot)
                kind = "enc"
                tr.add_span("encode", self.metrics.last_encode_duration_s)
            except Exception as e:  # noqa: BLE001 - window must still ship
                if getattr(self, "_encode_timed", False):
                    # Only span an encode that actually ran: the
                    # inflight-guard raise happens before any timing and
                    # must not fabricate a sample from the previous
                    # window's gauge value.
                    tr.add_span("encode",
                                self.metrics.last_encode_duration_s,
                                error=repr(e)[:200])
                if self._fallback is None:
                    raise
                _log.warn("fast encode failed; scalar fallback for this "
                          "window", error=repr(e))
                kind, out = fallback()
                fallback_reason = "encode"
        self.metrics.samples_aggregated += snapshot.total_samples()
        if kind == "prof":
            tr.annotate(path="scalar-fallback",
                        fallback_reason=fallback_reason)
            with tr.span("ship", usage=True):
                self._write_profiles(out)
            return len(out)
        tr.annotate(path="inline")
        try:
            with tr.span("ship", usage=True):
                n = self._write_encoded(out)
        finally:
            # Secondaries run even when the pprof write raised (the
            # iteration guard upstream owns that error): a store outage
            # must not starve the PGO loop — the same try/finally the
            # pipelined route's registry fan-out uses.
            if self._sinks is not None:
                self._emit_sinks_inline(out, snapshot)
        return n

    def _submit_to_pipeline(self, counts, snapshot: WindowSnapshot,
                            tr=NULL_TRACE) -> int | None:
        """Try to hand the closed window to the encode pipeline. Returns
        the handed-off pid count, the scalar-fallback profile count when
        backpressure forced an inline ship, or None when the window must
        take the inline encode path (no pipeline / pipeline disabled /
        backpressure without a fallback aggregator). On a successful
        hand-off the window's trace detaches: the worker records the
        encode/ship spans and completes it after the ship."""
        if self._pipeline is None or self._pipeline.disabled:
            return None
        fb = None
        if self._fallback is not None:
            fb = lambda snap=snapshot: self._ship_scalar(snap)  # noqa: E731
        try:
            n = self._pipeline.submit(counts, snapshot.time_ns,
                                      snapshot.window_ns,
                                      snapshot.period_ns, fallback=fb,
                                      trace=tr)
        except Exception as e:  # noqa: BLE001 - window must still ship
            # prepare() died on the profiler thread (e.g. MemoryError
            # growing mirrors): give this window to the inline path,
            # whose own try/except still ends in the scalar fallback.
            _log.warn("pipeline hand-off failed; inline encode for this "
                      "window", error=repr(e))
            return None
        if n is not None:
            tr.annotate(path="pipeline")
            self._window_piped = True
            return n
        # Backpressure: the worker is still encoding the previous window.
        # The encoder's state is its — this window cannot ride it inline,
        # so ship through the scalar path (counted, observable).
        self.metrics.encode_backpressure_total += 1
        if self._fallback is None:
            # No scalar path: wait the worker out (bounded), then retry
            # once — correctness over latency for fallback-less configs.
            self._pipeline.flush(timeout_s=self._encode_deadline or 60.0)
            n = self._pipeline.submit(counts, snapshot.time_ns,
                                      snapshot.window_ns,
                                      snapshot.period_ns, trace=tr)
            if n is None:
                raise RuntimeError(
                    "encode pipeline busy past its flush bound and no "
                    "fallback aggregator is configured")
            tr.annotate(path="pipeline")
            self._window_piped = True
            return n
        _log.warn("encode pipeline busy at window close; scalar fallback "
                  "for this window")
        tr.annotate(path="scalar-backpressure")
        with tr.span("ship", usage=True):
            return self._ship_scalar(snapshot)

    def _encode_inline(self, counts, snapshot: WindowSnapshot):
        """Encode on the profiler thread (no pipeline, or pipeline
        disabled). With encode_deadline_s set, the encode runs on an
        abandonable daemon thread: a pathological transient (a
        post-rotation template rebuild is tens of seconds at 50k pids)
        costs this window a scalar fallback instead of an unbounded
        capture stall — and the abandoned encode keeps warming the
        template for the windows after it."""
        # False until this WINDOW's encode is actually timed: the
        # inflight-guard raise below exits before any timing, and the
        # trace must not record the previous window's duration as this
        # window's errored encode span.
        self._encode_timed = False
        if self._encode_inflight is not None:
            if not self._encode_inflight.is_set():
                # The abandoned encode still owns the encoder's state.
                raise RuntimeError("abandoned encode still in flight")
            if "err" in (self._encode_abandoned or {}):
                # The abandoned encode DIED mid-flight: the template may
                # be half-mutated (same hazard the pipeline's
                # _fail_window resets for). Drop the mirrors before
                # touching the encoder again.
                _log.warn("abandoned encode failed; resetting encoder",
                          error=repr(self._encode_abandoned["err"]))
                self._encoder.reset()
            self._encode_inflight = None
            self._encode_abandoned = None
        t0 = time.perf_counter()
        self._encode_timed = True
        try:
            if self._encode_deadline is None:
                return self._encoder.encode(
                    counts, snapshot.time_ns, snapshot.window_ns,
                    snapshot.period_ns)
            import numpy as np

            from parca_agent_tpu.utils.bounded import bounded_call

            # The aggregator's counts buffer is only valid for one close;
            # an abandoned encode may still be reading after that.
            counts_copy = np.asarray(counts).copy()
            status, out, done, box = bounded_call(
                lambda: self._encoder.encode(
                    counts_copy, snapshot.time_ns, snapshot.window_ns,
                    snapshot.period_ns),
                self._encode_deadline, thread_name="encode-deadline")
            if status == "hang":
                self._encode_inflight = done
                self._encode_abandoned = box
                self.metrics.encode_deadline_hits_total += 1
                raise RuntimeError(
                    f"encode exceeded the soft deadline "
                    f"({self._encode_deadline}s); scalar fallback")
            if status == "err":
                raise out
            return out
        finally:
            self.metrics.last_encode_duration_s = \
                time.perf_counter() - t0

    # -- actor --------------------------------------------------------------

    def run(self) -> None:
        # Re-runnable under supervision: a crashed profiler actor is
        # restarted by the run group, so a successful re-entry clears the
        # previous crash record.
        self.crashed = None
        self._gc_closed = False
        try:
            while not self._stop.is_set():
                t0 = time.monotonic()
                faults.inject("actor.profiler")
                if not self.run_iteration():
                    return
                t_end = time.monotonic()
                wait_s = max(0.0, self._duration - (t_end - t0))
                self._loop_waited = (t_end, wait_s)
                # The wait for the next period, on the device trace's
                # clock: the chip's idle time under it is headroom.
                with trace_mod.annotation("sleep"):
                    self._stop.wait(wait_s)
        except BaseException as e:
            # Anything escaping run_iteration is a bug, not an iteration
            # failure; record it so the CLI can exit nonzero instead of
            # treating thread death as a clean shutdown (and so the
            # supervisor can decide to restart this actor).
            self.crashed = e
            raise
        finally:
            # The pipeline is torn down only on a real exit (stop
            # requested or source exhausted): a supervised restart after
            # a crash must find it alive, not stopped. GC stewardship is
            # ALWAYS restored — the process may outlive a crashed,
            # unsupervised profiler, and must not inherit a disabled
            # collector; a supervised re-entry re-arms it in _manage_gc.
            # After the pipeline's close has joined the worker, so a
            # clean exit restores behind the last window's collection;
            # after a crash the worker may still be on a window, and
            # finds the run closed (_collect_gc).
            if self.crashed is None and self._pipeline is not None:
                # Clean shutdown flushes the in-flight window: everything
                # aggregated gets shipped before the actor exits.
                self._pipeline.close()
            if self.crashed is None and self._sinks is not None:
                # After the pipeline drained: the sink close is the
                # AutoFDO accumulator's final crash-only flush, so a
                # clean shutdown persists the partial flush interval.
                self._sinks.close()
            self._restore_gc()

    crashed: BaseException | None = None
    # (the end of the iteration before, what run() then waited for):
    # read and cleared by the next run_iteration.
    _loop_waited: tuple | None = None

    def stop(self) -> None:
        self._stop.set()
