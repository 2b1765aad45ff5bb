"""Device-runtime health: bounded bring-up and demote/promote supervision.

``runtime/quarantine.py`` owns per-pid trust; this module owns the
ACCELERATOR BACKEND's lifecycle. The failure mode it exists for: a
wedged device runtime blocks *inside a C call* — backend init, a
dispatch, a fetch — that no exception ever leaves and no thread can
cancel. An always-on profiler must therefore (a) never
touch the backend from its capture loop without an abandonable guard,
and (b) never pay an unbounded backend *init*: bring-up probes run in a
THROWAWAY SUBPROCESS with a hard deadline and a kill, so a wedged init
costs one dead child, not a hung agent.

One process per chip. An accelerator belongs to one process at a time,
so the child probe and the agent can never both hold it: the child runs
only while the agent has NOT initialised a backend (bring-up, and
re-probes after a bring-up that failed), and nothing in the agent may
touch JAX while a child is alive. Once the agent has claimed its
backend (:meth:`DeviceHealthRegistry.claim_backend`) a child could
never get the chip, and the wedged-init argument no longer applies —
the backend IS initialised — so re-probes after a demotion run
IN-PROCESS under the abandonable guard (:func:`inprocess_probe`).
Every probe reports the platform it ran on, and the registry refuses a
result from any platform but the agent's own: JAX falls back to XLA:CPU
quietly when an accelerator fails to initialise, and a probe that
"passed" there proved nothing about the device.

State machine (all transitions on the profiler's window clock — a
stalled agent must not silently serve out cooldowns):

    probing ──probe ok──────────────► healthy
       │ probe fail/hang                 │ dispatch hang, or
       ▼                                 │ failure_strikes consecutive
    degraded (CPU fallback) ◄────────────┘ dispatch errors
       │ cooldown windows (doubles per trip, capped), then
       │ k consecutive healthy probes (--device-promote-after), then
       │ ONE shadow window: device + CPU fallback both aggregate and
       │ the results must MATCH (the aggregator A/B gate — a device
       │ that answers promptly but wrongly stays demoted)
       ├──shadow match──────────────► healthy   (promotion)
       ├──shadow mismatch/hang──────► degraded  (doubled cooldown)
       └──trips > dead_after_trips──► dead      (fallback forever;
                                                 0 = keep re-probing)

While degraded every window ships via the CPU fallback: windows are
COUNTED (``fallback_windows_total``), never dropped. The profiler's
per-window hang watchdog (`profiler/cpu.py:_guarded`) reports into this
registry, so wedge accounting, cooldowns, and metrics live in one place;
`/metrics` and `/healthz` render :meth:`snapshot`.

Chaos sites: ``device.probe`` fires inside the probe thread,
``device.dispatch`` inside the profiler's guarded device call — both
accept the duration-bearing ``hang`` kind (utils/faults.py).
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

from parca_agent_tpu.runtime import device_telemetry as dtel
from parca_agent_tpu.runtime.trace import thread_ended
from parca_agent_tpu.runtime.window_clock import (
    REFERENCE_WINDOW_S,
    check_window_s,
    windows_for,
)
from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.log import get_logger

_log = get_logger("device-health")

STATE_PROBING = "probing"
STATE_HEALTHY = "healthy"
STATE_DEGRADED = "degraded"
STATE_DEAD = "dead"

STATES = (STATE_PROBING, STATE_HEALTHY, STATE_DEGRADED, STATE_DEAD)

# One tiny device round trip: backend init + put + jit + fetch. Printing
# "1 <platform>" proves the whole path AND names where it ran — JAX
# lands on XLA:CPU without a word when an accelerator fails to
# initialise, so a bare "1" would pass vacuously there.
_PROBE_CODE = (
    "import numpy as np, jax\n"
    "x = jax.device_put(np.zeros(8, np.int32))\n"
    "y = int(np.asarray(jax.jit(lambda a: a + 1)(x))[0])\n"
    "print(y, jax.devices()[0].platform)\n"
)


def subprocess_probe(timeout_s: float, code: str = _PROBE_CODE
                     ) -> tuple[bool, str, str | None]:
    """One backend bring-up probe in a throwaway subprocess, killed at
    ``timeout_s``. A wedged backend init cannot be cancelled from a
    thread (it hangs inside a C call), but a child process CAN be
    killed — this is the only hang-proof shape for a probe while the
    agent itself has no backend. Only valid then: once the agent holds
    the chip a child can never get it (:func:`inprocess_probe`).
    Returns (ok, detail, platform)."""
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return False, f"probe hung >{timeout_s:.0f}s (child killed)", None
    except OSError as e:  # pragma: no cover - spawn failure is exotic
        return False, f"probe spawn failed: {e!r}", None
    if r.returncode != 0:
        tail = (r.stderr or "").strip().splitlines()
        last = tail[-1][-200:] if tail else "no output"
        return False, f"probe rc={r.returncode}: {last}", None
    words = ((r.stdout or "").strip().splitlines() or [""])[-1].split()
    if len(words) != 2 or words[0] != "1":
        return False, f"probe wrong output: {(r.stdout or '')[:80]!r}", None
    return True, f"ok on {words[1]}", words[1]


def inprocess_probe(timeout_s: float) -> tuple[bool, str, str | None]:
    """The same round trip on the backend THIS process already holds,
    under the abandonable guard (utils/bounded.py). The re-probe shape
    after a demotion: the agent owns the chip for good, so a child
    would fail forever (or, on a quiet XLA:CPU landing, pass
    vacuously); a hang here costs one abandoned daemon thread.
    Returns (ok, detail, platform)."""
    from parca_agent_tpu.utils.bounded import bounded_call

    def round_trip():
        import jax
        import numpy as np

        x = jax.device_put(np.zeros(8, np.int32))
        y = int(np.asarray(jax.jit(lambda a: a + 1)(x))[0])
        return y, str(jax.devices()[0].platform)

    status, out, _done, _box = bounded_call(
        round_trip, timeout_s, thread_name="device-probe-inprocess")
    if status == "hang":
        return False, f"in-process probe hung >{timeout_s:.0f}s", None
    if status == "err":
        return False, f"in-process probe failed: {out!r}"[:200], None
    y, platform = out
    if y != 1:
        return False, f"in-process probe wrong output: {y!r}", platform
    return True, f"ok on {platform}", platform


def shadow_compare(device_profiles, cpu_profiles) -> bool:
    """A/B correctness gate between two aggregations of the SAME window
    (the shadow-window promotion check, :meth:`record_shadow`): per pid,
    total sample mass and unique-stack count must agree,
    order-insensitively. A backend that answers promptly but WRONGLY (a
    half-reset dict table after a wedge, a corrupted transfer) fails
    here and stays demoted."""
    def digest(profiles):
        return {int(p.pid): (int(p.total()), int(len(p.values)))
                for p in profiles}

    return digest(device_profiles) == digest(cpu_profiles)


class DeviceHealthRegistry:
    """The device-backend trust state machine (module docs above).

    ``probe`` is a zero-arg callable returning ``(ok, detail)`` or
    ``(ok, detail, platform)`` — the CLI passes :func:`subprocess_probe`
    until the agent has claimed its backend and :func:`inprocess_probe`
    after; ``None`` disables the probe phase entirely (cooldown expiry
    goes straight to the shadow window, the pre-registry retry semantics
    the profiler's embedded default keeps). Probes run on a daemon
    thread so the window loop never waits on one; a probe that outlives
    ``probe_deadline_s`` is counted as a hang and its eventual (stale)
    result ignored. A probe that names a platform other than the
    agent's own (:attr:`platform`, once claimed) is refused.

    ``claim`` is a zero-arg callable returning the backend-identity
    record (``runtime/device_telemetry.collect_identity``): calling it
    is what initialises JAX in this process. :meth:`claim_backend` runs
    it exactly once, deliberately — after the bring-up probe child has
    exited, or inside the first guarded device call when bring-up
    failed and a later re-probe passed — never on the HTTP thread.

    All mutation is lock-protected: the profiler thread reports faults
    and ticks windows, probe threads deliver results, the HTTP thread
    reads snapshots.
    """

    def __init__(self, probe=None, probe_timeout_s: float = 60.0,
                 claim=None,
                 probe_deadline_s: float | None = None,
                 promote_after: int = 2,
                 cooldown_windows: int = 3,
                 max_cooldown_windows: int = 240,
                 failure_strikes: int = 3,
                 dead_after_trips: int = 0,
                 start_state: str = STATE_PROBING,
                 clock=time.monotonic,
                 window_s: float = REFERENCE_WINDOW_S):
        self._probe = probe
        self._claim = claim
        self._probe_timeout = probe_timeout_s
        # Grace over the probe's own (subprocess) timeout: the in-process
        # deadline only exists for probes wedged BEFORE their own bound
        # can fire (a hung spawn, an injected hang at the site).
        self._probe_deadline = (probe_deadline_s
                                if probe_deadline_s is not None
                                else probe_timeout_s + 5.0)
        self._promote_after = max(0, promote_after)
        # Cooldowns are wall-time commitments expressed at the reference
        # 10 s window (runtime/window_clock.py): "3 windows before the
        # first re-probe" means ~30 s of CPU-fallback patience whatever
        # the cadence. Probe counts (promote_after) and failure strikes
        # are per-event and stay unconverted; probe deadlines are
        # already seconds. Exact identity at the reference cadence.
        check_window_s(window_s)
        self._base_cooldown = windows_for(cooldown_windows, window_s)
        self._max_cooldown = max(self._base_cooldown, windows_for(
            max_cooldown_windows, window_s))
        self._failure_strikes = max(1, failure_strikes)
        self._dead_after = max(0, dead_after_trips)
        self._clock = clock
        self._lock = threading.Lock()

        if start_state not in STATES:
            raise ValueError(f"unknown start state {start_state!r}")
        # The state machine below is mutated from the profiler thread
        # (window clock), the probe-result callback thread, and read
        # from the HTTP thread — everything rides _lock (palint
        # lock-discipline; the _locked-suffix helpers are annotated
        # holds=_lock).
        self.state = start_state            # guarded-by: _lock
        self.windows = 0                    # guarded-by: _lock
        self.trips = 0                      # guarded-by: _lock
        self.cooldown_left = 0              # guarded-by: _lock
        self.consecutive_ok_probes = 0      # guarded-by: _lock
        self.shadow_pending = False         # guarded-by: _lock
        self.wedged_at: int | None = None   # window of the last hang
        self.last_demote_window: int | None = None
        self.last_promote_window: int | None = None
        self.last_error: str = ""
        # The platform the AGENT's backend runs on (None until
        # claim_backend lands) and the one the last passing probe
        # reported. They must agree: see _on_probe_result.
        self.platform: str | None = None         # guarded-by: _lock
        self.probe_platform: str | None = None   # guarded-by: _lock
        self._bringup_done = threading.Event()
        self._claim_mu = threading.Lock()
        self._consec_failures = 0                    # guarded-by: _lock
        self._probe_gen = 0                          # guarded-by: _lock
        self._probe_started_at: float | None = None  # guarded-by: _lock
        self.stats = {  # guarded-by: _lock
            "probes_total": 0,
            "probes_ok": 0,
            "probes_failed": 0,   # == probes_total - probes_ok (invariant)
            "probes_hung": 0,     # the probes_failed that were deadline
            #                       overruns (a wedged backend init)
            "probes_refused": 0,  # the probes_failed that passed on a
            #                       platform other than the agent's own
            "hangs_total": 0,
            "dispatch_errors_total": 0,
            "demotions_total": 0,
            "promotions_total": 0,
            "shadow_windows_total": 0,
            "shadow_mismatches_total": 0,
            "fallback_windows_total": 0,
        }

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        """Kick off the bounded bring-up. With no probe configured the
        registry trusts the backend optimistically (the first guarded
        dispatch is itself watchdogged); with one, the agent captures on
        the CPU fallback until the probe child proves the backend out —
        a wedged init costs a killed child, never a hung agent."""
        with self._lock:
            if self.state != STATE_PROBING:
                self._bringup_done.set()
                return
            if self._probe is None:
                self.state = STATE_HEALTHY
                self._bringup_done.set()
                return
            self._launch_probe_locked()

    def wait_bringup(self, timeout_s: float) -> bool:
        """Block until the bring-up probe has resolved (either way) or
        ``timeout_s`` passes; True when the state left ``probing``. The
        CLI holds the capture loop behind this — bounded by the probe's
        own kill deadline — so the first window is never a fallback
        window merely because it raced a healthy probe, and nothing
        touches JAX while the probe child is alive."""
        return self._bringup_done.wait(timeout_s)

    def claim_backend(self) -> dict | None:
        """Learn — once, deliberately — which backend this process runs
        on: run the ``claim`` callable (which initialises JAX here and,
        on an accelerator host, takes the chip), latch the platform, and
        say where the agent landed. From here on probes run in-process.
        A landing on ``cpu`` that ``JAX_PLATFORMS`` did not ask for is
        an ERROR line: JAX itself only logs it at INFO and carries on.
        No-op without a ``claim`` callable or once claimed; raises what
        the callable raises (callers run it under their own guard)."""
        with self._claim_mu:  # one claim at a time; _lock stays free
            with self._lock:
                if self._claim is None or self.platform is not None:
                    return None
                probed = self.probe_platform
            ident = self._claim()
            platform = str(ident.get("platform"))
            with self._lock:
                self.platform = platform
        _log.info("device backend claimed", platform=platform,
                  device_kind=ident.get("device_kind"),
                  device_count=ident.get("device_count"))
        wanted = os.environ.get("JAX_PLATFORMS", "")
        if platform == "cpu" and "cpu" not in wanted.split(","):
            _log.error("a device aggregator was chosen but JAX landed on "
                       "the CPU backend: no accelerator was found, or it "
                       "failed to initialise or is held by another "
                       "process (set JAX_PLATFORMS to make that fatal)",
                       jax_platforms=wanted or "(unset)")
        if probed is not None and probed != platform:
            with self._lock:
                self.last_error = (f"agent landed on {platform}, its "
                                   f"bring-up probe ran on {probed}")
                _log.error("device backend is not the one the bring-up "
                           "probe proved out", error=self.last_error)
                if self.state == STATE_HEALTHY:
                    self._demote_locked("platform mismatch")
        return ident

    # -- profiler-facing decisions -------------------------------------------

    def window_mode(self) -> str:
        """What this window's aggregation should do: ``device`` (normal),
        ``shadow`` (run device AND fallback, compare, report via
        :meth:`record_shadow`), or ``fallback``. The caller additionally
        gates device/shadow on its own abandoned-call state — an
        abandoned dispatch may still be executing inside the
        aggregator."""
        with self._lock:
            if self.state == STATE_HEALTHY:
                return "device"
            if self.state == STATE_DEGRADED and self.shadow_pending:
                return "shadow"
            return "fallback"

    def record_dispatch_ok(self) -> None:
        with self._lock:
            self._consec_failures = 0

    def record_dispatch_error(self, exc: BaseException) -> None:
        """A device call that FAILED (raised) — one strike; repeated
        consecutive failures demote (a flapping backend is as useless as
        a wedged one, just cheaper to discover)."""
        with self._lock:
            self.stats["dispatch_errors_total"] += 1
            self.last_error = repr(exc)[:200]
            self._consec_failures += 1
            if self.state == STATE_HEALTHY \
                    and self._consec_failures >= self._failure_strikes:
                self._demote_locked("dispatch failures")

    def record_hang(self) -> None:
        """The guarded device call blew its watchdog and was abandoned.
        Demotes immediately — a hang is never a strike to accumulate
        (the next one would stall another window's deadline)."""
        with self._lock:
            self.stats["hangs_total"] += 1
            self.wedged_at = self.windows
            self.last_error = "device call hung (abandoned)"
            self.shadow_pending = False  # a shadow that hung failed too
            self._demote_locked("dispatch hang")

    def record_claim_failure(self, detail: str) -> None:
        """The in-process backend init behind :meth:`claim_backend`
        raised or hung: no device to run on. Demotes (windows ship from
        the CPU fallback) with the usual capped-backoff re-probes."""
        with self._lock:
            self.last_error = f"backend claim failed: {detail}"[:200]
            _log.error("device backend claim failed",
                       error=self.last_error)
            self._demote_locked("claim failure")

    def record_fallback_window(self) -> None:
        with self._lock:
            self.stats["fallback_windows_total"] += 1

    def record_shadow(self, matched: bool, error: str = "") -> None:
        """Outcome of the promotion gate's A/B window."""
        with self._lock:
            self.stats["shadow_windows_total"] += 1
            self.shadow_pending = False
            if matched:
                trips_survived = self.trips
                self.state = STATE_HEALTHY
                self.trips = 0
                self.cooldown_left = 0
                self.consecutive_ok_probes = 0
                self._consec_failures = 0
                self.wedged_at = None
                self.last_promote_window = self.windows
                self.stats["promotions_total"] += 1
                dtel.note_backend("device", resolved="device",
                                  fallback=False)
                _log.info("device promoted: shadow window matched the "
                          "CPU fallback", window=self.windows,
                          trips_survived=trips_survived)
                return
            self.stats["shadow_mismatches_total"] += 1
            self.last_error = error or "shadow window mismatched the CPU " \
                                       "fallback"
            _log.warn("device promotion refused: shadow window did not "
                      "match the CPU fallback; re-demoting",
                      error=self.last_error)
            self._demote_locked("shadow mismatch")

    # -- the window clock ----------------------------------------------------

    def tick_window(self) -> None:
        """Advance cooldowns and drive re-probes; the profiler calls this
        once per iteration (window time, like the quarantine registry)."""
        probe_needed = False
        with self._lock:
            self.windows += 1
            self._check_probe_deadline_locked()
            if self.state != STATE_DEGRADED or self.shadow_pending:
                return
            if self.cooldown_left > 0:
                self.cooldown_left -= 1
                if self.cooldown_left > 0:
                    return
            if self._probe is None \
                    or self.consecutive_ok_probes >= self._promote_after:
                # Promotion gate's last hurdle: the next window runs the
                # device in the fallback's shadow.
                self.shadow_pending = True
                return
            if self._probe_started_at is None:
                probe_needed = True
                window = self.windows  # captured under the lock: the
                #                        log below runs after release
                self._launch_probe_locked()
        if probe_needed:
            _log.debug("device re-probe launched", window=window)

    # -- probes --------------------------------------------------------------

    def _launch_probe_locked(self) -> None:  # palint: holds=_lock
        self._probe_gen += 1
        self._probe_started_at = self._clock()
        self.stats["probes_total"] += 1
        threading.Thread(target=self._run_probe, args=(self._probe_gen,),
                         name="device-probe", daemon=True).start()

    def _run_probe(self, gen: int) -> None:
        platform = None
        try:
            faults.inject("device.probe")
            ok, detail, *rest = self._probe()
            platform = rest[0] if rest else None
        except BaseException as e:  # noqa: BLE001 - a broken probe = failed
            ok, detail = False, repr(e)[:200]
        self._on_probe_result(gen, bool(ok), str(detail), platform)
        thread_ended()  # a thread of its own, gone before any scrape

    def _check_probe_deadline_locked(self) -> None:  # palint: holds=_lock
        """A probe that outlived its deadline is a HANG: count it failed
        now and ignore its eventual result (generation bump). The probe
        subprocess bounds itself; this catches wedged spawns and
        injected in-process hangs."""
        if self._probe_started_at is None:
            return
        if self._clock() - self._probe_started_at <= self._probe_deadline:
            return
        self._probe_gen += 1  # stale result will be dropped
        self._probe_started_at = None
        self.stats["probes_failed"] += 1
        self.stats["probes_hung"] += 1
        self._note_probe_failed_locked(
            f"probe overran its deadline ({self._probe_deadline:.0f}s)")

    def _on_probe_result(self, gen: int, ok: bool, detail: str,
                         platform: str | None = None) -> None:
        with self._lock:
            if gen != self._probe_gen or self.state == STATE_DEAD:
                return  # stale (deadline already charged it) or moot
            self._probe_started_at = None
            if ok and platform is not None and self.platform is not None \
                    and platform != self.platform:
                # A pass on somebody else's platform proves nothing
                # about ours (the quiet XLA:CPU landing of a child that
                # could not get the chip): refused, counted, failed.
                self.stats["probes_refused"] += 1
                ok = False
                detail = (f"probe ran on {platform}, the agent owns "
                          f"{self.platform}: refused")
            if ok:
                if platform is not None:
                    self.probe_platform = platform
                self.stats["probes_ok"] += 1
                self.consecutive_ok_probes += 1
                if self.state == STATE_PROBING:
                    # Bring-up: the backend proved out; no shadow needed,
                    # there is nothing demoted to distrust yet.
                    self.state = STATE_HEALTHY
                    self._bringup_done.set()
                    _log.info("device backend probe ok; starting on the "
                              "device", detail=detail)
                elif self.state == STATE_DEGRADED \
                        and self.consecutive_ok_probes < self._promote_after:
                    # More consecutive probes wanted: next window's tick
                    # launches the next one.
                    self.cooldown_left = 0
                return
            self.stats["probes_failed"] += 1
            self._note_probe_failed_locked(detail)

    def _note_probe_failed_locked(self, detail: str) -> None:  # palint: holds=_lock
        self.consecutive_ok_probes = 0
        self.last_error = detail[:200]
        _log.warn("device probe failed", error=self.last_error,
                  trips=self.trips)
        self._demote_locked("probe failure")
        self._bringup_done.set()

    # -- transitions ---------------------------------------------------------

    def _demote_locked(self, reason: str) -> None:  # palint: holds=_lock
        """One more trip: enter (or stay in) degraded with a doubled,
        capped cooldown; past the trip budget, dead."""
        self.trips += 1
        self.consecutive_ok_probes = 0
        self.shadow_pending = False
        self.cooldown_left = min(
            self._base_cooldown * (2 ** (self.trips - 1)),
            self._max_cooldown)
        if self.state != STATE_DEGRADED:
            self.last_demote_window = self.windows
            self.stats["demotions_total"] += 1
        if self._dead_after and self.trips > self._dead_after:
            self.state = STATE_DEAD
            _log.error("device re-probe budget exhausted; backend marked "
                       "dead (CPU fallback is permanent)",
                       trips=self.trips, reason=reason,
                       error=self.last_error)
            return
        prev = self.state
        self.state = STATE_DEGRADED
        # Latch the demotion into the device flight recorder's backend
        # gauges: a node running its windows on the CPU fallback must be
        # visible from /metrics.
        dtel.note_backend("device", resolved="cpu_fallback", fallback=True)
        if prev != STATE_DEGRADED:
            _log.warn("device demoted to the CPU fallback", reason=reason,
                      window=self.windows, cooldown_windows=self.cooldown_left,
                      trips=self.trips)

    # -- observability -------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-shaped view for /healthz and the bench artifact."""
        with self._lock:
            return {
                "state": self.state,
                "windows": self.windows,
                "trips": self.trips,
                "cooldown_windows_left": self.cooldown_left,
                "consecutive_ok_probes": self.consecutive_ok_probes,
                "shadow_pending": self.shadow_pending,
                "probe_in_flight": self._probe_started_at is not None,
                "wedged_at_window": self.wedged_at,
                "last_demote_window": self.last_demote_window,
                "last_promote_window": self.last_promote_window,
                "last_error": self.last_error,
                "platform": self.platform,
                "probe_platform": self.probe_platform,
                "stats": dict(self.stats),
            }
