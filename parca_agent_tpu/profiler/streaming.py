"""Streaming window feeder: ship capture drains to the aggregation device
DURING the window.

The reference's BPF map absorbs samples in kernel as they happen
(bpf/cpu/cpu.bpf.c:110-116), so its window close never re-ships the
window; here each once-a-second drain is fed to the
dict aggregator's device table as it lands (H2D + the probe/accumulate
kernel ride the otherwise-idle window), and the profiler's window close
is just close_window() — one pack kernel, one packed fetch.

Safety model (SURVEY.md section 7 hard part #5 — device trouble must not
stall the capture loop):

  * Every feed runs under a daemon-thread watchdog with a SHORT timeout
    (the polling thread is stalled while a feed runs; perf rings are
    smaller than a window, so a long stall wraps them and loses samples).
    A feed inside which XLA was asked for a program (a shape the run
    had not met: JAX raises the request on the asking thread, runtime/
    device_telemetry.py compile_requests_here) is compiling, not
    hanging, and is given what is left of the first feed's long budget;
    a feed that asked for none is held to the short one, always.
    A failure or hang disables the feeder for a capped-exponential number
    of WINDOWS (2, 4, ... up to 32): mid-window the feeder never retries
    (a wedged device would stall the polling thread again next drain),
    but at window boundaries it re-probes, so a transient hiccup — a
    runtime stall, a slow compile — costs a few one-shot windows rather
    than forfeiting streaming for the process lifetime. Re-enable waits
    for device_blocked() to clear first (see below).
  * An abandoned (timed-out) feed may still be EXECUTING inside the
    aggregator. Until it actually returns, the aggregator must not be
    touched from any other thread: device_blocked() reports this, and
    the profiler's one-shot path raises into its own watchdog/fallback
    machinery instead of racing the abandoned call (the CPU fallback
    aggregator shares no state with the dict).
  * At window close the fed mass is checked against the snapshot's total;
    any mismatch (a feed died mid-window, a drain raced the boundary)
    discards the fed accumulator and re-aggregates the full snapshot
    one-shot — exactness never depends on the streaming path.

The drain tee and the window boundary both run on the profiler thread
(the sampler's poll() invokes the tee synchronously); only the watchdog
helper threads are extra, and they never mutate feeder state.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from parca_agent_tpu.capture.formats import WindowSnapshot
from parca_agent_tpu.capture.live import columns_to_snapshot
from parca_agent_tpu.runtime import device_telemetry, trace
from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.log import get_logger

_log = get_logger("streaming")

# The aggregator's per-feed timings the feeder sums over a window.
_FEED_TIMINGS = ("feed_dispatch", "feed_settle", "feed_hash",
                 "feed_coalesce", "feed_carry")

# Why a window was not streamed, as its ``meta`` (``stream_reason``) and
# ``parca_agent_streaming_windows_fallback_total{reason}`` name it: the
# feeder was cooling down after a failed or hung feed; the fed mass
# differed from the snapshot's; an abandoned feed was still in flight.
FALLBACK_REASONS = ("cooldown", "mass_mismatch", "blocked")


class StreamingWindowFeeder:
    """Per-drain feed glue between a capture source that drains during
    the window (frame-pointer mode) and a DictAggregator. Wire
    `source.on_drain = feeder.on_drain` and pass the feeder to
    CPUProfiler(streaming_feeder=...).

    The source is the capture-source protocol's streaming half:
    ``on_drain`` is called on its polling thread with each drain's
    columnar chunk, and ``source.mapping_table(pids)`` answers with the
    mappings of a drain's pids (the perf sampler from its caches of
    ``/proc/<pid>/maps``, charging a poisoned pid to its quarantine
    registry; the replay source from the open window's own table)."""

    def __init__(self, aggregator, source,
                 feed_timeout_s: float = 3.0,
                 first_feed_timeout_s: float = 60.0,
                 reprobe_base_windows: int = 2,
                 reprobe_max_windows: int = 32,
                 prebuild_period_ns: int = 0,
                 prebuild_budget_s: float = 0.25):
        self._agg = aggregator
        self._source = source
        self._timeout = feed_timeout_s
        # The very FIRST feed attempt of the process gets the longer
        # budget: it includes backend work no later feed repeats (the
        # XLA compile of the feed program, the first host-to-device
        # transfers, a population-sized miss settle), so a
        # compile-blind short timeout would trip on EVERY cold start and
        # streaming could never engage at all. The long budget applies
        # exactly once — if that attempt times out (device wedged from
        # boot), every later re-probe runs under the SHORT timeout, so a
        # dead device costs one long capture-loop stall, not one per
        # cooldown. A timed-out-but-healthy first feed keeps compiling
        # in its abandoned daemon thread, so a later 3 s re-probe still
        # lands on the warm program cache and succeeds. A LATER feed is
        # given the long budget only while it can be seen to compile
        # (_feed_guarded): the shapes a run's first feed does not meet
        # (aggregator/dict.py) come with a load that grew.
        self._first_timeout = max(feed_timeout_s, first_feed_timeout_s)
        self._first_attempted = False
        self._fed_total = 0          # mass fed into the open window
        self._inflight: threading.Event | None = None  # abandoned feed
        self.disabled = False        # not feeding (cooling down)
        self._cooldown = 0           # windows until re-probe
        self._backoff_base = max(1, reprobe_base_windows)
        self._backoff_max = max(self._backoff_base, reprobe_max_windows)
        self._backoff = self._backoff_base  # next cooldown length
        # Statics amortization: with an encoder attached, each successful
        # feed is followed by a BUDGETED WindowEncoder.build_statics pass,
        # so the pid population discovered during the window has its pprof
        # static sections built while the window is still open — bounding
        # the close-time statics transient (a cold 50k-pid first window
        # otherwise pays the full build inside the close) to one budget.
        # Pure host numpy, and race-free by construction: the sampler's
        # poll() invokes the tee synchronously on the profiler thread,
        # and the profiler's encode also runs on the profiler thread
        # (outside the device watchdog) — tee and encode literally cannot
        # overlap. external_blocked gates the remaining hazard: an
        # abandoned DEVICE aggregation call that shares registry state.
        self._encoder = None
        self._prebuild_fn = None
        self._prebuild_period = prebuild_period_ns
        self._prebuild_budget = prebuild_budget_s
        # Optional external gate (the profiler wires its hang-watchdog
        # state here): while an ABANDONED AGGREGATION call may still be
        # executing inside take_window_if_complete()/window_counts(),
        # neither the aggregator nor the encoder (which reads the
        # aggregator's registry) may be touched from the polling thread,
        # so on_drain skips entirely (the incomplete fed mass then makes
        # the window fall back, which is exactly right).
        self.external_blocked = None
        # Windows that were not streamed, by reason (FALLBACK_REASONS).
        self.fallback_reasons = dict.fromkeys(FALLBACK_REASONS, 0)
        self.stats = {"drains_fed": 0, "windows_streamed": 0,
                      "windows_fallback": 0, "reprobes": 0,
                      # The watchdog's headroom: feeds that took over
                      # half the timeout they ran under (0 in a sound
                      # run), and feeds inside which XLA was asked for
                      # a program (the first of a run, and any that
                      # meets a shape the first did not).
                      "feeds_slow": 0, "feed_compiles": 0,
                      "statics_prebuilt": 0, "last_close_s": 0.0,
                      # Flight-recorder feed/fetch spans (runtime/
                      # trace.py): capture-thread seconds spent in this
                      # window's drain tees, and whether the LAST window
                      # actually streamed (gates the fetch span — a
                      # fallback window must not re-record a stale
                      # last_close_s).
                      "last_window_feed_s": 0.0,
                      "last_window_streamed": 0,
                      # Double-buffer overlap accounting (docs/perf.md
                      # "sub-RTT close"): per-window capture-thread
                      # seconds spent DISPATCHING feeds (the device work
                      # overlaps capture) vs SETTLING the deferred miss
                      # checks (the residual wait, ~a completion check
                      # between drains).
                      "last_window_dispatch_s": 0.0,
                      "last_window_settle_s": 0.0,
                      # Ingest-wall split (docs/perf.md "ingest wall"):
                      # capture-thread seconds this window spent HASHING
                      # feed batches vs COALESCING them to (stack,
                      # weight) pairs — the two costs the native kernel
                      # and the fold exist to shrink. Popped, not read,
                      # like dispatch/settle: a stale value must never
                      # re-count into a later window's spans.
                      "last_window_hash_s": 0.0,
                      "last_window_coalesce_s": 0.0,
                      # Feed endgame (docs/perf.md): capture-thread
                      # seconds this window spent in the cross-drain
                      # carry match (the h1-keyed cache that folds
                      # repeated stacks host-side instead of
                      # re-dispatching them every drain).
                      "last_window_carry_s": 0.0}
        self._window_feed_s = 0.0
        self._window_dispatch_s = 0.0
        self._window_settle_s = 0.0
        self._window_hash_s = 0.0
        self._window_coalesce_s = 0.0
        self._window_carry_s = 0.0

    def _discard_open_window(self) -> None:
        """Drop the aggregator's open-window state across buffer flips:
        fed device mass, host pending corrections, and (on swap-aware
        aggregators) any deferred feed-miss check — dropping those too is
        what keeps recovery exact under double-buffering, since a stale
        miss check settling into a NEW window would inject the discarded
        window's corrections."""
        discard = getattr(self._agg, "discard_open_window", None)
        if discard is not None:
            discard()
            return
        self._agg._fed_total = 0
        self._agg._pending = []
        self._agg._needs_reset = True

    def attach_encoder(self, encoder, prebuild=None) -> None:
        """Wire the profiler's WindowEncoder for statics amortization.
        `prebuild(period_ns, budget_s)` overrides WHERE the budgeted
        build runs: the encode pipeline passes request_prebuild so the
        drain tick only enqueues and the build lands on the encoder
        thread (its thread-ownership contract); by default the build
        runs inline on the polling thread, as before."""
        self._encoder = encoder
        self._prebuild_fn = prebuild

    def _enter_cooldown(self, why: str) -> None:
        """Disable feeding for a capped-exponential number of windows
        (the single degradation path for feed failures, hangs, and
        injected crashes alike — chaos must degrade exactly like real
        trouble)."""
        self.disabled = True
        self._cooldown = self._backoff
        self._backoff = min(self._backoff * 2, self._backoff_max)
        _log.warn(why + "; one-shot window aggregation for the next "
                  "windows", cooldown_windows=self._cooldown)

    def device_blocked(self) -> bool:
        """True while an abandoned feed may still be executing inside the
        aggregator (nothing else may touch it until then)."""
        if self._inflight is None:
            return False
        if self._inflight.is_set():
            self._inflight = None
            return False
        return True

    # -- drain tee (called inside sampler.poll on the profiler thread) -------

    # palint: capture-path — runs synchronously inside the sampler's
    # poll() on the profiler thread; feed work here must be dispatch-
    # only (the aggregator's seeded feed carries the same contract).
    def on_drain(self, cols) -> None:
        if self.disabled:
            return
        if self.external_blocked is not None and self.external_blocked():
            return
        try:
            # Chaos site: the drain tick runs synchronously inside the
            # sampler's poll(), so an injected crash must degrade (the
            # feeder's own cooldown path), never escape into capture.
            faults.inject("actor.feeder")
        except Exception:  # noqa: BLE001 - injected crash -> cooldown
            self._enter_cooldown("injected feeder crash")
            return
        if not len(cols[0]):
            return
        # One stream_feed span a drain, under the profiler's drain span
        # open on this thread; a window's drains sum into the one span
        # the window keeps, and what a drain does is recorded inside it
        # where it runs.
        with trace.child("stream_feed", histogram=True, usage=True) as sp:
            self._feed_drain(cols)
        # Capture-thread seconds this window spent feeding.
        self._window_feed_s += sp.duration_s

    def _feed_drain(self, cols) -> None:
        # v1d chunks are 6 columns; v1h chunks (capture-side hash carry)
        # tail the drain-computed h1/h2/h3 triple.
        pids, tids, ulen, klen, stacks, counts = cols[:6]
        hashes = tuple(cols[6:9]) if len(cols) >= 9 else None
        try:
            with trace.child("drain_table"):
                table = self._source.mapping_table(np.unique(pids).tolist())
        except Exception as e:  # noqa: BLE001 - a poisoned maps file
            # (PoisonInput surfaces here only without a registry) must
            # cost this DRAIN, not the capture loop: skip the feed; the
            # fed-mass mismatch makes the window one-shot, exactly
            # right.
            _log.warn("drain mapping build failed; skipping feed",
                      error=repr(e))
            return
        with trace.child("drain_fold"):
            mini = columns_to_snapshot(pids, tids, ulen, klen, stacks,
                                       table, 0, 0, weights=counts,
                                       hashes=hashes)
            if hashes is not None:
                mini, hashes = mini
            mass = mini.total_samples()
        if len(mini) == 0:
            return
        tim = getattr(self._agg, "timings", None)
        if self._fed_total == 0 and tim is not None:
            # First feed of a new window: a one-shot fallback window
            # ran window_counts() on this same aggregator between the
            # boundary and now, leaving ITS feed_dispatch/feed_settle
            # timings behind — discard them so the pop below can't
            # credit them to this window's overlap accounting.
            for k in _FEED_TIMINGS:
                tim.pop(k, None)
        if self._fed_total == 0 \
                and (getattr(self._agg, "_fed_total", 0)
                     or getattr(self._agg, "_pending", None)):
            # First feed of a new window with residual open-window
            # state: a one-shot failed partway (its feed dispatched
            # mass and/or registered host-side pending rows, its close
            # never ran). Discard it all — device acc via the reset
            # flag, host mirrors directly — exactly as window_counts
            # guards its own entry (aggregator/dict.py). Without this
            # the residue would ride into the streamed close and
            # inflate counts past the feeder's own fed-mass gate
            # ("_pending" survives an acc reset: the flag only zeroes
            # the device accumulator).
            self._discard_open_window()
        if not self._feed_guarded(mini, hashes):
            # Do NOT try again this window: a wedged device would
            # stall the capture loop on every subsequent drain.
            # Re-probe only at a window boundary, after a
            # capped-exponential cooldown.
            self._enter_cooldown("streaming feed failed")
            return
        # Split the feed's capture-thread cost into dispatch (launch
        # the probe kernel; its device execution overlaps capture)
        # and settle (the PREVIOUS feed's deferred miss check — by
        # now a completion check, not a kernel wait). Popped, not
        # read: feed_settle is only written when an inflight check
        # existed, and a stale value must not re-count.
        if tim is not None:
            self._window_dispatch_s += tim.pop("feed_dispatch", 0.0)
            self._window_settle_s += tim.pop("feed_settle", 0.0)
            self._window_hash_s += tim.pop("feed_hash", 0.0)
            self._window_coalesce_s += tim.pop("feed_coalesce", 0.0)
            self._window_carry_s += tim.pop("feed_carry", 0.0)
        self._fed_total += mass
        self.stats["drains_fed"] += 1
        trace.count(drains_fed=1)
        if self._encoder is not None and self._prebuild_period:
            with trace.child("statics_prebuild"):
                try:
                    if self._prebuild_fn is not None:
                        self._prebuild_fn(self._prebuild_period,
                                          self._prebuild_budget)
                    else:
                        self._encoder.build_statics(
                            self._prebuild_period,
                            budget_s=self._prebuild_budget)
                    self.stats["statics_prebuilt"] += 1
                except Exception as e:  # noqa: BLE001 - never fail the tee
                    _log.warn("statics prebuild failed", error=repr(e))

    def _feed_guarded(self, mini: WindowSnapshot, hashes=None) -> bool:
        """One feed under the shared abandonable guard (utils/
        bounded.py — palint bounded-call: this was the last hand-rolled
        copy of the spawn/join/abandon dance PR 5 unified)."""
        from parca_agent_tpu.utils.bounded import bounded_call

        timeout = self._first_timeout if not self._first_attempted \
            else self._timeout
        self._first_attempted = True
        # The feed runs on a thread of its own; what the aggregator
        # records belongs under the span open here (the drain's
        # stream_feed). The two crossings between the threads (the
        # thread's start and this one's wake-up, each a turn at the GIL
        # that the encode worker may hold) are stages of their own.
        open_span = trace.current()
        clock = time.monotonic
        at = [clock(), 0.0, 0.0]  # the call; the feed's start; its end
        asked = [0]  # XLA compile requests of the feed, while it runs

        def site():
            with trace.adopt(open_span), \
                    device_telemetry.compile_requests_here(asked):
                at[1] = clock()
                try:
                    return self._agg.feed(mini, hashes=hashes)
                finally:
                    at[2] = clock()

        def more():
            # The short timeout has passed. A feed that asked XLA for a
            # program gets the rest of the long budget; one that asked
            # for nothing hangs, and is abandoned now.
            return self._first_timeout - timeout if asked[0] else 0.0

        status, out, done, _box = bounded_call(
            site, timeout, thread_name="stream-feed", extend=more)
        if asked[0]:
            self.stats["feed_compiles"] += 1
        budget = self._first_timeout if asked[0] else timeout
        if clock() - at[0] > budget / 2:
            self.stats["feeds_slow"] += 1
        if status != "hang":
            trace.note("feed_handoff", at[1] - at[0], start_s=at[0])
            trace.note("feed_return", clock() - at[2], start_s=at[2])
        if status == "hang":
            # Abandoned: the call may still be mutating the aggregator.
            self._inflight = done
            _log.error("streaming feed hung; abandoning",
                       timeout_s=timeout)
            return False
        if status == "err":
            _log.warn("streaming feed error", error=repr(out))
            return False
        return True

    # -- window boundary (profiler iteration) --------------------------------

    def take_window_if_complete(self, snapshot: WindowSnapshot):
        """If every drain of the window was fed and the fed mass equals
        the snapshot's, return the closed exact counts; else None (the
        caller one-shots the snapshot). Either way the feeder is reset
        for the next window."""
        fed = self._fed_total
        self._fed_total = 0
        self.stats["last_window_feed_s"] = self._window_feed_s
        self._window_feed_s = 0.0
        self.stats["last_window_dispatch_s"] = self._window_dispatch_s
        self._window_dispatch_s = 0.0
        self.stats["last_window_settle_s"] = self._window_settle_s
        self._window_settle_s = 0.0
        self.stats["last_window_hash_s"] = self._window_hash_s
        self._window_hash_s = 0.0
        self.stats["last_window_coalesce_s"] = self._window_coalesce_s
        self._window_coalesce_s = 0.0
        self.stats["last_window_carry_s"] = self._window_carry_s
        self._window_carry_s = 0.0
        self.stats["last_window_streamed"] = 0
        if snapshot.period_ns:
            self._prebuild_period = snapshot.period_ns
        if self.disabled:
            self.count_fallback("cooldown")
            self._cooldown -= 1
            # Re-probe here, at the boundary — never mid-window — and
            # only once any abandoned feed has actually returned (the
            # aggregator may not be touched before then).
            if self._cooldown <= 0 and not self.device_blocked():
                self.disabled = False
                self.stats["reprobes"] += 1
                # The device accumulator may hold residual mass from a
                # one-shot window_counts that failed AFTER its feed
                # dispatched (close raised -> CPU fallback, _needs_reset
                # left False), plus host-pending corrections and a
                # deferred miss check from that feed. Discard all of it
                # so the first streamed feed starts from a clean window.
                self._discard_open_window()
                _log.info("streaming feeder re-enabled; probing next "
                          "window")
            return None
        if fed != snapshot.total_samples():
            # A drain raced the window boundary or a tee was skipped:
            # exactness rules, stream the next window instead. Discard
            # the whole partial window — including any deferred miss
            # check, which would otherwise settle its corrections into
            # the NEXT window.
            self.count_fallback("mass_mismatch")
            self._discard_open_window()
            return None
        t0 = time.perf_counter()
        counts = self._agg.close_window(copy=False, streamed=True)
        self.stats["windows_streamed"] += 1
        self.stats["last_window_streamed"] = 1
        trace.annotate(streamed=1)
        self.stats["last_close_s"] = time.perf_counter() - t0
        # The close settled the window's final feed (and paid its
        # dispatch bookkeeping) AFTER the boundary reset above — pop the
        # timings into the window that just closed, or they'd leak into
        # the next window's first drain.
        tim = getattr(self._agg, "timings", None)
        if tim is not None:
            self.stats["last_window_dispatch_s"] += tim.pop(
                "feed_dispatch", 0.0)
            self.stats["last_window_settle_s"] += tim.pop(
                "feed_settle", 0.0)
            # hash/coalesce are feed-time-only writes, already popped by
            # the drains — popped again here purely so a stale value
            # can never survive into the next window's accounting.
            self.stats["last_window_hash_s"] += tim.pop("feed_hash", 0.0)
            self.stats["last_window_coalesce_s"] += tim.pop(
                "feed_coalesce", 0.0)
            self.stats["last_window_carry_s"] += tim.pop(
                "feed_carry", 0.0)
        self._backoff = self._backoff_base  # healthy again: reset backoff
        return counts

    def count_fallback(self, reason: str) -> None:
        """A window that is not streamed, and why (FALLBACK_REASONS):
        counted, and written on the ``meta`` of the window whose span is
        open on this thread. ``blocked`` is the profiler's to report:
        while an abandoned feed is in flight it does not ask the feeder
        for the window at all."""
        self.stats["windows_fallback"] += 1
        self.fallback_reasons[reason] += 1
        trace.annotate(streamed=0, stream_reason=reason)

    def metrics(self) -> dict:
        """The feeder's ``/metrics`` samples. What only rises is a
        counter under a counter's name (the windows streamed; those that
        were not, by reason); every other numeric stat is the gauge
        ``parca_agent_streaming_<stat>`` it has always been."""
        out = {"parca_agent_streaming_disabled": int(self.disabled),
               "parca_agent_streaming_windows_streamed_total":
                   self.stats["windows_streamed"],
               "parca_agent_streaming_feeds_slow_total":
                   self.stats["feeds_slow"],
               "parca_agent_streaming_feed_compiles_total":
                   self.stats["feed_compiles"]}
        for reason, n in self.fallback_reasons.items():
            out["parca_agent_streaming_windows_fallback_total"
                f'{{reason="{reason}"}}'] = n
        for k, v in self.stats.items():
            if k not in ("windows_streamed", "windows_fallback",
                         "feeds_slow", "feed_compiles") \
                    and isinstance(v, (int, float)):
                out[f"parca_agent_streaming_{k}"] = round(v, 4) \
                    if isinstance(v, float) else v
        return out
