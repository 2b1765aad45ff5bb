"""Device-runtime & fleet health (docs/robustness.md "device & fleet
health"): bounded bring-up probes, the probing → healthy → degraded →
dead state machine, the shadow-window promotion gate, the profiler's
wedge → cooldown → inflight-gated retry path under injected hangs, the
bounded fleet join, and collective degrade/rejoin. Everything here is
deterministic (fixed fault seed, scripted probes) and rides the `chaos`
marker, same as tests/test_chaos.py (`make chaos`)."""

import threading
import time

import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.capture.replay import ReplaySource
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.profiler.cpu import CPUProfiler
from parca_agent_tpu.runtime.device_health import (
    STATE_DEAD,
    STATE_DEGRADED,
    STATE_HEALTHY,
    STATE_PROBING,
    DeviceHealthRegistry,
    subprocess_probe,
)
from parca_agent_tpu.utils import faults

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faults.install(None)


def _snap(seed=1, n_pids=5, rows=40, samples=1_000):
    return generate(SyntheticSpec(n_pids=n_pids, n_unique_stacks=rows,
                                  n_rows=rows, total_samples=samples,
                                  seed=seed))


class CollectingWriter:
    def __init__(self):
        self.profiles = []

    def write(self, labels, blob):
        self.profiles.append((labels, blob))


def _wait(cond, timeout=10.0, tick=0.005):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(tick)
    return True


# -- fault grammar: the hang kind ---------------------------------------------


def test_hang_fault_kind_parses_with_duration_and_default():
    rules = faults.parse_rules("device.dispatch:hang:ms=250,count=2")
    assert rules[0].kind == "hang"
    assert rules[0].latency_s == pytest.approx(0.25)
    assert rules[0].count == 2
    # No ms= -> "forever" at any realistic watchdog deadline.
    assert faults.parse_rules("device.probe:hang")[0].latency_s == 3600.0


def test_hang_fault_sleeps_at_the_site():
    slept = []
    inj = faults.FaultInjector.from_spec("x:hang:ms=40", seed=0,
                                         sleep=slept.append)
    inj.check("x")
    assert slept == [pytest.approx(0.04)]
    assert inj.stats() == {"x": 1}


# -- the subprocess probe -----------------------------------------------------


def test_subprocess_probe_kills_a_hung_probe_within_deadline():
    t0 = time.monotonic()
    ok, detail, platform = subprocess_probe(
        0.5, code="import time; time.sleep(60)")
    assert not ok and "hung" in detail and platform is None
    assert time.monotonic() - t0 < 10  # the child was KILLED, not joined


def test_subprocess_probe_reports_a_crashing_probe():
    ok, detail, _ = subprocess_probe(30, code="raise SystemExit(3)")
    assert not ok and "rc=3" in detail


def test_subprocess_probe_names_the_platform_it_ran_on():
    """A bare "1" is no longer a pass: JAX lands on XLA:CPU quietly when
    an accelerator fails to initialise, so the probe must say where it
    ran (the real probe code is exercised by the slow test below)."""
    ok, detail, platform = subprocess_probe(30, code="print(1, 'tpu')")
    assert ok and platform == "tpu" and "tpu" in detail
    ok, detail, platform = subprocess_probe(30, code="print(1)")
    assert not ok and "wrong output" in detail and platform is None


@pytest.mark.slow
def test_subprocess_probe_real_backend_roundtrip():
    # The real probe code: backend init + put + jit + fetch in a child.
    ok, detail, platform = subprocess_probe(120)
    assert ok, detail
    assert platform == "cpu"  # conftest pins the children's platform too


def test_inprocess_probe_round_trips_on_the_held_backend():
    from parca_agent_tpu.runtime.device_health import inprocess_probe

    ok, detail, platform = inprocess_probe(60)
    assert ok, detail
    assert platform == "cpu"


# -- one owner per chip: claim, foreign platforms, probe shape ----------------


def test_registry_refuses_a_probe_from_a_foreign_platform():
    """The agent owns a TPU; a re-probe that 'passed' on cpu (a child
    that could not get the chip and fell back quietly) proves nothing:
    refused, counted failed, no progress toward promotion."""
    results = iter([(True, "ok on cpu", "cpu"), (True, "ok on tpu", "tpu")])
    reg = DeviceHealthRegistry(
        probe=lambda: next(results), probe_timeout_s=5, promote_after=1,
        cooldown_windows=1, start_state=STATE_HEALTHY,
        claim=lambda: {"platform": "tpu", "device_kind": "TPU v5 lite",
                       "device_count": 1})
    assert reg.claim_backend()["platform"] == "tpu"
    assert reg.platform == "tpu"
    assert reg.claim_backend() is None  # once
    reg.record_hang()
    reg.tick_window()  # cooldown over -> probe 1 (foreign)
    assert _wait(lambda: reg.stats["probes_refused"] == 1)
    assert reg.stats["probes_failed"] == 1 and reg.stats["probes_ok"] == 0
    assert "refused" in reg.last_error and not reg.shadow_pending
    for _ in range(6):  # doubled cooldown, then probe 2 (ours)
        reg.tick_window()
        _wait(lambda: not reg.snapshot()["probe_in_flight"])
        if reg.shadow_pending:
            break
    assert reg.shadow_pending and reg.stats["probes_ok"] == 1
    assert reg.snapshot()["platform"] == "tpu"


def test_claim_on_a_platform_the_bringup_probe_did_not_prove_demotes():
    """Bring-up probe child ran on tpu; the agent's own init then landed
    on cpu (the chip was lost in between): said loudly, and demoted."""
    reg = DeviceHealthRegistry(
        probe=lambda: (True, "ok on tpu", "tpu"), probe_timeout_s=5,
        claim=lambda: {"platform": "cpu", "device_kind": "cpu",
                       "device_count": 1})
    reg.start()
    assert reg.wait_bringup(10) and reg.state == STATE_HEALTHY
    assert reg.snapshot()["probe_platform"] == "tpu"
    reg.claim_backend()
    assert reg.platform == "cpu"
    assert reg.state == STATE_DEGRADED
    assert reg.stats["demotions_total"] == 1
    assert "bring-up probe ran on tpu" in reg.last_error


def test_wait_bringup_resolves_on_failure_too():
    reg = DeviceHealthRegistry(probe=lambda: (False, "no chip"),
                               probe_timeout_s=5)
    assert not reg.wait_bringup(0.01)  # not started: still probing
    reg.start()
    assert reg.wait_bringup(10) and reg.state == STATE_DEGRADED
    quiet = DeviceHealthRegistry(probe=None)
    quiet.start()
    assert quiet.wait_bringup(0) and quiet.state == STATE_HEALTHY


# -- the registry state machine -----------------------------------------------


def test_bringup_probe_ok_promotes_probing_to_healthy():
    reg = DeviceHealthRegistry(probe=lambda: (True, "ok"),
                               probe_timeout_s=5)
    assert reg.state == STATE_PROBING
    assert reg.window_mode() == "fallback"  # capture is safe during bring-up
    reg.start()
    assert _wait(lambda: reg.state == STATE_HEALTHY)
    assert reg.window_mode() == "device"
    assert reg.stats["probes_ok"] == 1


def test_bringup_probe_failure_starts_degraded_with_cooldown():
    reg = DeviceHealthRegistry(probe=lambda: (False, "no backend"),
                               probe_timeout_s=5, cooldown_windows=4)
    reg.start()
    assert _wait(lambda: reg.state == STATE_DEGRADED)
    assert reg.window_mode() == "fallback"
    assert reg.cooldown_left == 4
    assert "no backend" in reg.last_error


def test_demote_backoff_doubles_and_caps():
    reg = DeviceHealthRegistry(probe=None, cooldown_windows=2,
                               max_cooldown_windows=5,
                               start_state=STATE_HEALTHY)
    reg.record_hang()
    assert reg.state == STATE_DEGRADED and reg.cooldown_left == 2
    reg.record_shadow(False)     # failed recovery: doubled
    assert reg.cooldown_left == 4
    reg.record_shadow(False)     # capped
    assert reg.cooldown_left == 5


def test_promotion_needs_k_probes_then_a_matching_shadow_window():
    probe_results = [(False, "still down"), (True, "ok"), (True, "ok")]
    reg = DeviceHealthRegistry(probe=lambda: probe_results.pop(0),
                               probe_timeout_s=5, promote_after=2,
                               cooldown_windows=1,
                               start_state=STATE_HEALTHY)
    reg.record_hang()
    assert reg.stats["demotions_total"] == 1
    # Cooldown 1 window, then probes one per window: fail, ok, ok.
    for _ in range(10):
        reg.tick_window()
        if reg.shadow_pending:
            break
        assert _wait(lambda: not reg.snapshot()["probe_in_flight"])
    assert reg.shadow_pending
    assert reg.window_mode() == "shadow"
    assert reg.consecutive_ok_probes == 2
    # The failed probe was one more trip: cooldown doubled behind it.
    assert reg.stats["probes_failed"] == 1
    reg.record_shadow(True)
    assert reg.state == STATE_HEALTHY
    assert reg.stats["promotions_total"] == 1
    assert reg.last_promote_window == reg.windows
    assert reg.wedged_at is None


def test_shadow_mismatch_re_demotes():
    reg = DeviceHealthRegistry(probe=None, cooldown_windows=1,
                               start_state=STATE_HEALTHY)
    reg.record_hang()
    reg.tick_window()
    assert reg.shadow_pending
    reg.record_shadow(False, error="totals diverged")
    assert reg.state == STATE_DEGRADED and not reg.shadow_pending
    assert reg.stats["shadow_mismatches_total"] == 1
    assert "diverged" in reg.last_error


def test_dead_after_trip_budget_stops_probing():
    reg = DeviceHealthRegistry(probe=lambda: (False, "down"),
                               probe_timeout_s=5, cooldown_windows=1,
                               dead_after_trips=2,
                               start_state=STATE_HEALTHY)
    reg.record_hang()  # trip 1
    for _ in range(20):
        reg.tick_window()
        if reg.state == STATE_DEAD:
            break
        _wait(lambda: not reg.snapshot()["probe_in_flight"], timeout=5)
    assert reg.state == STATE_DEAD
    assert reg.window_mode() == "fallback"
    probes_at_death = reg.stats["probes_total"]
    for _ in range(5):
        reg.tick_window()
    assert reg.stats["probes_total"] == probes_at_death  # no more probing


def test_probe_deadline_overrun_counts_as_failed_and_drops_stale_result():
    release = threading.Event()

    def hung_probe():
        release.wait(20)
        return True, "late ok"

    clk = [0.0]
    reg = DeviceHealthRegistry(probe=hung_probe, probe_timeout_s=0.1,
                               probe_deadline_s=0.5, cooldown_windows=1,
                               start_state=STATE_HEALTHY,
                               clock=lambda: clk[0])
    reg.record_hang()
    reg.tick_window()          # cooldown expires -> probe launched
    assert reg.snapshot()["probe_in_flight"]
    clk[0] = 1.0               # past the deadline
    reg.tick_window()          # charged as a hung (failed) probe
    assert reg.stats["probes_failed"] == 1
    assert reg.stats["probes_hung"] == 1
    assert reg.stats["probes_total"] == \
        reg.stats["probes_ok"] + reg.stats["probes_failed"]
    assert not reg.snapshot()["probe_in_flight"]
    assert "deadline" in reg.last_error
    trips_after = reg.trips
    release.set()              # the stale "ok" arrives...
    time.sleep(0.1)
    assert reg.consecutive_ok_probes == 0   # ...and is ignored
    assert reg.trips == trips_after


def test_injected_probe_fault_site_fires_inside_probe_thread():
    faults.install(faults.FaultInjector.from_spec(
        "device.probe:error:count=1", seed=42))
    results = iter([(True, "ok"), (True, "ok")])
    reg = DeviceHealthRegistry(probe=lambda: next(results),
                               probe_timeout_s=5, cooldown_windows=1,
                               promote_after=1, start_state=STATE_HEALTHY)
    reg.record_hang()
    reg.tick_window()
    assert _wait(lambda: reg.stats["probes_failed"] == 1)  # injected error
    # Next probe (cooldown doubled to 2) passes; the gate advances.
    for _ in range(6):
        reg.tick_window()
        _wait(lambda: not reg.snapshot()["probe_in_flight"], timeout=5)
        if reg.shadow_pending:
            break
    assert reg.shadow_pending


# -- the profiler's wedge -> cooldown -> inflight-gated retry path ------------
# (the previously untested path, now driven via hang injection)


def test_profiler_hang_injection_wedge_cooldown_inflight_gated_retry():
    """Satellite coverage: a device.dispatch hang wedges the call, the
    watchdog abandons it, retry is REFUSED while the abandoned call is
    still executing inside the aggregator, and allowed (as a shadow
    window) once it returns."""
    faults.install(faults.FaultInjector.from_spec(
        "device.dispatch:hang:ms=400,count=1", seed=42))
    calls = []

    class Dev(CPUAggregator):
        def aggregate(self, snapshot):
            calls.append(1)
            return super().aggregate(snapshot)

    w = CollectingWriter()
    snaps = [_snap() for _ in range(6)]
    p = CPUProfiler(source=ReplaySource(snaps), aggregator=Dev(),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, device_timeout_s=0.05,
                    device_retry_windows=1)
    assert p.run_iteration()            # hang -> abandoned -> fallback
    assert p.last_error is None and len(w.profiles) == 5
    assert p._device_wedged_at is not None
    # The hang-path window shipped from the CPU like any planned
    # fallback window, and is counted where those are: "0 fallback
    # windows" on /metrics has to mean no window left the device.
    assert p._health.stats["fallback_windows_total"] == 1
    inflight = p._device_inflight
    assert inflight is not None and not inflight.is_set()
    assert len(calls) == 0              # wedged in the injected hang
    # Cooldown expired after one window, but the abandoned call (still
    # sleeping in the injected hang) gates the retry: fallback again.
    assert p.run_iteration()
    assert p._health.shadow_pending     # gate armed...
    assert p._health.stats["fallback_windows_total"] == 2  # ...not taken
    assert inflight.wait(10)            # the abandoned call returns (ok)
    assert len(calls) == 1
    assert p.run_iteration()            # shadow window: device + fallback
    assert len(calls) == 2
    assert p._health.state == STATE_HEALTHY   # matched -> promoted
    assert p.metrics.device_abandoned_ok_total == 1
    assert p.run_iteration()            # back on the device
    assert len(calls) == 3
    assert p._device_wedged_at is None
    assert len(w.profiles) == 4 * 5     # zero windows lost throughout


def test_abandoned_call_late_failure_is_logged_and_counted():
    """Satellite: box["err"] set after the timeout used to vanish; now
    the late failure is inspected, logged, and counted."""
    faults.install(faults.FaultInjector.from_spec(
        "device.dispatch:hang:ms=150,count=1;"
        "device.dispatch:error:count=1", seed=42))
    w = CollectingWriter()
    snaps = [_snap() for _ in range(4)]
    p = CPUProfiler(source=ReplaySource(snaps), aggregator=CPUAggregator(),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, device_timeout_s=0.05,
                    device_retry_windows=1)
    assert p.run_iteration()            # sleeps 150ms, then raises -> hang
    inflight = p._device_inflight
    assert inflight.wait(10)            # abandoned call died late
    assert p.run_iteration()            # inspection happens here
    assert p.metrics.device_abandoned_err_total == 1
    assert p.metrics.device_abandoned_ok_total == 0
    assert p.last_error is None
    assert len(w.profiles) == 2 * 5     # both windows shipped regardless


def test_error_path_fallback_windows_are_counted():
    """A device call that RAISES ships its window from the CPU too —
    one counted fallback window per strike, before any demotion."""
    class Broken(CPUAggregator):
        def aggregate(self, snapshot):
            raise RuntimeError("transfer error")

    w = CollectingWriter()
    p = CPUProfiler(source=ReplaySource([_snap() for _ in range(2)]),
                    aggregator=Broken(),
                    fallback_aggregator=CPUAggregator(), profile_writer=w)
    assert p.run_iteration() and p.run_iteration()
    st = p._health.stats
    assert st["dispatch_errors_total"] == 2 and st["demotions_total"] == 0
    assert st["fallback_windows_total"] == 2
    assert len(w.profiles) == 2 * 5


def test_first_device_call_gets_the_long_budget_exactly_once():
    """The process's first guarded call is compile + population insert,
    not a device wait: it runs under first_device_timeout_s; every
    later call under the steady-state bound, which is not raised."""
    faults.install(faults.FaultInjector.from_spec(
        "device.dispatch:hang:ms=300,count=2", seed=42))
    w = CollectingWriter()
    p = CPUProfiler(source=ReplaySource([_snap() for _ in range(2)]),
                    aggregator=CPUAggregator(),
                    fallback_aggregator=CPUAggregator(), profile_writer=w,
                    device_timeout_s=0.05, first_device_timeout_s=30.0)
    assert p.run_iteration()            # 300 ms inside the 30 s budget
    assert p._health.stats["hangs_total"] == 0
    assert p._health.state == STATE_HEALTHY
    assert p.run_iteration()            # 300 ms against 50 ms: a hang
    assert p._health.stats["hangs_total"] == 1
    assert p._device_inflight.wait(10)


def test_device_failure_strikes_demote_then_shadow_recovers():
    boom = {"on": True}
    calls = []

    class Flaky(CPUAggregator):
        def aggregate(self, snapshot):
            calls.append(1)
            if boom["on"]:
                raise RuntimeError("transfer error")
            return super().aggregate(snapshot)

    w = CollectingWriter()
    snaps = [_snap() for _ in range(8)]
    p = CPUProfiler(source=ReplaySource(snaps), aggregator=Flaky(),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, device_timeout_s=2,
                    device_retry_windows=2)
    for _ in range(3):                  # three consecutive failures...
        assert p.run_iteration()
    assert p._health.state == STATE_DEGRADED   # ...demote
    assert p._health.stats["dispatch_errors_total"] == 3
    boom["on"] = False
    n_calls = len(calls)
    assert p.run_iteration()            # cooldown window: no device touch
    assert len(calls) == n_calls
    assert p.run_iteration()            # shadow window
    assert len(calls) == n_calls + 1
    assert p._health.state == STATE_HEALTHY
    assert len(w.profiles) == 5 * 5     # every window shipped


# -- the scripted outage acceptance test --------------------------------------


@pytest.mark.parametrize("n_pids, rows, samples, seed", [
    (5, 40, 1_000, 1), (8, 64, 2_000, 3)])
def test_scripted_device_outage_zero_windows_lost(n_pids, rows, samples,
                                                  seed):
    """THE acceptance bar (ISSUE criteria): chaos injects a 2-window
    device.dispatch hang and one device.probe hang; zero windows may be
    dropped (every demoted window ships via the CPU fallback), demotion
    happens within the hang window itself, and promotion lands within
    the re-probe budget."""
    faults.install(faults.FaultInjector.from_spec(
        "device.dispatch:hang:ms=250,count=2;"
        "device.probe:hang:ms=250,count=1", seed=42))
    reg = DeviceHealthRegistry(probe=lambda: (True, "ok"),
                               probe_timeout_s=0.2, probe_deadline_s=2.0,
                               promote_after=1, cooldown_windows=1)
    reg.start()
    snap = _snap(seed, n_pids, rows, samples)

    class Source:
        def __init__(self, budget):
            self.left = budget

        def poll(self):
            if self.left <= 0:
                return None
            self.left -= 1
            return snap

    w = CollectingWriter()
    p = CPUProfiler(source=Source(80), aggregator=CPUAggregator(),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, device_timeout_s=0.05,
                    device_health=reg)
    windows = 0
    t0 = time.monotonic()
    while p.run_iteration():
        windows += 1
        # Zero loss: every window — healthy, demoted, shadow — ships all
        # its pids' profiles (demotion within the window deadline).
        assert len(w.profiles) == windows * n_pids, \
            f"window {windows} lost profiles"
        s = reg.snapshot()
        if s["stats"]["hangs_total"] >= 2 \
                and s["last_promote_window"] is not None:
            break
        assert time.monotonic() - t0 < 30, "promotion did not land"
        time.sleep(0.02)
    s = reg.snapshot()
    assert s["stats"]["hangs_total"] == 2          # both hangs consumed
    assert faults.get().stats()["device.probe"] == 1  # probe hang fired
    assert s["state"] == STATE_HEALTHY             # promoted back
    # The outage was ridden out on the fallback, and promotion passed
    # its shadow window.
    assert s["stats"]["fallback_windows_total"] >= 1
    assert s["stats"]["shadow_windows_total"] >= 1
    # Promotion within the configured re-probe budget: cooldowns of 1+2
    # windows, one probe round each, plus the shadow window — bounded
    # well under the window budget above.
    assert s["last_promote_window"] - s["last_demote_window"] <= windows
    assert p.metrics.errors_total == 0


# -- fleet: bounded join ------------------------------------------------------


def test_fleet_join_timeout_raises_fleet_join_error():
    from parca_agent_tpu.parallel.distributed import (
        FleetJoinError,
        fleet_initialize,
    )

    faults.install(faults.FaultInjector.from_spec(
        "fleet.join:hang:ms=5000", seed=42))
    t0 = time.monotonic()
    with pytest.raises(FleetJoinError, match="did not complete"):
        fleet_initialize("127.0.0.1:1", 2, 0, timeout_s=0.2)
    assert time.monotonic() - t0 < 5


def test_fleet_join_refusal_raises_fleet_join_error():
    from parca_agent_tpu.parallel.distributed import (
        FleetJoinError,
        fleet_initialize,
    )

    faults.install(faults.FaultInjector.from_spec(
        "fleet.join:error", seed=42))
    with pytest.raises(FleetJoinError, match="failed"):
        fleet_initialize("127.0.0.1:1", 2, 0, timeout_s=5)


def test_cli_fleet_join_failure_continues_single_node(tmp_path):
    """Satellite: a refusing coordinator at startup degrades the agent to
    single-node instead of crashing it."""
    from parca_agent_tpu.capture.formats import save_snapshot
    from parca_agent_tpu.cli import run

    snap_path = tmp_path / "w.bin"
    save_snapshot(_snap(), str(snap_path))
    rc = run(["--capture", "replay", "--replay", str(snap_path),
              "--http-address", "127.0.0.1:0", "--windows", "1",
              "--profiling-duration", "0.05",
              "--fleet-coordinator", "127.0.0.1:1",
              "--fleet-nodes", "2", "--fleet-node-id", "0",
              "--fault-inject", "fleet.join:error", "--fault-seed", "42"])
    assert rc == 0


# -- fleet: hang-proof collectives --------------------------------------------


def _single_node_merger(**kw):
    """A FleetWindowMerger over the implicit single-process group (no
    jax.distributed init needed: process_count() == 1). The exact-merge
    shard_map program is stubbed with its numpy oracle — the machinery
    under test is the bound/degrade/rejoin layer AROUND the collective
    (the fleet.collective site and the width-agreement allgather still
    run), not the merge math (tests/test_fleet.py owns that)."""
    import numpy as np

    from parca_agent_tpu.parallel.distributed import FleetWindowMerger

    m = FleetWindowMerger(interval_s=0.0, **kw)
    real = m._merge_collective

    def merge(h1, h2, counts):
        faults.inject("fleet.collective")
        from parca_agent_tpu.parallel.distributed import _agree_width

        _agree_width(len(h1))            # the real pre-merge collective
        key = (h1.astype(np.uint64) << np.uint64(32)) | h2
        uniq, inv = np.unique(key, return_inverse=True)
        sums = np.zeros(len(uniq), np.int64)
        np.add.at(sums, inv, counts.astype(np.int64))
        u1 = (uniq >> np.uint64(32)).astype(np.uint32)
        u2 = uniq.astype(np.uint32)
        return u1, u2, sums.astype(np.int32)

    m._merge_collective = merge
    del real
    return m


def _submit(m, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    h = rng.integers(0, 2**32, 16, dtype=np.uint64).astype(np.uint32)
    m.submit_window((h, h), np.ones(16, np.int32))


def test_collective_timeout_degrades_then_rejoins():
    faults.install(faults.FaultInjector.from_spec(
        "fleet.collective:hang:ms=600,count=1", seed=42))
    m = _single_node_merger(collective_timeout_s=0.1,
                            rejoin_after_rounds=2)
    _submit(m, 1)
    m.merge_round()                      # wedged -> degraded
    assert m.degraded
    assert m.stats["collective_timeouts"] == 1
    assert m.fleet_stats == {}           # no bogus gauges from the hang
    # Degraded rounds: node-local only, counted, never raising.
    _submit(m, 2)
    m.merge_round()
    assert m.stats["local_only_rounds"] == 1
    # Next degraded round hits the rejoin schedule, but the abandoned
    # collective may still be in flight — wait it out, then rejoin.
    assert _wait(m._inflight_clear, timeout=10)
    for _ in range(4):
        m.merge_round()
        if not m.degraded:
            break
    assert not m.degraded
    assert m.stats["rejoins"] == 1
    # Back on the schedule: a real merge round completes with gauges.
    _submit(m, 3)
    m.merge_round()
    assert m.fleet_stats["fleet_rounds"] == 1
    assert m.fleet_stats["fleet_total_samples"] == 16
    assert m.failed is None              # the actor never died


def test_collective_failure_degrades_instead_of_killing_fleet_mode():
    faults.install(faults.FaultInjector.from_spec(
        "fleet.collective:error:count=1", seed=42))
    m = _single_node_merger(collective_timeout_s=5,
                            rejoin_after_rounds=1)
    _submit(m)
    m.merge_round()
    assert m.degraded and m.failed is None
    assert "injected fault" in m.last_degrade_error
    m.merge_round()                      # rejoin probe (injector spent)
    assert not m.degraded


def test_failed_rejoin_probe_backs_off():
    faults.install(faults.FaultInjector.from_spec(
        "fleet.collective:error:count=3", seed=42))
    m = _single_node_merger(collective_timeout_s=5, rejoin_after_rounds=1,
                            max_rejoin_after_rounds=8)
    m.merge_round()                      # fault 1: degrade
    assert m.degraded
    m.merge_round()                      # fault 2: rejoin probe fails
    assert m.stats["rejoin_probes_failed"] == 1
    assert m._rejoin_in == 2             # doubled backoff
    m.merge_round()
    m.merge_round()                      # fault 3: second probe fails
    assert m.stats["rejoin_probes_failed"] == 2
    assert m._rejoin_in == 4


def test_heartbeat_reports_stall_and_request_rejoin_pulls_forward():
    m = _single_node_merger(collective_timeout_s=None,
                            rejoin_after_rounds=8)
    assert m.heartbeat()
    m.round_started_at = m._clock() - 1000  # a wedged unbounded round
    assert not m.heartbeat()
    m.round_started_at = None
    m.degraded = True
    m._rejoin_in = 8
    m.request_rejoin()
    assert m._rejoin_in == 1


# -- observability ------------------------------------------------------------


def test_metrics_and_healthz_expose_device_state():
    import json
    import urllib.request

    from parca_agent_tpu.web import AgentHTTPServer, render_metrics

    reg = DeviceHealthRegistry(probe=None, start_state=STATE_HEALTHY)
    reg.record_hang()
    text = render_metrics([], device_health=reg)
    assert 'parca_agent_device_state{state="degraded"} 1' in text
    assert 'parca_agent_device_state{state="healthy"} 0' in text
    assert "parca_agent_device_hangs_total 1" in text
    assert "parca_agent_device_demotions_total 1" in text

    srv = AgentHTTPServer(port=0, device_health=reg)
    srv.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=5).read()
        got = json.loads(body)
        assert got["device"]["state"] == "degraded"
        assert got["device"]["stats"]["hangs_total"] == 1
        # A demoted device never turns readiness red.
        assert got["status"] == "healthy"
    finally:
        srv.stop()


def test_abandoned_call_counters_on_metrics():
    from parca_agent_tpu.web import render_metrics

    p = CPUProfiler(source=ReplaySource([]), aggregator=CPUAggregator())
    p.metrics.device_abandoned_ok_total = 2
    p.metrics.device_abandoned_err_total = 1
    text = render_metrics([p])
    assert 'parca_agent_profiler_device_abandoned_ok_total{profiler="cpu"} 2' \
        in text
    assert 'parca_agent_profiler_device_abandoned_err_total{profiler="cpu"} 1' \
        in text


def test_cli_flags_parse():
    from parca_agent_tpu.cli import build_parser

    args = build_parser().parse_args([
        "--device-probe-timeout", "30", "--device-promote-after", "3",
        "--fleet-join-timeout", "15", "--collective-timeout", "7",
    ])
    assert args.device_probe_timeout == 30.0
    assert args.device_promote_after == 3
    assert args.fleet_join_timeout == 15.0
    assert args.collective_timeout == 7.0


def test_shadow_compare_digests():
    from parca_agent_tpu.runtime.device_health import shadow_compare

    snap = _snap()
    a = CPUAggregator().aggregate(snap)
    b = CPUAggregator().aggregate(snap)
    assert shadow_compare(a, b)
    b[0].values[0] += 1                  # one count diverges
    assert not shadow_compare(a, b)
    assert not shadow_compare(a, b[:-1])  # a missing pid diverges
