"""Workload zoo (chaos) suite: the matrix must be deterministic, the
rows must clear their bars through the REAL window loop, and the
``zoo.scenario`` chaos site must fail open (a poisoned window build
degrades to an idle filler — the run narrows, it never dies).

The full sweep is `make bench-zoo` (`python -m parca_agent_tpu.bench_zoo`);
this suite pins the contracts cheaply at reduced scale: seeded
determinism (same seed -> same schedule, same bars, same shipped-bytes
digest), schedule coverage (every scenario exactly once), one
representative scored row per hardening arm, and the chaos drill.
"""

import pytest

from parca_agent_tpu.bench_zoo import (
    SCENARIOS, build_schedule, run_scenario, run_zoo)
from parca_agent_tpu.utils import faults

pytestmark = pytest.mark.chaos

# The chaos site this module drills (utils/faults.py SITES).
SITE = "zoo.scenario"


def test_scenario_registry_covers_the_required_axes():
    # The breadth matrix the robustness arc calls for: one scenario per
    # orthogonal axis, >= 6 rows.
    axes = {cls().axis for cls in SCENARIOS.values()}
    assert len(SCENARIOS) >= 6
    assert {"identity", "jit", "churn", "depth", "kernel",
            "tenancy"} <= axes


def test_schedule_is_seeded_and_covers_every_scenario():
    a = build_schedule(99)
    b = build_schedule(99)
    c = build_schedule(100)
    assert a == b
    assert a != c
    assert sorted(e["scenario"] for e in a) == sorted(SCENARIOS)


def test_window_builds_are_deterministic():
    for name, cls in SCENARIOS.items():
        s1, s2 = cls(), cls()
        w1 = s1.build(7, 0.25)
        w2 = s2.build(7, 0.25)
        assert len(w1) == len(w2) and len(w1) >= 6, name
        for a, b in zip(w1, w2):
            assert a.snapshot.counts.tolist() == b.snapshot.counts.tolist()
            assert (a.snapshot.stacks == b.snapshot.stacks).all()
            assert a.files == b.files
            assert a.starttimes == b.starttimes


def test_seeded_run_is_digest_identical():
    # Same zoo seed -> same schedule, same scores, same canonical
    # digest of the shipped output. A digest drift here is a behaviour
    # change in the window loop, not noise.
    a = run_scenario("deep_stacks", 31, scale=0.25)
    b = run_scenario("deep_stacks", 31, scale=0.25)
    assert a["digest"] == b["digest"]
    # Every bar but the one that reads a wall clock: `a` is this file's
    # first run, and in a worker that starts with this file its first
    # window carries the lazy imports, seconds over the 2 s ceiling.
    seeded = [k for k in a["bars"] if k != "close_latency_ceiling"]
    assert [a["bars"][k] for k in seeded] == [b["bars"][k] for k in seeded]
    assert a["bars"].keys() == b["bars"].keys()
    c = run_scenario("deep_stacks", 32, scale=0.25)
    assert a["digest"] != c["digest"]  # the seed genuinely feeds content


def test_pid_reuse_row_passes_both_arms():
    hardened = run_scenario("pid_reuse", 11, scale=0.25, hardened=True)
    assert hardened["passed"], hardened["bars"]
    control = run_scenario("pid_reuse", 11, scale=0.25, hardened=False)
    assert control["passed"], control["bars"]
    assert control["misattributed_mass"] > 0


def test_fork_storm_row_sheds_without_losing_windows():
    row = run_scenario("fork_storm", 13, scale=0.25)
    assert row["passed"], row["bars"]
    assert row["windows_lost"] == 0
    assert row["admission"]["fork_storm_sheds_total"] >= 1


def test_run_zoo_sweep_scores_every_row():
    out = run_zoo(5, scale=0.25)
    assert out["scenarios_total"] == len(SCENARIOS)
    assert out["passed"], [
        (r["scenario"], {k: v for k, v in r["bars"].items() if not v})
        for r in out["rows"] if not r["passed"]]


def test_injected_scenario_fault_degrades_builds_not_the_run():
    # Chaos site zoo.scenario: a window build that the injector kills
    # degrades to an idle filler window — counted, fed through the
    # loop, never a lost run. Bars are allowed to fail under faults;
    # the contract is survival + accounting.
    faults.install(faults.FaultInjector.from_spec(
        f"{SITE}:error:p=1.0", seed=42))
    try:
        row = run_scenario("kernel_heavy", 17, scale=0.25)
    finally:
        faults.install(None)
    assert row["degraded_builds"] == row["windows"]
    assert row["windows_lost"] == 0
    assert row["windows_closed"] == row["windows"]


# -- the endurance matrix: path x cadence x outage ---------------------------

def test_matrix_runs_every_path_cadence_and_outage_row():
    from parca_agent_tpu.bench_zoo import run_matrix

    m = run_matrix(11, scale=0.25, names=["pid_reuse"],
                   cadences=(10.0, 1.0), outages=("dispatch",))
    # 3 paths x 2 cadences + 1 outage x 2 cadences, one scenario.
    assert m["rows_total"] == 8
    assert m["passed"], [
        (r["scenario"], r["path"], r["window_s"], r["outage"],
         {k: v for k, v in r["bars"].items() if not v})
        for r in m["rows"] if not r["passed"]]
    cross = m["cross"][0]
    # The cross-arm contract: the fast arms ship byte-identical pprof
    # sequences, all three arms agree on per-window mass, and the
    # scalar digest is cadence-invariant.
    assert cross["bars"]["path_bytes_identical@10s"]
    assert cross["bars"]["path_bytes_identical@1s"]
    assert cross["bars"]["path_mass_identical@10s"]
    assert cross["bars"]["path_mass_identical@1s"]
    assert cross["bars"]["cadence_digest_identical"]


def test_outage_probe_demotes_and_recovers_at_subsecond_cadence():
    row = run_scenario("fork_storm", 23, scale=0.25, outage="probe",
                       window_s=1.0)
    assert row["passed"], row["bars"]
    assert row["bars"]["outage_injected"]
    assert row["bars"]["outage_demoted"]
    assert row["bars"]["outage_recovered"]
    assert row["windows_lost"] == 0


def test_outage_rows_require_the_scalar_path():
    with pytest.raises(ValueError):
        run_scenario("pid_reuse", 3, scale=0.25, path="pipeline",
                     outage="dispatch")


def test_injected_path_fault_falls_open_to_oneshot_close():
    # Chaos site zoo.path: a poisoned streaming drain discards the
    # feeder's partial window and falls open to the aggregator's
    # one-shot close — counted, never a lost window.
    faults.install(faults.FaultInjector.from_spec(
        "zoo.path:error:count=2", seed=42))
    try:
        row = run_scenario("pid_reuse", 19, scale=0.25, path="streaming")
    finally:
        faults.install(None)
    assert row["streaming"]["path_fallbacks"] >= 1
    assert row["windows_lost"] == 0
    assert row["passed"], row["bars"]
