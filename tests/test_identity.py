"""Generation-stamped process identity (chaos) suite.

Pid reuse is the quiet data-corruption path of a procfs profiler: the
kernel hands a recycled pid to a NEW process and every bare-pid cache in
the agent — the aggregator's per-pid location registry above all —
silently attributes the new process's samples to the dead one's binary.
process/identity.py stamps identity the way the kernel does, ``(pid,
starttime)``, and fires per-layer invalidators on a mismatch. This suite
pins: starttime parsing, reuse detection and invalidator fan-out, the
aggregator/quarantine invalidation semantics, the cross-process
attribution REGRESSION (the bug must reproduce with the stamp pinned
off, and vanish with it on — through the real window loop, via the
workload zoo's pid-reuse scenario), the ``process.identity`` chaos
site's fail-open contract, and that the bulk check (one ``np.unique``,
one ``/proc`` listing, a read per listed pid) equals the per-row loop
it replaced, which stays here as the plain reference; that the check's
three steps are spans under the profiler's ``identity``; and that every
listed pid is read in every window, whatever the windows before read.
"""

import sys
import threading

import numpy as np
import pytest

from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.bench_zoo import run_scenario
from parca_agent_tpu.capture.formats import STACK_SLOTS, WindowSnapshot
from parca_agent_tpu.process import identity as identity_mod
from parca_agent_tpu.process.identity import (
    ProcessIdentityTracker, read_starttime)
from parca_agent_tpu.process.maps import ProcMapping, build_mapping_table
from parca_agent_tpu.runtime.quarantine import QuarantineRegistry
from parca_agent_tpu.runtime.trace import FlightRecorder
from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.vfs import FakeFS

pytestmark = pytest.mark.chaos

# The chaos site this module drills (utils/faults.py SITES).
SITE = "process.identity"


# -- starttime parsing --------------------------------------------------------

def test_read_starttime_parses_field_22():
    # comm may embed spaces AND parens; parsing must anchor after the
    # LAST ')'. starttime is field 22 (1-based), index 19 after comm.
    rest = ["R", "1", "1", "1", "0", "-1", "4194560", "0", "0", "0", "0",
            "5", "6", "0", "0", "20", "0", "1", "0", "123456789", "0"]
    fs = FakeFS({"/proc/7/stat":
                 ("7 (a (b) c) " + " ".join(rest)).encode()})
    assert read_starttime(fs, 7) == 123456789


def test_read_starttime_raises_on_garbage():
    fs = FakeFS({"/proc/7/stat": b"no parens here"})
    with pytest.raises(ValueError):
        read_starttime(fs, 7)
    with pytest.raises(FileNotFoundError):
        read_starttime(fs, 8)


# -- reuse detection + invalidator fan-out ------------------------------------

def _tracker(world):
    return ProcessIdentityTracker(starttime_of=world.__getitem__,
                                  enabled=True)


def test_same_generation_never_invalidates():
    world = {10: 100, 11: 200}
    t = _tracker(world)
    fired = []
    t.add_invalidator("rec", fired.append)
    for _ in range(3):
        assert t.observe_window([10, 11, 11]) == []
    assert fired == []
    assert t.metrics()["reuse_detected_total"] == 0
    # Duplicate pids in one window are checked once.
    assert t.metrics()["checks_total"] == 6


def test_reuse_fires_every_invalidator_and_survives_a_raising_one():
    world = {10: 100}
    t = _tracker(world)
    fired = []
    t.add_invalidator("boom", lambda pid: 1 / 0)
    t.add_invalidator("rec", fired.append)
    t.observe_window([10])
    world[10] = 999  # the kernel recycled pid 10
    assert t.observe_window([10]) == [10]
    # The raising layer is counted; the next one still dropped state.
    assert fired == [10]
    m = t.metrics()
    assert m["reuse_detected_total"] == 1
    assert m["invalidations_total"] == 1
    assert m["invalidation_errors_total"] == 1
    # The new generation is now the remembered one: no re-fire.
    assert t.observe_window([10]) == []


def test_unreadable_stat_keeps_remembered_generation():
    # A pid that exits mid-window keeps its entry — if the pid comes
    # back it is BY DEFINITION a new incarnation, and the stale entry
    # is exactly what detects it.
    world = {10: 100}
    t = _tracker(world)
    t.observe_window([10])
    del world[10]  # exited: starttime_of raises KeyError
    assert t.observe_window([10]) == []
    assert t.metrics()["errors_total"] == 1
    world[10] = 555  # recycled
    assert t.observe_window([10]) == [10]


def test_disabled_tracker_is_inert():
    world = {10: 100}
    t = ProcessIdentityTracker(starttime_of=world.__getitem__,
                               enabled=False)
    fired = []
    t.add_invalidator("rec", fired.append)
    t.observe_window([10])
    world[10] = 999
    assert t.observe_window([10]) == []
    assert fired == []
    assert t.metrics()["reuse_detected_total"] == 0


def test_env_flag_pins_hardening_off(monkeypatch):
    monkeypatch.setenv("PARCA_NO_PID_GENERATION", "1")
    t = ProcessIdentityTracker(starttime_of=lambda pid: 1)
    assert t.enabled is False
    monkeypatch.delenv("PARCA_NO_PID_GENERATION")
    assert ProcessIdentityTracker(starttime_of=lambda pid: 1).enabled


def test_forget_drops_the_generation():
    world = {10: 100}
    t = _tracker(world)
    t.observe_window([10])
    t.forget(10)
    world[10] = 999
    # No remembered generation -> first observation, not a reuse.
    assert t.observe_window([10]) == []


# -- per-layer invalidation semantics -----------------------------------------

def _one_pid_snapshot(pid, path, time_ns=0):
    maps = {pid: [ProcMapping(start=0x400000, end=0x500000, perms="r-xp",
                              offset=0, dev="08:01", inode=1, path=path)]}
    stacks = np.zeros((1, STACK_SLOTS), np.uint64)
    stacks[0, :3] = [0x400010, 0x400020, 0x400030]
    return WindowSnapshot(
        np.array([pid], np.int32), np.array([pid], np.int32),
        np.array([50], np.int64), np.array([3], np.int32),
        np.array([0], np.int32), stacks, build_mapping_table(maps),
        time_ns=time_ns)


def test_aggregator_invalidate_pid_rebinds_the_registry():
    # The tentpole's core fix: after invalidate_pid, the SAME (pid,
    # stack) key must re-register against the CURRENT mapping table —
    # without it the recycled pid inherits the dead binary's locations.
    agg = DictAggregator(capacity=1 << 12)
    old = agg.aggregate(_one_pid_snapshot(42, "/app/old", time_ns=1))
    assert old[0].mappings[0].path == "/app/old"
    epoch = agg.registry_epoch
    assert agg.invalidate_pid(42) is True
    assert agg.registry_epoch > epoch  # encoder/statics validity key
    new = agg.aggregate(_one_pid_snapshot(42, "/app/new", time_ns=2))
    assert new[0].mappings[0].path == "/app/new"
    assert new[0].total() == 50
    assert agg.stats["pid_invalidations"] == 1


def test_aggregator_invalidation_without_stamp_inherits_stale_mappings():
    # The un-hardened failure mode, at the unit level: same pid, same
    # addresses, NEW binary in the snapshot's table — the registry
    # still resolves through the dead generation's mapping.
    agg = DictAggregator(capacity=1 << 12)
    agg.aggregate(_one_pid_snapshot(42, "/app/old", time_ns=1))
    new = agg.aggregate(_one_pid_snapshot(42, "/app/new", time_ns=2))
    assert new[0].mappings[0].path == "/app/old"


def test_quarantine_forget_pid_clears_strikes():
    reg = QuarantineRegistry(max_strikes=2)
    reg.record_error(9, "perfmap.parse", ValueError("x"))
    reg.forget_pid(9)
    # A fresh incarnation re-earns its budget from zero: one more
    # strike must NOT trip the 2-strike ladder.
    reg.record_error(9, "perfmap.parse", ValueError("x"))
    assert reg.level(9) == 0
    assert reg.stats["pids_forgotten_total"] == 1


# -- the regression, end to end through the real window loop ------------------

def test_cross_process_attribution_regression():
    # Un-hardened arm (the pre-PR agent): tenant B's samples land on
    # tenant A's binary. Hardened arm: zero misattribution, every
    # recycled pid detected. Same seed, same windows, same loop.
    bad = run_scenario("pid_reuse", 2026, scale=0.25, hardened=False)
    assert bad["misattributed_mass"] > 0
    assert bad["bars"]["misattribution_reproduced"]
    good = run_scenario("pid_reuse", 2026, scale=0.25, hardened=True)
    assert good["misattributed_mass"] == 0
    assert good["passed"], good["bars"]
    assert good["identity"]["reuse_detected_total"] >= 2


# -- chaos drill: the process.identity site is fail-open ----------------------

def test_injected_identity_fault_is_contained():
    # Chaos site process.identity: the injected error is counted, the
    # window proceeds UNHARDENED (no invalidation fired), and nothing
    # raises into the window loop.
    world = {10: 100}
    t = _tracker(world)
    fired = []
    t.add_invalidator("rec", fired.append)
    t.observe_window([10])
    faults.install(faults.FaultInjector.from_spec(
        f"{SITE}:error", seed=42))
    try:
        world[10] = 999
        assert t.observe_window([10]) == []  # degraded, not raised
        assert t.metrics()["errors_total"] >= 1
        assert fired == []
    finally:
        faults.install(None)
    # Fault lifted: the next window detects the still-stale entry.
    assert t.observe_window([10]) == [10]
    assert fired == [10]


def test_metrics_and_healthz_surface_identity():
    from parca_agent_tpu.web import render_metrics

    world = {10: 100}
    t = _tracker(world)
    t.observe_window([10])
    world[10] = 999
    t.observe_window([10])
    text = render_metrics([], identity=t)
    assert "parca_agent_pid_reuse_detected_total 1" in text
    assert "parca_agent_pid_identity_checks_total" in text
    snap = t.snapshot()
    assert snap["enabled"] is True
    assert snap["last_reuse"]["pid"] == 10
    assert "parca_agent_pid_identity_absent_total 0" in text


def test_absent_series_counts_pids_settled_by_the_listing():
    from parca_agent_tpu.web import render_metrics

    fs = _procfs({10: 100})
    t = ProcessIdentityTracker(fs=fs, enabled=True)
    t.observe_window([10, 11, 12])
    t.observe_window([10, 12])
    text = render_metrics([], identity=t)
    assert "parca_agent_pid_identity_absent_total 3" in text
    assert "parca_agent_pid_identity_checks_total 2" in text
    # An absent pid is today's "exited mid-window": an error, no open.
    assert "parca_agent_pid_identity_errors_total 3" in text
    assert t.snapshot()["stats"]["absent_total"] == 3


# -- the bulk check against the plain per-row loop ----------------------------

def _stat(pid, start):
    """A /proc/<pid>/stat line whose field 22 is ``start``."""
    rest = ["R"] + ["0"] * 18 + [str(start), "0"]
    return (f"{pid} (p {pid}) " + " ".join(rest)).encode()


def _procfs(world, garbled=()):
    """FakeFS /proc over ``{pid: starttime}``: every pid of the world
    is listed; a ``garbled`` one is listed and its stat does not parse
    (the read raises)."""
    files = {"/proc/cpuinfo": b"", "/proc/self/stat": b""}
    for pid, start in world.items():
        files[f"/proc/{pid}/comm"] = b"p\n"
        files[f"/proc/{pid}/stat"] = (b"no parens" if pid in garbled
                                      else _stat(pid, start))
    return CountingFS(files)


class CountingFS(FakeFS):
    """FakeFS that counts what the tracker asks of it."""

    def __init__(self, files=None, listdir_raises=False):
        super().__init__(files)
        self.listdirs: list[str] = []
        self.opens: list[str] = []
        self.listdir_raises = listdir_raises

    def listdir(self, path):
        self.listdirs.append(path)
        if self.listdir_raises:
            raise PermissionError(path)
        return super().listdir(path)

    def open(self, path):
        self.opens.append(path)
        return super().open(path)


class LoopReference:
    """The tracker as it stood before the bulk check, kept as the plain
    reference: a Python loop over every row, one read per distinct pid
    in first-occurrence order, the table and the counters touched pid
    by pid, invalidators fired as each reuse is met."""

    def __init__(self, starttime_of, enabled=True):
        self._start_of = starttime_of
        self.enabled = enabled
        self._gens = {}
        self._invalidators = []
        self.stats = {
            "checks_total": 0, "reuse_detected_total": 0,
            "invalidations_total": 0, "invalidation_errors_total": 0,
            "errors_total": 0, "trims_total": 0}

    def add_invalidator(self, name, fn):
        self._invalidators.append((name, fn))

    def observe_window(self, pids):
        reused = []
        try:
            if not self.enabled:
                return reused
            faults.inject("process.identity")
            seen = set()
            for pid in pids:
                pid = int(pid)
                if pid in seen or pid < 0:
                    continue
                seen.add(pid)
                try:
                    start = int(self._start_of(pid))
                except Exception:
                    self.stats["errors_total"] += 1
                    continue
                self.stats["checks_total"] += 1
                prev = self._gens.get(pid)
                self._gens[pid] = start
                if prev is not None and prev != start:
                    reused.append(pid)
                    self.stats["reuse_detected_total"] += 1
                    self._invalidate(pid)
            self._trim(seen)
        except Exception:
            self.stats["errors_total"] += 1
        return reused

    def _invalidate(self, pid):
        for _name, fn in list(self._invalidators):
            try:
                fn(pid)
                self.stats["invalidations_total"] += 1
            except Exception:
                self.stats["invalidation_errors_total"] += 1

    def _trim(self, live):
        if len(self._gens) <= max(identity_mod._MAX_TRACKED,
                                  4 * len(live)):
            return
        self._gens = {p: s for p, s in self._gens.items() if p in live}
        self.stats["trims_total"] += 1


# case -> how the seeded world is drawn and disturbed. ``reader`` is
# "procfs" (the default batch reader over a FakeFS) or "injected" (a
# starttime_of callable, the zoo's way).
EQUIVALENCE_CASES = {
    "all-live": dict(reader="procfs", absent=0.0),
    "all-absent": dict(reader="procfs", absent=1.0),
    "mixed": dict(reader="procfs", absent=0.5),
    "duplicates-and-pseudo-pids": dict(reader="procfs", absent=0.3,
                                       negatives=True, repeat=7),
    "pid-reused-between-windows": dict(reader="procfs", absent=0.2,
                                       recycle=0.3),
    "reader-raises-for-some-procfs": dict(reader="procfs", absent=0.2,
                                          garbled=0.3, recycle=0.2),
    "reader-raises-for-some-injected": dict(reader="injected", absent=0.4,
                                            recycle=0.2),
    "listing-raises": dict(reader="procfs", absent=0.4, recycle=0.2,
                           listdir_raises=True),
    "injected-fault": dict(reader="procfs", absent=0.2, recycle=0.3,
                           fault_windows=(1, 2)),
    "trim-past-max-tracked": dict(reader="injected", absent=0.1,
                                  recycle=0.1, max_tracked=16, churn=True,
                                  windows=12),
}


@pytest.mark.parametrize("seed", [2026, 2147484201])
@pytest.mark.parametrize("case", sorted(EQUIVALENCE_CASES))
def test_bulk_check_equals_the_per_row_loop(case, seed, monkeypatch):
    """On a seeded random world the bulk tracker and the plain loop
    agree, window by window: the set of reused pids, the generation
    table, every counter the loop had, and the multiset of invalidator
    calls (one of the invalidators raises)."""
    spec = EQUIVALENCE_CASES[case]
    rng = np.random.default_rng(seed)
    if "max_tracked" in spec:
        monkeypatch.setattr(identity_mod, "_MAX_TRACKED",
                            spec["max_tracked"])
    population = rng.choice(np.arange(1, 5000), size=120, replace=False)
    world = {int(p): int(rng.integers(1, 1 << 40)) for p in population
             if rng.random() >= spec["absent"]}
    garbled: set[int] = set()

    if spec["reader"] == "procfs":
        fs_box = {}

        def ref_start_of(pid):
            return read_starttime(fs_box["ref"], pid)
        new = ProcessIdentityTracker(fs=None, enabled=True)
    else:
        def ref_start_of(pid):
            return world[pid]
        new = ProcessIdentityTracker(starttime_of=world.__getitem__,
                                     enabled=True)
    ref = LoopReference(ref_start_of)
    calls = {"new": [], "ref": []}
    for name, t in (("new", new), ("ref", ref)):
        t.add_invalidator("boom", lambda pid: 1 / 0)
        t.add_invalidator("rec", calls[name].append)

    any_reuse = False
    for w in range(spec.get("windows", 5)):
        # Disturb the world the way a host does between two drains.
        live = sorted(world)
        for pid in live:
            if rng.random() < spec.get("recycle", 0.0):
                world[pid] = int(rng.integers(1, 1 << 40))  # recycled
            elif spec.get("churn") and rng.random() < 0.5:
                del world[pid]                              # exited
        if spec.get("churn"):
            fresh = rng.choice(np.arange(5000, 9000), size=40,
                               replace=False) + 4000 * w
            population = np.concatenate([population[-10:], fresh])
            world.update({int(p): int(rng.integers(1, 1 << 40))
                          for p in fresh})
        if "garbled" in spec:
            garbled = {p for p in world if rng.random() < spec["garbled"]}
        if spec["reader"] == "procfs":
            # One tree each: a read must not be shared between the two.
            fs_box["ref"] = _procfs(world, garbled)
            new._fs = _procfs(world, garbled)
            new._fs.listdir_raises = spec.get("listdir_raises", False)
        rows = rng.choice(population, size=600).astype(np.int64)
        rows = np.repeat(rows, spec.get("repeat", 1))
        if spec.get("negatives"):
            rows = np.concatenate([rows, [-1, -1, -7], rows[:5]])
        rng.shuffle(rows)
        if w in spec.get("fault_windows", ()):
            faults.install(faults.FaultInjector.from_spec(
                f"{SITE}:error", seed=seed))
        try:
            got = new.observe_window(rows.astype(np.int32))
            want = ref.observe_window(rows.tolist())
        finally:
            faults.install(None)
        assert got == sorted(want), (case, w)
        any_reuse |= bool(want)
        assert new._gens == ref._gens, (case, w)
        stats = new.metrics()
        absent = stats.pop("absent_total")
        assert stats == ref.stats, (case, w)
        assert sorted(calls["new"]) == sorted(calls["ref"]), (case, w)
        if spec["reader"] == "procfs" and not spec.get("listdir_raises") \
                and w not in spec.get("fault_windows", ()):
            distinct = {int(p) for p in rows if p >= 0}
            assert new._fs.listdirs == ["/proc"]
            assert sorted(new._fs.opens) == sorted(
                f"/proc/{p}/stat" for p in distinct & set(world))
            assert len(fs_box["ref"].opens) == len(distinct)
        elif spec["reader"] == "injected" or spec.get("listdir_raises"):
            assert absent == 0
    # The case did what its name says.
    if spec.get("recycle"):
        assert any_reuse and ref.stats["invalidation_errors_total"] > 0
    if spec["absent"] == 1.0:
        assert new.metrics()["absent_total"] == ref.stats["errors_total"] > 0
    if spec["absent"] == 0.0:
        assert ref.stats["errors_total"] == 0
    if "garbled" in spec or spec["reader"] == "injected":
        assert ref.stats["errors_total"] > new.metrics()["absent_total"]
    if spec.get("listdir_raises"):
        assert new._fs.listdirs == ["/proc"] and new._fs.opens
    if "fault_windows" in spec:
        assert ref.stats["errors_total"] >= len(spec["fault_windows"])
    if "max_tracked" in spec:
        assert ref.stats["trims_total"] > 1


def test_one_listing_a_window_and_no_open_for_an_absent_pid():
    fs = _procfs({10: 100, 11: 200, 12: 300})
    t = ProcessIdentityTracker(fs=fs, enabled=True)
    assert t.observe_window([10, 11, 11, 500, 501, 502, -1]) == []
    assert fs.listdirs == ["/proc"]
    # Listed pids of the window are opened, each once; pid 12 is listed
    # and not in the window; 500-502 are settled by the listing alone.
    assert sorted(fs.opens) == ["/proc/10/stat", "/proc/11/stat"]
    m = t.metrics()
    assert (m["checks_total"], m["absent_total"], m["errors_total"]) \
        == (2, 3, 3)
    # An absent pid keeps its remembered generation: when it is listed
    # again with another starttime, that is a reuse.
    t2 = ProcessIdentityTracker(fs=_procfs({10: 100}), enabled=True)
    t2.observe_window([10])
    t2._fs = _procfs({})
    assert t2.observe_window([10]) == []
    assert t2._fs.opens == []
    t2._fs = _procfs({10: 101})
    assert t2.observe_window([10]) == [10]


def test_a_failed_listing_falls_back_to_a_read_per_pid():
    fs = _procfs({10: 100})
    fs.listdir_raises = True
    t = ProcessIdentityTracker(fs=fs, enabled=True)
    t.observe_window([10, 500])
    assert sorted(fs.opens) == ["/proc/10/stat", "/proc/500/stat"]
    m = t.metrics()
    assert (m["checks_total"], m["absent_total"], m["errors_total"]) \
        == (1, 0, 1)


def test_oversized_stat_is_refused_at_the_cap():
    # The bounded read is the one read_starttime always made: a listed
    # pid whose "stat" is larger than procfs can make it is an error,
    # not a parse.
    fs = _procfs({10: 100})
    fs.put("/proc/10/stat", _stat(10, 100)
           + b" " * (identity_mod._STAT_CAP + 1))
    t = ProcessIdentityTracker(fs=fs, enabled=True)
    t.observe_window([10])
    m = t.metrics()
    assert (m["checks_total"], m["errors_total"], m["absent_total"]) \
        == (0, 1, 0)


def _firehose_column():
    """262,144 int32 rows over 12,500 distinct pids (the firehose
    cell's shape: rows grouped by pid, ~21 a pid)."""
    pids = np.arange(1000, 13500, dtype=np.int32)
    col = np.sort(np.resize(pids, 262144))
    assert len(np.unique(col)) == 12500
    return col


def test_injected_reader_is_asked_once_per_distinct_pid():
    asked = []

    def starttime_of(pid):
        asked.append(pid)
        return 7

    t = ProcessIdentityTracker(starttime_of=starttime_of, enabled=True)
    assert t.observe_window(_firehose_column()) == []
    assert len(asked) == 12500 and len(set(asked)) == 12500
    assert all(type(p) is int for p in asked[:10])
    assert t.metrics()["checks_total"] == 12500
    assert t.snapshot()["tracked_pids"] == 12500


def test_no_statement_of_the_tracker_runs_once_per_row():
    # 262,144 rows, 8 distinct pids: the lines the module executes are
    # counted, and they must not grow with the rows.
    col = np.resize(np.arange(8, dtype=np.int32), 262144)
    t = ProcessIdentityTracker(starttime_of=lambda pid: 7, enabled=True)
    lines = [0]
    target = identity_mod.__file__

    def tracer(frame, event, arg):
        if frame.f_code.co_filename != target:
            return None
        if event == "line":
            lines[0] += 1
        return tracer

    before = sys.gettrace()
    sys.settrace(tracer)
    try:
        t.observe_window(col)
    finally:
        sys.settrace(before)
    assert t.metrics()["checks_total"] == 8
    assert 0 < lines[0] < 400, lines[0]


@pytest.mark.parametrize("kind", ["list", "tuple", "generator", "int32",
                                  "int64"])
def test_every_input_kind_gives_the_same_result(kind):
    rows = [5, 3, 3, -1, 9, 5, 7]
    make = {"list": list, "tuple": tuple,
            "generator": lambda r: (p for p in r),
            "int32": lambda r: np.array(r, np.int32),
            "int64": lambda r: np.array(r, np.int64)}[kind]
    world = {3: 30, 5: 50, 7: 70}  # 9 exited
    t = _tracker(world)
    fired = []
    t.add_invalidator("rec", fired.append)
    assert t.observe_window(make(rows)) == []
    world[7], world[3] = 71, 31
    # Ascending pid order, whatever the order of the rows.
    assert t.observe_window(make(rows[::-1])) == [3, 7]
    assert fired == [3, 7]
    assert t._gens == {3: 31, 5: 50, 7: 71}
    m = t.metrics()
    assert (m["checks_total"], m["errors_total"], m["absent_total"],
            m["reuse_detected_total"]) == (6, 2, 0, 2)
    assert t.snapshot()["last_reuse"] == {
        "pid": 7, "old_starttime": 70, "new_starttime": 71}
    assert t.observe_window(make([])) == []


def test_one_lock_a_window_loses_no_update_under_threads():
    # More threads than cores, a short switch interval: observers fold
    # whole windows into the table and the counters while others forget
    # pids and read the views. A lost update breaks the sums.
    world = {p: 7 for p in range(200)}
    t = _tracker(world)
    col = np.resize(np.arange(200, dtype=np.int32), 5000)
    windows, observers = 40, 6
    stop = threading.Event()
    raised = []

    def observe():
        try:
            for _ in range(windows):
                assert t.observe_window(col) == []
        except Exception as e:  # noqa: BLE001 - reported below
            raised.append(e)

    def disturb(k):
        try:
            while not stop.is_set():
                t.forget(k % 200)
                assert t.metrics()["reuse_detected_total"] == 0
                assert t.snapshot()["tracked_pids"] <= 200
                k += 7
        except Exception as e:  # noqa: BLE001 - reported below
            raised.append(e)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        obs = [threading.Thread(target=observe) for _ in range(observers)]
        dis = [threading.Thread(target=disturb, args=(k,))
               for k in range(8)]
        for th in obs + dis:
            th.start()
        for th in obs:
            th.join(60)
        stop.set()
        for th in dis:
            th.join(60)
    finally:
        stop.set()
        sys.setswitchinterval(before)
    assert not any(th.is_alive() for th in obs + dis)
    assert raised == []
    m = t.metrics()
    assert m["checks_total"] == observers * windows * 200
    assert (m["errors_total"], m["absent_total"]) == (0, 0)
    t.observe_window(col)
    assert t._gens == world


# -- the three steps as spans under ``identity`` ------------------------------

# What ``identity`` holds beside its children is the reduction of the
# pid column (``np.unique``), the fault site and the clock readings of
# the spans themselves: ~0.1 ms. The tolerance leaves room for a test
# machine that takes the thread off the core in between; the children
# never add up to MORE than their parent (1 us a span for the rounding
# of ``/debug/windows``' six decimals).
_SELF_TOLERANCE_S = 0.020
_ROUNDING_S = 1e-6

_SPAN_CASES = {
    # name: (world {pid: start}, the window's pids, steps expected)
    "every_pid_lives": ({p: 7 for p in range(100, 140)},
                        list(range(100, 140)), 3),
    "some_pids_absent": ({p: 7 for p in range(100, 120)},
                         list(range(100, 140)), 3),
    "no_pid_lives": ({}, list(range(100, 140)), 3),
    "a_pid_reused": ({100: 7, 101: 8}, [100, 101], 3),
    "the_listing_fails": ({p: 7 for p in range(100, 110)},
                          list(range(100, 112)), 3),
    # An injected reader is the world: no listing is made, no span of it.
    "an_injected_reader": (None, list(range(100, 140)), 2),
}


def _window_spans(tracker, pids):
    """One window through ``observe_window`` inside an open ``identity``
    span, as ``profiler/cpu.py`` opens it: ({stage: span}, meta)."""
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("identity"):
        reused = tracker.observe_window(np.asarray(pids, np.int32))
    tr.complete()
    trace = rec.traces()[0]
    return {s["stage"]: s for s in trace["spans"]}, trace["meta"], reused


@pytest.mark.parametrize("case", sorted(_SPAN_CASES))
def test_the_checks_steps_are_spans_under_identity(case):
    world, pids, n_steps = _SPAN_CASES[case]
    if world is None:
        t = ProcessIdentityTracker(starttime_of=lambda pid: 7, enabled=True)
    else:
        fs = _procfs(world)
        fs.listdir_raises = case == "the_listing_fails"
        t = ProcessIdentityTracker(fs=fs, enabled=True)
    if case == "a_pid_reused":
        t.observe_window(pids)
        t._fs = _procfs({100: 7, 101: 9})
    spans, meta, reused = _window_spans(t, pids)
    assert reused == ([101] if case == "a_pid_reused" else [])
    steps = [s for s in ("identity_list", "identity_read",
                         "identity_settle") if s in spans]
    assert len(steps) == n_steps and steps[-2:] == [
        "identity_read", "identity_settle"]
    parent = spans["identity"]
    end = parent["start_s"] + parent["duration_s"]
    at = parent["start_s"]
    for stage in steps:
        s = spans[stage]
        # Nested under ``identity``, one after the other, inside it.
        assert s["parent"] == parent["id"], stage
        assert s["start_s"] >= at - _ROUNDING_S, stage
        at = s["start_s"] + s["duration_s"]
        assert at <= end + _ROUNDING_S, stage
    inside = sum(spans[s]["duration_s"] for s in steps)
    assert inside <= parent["duration_s"] + len(steps) * _ROUNDING_S
    assert parent["duration_s"] - inside <= _SELF_TOLERANCE_S
    assert meta["identity_pids"] == len(pids)
    assert meta["identity_stat_reads"] + meta["identity_absent"] \
        == len(pids)


def test_with_no_window_open_the_steps_record_nowhere():
    # Library use: no span is open on the thread, the check is the same.
    t = ProcessIdentityTracker(fs=_procfs({10: 100}), enabled=True)
    assert t.observe_window([10, 11]) == []
    m = t.metrics()
    assert (m["checks_total"], m["absent_total"]) == (1, 1)


@pytest.mark.parametrize("n_live", [1, 40, 400])
def test_every_listed_pid_is_read_in_every_window(n_live):
    """No watermark, no cache of "checked recently", no sampling of
    pids: window after window of the same live pids, every one of them
    is opened once a window, and a pid of the window that ``/proc`` does
    not list is never opened. Listed pids the window does not hold are
    not opened either."""
    live = list(range(1000, 1000 + n_live))
    unsampled = list(range(5000, 5020))     # listed, not in the window
    gone = list(range(9000, 9007))          # in the window, not listed
    fs = _procfs({p: 3 * p for p in live + unsampled})
    t = ProcessIdentityTracker(fs=fs, enabled=True)
    col = np.resize(np.asarray(live + gone, np.int32), 4 * (n_live + 7))
    want = sorted(f"/proc/{p}/stat" for p in live)
    for window in range(1, 7):
        fs.opens.clear()
        _spans, meta, reused = _window_spans(t, col)
        assert reused == []
        assert sorted(fs.opens) == want, window
        assert meta["identity_stat_reads"] == n_live
        assert meta["identity_absent"] == len(gone)
        assert meta["identity_pids"] == n_live + len(gone)
        assert t.metrics()["checks_total"] == window * n_live
    assert fs.listdirs == ["/proc"] * 6
