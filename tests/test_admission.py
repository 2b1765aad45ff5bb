"""Multi-tenant admission control (chaos) suite.

Deterministic like test_ingest_poison.py: fixed seeds, FakeFS cgroup
inputs. The headline test is
test_noisy_tenant_storm_through_real_window_loop — the ISSUE 13
acceptance drill: one tenant driven ~10x over its sample quota through
the real profiler window loop; only that tenant's pids degrade, every
window ships every pid's mass (windows_lost == 0), in-quota tenants'
profile bytes stay identical to a no-admission control run, and the
noisy tenant recovers to full fidelity after the storm clears. The
chaos sites `admission.resolve` / `admission.shed` (utils/faults.py
SITES) are drilled with injected faults — both fail-open by contract.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.capture.formats import (
    STACK_SLOTS,
    MappingTable,
    WindowSnapshot,
)
from parca_agent_tpu.metadata.providers import (
    CgroupParseError,
    CgroupProvider,
    TenantProvider,
    parse_cgroup_path,
)
from parca_agent_tpu.pprof.builder import parse_pprof
from parca_agent_tpu.runtime.admission import (
    AdmissionController,
    OverloadPolicy,
    TenantResolver,
    UNKNOWN_TENANT,
    tenant_from_cgroup,
    validate_tenant,
)
from parca_agent_tpu.runtime.quarantine import (
    LEVEL_ADDRESSES,
    LEVEL_FULL,
    LEVEL_SCALAR,
    QuarantineRegistry,
    apply_ladder,
)
from parca_agent_tpu.utils import faults
from parca_agent_tpu.utils.vfs import FakeFS
from parca_agent_tpu.web import AgentHTTPServer, render_metrics

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faults.install(None)


def _fs(tenant_paths: dict) -> FakeFS:
    """pid -> cgroup path, as /proc/<pid>/cgroup v2 files."""
    return FakeFS({f"/proc/{pid}/cgroup": b"0::" + path.encode() + b"\n"
                   for pid, path in tenant_paths.items()})


def _two_tenant_fs(good_pids, noisy_pids) -> FakeFS:
    paths = {p: "/system.slice/good.service" for p in good_pids}
    paths.update({p: "/kubepods/podaaaabbbb-0000-1111-2222-333344445555/c"
                  for p in noisy_pids})
    return _fs(paths)


def _snap(pid_counts: dict, time_ns: int = 0) -> WindowSnapshot:
    pids = sorted(pid_counts)
    stacks = np.zeros((len(pids), STACK_SLOTS), np.uint64)
    for i, pid in enumerate(pids):
        stacks[i, :2] = [0x1000 * pid + 0x10, 0x1000 * pid + 0x20]
    return WindowSnapshot(
        pids=pids, tids=pids, counts=[pid_counts[p] for p in pids],
        user_len=[2] * len(pids), kernel_len=[0] * len(pids),
        stacks=stacks, mappings=MappingTable.empty(), time_ns=time_ns,
    )


# -- tenant identity ----------------------------------------------------------


@pytest.mark.parametrize("path,want", [
    ("/kubepods/burstable/pod12345678-dead-beef-0000-000000000001/abc",
     "pod:12345678-dead-beef-0000-000000000001"),
    ("/kubepods.slice/kubepods-burstable.slice/"
     "kubepods-burstable-pod12345678_dead_beef_0000_000000000001.slice/x",
     "pod:12345678-dead-beef-0000-000000000001"),
    ("/system.slice/docker-0123456789abcdef0123456789abcdef.scope",
     "ctr:0123456789ab"),
    ("/machine.slice/crio-deadbeefdeadbeefdeadbeef.scope",
     "ctr:deadbeefdead"),
    ("/user.slice/user-1000.slice/session-3.scope", "user:1000"),
    ("/system.slice/nginx.service", "svc:nginx.service"),
    ("/build-farm/workers", "grp:build-farm"),
    ("/", "system"),
    ("", "system"),
    (None, "system"),
])
def test_tenant_from_cgroup_shapes(path, want):
    assert tenant_from_cgroup(path) == want


def test_tenant_from_cgroup_hostile_path_is_unknown():
    # A cgroup named with bytes that cannot be a metric label value must
    # collapse to the unknown tenant, never poison the exposition.
    assert tenant_from_cgroup('/x"evil\nname') == UNKNOWN_TENANT


def test_validate_tenant_rejects_malformed():
    assert validate_tenant("svc:a.service") == "svc:a.service"
    for bad in ("", 'a"b', "a\nb", "-leading", "x" * 200, None, "a b"):
        with pytest.raises(ValueError):
            validate_tenant(bad)


# -- cgroup parser hardening (the one /proc reader outside the PR 4
#    taxonomy, now inside it) -------------------------------------------------


def test_parse_cgroup_path_prefers_v2_else_cpu():
    data = (b"3:memory:/mem-path\n"
            b"2:cpu,cpuacct:/cpu-path\n"
            b"junk line without colons\n"
            b"0::/v2-path\n")
    assert parse_cgroup_path(data) == "/v2-path"
    assert parse_cgroup_path(
        b"3:memory:/mem-path\n2:cpu,cpuacct:/cpu-path\n") == "/cpu-path"
    assert parse_cgroup_path(b"3:memory:/mem-path\n") == "/mem-path"
    assert parse_cgroup_path(b"") is None
    assert parse_cgroup_path(b"garbage\n\x00\xff\n") is None


def test_parse_cgroup_row_bomb_is_poison():
    bomb = b"".join(b"%d:cpu:/x%d\n" % (i, i) for i in range(400))
    with pytest.raises(CgroupParseError):
        parse_cgroup_path(bomb)


def test_cgroup_provider_bounds_read_and_contains_poison(monkeypatch):
    import parca_agent_tpu.metadata.providers as prov_mod

    fs = _fs({7: "/system.slice/a.service"})
    assert CgroupProvider(fs=fs).labels(7) == \
        {"cgroup_name": "/system.slice/a.service"}
    # Row bomb: contained to an empty label set, not an exception.
    fs.put("/proc/8/cgroup",
           b"".join(b"%d:cpu:/x\n" % i for i in range(400)))
    assert CgroupProvider(fs=fs).labels(8) == {}
    # Byte bomb: the READ is bounded (read_bounded raises OversizedInput
    # past the cap) and contained the same way.
    monkeypatch.setattr(prov_mod, "CGROUP_MAX_BYTES", 64)
    fs.put("/proc/9/cgroup", b"0::/" + b"a" * 200 + b"\n")
    assert CgroupProvider(fs=fs).labels(9) == {}
    # Missing file (pid exited): empty, no raise.
    assert CgroupProvider(fs=fs).labels(12345) == {}


def test_cgroup_fuzz_no_taxonomy_escapes():
    from parca_agent_tpu.utils.fuzz import fuzz_parser

    report = fuzz_parser("cgroup", n=300, seed=42)
    assert report["escapes"] == [], report["escapes"]
    assert report["benign"] + report["contained"] == 300


# -- the resolver -------------------------------------------------------------


def test_resolver_resolves_and_caches():
    res = TenantResolver(fs=_fs({5: "/system.slice/a.service"}))
    assert res.resolve(5) == "svc:a.service"
    assert res.resolve(5) == "svc:a.service"
    assert res.stats["resolves_total"] == 1
    assert res.stats["cache_hits_total"] == 1
    res.forget(5)
    res.resolve(5)
    assert res.stats["resolves_total"] == 2


def test_resolver_is_fail_open_and_counts():
    res = TenantResolver(fs=FakeFS())
    assert res.resolve(99) == UNKNOWN_TENANT  # missing file: pid exited
    assert res.stats["resolve_errors_total"] == 1
    # The failure is cached too — a storm of dead pids must not re-stat
    # /proc per sample.
    assert res.resolve(99) == UNKNOWN_TENANT
    assert res.stats["resolve_errors_total"] == 1


def test_resolver_cache_is_bounded(monkeypatch):
    monkeypatch.setattr(TenantResolver, "_MAX_CACHED", 8)
    res = TenantResolver(
        fs=_fs({p: f"/system.slice/s{p}.service" for p in range(32)}))
    for p in range(32):
        res.resolve(p)
    assert len(res._cache) == 8


def test_injected_resolve_fault_is_contained():
    # Chaos site admission.resolve: the injected error is counted and
    # lands the pid in the unknown tenant — never a raise, never a
    # window.
    faults.install(faults.FaultInjector.from_spec(
        "admission.resolve:error", seed=42))
    try:
        res = TenantResolver(fs=_fs({5: "/system.slice/a.service"}))
        assert res.resolve(5) == UNKNOWN_TENANT
        assert res.stats["resolve_errors_total"] == 1
    finally:
        faults.install(None)


def test_resolver_ttl_rebinds_reused_pid():
    # Pid reuse: an actively profiled pid is a cache hit every window,
    # so pure recency would NEVER re-resolve it and a recycled pid
    # would keep its dead predecessor's tenant forever. The TTL bounds
    # the mis-attribution window.
    fs = _fs({5: "/system.slice/old.service"})
    now = [0.0]
    res = TenantResolver(fs=fs, ttl_s=10.0, clock=lambda: now[0])
    assert res.resolve(5) == "svc:old.service"
    fs.put("/proc/5/cgroup", b"0::/system.slice/new.service\n")
    now[0] = 5.0
    assert res.resolve(5) == "svc:old.service"  # inside the TTL: cached
    now[0] = 11.0
    assert res.resolve(5) == "svc:new.service"  # expired: re-resolved
    assert res.stats["cache_expired_total"] == 1


def test_tenant_provider_labels():
    res = TenantResolver(fs=_fs({5: "/system.slice/a.service"}))
    assert TenantProvider(resolver=res).labels(5) == \
        {"tenant": "svc:a.service"}
    assert TenantProvider().labels(5) == {}


def test_shard_of_is_stable_and_tenant_keyed():
    fs = _two_tenant_fs([1, 2], [101, 102])
    res = TenantResolver(fs=fs)
    for n in (2, 3, 8):
        assert res.shard_of(1, n) == res.shard_of(2, n)      # same tenant
        assert res.shard_of(101, n) == res.shard_of(102, n)
        assert 0 <= res.shard_of(1, n) < n


# -- quotas + the ladder ------------------------------------------------------


def _controller(fs, **kw):
    kw.setdefault("quota_samples", 100)
    kw.setdefault("burst_windows", 1)
    kw.setdefault("degrade_after", 1)
    kw.setdefault("escalate_after", 2)
    kw.setdefault("recover_windows", 2)
    return AdmissionController(TenantResolver(fs=fs), **kw)


def test_over_quota_tenant_rides_ladder_and_recovers():
    adm = _controller(_two_tenant_fs([1, 2], [101]))
    storm = {1: 40, 2: 40, 101: 1000}  # noisy at 10x the quota
    for w in range(4):
        adm.account_window(list(storm), list(storm.values()))
        adm.tick_window()
    assert adm.level_for(101) == LEVEL_SCALAR   # escalated through addresses
    assert adm.level_for(1) == LEVEL_FULL       # in-quota: untouched
    assert adm.level_for(2) == LEVEL_FULL
    assert adm.stats["over_quota_windows_total"] >= 3
    # Storm clears: recovery steps DOWN one rung per recover_windows.
    calm = {1: 40, 2: 40, 101: 10}
    seen = [adm.level_for(101)]
    for w in range(10):
        adm.account_window(list(calm), list(calm.values()))
        adm.tick_window()
        seen.append(adm.level_for(101))
        if seen[-1] == LEVEL_FULL:
            break
    assert seen[-1] == LEVEL_FULL
    assert LEVEL_ADDRESSES in seen  # full fidelity came back via addresses


def test_pid_churn_quota_axis():
    paths = {p: "/system.slice/churn.service" for p in range(100, 140)}
    paths[1] = "/system.slice/calm.service"
    adm = AdmissionController(
        TenantResolver(fs=_fs(paths)), quota_pids=8, burst_windows=1,
        degrade_after=1, escalate_after=2)
    pid_counts = {p: 1 for p in range(100, 140)}
    pid_counts[1] = 1
    for w in range(3):
        adm.account_window(list(pid_counts), list(pid_counts.values()))
        adm.tick_window()
    assert adm.level_for(100) >= LEVEL_ADDRESSES  # 40 pids vs quota 8
    assert adm.level_for(1) == LEVEL_FULL


def test_burst_banking_tolerates_one_spike():
    adm = _controller(_fs({1: "/system.slice/spiky.service"}),
                      quota_samples=100, burst_windows=3)
    # Idle windows bank tokens up to 3x quota; one 250-sample spike then
    # rides the bank without degradation.
    adm.account_window([1], [10])
    adm.tick_window()
    adm.account_window([1], [250])
    adm.tick_window()
    assert adm.level_for(1) == LEVEL_FULL
    # A sustained 2.5x overload drains the bank and degrades.
    for w in range(4):
        adm.account_window([1], [250])
        adm.tick_window()
    assert adm.level_for(1) > LEVEL_FULL


def test_account_failure_is_counted_not_raised():
    adm = _controller(_fs({1: "/system.slice/a.service"}))
    adm.account_window([1, 2], [1])  # mismatched lengths: np raises inside
    assert adm.stats["account_errors_total"] == 1


def test_tenant_cap_evicts_idle_recovered_only(monkeypatch):
    monkeypatch.setattr(AdmissionController, "_MAX_TENANTS", 4)
    paths = {p: f"/system.slice/s{p}.service" for p in range(10)}
    # recover_windows high: s0 must still be DEGRADED while the churn
    # rolls through the cap (recovery would legitimately make it
    # evictable — decayed history is no longer containment state).
    adm = _controller(_fs(paths), quota_samples=100, recover_windows=50)
    # Tenant s0 goes over quota (its state is containment history).
    for w in range(3):
        adm.account_window([0], [1000])
        adm.tick_window()
    assert adm.level_for(0) > LEVEL_FULL
    for p in range(1, 10):  # nine more tenants churn through the cap
        adm.account_window([p], [10])
        adm.tick_window()
    with adm._lock:
        assert len(adm._tenants) <= 4
        assert "svc:s0.service" in adm._tenants  # degraded: never evicted
    assert adm.stats["tenants_evicted_total"] >= 6


# -- the overload governor ----------------------------------------------------


def _governor_fs():
    return _two_tenant_fs([1, 2, 3], [101, 102])


def test_governor_sheds_heaviest_first_and_releases():
    adm = AdmissionController(
        TenantResolver(fs=_governor_fs()), quota_samples=10_000,
        overload=OverloadPolicy(close_latency_s=0.5, shed_after=2,
                                recover_after=2))
    load = {1: 10, 2: 10, 3: 10, 101: 900, 102: 900}
    for w in range(3):  # sustained overload: two shed steps land
        adm.account_window(list(load), list(load.values()))
        adm.tick_window(close_latency_s=2.0)
    # The heavy (noisy-tenant) pids shed first; the light tenant is
    # reachable only after every heavier tenant is at the floor —
    # untouched while the heavy one still has rungs to give.
    assert adm.level_for(101) == LEVEL_SCALAR
    assert adm.level_for(1) == LEVEL_FULL
    assert adm.stats["overload_windows_total"] >= 3
    assert adm.stats["shed_steps_total"] >= 2
    # Overload persisting past the heavy tenant's floor now spreads to
    # the lighter tenants instead of degenerating into no-op steps.
    adm.account_window(list(load), list(load.values()))
    adm.tick_window(close_latency_s=2.0)
    assert adm.level_for(1) == LEVEL_ADDRESSES
    for w in range(10):  # back in budget: stepwise release, everyone
        adm.account_window(list(load), list(load.values()))
        adm.tick_window(close_latency_s=0.01)
    assert adm.level_for(101) == LEVEL_FULL
    assert adm.level_for(1) == LEVEL_FULL
    assert adm.stats["shed_releases_total"] >= 1


def test_governor_shed_reaches_lighter_tenants_once_heavies_floor():
    # Once the heaviest tenants are at the ladder floor they must stop
    # counting toward the coverage target, or every later shed step is
    # a no-op and mid-weight tenants are never reached.
    paths = {1: "/system.slice/heavy.service",
             2: "/system.slice/mid.service",
             3: "/system.slice/light.service"}
    adm = AdmissionController(
        TenantResolver(fs=_fs(paths)), quota_samples=100_000,
        overload=OverloadPolicy(close_latency_s=0.5, shed_after=1,
                                recover_after=100))
    load = {1: 900, 2: 300, 3: 10}
    for w in range(8):  # sustained overload, one shed step per window
        adm.account_window(list(load), list(load.values()))
        adm.tick_window(close_latency_s=2.0)
    assert adm.tenant_level("svc:heavy.service") == LEVEL_SCALAR
    assert adm.tenant_level("svc:mid.service") == LEVEL_SCALAR
    assert adm.tenant_level("svc:light.service") == LEVEL_SCALAR
    assert adm.stats["shed_steps_total"] >= 6


def test_governor_registry_rows_and_backlog_signals():
    adm = AdmissionController(
        TenantResolver(fs=_governor_fs()), quota_samples=10_000,
        overload=OverloadPolicy(registry_rows=1000, backlog=1,
                                shed_after=1, recover_after=100))
    adm.account_window([101], [500])
    adm.tick_window(registry_rows=5000)  # rows over budget
    assert adm.stats["overload_windows_total"] == 1
    # backlog is the pipeline's CUMULATIVE counter; the diff per window
    # is what the governor judges.
    adm.account_window([101], [500])
    adm.tick_window(backlog=3)   # delta 3 >= 1: over
    adm.account_window([101], [500])
    adm.tick_window(backlog=3)   # delta 0: calm
    assert adm.stats["overload_windows_total"] == 2


def test_injected_shed_fault_is_contained():
    # Chaos site admission.shed: the injected error costs the shed step
    # only — counted, quotas and the window untouched.
    faults.install(faults.FaultInjector.from_spec(
        "admission.shed:error", seed=42))
    try:
        adm = AdmissionController(
            TenantResolver(fs=_governor_fs()), quota_samples=10_000,
            overload=OverloadPolicy(close_latency_s=0.5, shed_after=1))
        for w in range(3):
            adm.account_window([101], [900])
            adm.tick_window(close_latency_s=2.0)
        assert adm.stats["shed_errors_total"] >= 1
        assert adm.stats["shed_steps_total"] == 0
        assert adm.level_for(101) == LEVEL_FULL  # no shed happened
    finally:
        faults.install(None)


# -- ladder composition (quarantine x admission) ------------------------------


def _profiles(snap):
    return CPUAggregator().aggregate(snap)


def test_apply_ladder_takes_max_of_both_layers():
    fs = _two_tenant_fs([7], [9])
    adm = _controller(fs)
    for w in range(3):
        adm.account_window([9], [1000])
        adm.tick_window()
    assert adm.level_for(9) >= LEVEL_ADDRESSES
    reg = QuarantineRegistry(max_strikes=0, escalate_after=0)
    reg.record_error(7, "maps.parse", ValueError("x"))  # 7: scalar (poison)
    out = apply_ladder(_profiles(_snap({7: 5, 9: 11})), reg, adm)
    by_pid = {p.pid: p for p in out}
    assert len(out) == 2                        # nothing dropped
    assert by_pid[7].total() == 5               # scalar keeps the mass
    assert by_pid[9].total() == 11
    assert len(by_pid[7].stack_loc_ids) == 1    # quarantine-collapsed
    assert adm.stats["samples_degraded_total"] >= 11
    assert reg.stats["samples_degraded_total"] >= 5


def test_symbolizer_skips_admission_degraded_pids():
    from parca_agent_tpu.symbolize.symbolizer import Symbolizer

    fs = _two_tenant_fs([7], [9])
    adm = _controller(fs)
    for w in range(3):
        adm.account_window([9], [1000])
        adm.tick_window()

    seen = []

    class SpyKsym:
        def resolve(self, addrs):
            seen.extend(int(a) for a in np.asarray(addrs))
            return [None] * len(addrs)

    profiles = _profiles(_snap({7: 5, 9: 11}))
    for p in profiles:
        p.loc_is_kernel[:] = True  # force the kernel resolve path
    Symbolizer(ksym=SpyKsym(), admission=adm).symbolize(profiles)
    # Only pid 7's addresses reached the resolver; 9 ships addresses-only.
    assert set(seen) == {0x7010, 0x7020}


# -- per-tenant quarantine eviction (the cross-tenant flush fix) --------------


def test_quarantine_churn_storm_stays_in_its_own_tenant(monkeypatch):
    monkeypatch.setattr(QuarantineRegistry, "_MAX_TRACKED", 8)
    fs = _two_tenant_fs(range(1, 5), range(1000, 1100))

    def run_storm(reg):
        # Tenant "good" builds incriminating history (1 strike each),
        # then tenant "pod" churns pids through the cap, each erroring
        # TWICE — more incriminated than the good entries, so the
        # global least-incriminated rule targets the good tenant.
        for pid in range(1, 5):
            reg.record_error(pid, "maps.parse", ValueError("x"))
        for pid in range(1000, 1040):
            reg.record_error(pid, "elf.read", ValueError("y"))
            reg.record_error(pid, "elf.read", ValueError("y"))
        return sorted(p for p in reg._pids if p < 1000)

    # Baseline (no resolver): the storm flushes the other tenant's
    # accumulated strikes — the regression this fix targets.
    assert run_storm(QuarantineRegistry(max_strikes=3)) == []
    # Scoped: the storm recycles its OWN tenant's slots; the good
    # tenant's history survives intact.
    reg = QuarantineRegistry(max_strikes=3)
    reg.tenant_of = TenantResolver(fs=fs).resolve
    assert run_storm(reg) == [1, 2, 3, 4]
    for pid in range(1, 5):
        assert reg._pids[pid].strikes == 1


def test_quarantine_eviction_tenant_resolver_failure_falls_back():
    reg = QuarantineRegistry(max_strikes=3)
    reg.tenant_of = lambda pid: (_ for _ in ()).throw(RuntimeError("x"))
    reg._MAX_TRACKED = 2
    reg.record_error(1, "maps.parse", ValueError("x"))
    reg.record_error(2, "maps.parse", ValueError("x"))
    reg.record_error(3, "maps.parse", ValueError("x"))  # global fallback
    assert len(reg._pids) == 2


# -- tenant-keyed shard routing ----------------------------------------------


def test_route_h2_rewrites_residue_keeps_stride():
    from parca_agent_tpu.aggregator.sharded import route_h2

    rng = np.random.default_rng(7)
    h2 = rng.integers(0, 1 << 32, 4096, dtype=np.uint64).astype(np.uint32)
    h2[:4] = [0xFFFFFFFF, 0xFFFFFFFE, 0, 1]  # top-block + floor edges
    pids = rng.integers(1, 64, 4096)
    for n in (1, 2, 3, 4, 7, 8, 16):  # non-pow2 counts must stay exact
        out = route_h2(h2, pids, lambda p: p * 13 + 5, n)
        assert out.dtype == np.uint32
        want = ((np.asarray(pids) * 13 + 5) % n).astype(np.uint32)
        assert np.array_equal(out % n, want), n
        # The non-residue part of the hash survives (minus at most one
        # stride step at the uint32 ceiling) — keys stay well spread.
        drift = np.abs(out.astype(np.int64) - h2.astype(np.int64))
        assert int(drift.max()) < 2 * n


def test_route_h2_same_pid_same_residue_every_window():
    from parca_agent_tpu.aggregator.sharded import route_h2

    pids = np.array([5, 9, 5, 9, 5])
    h2a = np.array([10, 20, 30, 40, 50], np.uint32)
    h2b = np.array([99, 98, 97, 96, 95], np.uint32)
    out_a = route_h2(h2a, pids, lambda p: p, 4)
    out_b = route_h2(h2b, pids, lambda p: p, 4)
    assert set((out_a % 4).tolist()) == {1, 5 % 4, 9 % 4} - {5}  # {1}
    assert np.array_equal(out_a % 4, out_b % 4)


# -- the profiler wiring ------------------------------------------------------


class _ListWriter:
    def __init__(self):
        self.rows = []

    def write(self, labels, blob):
        self.rows.append((labels["pid"], blob))


class _ScriptSource:
    def __init__(self, snaps):
        self.snaps = list(snaps)

    def poll(self):
        return self.snaps.pop(0) if self.snaps else None


def _run_profiler(snaps, admission=None, quarantine=None):
    from parca_agent_tpu.profiler.cpu import CPUProfiler

    writer = _ListWriter()
    windows = []
    prof = CPUProfiler(source=_ScriptSource(snaps),
                       aggregator=CPUAggregator(),
                       profile_writer=writer,
                       quarantine=quarantine, admission=admission)
    while True:
        mark = len(writer.rows)
        if not prof.run_iteration():
            break
        windows.append(writer.rows[mark:])
    return windows


def test_noisy_tenant_storm_through_real_window_loop():
    """ISSUE 13 acceptance drill: one tenant ~10x over quota through
    the real window loop — only its pids degrade, windows_lost == 0,
    in-quota tenants byte-identical to a no-admission control run, and
    full fidelity returns once the storm clears."""
    GOOD = [1, 2, 3, 4, 5, 6]
    NOISY = [101, 102]
    fs = _two_tenant_fs(GOOD, NOISY)

    def snaps():
        out = []
        for w in range(6):   # storm: noisy tenant at ~10x its quota
            counts = {p: 20 for p in GOOD}
            counts.update({p: 600 for p in NOISY})
            out.append(_snap(counts, time_ns=w * 10**10))
        for w in range(6, 16):  # storm clears
            counts = {p: 20 for p in GOOD}
            counts.update({p: 20 for p in NOISY})
            out.append(_snap(counts, time_ns=w * 10**10))
        return out

    adm = AdmissionController(
        TenantResolver(fs=fs), quota_samples=150, burst_windows=1,
        degrade_after=1, escalate_after=2, recover_windows=2)
    windows = _run_profiler(snaps(), admission=adm)
    control = _run_profiler(snaps())

    # windows_lost == 0: every polled window shipped, and every window
    # shipped EVERY pid's profile — degradation never drops samples.
    assert len(windows) == len(control) == 16
    all_pids = sorted(str(p) for p in GOOD + NOISY)
    for rows in windows:
        assert sorted(p for p, _ in rows) == all_pids

    by_key = {(w, p): blob for w, rows in enumerate(windows)
              for p, blob in rows}
    ctl_key = {(w, p): blob for w, rows in enumerate(control)
               for p, blob in rows}
    # In-quota tenants: byte-identical to the control run, storm or not.
    for w in range(16):
        for p in GOOD:
            assert by_key[(w, str(p))] == ctl_key[(w, str(p))], (w, p)
    # The noisy tenant degraded during the storm: by its tail the
    # profiles are scalar-collapsed (one depth-1 sample, exact mass)...
    parsed = parse_pprof(by_key[(4, "101")])
    assert len(parsed.samples) == 1
    assert sum(v[0] for _, v, _ in parsed.samples) == 600
    assert by_key[(4, "101")] != ctl_key[(4, "101")]
    # ...and zero non-offending pids were EVER degraded.
    assert adm.stats["samples_degraded_total"] > 0
    for p in GOOD:
        assert adm.level_for(p) == LEVEL_FULL
    # Recovery: the last windows are byte-identical again for everyone.
    assert adm.level_for(101) == LEVEL_FULL
    for p in NOISY:
        assert by_key[(15, str(p))] == ctl_key[(15, str(p))]


def test_pid_axis_grows_tenfold_under_32_tenants_one_of_them_noisy():
    """One dict aggregator rides three tiers of pids (two stacks a pid,
    32 tenants, the quota twice the fair share at the top tier); at the
    top tier one tenant sends ten times its share. Every window's counts
    hold the snapshot's mass, the noisy tenant leaves full fidelity, and
    none of the 31 in-quota tenants ever does."""
    from parca_agent_tpu.aggregator.dict import DictAggregator

    tiers, n_tenants = (500, 2_000, 5_000), 32

    class Spread:
        stats: dict = {}

        def resolve(self, pid):
            return f"svc:t{int(pid) % n_tenants}"

    def tier_snapshot(n_pids, noisy_mult):
        n = 2 * n_pids
        pids = np.repeat(np.arange(1, n_pids + 1, dtype=np.int64), 2)
        row = np.arange(n, dtype=np.uint64)
        stacks = np.zeros((n, STACK_SLOTS), np.uint64)
        stacks[:, 0] = 0x10000 + row * 0x40
        stacks[:, 1] = 0x900000 + (row % 4096) * 0x10
        counts = np.ones(n, np.int64)
        counts[pids % n_tenants == 0] = noisy_mult
        return WindowSnapshot(
            pids=pids, tids=pids, counts=counts,
            user_len=np.full(n, 2, np.int32),
            kernel_len=np.zeros(n, np.int32),
            stacks=stacks, mappings=MappingTable.empty())

    top = max(tiers)
    adm = AdmissionController(
        Spread(), quota_samples=2 * (2 * top) // n_tenants,
        burst_windows=1, degrade_after=1, escalate_after=2,
        recover_windows=2)
    agg = DictAggregator(capacity=1 << 16, overflow="sketch")
    for n_pids in tiers:
        snap = tier_snapshot(n_pids, 10 if n_pids == top else 1)
        for _ in range(3):
            adm.account_window(snap.pids, snap.counts)
            assert int(np.asarray(agg.window_counts(snap)).sum()) \
                == snap.total_samples(), n_pids
            adm.tick_window(registry_rows=int(agg._next_id))
        assert [t for t in range(1, n_tenants)
                if adm.tenant_level(f"svc:t{t}") != LEVEL_FULL] == []
        assert (adm.tenant_level("svc:t0") != LEVEL_FULL) \
            == (n_pids == top)
    assert agg._next_id == 2 * top  # every tier's new stacks registered


def test_profiler_ticks_admission_on_window_clock():
    fs = _fs({1: "/system.slice/a.service"})
    adm = _controller(fs)
    _run_profiler([_snap({1: 5}, time_ns=w * 10**10) for w in range(3)],
                  admission=adm)
    assert adm.stats["windows_total"] == 3


# -- observability surfaces ---------------------------------------------------


def _loaded_controller():
    fs = _two_tenant_fs([1, 2], [101])
    adm = _controller(fs, top_n=2)
    for w in range(3):
        adm.account_window([1, 2, 101], [40, 30, 1000])
        adm.tick_window()
    return adm


def test_metrics_bounded_cardinality_with_other_rollup(monkeypatch):
    paths = {p: f"/system.slice/s{p}.service" for p in range(30)}
    adm = AdmissionController(TenantResolver(fs=_fs(paths)),
                              quota_samples=10_000, top_n=5)
    adm.account_window(list(range(30)), [10 * (p + 1) for p in range(30)])
    adm.tick_window()
    m = adm.metrics()
    names = [t["tenant"] for t in m["tenants"]]
    assert len(names) == 6 and names[-1] == "other"
    other = m["tenants"][-1]
    assert other["tenants"] == 25
    # Rollup conservation: top-5 + other == the whole window's mass.
    assert sum(t["window_samples"] for t in m["tenants"]) == \
        sum(10 * (p + 1) for p in range(30))


def test_render_metrics_tenant_families():
    text = render_metrics([], admission=_loaded_controller())
    assert "# TYPE parca_agent_tenant_samples_total counter" in text
    assert 'parca_agent_tenant_ladder_level{tenant="pod:' in text
    assert "parca_agent_admission_windows_total 3" in text
    assert "parca_agent_admission_shed_steps_total 0" in text
    assert "parca_agent_tenant_resolves_total" in text


def test_render_metrics_other_rollup_has_no_counter_series():
    # The rollup's membership changes per scrape, so a cumulative
    # tenant="other" series would fake counter resets whenever a tenant
    # migrates into the top-N; only the last-window gauges carry it.
    paths = {p: f"/system.slice/s{p}.service" for p in range(30)}
    adm = AdmissionController(TenantResolver(fs=_fs(paths)),
                              quota_samples=10_000, top_n=5)
    adm.account_window(list(range(30)), [10 * (p + 1) for p in range(30)])
    adm.tick_window()
    text = render_metrics([], admission=adm)
    assert 'parca_agent_tenant_samples_total{tenant="other"}' not in text
    assert 'parca_agent_tenant_window_samples{tenant="other"}' in text


def test_healthz_admission_section_never_red():
    adm = _loaded_controller()
    assert adm.stats["tenants_degraded"] >= 1  # actively shedding...
    srv = AgentHTTPServer(port=0, profilers=[], admission=adm)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=10) as r:
            assert r.status == 200  # ...and still ready, by contract
            body = json.loads(r.read().decode())
        assert body["admission"]["stats"]["over_quota_windows_total"] >= 3
        assert any(t["level"] > 0
                   for t in body["admission"]["tenants"].values())
    finally:
        srv.stop()


# -- the read path's tenant= selector shorthand -------------------------------


class _StubListener:
    def __init__(self):
        self.want = None

    def next_matching_profile(self, match, timeout):
        self.want = match
        ok = match({"tenant": "svc:a.service", "pid": "5"})
        return ({"tenant": "svc:a.service"}, b"blob") if ok else None


def test_query_tenant_selector_and_400():
    listener = _StubListener()
    srv = AgentHTTPServer(port=0, profilers=[], listener=listener)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        with urllib.request.urlopen(
                f"{base}/query?tenant=svc:a.service&timeout=0",
                timeout=10) as r:
            assert r.status == 200
        assert listener.want({"tenant": "svc:a.service"})
        assert not listener.want({"tenant": "svc:b.service"})
        # (a BLANK tenant= is dropped by parse_qsl before the handler
        # sees it — it means "no selector", not a 400)
        for bad in ("tenant=a%20b", "tenant=a%22b",
                    "tenant=" + "x" * 200):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/query?{bad}&timeout=0",
                                       timeout=10)
            assert ei.value.code == 400, bad
    finally:
        srv.stop()


def test_hotspots_tenant_selector_and_400():
    from parca_agent_tpu.ops.sketch import CountMinSpec
    from parca_agent_tpu.runtime.hotspots import (
        HotspotSpec,
        HotspotStore,
        WindowSummary,
    )

    spec = HotspotSpec(k=5, candidates=16,
                       cm=CountMinSpec(depth=3, width=1 << 8))
    store = HotspotStore(spec=spec, window_s=10.0,
                         rollup_spans_s=(60.0,))
    h1 = np.arange(1, 9, dtype=np.uint32)
    h2 = np.arange(1, 9, dtype=np.uint32)
    counts = np.full(8, 10, np.int64)

    def ctx(i):
        tenant = "svc:a.service" if i % 2 else "pod:bbbb1111"
        return 100 + i, (f"f{i}",), {"tenant": tenant, "pid": str(100 + i)}

    store.fold(WindowSummary.build(h1, h2, counts, ctx, spec,
                                   0, 10 * 10**9))
    srv = AgentHTTPServer(port=0, profilers=[], hotspots=store)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        with urllib.request.urlopen(
                f"{base}/hotspots?tenant=svc:a.service", timeout=10) as r:
            ans = json.loads(r.read().decode())
        assert ans["entries"]
        assert all(e["labels"]["tenant"] == "svc:a.service"
                   for e in ans["entries"])
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/hotspots?tenant=a%0Ab",
                                   timeout=10)
        assert ei.value.code == 400
    finally:
        srv.stop()
