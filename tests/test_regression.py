"""Regression sentinel tests (docs/regression.md): judgment mechanics
(baseline freeze, noise floor, verdict gates, drift/staleness), the
(build-id, tenant) attribution fold, crash-only baseline persistence,
the /diff HTTP surface, the alerts sink, and the chaos drills for the
``regression.fold`` / ``regression.baseline`` sites (in ``make chaos``)."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

from parca_agent_tpu.aggregator.base import ProfileMapping
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.ops.sketch import CountMinSpec
from parca_agent_tpu.pprof.window_encoder import WindowEncoder
from parca_agent_tpu.profiler.encode_pipeline import EncodePipeline
from parca_agent_tpu.runtime.hotspots import (
    HotspotSpec,
    HotspotStore,
    RegistryView,
    WindowSummary,
)
from parca_agent_tpu.runtime.regression import (
    VERDICT_KINDS,
    RegressionSentinel,
    RegressionSpec,
)
from parca_agent_tpu.sinks.alerts import AlertsSink
from parca_agent_tpu.utils import faults

T0_NS = 1_700_000_000_000_000_000


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faults.install(None)


# -- a hand-rolled (view, prep) pair: precise control over builds,
# -- tenants, and counts, without a full aggregator run ----------------------

def _Reg(mappings, n_locs, kernel=()):
    """A registry as the aggregator keeps one: array columns."""
    from parca_agent_tpu.aggregator.dict import _PidRegistry

    normalized = 0x100 * np.arange(1, n_locs + 1, dtype=np.uint64)
    return _PidRegistry(
        normalized.copy(), normalized,
        (1 + np.arange(n_locs) % len(mappings)).astype(np.int32),
        np.isin(np.arange(n_locs), list(kernel)), mappings, {})


class _View:
    """RegistryView duck-type: sid i has hashes (i+1, 2*(i+1)), pid
    1000, and leaf location id i+1 (1-based)."""

    def __init__(self, n, pid=1000):
        self._loc_off = np.arange(n + 1, dtype=np.int64)
        self._loc_flat = np.arange(1, n + 1, dtype=np.int64)
        self._id_pid = np.full(n, pid, np.int64)
        self._h1 = np.arange(1, n + 1, dtype=np.uint32)
        self._h2 = (2 * np.arange(1, n + 1)).astype(np.uint32)

    def id_hashes(self, n=None):
        return self._h1, self._h2


class _Prep:
    def __init__(self, idx, vals, pid, time_ns, caps,
                 duration_ns=10_000_000_000):
        self.idx = np.asarray(idx, np.int64)
        self.vals = np.asarray(vals, np.int64)
        self.pids_live = np.full(len(self.idx), pid, np.int64)
        self.time_ns = time_ns
        self.duration_ns = duration_ns
        self.caps = caps


def _spec(**kw):
    base = dict(interval_s=10.0, baseline_rollups=3, min_count=4,
                k_sigma=4.0, min_ratio=1.5,
                cm=CountMinSpec(depth=4, width=1 << 10))
    base.update(kw)
    return RegressionSpec(**base)


def _harness(n=8, builds=("b1",), spec=None):
    """One pid, n stacks round-robined over len(builds) mappings."""
    sent = RegressionSentinel(spec=spec or _spec())
    maps = [ProfileMapping(id=i + 1, start=0, end=0, offset=0,
                           path=f"/bin/{b}", build_id=b, base=0)
            for i, b in enumerate(builds)]
    reg = _Reg(maps, n)
    view = _View(n)
    caps = {1000: (reg, len(maps), n)}
    return sent, view, caps


def _feed(sent, view, caps, counts_by_window, t0_ns=T0_NS,
          window_s=10.0):
    """Feed windows (one per rollup interval at the default spec) and a
    final empty window so the last bucket seals."""
    n = len(counts_by_window[0])
    for w, counts in enumerate(counts_by_window):
        prep = _Prep(np.arange(n), counts, 1000,
                     t0_ns + int(w * window_s * 1e9), caps)
        sent.fold_from_prepared(view, prep)
    prep = _Prep([], [], 1000,
                 t0_ns + int(len(counts_by_window) * window_s * 1e9),
                 caps)
    sent.fold_from_prepared(view, prep)


# -- judgment mechanics ------------------------------------------------------

def test_spec_validation():
    with pytest.raises(ValueError):
        RegressionSpec(interval_s=0)
    with pytest.raises(ValueError):
        RegressionSpec(baseline_rollups=0)
    with pytest.raises(ValueError):
        RegressionSpec(min_ratio=0.5)
    with pytest.raises(ValueError):
        RegressionSpec(drift_threshold=0.0)


def test_baseline_freezes_after_configured_rollups():
    sent, view, caps = _harness()
    _feed(sent, view, caps, [[100] * 8] * 3)
    m = sent.metrics()
    assert m["baselines_frozen"] == 1
    assert m["rollups_sealed"] == 3
    g = sent.verdicts()["groups"][0]
    assert g["baseline_id"] is not None
    assert g["baseline_rollups"] == 3


def test_clean_stream_produces_zero_verdicts():
    sent, view, caps = _harness()
    rng = np.random.default_rng(5)
    # Poisson noise around a stationary rate: nothing should fire.
    windows = [rng.poisson(200, 8).tolist() for _ in range(40)]
    _feed(sent, view, caps, windows)
    assert sum(sent.metrics()["verdicts"].values()) == 0


def test_2x_shift_detected_within_two_rollups():
    sent, view, caps = _harness()
    rng = np.random.default_rng(7)
    clean = [rng.poisson(200, 8).tolist() for _ in range(10)]
    shifted = []
    for _ in range(4):
        w = rng.poisson(200, 8)
        w[0] *= 2  # one stack doubles
        shifted.append(w.tolist())
    _feed(sent, view, caps, clean + shifted)
    v = sent.verdicts()["verdicts"]
    assert any(rec["kind"] == "regressed" for rec in v)
    first = min(rec["t_s"] for rec in v if rec["kind"] == "regressed")
    shift_at_s = (T0_NS + 10 * 10 * 1e9) / 1e9
    assert first <= shift_at_s + 2 * sent.spec.interval_s
    rec = next(r for r in v if r["kind"] == "regressed")
    assert rec["build"] == "b1" and rec["exact"]
    assert rec["current"] > rec["baseline"] * 1.5
    assert rec["delta"] > rec["threshold"] >= rec["error_bound"]


def test_improvement_and_new_hotspot_verdicts():
    sent, view, caps = _harness()
    base = [[400, 400, 400, 400, 0, 0, 0, 0]] * 3
    after = [[400, 400, 400, 40, 0, 0, 0, 300]] * 2
    _feed(sent, view, caps, base + after)
    kinds = {rec["kind"]: rec for rec in sent.verdicts()["verdicts"]}
    assert "improved" in kinds and kinds["improved"]["delta"] < 0
    assert "new_hotspot" in kinds
    assert kinds["new_hotspot"]["baseline"] <= 1.0


def test_noise_floor_suppresses_learned_variance():
    # A stack that always flaps +/- 300 must not fire even though the
    # swing clears min_count and the sketch bound.
    sent, view, caps = _harness()
    windows = []
    for w in range(30):
        c = [500, 500, 500, 500, 500, 500, 500, 500]
        c[0] = 200 if w % 2 else 800
        windows.append(c)
    _feed(sent, view, caps, windows)
    assert sum(sent.metrics()["verdicts"].values()) == 0


def test_verdicts_repeat_only_after_cooldown():
    sent, view, caps = _harness(spec=_spec(repeat_every=5))
    windows = [[200] * 8] * 3 + [[200, 200, 200, 200, 200, 200, 200,
                                  1000]] * 12
    _feed(sent, view, caps, windows)
    regressed = [r for r in sent.verdicts()["verdicts"]
                 if r["kind"] == "regressed"]
    # 12 shifted rollups / cooldown 5 -> ceil = 3 emissions, not 12.
    assert 1 <= len(regressed) <= 3
    assert sent.metrics()["verdicts_suppressed"] > 0


def test_drift_marks_autofdo_stale_once_per_excursion():
    marked = []
    sent, view, caps = _harness(spec=_spec(drift_threshold=0.3))
    sent.bind_staleness(marked.append)
    base = [[1000, 0, 0, 0, 1000, 0, 0, 0]] * 3
    # Same total mass, completely different shape: pure drift.
    after = [[0, 1000, 0, 0, 0, 1000, 0, 0]] * 8
    _feed(sent, view, caps, base + after)
    m = sent.metrics()
    assert m["verdicts"]["drifted"] == 1
    assert m["stale_marks"] == 1
    assert marked == ["b1"]
    drifted = next(r for r in sent.verdicts()["verdicts"]
                   if r["kind"] == "drifted")
    assert drifted["drift"] > 0.3 and drifted["stack"] is None


def test_kernel_and_unmapped_groups_never_mark_stale():
    marked = []
    spec = _spec(drift_threshold=0.2)
    sent = RegressionSentinel(spec=spec)
    sent.bind_staleness(marked.append)
    n = 8
    maps = [ProfileMapping(id=1, start=0, end=0, offset=0,
                           path="/bin/b1", build_id="b1", base=0)]
    reg = _Reg(maps, n, kernel=set(range(n)))  # every leaf is kernel
    view = _View(n)
    caps = {1000: (reg, 1, n)}
    base = [[1000, 0, 0, 0, 0, 0, 0, 0]] * 3
    after = [[0, 0, 0, 1000, 0, 0, 0, 0]] * 8
    _feed(sent, view, caps, base + after)
    assert sent.metrics()["verdicts"]["drifted"] == 1
    assert marked == []  # judged, but no profdata to mark
    assert sent.verdicts()["groups"][0]["build"] == "kernel"


def test_tenant_label_splits_groups():
    spec = _spec()
    sent = RegressionSentinel(
        spec=spec,
        labels_for=lambda pid: {"tenant": f"t{pid % 2}"})
    maps = [ProfileMapping(id=1, start=0, end=0, offset=0,
                           path="/bin/b1", build_id="b1", base=0)]
    n = 4
    reg = _Reg(maps, n)
    view = _View(n)
    view._id_pid = np.array([1000, 1001, 1000, 1001], np.int64)
    caps = {1000: (reg, 1, n), 1001: (reg, 1, n)}
    for w in range(4):
        prep = _Prep(np.arange(n), [100] * n, 1000,
                     T0_NS + int(w * 10e9), caps)
        prep.pids_live = view._id_pid
        sent.fold_from_prepared(view, prep)
    groups = {(g["build"], g["tenant"])
              for g in sent.verdicts()["groups"]}
    assert groups == {("b1", "t0"), ("b1", "t1")}


def test_vanished_group_still_seals_and_judges():
    # The binary disappears entirely (a deploy): its open bucket must
    # still seal on later windows' clock and judge the mass gone.
    sent, view, caps = _harness()
    _feed(sent, view, caps, [[500] * 8] * 3)
    # Windows that no longer touch the group at all.
    for w in range(3, 6):
        prep = _Prep([], [], 1000, T0_NS + int(w * 10e9), caps)
        sent.fold_from_prepared(view, prep)
    kinds = [r["kind"] for r in sent.verdicts()["verdicts"]]
    assert "improved" in kinds


def test_fold_without_view_is_counted_skip():
    sent, _, caps = _harness()
    sent.fold_from_prepared(None, _Prep([0], [10], 1000, T0_NS, caps))
    assert sent.stats["windows_skipped"] == 1
    assert sent.stats["windows_folded"] == 0


def test_verdict_query_filters():
    sent, view, caps = _harness()
    windows = [[200] * 8] * 3 + [[200, 200, 200, 200, 200, 200, 200,
                                  2000]] * 2
    _feed(sent, view, caps, windows)
    with pytest.raises(ValueError):
        sent.verdicts(kind="bogus")
    assert sent.verdicts(kind="improved")["verdicts"] == []
    got = sent.verdicts(kind="regressed", tenant="default", build="b1")
    assert got["verdicts"]
    assert sent.verdicts(tenant="nope")["verdicts"] == []
    assert set(got["verdict_counts"]) == set(VERDICT_KINDS)


# -- persistence -------------------------------------------------------------

def test_baseline_save_and_adopt_roundtrip(tmp_path):
    path = str(tmp_path / "baselines.bin")
    spec = _spec(save_every=1)
    sent, view, caps = _harness(spec=spec)
    sent.path = path
    _feed(sent, view, caps, [[100] * 8] * 4)
    assert sent.metrics()["baseline_saves"] >= 1
    ident = sent.verdicts()["groups"][0]["baseline_id"]

    warm = RegressionSentinel(spec=spec, path=path)
    m = warm.metrics()
    assert m["baselines_adopted"] == 1 and m["baselines"] == 1
    assert warm.verdicts()["groups"][0]["baseline_id"] == ident


def test_adopt_skips_corrupt_record(tmp_path):
    path = str(tmp_path / "baselines.bin")
    spec = _spec(save_every=1)
    sent, view, caps = _harness(builds=("b1", "b2"), spec=spec)
    sent.path = path
    _feed(sent, view, caps, [[100] * 8] * 4)
    data = bytearray(open(path, "rb").read())
    data[len(data) - 40] ^= 0xFF  # flip one byte in the last record
    open(path, "wb").write(bytes(data))
    warm = RegressionSentinel(spec=spec, path=path)
    m = warm.metrics()
    assert m["baseline_adopt_errors"] >= 1
    assert m["baselines_adopted"] == 1  # the other record still adopts


def test_adopt_rejects_spec_mismatch(tmp_path):
    path = str(tmp_path / "baselines.bin")
    spec = _spec(save_every=1)
    sent, view, caps = _harness(spec=spec)
    sent.path = path
    _feed(sent, view, caps, [[100] * 8] * 4)
    other = _spec(interval_s=30.0, save_every=1)
    warm = RegressionSentinel(spec=other, path=path)
    m = warm.metrics()
    assert m["baselines_adopted"] == 0
    assert m["baseline_adopt_errors"] >= 1


def test_adopt_missing_file_is_clean_cold_start(tmp_path):
    warm = RegressionSentinel(spec=_spec(),
                              path=str(tmp_path / "absent.bin"))
    m = warm.metrics()
    assert m["baselines_adopted"] == 0
    assert m["baseline_adopt_errors"] == 0


# -- the real window loop (pipeline integration + chaos) ---------------------

def _pipeline_snap():
    return generate(SyntheticSpec(
        n_pids=10, n_unique_stacks=256, n_rows=256, total_samples=2500,
        mean_depth=8, seed=11))


def _pipeline_run(n_windows, fault_spec=None, sentinel_spec=None,
                  shift_after=None, path=None):
    """Drive synthetic windows through the REAL encode pipeline with
    the sentinel riding the rollup hook; returns (sentinel, pipeline,
    sha256 of shipped pprof bytes)."""
    snap = _pipeline_snap()
    agg = DictAggregator(capacity=1 << 14)
    sent = RegressionSentinel(spec=sentinel_spec or _spec())
    sent.path = path
    sha = hashlib.sha256()

    def ship(out, prep):
        for _, blob in out:
            sha.update(bytes(blob))

    pipe = EncodePipeline(
        WindowEncoder(agg), ship=ship,
        rollup=lambda prep, ctx: sent.fold_from_prepared(ctx, prep),
        rollup_capture=lambda prep: RegistryView(agg))
    if fault_spec:
        faults.install(faults.FaultInjector.from_spec(fault_spec,
                                                      seed=42))
    try:
        lo, hi = 0x0000_7F00_0000_0000, 0x0000_7F00_0000_0000 + (1 << 24)
        for w in range(n_windows):
            counts = snap.counts.copy()
            if shift_after is not None and w >= shift_after:
                leaf = snap.stacks[:, 0]
                counts[(leaf >= lo) & (leaf < hi)] *= 2
            s = dataclasses.replace(snap, counts=counts,
                                    time_ns=snap.time_ns + int(w * 10e9))
            wc = np.asarray(agg.window_counts(s))
            assert pipe.submit(wc, s.time_ns, s.window_ns,
                               s.period_ns) is not None
            assert pipe.flush(30)
        assert pipe.close()
    finally:
        faults.install(None)
    return sent, pipe, sha.hexdigest()


def test_pipeline_attribution_by_synthetic_build_id():
    sent, pipe, _ = _pipeline_run(6)
    assert pipe.stats["windows_lost"] == 0
    assert sent.stats["windows_folded"] == 6
    builds = {g["build"] for g in sent.verdicts()["groups"]}
    # The synthetic layout: one exe + shared objects, build ids
    # f"{i:040x}" — every group key is one of those (never unmapped).
    assert builds and all(b.endswith(("1", "2", "3", "4"))
                          for b in builds)


def test_sentinel_does_not_perturb_pprof_bytes():
    base_sent, _, sha_with = _pipeline_run(6)
    # The same windows with the sentinel disabled (no rollup hook).
    snap = _pipeline_snap()
    agg = DictAggregator(capacity=1 << 14)
    sha = hashlib.sha256()
    pipe = EncodePipeline(WindowEncoder(agg),
                          ship=lambda out, prep: [
                              sha.update(bytes(b)) for _, b in out])
    for w in range(6):
        s = dataclasses.replace(snap, time_ns=snap.time_ns
                                + int(w * 10e9))
        wc = np.asarray(agg.window_counts(s))
        assert pipe.submit(wc, s.time_ns, s.window_ns,
                           s.period_ns) is not None
        assert pipe.flush(30)
    assert pipe.close()
    assert sha.hexdigest() == sha_with


def test_pipeline_2x_shift_on_one_build_names_it_within_two_rollups():
    """Through the real pipeline: ten clean windows give no verdict, then
    every stack of one shared object doubles and the sentinel names that
    build `regressed` within two rollup intervals, with no window lost."""
    shift_after, victim = 10, f"{2:040x}"
    sent, pipe, _ = _pipeline_run(14, shift_after=shift_after)
    assert pipe.stats["windows_lost"] == 0
    v = sent.verdicts(limit=sent.spec.verdict_ring)["verdicts"]
    shift_at_s = _pipeline_snap().time_ns / 1e9 \
        + shift_after * sent.spec.interval_s
    assert [r for r in v if r["t_s"] <= shift_at_s] == []
    hits = [r for r in v
            if r["kind"] == "regressed" and r["build"] == victim]
    assert hits
    assert min(r["t_s"] for r in hits) - shift_at_s \
        <= 2 * sent.spec.interval_s
    assert {r["build"] for r in v if r["kind"] == "regressed"} == {victim}


@pytest.mark.chaos
@pytest.mark.parametrize("fault, counted, folded", [
    ("regression.fold:error:count=3", {"fold_errors": 3}, 5),
    ("regression.baseline:error:count=2",
     {"fold_errors": 0, "baseline_save_errors": 2}, 8),
], ids=["fold", "baseline-save"])
def test_chaos_fold_error_costs_judgment_never_windows(
        fault, counted, folded, tmp_path):
    sent, pipe, sha_chaos = _pipeline_run(
        8, fault_spec=fault, sentinel_spec=_spec(save_every=1),
        path=str(tmp_path / "baselines.bin"))
    assert {k: sent.metrics()[k] for k in counted} == counted
    assert sent.stats["windows_folded"] == folded
    assert pipe.stats["windows_lost"] == 0
    assert pipe.stats["rollup_errors"] == 0  # fail-open inside the hook
    _, _, sha_clean = _pipeline_run(8)
    assert sha_chaos == sha_clean  # the ship path never noticed


@pytest.mark.chaos
def test_chaos_baseline_error_counted_never_torn(tmp_path):
    path = str(tmp_path / "baselines.bin")
    spec = _spec(save_every=1)
    sent, view, caps = _harness(spec=spec)
    sent.path = path
    faults.install(faults.FaultInjector.from_spec(
        "regression.baseline:error:count=2", seed=42))
    try:
        _feed(sent, view, caps, [[100] * 8] * 6)
    finally:
        faults.install(None)
    m = sent.metrics()
    assert m["baseline_save_errors"] == 2
    assert m["baseline_saves"] >= 1  # retried after the fault cleared
    # Never torn: whatever is on disk adopts cleanly.
    warm = RegressionSentinel(spec=spec, path=path)
    assert warm.metrics()["baselines_adopted"] == 1
    assert warm.metrics()["baseline_adopt_errors"] == 0


@pytest.mark.chaos
def test_chaos_disk_full_save_is_counted(tmp_path):
    sent, view, caps = _harness(spec=_spec(save_every=1))
    sent.path = str(tmp_path / "baselines.bin")
    faults.install(faults.FaultInjector.from_spec(
        "regression.baseline:disk_full", seed=42))
    try:
        _feed(sent, view, caps, [[100] * 8] * 4)
    finally:
        faults.install(None)
    m = sent.metrics()
    assert m["baseline_save_errors"] >= 1
    assert m["fold_errors"] == 0  # save failure never reads as fold failure
    assert not os.path.exists(sent.path)


# -- HTTP surface ------------------------------------------------------------

def _http(sent=None, store=None):
    from parca_agent_tpu.web import AgentHTTPServer

    http = AgentHTTPServer(port=0, profilers=[], regression=sent,
                           hotspots=store)
    http.start()
    return http, f"http://127.0.0.1:{http.port}"


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=10) as r:
        return json.loads(r.read().decode())


def _status(base, path):
    try:
        with urllib.request.urlopen(base + path, timeout=10) as r:
            return r.status
    except urllib.error.HTTPError as e:
        return e.code


def test_diff_endpoint_verdict_mode():
    sent, view, caps = _harness()
    windows = [[200] * 8] * 3 + [[200, 200, 200, 200, 200, 200, 200,
                                  2000]] * 2
    _feed(sent, view, caps, windows)
    http, base = _http(sent)
    try:
        body = _get(base, "/diff")
        assert body["verdicts"] and body["groups"]
        assert body["verdicts"][0]["kind"] == "regressed"
        assert _get(base, "/diff?kind=improved")["verdicts"] == []
        assert _get(base, "/diff?tenant=default&build=b1&limit=1")[
            "verdicts"]
        for bad in ("/diff?kind=bogus", "/diff?limit=0",
                    "/diff?since=nan", "/diff?tenant=%00bad",
                    "/diff?a0=1&a1=2", "/diff?kin=regressed"):
            # The last one: verdict mode has a closed parameter set — a
            # typo'd filter must be a 400, never an unfiltered 200.
            assert _status(base, bad) == 400, bad
        assert sent.stats["query_errors"] == 6
    finally:
        http.stop()


def test_diff_endpoint_range_mode_rides_hotspot_levels():
    spec = HotspotSpec(k=10, candidates=64,
                       cm=CountMinSpec(depth=4, width=1 << 10))
    store = HotspotStore(spec=spec, window_s=10.0)
    h1 = np.arange(1, 9, dtype=np.uint32)
    h2 = h1 * 2

    def ctx(i):
        return 1, (f"f{i}",), {"pid": "1", "tenant": "t0"}

    for w, counts in enumerate([[100] * 8] * 3 + [[100, 100, 100, 100,
                                                   100, 100, 100,
                                                   400]] * 3):
        s = WindowSummary.build(h1, h2, np.asarray(counts, np.int64),
                                ctx, spec, T0_NS + int(w * 10e9),
                                int(10e9))
        store.fold(s)
    sent = RegressionSentinel(spec=_spec())
    http, base = _http(sent, store)
    try:
        t0 = T0_NS / 1e9
        q = (f"/diff?a0={t0 + 30}&a1={t0 + 60}"
             f"&b0={t0}&b1={t0 + 30}&scope=local")
        body = _get(base, q)
        assert body["mode"] == "range"
        top = body["entries"][0]
        assert top["delta"] == 900  # 3x300 shifted mass on one stack
        assert top["delta_min"] <= top["delta"] <= top["delta_max"]
        assert body["exact"] == (body["a"]["cut"] == 0
                                 and body["b"]["cut"] == 0)
        # tenant= selector (PR 13 validation) rides the range mode.
        sel = _get(base, q + "&tenant=t0")
        assert sel["entries"]
        none = _get(base, q + "&tenant=other")
        assert none["entries"] == []
        assert _status(base, q + "&scope=galaxy") == 400
        assert _status(base, "/diff?a0=1&a1=2&b0=3&b1=inf") == 400
    finally:
        http.stop()


def test_diff_endpoint_disabled_is_503():
    http, base = _http(None)
    try:
        assert _status(base, "/diff") == 503
    finally:
        http.stop()


def test_metrics_and_healthz_surfaces():
    sent, view, caps = _harness()
    _feed(sent, view, caps, [[100] * 8] * 4)
    http, base = _http(sent)
    try:
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "# TYPE parca_agent_regression_windows_folded_total " \
               "counter" in text
        assert 'parca_agent_regression_verdicts_total{kind="regressed"}' \
            in text
        assert "parca_agent_regression_baselines 1" in text
        assert "parca_agent_regression_drift_max" in text
        body = _get(base, "/healthz")
        assert body["status"] == "healthy"
        reg = body["regression"]
        assert reg["baselines"] == 1 and reg["fold_errors"] == 0
        assert _status(base, "/healthz") == 200
    finally:
        http.stop()


# -- alerts sink -------------------------------------------------------------

def test_alerts_sink_appends_jsonl_and_rotates(tmp_path):
    path = str(tmp_path / "alerts.jsonl")
    sent, view, caps = _harness()
    sink = AlertsSink(path, sentinel=sent, max_bytes=4096)
    windows = [[200] * 8] * 3 + [[200, 200, 200, 200, 200, 200, 200,
                                  2000]] * 2
    _feed(sent, view, caps, windows)
    assert sent.metrics()["alerts_pending"] > 0
    sink.emit(None)  # the window payload is unused; emit drains
    assert sent.metrics()["alerts_pending"] == 0
    lines = [json.loads(ln) for ln in open(path)]
    assert lines and lines[0]["kind"] == "regressed"
    assert lines[0]["build"] == "b1"
    assert sink.stats["verdicts"] == len(lines)
    # Rotation: stuff the ring repeatedly until the size cap trips.
    for _ in range(200):
        sent._alerts.append(dict(lines[0]))
        sink.emit(None)
        if sink.stats["rotations"]:
            break
    assert sink.stats["rotations"] >= 1
    assert os.path.exists(path + ".1")


def test_alerts_sink_requeues_on_failed_append(tmp_path):
    # The append target is a DIRECTORY: open() fails after the drain.
    # The drained verdicts must go back into the sentinel's ring (no
    # loss), and a later healthy sink must land all of them.
    sent, view, caps = _harness()
    windows = [[200] * 8] * 3 + [[200, 200, 200, 200, 200, 200, 200,
                                  2000]] * 2
    _feed(sent, view, caps, windows)
    pending = sent.metrics()["alerts_pending"]
    assert pending > 0
    broken = AlertsSink(str(tmp_path / "as-dir"), sentinel=sent)
    os.makedirs(str(tmp_path / "as-dir" / "x"))  # make the path a dir
    with pytest.raises(Exception):
        broken.emit(None)
    assert sent.metrics()["alerts_pending"] == pending  # requeued
    assert broken.stats["verdicts"] == 0
    ok = AlertsSink(str(tmp_path / "alerts.jsonl"), sentinel=sent)
    ok.emit(None)
    lines = [json.loads(ln) for ln in open(tmp_path / "alerts.jsonl")]
    assert len(lines) == pending
    assert sent.metrics()["alerts_pending"] == 0


def test_no_test_is_gated_on_the_jax_version():
    # One installation, and pyproject.toml names it as the floor: the
    # sharded/fleet sets (and test_walker's numpy-only ShardedTable
    # cases) run unconditionally — the conftest carries no shard_map
    # switch any more, because the jax it defended against cannot be
    # installed under that floor.
    import jax

    import tests.conftest as conftest

    assert hasattr(jax, "shard_map")
    assert not [n for n in vars(conftest) if "shard_map" in n.lower()]
    floor = [ln for ln in open(os.path.join(
        os.path.dirname(conftest.__file__), "..", "pyproject.toml"))
        if ln.startswith("tpu = ")][0]
    assert f"jax>={jax.__version__}" in floor


def test_alerts_sink_without_sentinel_is_inert(tmp_path):
    sink = AlertsSink(str(tmp_path / "alerts.jsonl"))
    sink.emit(None)
    sink.close()
    assert sink.stats["verdicts"] == 0


# -- autofdo staleness marker ------------------------------------------------

def test_autofdo_mark_stale_writes_marker(tmp_path):
    from parca_agent_tpu.sinks.autofdo import AutoFDOSink

    sink = AutoFDOSink(str(tmp_path), flush_windows=1)
    sink.mark_stale("deadbeef01")
    assert sink.stats["stale_marked"] == 1
    marker = tmp_path / "deadbeef01.stale"
    assert marker.exists()
    assert b"stale" in marker.read_bytes()
