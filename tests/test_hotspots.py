"""Hotspot rollup subsystem (runtime/hotspots.py, docs/hotspots.md):
summary build/merge semantics, the level hierarchy's sealing and byte
caps, the query engine (selector, range, scope fallback), the encode-
pipeline fold hook, the /hotspots HTTP surface, metrics strictness, and
the /query timeout clamp satellite."""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.ops.sketch import CountMinSpec
from parca_agent_tpu.profiler.cpu import CPUProfiler
from parca_agent_tpu.runtime.hotspots import (
    HotspotSpec,
    HotspotStore,
    WindowSummary,
)
from parca_agent_tpu.web import AgentHTTPServer, render_metrics

SEC = 1_000_000_000


def _spec(k=5, candidates=16, width=1 << 8, frames=4):
    return HotspotSpec(k=k, candidates=candidates,
                       cm=CountMinSpec(depth=3, width=width),
                       frames=frames)


def _stream(n, seed=0, base=0):
    rng = np.random.default_rng(seed)
    h1 = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    h2 = (np.arange(n, dtype=np.uint64) + base).astype(np.uint32)
    counts = rng.integers(1, 100, n).astype(np.int64)
    return h1, h2, counts


def _ctx(i):
    return 1000 + (i % 3), (f"bin{i % 3}+0x{i:x}",), \
        {"pid": str(1000 + (i % 3))}


def _summary(spec, n=32, seed=0, t_ns=0, dur_ns=10 * SEC):
    h1, h2, counts = _stream(n, seed)
    return WindowSummary.build(h1, h2, counts, _ctx, spec, t_ns, dur_ns), \
        (h1, h2, counts)


# -- summary semantics --------------------------------------------------------


def test_build_keeps_top_candidates_exact():
    spec = _spec(candidates=8)
    h1, h2, counts = _stream(32, seed=1)
    s = WindowSummary.build(h1, h2, counts, _ctx, spec, 0, 10 * SEC)
    assert len(s.entries) == 8
    assert s.total == int(counts.sum())
    key64 = (h1.astype(np.uint64) << np.uint64(32)) | h2.astype(np.uint64)
    top = np.argsort(counts)[-8:]
    assert {int(key64[i]) for i in top} == set(s.entries)
    for i in top:
        assert s.entries[int(key64[i])][0] == int(counts[i])
    # cut = the largest excluded count: the bound on any absent stack.
    excluded = np.sort(counts)[:-8]
    assert s.cut == int(excluded.max())


def test_build_small_stream_is_exact():
    spec = _spec(candidates=64)
    s, (h1, h2, counts) = _summary(spec, n=32, seed=2)
    assert s.cut == 0 and len(s.entries) == 32


def test_merge_matches_concat_within_candidate_bound():
    """Candidate-table merge is linear: when nothing is pruned, merging
    per-window summaries equals one summary over the concatenated
    stream, entry for entry and cm cell for cm cell."""
    spec = _spec(candidates=128)
    a, (h1a, h2a, ca) = _summary(spec, n=40, seed=3, t_ns=0)
    b, (h1b, h2b, cb) = _summary(spec, n=40, seed=4, t_ns=10 * SEC)
    merged = WindowSummary(spec)
    merged.merge_in(a, spec)
    merged.merge_in(b, spec)
    direct = WindowSummary.build(
        np.concatenate([h1a, h1b]), np.concatenate([h2a, h2b]),
        np.concatenate([ca, cb]), _ctx, spec, 0, 20 * SEC)
    assert np.array_equal(merged.cm, direct.cm)
    assert merged.total == direct.total
    assert {k: e[0] for k, e in merged.entries.items()} \
        == {k: e[0] for k, e in direct.entries.items()}
    assert merged.windows == 2 and merged.t1_ns == 20 * SEC


def test_merge_prune_raises_cut_and_preserves_heavy_hitters():
    spec = _spec(candidates=8)
    a, (h1a, h2a, ca) = _summary(spec, n=32, seed=5)
    b, (h1b, h2b, cb) = _summary(spec, n=32, seed=6)
    merged = WindowSummary(spec)
    merged.merge_in(a, spec)
    merged.merge_in(b, spec)
    assert len(merged.entries) == 8
    assert merged.cut >= a.cut + b.cut
    # The heaviest surviving entries dominate everything pruned.
    survivors = sorted((e[0] for e in merged.entries.values()),
                       reverse=True)
    assert survivors[0] >= merged.cut - a.cut - b.cut


# -- the store: folding, levels, query ---------------------------------------


def _store(spec=None, **kw):
    kw.setdefault("window_s", 10.0)
    kw.setdefault("rollup_spans_s", (60.0, 3600.0))
    return HotspotStore(spec=spec or _spec(), **kw)


def _fold_windows(store, n, start_s=0.0, window_s=10.0, seed0=0,
                  uniques=64):
    """Fold n windows of a FIXED population with per-window counts."""
    rng = np.random.default_rng(123)
    h1 = rng.integers(0, 1 << 32, uniques, dtype=np.uint64).astype(np.uint32)
    h2 = np.arange(uniques, dtype=np.uint32)
    exact = np.zeros(uniques, np.int64)
    for w in range(n):
        counts = np.random.default_rng(seed0 + w).integers(
            1, 50, uniques).astype(np.int64)
        exact += counts
        s = WindowSummary.build(
            h1, h2, counts, _ctx, store.spec,
            int((start_s + w * window_s) * SEC), int(window_s * SEC))
        store.fold(s)
    return h1, h2, exact


def test_fold_and_query_topk_matches_exact():
    store = _store(_spec(k=5, candidates=128))
    h1, h2, exact = _fold_windows(store, 12)
    ans = store.query(k=5)
    assert ans["windows"] == 12
    assert ans["total_samples"] == int(exact.sum())
    key64 = (h1.astype(np.uint64) << np.uint64(32)) | h2.astype(np.uint64)
    want = {f"0x{int(key64[i]):016x}": int(exact[i])
            for i in np.argsort(exact)[-5:]}
    got = {e["stack"]: e["count"] for e in ans["entries"]}
    assert got == want
    assert ans["exact"] and all(e["exact"] for e in ans["entries"])
    # The cm estimate never undercuts the exact count.
    for e in ans["entries"]:
        assert e["estimate"] >= e["count"]


def test_rollup_levels_seal_and_promote():
    store = _store(_spec(candidates=128))
    # 130 windows x 10 s = ~21.7 min: minute buckets seal, the hour
    # bucket accumulates, the window ring holds everything.
    _fold_windows(store, 130)
    m = store.metrics()
    lv = {x["name"]: x for x in m["levels"] if x["scope"] == "local"}
    assert lv["window"]["summaries"] == 130
    assert 20 <= lv["1m"]["summaries"] <= 23  # ~21 sealed + the open one
    assert lv["1h"]["summaries"] == 1         # the open hour bucket
    assert m["windows_folded"] == 130
    # A minute bucket merges its 6 windows.
    minute = store._levels[1].ring[0][0]
    assert minute.windows == 6
    assert minute.t1_ns - minute.t0_ns == 60 * SEC


def test_query_picks_granularity_by_range():
    store = _store(_spec(candidates=128))
    _fold_windows(store, 130)
    assert store.query(t0_s=0, t1_s=30)["level"] == "window"
    assert store.query(t0_s=0, t1_s=600)["level"] == "1m"
    # The full ~22 min range still rides minute buckets (2 h would be
    # needed to justify hour granularity).
    assert store.query()["level"] == "1m"
    assert 0.9 <= store.query()["cover"] <= 1.0


def test_byte_cap_evicts_oldest():
    spec = _spec(candidates=64, width=1 << 8)
    probe = WindowSummary(spec)
    cap = probe.cm.nbytes * 4  # room for ~3-4 summaries per level
    store = _store(spec, level_bytes=cap)
    _fold_windows(store, 20)
    m = store.metrics()
    win = next(x for x in m["levels"]
               if x["scope"] == "local" and x["name"] == "window")
    assert win["evictions"] > 0
    assert win["bytes"] <= cap
    # Old windows evicted: a query over the start of the range falls
    # back to whatever level still covers it (the open rollup buckets).
    recent = store.query(t0_s=150, t1_s=200)
    assert recent["windows"] > 0


@pytest.mark.parametrize("skew, k", [(0.55, 50), (1.0, 20)],
                         ids=["every-window-prunes", "long-dormant-tail"])
def test_two_hours_of_pruned_windows_answer_with_the_exact_top_k(skew, k):
    """720 windows of a zipf population sixteen times the candidate
    bound, each with its own Poisson noise: the whole range is served
    from the hour rollups and names the top K of the exact aggregate
    (agreement 0.99 or better, counts within 2%), while every ring sits
    at its byte cap with the oldest windows evicted."""
    uniques, windows, cap = 1 << 12, 720, 2 << 20
    spec = HotspotSpec(k=k, candidates=256,
                       cm=CountMinSpec(depth=4, width=1 << 9))
    store = _store(spec, level_bytes=cap)
    rng = np.random.default_rng(0xA77)
    h1 = rng.integers(0, 1 << 32, uniques, dtype=np.uint64).astype(np.uint32)
    h2 = np.arange(uniques, dtype=np.uint32)
    rates = 200.0 / np.arange(1, uniques + 1, dtype=np.float64) ** skew
    rng.shuffle(rates)  # key order says nothing of hotness
    exact = np.zeros(uniques, np.int64)
    for w in range(windows):
        counts = rng.poisson(rates).astype(np.int64)
        live = np.flatnonzero(counts)
        assert len(live) > spec.candidates  # every window prunes
        exact += counts
        store.fold(WindowSummary.build(
            h1[live], h2[live], counts[live],
            lambda i, live=live: _ctx(int(live[i])), spec,
            w * 10 * SEC, 10 * SEC))

    ans = store.query(k=k, t0_s=0, t1_s=windows * 10)
    assert ans["level"] == "1h" and ans["cover"] > 0.99
    assert not ans["exact"]  # pruned keys' mass is estimated, and said so
    key64 = (h1.astype(np.uint64) << np.uint64(32)) | h2.astype(np.uint64)
    want = {f"0x{int(key64[i]):016x}": int(exact[i])
            for i in np.argsort(exact)[-k:]}
    got = {e["stack"]: e["count"] for e in ans["entries"]}
    agreed = set(got) & set(want)
    assert len(agreed) >= 0.99 * k
    assert all(abs(got[s] - want[s]) <= 0.02 * want[s] for s in agreed)

    levels = [lv for lv in store.metrics()["levels"]
              if lv["scope"] == "local"]
    assert all(lv["bytes"] <= 1.05 * cap for lv in levels)
    assert sum(lv["evictions"] for lv in levels) > 0


def test_label_selector_filters_and_unlabeled_entries_drop():
    store = _store(_spec(k=10, candidates=128))
    _fold_windows(store, 3)
    all_ans = store.query(k=10)
    one = store.query(k=10, selector={"pid": "1001"})
    assert one["entries"]
    assert all(e["labels"]["pid"] == "1001" for e in one["entries"])
    assert len(one["entries"]) < len(all_ans["entries"]) or \
        len(all_ans["entries"]) == 10
    assert store.query(k=10, selector={"pid": "nope"})["entries"] == []


def test_fleet_fold_context_join_and_staleness():
    clock = [100.0]
    store = _store(_spec(k=5, candidates=128), clock=lambda: clock[0])
    h1, h2, exact = _fold_windows(store, 2)
    # Fleet scope before any round: local fallback, stale.
    ans = store.query(scope="fleet")
    assert ans["fallback"] == "local" and ans["stale"]
    # A fleet round over the same keys: context joins back locally.
    counts = np.arange(1, len(h1) + 1, dtype=np.int64) * 10
    store.fleet_fold(h1, h2, counts, time_ns=0)
    ans = store.query(scope="fleet")
    assert "fallback" not in ans
    assert not ans["stale"] and not ans["degraded"]
    top = ans["entries"][0]
    assert top["count"] == int(counts.max())
    assert top["frames"] and not top["frames"][0].startswith("stack:")
    assert top["labels"] is not None
    # Unknown keys (only other nodes saw them) render opaquely.
    store.fleet_fold(np.array([7], np.uint32), np.array([9], np.uint32),
                     np.array([10_000], np.int64), time_ns=0)
    ans = store.query(scope="fleet", k=1)
    assert ans["entries"][0]["frames"][0].startswith("stack:0x")
    assert ans["entries"][0]["labels"] is None
    # Degrade notification flags answers; recovery clears it.
    store.fleet_degraded("CollectiveTimeout('...')")
    ans = store.query(scope="fleet")
    assert ans["stale"] and ans["degraded"]
    assert ans["fleet_error"].startswith("CollectiveTimeout")
    store.fleet_fold(h1, h2, counts, time_ns=0)
    assert not store.query(scope="fleet")["stale"]
    # Staleness by age alone (no degrade event).
    clock[0] += 10_000
    assert store.query(scope="fleet")["stale"]


def test_query_rejects_bad_args():
    store = _store()
    with pytest.raises(ValueError):
        store.query(scope="galaxy")
    with pytest.raises(ValueError):
        store.query(t0_s=10, t1_s=1)


# -- aggregator id hashes -----------------------------------------------------


def _snap(seed=7, n=64):
    return generate(SyntheticSpec(
        n_pids=4, n_unique_stacks=n, n_rows=n, total_samples=4 * n,
        mean_depth=6, seed=seed))


def test_dict_aggregator_publishes_id_hashes():
    agg = DictAggregator(capacity=1 << 10)
    agg.window_counts(_snap(1))
    agg.window_counts(_snap(2))
    h1, h2 = agg.id_hashes()
    assert len(h1) == agg._published == agg._next_id
    for (k1, k2, _k3), sid in agg._key_to_id.items():
        assert int(h1[sid]) == k1 and int(h2[sid]) == k2


def test_id_hashes_survive_rotation():
    agg = DictAggregator(capacity=1 << 10, rotate_min_age=1)
    agg.window_counts(_snap(1, n=32))
    agg._rotate_pending = True
    agg.window_counts(_snap(9, n=32))  # different population: evicts
    h1, h2 = agg.id_hashes()
    assert len(h1) == agg._next_id
    for (k1, k2, _k3), sid in agg._key_to_id.items():
        assert int(h1[sid]) == k1 and int(h2[sid]) == k2


def test_registry_view_isolates_fold_from_rotation():
    """The hazard the hand-off capture exists for: a cold-stack rotation
    between hand-off and the worker's fold compacts the live per-id
    mirrors, so a fold reading them with prepared ids would attribute
    the window to the wrong stacks. A RegistryView captured at hand-off
    (profiler thread) must keep the prepared ids naming exactly what
    they named then — identical answers to folding before the rotation."""
    from parca_agent_tpu.runtime.hotspots import RegistryView

    spec = _spec(k=5, candidates=256)
    agg = DictAggregator(capacity=1 << 10, rotate_min_age=1)
    counts = agg.window_counts(_snap(1, n=32))
    idx = np.flatnonzero(counts)
    vals = counts[idx].astype(np.int64)
    view = RegistryView(agg)
    before = HotspotStore(spec=spec)
    before.fold_from_aggregator(agg, idx, vals, 0, 10 * SEC)
    # Rotation slides in (the next window's first feed, profiler
    # thread) with a disjoint population: every old id is remapped.
    agg._rotate_pending = True
    agg.window_counts(_snap(9, n=32))
    after = HotspotStore(spec=spec)
    after.fold_from_aggregator(view, idx, vals, 0, 10 * SEC)
    assert after.query(k=5)["entries"] == before.query(k=5)["entries"]
    assert after.stats["fold_errors"] == 0


def test_fold_errors_counted_on_the_store():
    """fold_errors is the store's EXPORTED error contract
    (parca_agent_hotspot_fold_errors_total): a failing fold must both
    raise (for the pipeline to contain) and count."""
    store = _store()
    agg = DictAggregator(capacity=1 << 10)
    agg.window_counts(_snap(1, n=8))
    with pytest.raises(IndexError):
        store.fold_from_aggregator(
            agg, np.array([10 ** 6]), np.array([1], np.int64), 0, SEC)
    assert store.stats["fold_errors"] == 1


def test_store_rejects_nonpositive_rollup_spans():
    for spans in ((0.0,), (-5.0, 60.0), (float("nan"),)):
        with pytest.raises(ValueError):
            HotspotStore(spec=_spec(), rollup_spans_s=spans)


# -- pipeline integration -----------------------------------------------------


class _Sink:
    def write(self, labels, blob):
        pass


def _profiler(store, snaps):
    class Src:
        def __init__(self):
            self.snaps = list(snaps)

        def poll(self):
            return self.snaps.pop(0) if self.snaps else None

    return CPUProfiler(
        source=Src(), aggregator=DictAggregator(capacity=1 << 12),
        fallback_aggregator=CPUAggregator(), profile_writer=_Sink(),
        duration_s=0.0, fast_encode=True, encode_pipeline=True,
        hotspot_store=store)


def test_pipeline_folds_every_window_off_the_capture_thread():
    store = _store(_spec(k=5, candidates=256))
    snaps = [_snap(i) for i in range(4)]
    prof = _profiler(store, snaps)
    while prof.run_iteration():
        # Per-window flush: the test drives windows back-to-back, and a
        # backpressure fallback would (correctly) skip that window's fold.
        assert prof._pipeline.flush(30)
    assert prof._pipeline.quiesce(30)
    try:
        assert prof._pipeline.stats["windows_rolled"] == 4
        assert prof._pipeline.stats["rollup_errors"] == 0
        assert store.stats["windows_folded"] == 4
        ans = store.query(k=5)
        assert ans["entries"] and ans["windows"] == 4
        assert ans["total_samples"] == sum(
            int(s.total_samples()) for s in snaps)
        top = ans["entries"][0]
        assert top["frames"] and top["pid"] is not None
        assert top["labels"]["pid"] == str(top["pid"])
    finally:
        prof._pipeline.close(10)


def test_fold_failure_is_contained_and_counted():
    from parca_agent_tpu.utils import faults

    store = _store()
    prof = _profiler(store, [_snap(0), _snap(1)])
    faults.install(faults.FaultInjector.from_spec(
        "hotspot.fold:error:count=1", seed=42))
    try:
        while prof.run_iteration():
            assert prof._pipeline.flush(30)
        assert prof._pipeline.quiesce(30)
        stats = prof._pipeline.stats
        assert stats["rollup_errors"] == 1
        assert stats["windows_rolled"] == 1
        assert stats["windows_lost"] == 0
        assert stats["windows_pipelined"] == 2  # both windows shipped
        assert prof.crashed is None and prof.last_error is None
    finally:
        faults.install(None)
        prof._pipeline.close(10)


# -- HTTP surface -------------------------------------------------------------


def _http(**kw):
    srv = AgentHTTPServer(port=0, profilers=[], **kw)
    srv.start()
    return srv, f"http://127.0.0.1:{srv.port}"


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read().decode())


def test_hotspots_endpoint_serves_and_validates():
    store = _store(_spec(k=5, candidates=128))
    _fold_windows(store, 3)
    srv, base = _http(hotspots=store)
    try:
        ans = _get(f"{base}/hotspots?k=3")
        assert len(ans["entries"]) == 3
        assert ans["scope"] == "local"
        sel = _get(f"{base}/hotspots?k=5&pid=1002")
        assert all(e["labels"]["pid"] == "1002" for e in sel["entries"])
        fleet = _get(f"{base}/hotspots?scope=fleet")
        assert fleet["fallback"] == "local" and fleet["stale"]
        for bad in ("k=x", "k=0", "range=-1", "range=inf", "scope=blah",
                    "t0=5&t1=2", "t0=inf", "t1=nan", "t0=1e308"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/hotspots?{bad}",
                                       timeout=10)
            assert ei.value.code == 400, bad
        assert store.stats["query_errors"] >= 6
    finally:
        srv.stop()


def test_hotspots_endpoint_503_without_store():
    srv, base = _http()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/hotspots", timeout=10)
        assert ei.value.code == 503
    finally:
        srv.stop()


def test_healthz_hotspots_section_never_red():
    store = _store()
    _fold_windows(store, 2)
    store.fleet_degraded("boom")  # degraded fleet must not flip readiness
    srv, base = _http(hotspots=store)
    try:
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            assert r.status == 200
            body = json.loads(r.read().decode())
        assert body["hotspots"]["windows_folded"] == 2
        assert body["hotspots"]["fleet"]["stale"]
        assert body["hotspots"]["fleet"]["rounds_degraded"] == 1
    finally:
        srv.stop()


def test_hotspot_metrics_are_strict_prometheus():
    from test_metrics_format import parse_prometheus_text

    store = _store()
    _fold_windows(store, 5)
    store.fleet_fold(*_stream(8, seed=1)[:2],
                     np.arange(1, 9, dtype=np.int64), time_ns=0)
    fams = parse_prometheus_text(render_metrics([], hotspots=store))
    lv = fams["parca_agent_hotspot_level_summaries"]
    scopes = {(lab["scope"], lab["level"]) for _, lab, _ in lv["samples"]}
    assert ("local", "window") in scopes and ("fleet", "1h") in scopes
    assert fams["parca_agent_hotspot_level_evictions_total"]["type"] \
        == "counter"
    assert fams["parca_agent_hotspot_windows_folded_total"][
        "samples"][0][2] == 5
    assert fams["parca_agent_hotspot_fleet_rounds_ok_total"][
        "samples"][0][2] == 1
    assert "parca_agent_hotspot_fleet_age_seconds" in fams


# -- /query timeout clamp satellite ------------------------------------------


class _Listener:
    """Records the timeout the handler actually passes down."""

    def __init__(self):
        self.timeouts = []

    def next_matching_profile(self, match, timeout):
        self.timeouts.append(timeout)
        return None


def test_query_timeout_clamped_and_validated():
    lst = _Listener()
    srv, base = _http(listener=lst)
    try:
        for bad in ("timeout=-1", "timeout=nan", "timeout=inf",
                    "timeout=abc"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/query?{bad}", timeout=10)
            assert ei.value.code == 400, bad
        assert lst.timeouts == []  # rejected before touching the listener
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/query?timeout=0.01&pid=1",
                                   timeout=10)
        assert ei.value.code == 404  # no profile: listener consulted
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/query?timeout=86400&pid=1",
                                   timeout=10)
        assert lst.timeouts == [0.01, 60.0]  # huge timeout clamped
    finally:
        srv.stop()
