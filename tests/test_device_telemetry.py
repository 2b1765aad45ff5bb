"""Device flight recorder (runtime/device_telemetry.py,
docs/observability.md "device flight recorder").

The contract under test: every kernel dispatch site reports into the
process-global registry; the shape-signature first-call latch separates
``compile`` from ``execute``; a NEW signature on a latched kernel is a
counted recompile that routes exactly one rate-limited incident through
the window flight recorder; transfer bytes and the window-SLO budget
layer accumulate without device syncs; and the whole path is FAIL-OPEN —
an injected ``device.telemetry`` fault on EVERY entry point never loses
a window and never changes a pprof byte.
"""

from __future__ import annotations

import hashlib
import json
import threading

import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.profiler.cpu import CPUProfiler
from parca_agent_tpu.runtime import device_telemetry as dtel_mod
from parca_agent_tpu.runtime import trace as trace_mod
from parca_agent_tpu.runtime.device_telemetry import DeviceTelemetry
from parca_agent_tpu.runtime.trace import FlightRecorder
from parca_agent_tpu.utils import faults
from parca_agent_tpu.web import render_metrics

pytestmark = pytest.mark.chaos


def _snap(seed=7, n_pids=6, rows=200):
    return generate(SyntheticSpec(
        n_pids=n_pids, n_unique_stacks=rows, n_rows=rows,
        total_samples=rows * 4, mean_depth=8, kernel_fraction=0.25,
        seed=seed))


class ListSource:
    def __init__(self, snaps):
        self._snaps = list(snaps)

    def poll(self):
        return self._snaps.pop(0) if self._snaps else None


class Collect:
    def __init__(self):
        self.got = []

    def write(self, labels, blob):
        self.got.append((labels, bytes(blob)))


@pytest.fixture(autouse=True)
def _no_global_state():
    yield
    faults.install(None)
    trace_mod.install(None)
    dtel_mod.install(None)


# -- latch / recompile machinery ----------------------------------------------


def test_first_signature_is_compile_rest_execute():
    t = DeviceTelemetry()
    t.record("feed_probe", 0.2, shape=(4096, 8, 128))
    for _ in range(3):
        t.record("feed_probe", 0.001, shape=(4096, 8, 128))
    p = t.percentiles()["feed_probe"]
    assert p["compile"]["count"] == 1
    assert p["execute"]["count"] == 3
    # The compile observation carries the compile-heavy latency.
    assert p["compile"]["max_ms"] > p["execute"]["max_ms"]
    assert t.stats["compiles_total"] == 1
    assert t.stats["recompiles_total"] == 0
    assert t.shape_counts() == {"feed_probe": 1}


def test_new_signature_on_latched_kernel_counts_recompile():
    t = DeviceTelemetry()
    t.record("feed_probe", 0.2, shape=(4096,))
    t.record("feed_probe", 0.3, shape=(8192,))   # recompile
    t.record("feed_probe", 0.001, shape=(8192,))  # cached again
    assert t.stats["compiles_total"] == 2
    assert t.stats["recompiles_total"] == 1
    assert t.shape_counts() == {"feed_probe": 2}
    # Distinct kernels latch independently — no cross-kernel storms.
    t.record("loc_dedup", 0.1, shape=(4096,))
    assert t.stats["recompiles_total"] == 1


def test_shapeless_record_is_execute_only():
    t = DeviceTelemetry()
    t.record("close_fetch", 0.002, d2h_bytes=4096)
    p = t.percentiles()["close_fetch"]
    assert "compile" not in p
    assert p["execute"]["count"] == 1
    assert t.shape_counts() == {}


def test_recompile_routes_one_incident_through_recorder(tmp_path):
    rec = FlightRecorder(incident_dir=str(tmp_path), self_profile=None)
    trace_mod.install(rec)
    t = DeviceTelemetry(incident_interval_s=3600.0)
    t.record("feed_probe", 0.2, shape=(4096,))
    t.record("feed_probe", 0.3, shape=(8192,))
    t.record("feed_probe", 0.3, shape=(16384,))  # pre-filter suppresses
    deadline = threading.Event()
    for _ in range(100):
        if not rec._dumping and list(tmp_path.iterdir()):
            break
        deadline.wait(0.05)
    files = sorted(tmp_path.iterdir())
    assert len(files) == 1, files
    body = json.loads(files[0].read_text())
    assert body["kind"] == "recompile_storm"
    assert body["detail"]["kernel"] == "feed_probe"
    assert body["detail"]["shapes_latched"] == 2
    assert "feed_probe" in body["detail"]["kernel_percentiles"]
    assert t.stats["recompile_incidents"] == 1
    assert t.stats["recompile_incidents_suppressed"] == 1


def test_recompile_without_recorder_is_counted_suppressed():
    t = DeviceTelemetry()
    t.record("feed_probe", 0.2, shape=(1,))
    t.record("feed_probe", 0.2, shape=(2,))
    assert t.stats["recompiles_total"] == 1
    assert t.stats["recompile_incidents"] == 0
    assert t.stats["recompile_incidents_suppressed"] == 1


# -- transfers / backends / identity ------------------------------------------


def test_transfer_accounting_by_kernel_and_direction():
    t = DeviceTelemetry()
    t.record("feed_probe", 0.01, shape=(1,), h2d_bytes=1000)
    t.record("feed_probe", 0.01, shape=(1,), h2d_bytes=500)
    t.record_transfer("miss_settle", "h2d", 256)
    t.record("close_fetch", 0.01, d2h_bytes=2048)
    assert t.transfers() == [
        ("close_fetch", "d2h", 2048, 1),
        ("feed_probe", "h2d", 1500, 2),
        ("miss_settle", "h2d", 256, 1),
    ]


def test_note_backend_fields_are_sticky():
    t = DeviceTelemetry()
    t.note_backend("device", requested="device", resolved="device",
                   fallback=False)
    t.note_backend("device", resolved="cpu_fallback", fallback=True)
    b = t.backends()["device"]
    assert b == {"requested": "device", "resolved": "cpu_fallback",
                 "fallback": True}


def test_identity_latches_once_and_names_the_backend():
    from parca_agent_tpu.runtime.device_telemetry import collect_identity

    t = DeviceTelemetry()
    t.set_identity(collect_identity())  # the device owner's deliberate act
    a = t.ensure_identity()
    assert a["platform"] == "cpu"
    assert a["device_kind"] == "cpu"
    assert a["jax_version"] != "unknown"
    assert a["jaxlib_version"] not in ("unknown", "none")
    assert a["device_count"] >= 1
    assert a["hostname"]
    t.set_identity({"platform": "other"})  # first write wins
    assert t.ensure_identity() == a
    assert t.snapshot()["identity"] == a


def test_xla_compile_events_are_routed_to_the_installed_telemetry():
    """watch_xla_compiles: the compiler's own seconds and the persistent
    cache's request count reach whatever telemetry is installed."""
    import jax
    import jax.numpy as jnp

    from parca_agent_tpu.runtime import device_telemetry as dtel_mod

    t = DeviceTelemetry()
    dtel_mod.install(t)
    try:
        dtel_mod.watch_xla_compiles()
        dtel_mod.watch_xla_compiles()  # idempotent: one listener pair
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(37)).block_until_ready()
        assert t.xla["backend_compile_seconds_total"] > 0
        assert t.snapshot()["xla"] == t.xla
        from parca_agent_tpu.web import render_metrics

        m = render_metrics([], device_telemetry=t)
        assert "parca_agent_xla_backend_compile_seconds_total" in m
        assert "# TYPE parca_agent_xla_cache_hits_total counter" in m
    finally:
        dtel_mod.install(None)


# -- window-SLO layer ---------------------------------------------------------


def test_window_budget_ratio_and_burn_counter():
    t = DeviceTelemetry(period_s=1.0)
    t.tick_window(0.25)
    t.tick_window(1.5)
    ws = t.window_stats
    assert ws["windows_total"] == 2
    assert ws["windows_over_budget_total"] == 1
    assert ws["budget_used_last"] == pytest.approx(1.5)
    b = t.budget_export()
    assert b["period_s"] == 1.0
    assert b["hist"]["count"] == 2


def test_zero_period_counts_windows_without_budget():
    t = DeviceTelemetry(period_s=0.0)
    t.tick_window(0.25)
    assert t.window_stats["windows_total"] == 1
    assert t.window_stats["windows_over_budget_total"] == 0
    assert t.budget_export()["hist"]["count"] == 0


def test_other_thread_kernel_seconds_fold_into_window():
    """Kernel time recorded off the capture thread (streaming tees,
    encode-side fetches) adds to used_s; same-thread kernel time is
    already inside the busy wall and must not double-count."""
    t = DeviceTelemetry(period_s=1.0)
    t.record("feed_probe", 0.4, shape=(1,))  # same thread as the tick
    th = threading.Thread(
        target=lambda: t.record("loc_dedup", 0.3, shape=(2,)))
    th.start()
    th.join()
    t.tick_window(0.5)
    # 0.5 busy wall + 0.3 off-thread; the same-thread 0.4 is NOT added.
    assert t.window_stats["budget_used_last"] == pytest.approx(0.8)
    # The accumulator clears per tick.
    t.tick_window(0.1)
    assert t.window_stats["budget_used_last"] == pytest.approx(0.1)


# -- fail-open (the device.telemetry chaos site) ------------------------------


def test_telemetry_fault_is_swallowed_and_counted():
    faults.install(faults.FaultInjector.from_spec("device.telemetry:error"))
    t = DeviceTelemetry(period_s=1.0)
    t.record("feed_probe", 0.01, shape=(1,), h2d_bytes=64)
    t.record_transfer("miss_settle", "h2d", 64)
    t.note_backend("device", resolved="cpu_fallback")
    t.tick_window(0.5)
    assert t.stats["record_errors"] == 4
    assert t.stats["events_total"] == 0
    assert t.window_stats["windows_total"] == 0
    assert t.transfers() == [] and t.backends() == {}
    faults.install(None)
    t.record("feed_probe", 0.01, shape=(1,))
    assert t.stats["events_total"] == 1


def test_module_hooks_are_free_without_telemetry():
    dtel_mod.install(None)
    dtel_mod.record("feed_probe", 0.01, shape=(1,))
    dtel_mod.transfer("miss_settle", "h2d", 64)
    dtel_mod.note_backend("device", resolved="cpu_fallback")
    dtel_mod.tick_window(0.5)
    assert dtel_mod.get() is None


def _pprof_digest(sink):
    h = hashlib.sha256()
    for labels, blob in sink.got:
        h.update(str(sorted(labels.items())).encode())
        h.update(blob)
    return h.hexdigest()


def _run_windows(n=3):
    sink = Collect()
    prof = CPUProfiler(
        source=ListSource([_snap(seed=i) for i in range(n)]),
        aggregator=DictAggregator(capacity=1 << 12),
        fallback_aggregator=CPUAggregator(), profile_writer=sink,
        duration_s=0.0, fast_encode=True, encode_pipeline=True)
    prof.run()
    assert prof.crashed is None and prof.last_error is None
    assert prof.metrics.attempts_total == n
    assert prof._pipeline.stats["windows_lost"] == 0
    return _pprof_digest(sink)


def test_telemetry_and_faults_never_change_pprof_bytes():
    """The acceptance bar: pprof output is sha256-identical and zero
    windows are lost with telemetry off, on, and on-with-every-hook-
    faulting — observation must never touch the data plane."""
    dtel_mod.install(None)
    baseline = _run_windows()

    tel = DeviceTelemetry(period_s=1.0)
    dtel_mod.install(tel)
    assert _run_windows() == baseline
    assert tel.stats["events_total"] > 0
    assert tel.window_stats["windows_total"] == 3
    assert tel.stats["record_errors"] == 0

    tel2 = DeviceTelemetry(period_s=1.0)
    dtel_mod.install(tel2)
    faults.install(faults.FaultInjector.from_spec("device.telemetry:error"))
    try:
        assert _run_windows() == baseline
    finally:
        faults.install(None)
    assert tel2.stats["record_errors"] > 0
    assert tel2.stats["events_total"] == 0
    assert faults.get() is None or True


# -- /metrics rendering -------------------------------------------------------


def test_render_metrics_kernel_transfer_and_budget_families():
    t = DeviceTelemetry(period_s=1.0)
    t.record("feed_probe", 0.2, shape=(4096,), h2d_bytes=1024)
    t.record("feed_probe", 0.001, shape=(4096,))
    t.note_backend("device", resolved="cpu_fallback", fallback=True)
    t.tick_window(0.5)
    t.tick_window(1.5)
    m = render_metrics([], device_telemetry=t)
    assert "# TYPE parca_agent_kernel_duration_seconds histogram" in m
    assert 'parca_agent_kernel_duration_seconds_count' \
        '{kernel="feed_probe",event="compile"} 1' in m
    assert 'parca_agent_kernel_duration_seconds_count' \
        '{kernel="feed_probe",event="execute"} 1' in m
    assert 'parca_agent_kernel_compiles_total{kernel="feed_probe"} 1' in m
    assert 'parca_agent_kernel_recompiles_total{kernel="feed_probe"} 0' in m
    assert 'parca_agent_kernel_backend{kernel="device",' \
        'backend="cpu_fallback"} 1' in m
    assert 'parca_agent_kernel_backend{kernel="device",' \
        'backend="device"} 0' in m
    assert m.count('parca_agent_kernel_backend{') == 2
    assert 'parca_agent_kernel_fallback{kernel="device"} 1' in m
    assert "parca_agent_kernel_interpret" not in m
    assert 'parca_agent_transfer_bytes_total{kernel="feed_probe",' \
        'direction="h2d"} 1024' in m
    assert "parca_agent_window_budget_windows_total 2" in m
    assert "parca_agent_window_budget_windows_over_total 1" in m
    assert "parca_agent_window_budget_period_seconds 1" in m
    assert 'platform="cpu"' in m and "parca_agent_device_info" in m
    assert "parca_agent_device_telemetry_record_errors_total 0" in m


def test_render_metrics_without_telemetry_has_no_kernel_families():
    m = render_metrics([])
    assert "parca_agent_kernel_" not in m
    assert "parca_agent_window_budget_" not in m
