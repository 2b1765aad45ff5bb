"""bench.py supervisor helpers (the measurement itself runs on hardware;
these pin the pure-host pieces: JSON-line recovery, snapshot caching,
and the no-accelerator contract — fail, never time XLA:CPU under a
device metric's name)."""

import importlib.util
import json
import sys


def _bench():
    spec = importlib.util.spec_from_file_location("bench", "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench = _bench()


def test_scan_json_line_takes_last_dict():
    out = "\n".join([
        "garbage",
        json.dumps({"metric": "old"}),
        "42",            # stray scalar: ignored
        json.dumps({"metric": "new"}),
        "null",          # stray scalar after the result: ignored
    ])
    assert bench._scan_json_line(out) == {"metric": "new"}
    assert bench._scan_json_line("") is None
    assert bench._scan_json_line("true\n7\n") is None


def test_snapshot_path_fingerprints_spec(monkeypatch):
    p1 = bench._snapshot_path(1024, 10)
    assert p1 == bench._snapshot_path(1024, 10)  # deterministic
    assert p1 != bench._snapshot_path(2048, 10)  # rows in the key
    assert p1 != bench._snapshot_path(1024, 11)  # pids in the key

    # ANY spec field change must change the cache file (stale-file guard).
    orig = bench._bench_spec

    def tweaked(rows, pids):
        import dataclasses

        return dataclasses.replace(orig(rows, pids), seed=43)

    monkeypatch.setattr(bench, "_bench_spec", tweaked)
    assert bench._snapshot_path(1024, 10) != p1


def test_make_snapshot_roundtrips_through_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    import tempfile

    monkeypatch.setattr(tempfile, "gettempdir", lambda: str(tmp_path))
    s1 = bench._make_snapshot(64, 4)
    cached = list(tmp_path.glob("parca_bench_snap_*.bin"))
    assert len(cached) == 1
    s2 = bench._make_snapshot(64, 4)  # loads, not regenerates
    import numpy as np

    np.testing.assert_array_equal(s1.counts, s2.counts)
    np.testing.assert_array_equal(s1.stacks, s2.stacks)

    # A corrupt cache regenerates instead of crashing.
    cached[0].write_bytes(b"not a snapshot")
    s3 = bench._make_snapshot(64, 4)
    np.testing.assert_array_equal(s1.counts, s3.counts)


def test_run_child_recovers_result_from_failing_child(monkeypatch):
    """A child that prints its JSON and then dies (teardown crash) still
    yields the measurement."""
    import subprocess

    def fake_run(argv, **kw):
        return subprocess.CompletedProcess(
            argv, returncode=1,
            stdout=json.dumps({"metric": "m", "value": 1}) + "\n",
            stderr="backend teardown exploded\n")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    got = bench._run_child(5.0)
    assert got["metric"] == "m" and got["value"] == 1
    assert "rc=1" in got["attempt_note"]  # teardown crash is marked


def test_run_child_recovers_provisional_line_from_hung_child(monkeypatch):
    """The r3 failure mode: the measurement finished and emitted the
    flushed provisional headline, then an optional extra hung past the
    attempt timeout. The supervisor must recover the provisional dict
    from the captured stdout instead of scoring the attempt failed."""

    def fake_run(argv, **kw):
        raise bench.subprocess.TimeoutExpired(
            argv, kw.get("timeout"),
            output=json.dumps({"metric": "m", "value": 121.9}) + "\n",
            stderr=b"[bench + 360.0s] A/B sketch done\n")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    got = bench._run_child(600.0)
    assert got["metric"] == "m" and got["value"] == 121.9
    # The truncation is marked: a scavenged attempt must not read as a
    # clean run whose extras were merely disabled.
    assert "hung >600s" in got["attempt_note"]


def test_run_child_reports_hang(monkeypatch):
    def fake_run(argv, **kw):
        raise bench.subprocess.TimeoutExpired(
            argv, kw.get("timeout"), output="",
            stderr=b"[bench +  10.0s] first window\n")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    got = bench._run_child(7.0)
    assert isinstance(got, str)
    assert "hung >7s" in got
    assert "first window" in got  # last progress line surfaced


def _run_bench(env_overrides, drop=()):
    import os
    import subprocess

    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(bench.__file__),
                                      "bench.py")],
        capture_output=True, text=True, timeout=300, env=env)


def test_child_refuses_a_quiet_cpu_landing():
    """No accelerator and no explicit cpu pin: the measurement child
    exits non-zero, names the platform it found and prints NO result —
    JAX itself only logs the XLA:CPU landing at INFO and carries on."""
    r = _run_bench({"PARCA_BENCH_CHILD": "1"}, drop=("JAX_PLATFORMS",))
    assert r.returncode == 2, r.stderr[-500:]
    assert bench._scan_json_line(r.stdout) is None
    assert "no accelerator" in r.stderr and "'cpu'" in r.stderr


def test_parent_fails_when_the_measurement_fails(monkeypatch, capsys):
    """A run that produced no measurement exits non-zero with no result
    line (it used to print an XLA:CPU or numpy timing under the device
    metric's name and exit 0)."""
    monkeypatch.setattr(bench, "_make_snapshot", lambda rows, pids: None)
    monkeypatch.setattr(bench, "_run_child",
                        lambda timeout_s, extra_env=None: "rc=2: no chip")
    monkeypatch.delenv("PARCA_BENCH_CHILD", raising=False)
    assert bench.main() == 1
    out = capsys.readouterr()
    assert out.out == "" and "no chip" in out.err


def test_parent_passes_the_childs_line_through_and_stays_off_jax():
    """The supervising parent prints what the child measured and never
    imports jax itself: the child is the one process that holds the
    chip (checked in a fresh interpreter — this one has jax loaded)."""
    import os
    import subprocess

    code = (
        "import importlib.util, json, sys\n"
        "spec = importlib.util.spec_from_file_location('bench', 'bench.py')\n"
        "b = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(b)\n"
        "b._make_snapshot = lambda rows, pids: None\n"
        "b._run_child = lambda t, e=None: {'metric': 'steady_window_ms',"
        " 'platform': 'tpu'}\n"
        "rc = b.main()\n"
        "assert rc == 0 and 'jax' not in sys.modules, sorted(sys.modules)\n"
    )
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PARCA_BENCH_")}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env,
                       cwd=os.path.dirname(bench.__file__))
    assert r.returncode == 0, r.stderr[-800:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == {
        "metric": "steady_window_ms", "platform": "tpu"}


def test_cpu_functional_run_carries_no_device_metric_or_ratio():
    """An explicit JAX_PLATFORMS=cpu run is labelled for what it is: its
    own metric name, no vs_baseline, every host-clock reading nested
    under a key that says XLA:CPU."""
    r = bench._as_cpu_functional({
        "metric": "steady_window_ms", "value": 5.3, "unit": "ms",
        "vs_baseline": 180.4, "vs_baseline_sync": 12.0, "backend": "cpu",
        "rows": 1 << 17, "pids": 10_000, "window_to_pprof_ms": 41.4})
    assert r["metric"] == "cpu_functional_run" and r["value"] is None
    assert r["platform"] == "cpu"
    assert not any("vs_baseline" in k for k in r)
    nested = r["xla_cpu_host_clock"]
    assert not any("vs_baseline" in k for k in nested)
    assert nested["close_median_ms"] == 5.3
    assert nested["window_to_pprof_ms"] == 41.4
    assert "window_to_pprof_ms" not in r and "steady_window_ms" not in r


def test_finalize_result_scoring_fields():
    """scored/scale are stamped mechanically, by the process that
    measured, with the identity of the backend it ran on."""
    # The real thing: full scale, device backend, no error.
    r = {"rows": 1 << 20, "pids": 50_000, "backend": "tpu",
         "vs_baseline": 25.0}
    bench._finalize_result(r)
    assert r["scored"] is True and r["scale"] == "full"
    # The stamp names THIS process's backend (cpu under the test pin).
    assert r["env"]["platform"] == "cpu" and r["env"]["device_count"] >= 1
    assert r["env"]["jax_version"] != "unknown"

    # A CPU run at reduced scale: unscored, marked.
    r = {"rows": 1 << 17, "pids": 10_000, "backend": "cpu"}
    bench._finalize_result(r)
    assert r["scored"] is False and r["scale"] == "reduced"

    # Device backend but error field set (e.g. a phase died): unscored.
    r = {"rows": 1 << 20, "pids": 50_000, "backend": "tpu",
         "error": "pprof phase died"}
    bench._finalize_result(r)
    assert r["scored"] is False and r["scale"] == "full"

    # A sub-phase with its own bars: scale/backend requirements relaxed.
    r = {"backend": "cpu"}
    bench._finalize_result(r, require_full_scale=False,
                           require_device=False)
    assert r["scored"] is True and "scale" not in r
