"""chip_smoke.py: the verdict logic on canned scrapes, the no-accelerator
contract, and a tiny XLA:CPU rehearsal of the whole script (which must
never print an on-chip pass). The real run needs the chip."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load()

SAMPLES = 5_000_000


def _metrics(platform="tpu", kind="TPU v5 lite", kernel_fallback=0,
             h2d=67108864, d2h=5366872, errors=0,
             streamed=None):
    lines = [
        "# TYPE parca_agent_device_info gauge",
        f'parca_agent_device_info{{device_count="1",device_kind="{kind}",'
        f'hostname="a\\"b",platform="{platform}"}} 1',
        f'parca_agent_profiler_errors_total{{profiler="cpu"}} {errors}',
        f'parca_agent_kernel_fallback{{kernel="feed_probe"}} '
        f'{kernel_fallback}',
        'parca_agent_kernel_fallback{kernel="device"} 0',
        'parca_agent_kernel_compiles_total{kernel="feed_probe"} 2',
        'parca_agent_kernel_recompiles_total{kernel="feed_probe"} 1',
        'parca_agent_kernel_duration_seconds_sum{kernel="feed_probe",'
        'event="compile"} 1.5',
        'parca_agent_kernel_duration_seconds_sum{kernel="feed_probe",'
        'event="execute"} 0.25',
        f'parca_agent_transfer_bytes_total{{kernel="feed_probe",'
        f'direction="h2d"}} {h2d}',
        f'parca_agent_transfer_bytes_total{{kernel="close_fetch",'
        f'direction="d2h"}} {d2h}',
        "parca_agent_xla_cache_hits_total 7",
        "parca_agent_xla_backend_compile_seconds_total 0.25",
    ]
    if streamed is not None:
        lines += [f"parca_agent_streaming_windows_streamed_total {streamed}",
                  "parca_agent_streaming_disabled 0",
                  'parca_agent_feed_carry_fallbacks_total{profiler="cpu"} 0']
    return "\n".join(lines) + "\n"


def _healthz(state="healthy", **bad):
    stats = {k: 0 for k in smoke.HEALTH_COUNTERS}
    stats.update(bad)
    return {"status": "healthy",
            "device": {"state": state, "last_error": "", "stats": stats}}


def _windows(paths=("pipeline", "scalar-backpressure", "pipeline",
                    "scalar-backpressure", "pipeline"), reason=None):
    traces = []
    for i, path in enumerate(paths, 1):
        meta = {"path": path, "samples": SAMPLES}
        if reason and path == "scalar-fallback":
            meta["fallback_reason"] = reason
        traces.append({"seq": i, "complete": True, "duration_s": 2.0,
                       "spans": [{"stage": "close", "duration_s": 0.5},
                                 {"stage": "ship", "duration_s": 1.0}],
                       "meta": meta})
    # A linger window and an incomplete trace: neither is a real window.
    traces.append({"seq": 98, "complete": True, "duration_s": 0.1,
                   "spans": [], "meta": {"path": "pipeline", "samples": 1}})
    traces.append({"seq": 99, "complete": False, "spans": [],
                   "meta": {"samples": SAMPLES}})
    return {"traces": traces}


def _replay(metrics=None, healthz=None, windows=None):
    return smoke.judge_replay(
        metrics if metrics is not None else _metrics(),
        healthz if healthz is not None else _healthz(),
        windows if windows is not None else _windows(),
        "tpu", "v5", 5, SAMPLES)


def test_clean_scrape_passes():
    assert _replay() == []


@pytest.mark.parametrize("why,kw,needle", [
    ("landed on the CPU", {"metrics": _metrics(platform="cpu", kind="cpu")},
     "platform is 'cpu'"),
    ("not a v5e", {"metrics": _metrics(kind="TPU v4")}, "device_kind"),
    ("a hang", {"healthz": _healthz(hangs_total=1)}, "hangs_total = 1"),
    ("a demotion", {"healthz": _healthz(demotions_total=1)},
     "demotions_total"),
    ("a fallback window", {"healthz": _healthz(fallback_windows_total=2)},
     "fallback_windows_total = 2"),
    ("degraded at the end", {"healthz": _healthz(state="degraded")},
     "device state 'degraded'"),
    ("a kernel fell back", {"metrics": _metrics(kernel_fallback=1)},
     "kernel feed_probe fell back"),
    ("nothing went up", {"metrics": _metrics(h2d=0)}, "no h2d transfer"),
    ("nothing came back", {"metrics": _metrics(d2h=0)}, "no d2h transfer"),
    ("an iteration error", {"metrics": _metrics(errors=1)},
     "errors_total = 1"),
    ("scalar fallback for a device reason",
     {"windows": _windows(("pipeline", "scalar-fallback", "pipeline",
                           "pipeline", "pipeline"), reason="device")},
     "device reason"),
    ("last window took backpressure",
     {"windows": _windows(("pipeline",) * 4 + ("scalar-backpressure",))},
     "not the fast encoder"),
    ("a window missing", {"windows": _windows(("pipeline",) * 4)},
     "4 of 5"),
    ("never learned its backend",
     {"metrics": _metrics().replace("parca_agent_device_info", "x_info")},
     "never learned"),
])
def test_each_defect_fails(why, kw, needle):
    fails = _replay(**kw)
    assert any(needle in f for f in fails), (why, fails)


def test_encode_reason_scalar_fallback_is_not_a_device_failure():
    w = _windows(("pipeline", "scalar-fallback", "pipeline", "pipeline",
                  "pipeline"), reason="encode")
    assert _replay(windows=w) == []


def test_replay_is_done_at_the_first_fast_last_window_or_the_spare():
    def done(paths):
        return smoke.replay_done(_windows(paths), 3, SAMPLES)

    assert not done(("pipeline", "scalar-backpressure"))
    assert done(("pipeline", "scalar-backpressure", "pipeline"))
    # Third replay met a busy encode worker: wait for the spare...
    assert not done(("pipeline",) + ("scalar-backpressure",) * 2)
    # ...which decides either way (judge_replay then passes or fails it).
    assert done(("pipeline",) + ("scalar-backpressure",) * 2
                + ("pipeline",))
    assert done(("pipeline",) + ("scalar-backpressure",) * 3)
    # Only replays of the real window count, in the agent's order.
    real = smoke.real_windows(_windows(), SAMPLES)
    assert [r["seq"] for r in real] == [1, 2, 3, 4, 5]


def test_live_checks_need_a_streamed_window():
    ok = smoke.judge_live(_metrics(streamed=3), _healthz(), _windows(),
                          "tpu", "v5")
    assert ok == []
    none = smoke.judge_live(_metrics(streamed=0), _healthz(), _windows(),
                            "tpu", "v5")
    assert any("no window streamed" in f for f in none)
    silent = smoke.judge_live(_metrics(), _healthz(), _windows(),
                              "tpu", "v5")
    assert any("feeder is disabled" in f for f in silent)


def test_mass_check_is_exact_per_pid():
    want = {1: 10, 2: 20, 3: 30}
    assert smoke.judge_mass(want, dict(want), 60) == []
    off = smoke.judge_mass(want, {1: 10, 2: 21, 3: 30}, 60)  # off by one
    assert any("1 of 3 pids differ" in f for f in off)
    assert any("written mass 61" in f for f in off)
    moved = smoke.judge_mass(want, {1: 11, 2: 19, 3: 30}, 60)
    assert any("2 of 3 pids differ" in f for f in moved)  # mass alone: ok
    assert smoke.judge_mass(want, {1: 10, 2: 20}, 60)     # a pid missing
    assert smoke.judge_mass(want, {**want, 4: 0}, 60)     # a pid too many


def test_metrics_parser_and_tables():
    m = smoke.parse_metrics(_metrics())
    info = [lab for n, lab, _ in m if n == "parca_agent_device_info"][0]
    assert info["hostname"] == 'a\\"b' and info["platform"] == "tpu"
    assert smoke.kernel_table(m) == {"feed_probe": {
        "compiles": 2, "recompiles": 1, "first_call_s": 1.5}}
    assert smoke.xla_table(m) == {"cache_hits_total": 7.0,
                                  "backend_compile_seconds_total": 0.25}
    rows = smoke.window_rows(_windows())
    assert [r["seq"] for r in rows] == [1, 2, 3, 4, 5, 98]
    assert rows[0]["close_s"] == 0.5 and rows[0]["ship_s"] == 1.0


def test_nth_window_files_picks_in_write_order(tmp_path):
    for pid, stamps in ((7, (300, 100, 200)), (8, (150, 50))):
        for s in stamps:
            (tmp_path / f"node=n_pid={pid}.{s}.pb.gz").write_bytes(b"")
    (tmp_path / "node=n_pid=9.1.pb.gz.tmp").write_bytes(b"")
    picked, fails = smoke.nth_window_files(str(tmp_path), [7, 8], 3)
    assert picked == {7: str(tmp_path / "node=n_pid=7.300.pb.gz")}
    assert any("pid 8: 2" in f for f in fails)
    picked, fails = smoke.nth_window_files(str(tmp_path), [7, 8], 2)
    assert not fails
    assert picked[7].endswith("pid=7.200.pb.gz")
    assert picked[8].endswith("pid=8.150.pb.gz")


def test_verdict_line_has_exactly_the_contract_keys():
    """The last stdout line: "ok" and "device" {platform, kind, count}
    as JAX reports them, whatever else the summary carries."""
    summary = {"ok": True, "claim": None, "replay": {"verdict": "pass"},
               "device": {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1}}
    line = smoke.verdict_line(summary)
    assert "\n" not in line
    got = json.loads(line)
    assert got == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert list(got) == ["ok", "device"]
    assert list(got["device"]) == ["platform", "kind", "count"]
    assert json.loads(smoke.verdict_line(dict(summary, ok=False)))["ok"] \
        is False


def _run(args, cwd=REPO, script=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # inherited pin: must be overridden
    return subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py"),
         *args], capture_output=True, text=True, timeout=600, cwd=cwd,
        env=env)


def test_no_accelerator_exits_nonzero_prints_no_result(tmp_path):
    """Here there is no chip: the script must fail, print no result
    line, and name the platform JAX does find — even though the
    environment says JAX_PLATFORMS=cpu, which its children do not obey."""
    r = _run(["--out", str(tmp_path / "out")])
    assert r.returncode == smoke.EXIT_NO_ACCELERATOR, r.stderr[-800:]
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr and "'cpu'" in r.stderr


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run([], cwd=str(tmp_path),
             script=str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "not beside this script" in r.stderr


def test_cpu_rehearsal_runs_every_check_and_can_never_pass(tmp_path):
    """The whole script at a tiny size on XLA:CPU, driven from a fresh
    interpreter that also proves the parent stays off jax: phases run,
    the verdict logic sees real scrapes, the per-pid comparison against
    aggregator/cpu.py is exact — and the result is still ok:false,
    exit 3, platform cpu: a rehearsal is never an on-chip pass."""
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('chip_smoke', "
        f"{os.path.join(REPO, 'chip_smoke.py')!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        f"rc = m.main(['--rehearse-cpu', '--skip-live', '--out', "
        f"{str(tmp_path / 'out')!r}])\n"
        "assert 'jax' not in sys.modules, 'the parent imported jax'\n"
        "sys.exit(rc)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == smoke.EXIT_REHEARSAL, r.stderr[-1500:]
    lines = r.stdout.strip().splitlines()
    # The last line is the verdict and nothing else; the summary is the
    # line before it.
    assert json.loads(lines[-1]) == {
        "ok": False, "device": json.loads(lines[-2])["device"]}
    got = json.loads(lines[-2])
    assert got["ok"] is False and got["claim"] is None
    assert got["device"]["platform"] == "cpu"
    assert got["rehearsal"] == "pass", got["failures"]
    assert got["replay"]["verdict"] == "pass"
    assert got["replay"]["verify"]["files_parsed"] == got["size"]["pids"]
    # Tiny windows never meet a busy encode worker: the third replay is
    # the last, and the spare after the settle gap is never needed.
    assert [w["path"] for w in got["replay"]["windows"]] \
        == ["pipeline"] * got["size"]["windows"]
    assert all(w["samples"] == got["size"]["samples"]
               for w in got["replay"]["windows"])
    assert got["replay"]["xla"]["compile_requests_total"] > 0
    # What the agent served was kept for the reader.
    kept = os.listdir(tmp_path / "out")
    for name in ("summary.json", "replay.log", "replay.metrics.txt",
                 "replay.healthz.txt", "replay.debug_device.txt",
                 "replay.debug_windows.txt"):
        assert name in kept
