#!/usr/bin/env python3
"""chip_smoke.py — prove the agent's main path runs on the accelerator.

Drives ``python -m parca_agent_tpu`` (cli.py), the entry point a user
calls, on the chip this machine holds, at the repo's flagship deployment
(BASELINE.json configs[3]: 50,000 pids, 1,048,576 unique stacks in
128-slot rows, mean depth 24, 20% kernel tails, 5,000,000 samples per
10 s window, a 4,194,304-slot device dictionary), and checks what came
out against the plain reference (aggregator/cpu.py on the same seeded
window). It times nothing for anyone's benefit: every number it prints
is information, stamped with the platform it came from.

One process per chip. This parent never imports JAX (stdlib, numpy and
the numpy-only modules of the package). Each phase is ONE child process
that owns the chip; phases run one after another and a child is reaped
before the next starts. Children get an explicit accelerator platform in
their environment — an inherited ``JAX_PLATFORMS=cpu`` is overridden,
not obeyed — so a chip that fails to initialise is an error in the
child, never a quiet XLA:CPU run.

  step 0   rebuild parca_agent_tpu/native/*.so from the tracked sources
  device   a child asks JAX what is there (fails fast with no chip)
  step 1   generate the window from --seed (numpy, in this process)
  replay   agent: --capture replay <W>x3 back to back (+ one spare)
           --aggregator dict --aggregator-capacity 4194304
           --fast-encode, 10 s cadence; scraped over HTTP while it runs,
           judged, and the last window's written pprofs compared per pid
           with the reference
  live     agent: --capture perf --aggregator dict+cm --fast-encode
           --streaming-window over a small CPU burner (not_run, not a
           failure, where perf_event_open is refused)

Stdout carries two JSON lines. The first is the summary (size, phases,
windows, kernels, cache, failures, ``"claim": null``; also written to
``summary.json``). The LAST is the verdict, exactly ``{"ok": true,
"device": {"platform": "tpu", "kind": ..., "count": ...}}`` with the
device as JAX reports it, and nothing else. No accelerator (or no
program beside this file): nothing on stdout, exit code 2. Any failed
check or child: ``"ok": false`` and exit code 1.

``--rehearse-cpu`` runs the same phases at a tiny size on XLA:CPU so
the script's own logic can be debugged without a chip. A rehearsal can
never pass: it prints ``"ok": false`` and exits 3 whatever it found.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

# BASELINE.json configs[3]. Row width (128 slots), table capacity and
# the 10 s cadence are never cut; rows and pids may be, in proportion,
# and the cut is printed under "reduced".
FULL = {"pids": 50_000, "rows": 1 << 20, "samples": 5_000_000,
        "mean_depth": 24, "kernel_fraction": 0.2,
        "capacity": 4_194_304, "windows": 3, "cadence_s": 10.0}
REHEARSAL = {"pids": 64, "rows": 2048, "samples": 20_000,
             "mean_depth": 24, "kernel_fraction": 0.2,
             "capacity": 1 << 14, "windows": 3, "cadence_s": 1.0}

EXIT_FAILED, EXIT_NO_ACCELERATOR, EXIT_REHEARSAL = 1, 2, 3
DEADLINE_S = 1150.0  # the contract allows 1200 s, compilation included

SETTLE_GAP = 3  # linger windows before the spare replay

HEALTH_COUNTERS = ("hangs_total", "demotions_total",
                   "dispatch_errors_total", "fallback_windows_total",
                   "shadow_windows_total")
ENDPOINTS = ("metrics", "healthz", "debug/device", "debug/windows")

# The device as JAX reports it, by the agent's own identity record.
_DEVICE_CODE = (
    "import json\n"
    "from parca_agent_tpu.runtime.device_telemetry import collect_identity\n"
    "print(json.dumps(collect_identity()))\n"
)


def say(msg: str) -> None:
    """Progress goes to stderr; stdout carries the two JSON lines."""
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


# -- reading what the agent serves -------------------------------------------

_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> list[tuple[str, dict, float]]:
    """Prometheus text exposition -> [(name, labels, value)]."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if m is None:
            continue
        labels = dict(_LABEL.findall(m.group(2) or ""))
        try:
            out.append((m.group(1), labels, float(m.group(3))))
        except ValueError:
            continue
    return out


def _samples(metrics, name: str) -> list[tuple[dict, float]]:
    return [(lab, v) for n, lab, v in metrics if n == name]


def window_rows(windows: dict) -> list[dict]:
    """/debug/windows -> one row per completed window trace: seq, path,
    samples and the close/encode/ship seconds."""
    rows = []
    for t in windows.get("traces", []):
        if not t.get("complete"):
            continue
        spans: dict[str, float] = {}
        for s in t.get("spans", []):
            spans[s["stage"]] = spans.get(s["stage"], 0.0) + s["duration_s"]
        meta = t.get("meta", {})
        rows.append({
            "seq": t["seq"], "path": meta.get("path"),
            "fallback_reason": meta.get("fallback_reason"),
            "samples": meta.get("samples"),
            "close_s": spans.get("close"), "encode_s": spans.get("encode"),
            "ship_s": spans.get("ship"), "total_s": t.get("duration_s"),
            "error": t.get("error") or meta.get("iteration_error"),
        })
    return rows


def kernel_table(metrics) -> dict:
    """Compiles and recompiles per kernel as the agent's device
    telemetry counts them (new shape signatures), with the host-clock
    seconds of those first calls — XLA's compile plus whatever else the
    call wraps (for miss_settle, the host-side insert)."""
    out: dict[str, dict] = {}
    for lab, v in _samples(metrics, "parca_agent_kernel_compiles_total"):
        out.setdefault(lab["kernel"], {})["compiles"] = int(v)
    for lab, v in _samples(metrics, "parca_agent_kernel_recompiles_total"):
        out.setdefault(lab["kernel"], {})["recompiles"] = int(v)
    for lab, v in _samples(metrics,
                           "parca_agent_kernel_duration_seconds_sum"):
        if lab.get("event") == "compile":
            out.setdefault(lab["kernel"], {})["first_call_s"] = round(v, 3)
    return out


def xla_table(metrics) -> dict:
    """XLA's own account: compile requests, persistent-cache hits and
    misses, and the seconds spent in the compiler (or in loading a
    cached binary)."""
    return {n[len("parca_agent_xla_"):]: v for n, _lab, v in metrics
            if n.startswith("parca_agent_xla_")}


def judge_device(metrics_text: str, healthz: dict, windows: dict,
                 platform: str, want_kind: str | None) -> list[str]:
    """The checks every phase must pass: the agent ran on `platform`,
    stayed healthy, hid nothing behind a fallback and really moved the
    window through the device. Returns the failures (empty = clean)."""
    fails: list[str] = []
    metrics = parse_metrics(metrics_text)

    info = _samples(metrics, "parca_agent_device_info")
    if not info:
        fails.append("no parca_agent_device_info: the agent never learned "
                     "its backend")
    else:
        lab = info[0][0]
        if lab.get("platform") != platform:
            fails.append(f"agent platform is {lab.get('platform')!r}, "
                         f"want {platform!r}")
        if want_kind and want_kind not in lab.get("device_kind", "").lower():
            fails.append(f"device_kind {lab.get('device_kind')!r} is not "
                         f"a {want_kind} part")

    dev = healthz.get("device")
    if not dev:
        fails.append("/healthz has no device section")
    else:
        if dev.get("state") != "healthy":
            fails.append(f"device state {dev.get('state')!r} at the end "
                         f"({dev.get('last_error')!r})")
        for k in HEALTH_COUNTERS:
            if dev.get("stats", {}).get(k, -1) != 0:
                fails.append(f"device {k} = {dev.get('stats', {}).get(k)}")

    for lab, v in _samples(metrics, "parca_agent_profiler_errors_total"):
        if v:
            fails.append(f"profiler errors_total = {int(v)}")
    for row in window_rows(windows):
        if row["path"] == "scalar-fallback" \
                and row["fallback_reason"] != "encode":
            fails.append(f"window {row['seq']} shipped through the scalar "
                         "fallback for a device reason")
        if row["error"]:
            fails.append(f"window {row['seq']} error: {row['error']}")
    for lab, v in _samples(metrics, "parca_agent_kernel_fallback"):
        if v:
            fails.append(f"kernel {lab.get('kernel')} fell back")
    moved = {"h2d": 0.0, "d2h": 0.0}
    for lab, v in _samples(metrics, "parca_agent_transfer_bytes_total"):
        if lab.get("direction") in moved:
            moved[lab["direction"]] += v
    for direction, n in moved.items():
        if n <= 0:
            fails.append(f"no {direction} transfer bytes: the device never "
                         "moved the window")
    return fails


def real_windows(windows: dict, window_samples: int) -> list[dict]:
    """The completed replays of the real window (linger windows and
    incomplete traces left out), in the order the agent took them."""
    return sorted((r for r in window_rows(windows)
                   if r["samples"] == window_samples),
                  key=lambda r: r["seq"])


def replay_done(windows: dict, n_windows: int, window_samples: int) -> bool:
    """`n_windows` back to back, the last through the fast encoder; if
    it was not, the spare replay after the settle gap decides."""
    real = real_windows(windows, window_samples)
    return len(real) > n_windows or (
        len(real) == n_windows
        and real[-1]["path"] in ("pipeline", "inline"))


def judge_replay(metrics_text: str, healthz: dict, windows: dict,
                 platform: str, want_kind: str | None, n_windows: int,
                 window_samples: int) -> list[str]:
    """The device checks, at least `n_windows` replays of the real
    window, and the last of them shipped through the fast encoder
    (earlier ones may take the encode-backpressure path: counted and
    printed, not failed)."""
    fails = judge_device(metrics_text, healthz, windows, platform, want_kind)
    real = real_windows(windows, window_samples)
    if len(real) < n_windows:
        fails.append(f"{len(real)} of {n_windows} replayed windows "
                     "completed")
    elif real[-1]["path"] not in ("pipeline", "inline"):
        fails.append(f"the last window shipped through "
                     f"{real[-1]['path']!r}, not the fast encoder")
    return fails


def judge_live(metrics_text: str, healthz: dict, windows: dict,
               platform: str, want_kind: str | None) -> list[str]:
    fails = judge_device(metrics_text, healthz, windows, platform, want_kind)
    metrics = parse_metrics(metrics_text)

    def one(name, default):
        got = _samples(metrics, name)
        return got[0][1] if got else default

    if one("parca_agent_streaming_windows_streamed_total", 0) < 1:
        fails.append("no window streamed")
    if one("parca_agent_streaming_disabled", 1) != 0:
        fails.append("the streaming feeder is disabled")
    if one("parca_agent_feed_carry_fallbacks_total", 1) != 0:
        fails.append("carry_fallbacks != 0")
    return fails


def judge_mass(want: dict[int, int], got: dict[int, int],
               total_samples: int) -> list[str]:
    """Per-pid sample totals of the written pprofs against the plain
    reference, for every pid, and the window's total mass."""
    fails = []
    if sum(want.values()) != total_samples:
        fails.append(f"reference mass {sum(want.values())} != snapshot "
                     f"total {total_samples}")
    if sum(got.values()) != total_samples:
        fails.append(f"written mass {sum(got.values())} != snapshot total "
                     f"{total_samples}")
    bad = [p for p in want if got.get(p) != want[p]]
    bad += [p for p in got if p not in want]
    if bad:
        p = bad[0]
        fails.append(f"{len(bad)} of {len(want)} pids differ from the "
                     f"reference (pid {p}: wrote {got.get(p)}, reference "
                     f"{want.get(p)})")
    return fails


# -- the written profiles ----------------------------------------------------

_PID_IN_NAME = re.compile(r"(?:^|_)pid=(\d+)(?:_|\.)")


def nth_window_files(directory: str, pids, n: int) -> tuple[dict, list]:
    """{pid: path of the pid's n-th written profile (1-based, in write
    order)} for the given pids, plus complaints about pids that did not
    get exactly n files' worth of windows."""
    by_pid: dict[int, list] = {}
    for name in os.listdir(directory):
        if not name.endswith(".pb.gz"):
            continue
        m = _PID_IN_NAME.search(name)
        if m is None:
            continue
        stamp = name[:-len(".pb.gz")].rsplit(".", 1)[-1]
        by_pid.setdefault(int(m.group(1)), []).append((int(stamp), name))
    picked, fails = {}, []
    short = [p for p in pids if len(by_pid.get(p, ())) < n]
    if short:
        fails.append(f"{len(short)} pids have fewer than {n} written "
                     f"profiles (pid {short[0]}: "
                     f"{len(by_pid.get(short[0], ()))})")
    for p in pids:
        files = sorted(by_pid.get(p, ()))
        if len(files) >= n:
            picked[p] = os.path.join(directory, files[n - 1][1])
    return picked, fails


def _file_totals(items):
    """[(pid, path)] -> [(pid, sum of sample values)] via the repo's own
    pprof parser. Module-level: runs in spawn-ed pool workers."""
    sys.path.insert(0, REPO)
    from parca_agent_tpu.pprof.builder import parse_pprof

    out = []
    for pid, path in items:
        with open(path, "rb") as f:
            prof = parse_pprof(gzip.decompress(f.read()))
        out.append((pid, sum(v[0] for _, v, _ in prof.samples)))
    return out


def written_totals(picked: dict[int, str]) -> dict[int, int]:
    items = sorted(picked.items())
    if len(items) < 4096:
        return dict(_file_totals(items))
    import multiprocessing

    n = min(8, os.cpu_count() or 1)
    chunks = [items[i::n * 8] for i in range(n * 8)]
    with multiprocessing.get_context("spawn").Pool(n) as pool:
        return {p: t for part in pool.map(_file_totals, chunks)
                for p, t in part}


# -- children ----------------------------------------------------------------

class Children:
    """Every process this script starts, so that it stops every one."""

    def __init__(self):
        self.live: list[subprocess.Popen] = []

    def spawn(self, argv, env, log_path=None) -> subprocess.Popen:
        log = open(log_path, "wb") if log_path else subprocess.DEVNULL
        try:
            p = subprocess.Popen(argv, env=env, stdout=log,
                                 stderr=subprocess.STDOUT,
                                 start_new_session=True, cwd=REPO)
        finally:
            if log_path:
                log.close()
        self.live.append(p)
        return p

    def reap(self, p: subprocess.Popen, grace_s: float = 30.0) -> int:
        """Stop one child's whole process group and wait for it."""
        if p.poll() is None:
            self._signal(p, signal.SIGTERM)
            try:
                p.wait(grace_s)
            except subprocess.TimeoutExpired:
                pass
        self._signal(p, signal.SIGKILL)  # stragglers in its group
        rc = p.wait()
        if p in self.live:
            self.live.remove(p)
        return rc

    @staticmethod
    def _signal(p: subprocess.Popen, sig) -> None:
        try:
            os.killpg(p.pid, sig)
        except (ProcessLookupError, PermissionError):
            pass

    def reap_all(self) -> None:
        for p in list(self.live):
            self.reap(p, grace_s=5.0)


def child_env(platform: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform  # overrides an inherited cpu pin
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def ask_device(platform: str) -> tuple[dict | None, str]:
    """What does JAX find when told to use `platform`? One child."""
    r = subprocess.run([sys.executable, "-c", _DEVICE_CODE],
                       env=child_env(platform), capture_output=True,
                       text=True, timeout=300, cwd=REPO)
    if r.returncode == 0:
        try:
            return json.loads(r.stdout.strip().splitlines()[-1]), ""
        except (ValueError, IndexError):
            pass
    tail = (r.stderr or r.stdout or "no output").strip().splitlines()
    return None, tail[-1][-300:] if tail else "no output"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def scrape(port: int) -> dict | None:
    """One pass over the agent's endpoints; None unless all answered."""
    got = {}
    for ep in ENDPOINTS:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/{ep}", timeout=10) as r:
                got[ep] = r.read().decode()
        except (OSError, ValueError):
            return None
    return got


def run_agent(children: Children, name: str, argv: list, platform: str,
              out_dir: str, done, timeout_s: float) -> dict:
    """Run one agent child to the point `done(scrape)` says its work is
    finished, take the final scrape, stop it, and return what was seen.
    The scrape loop hits the endpoints from t=0, before the agent's HTTP
    server (or its backend) is up: nothing it serves may touch JAX."""
    port = free_port()
    log_path = os.path.join(out_dir, f"{name}.log")
    t0 = time.monotonic()
    p = children.spawn(
        [sys.executable, "-m", "parca_agent_tpu", *argv,
         "--http-address", f"127.0.0.1:{port}"],
        child_env(platform), log_path)
    seen: dict = {"name": name, "log": log_path, "scrapes": 0,
                  "first_scrape_s": None, "final": None,
                  "finished": False}
    try:
        while time.monotonic() - t0 < timeout_s and p.poll() is None:
            got = scrape(port)
            if got is not None:
                seen["scrapes"] += 1
                if seen["first_scrape_s"] is None:
                    seen["first_scrape_s"] = round(time.monotonic() - t0, 1)
                seen["final"] = got
                if done(got):
                    seen["finished"] = True
                    break
            time.sleep(0.5)
        seen["wall_s"] = round(time.monotonic() - t0, 1)
        if p.poll() is not None:
            seen["exit_code"] = p.returncode  # it left by itself: wrong
            seen["exited_early"] = True
        else:
            # SIGTERM is the agent's clean shutdown: exit code 0.
            children._signal(p, signal.SIGTERM)
            try:
                seen["exit_code"] = p.wait(120)
            except subprocess.TimeoutExpired:
                seen["exit_code"] = None
    finally:
        children.reap(p)
    final = seen["final"] or {}
    for ep, body in final.items():
        with open(os.path.join(
                out_dir, f"{name}.{ep.replace('/', '_')}.txt"), "w") as f:
            f.write(body)
    return seen


def _loads(final: dict | None, ep: str) -> dict:
    try:
        return json.loads((final or {}).get(ep) or "{}")
    except ValueError:
        return {}


def agent_failures(seen: dict) -> list[str]:
    fails = []
    if seen["final"] is None:
        fails.append("the agent never answered a scrape")
    if seen.get("exited_early"):
        fails.append(f"the agent exited by itself (code "
                     f"{seen['exit_code']}) before its work was seen done")
    elif not seen["finished"]:
        fails.append(f"not finished after {seen['wall_s']} s")
    if seen.get("exit_code") != 0:
        fails.append(f"agent exit code {seen.get('exit_code')}")
    return fails


def phase_report(seen: dict, window_samples: int | None = None) -> dict:
    """What a phase prints about itself, from its final scrape. With
    `window_samples`, only replays of the real window are listed and
    the linger windows are counted."""
    report = {"wall_s": seen["wall_s"], "scrapes": seen["scrapes"],
              "first_scrape_s": seen["first_scrape_s"]}
    final = seen["final"]
    if final is not None:
        metrics = parse_metrics(final["metrics"])
        windows = _loads(final, "debug/windows")
        rows = window_rows(windows)
        if window_samples is not None:
            report["linger_windows"] = len(rows) - len(
                real_windows(windows, window_samples))
            rows = real_windows(windows, window_samples)
        report["windows"] = rows
        report["backpressure_windows"] = [
            r["seq"] for r in rows if r["path"] == "scalar-backpressure"]
        report["kernels"] = kernel_table(metrics)
        report["xla"] = xla_table(metrics)
        report["transfer_bytes"] = {
            f"{lab['kernel']}.{lab['direction']}": int(v)
            for lab, v in _samples(metrics,
                                   "parca_agent_transfer_bytes_total")}
    return report


def verdict_line(summary: dict) -> str:
    """The last line of stdout: the verdict and the device as JAX
    reports it, exactly these keys. Everything else is the summary's."""
    dev = summary["device"]
    return json.dumps({
        "ok": bool(summary["ok"]),
        "device": {"platform": str(dev["platform"]),
                   "kind": str(dev["kind"]), "count": int(dev["count"])}})


def cache_entries(directory: str) -> int:
    try:
        return len(os.listdir(directory))
    except OSError:
        return 0


# -- phases ------------------------------------------------------------------

def phase_replay(children, snap_path, linger_path, work, out_dir, size,
                 platform, want_kind, total_samples) -> tuple[dict, list]:
    # The window, back to back at the cadence, `windows` times. At this
    # size the first replay pays the cold start, the second finds the
    # encode worker still busy with the first (cold statics, 50,000
    # files to ship) and takes the encode-backpressure path — counted
    # and printed — and the third ships through the fast encoder: that
    # one is compared with the reference. Should the third still meet a
    # busy worker, a spare replay follows a settle gap of one-row linger
    # windows (a pid outside the population) and decides instead. More
    # linger windows keep the agent (and its /metrics) up until the
    # final scrape; SIGTERM then stops it.
    n = size["windows"]
    profiles = os.path.join(work, "profiles")
    argv = ["--capture", "replay", "--replay",
            *([snap_path] * n), *([linger_path] * SETTLE_GAP),
            snap_path, *([linger_path] * 60),
            "--aggregator", "dict",
            "--aggregator-capacity", str(size["capacity"]),
            "--fast-encode", "--local-store-directory", profiles,
            "--profiling-duration", str(size["cadence_s"]),
            "--debuginfo-upload-disable", "--node", "chip-smoke"]

    def done(got) -> bool:
        return replay_done(_loads(got, "debug/windows"), n, total_samples)

    seen = run_agent(children, "replay", argv, platform, out_dir, done,
                     timeout_s=max(60.0, DEADLINE_S - 200.0
                                   - (time.monotonic() - _T0)))
    fails = agent_failures(seen)
    final = seen["final"]
    report = phase_report(seen, total_samples)
    if final is not None:
        fails += judge_replay(
            final["metrics"], _loads(final, "healthz"),
            _loads(final, "debug/windows"), platform, want_kind, n,
            total_samples)
        report["identity"] = _loads(final, "debug/device").get("identity")
    return report, fails


def phase_live(children, work, out_dir, platform, want_kind,
               cadence_s, n_windows=5) -> tuple[dict | str, list]:
    from parca_agent_tpu.capture.live import (
        PerfEventSampler,
        SamplerUnavailable,
    )

    try:
        PerfEventSampler(frequency_hz=100, window_s=cadence_s).close()
    except (SamplerUnavailable, OSError, RuntimeError) as e:
        return f"not_run: {e}", []

    burner = children.spawn(
        [sys.executable, "-c",
         "import time\nt = time.time()\n"
         "while time.time() - t < 900: sum(range(2000))\n"],
        dict(os.environ))
    argv = ["--capture", "perf", "--aggregator", "dict+cm",
            "--fast-encode", "--streaming-window",
            "--windows", str(n_windows + 6),
            "--profiling-duration", str(cadence_s),
            "--local-store-directory", os.path.join(work, "live-profiles"),
            "--debuginfo-upload-disable", "--node", "chip-smoke"]

    def done(got) -> bool:
        return len(window_rows(_loads(got, "debug/windows"))) >= n_windows

    try:
        seen = run_agent(children, "live", argv, platform, out_dir, done,
                         timeout_s=max(30.0, DEADLINE_S
                                       - (time.monotonic() - _T0)))
    finally:
        children.reap(burner, grace_s=2.0)
    fails = agent_failures(seen)
    final = seen["final"]
    report = phase_report(seen)
    if final is not None:
        fails += judge_live(final["metrics"], _loads(final, "healthz"),
                            _loads(final, "debug/windows"), platform,
                            want_kind)
        report["streaming"] = {
            ".".join([n[len("parca_agent_streaming_"):], *lab.values()]): v
            for n, lab, v in parse_metrics(final["metrics"])
            if n.startswith("parca_agent_streaming_")}
    return report, fails


# -- main --------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--rows", type=int, default=None,
                    help="cut the window's unique stacks (pids follow in "
                         "proportion); printed under 'reduced'")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "chip_smoke"))
    ap.add_argument("--skip-live", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny XLA:CPU run of the same phases; can never "
                         "pass (exit 3)")
    args = ap.parse_args(argv)

    rehearsal = args.rehearse_cpu
    platform = "cpu" if rehearsal else "tpu"
    want_kind = None if rehearsal else "v5"
    # A rehearsal is tiny unless --rows asks for a cut of the real size.
    size = dict(REHEARSAL if rehearsal and args.rows is None else FULL)
    reduced = None
    if args.rows is not None and args.rows != size["rows"]:
        frac = args.rows / size["rows"]
        reduced = {"rows": args.rows,
                   "pids": max(1, int(size["pids"] * frac)),
                   "samples": max(args.rows + 1,
                                  int(size["samples"] * frac)),
                   "of": {k: size[k] for k in ("rows", "pids", "samples")}}
        size.update({k: reduced[k] for k in ("rows", "pids", "samples")})

    sys.path.insert(0, REPO)
    try:
        import numpy as np  # noqa: F401

        from parca_agent_tpu.aggregator.cpu import CPUAggregator
        from parca_agent_tpu.capture.formats import save_snapshot
        from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
        from parca_agent_tpu.runtime import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the program is not beside this script: {e}",
              file=sys.stderr)
        return EXIT_NO_ACCELERATOR

    os.makedirs(args.out, exist_ok=True)
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    children = Children()
    fails: dict[str, list] = {}
    summary: dict = {"ok": False}
    try:
        # -- step 0: the native pieces, from the tracked sources ------------
        say("step 0: make -C parca_agent_tpu/native clean all")
        r = subprocess.run(
            ["make", "-C", os.path.join(REPO, "parca_agent_tpu", "native"),
             "clean", "all"], capture_output=True, text=True)
        with open(os.path.join(args.out, "native_build.log"), "w") as f:
            f.write(r.stdout + r.stderr)
        if r.returncode != 0:
            print("chip_smoke: native build failed:\n" + r.stderr[-2000:],
                  file=sys.stderr)
            return EXIT_FAILED

        # -- the device, as JAX reports it ----------------------------------
        say(f"device: asking JAX for platform {platform!r}")
        device, err = ask_device(platform)
        if device is None or device["platform"] != platform:
            found, _ = ask_device("")  # what IS here, for the message
            print(f"chip_smoke: no accelerator: JAX_PLATFORMS={platform} "
                  f"gave {err or device!r}; left to itself JAX finds "
                  f"platform {found['platform'] if found else 'nothing'!r}",
                  file=sys.stderr)
            return EXIT_NO_ACCELERATOR
        say(f"device: {device}")

        cache_dir = os.environ.get(compile_cache.ENV_VAR) \
            or compile_cache.default_dir()
        cache_before = cache_entries(cache_dir)

        # -- step 1: the window, from the seed ------------------------------
        say(f"step 1: generating the window (seed {args.seed}, "
            f"{size['rows']} rows, {size['pids']} pids)")
        t0 = time.monotonic()
        spec = SyntheticSpec(
            n_pids=size["pids"], n_unique_stacks=size["rows"],
            n_rows=size["rows"], total_samples=size["samples"],
            mean_depth=size["mean_depth"],
            kernel_fraction=size["kernel_fraction"], seed=args.seed)
        snap = generate(spec)
        snap_path = os.path.join(work, "window.snap")
        save_snapshot(snap, snap_path)
        # The linger window: one row, a pid outside the population.
        linger = generate(SyntheticSpec(
            n_pids=1, n_unique_stacks=1, n_rows=1, total_samples=1,
            mean_depth=4, kernel_fraction=0.0, seed=args.seed + 1))
        linger.pids[:] = int(snap.pids.max()) + 1
        linger.mappings.pids[:] = int(snap.pids.max()) + 1
        linger_path = os.path.join(work, "linger.snap")
        save_snapshot(linger, linger_path)
        total = int(snap.total_samples())
        gen_s = round(time.monotonic() - t0, 1)
        say(f"step 1: done in {gen_s} s ({total} samples)")

        # The plain reference runs beside the agent child (numpy only).
        reference: dict = {}

        def compute_reference():
            t = time.monotonic()
            reference["totals"] = {
                int(p.pid): int(p.total())
                for p in CPUAggregator().aggregate(snap)}
            reference["seconds"] = round(time.monotonic() - t, 1)

        ref_thread = threading.Thread(target=compute_reference,
                                      name="reference", daemon=True)
        ref_thread.start()

        # -- phase replay ---------------------------------------------------
        say("phase replay: starting the agent")
        replay, fails["replay"] = phase_replay(
            children, snap_path, linger_path, work, args.out, size,
            platform, want_kind, total)
        ref_thread.join(600)
        if "totals" not in reference:
            fails["replay"].append("the reference did not finish")
        else:
            say("phase replay: comparing the last window's pprofs with "
                "the reference")
            t0 = time.monotonic()
            picked, complaints = nth_window_files(
                os.path.join(work, "profiles"), sorted(reference["totals"]),
                len(replay.get("windows", ())))
            fails["replay"] += complaints
            got = written_totals(picked)
            fails["replay"] += judge_mass(reference["totals"], got, total)
            replay["verify"] = {
                "pids": len(reference["totals"]), "files_parsed": len(got),
                "reference_s": reference["seconds"],
                "parse_s": round(time.monotonic() - t0, 1)}
        replay["verdict"] = "pass" if not fails["replay"] else "fail"
        cache_after_replay = cache_entries(cache_dir)
        shutil.rmtree(os.path.join(work, "profiles"), ignore_errors=True)

        # -- phase live -----------------------------------------------------
        if args.skip_live:
            live = "not_run: --skip-live"
        else:
            say("phase live: starting the agent over a CPU burner")
            live, fails["live"] = phase_live(
                children, work, args.out, platform, want_kind,
                size["cadence_s"])
            if isinstance(live, dict):
                live["verdict"] = "pass" if not fails["live"] else "fail"

        failed = {k: v for k, v in fails.items() if v}
        summary = {
            "ok": not failed and not rehearsal,
            "device": {"platform": device["platform"],
                       "kind": device["device_kind"],
                       "count": device["device_count"]},
            "jax": device["jax_version"],
            "libtpu": device["libtpu_version"],
            "size": {k: size[k] for k in (
                "pids", "rows", "samples", "capacity", "windows",
                "cadence_s")},
            "reduced": reduced,
            "seed": args.seed, "generate_s": gen_s,
            "replay": replay, "live": live,
            # Hit = the replay agent found every program it asked for
            # already on disk (XLA's own count, see "xla" per phase).
            "cache": {"dir": cache_dir, "entries_before": cache_before,
                      "entries_after_replay": cache_after_replay,
                      "entries_after": cache_entries(cache_dir),
                      "hit": bool(
                          replay.get("xla", {}).get("cache_hits_total"))
                      and not replay["xla"].get("cache_misses_total")},
            "failures": failed,
            "wall_s": round(time.monotonic() - _T0, 1),
        }
        if rehearsal:
            summary["rehearsal"] = "pass" if not failed else "fail"
        summary["claim"] = None
    finally:
        children.reap_all()
        shutil.rmtree(work, ignore_errors=True)

    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    for phase, why in summary["failures"].items():
        for w in why:
            say(f"FAILED {phase}: {w}")
    print(json.dumps(summary), flush=True)
    print(verdict_line(summary), flush=True)
    if rehearsal:
        return EXIT_REHEARSAL
    return 0 if summary["ok"] else EXIT_FAILED


if __name__ == "__main__":
    sys.exit(main())
