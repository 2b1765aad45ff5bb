"""One run of one cell: the agent through ``cli.run()`` in this process.

Only the process that holds the chip can trace it, so the harness does
not start the agent as a child: the main thread calls
``parca_agent_tpu.cli.run(argv)`` (it installs signal handlers, which
only the main thread may) and a driver thread does the benchmark's own
work around it: waits for the device, lets the warm windows pass,
stamps the set-up, measures for ``--seconds``, optionally traces the
first measured windows, and ends the agent with SIGTERM to this pid.
The store is a child process that never imports JAX (``sink.py``).

The order of a run:

  main    build native/*.so if missing; generate the windows from the
          seed; write them as snapshot files under TMPDIR; start the sink
  driver  /healthz device healthy on the asked platform -> warm windows
          -> setup_s -> measured window -> SIGTERM
  main    cli.run() returns -> the sink's profiles against the plain
          reference -> the result line
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import json
import math
import os
import pickle
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from . import compare, generate, mixes, scrape, snapfile, trace_reduce
from .cell import BENCH_DIR, CHECKOUT, Cell, load_peaks

EXIT_FAILED, EXIT_NO_ACCELERATOR = 1, 2
BRINGUP_TIMEOUT_S, WARM_TIMEOUT_S = 240.0, 900.0
GOOD_PATHS = ("pipeline", "inline")
_WRITER_THREADS = 8


def say(msg: str) -> None:
    """Progress goes to stderr; stdout ends with the result line."""
    print(f"[bench +{time.monotonic() - _T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


_T0 = time.monotonic()


class RunFailed(Exception):
    """The run cannot give a result; carries the exit code."""

    def __init__(self, msg: str, code: int = EXIT_FAILED):
        super().__init__(msg)
        self.code = code


@dataclasses.dataclass
class Collected:
    """What a run collected, as the metric readers see it."""

    cell: Cell
    seconds: float
    period_s: float
    stamps: dict = dataclasses.field(default_factory=dict)
    cpu: dict = dataclasses.field(default_factory=dict)
    metrics0: scrape.Metrics | None = None
    metrics1: scrape.Metrics | None = None
    rows: list = dataclasses.field(default_factory=list)      # measured windows
    all_rows: list = dataclasses.field(default_factory=list)  # warm ones too
    windows_closed: int = 0
    trace: dict | None = None
    trace_windows: int = 0
    device: dict = dataclasses.field(default_factory=dict)
    peaks: dict | None = None


def ensure_program() -> None:
    """The program beside the benchmark, with its native libraries (the
    ``.so`` files are not committed; ``make`` builds them in place)."""
    pkg = os.path.join(CHECKOUT, "parca_agent_tpu")
    if not os.path.isfile(os.path.join(pkg, "cli.py")):
        raise RunFailed("no program beside the benchmark", EXIT_FAILED)
    native = os.path.join(pkg, "native")
    if not all(os.path.isfile(os.path.join(native, so))
               for so in ("libpasampler.so", "libpavecenc.so")):
        say("building native/*.so")
        subprocess.run(["make", "-C", native, "all"], check=True,
                       stdout=sys.stderr, stderr=sys.stderr)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Sink:
    """The sink child and its line protocol."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "lib", "sink.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.port = json.loads(self.proc.stdout.readline())["port"]

    def ask(self, **msg) -> dict:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        if not line:
            raise RunFailed("the sink died")
        return json.loads(line)

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write('{"cmd": "quit"}\n')
                self.proc.stdin.flush()
                self.proc.wait(timeout=20)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
        self.proc.wait()


def plan_windows(cell: Cell, seconds: float) -> int:
    """Windows to hand the agent: the warm ones at most, the measured
    window's (the loop never runs faster than the period) and a few
    spare, so that the replay source does not run dry."""
    rp = cell.config["replay"]
    return int(rp["warm_windows_max"]) + math.ceil(
        seconds / float(rp["period_s"])) + 6


def population_and_mix(cell: Cell, sizes: dict | None):
    return (generate.Population.from_config(cell.config, sizes),
            mixes.Mix(cell.traffic))


def write_windows(cell: Cell, seed: int, needed: int, directory: str,
                  sizes: dict | None = None) -> list[str]:
    """Generate the cell's windows from the seed and write each distinct
    one as a snapshot file; returns the file handed for each window."""
    pop, mix = population_and_mix(cell, sizes)
    seq = mix.sequence(pop, seed)
    n_files = mix.distinct_windows(needed)
    paths = [os.path.join(directory, f"w{i:05d}.snap") for i in range(n_files)]
    pending: list = []
    with concurrent.futures.ThreadPoolExecutor(_WRITER_THREADS) as pool:
        for path in paths:
            # The sequence may rewrite its rows in place next window.
            w = _own_copy(seq.next())
            pending.append(pool.submit(snapfile.write_snapshot, w, path))
            while len(pending) > _WRITER_THREADS + 2:
                pending.pop(0).result()
        for f in pending:
            f.result()
    return [paths[i] for i in mix.replay_order(needed)]


def _own_copy(w: generate.Window) -> generate.Window:
    return dataclasses.replace(
        w, pids=w.pids.copy(), counts=w.counts.copy(),
        stacks=w.stacks.copy(), user_len=w.user_len.copy(),
        kernel_len=w.kernel_len.copy())


def agent_argv(cell: Cell, files: list[str], store_port: int,
               http_port: int, sizes: dict | None = None) -> list[str]:
    """The agent's command line: the configuration's flags (data in its
    file) plus what only a run knows."""
    rp = cell.config["replay"]
    period = repr(float(rp["period_s"]))
    flush = repr(float(rp.get("flush_interval_s", rp["period_s"])))
    values = {**cell.config, **(sizes or {})}
    return [*(flag.format(**values) for flag in cell.config["agent_flags"]),
            "--replay", *files,
            "--profiling-duration", period,
            "--remote-store-batch-write-interval", flush,
            "--remote-store-address", f"127.0.0.1:{store_port}",
            "--http-address", f"127.0.0.1:{http_port}"]


class EdgeClock:
    """The harness's own clock at the program's span events.

    The latency the benchmark reports runs between two edges inside the
    agent (the last sample is in; the pprof bytes exist) that nothing
    outside it can see. The program tells when: its flight recorder
    records a span the moment the span ends (``WindowTrace.add_span``).
    The harness reads its own clock at that moment, so the program
    supplies the event and the benchmark the time; the durations the
    program writes into ``/debug/windows`` are for the per-layer metrics
    only. A stage that ran twice in a window keeps its last end.
    """

    def __init__(self):
        self.ended: dict[tuple[int, str], float] = {}

    def install(self) -> None:
        from parca_agent_tpu.runtime import trace

        sound, ended = trace.WindowTrace.add_span, self.ended

        def add_span(window_trace, stage, *args, **kwargs):
            ended[(window_trace.seq, stage)] = time.monotonic()
            return sound(window_trace, stage, *args, **kwargs)

        trace.WindowTrace.add_span = add_span

    def of_window(self, seq: int) -> dict[str, float]:
        return {stage: t for (s, stage), t in list(self.ended.items())
                if s == seq}


class Driver(threading.Thread):
    """The benchmark's own thread beside the agent's main thread."""

    def __init__(self, col: Collected, http_port: int, sink: Sink,
                 platform: str, trace: bool, trace_dir: str,
                 agent_gone: threading.Event, clock: EdgeClock):
        super().__init__(name="bench-driver", daemon=True)
        self.col, self.port, self.sink, self.clock = col, http_port, sink, clock
        self.platform, self.trace, self.trace_dir = platform, trace, trace_dir
        self.agent_gone = agent_gone
        self.error: RunFailed | None = None
        rp = col.cell.config["replay"]
        self.warm_max = int(rp["warm_windows_max"])
        self.n_trace = int(rp["trace_windows"])
        self.poll_s = min(0.02, col.period_s / 10)

    def run(self) -> None:
        try:
            self._run()
        except RunFailed as e:
            self.error = e
        except Exception as e:  # noqa: BLE001 - reported by the main thread
            self.error = RunFailed(f"driver thread: {e!r}")
        finally:
            os.kill(os.getpid(), signal.SIGTERM)

    # -- waiting -----------------------------------------------------------

    def _until(self, what: str, cond, timeout: float, poll: float):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.agent_gone.is_set():
                raise RunFailed(f"the agent ended while waiting for {what}")
            got = cond()
            if got:
                return got
            time.sleep(poll)
        raise RunFailed(f"timed out after {timeout:.0f}s waiting for {what}")

    def _completed(self) -> int | None:
        body = scrape.get_json(self.port, "debug/windows?limit=1")
        return None if body is None else int(
            body["stats"]["traces_completed"])

    def _next_completion(self, timeout: float) -> int:
        """Return right after the next window completes, with the count
        of windows completed so far."""
        first = self._completed()

        def moved():
            n = self._completed()
            return n if n is not None and first is not None and n > first \
                else None

        return self._until("a window to complete", moved, timeout,
                           self.poll_s)

    # -- the run -----------------------------------------------------------

    def _bring_up(self) -> None:
        def healthy():
            h = scrape.get_json(self.port, "healthz")
            dev = (h or {}).get("device")
            if not dev:
                return None
            if dev.get("state") == "healthy" and dev.get("platform"):
                return dev
            if dev.get("state") in ("degraded", "dead") \
                    and not dev.get("probe_in_flight"):
                raise RunFailed(
                    f"device {dev.get('state')}: {dev.get('last_error')!r}",
                    EXIT_NO_ACCELERATOR)
            return None

        dev = self._until("/healthz device healthy", healthy,
                          BRINGUP_TIMEOUT_S, 0.1)
        self.col.stamps["healthy"] = time.monotonic()
        if dev["platform"] != self.platform:
            raise RunFailed(
                f"the agent landed on {dev['platform']!r}, not "
                f"{self.platform!r}", EXIT_NO_ACCELERATOR)
        import jax

        devices = jax.devices()
        self.col.device = {"platform": devices[0].platform,
                           "kind": devices[0].device_kind,
                           "count": len(devices)}
        if devices[0].platform != self.platform \
                or len(devices) < self.col.cell.chips:
            raise RunFailed(f"JAX reports {self.col.device}",
                            EXIT_NO_ACCELERATOR)
        if self.platform == "tpu":
            self.col.peaks = load_peaks(devices[0].device_kind)
        say(f"device healthy: {self.col.device}")

    def _warm_up(self) -> None:
        """Until two windows in a row went through the fast encoder and
        XLA was asked for no new program meanwhile. A mix under which
        the program compiles in every window says ``"warm_until":
        "fast_path"`` in its file and is held to the paths alone; what
        it then compiles inside the measured window is printed."""
        paths_alone = self.col.cell.traffic.get("warm_until") == "fast_path"
        history: list[tuple[int, float]] = []   # (windows complete, compiles)

        def settled():
            body = scrape.get_json(self.port, "debug/windows")
            m = scrape.scrape_metrics(self.port)
            if body is None or m is None:
                return None
            rows = [r for r in scrape.window_rows(body) if r["complete"]]
            compiles = m.total("parca_agent_xla_compile_requests_total")
            if not history or history[-1][0] != len(rows):
                history.append((len(rows), compiles))
                if rows:
                    say(f"warm window {len(rows)}: path "
                        f"{rows[-1]['path']}, compiles {compiles:.0f}")
            if len(rows) > self.warm_max:
                raise RunFailed(
                    f"not warm after {self.warm_max} windows: paths "
                    f"{[r['path'] for r in rows[-4:]]}, compiles {compiles}")
            if len(rows) < 3 or any(r["path"] not in GOOD_PATHS
                                    for r in rows[-2:]):
                return None
            before = [c for n, c in history if n <= len(rows) - 2]
            return paths_alone or (bool(before) and before[-1] == compiles)

        self._until("the warm windows", settled, WARM_TIMEOUT_S,
                    max(self.poll_s, 0.05))

    def _snapshot(self, tag: str) -> None:
        col = self.col
        col.stamps[tag] = time.monotonic()
        t = os.times()
        col.cpu[tag] = t.user + t.system

    def _measure(self) -> None:
        col = self.col
        self._next_completion(max(60.0, 4 * col.period_s))
        self._snapshot("t0")
        self.sink.ask(cmd="mark")
        col.metrics0 = scrape.scrape_metrics(self.port)
        done0 = {r["seq"] for r in scrape.window_rows(
            scrape.get_json(self.port, "debug/windows") or {})
            if r["complete"]}
        completed0 = self._completed()
        say(f"set-up over after {col.stamps['t0'] - col.stamps['start']:.1f}s"
            f"; measuring for {col.seconds:g}s")
        if self.trace:
            self._trace()
        rest = col.stamps["t0"] + col.seconds - time.monotonic() \
            - 4 * self.poll_s
        if rest > 0 and self.agent_gone.wait(rest):
            raise RunFailed("the agent ended inside the measured window")
        while True:
            n = self._next_completion(max(60.0, 4 * col.period_s))
            if time.monotonic() >= col.stamps["t0"] + col.seconds:
                break
        self._snapshot("t1")
        col.windows_closed = n - completed0
        col.metrics1 = scrape.scrape_metrics(self.port)
        body = scrape.get_json(self.port, "debug/windows") or {}
        rows = col.all_rows = scrape.window_rows(body)
        for r in rows:
            r["ended"] = self.clock.of_window(r["seq"])
        # A window still open behind a completed one is stuck, and counts.
        newest = max((r["seq"] for r in rows if r["complete"]), default=0)
        col.rows = [r for r in rows if r["seq"] not in done0
                    and (r["complete"] or r["seq"] < newest)]
        import jax

        col.device["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices())

    def _trace(self) -> None:
        """Trace whole windows: from one completion to the n-th after
        it. The Python tracer stays off: its events are most of what
        ``stop_trace`` has to write and none of what is reduced."""
        import jax.profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        col, wait = self.col, max(60.0, 4 * self.col.period_s)
        first = self._completed()
        col.stamps["trace0"] = time.monotonic()
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            start = self._next_completion(wait)
            self._until("the traced windows",
                        lambda: (self._completed() or 0) >= start + self.n_trace,
                        (self.n_trace + 4) * wait, self.poll_s)
            col.trace_windows = (self._completed() or 0) - first
        finally:
            col.stamps["trace1"] = time.monotonic()
            jax.profiler.stop_trace()
        say(f"traced {col.trace_windows} windows in "
            f"{col.stamps['trace1'] - col.stamps['trace0']:.2f}s; writing "
            f"the trace took {time.monotonic() - col.stamps['trace1']:.1f}s")

    def _run(self) -> None:
        self._bring_up()
        self._warm_up()
        self._measure()


def judge_windows(col: Collected) -> tuple[int, int, list[str]]:
    """(attempted, failed, why) over the measured windows."""
    why = []
    failed = 0
    for r in col.rows:
        bad = None
        if not r["complete"]:
            bad = "never completed"
        elif r["error"]:
            bad = f"error {r['error']}"
        elif r["lost"]:
            bad = "lost"
        elif r["path"] not in GOOD_PATHS:
            bad = f"path {r['path']}"
        if bad:
            failed += 1
            why.append(f"window seq {r['seq']}: {bad}")
    return len(col.rows), failed, why


# The agent's own counters of what it dropped or failed to send; a rise
# inside the measured window is a failure. Compile requests are printed.
DROP_COUNTERS = ("parca_agent_remote_write_samples_dropped",
                 "parca_agent_remote_write_errors_total",
                 "parca_agent_remote_write_overflow_spills",
                 "parca_agent_remote_write_failure_spills",
                 "parca_agent_profiler_errors_total")
COMPILE_COUNTER = "parca_agent_xla_compile_requests_total"


def rise(col: Collected, name: str) -> float:
    """How far a ``/metrics`` counter rose over the measured window."""
    return col.metrics1.total(name) - col.metrics0.total(name)


def check_output(col: Collected, sink: Sink, seed: int, needed: int,
                 scratch: str, sizes: dict | None = None) -> dict:
    """After the window: the first and the last measured window, as the
    sink holds them, against the plain reference; and that every pid got
    a profile for every window it lived in, through the last measured."""
    cell = col.cell
    pop, mix = population_and_mix(cell, sizes)
    order = mix.replay_order(needed)
    measured = sorted(r["seq"] for r in col.rows if r["complete"])
    numbers = {k: 0 for k in compare.LIMITS}
    numbers["windows_short_at_sink"] = 0
    if not measured:
        numbers["profiles_missing"] = 1
        return numbers
    # Window i of the run (0-based) is the trace with seq i + 1: the
    # replay source hands one file per iteration, in order.
    targets = sorted({measured[0] - 1, measured[-1] - 1})
    stats = sink.ask(cmd="stats")
    per_pid = {int(p): n for p, n in stats["per_pid"].items()}

    # Regenerate the run's windows: keep the two that are compared, and
    # every pid's first and last window of the run.
    seq = mix.sequence(pop, seed)
    wanted = {order[t] for t in targets}
    distinct: dict[int, generate.Window] = {}
    pids_of: list[set[int]] = []
    for d in range(mix.distinct_windows(needed)):
        w = seq.next()
        if d in wanted:
            distinct[d] = _own_copy(w)
        pids_of.append(set(np.unique(w.pids).tolist()))
    born: dict[int, int] = {}
    died: dict[int, int] = {}
    alive: set[int] = set()
    for i, d in enumerate(order):
        now = pids_of[d]
        born.update((p, i) for p in now - alive)
        died.update((p, i) for p in alive - now)
        alive = now

    # Every pid got one profile for every window it lived in, up to and
    # with the last measured one. A window still in flight when the agent
    # is told to stop is not part of the measured window, and the agent
    # may abandon it (its supervisor waits 5 s for the profiler actor).
    through = measured[-1]
    numbers["windows_short_at_sink"] = sum(
        1 for p, b in born.items() if b < through
        and per_pid.get(p, 0) < min(died.get(p, through), through) - b)

    n_sampled = int(cell.config["check"]["sampled_pids"])
    for t in targets:
        w = distinct[order[t]]
        path = os.path.join(scratch, f"fetch{t}.pkl")
        sink.ask(cmd="fetch", path=path,
                 want=[[p, t - born[p]] for p in np.unique(w.pids).tolist()])
        with open(path, "rb") as f:
            got = pickle.load(f)
        os.unlink(path)
        blobs = {pid: blob for (pid, _k), blob in got.items()}
        for k, v in compare.compare_window(w, blobs, seed, n_sampled).items():
            numbers[k] += v
    return numbers


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, platform: str = "tpu",
             sizes: dict | None = None) -> tuple[int, dict | None]:
    """Run the cell once. Returns (exit code, result line or None)."""
    os.environ["JAX_PLATFORMS"] = platform
    period = float(cell.config["replay"]["period_s"])
    col = Collected(cell=cell, seconds=seconds, period_s=period)
    col.stamps["start"] = t_start
    scratch = tempfile.mkdtemp(prefix="parca-bench-")
    sink = None
    try:
        ensure_program()
        needed = plan_windows(cell, seconds)
        files = write_windows(cell, seed, needed, scratch, sizes)
        col.stamps["generated"] = time.monotonic()
        say(f"{len(set(files))} snapshot files for {needed} windows")
        sink = Sink()
        http_port = _free_port()
        argv = agent_argv(cell, files, sink.port, http_port, sizes)
        trace_dir = os.path.join(scratch, "trace")
        agent_gone = threading.Event()
        sys.path.insert(0, CHECKOUT)
        from parca_agent_tpu.cli import run as agent_run

        clock = EdgeClock()
        clock.install()
        driver = Driver(col, http_port, sink, platform, trace, trace_dir,
                        agent_gone, clock)
        driver.start()
        try:
            rc = agent_run(argv)
        finally:
            agent_gone.set()
        driver.join(timeout=120)
        if driver.error is not None:
            raise driver.error
        if driver.is_alive() or "t1" not in col.stamps:
            raise RunFailed(f"the agent ended early (rc {rc})")
        if rc != 0:
            raise RunFailed(f"the agent exited with {rc}")

        if trace:
            col.trace = trace_reduce.reduce_dir(trace_dir)
            keep_xplane(trace_dir, cell.name, seed)
            if col.trace is not None:
                # The traced window is from start_trace to stop_trace by
                # the host's clock; the trace's own extent also holds the
                # seconds stop_trace spends writing host events.
                col.trace["window_s"] = \
                    col.stamps["trace1"] - col.stamps["trace0"]
        t_check = time.monotonic()
        numbers = check_output(col, sink, seed, needed, scratch, sizes)
        attempted, failed, why = judge_windows(col)
        dropped = {name: rise(col, name) for name in DROP_COUNTERS}
        for name, v in dropped.items():
            if v:
                failed = max(failed, 1)
                why.append(f"{name} rose by {v:g}")
        if numbers["windows_short_at_sink"]:
            failed = max(failed, 1)
        correct = compare.verdict(numbers) \
            and numbers["windows_short_at_sink"] == 0
        for k, v in numbers.items():
            print(f"compared {k} = {v} (limit {compare.LIMITS.get(k, 0)})")
        for name, v in {**dropped,
                        COMPILE_COUNTER: rise(col, COMPILE_COUNTER)}.items():
            print(f"in-window {name} = {v:g}")
        for line in why:
            print(f"failed: {line}")
        print(f"check took {time.monotonic() - t_check:.1f}s; windows "
              f"closed {col.windows_closed}, measured {attempted}")
        line = result_line(col, correct, attempted, failed, trace)
        keep_details(col, seed, trace, numbers, dropped, why, line)
        return 0, line
    except RunFailed as e:
        say(f"run failed: {e}")
        return e.code, None
    finally:
        if sink is not None:
            sink.close()
        shutil.rmtree(scratch, ignore_errors=True)


def keep_xplane(trace_dir: str, cell: str, seed: int) -> None:
    """The raw trace beside the details, for whoever wants to look."""
    src = trace_reduce.newest_xplane(trace_dir)
    if src is not None:
        out = os.path.join(CHECKOUT, "chiprun_out", "bench")
        os.makedirs(out, exist_ok=True)
        shutil.copyfile(src, os.path.join(out, f"{cell}-{seed}.xplane.pb"))


def keep_details(col: Collected, seed: int, trace: bool, numbers: dict,
                 dropped: dict, why: list, line: dict) -> None:
    """What is too long for the output, under ``chiprun_out/bench/``."""
    out = os.path.join(CHECKOUT, "chiprun_out", "bench")
    os.makedirs(out, exist_ok=True)
    t0 = col.stamps["start"]
    detail = {
        "cell": col.cell.name, "seed": seed, "seconds": col.seconds,
        "period_s": col.period_s, "line": line, "compared": numbers,
        "in_window": dropped, "failed_why": why,
        "stamps": {k: v - t0 for k, v in col.stamps.items()},
        "windows": [{"seq": r["seq"], "path": r["path"],
                     "measured": r in col.rows,
                     "spans": {k: [v[0], v[1]] for k, v in r["spans"].items()},
                     "ended": {k: v - t0 for k, v in r["ended"].items()}}
                    for r in col.all_rows],
        "trace": col.trace}
    name = f"{col.cell.name}-{seed}-t{int(trace)}.json"
    with open(os.path.join(out, name), "w") as f:
        json.dump(detail, f, indent=1)


def result_line(col: Collected, correct: bool, attempted: int, failed: int,
                trace: bool) -> dict:
    metrics = {}
    for m in (col.cell.per_layer if trace else col.cell.end_to_end):
        value = m.read(col)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    device = dict(col.device)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if trace and col.trace is not None:
        device["busy_s"] = col.trace["busy_s"]
        device["window_s"] = col.trace["window_s"]
        line["breakdown"] = {"device_ops": col.trace["device_ops"][:10],
                             "idle_gaps": col.trace["idle_gaps"][:10]}
    return line
