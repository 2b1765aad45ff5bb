"""/metrics exposition strictness (web.py render_metrics).

The satellite contract (ISSUE 7): every emitted family carries a
``# TYPE`` line, label values are escaped, histogram series are
internally consistent — validated here by a STRICT Prometheus
text-format parser (written to the text exposition format spec: name
syntax, label syntax with escape handling, TYPE-before-sample, family
contiguity, no duplicate series, bucket monotonicity, le="+Inf" ==
_count, _sum present).
"""

from __future__ import annotations

import re
import urllib.request

import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.profiler.cpu import CPUProfiler, ProfilerMetrics
from parca_agent_tpu.runtime.trace import FlightRecorder
from parca_agent_tpu.web import (
    AgentHTTPServer,
    escape_label_value,
    render_metrics,
)

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
_VALUE_RE = re.compile(
    r"^(?:[+-]?(?:\d+(?:\.\d*)?|\.\d+)(?:[eE][+-]?\d+)?|NaN|\+Inf|-Inf)$")
_TYPES = ("counter", "gauge", "histogram", "summary", "untyped")


def _parse_labels(s: str) -> dict:
    """Parse the inside of a {...} label set, honoring \\\\, \\" and \\n
    escapes; raises on any syntax violation."""
    labels: dict[str, str] = {}
    i = 0
    while i < len(s):
        m = re.match(r'([a-zA-Z_][a-zA-Z0-9_]*)="', s[i:])
        if not m:
            raise AssertionError(f"bad label syntax at {s[i:]!r}")
        name = m.group(1)
        if name in labels:
            raise AssertionError(f"duplicate label {name!r}")
        i += m.end()
        val = []
        while True:
            if i >= len(s):
                raise AssertionError("unterminated label value")
            c = s[i]
            if c == "\\":
                if i + 1 >= len(s) or s[i + 1] not in '\\"n':
                    raise AssertionError(f"bad escape in {s!r}")
                val.append({"\\": "\\", '"': '"', "n": "\n"}[s[i + 1]])
                i += 2
            elif c == '"':
                i += 1
                break
            elif c == "\n":
                raise AssertionError("raw newline in label value")
            else:
                val.append(c)
                i += 1
        labels[name] = "".join(val)
        if i < len(s):
            if s[i] != ",":
                raise AssertionError(f"expected ',' at {s[i:]!r}")
            i += 1
    return labels


def parse_prometheus_text(text: str) -> dict:
    """Strict parse; returns {family: {"type": t, "samples":
    [(sample_name, labels_dict, float_value)]}}."""
    assert text.endswith("\n"), "exposition must end with a newline"
    families: dict[str, dict] = {}
    current: str | None = None
    seen_series: set = set()
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 2 and parts[1] == "TYPE":
                assert len(parts) == 4, f"line {lineno}: malformed TYPE"
                _, _, name, mtype = parts
                assert _NAME_RE.match(name), f"line {lineno}: bad name"
                assert mtype in _TYPES, f"line {lineno}: bad type {mtype}"
                assert name not in families, \
                    f"line {lineno}: duplicate TYPE for {name}"
                families[name] = {"type": mtype, "samples": []}
                current = name
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$",
                     line)
        assert m, f"line {lineno}: unparseable sample {line!r}"
        name, labelstr, value = m.group(1), m.group(2), m.group(3)
        assert _VALUE_RE.match(value), f"line {lineno}: bad value {value!r}"
        labels = _parse_labels(labelstr) if labelstr else {}
        for k in labels:
            assert _LABEL_NAME_RE.match(k)
        # Resolve the family: histogram samples use suffixed names.
        fam = name
        if fam not in families:
            for suffix in ("_bucket", "_sum", "_count"):
                base = name.removesuffix(suffix)
                if name.endswith(suffix) and base in families \
                        and families[base]["type"] == "histogram":
                    fam = base
                    break
        assert fam in families, \
            f"line {lineno}: sample {name} before its # TYPE line"
        assert fam == current, \
            f"line {lineno}: {name} outside its family's block"
        key = (name, tuple(sorted(labels.items())))
        assert key not in seen_series, f"line {lineno}: duplicate {key}"
        seen_series.add(key)
        families[fam]["samples"].append((name, labels, float(value)))
    _check_histograms(families)
    return families


def _check_histograms(families: dict) -> None:
    for fam, data in families.items():
        if data["type"] != "histogram":
            continue
        series: dict[tuple, dict] = {}
        for name, labels, value in data["samples"]:
            rest = tuple(sorted((k, v) for k, v in labels.items()
                                if k != "le"))
            s = series.setdefault(rest, {"buckets": [], "sum": None,
                                         "count": None})
            if name == fam + "_bucket":
                assert "le" in labels, f"{fam}: bucket without le"
                s["buckets"].append((labels["le"], value))
            elif name == fam + "_sum":
                s["sum"] = value
            elif name == fam + "_count":
                s["count"] = value
        for rest, s in series.items():
            assert s["buckets"], f"{fam}{dict(rest)}: no buckets"
            assert s["sum"] is not None, f"{fam}{dict(rest)}: missing _sum"
            assert s["count"] is not None, \
                f"{fam}{dict(rest)}: missing _count"
            les = [float("inf") if le == "+Inf" else float(le)
                   for le, _ in s["buckets"]]
            counts = [c for _, c in s["buckets"]]
            assert les == sorted(les), f"{fam}{dict(rest)}: le not sorted"
            assert les[-1] == float("inf"), \
                f"{fam}{dict(rest)}: missing le=+Inf"
            assert counts == sorted(counts), \
                f"{fam}{dict(rest)}: buckets not cumulative"
            assert counts[-1] == s["count"], \
                f"{fam}{dict(rest)}: +Inf bucket != _count"


def _snap(seed=7):
    return generate(SyntheticSpec(
        n_pids=4, n_unique_stacks=64, n_rows=64, total_samples=256,
        mean_depth=6, seed=seed))


class Collect:
    def write(self, labels, blob):
        pass


def _loaded_recorder() -> FlightRecorder:
    rec = FlightRecorder()
    for stage in ("drain", "close", "prepare", "encode", "ship",
                  "batch_flush", "store_ack", "statics"):
        for i in range(5):
            rec.observe(stage, 0.001 * (i + 1))
    tr = rec.begin()
    tr.add_span("close", 0.01)
    tr.complete()
    return rec


def _full_stack(tmp_path):
    """A realistic component set for render_metrics: a profiler that ran
    a window, a batch client with a spool, quarantine + device health +
    supervisor + recorder."""
    from parca_agent_tpu.agent.batch import BatchWriteClient, NoopStoreClient
    from parca_agent_tpu.agent.spool import SpoolDir
    from parca_agent_tpu.runtime.device_health import (
        STATE_HEALTHY,
        DeviceHealthRegistry,
    )
    from parca_agent_tpu.runtime.quarantine import QuarantineRegistry
    from parca_agent_tpu.runtime.supervisor import Supervisor

    prof = CPUProfiler(source=None, aggregator=CPUAggregator(),
                       profile_writer=Collect(), duration_s=0.0,
                       trace_recorder=None)
    prof._source = type("S", (), {
        "poll": lambda self_: _snap()})()
    prof.run_iteration()
    batch = BatchWriteClient(
        NoopStoreClient(), spool=SpoolDir(str(tmp_path / "spool")))
    batch.write_raw({"__name__": "x"}, b"blob")
    batch.flush()
    return dict(
        profilers=[prof], batch_client=batch,
        supervisor=Supervisor(),
        quarantine=QuarantineRegistry(),
        device_health=DeviceHealthRegistry(probe=None,
                                           start_state=STATE_HEALTHY),
        recorder=_loaded_recorder(),
    )


def test_escape_label_value():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert _parse_labels(f'k="{escape_label_value(chr(10) + "x")}"') \
        == {"k": "\nx"}


def test_render_metrics_is_strict_prometheus(tmp_path):
    kw = _full_stack(tmp_path)
    text = render_metrics(
        kw.pop("profilers"), kw.pop("batch_client"),
        {"parca_agent_capture_lost_samples_total": 3,
         'parca_agent_build_info{version="dev",python="3.x"}': 1},
        **kw)
    fams = parse_prometheus_text(text)
    # Every family got a TYPE line by construction of the parse; spot
    # checks on semantics:
    assert fams["parca_agent_profiler_attempts_total"]["type"] == "counter"
    assert fams["parca_agent_profiler_attempt_duration_seconds"]["type"] \
        == "gauge"
    hist = fams["parca_agent_window_stage_duration_seconds"]
    assert hist["type"] == "histogram"
    stages = {lab["stage"] for _, lab, _ in hist["samples"]}
    # The acceptance bar: real Prometheus histograms for >= 6 stages.
    assert len(stages) >= 6
    assert {"drain", "close", "prepare", "encode", "ship",
            "batch_flush"} <= stages
    assert fams["parca_agent_build_info"]["samples"][0][1]["version"] == "dev"
    assert fams["parca_agent_trace_traces_completed_total"]["type"] \
        == "counter"


def test_render_metrics_escapes_hostile_label_values(tmp_path):
    class Hostile:
        name = 'evil"profiler\\with\nnewline'
        metrics = ProfilerMetrics()

    text = render_metrics([Hostile()])
    fams = parse_prometheus_text(text)
    name = fams["parca_agent_profiler_attempts_total"]["samples"][0][1][
        "profiler"]
    assert name == Hostile.name  # round-trips through escaping


def test_device_and_quarantine_series_sum_consistently(tmp_path):
    kw = _full_stack(tmp_path)
    text = render_metrics([], **{k: kw[k] for k in
                                 ("quarantine", "device_health")})
    fams = parse_prometheus_text(text)
    one_hot = [v for _, _, v in
               fams["parca_agent_device_state"]["samples"]]
    assert sum(one_hot) == 1


def test_metrics_endpoint_serves_strict_text_and_debug_windows(tmp_path):
    kw = _full_stack(tmp_path)
    rec = kw["recorder"]
    srv = AgentHTTPServer(port=0, profilers=kw["profilers"],
                          batch_client=kw["batch_client"],
                          supervisor=kw["supervisor"],
                          quarantine=kw["quarantine"],
                          device_health=kw["device_health"],
                          recorder=rec)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            text = r.read().decode()
        parse_prometheus_text(text)
        import json

        with urllib.request.urlopen(f"{base}/debug/windows",
                                    timeout=10) as r:
            body = json.loads(r.read().decode())
        assert body["traces"][-1]["complete"]
        seq = body["traces"][-1]["seq"]
        with urllib.request.urlopen(f"{base}/debug/trace/{seq}",
                                    timeout=10) as r:
            one = json.loads(r.read().decode())
        assert one["seq"] == seq
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/debug/trace/999999", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_debug_windows_503_without_recorder():
    srv = AgentHTTPServer(port=0, profilers=[])
    srv.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/windows", timeout=10)
        assert ei.value.code == 503
    finally:
        srv.stop()


# -- CPU accounting families (ISSUE 39) ---------------------------------------


def _burn(cpu_s: float) -> None:
    import time

    t0 = time.thread_time()
    while time.thread_time() - t0 < cpu_s:
        pass


def _thread_cpu(text: str) -> dict:
    fams = parse_prometheus_text(text)
    return {lab["thread"]: v for _, lab, v in
            fams["parca_agent_thread_cpu_seconds_total"]["samples"]}


def test_cpu_accounting_families_are_strict_counters():
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("drain", usage=True):
        _burn(0.01)
    with tr.span("close"):
        _burn(0.001)
    tr.add_span("ship", 0.002, used={"cpu_s": 0.0015})
    tr.complete()
    fams = parse_prometheus_text(render_metrics([], recorder=rec))
    assert fams["parca_agent_stage_cpu_seconds_total"]["type"] == "counter"
    cpu = {lab["stage"]: v for _, lab, v in
           fams["parca_agent_stage_cpu_seconds_total"]["samples"]}
    # A series for each stage that asked for its threads' CPU.
    assert set(cpu) == {"drain", "ship"}
    assert cpu["ship"] == 0.0015 and cpu["drain"] >= 0.01
    threads = {lab["thread"] for _, lab, _ in
               fams["parca_agent_thread_cpu_seconds_total"]["samples"]}
    assert {"MainThread", "native"} <= threads
    assert fams["parca_agent_process_cpu_seconds_total"]["type"] == "counter"
    # Without a recorder (--no-window-trace) none of it is served.
    assert "parca_agent_thread_cpu" not in render_metrics([])


def test_every_threads_cpu_is_a_monotone_counter():
    """A bounded_call thread that has ended is credited under its name;
    a live thread is credited by the scrape, once; over three scrapes
    with threads starting and ending between them every label only
    rises, and ``native`` (the remainder) is never negative. A live
    thread's CPU is the kernel's count of it in clock ticks."""
    import threading

    from parca_agent_tpu.utils.bounded import bounded_call

    rec = FlightRecorder()
    stop = threading.Event()

    def live():
        while not stop.wait(0.001):
            _burn(0.002)

    worker = threading.Thread(target=live, name="cpu-test-live_1")
    worker.start()
    try:
        before = _thread_cpu(render_metrics([], recorder=rec))
        assert bounded_call(lambda: _burn(0.03), 10,
                            "cpu-test-device")[0] == "ok"
        first = _thread_cpu(render_metrics([], recorder=rec))
        again = _thread_cpu(render_metrics([], recorder=rec))
        assert bounded_call(lambda: _burn(0.02), 10,
                            "cpu-test-device")[0] == "ok"
        _burn(0.03)
        third = _thread_cpu(render_metrics([], recorder=rec))
    finally:
        stop.set()
        worker.join(10)
    # The thread is gone when the scrape looks, and its CPU is there.
    assert "cpu-test-device" not in {t.name for t in threading.enumerate()}
    assert first["cpu-test-device"] - before.get("cpu-test-device", 0) \
        == pytest.approx(0.03, abs=0.01)
    assert third["cpu-test-device"] - first["cpu-test-device"] \
        == pytest.approx(0.02, abs=0.01)
    # A live thread (trailing number off its name) is not counted twice:
    # two scrapes back to back differ by what it burnt between them.
    assert again.get("cpu-test-live", 0) - first.get("cpu-test-live", 0) \
        < 0.05
    assert third["cpu-test-live"] > before.get("cpu-test-live", 0)
    for a, b in ((before, first), (first, again), (again, third)):
        for label, v in a.items():
            assert b[label] >= v, label
        assert b["native"] >= 0
    assert third["MainThread"] - again["MainThread"] >= 0.01


def test_the_thread_counters_add_up_to_the_process():
    import time

    from parca_agent_tpu.runtime.trace import ThreadCpu

    acct = ThreadCpu()
    _burn(0.02)
    got = acct.scrape()
    assert got["native"] >= 0
    assert sum(got["threads"].values()) + got["native"] \
        == pytest.approx(got["process"], abs=2e-3)
    assert got["process"] <= time.process_time()


def test_what_native_is_made_of_comes_from_proc_by_comm():
    from parca_agent_tpu.runtime.trace import ThreadCpu

    acct = ThreadCpu()
    _burn(0.06)  # several clock ticks
    # Told that no thread is Python's, it files this one under its comm
    # (digits stripped), at tick resolution.
    with acct._proc_lock:
        acct._scrape_native(set())
        first = dict(acct._comm_total)
        acct._scrape_native(set())  # inside the rate limit: not read again
        assert acct._comm_total == first
    assert len(first) <= ThreadCpu._MAX_COMMS + 1
    assert all(not any(c.isdigit() for c in comm) for comm in first)
    assert sum(first.values()) >= 0.04
    # And with the Python threads named, a process with no native
    # thread at work serves next to nothing.
    got = ThreadCpu().scrape()["native_comm"]
    assert sum(got.values()) <= sum(first.values())


def test_a_requests_thread_is_credited_as_http():
    rec = FlightRecorder()
    srv = AgentHTTPServer(port=0, profilers=[], recorder=rec)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        seen = []
        for _ in range(3):
            with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
                seen.append(_thread_cpu(r.read().decode()))
    finally:
        srv.stop()
    # Each request ran on a thread of its own that credited its CPU as
    # it ended; the label is the server thread's, and it only rises.
    assert seen[0].get("http", 0) <= seen[1]["http"] <= seen[2]["http"]
    assert seen[2]["http"] > 0
    assert not any(label.startswith("Thread") for label in seen[-1])


def test_a_scrape_asks_no_thread_for_its_clock(monkeypatch):
    """``pthread_getcpuclockid`` of a thread that has ended is undefined
    behaviour, and any thread may end under a scrape: a live thread's
    CPU is read from ``/proc/self/task/<tid>/stat``, a thread that is
    gone has no file there, and a file read for a thread that ended
    meanwhile (its id may be another's by then) is thrown away."""
    import threading
    import time

    from parca_agent_tpu.runtime import trace as trace_mod

    def forbidden(*_a):
        raise AssertionError("asked a thread for its clock id")

    monkeypatch.setattr(time, "pthread_getcpuclockid", forbidden)
    gone = threading.Thread(target=lambda: _burn(0.03), name="cpu-test-gone")
    gone.start()
    gone.join(10)
    deadline = time.monotonic() + 10  # the kernel's thread outlives join()
    while trace_mod._task_stat(gone.native_id) and time.monotonic() < deadline:
        time.sleep(0.001)
    assert trace_mod._task_stat(gone.native_id) is None
    assert trace_mod._task_stat(threading.get_native_id())[1] >= 0
    listed = threading.enumerate() + [gone]
    monkeypatch.setattr(threading, "enumerate", lambda: listed)
    acct = trace_mod.ThreadCpu()
    assert "cpu-test-gone" not in acct.scrape()["threads"]
    monkeypatch.setattr(trace_mod, "_task_stat", lambda tid: (b"x", 700))
    got = acct.scrape()["threads"]
    assert "cpu-test-gone" not in got and got["MainThread"] >= 7.0
