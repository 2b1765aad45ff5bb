"""Agent shell tests: profiler loop, config reload, kconfig, web UI,
procfs sampler, and the CLI wired end-to-end in replay mode."""

import gzip
import io
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from parca_agent_tpu.aggregator.cpu import CPUAggregator
from parca_agent_tpu.capture.replay import ReplaySource
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.config import ConfigReloader, load_config
from parca_agent_tpu.kconfig import (
    check_profiling_enabled,
    is_in_container,
    parse_kernel_config,
)
from parca_agent_tpu.profiler.cpu import CPUProfiler
from parca_agent_tpu.utils.vfs import FakeFS


def _snap(seed=1):
    return generate(SyntheticSpec(n_pids=5, n_unique_stacks=50,
                                  total_samples=500, seed=seed))


class CollectingWriter:
    def __init__(self):
        self.profiles = []

    def write(self, labels, pprof_bytes):
        self.profiles.append((labels, pprof_bytes))


def test_profiler_iteration_end_to_end():
    w = CollectingWriter()
    p = CPUProfiler(
        source=ReplaySource([_snap()]),
        aggregator=CPUAggregator(),
        profile_writer=w,
    )
    assert p.run_iteration()
    assert not p.run_iteration()  # exhausted
    assert p.metrics.attempts_total == 1
    assert p.metrics.profiles_written == len(w.profiles) == 5
    assert p.last_error is None
    # pprof payloads parse back
    from parca_agent_tpu.pprof.builder import parse_pprof

    labels, blob = w.profiles[0]
    assert labels["__name__"] == "parca_agent_cpu"
    parsed = parse_pprof(blob)
    assert parsed.samples


def test_profiler_fast_encode_matches_classic_path():
    """fast_encode writes the same profile content as the classic
    per-PidProfile path (parsed-message equality per pid), minus
    gzip framing."""
    from parca_agent_tpu.aggregator.dict import DictAggregator
    from parca_agent_tpu.pprof.builder import parse_pprof

    snap = _snap(seed=3)
    w_classic = CollectingWriter()
    CPUProfiler(source=ReplaySource([snap]), aggregator=CPUAggregator(),
                profile_writer=w_classic).run_iteration()

    w_fast = CollectingWriter()
    p = CPUProfiler(source=ReplaySource([snap]),
                    aggregator=DictAggregator(capacity=1 << 10),
                    profile_writer=w_fast, fast_encode=True)
    assert p.run_iteration()
    assert p.last_error is None
    assert p.metrics.profiles_written == len(w_classic.profiles)

    classic = {l["pid"]: parse_pprof(b) for l, b in w_classic.profiles}
    for labels, blob in w_fast.profiles:
        want = classic[labels["pid"]]
        have = parse_pprof(blob)
        assert have.stacks_by_address() == want.stacks_by_address()
        assert have.period == want.period


def test_profiler_fast_encode_rejects_symbolizer():
    from parca_agent_tpu.aggregator.dict import DictAggregator

    class Sym:
        def symbolize(self, profiles):
            pass

    with pytest.raises(ValueError):
        CPUProfiler(source=ReplaySource([]),
                    aggregator=DictAggregator(capacity=1 << 10),
                    symbolizer=Sym(), fast_encode=True)
    with pytest.raises(ValueError):
        CPUProfiler(source=ReplaySource([]), aggregator=CPUAggregator(),
                    fast_encode=True)


def test_profiler_fast_encode_fallback_on_device_failure():
    from parca_agent_tpu.aggregator.dict import DictAggregator

    class BoomDict(DictAggregator):
        def window_counts(self, snapshot, hashes=None):
            raise RuntimeError("device gone")

    w = CollectingWriter()
    p = CPUProfiler(source=ReplaySource([_snap(seed=4)]),
                    aggregator=BoomDict(capacity=1 << 10),
                    fallback_aggregator=CPUAggregator(),
                    profile_writer=w, fast_encode=True)
    assert p.run_iteration()
    assert p.last_error is None
    assert len(w.profiles) == 5  # fallback wrote via the scalar builder


@pytest.mark.parametrize("piped", [False, True],
                         ids=["loop", "pipeline"])
def test_profiler_gc_stewardship_opt_in(piped):
    """manage_gc=True (the agent CLI's setting) freezes the warm state and
    disables the automatic scheduler after window 1, collecting explicitly
    at boundaries instead; the default leaves process GC untouched. With
    the encode pipeline the collections ride the worker, after each
    window's ship; the scheduler still goes off at the end of the first
    iteration, on the capture thread."""
    import gc

    if piped:
        from parca_agent_tpu.aggregator.dict import DictAggregator

        kw = dict(aggregator=DictAggregator(capacity=1 << 12),
                  fallback_aggregator=CPUAggregator(), fast_encode=True,
                  encode_pipeline=True)
    else:
        kw = dict(aggregator=CPUAggregator())
    assert gc.isenabled()
    p = CPUProfiler(source=ReplaySource([_snap(), _snap()]),
                    profile_writer=CollectingWriter(), manage_gc=True, **kw)
    try:
        assert p.run_iteration()
        assert not gc.isenabled()  # explicit boundary collects from now on
        if piped:
            assert p._pipeline.flush(30)
        assert p.run_iteration()
        assert not gc.isenabled()
        if piped:
            assert p._pipeline.close()
        m = p.metrics
        assert (m.gc_collections_worker_total,
                m.gc_collections_loop_total) == ((2, 0) if piped else (0, 2))
        assert gc.get_freeze_count() > 0
    finally:
        p._restore_gc()
    assert gc.isenabled() and gc.get_freeze_count() == 0

    # Default: no global side effects.
    q = CPUProfiler(source=ReplaySource([_snap()]),
                    aggregator=CPUAggregator())
    assert q.run_iteration()
    assert gc.isenabled() and gc.get_freeze_count() == 0
    assert q.metrics.gc_collections_loop_total == 0


def test_profiler_fallback_on_device_failure():
    class Boom:
        name = "boom"

        def aggregate(self, snapshot):
            raise RuntimeError("device lost")

    w = CollectingWriter()
    p = CPUProfiler(
        source=ReplaySource([_snap()]),
        aggregator=Boom(),
        fallback_aggregator=CPUAggregator(),
        profile_writer=w,
    )
    assert p.run_iteration()
    assert p.last_error is None and len(w.profiles) == 5


def test_profiler_fallback_on_device_hang():
    """A device call that never returns (wedged runtime inside a C call)
    must not stall the window loop: the watchdog abandons it, the CPU
    fallback aggregates, and the device is only retried after the
    cooldown AND once the abandoned call finished (r3: observed
    multi-minute backend-init hangs on real hardware)."""
    import threading as _t

    release = _t.Event()
    calls = []

    class Wedge:
        name = "wedge"

        def aggregate(self, snapshot):
            calls.append(1)
            release.wait(20)  # wedged until the test releases it
            return CPUAggregator().aggregate(snapshot)

    w = CollectingWriter()
    snaps = [_snap() for _ in range(4)]
    p = CPUProfiler(
        source=ReplaySource(snaps),
        aggregator=Wedge(),
        fallback_aggregator=CPUAggregator(),
        profile_writer=w,
        device_timeout_s=0.2,
        device_retry_windows=2,
    )
    t0 = time.monotonic()
    assert p.run_iteration()          # hang -> watchdog -> fallback
    assert time.monotonic() - t0 < 5
    assert p.last_error is None and len(w.profiles) == 5
    assert len(calls) == 1

    assert p.run_iteration()          # cooldown: no device attempt
    assert len(calls) == 1
    release.set()                     # abandoned call completes...
    assert p._device_inflight.wait(10)  # ...deterministically
    assert p.run_iteration()          # window 3: cooldown reached, retry
    assert len(calls) == 2
    assert p.run_iteration()
    assert len(w.profiles) == 4 * 5
    assert p.last_error is None


def test_profiler_iteration_failure_nonfatal():
    class BadWriter:
        def write(self, labels, blob):
            raise ConnectionError("store down")

    p = CPUProfiler(
        source=ReplaySource([_snap(), _snap(2)]),
        aggregator=CPUAggregator(),
        profile_writer=BadWriter(),
    )
    assert p.run_iteration()
    assert isinstance(p.last_error, ConnectionError)
    assert p.metrics.errors_total == 1
    assert p.run_iteration()  # loop continues


def test_config_load_and_reloader(tmp_path):
    cfg = load_config("relabel_configs:\n- action: drop\n  source_labels: [comm]\n  regex: java\n")
    assert cfg.relabel_configs[0].action == "drop"
    path = tmp_path / "c.yaml"
    path.write_text("relabel_configs: []\n")
    seen = []
    r = ConfigReloader(str(path), [lambda c: seen.append(len(c.relabel_configs))],
                       poll_s=0.01, debounce_s=0.0)
    assert r.check_once()  # initial load
    assert not r.check_once()  # unchanged
    path.write_text("relabel_configs:\n- action: labeldrop\n  regex: tmp_.*\n")
    assert r.check_once()
    assert seen == [0, 1]
    # Malformed config does not fire callbacks
    path.write_text("relabel_configs:\n- action: bogus\n")
    assert not r.check_once()
    assert r.errors == 1


def test_kconfig_parse_and_check():
    text = "CONFIG_PERF_EVENTS=y\nCONFIG_BPF=y\n# CONFIG_BPF_JIT is not set\n"
    cfg = parse_kernel_config(text)
    assert cfg["CONFIG_PERF_EVENTS"] == "y"
    fs = FakeFS({
        "/proc/sys/kernel/osrelease": b"6.6-test\n",
        "/boot/config-6.6-test": text.encode(),
    })
    ok, missing, advisory = check_profiling_enabled(fs)
    assert ok and missing == []
    assert "CONFIG_BPF_JIT" in advisory  # advisory only
    # gzip path
    import gzip as _gz

    fs2 = FakeFS({"/proc/config.gz": _gz.compress(b"CONFIG_PERF_EVENTS=n\n")})
    ok2, missing2, _adv = check_profiling_enabled(fs2)
    assert not ok2 and "CONFIG_PERF_EVENTS" in missing2


def test_is_in_container():
    assert is_in_container(FakeFS({"/.dockerenv": b""}))
    assert is_in_container(FakeFS({
        "/proc/1/cgroup": b"0::/kubepods/pod1/abc\n",
    }))
    assert not is_in_container(FakeFS({"/proc/1/cgroup": b"0::/\n"}))


def test_procfs_sampler_collect():
    from parca_agent_tpu.capture.procfs import ProcfsSampler, read_cpu_ticks

    stat = b"7 (wor ker)) S 1 7 7 0 -1 0 0 0 0 0 30 12 0 0 20 0 1 0 100 0 0\n"
    fs = FakeFS({"/proc/7/stat": stat})
    assert read_cpu_ticks(fs, 7) == 42

    import subprocess
    import tempfile

    d = tempfile.mkdtemp()
    subprocess.run(["gcc", "-pie", "-fPIE", "-x", "c", "-", "-o", f"{d}/exe"],
                   input=b"int main(void){return 0;}", check=True)
    exe = open(f"{d}/exe", "rb").read()
    from parca_agent_tpu.elf.reader import ElfFile

    seg = ElfFile(exe).exec_load_segment()
    off = (seg.offset // 4096) * 4096
    base = 0x560000000000
    maps_line = (f"{base + off:x}-{base + off + seg.filesz:x} r-xp "
                 f"{off:08x} 08:01 11 /exe\n").encode()
    fs = FakeFS({
        "/proc/7/stat": stat,
        "/proc/7/maps": maps_line,
        "/proc/7/root/exe": exe,
    })
    s = ProcfsSampler(fs=fs, frequency_hz=100, window_s=1.0)
    snap = s.collect({7: 42})
    assert len(snap) == 1
    assert int(snap.counts[0]) == 42  # 100Hz nominal == USER_HZ
    assert int(snap.user_len[0]) == 1
    # entry frame lands inside the mapped executable range
    addr = int(snap.stacks[0, 0])
    assert base + off <= addr < base + off + seg.filesz
    assert len(snap.mappings) == 1
    # aggregates cleanly
    profiles = CPUAggregator().aggregate(snap)
    assert profiles[0].total() == 42


def test_procfs_sampler_catches_mid_window_exit():
    """A process that burns CPU then exits mid-window must still be
    attributed (the reason poll() samples at poll_hz, not only at edges)."""
    from parca_agent_tpu.capture.procfs import ProcfsSampler

    def stat(ticks):
        return f"7 (w) R 1 7 7 0 -1 0 0 0 0 0 {ticks} 0 0 0 20 0 1 0 1 0 0\n".encode()

    fs = FakeFS({"/proc/7/stat": stat(10)})
    clock = [0.0]

    s = ProcfsSampler(fs=fs, window_s=1.0, poll_hz=2.0,
                      clock=lambda: clock[0], sleep=lambda t: None)

    orig_acc = s.accumulate
    steps = {"n": 0}

    def stepping(window_deltas):
        steps["n"] += 1
        clock[0] += 0.5
        if steps["n"] == 1:
            fs.put("/proc/7/stat", stat(90))  # burned 80 ticks
        orig_acc(window_deltas)
        if steps["n"] == 2:
            del fs.files["/proc/7/stat"]  # process exits mid-window

    s.accumulate = stepping
    snap = s.poll()
    assert len(snap) == 0 or int(snap.counts.sum()) >= 0  # may lack mappings
    # The tick accounting itself saw the 80 ticks before exit:
    deltas = {}
    fs.put("/proc/7/stat", stat(10))
    s2 = ProcfsSampler(fs=fs, clock=lambda: 0.0, sleep=lambda t: None)
    s2._prev = s2.sample_ticks()
    s2._started = True
    fs.put("/proc/7/stat", stat(90))
    s2.accumulate(deltas)
    del fs.files["/proc/7/stat"]
    s2.accumulate(deltas)
    assert deltas == {7: 80}


def test_cli_replay_end_to_end(tmp_path):
    """The full shell in replay mode: writes local pprofs, serves HTTP."""
    from parca_agent_tpu.capture.formats import save_snapshot
    from parca_agent_tpu.cli import run

    snap_path = tmp_path / "w.snap"
    save_snapshot(_snap(), str(snap_path))
    out_dir = tmp_path / "profiles"
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("relabel_configs:\n- action: labeldrop\n  regex: kernel_release\n")

    rc = run([
        "--capture", "replay", "--replay", str(snap_path),
        "--local-store-directory", str(out_dir),
        "--config-path", str(cfg),
        "--http-address", "127.0.0.1:0",
        "--windows", "1",
        "--debuginfo-upload-disable",
        "--node", "testnode",
        "--metadata-external-labels", "env=ci",
    ])
    assert rc == 0
    files = list(out_dir.iterdir())
    assert len(files) == 5
    # Written profiles are valid gzipped pprof with our labels applied.
    from parca_agent_tpu.pprof.builder import parse_pprof

    blob = gzip.decompress(files[0].read_bytes())
    assert parse_pprof(blob).samples
    names = {f.name for f in files}
    assert all("kernel_release" not in n for n in names)  # relabel applied


def test_web_server_endpoints():
    from parca_agent_tpu.agent.listener import MatchingProfileListener
    from parca_agent_tpu.web import AgentHTTPServer

    w = CollectingWriter()
    p = CPUProfiler(source=ReplaySource([_snap()]),
                    aggregator=CPUAggregator(), profile_writer=w)
    p.run_iteration()
    listener = MatchingProfileListener()
    srv = AgentHTTPServer(port=0, profilers=[p], listener=listener)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        status = urllib.request.urlopen(f"{base}/").read().decode()
        assert "parca-agent-tpu" in status and "attempts: 1" in status
        metrics = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert 'parca_agent_profiler_attempts_total{profiler="cpu"} 1' in metrics
        assert urllib.request.urlopen(f"{base}/healthy").status == 200

        got = {}

        def fetch():
            req = urllib.request.urlopen(f"{base}/query?pid=9&timeout=5")
            got["labels"] = json.loads(req.headers["X-Profile-Labels"])["labels"]
            got["body"] = req.read()

        t = threading.Thread(target=fetch)
        t.start()
        import time

        time.sleep(0.2)
        listener.write_raw({"pid": "9"}, b"sample-bytes")
        t.join(timeout=5)
        assert got["body"] == b"sample-bytes" and got["labels"]["pid"] == "9"
    finally:
        srv.stop()


def test_cli_help_and_flags():
    from parca_agent_tpu.cli import build_parser

    p = build_parser()
    args = p.parse_args(["--aggregator", "dict", "--profiling-duration", "5"])
    assert args.aggregator == "dict" and args.profiling_duration == 5.0
    for refused in ("gpu", "tpu"):
        with pytest.raises(SystemExit):
            p.parse_args(["--aggregator", refused])


def test_status_page_renders_process_errors():
    from parca_agent_tpu.web import render_status_page

    p = CPUProfiler(source=ReplaySource([]), aggregator=CPUAggregator())
    p.process_last_errors[12] = None
    p.process_last_errors[13] = RuntimeError("unwind failed")
    html_out = render_status_page([p])
    assert "12" in html_out and "unwind failed" in html_out


def test_buildinfo_collects_and_never_raises(monkeypatch):
    """Buildinfo (reference pkg/buildinfo analog): git metadata in a
    checkout, env stamping in containers, bare version otherwise."""
    import parca_agent_tpu.buildinfo as bi

    bi.collect.cache_clear()
    info = bi.collect()
    assert info.version
    assert info.display().startswith(info.version)
    # Env stamping wins over git probing (container images).
    bi.collect.cache_clear()
    monkeypatch.setenv("PARCA_AGENT_VCS_REVISION", "f" * 40)
    info2 = bi.collect()
    assert info2.vcs_revision == "f" * 40
    assert "ffffffffffff" in info2.display()
    m = info2.as_metrics()
    assert m["revision"] == "f" * 40 and m["version"] == info2.version
    bi.collect.cache_clear()


def test_cli_sharded_aggregator_replay(tmp_path):
    """--aggregator sharded over the virtual 8-device mesh, through the
    full shell in replay mode with the fast encoder."""
    from parca_agent_tpu.capture.formats import save_snapshot
    from parca_agent_tpu.cli import run
    from parca_agent_tpu.pprof.builder import parse_pprof

    snap = _snap(seed=8)
    snap_path = tmp_path / "w.snap"
    save_snapshot(snap, str(snap_path))
    out = tmp_path / "profiles"
    rc = run(["--capture", "replay", "--replay", str(snap_path),
              "--local-store-directory", str(out),
              "--aggregator", "sharded", "--fast-encode",
              "--http-address", "127.0.0.1:0", "--windows", "1",
              "--debuginfo-upload-disable", "--node", "n"])
    assert rc == 0
    tot = 0
    for f in out.iterdir():
        p = parse_pprof(gzip.decompress(f.read_bytes()))
        tot += sum(v[0] for _, v, _ in p.samples)
    assert tot == snap.total_samples()


def test_cli_reference_parity_flags_parse():
    """Round-5 flag-parity additions parse and land in the expected
    destinations (reference main.go flags struct)."""
    from parca_agent_tpu.cli import build_parser

    args = build_parser().parse_args([
        "--remote-store-insecure-skip-verify",
        "--debuginfo-directories", "/usr/lib/debug,/opt/debug",
        "--no-debuginfo-strip",
        "--debuginfo-upload-cache-duration", "120",
        "--debuginfo-upload-timeout", "30",
        "--metadata-container-runtime-socket-path", "/run/x.sock",
        "--debug-process-names", "nginx.*,redis",
    ])
    assert args.remote_store_insecure_skip_verify is True
    assert args.debuginfo_directories == "/usr/lib/debug,/opt/debug"
    assert args.debuginfo_strip is False
    assert args.debuginfo_upload_cache_duration == 120.0
    assert args.debuginfo_upload_timeout == 30.0
    assert args.metadata_container_runtime_socket_path == "/run/x.sock"
    assert args.debug_process_names == "nginx.*,redis"
    # Defaults mirror the reference's.
    d = build_parser().parse_args([])
    assert d.debuginfo_strip is True
    assert d.debuginfo_upload_cache_duration == 300.0
    assert d.debuginfo_upload_timeout == 120.0
