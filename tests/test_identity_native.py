"""The native stat reader of the pid-identity check.

``process/identity.py`` reads a window's listed pids in one call of
``native/procstat.cc`` when, and only when, it reads the host's
``/proc`` with the procfs reader. This suite pins: that the call gives
what ``read_starttime`` gives, file by file, over a tree of files (the
Python function stays the reference); that over the real ``/proc`` with
live children the tracker settles what the Python loop settles, counts
what it read, opens nothing for a child that is gone and detects a
reuse; that a fake filesystem, a subclass of the real one and an
injected reader never load or call the library; that a library that
cannot be loaded, or a call that reports failure, leaves the same result
by the loop, with one warning and one fall-back counted; and that every
live pid is read in every window, and no other.
"""

import os
import signal
import subprocess

import numpy as np
import pytest

from parca_agent_tpu.process import identity as identity_mod
from parca_agent_tpu.process.identity import (
    ProcessIdentityTracker, native_starttimes, read_starttime)
from parca_agent_tpu.runtime.trace import FlightRecorder
from parca_agent_tpu.utils.poison import OversizedInput
from parca_agent_tpu.utils.vfs import FakeFS, RealFS

CAP = identity_mod._STAT_CAP


@pytest.fixture(scope="module")
def lib():
    lib = identity_mod._load_native()
    if lib is None:
        pytest.skip("native/procstat.cc cannot be built or loaded here")
    return lib


def _line(pid, start, comm="p"):
    """A /proc/<pid>/stat record whose field 22 is ``start``."""
    rest = ["R"] + ["0"] * 18 + [str(start), "0"]
    return f"{pid} ({comm}) {' '.join(rest)}\n".encode()


def _padded(pid, start, size):
    """The record grown to ``size`` bytes by blanks before its newline
    (the parse skips them)."""
    line = _line(pid, start)
    return line[:-1] + b" " * (size - len(line)) + b"\n"


class TreeFS(RealFS):
    """``/proc/...`` served from a directory, through ``RealFS``'s own
    buffered open: what ``read_starttime`` reads when the native call is
    given the same directory as ``root``."""

    def __init__(self, root):
        self.root = str(root)

    def open(self, path):
        assert path.startswith("/proc/")
        return super().open(self.root + path[len("/proc"):])


# name: (what <root>/7 holds, the starttime or the code expected)
STAT_FILES = {
    "a_plain_line": (_line(7, 123456789), 123456789),
    "a_comm_with_parens_and_spaces": (_line(7, 4242, "a ) (b) c"), 4242),
    "no_newline_at_the_end": (_line(7, 99)[:-1], 99),
    "a_second_line_after_the_record": (_line(7, 5) + _line(8, 6), 6),
    "a_plus_sign": (_line(7, "+17"), 17),
    "a_starttime_of_2_to_the_62": (_line(7, 1 << 62), 1 << 62),
    "exactly_the_cap": (_padded(7, 31, CAP), 31),
    "one_byte_over_the_cap": (_padded(7, 31, CAP + 1), -2),
    "far_over_the_cap": (_padded(7, 31, 3 * CAP), -2),
    "no_directory": (None, -1),
    "a_directory_with_no_stat": ("dir", -1),
    "stat_is_a_directory": ("statdir", -1),
    "an_empty_file": (b"", -3),
    "no_closing_paren": (b"7 (p R 0 0 0\n", -3),
    "fewer_than_20_fields_after_it": (
        b"7 (p) R " + b" ".join([b"0"] * 18) + b"\n", -3),
    "a_field_that_is_no_number": (_line(7, "12x"), -3),
    "a_negative_number": (_line(7, -5), -3),
}


@pytest.mark.parametrize("case", sorted(STAT_FILES))
def test_the_native_read_is_read_starttime_file_by_file(case, lib, tmp_path):
    content, want = STAT_FILES[case]
    if content == "dir":
        (tmp_path / "7").mkdir()
    elif content == "statdir":
        (tmp_path / "7" / "stat").mkdir(parents=True)
    elif content is not None:
        (tmp_path / "7").mkdir()
        (tmp_path / "7" / "stat").write_bytes(content)
    # A neighbour that reads, so that one pid's code is its own.
    (tmp_path / "8").mkdir()
    (tmp_path / "8" / "stat").write_bytes(_line(8, 808))
    out = native_starttimes(lib, np.array([7, 8]), root=str(tmp_path))
    assert out.tolist() == [want, 808]
    # The reference: the same file through the Python reader.
    raises = {-1: OSError, -2: OversizedInput,
              -3: (ValueError, IndexError)}
    if case == "a_negative_number":
        # A starttime counts ticks since boot: the call has no negative
        # one to give, where Python's int() would parse the sign.
        assert read_starttime(TreeFS(tmp_path), 7) == -5
    elif want < 0:
        with pytest.raises(raises[want]):
            read_starttime(TreeFS(tmp_path), 7)
    else:
        assert read_starttime(TreeFS(tmp_path), 7) == want


def test_a_call_that_cannot_run_says_so(lib, tmp_path):
    assert native_starttimes(lib, np.array([1]),
                             root="/" + "x" * 5000) is None
    assert native_starttimes(lib, np.array([], np.int64),
                             root=str(tmp_path)).tolist() == []
    # int32 pids (the capture's column) are widened, not reinterpreted.
    (tmp_path / "70000").mkdir()
    (tmp_path / "70000" / "stat").write_bytes(_line(70000, 9))
    assert native_starttimes(lib, np.array([70000, 3], np.int32),
                             root=str(tmp_path)).tolist() == [9, -1]


# -- the real /proc -----------------------------------------------------------

@pytest.fixture
def children():
    """Idle processes of this machine, ended with the test."""
    procs = [subprocess.Popen(["sleep", "300"]) for _ in range(5)]
    try:
        yield procs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)


@pytest.fixture
def asked(monkeypatch):
    """The pids of every native call made, call by call."""
    calls = []
    real = identity_mod.native_starttimes
    monkeypatch.setattr(
        identity_mod, "native_starttimes",
        lambda lib, p, *a: calls.append(p.tolist()) or real(lib, p, *a))
    return calls


def _window(tracker, pids):
    """One window inside an open ``identity`` span: (reused, meta)."""
    rec = FlightRecorder()
    tr = rec.begin()
    with tr.span("identity"):
        reused = tracker.observe_window(np.asarray(pids, np.int32))
    tr.complete()
    return reused, rec.traces()[0]["meta"]


def test_over_the_real_proc_the_tracker_settles_what_the_loop_reads(
        lib, children):
    pids = sorted(p.pid for p in children)
    t = ProcessIdentityTracker(enabled=True)
    reused, meta = _window(t, pids + pids)
    assert reused == []
    assert t._gens == {p: read_starttime(RealFS(), p) for p in pids}
    assert meta["identity_native_reads"] == meta["identity_stat_reads"] \
        == len(pids)
    assert t.metrics()["checks_total"] == len(pids)
    assert t.native_metrics() == {"reads_total": len(pids),
                                  "fallbacks_total": 0}
    assert t.snapshot()["native"]["reads_total"] == len(pids)
    from parca_agent_tpu.web import render_metrics
    text = render_metrics([], identity=t)
    assert f"parca_agent_pid_identity_native_reads_total {len(pids)}" \
        in text
    assert "parca_agent_pid_identity_native_fallbacks_total 0" in text


def test_a_child_gone_between_two_windows_is_settled_with_no_read(
        lib, children, asked):
    pids = sorted(p.pid for p in children)
    t = ProcessIdentityTracker(enabled=True)
    _window(t, pids)
    gone = children[0]
    gone.send_signal(signal.SIGKILL)
    gone.wait(timeout=10)
    del asked[:]
    before = dict(t._gens)
    reused, meta = _window(t, pids)
    assert reused == []
    left = sorted(set(pids) - {gone.pid})
    assert asked == [left]
    assert (meta["identity_native_reads"], meta["identity_absent"]) \
        == (len(left), 1)
    # The remembered generation is kept: a later process on that number
    # is a reuse.
    assert t._gens == before
    m = t.metrics()
    assert (m["checks_total"], m["absent_total"], m["errors_total"]) \
        == (2 * len(pids) - 1, 1, 1)


def test_a_fresh_process_on_a_remembered_pid_is_a_reuse(lib, children):
    pids = sorted(p.pid for p in children)
    t = ProcessIdentityTracker(enabled=True)
    fired = []
    t.add_invalidator("rec", fired.append)
    _window(t, pids)
    # The harness cannot choose a pid number: the remembered generation
    # of two live pids is set to that of processes that came before.
    t._gens[pids[3]] -= 1
    t._gens[pids[1]] -= 7
    reused, _meta = _window(t, pids)
    assert reused == [pids[1], pids[3]] and fired == reused
    assert t._gens == {p: read_starttime(RealFS(), p) for p in pids}
    assert t.metrics()["reuse_detected_total"] == 2


@pytest.mark.parametrize("n_live", [1, 4])
def test_every_live_pid_is_read_in_every_window_and_no_other(
        lib, children, asked, n_live):
    """The real-/proc twin of ``tests/test_identity.py``
    ``test_every_listed_pid_is_read_in_every_window``: no watermark, no
    cache, no sampling of pids, whatever the windows before read."""
    live = sorted(p.pid for p in children[:n_live])
    unsampled = children[4].pid           # listed, not in the window
    gone = [2 ** 22 + 11, 2 ** 22 + 12]   # over pid_max: never listed
    t = ProcessIdentityTracker(enabled=True)
    col = np.resize(np.asarray(live + gone, np.int32),
                    4 * (n_live + len(gone)))
    for window in range(1, 7):
        reused, meta = _window(t, col)
        assert reused == []
        assert asked[-1] == live and unsampled not in asked[-1]
        assert meta["identity_native_reads"] == len(live)
        assert meta["identity_stat_reads"] == len(live)
        assert meta["identity_absent"] == len(gone)
        assert t.metrics()["checks_total"] == window * len(live)
        assert t.native_metrics()["reads_total"] == window * len(live)
    assert len(asked) == 6


# -- when the native call engages ---------------------------------------------

class _SubclassedRealFS(RealFS):
    """A test's subclass of the host's filesystem is a world of its
    own too."""


def _never_load():
    raise AssertionError("the library was asked for")


class _SubclassedFakeFS(FakeFS):
    pass


def _world_tracker(kind, world):
    if kind == "a_subclass_of_the_real":
        return ProcessIdentityTracker(fs=_SubclassedRealFS(), enabled=True)
    if kind == "an_injected_reader":
        return ProcessIdentityTracker(starttime_of=world.__getitem__,
                                      fs=RealFS(), enabled=True)
    fake = {"a_fake_filesystem": FakeFS,
            "a_subclass_of_the_fake": _SubclassedFakeFS}[kind]
    return ProcessIdentityTracker(enabled=True, fs=fake(
        {f"/proc/{p}/stat": _line(p, s) for p, s in world.items()}))


@pytest.mark.parametrize("kind", [
    "a_fake_filesystem", "a_subclass_of_the_fake",
    "a_subclass_of_the_real", "an_injected_reader"])
def test_any_world_but_the_hosts_proc_never_touches_the_library(
        kind, monkeypatch):
    monkeypatch.setattr(identity_mod, "_load_native", _never_load)
    monkeypatch.setattr(identity_mod, "native_starttimes",
                        lambda *a: _never_load())
    if kind == "a_subclass_of_the_real":
        world = {os.getpid(): None, os.getppid(): None}  # live, listed
    else:
        world = {10: 100, 11: 200}
    t = _world_tracker(kind, world)
    reused, meta = _window(t, sorted(world))
    assert reused == []
    assert meta["identity_stat_reads"] == len(world)
    assert meta["identity_native_reads"] == 0
    assert t.metrics()["checks_total"] == len(world)
    assert t.native_metrics() == {"reads_total": 0, "fallbacks_total": 0}


def test_a_window_with_no_listed_pid_never_touches_the_library(monkeypatch):
    # Every cell whose pids are no processes of the machine: the listing
    # settles them all and nothing is left to read.
    monkeypatch.setattr(identity_mod, "_load_native", _never_load)
    t = ProcessIdentityTracker(enabled=True)
    reused, meta = _window(t, [2 ** 22 + 1, 2 ** 22 + 2])
    assert reused == []
    assert (meta["identity_absent"], meta["identity_stat_reads"],
            meta["identity_native_reads"]) == (2, 0, 0)


def _two_windows_with_a_reuse(t, pids):
    """Two windows over live pids with one remembered generation aged
    between them: what the tracker leaves, to compare paths by."""
    _window(t, pids)
    t._gens[pids[2]] -= 1
    reused, meta = _window(t, pids)
    return (reused, dict(t._gens), t.metrics(),
            meta["identity_stat_reads"], meta["identity_absent"])


def test_a_library_that_cannot_load_leaves_the_loop_and_one_warning(
        lib, children, monkeypatch):
    pids = sorted(p.pid for p in children)
    want = _two_windows_with_a_reuse(
        ProcessIdentityTracker(enabled=True), pids)
    assert want[0] == [pids[2]]

    def no_compiler(*_a, **_k):
        raise RuntimeError("native build failed:\nmake: g++: not found")
    import parca_agent_tpu.native as native_pkg
    monkeypatch.setattr(native_pkg, "ensure_built", no_compiler)
    monkeypatch.setattr(identity_mod, "_native", False)
    warned = []

    class Log:
        def warn(self, msg, **kw):
            warned.append((msg, kw))
    monkeypatch.setattr(identity_mod, "get_logger", lambda name: Log())
    t = ProcessIdentityTracker(enabled=True)
    assert _two_windows_with_a_reuse(t, pids) == want
    assert len(warned) == 1 and "g++: not found" in warned[0][1]["error"]
    # Nothing loaded, so nothing fell back: the loop is this host's path.
    assert t.native_metrics() == {"reads_total": 0, "fallbacks_total": 0}


def test_a_call_that_reports_failure_falls_back_for_that_window(
        lib, children, monkeypatch):
    pids = sorted(p.pid for p in children)
    want = _two_windows_with_a_reuse(
        ProcessIdentityTracker(enabled=True), pids)
    calls = []

    class Failing:
        @staticmethod
        def pa_read_starttimes(*args):
            calls.append(args)
            return -1
    monkeypatch.setattr(identity_mod, "_native", Failing)
    t = ProcessIdentityTracker(enabled=True)
    _window(t, pids)
    assert t.native_metrics() == {"reads_total": 0, "fallbacks_total": 1}
    # The next window asks the library again: the fall-back is a
    # window's, not the process's.
    monkeypatch.setattr(identity_mod, "_native", lib)
    t._gens[pids[2]] -= 1
    reused, meta = _window(t, pids)
    assert len(calls) == 1
    assert (reused, dict(t._gens), t.metrics(),
            meta["identity_stat_reads"], meta["identity_absent"]) == want
    assert t.native_metrics() == {"reads_total": len(pids),
                                  "fallbacks_total": 1}


def test_the_failed_listing_reads_every_distinct_pid_through_the_call(
        lib, children, monkeypatch):
    pids = sorted(p.pid for p in children)
    gone = 2 ** 22 + 5

    def refused(self, path):
        raise PermissionError(path)
    monkeypatch.setattr(RealFS, "listdir", refused)
    t = ProcessIdentityTracker(enabled=True)
    reused, meta = _window(t, pids + [gone])
    assert reused == []
    assert (meta["identity_stat_reads"], meta["identity_absent"],
            meta["identity_native_reads"]) == (len(pids) + 1, 0, len(pids))
    m = t.metrics()
    assert (m["checks_total"], m["errors_total"], m["absent_total"]) \
        == (len(pids), 1, 0)
    assert os.path.exists(f"/proc/{pids[0]}/stat")
