"""The pool of idle processes and the mix that hands its pids to the
window sequence (``lib/idle_pool.py``, ``lib/mixes/live_ring.py``): the
pids are processes, the same ones for every sequence of one process,
the seed still fixes everything but their numbers, and the end of file
on the pipe the owner holds ends every one of them."""

import os
import signal
import time

import numpy as np
import pytest

from lib import generate, idle_pool, mixes
from lib.mixes import live_ring

POP = generate.Population(pids=24, stacks=96, samples_per_window=960)
LIVE = {"generator": "live_ring", "args": {"ring": 4}}
RING = {"generator": "ring", "args": {"ring": 4}}


@pytest.fixture()
def no_pool_left():
    yield
    live_ring.close_pool()


def _alive(pids) -> list[int]:
    return [p for p in pids if os.path.exists(f"/proc/{p}")]


def _stat_fields(pid: int) -> list[str]:
    """``/proc/<pid>/stat`` from its third field (the state) on."""
    with open(f"/proc/{pid}/stat") as f:
        stat = f.read()
    return stat[stat.rindex(")") + 2:].split()


def _gone_within(pids, seconds: float) -> list[int]:
    deadline = time.monotonic() + seconds
    while (left := _alive(pids)) and time.monotonic() < deadline:
        time.sleep(0.02)
    return left


def test_two_sequences_of_one_process_get_the_same_live_pids(no_pool_left):
    mix = mixes.Mix(LIVE)
    a, b = mix.sequence(POP, 5), mix.sequence(POP, 6)
    pids = a.pid_of_slot.tolist()
    assert pids == b.pid_of_slot.tolist() == sorted(set(pids))
    assert len(pids) == POP.pids and _alive(pids) == pids
    assert a.pid_of_slot.dtype == np.int32
    # What a run's output check regenerates is what the agent was handed.
    w1, w2 = a.next(), mix.sequence(POP, 5).next()
    assert (w1.pids == w2.pids).all() and (w1.counts == w2.counts).all()
    assert (w1.stacks == w2.stacks).all()


def test_the_seed_fixes_everything_but_the_pid_numbers(no_pool_left):
    live = mixes.Mix(LIVE).sequence(POP, 9)
    ring = mixes.Mix(RING).sequence(POP, 9)
    for _ in range(3):
        w, r = live.next(), ring.next()
        # ring's pid of slot i is 1000 + i; the pool's i-th pid takes it.
        assert (w.pids == live.pid_of_slot[r.pids - 1000]).all()
        for col in ("counts", "stacks", "user_len", "kernel_len",
                    "map_starts", "map_ends", "map_offsets", "map_objs"):
            assert (getattr(w, col) == getattr(r, col)).all(), col
        assert (w.time_ns, w.window_ns) == (r.time_ns, r.window_ns)
    assert mixes.Mix(LIVE).distinct_windows(70) == 4
    assert mixes.Mix(LIVE).replay_order(6) == [0, 1, 2, 3, 0, 1]


def test_the_mapping_table_is_sorted_by_pid_and_start(no_pool_left):
    w = mixes.Mix(LIVE).sequence(POP, 3).next()
    key = list(zip(w.map_pids.tolist(), w.map_starts.tolist()))
    assert key == sorted(key) and len(set(key)) == len(key)
    assert sorted(set(w.map_pids.tolist())) == sorted(set(w.pids.tolist()))
    assert len(key) == POP.pids * POP.mappings_per_pid


def test_another_size_ends_the_pool_there_is(no_pool_left):
    first = live_ring.pool(6).pids
    assert live_ring.pool(6).pids == first
    second = live_ring.pool(8).pids
    assert len(second) == 8 and _alive(first) == []
    live_ring.close_pool()
    assert _alive(second) == []
    live_ring.close_pool()      # nothing to end: nothing done


def test_the_children_are_idle_cats_of_the_helper_marked_with_the_owner():
    pool = idle_pool.IdlePool(5)
    try:
        helper = pool._proc.pid
        for pid in pool.pids:
            assert int(_stat_fields(pid)[1]) == helper      # ppid
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                assert f.read() == \
                    f"idle_pool.py --owner={os.getpid()}\0".encode()
            with open(f"/proc/{pid}/comm") as f:
                assert f.read() == "cat\n"
        ticks = []
        for _ in range(2):
            ticks.append(sum(int(t) for pid in pool.pids   # utime + stime
                             for t in _stat_fields(pid)[11:13]))
            time.sleep(0.3)
        assert ticks[0] == ticks[1]      # blocked in read: no CPU
    finally:
        pool.close()
    assert _alive(pool.pids + [helper]) == []


def test_the_end_of_file_ends_the_children_even_without_their_helper():
    """The helper killed first: its children stay (their pipe is still
    held) until the owner lets go, then they end with nobody to tell
    them."""
    pool = idle_pool.IdlePool(5)
    try:
        os.kill(pool._proc.pid, signal.SIGKILL)
        pool._proc.wait(timeout=10)
        time.sleep(0.2)
        assert _alive(pool.pids) == pool.pids
    finally:
        pool.close()
    assert _gone_within(pool.pids, 10.0) == []


def test_a_helper_that_cannot_start_the_pool_says_so(monkeypatch):
    monkeypatch.setattr(idle_pool.sys, "executable", "/bin/false")
    with pytest.raises(RuntimeError, match="started no 3 processes"):
        idle_pool.IdlePool(3)
