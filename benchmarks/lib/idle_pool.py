#!/usr/bin/env python3
"""A pool of processes that do nothing, so that a window's pids are
processes of this machine.

The agent opens every window with ``process/identity.py``
``observe_window``: one listing of ``/proc``, then one read of
``/proc/<pid>/stat`` for every listed pid of the window. A generated pid
number (1000, 1001, ...) is no process, is settled absent by the listing
and costs no read; a node's pids live. ``IdlePool(n)`` starts ``n``
processes whose numbers a generator hands to the window sequence
(``lib/mixes/live_ring.py``).

They must not outlive the run on any exit of the harness, SIGKILL
included, or they would lengthen ``/proc`` for every later run on the
machine. So nothing has to be *done* to end them: this file, run as a
helper (``python3 idle_pool.py <n> --owner=<pid>``), spawns ``n``
``cat``s that read the helper's stdin, a pipe whose only write end the
harness holds. The harness's death, however it comes, is their end of
file. The helper prints ``{"pids": [...]}``, waits for the same end of
file, reaps its children and exits, so ``close()`` returns when no
process of the pool is left. A child blocked in ``read`` takes no CPU.
It is a ``cat`` and no fork of the helper because of what a process
costs on the chip's host (gVisor; my chip run, PR 42): a forked Python
9 MB and 9 ms, 3.7 GB for 400, a ``cat`` 0.1 MB. Its ``argv[0]`` is the
helper's mark with the harness's pid, for whoever counts what a run
left behind.
"""

from __future__ import annotations

import json
import os
import select
import shutil
import subprocess
import sys

START_TIMEOUT_S, CLOSE_TIMEOUT_S = 120.0, 60.0


class IdlePool:
    """``n`` idle processes, children of one helper; ``pids`` ascending."""

    def __init__(self, n: int):
        self._proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), str(int(n)),
             f"--owner={os.getpid()}"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        # The helper says nothing until its last child is started: a
        # machine that cannot start them in time is an error, not a hang.
        ready, _, _ = select.select([self._proc.stdout], [], [],
                                    START_TIMEOUT_S)
        line = self._proc.stdout.readline() if ready else b""
        self._proc.stdout.close()
        try:
            self.pids = sorted(json.loads(line)["pids"])
        except (ValueError, KeyError):
            self.close()
            raise RuntimeError(
                f"the idle pool's helper started no {n} processes in "
                f"{START_TIMEOUT_S:g} s (exit {self._proc.returncode}): "
                f"{line[:200]!r}") from None

    def close(self) -> None:
        """End the pool and wait until its last process is gone."""
        if self._proc.stdin and not self._proc.stdin.closed:
            self._proc.stdin.close()
        try:
            self._proc.wait(timeout=CLOSE_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            self._proc.kill()
            self._proc.wait()


def helper(n: int, mark: str) -> int:
    """Spawn ``n`` ``cat``s on this process's stdin, each with ``mark``
    as its ``argv[0]``; say their pids; read stdin to its end; reap
    them."""
    cat = shutil.which("cat")
    quiet = [(os.POSIX_SPAWN_OPEN, 1, os.devnull, os.O_WRONLY, 0)]
    pids = []
    try:
        if cat is None:
            raise FileNotFoundError("cat")
        for _ in range(n):
            pids.append(os.posix_spawn(cat, [mark], {}, file_actions=quiet))
    except OSError as e:
        # No cat, or the machine bears no more processes: say how far
        # it got, and leave (those there are end with the owner's pipe).
        print(json.dumps({"error": repr(e), "started": len(pids)}),
              flush=True)
        return 1
    print(json.dumps({"pids": pids}), flush=True)
    os.close(1)
    while os.read(0, 4096):
        pass
    for _ in pids:
        os.wait()
    return 0


if __name__ == "__main__":
    raise SystemExit(helper(int(sys.argv[1]),
                            f"{os.path.basename(__file__)} {sys.argv[2]}"))
