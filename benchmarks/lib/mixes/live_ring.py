"""``ring`` over pids that live: the stationary population of ``ring``,
every pid slot a process of this machine (``lib/idle_pool.py``).

The seed fixes the population's rows, ownership, weights and counts as
under ``ring``; it no longer fixes the pid *numbers*, which are whatever
the kernel gave the pool's processes. The pool is started once per
process, by the first ``sequence`` call (in a run: before the windows
are generated, so before the agent and JAX are imported), and every
later call of the same size gets the same pids, so the windows the
output check regenerates are the windows the agent was handed. The pids
go to the slots in ascending order, as ``ring`` numbers them. The pool
ends with the process that started it, on any exit (``idle_pool``);
``close_pool()`` ends it earlier, for a process that outlives its run.
"""

import atexit

import numpy as np

from .. import generate, idle_pool
from . import ring

_pool: idle_pool.IdlePool | None = None


def pool(n: int) -> idle_pool.IdlePool:
    """This process's pool of ``n`` idle processes, started on the first
    call; a call for another size ends the pool there is and starts one."""
    global _pool
    if _pool is None or len(_pool.pids) != n:
        close_pool()
        _pool = idle_pool.IdlePool(n)
    return _pool


@atexit.register
def close_pool() -> None:
    global _pool
    if _pool is not None:
        _pool.close()
        _pool = None


def sequence(pop, args: dict, seed: int):
    seq = generate.PopulationSequence(pop, seed)
    seq.pid_of_slot = np.asarray(pool(pop.pids).pids, np.int32)
    return seq


distinct_windows = ring.distinct_windows
replay_order = ring.replay_order
