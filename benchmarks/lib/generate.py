"""Window generator: a seeded sequence of capture windows for one cell.

The benchmark's own copy of the shapes of the program's
``capture/synthetic.py`` ``generate`` (128-slot rows, Poisson user depth,
a share of rows with a kernel tail of 1..16 frames, a few executable
mappings per pid with the shared objects common to every pid,
Zipf-weighted counts with no empty row), extended from one window to a
sequence: the population of (pid, stack) rows persists from window to
window and every window redraws its counts from the fixed weights.
What a traffic mix does to the population from window to window is the
mix's own module (``lib/mixes/<generator>.py``, named by
``traffic/<mix>.json``); this file is what every mix shares. Numpy only.

The deployment's sizes come from ``configs/<name>.json`` (its top-level
sizes); nothing here knows a name.
"""

from __future__ import annotations

import dataclasses

import numpy as np

STACK_SLOTS = 128
MAX_STACK_DEPTH = 127
KERNEL_ADDR_START = 0xFFFF_8000_0000_0000
_MAP_SIZE = 1 << 24            # 16 MiB of text per mapping
_EXE_BASE = 0x0000_5500_0000_0000
_SHARED_BASE = 0x0000_7F00_0000_0000
_TIME0_NS = 1_700_000_000_000_000_000
_FIRST_PID = 1000


@dataclasses.dataclass(frozen=True)
class Population:
    """A deployment's sizes (the top-level sizes of a config file)."""

    pids: int
    stacks: int
    samples_per_window: int
    mean_depth: int = 24
    kernel_fraction: float = 0.2
    max_kernel_depth: int = 16
    mappings_per_pid: int = 4
    funcs_per_object: int = 4096
    zipf_exponent: float = 1.1
    window_s: float = 10.0
    sample_hz: int = 100

    @staticmethod
    def from_config(cfg: dict, sizes: dict | None = None) -> "Population":
        """The sizes a config file states at its top level; ``sizes``
        (a rehearsal's) override them."""
        merged = {**cfg, **(sizes or {})}
        return Population(**{f.name: merged[f.name]
                             for f in dataclasses.fields(Population)
                             if f.name in merged})


@dataclasses.dataclass
class Window:
    """One capture window, as columns (the snapshot container's layout)."""

    index: int
    time_ns: int
    period_ns: int
    window_ns: int
    pids: np.ndarray        # int32 [N]
    counts: np.ndarray      # int64 [N]
    user_len: np.ndarray    # int32 [N]
    kernel_len: np.ndarray  # int32 [N]
    stacks: np.ndarray      # uint64 [N, 128]
    map_pids: np.ndarray    # int32 [M], sorted by (pid, start)
    map_starts: np.ndarray  # uint64 [M]
    map_ends: np.ndarray
    map_offsets: np.ndarray
    map_objs: np.ndarray    # int32 [M]
    obj_paths: tuple
    obj_buildids: tuple

    def total_samples(self) -> int:
        return int(self.counts.sum())


def new_rows(rng: np.random.Generator, pop: Population, exe_base: np.ndarray):
    """``len(exe_base)`` fresh stack rows: (stacks, user_len, kernel_len).
    ``exe_base[i]`` is the main executable's base in row i's process."""
    n = len(exe_base)
    n_shared = pop.mappings_per_pid - 1
    depth = np.clip(rng.poisson(pop.mean_depth, n), 2,
                    MAX_STACK_DEPTH - pop.max_kernel_depth).astype(np.int32)
    # One draw per frame: the low bits pick the object, the next the
    # function inside it. Columns past the deepest row stay zero.
    deepest = int(depth.max(initial=0))
    r = rng.integers(0, 1 << 40, (n, deepest), dtype=np.uint64)
    which = (r % np.uint64(n_shared + 1)).astype(np.int64)
    func = (r >> np.uint64(8)) % np.uint64(pop.funcs_per_object)
    off = ((func << np.uint64(8)) + np.uint64(0x40)) % np.uint64(_MAP_SIZE)
    shared = np.uint64(_SHARED_BASE) + (
        np.arange(n_shared, dtype=np.uint64) << np.uint64(28))
    base = np.where(which == 0, exe_base[:, None],
                    shared[np.clip(which - 1, 0, n_shared - 1)])
    slot = np.arange(deepest, dtype=np.int32)[None, :]
    stacks = np.zeros((n, STACK_SLOTS), np.uint64)
    stacks[:, :deepest] = np.where(slot < depth[:, None], base + off,
                                   np.uint64(0))
    del r, which, func, off, base
    has_k = rng.random(n) < pop.kernel_fraction
    kdepth = np.where(has_k, rng.integers(1, pop.max_kernel_depth + 1, n),
                      0).astype(np.int32)
    kaddr = np.uint64(KERNEL_ADDR_START) + (
        rng.integers(0, 65536, (n, pop.max_kernel_depth), dtype=np.uint64)
        << np.uint64(6))
    rows_k = np.flatnonzero(has_k)
    for j in range(pop.max_kernel_depth):
        sel = rows_k[kdepth[rows_k] > j]
        stacks[sel, depth[sel] + j] = kaddr[sel, j]
    return stacks, depth, kdepth


def _draw_counts(rng: np.random.Generator, weights: np.ndarray,
                 total: int) -> np.ndarray:
    """Multinomial counts over the fixed weights with no empty row: a
    capture map never holds a zero-count entry, so a row that drew zero
    gets 1 and the excess is taken back from the heaviest rows. The
    window's mass is exactly ``total``."""
    if total < len(weights):
        raise ValueError("samples_per_window must be >= distinct stacks")
    counts = np.maximum(rng.multinomial(total, weights), 1).astype(np.int64)
    excess = int(counts.sum()) - total
    if excess > 0:
        for i in np.argsort(counts)[::-1]:
            take = min(excess, int(counts[i]) - 1)
            counts[i] -= take
            excess -= take
            if excess == 0:
                break
    return counts


class PopulationSequence:
    """Windows 0, 1, 2, ... of one (population, seed), in order: a
    stationary population whose counts are redrawn every window.

    Row r of every window is the stack of rank r in the weight vector
    and belongs to pid slot ``slot_of_row[r]``. Every slot owns one row
    and the rest fall as the seed draws them, so the stacks a pid owns
    differ from pid to pid and from seed to seed. A mix that changes the
    population overrides ``_before_window``. ``next()`` advances one
    window and returns it; the returned arrays are the sequence's own and
    may be overwritten by the next call, so a caller that keeps a window
    copies what it needs.
    """

    def __init__(self, pop: Population, seed: int):
        if pop.mappings_per_pid < 2:
            raise ValueError("mappings_per_pid must be >= 2")
        if pop.stacks < pop.pids:
            raise ValueError("stacks must be >= pids (every pid is sampled)")
        self.pop, self.seed = pop, int(seed)
        rng = np.random.default_rng([self.seed, 0])
        # A seeded order of the slots (a mix may use it), then the rows.
        self.slot_order = rng.permutation(pop.pids)
        extra = self.slot_order[rng.integers(0, pop.pids,
                                             pop.stacks - pop.pids)]
        self.slot_of_row = rng.permutation(
            np.concatenate([self.slot_order, extra]))
        w = 1.0 / np.arange(1, pop.stacks + 1, dtype=np.float64) \
            ** pop.zipf_exponent
        self.weights = w / w.sum()
        self.pid_of_slot = (_FIRST_PID
                            + np.arange(pop.pids)).astype(np.int32)
        self.exe_of_slot = self.exe_bases(rng, pop.pids)
        self.stacks, self.user_len, self.kernel_len = new_rows(
            rng, pop, self.exe_of_slot[self.slot_of_row])
        self._index = -1
        n_shared = pop.mappings_per_pid - 1
        self.obj_paths = ("/app/bin/worker",) + tuple(
            f"/usr/lib/libshared{i}.so" for i in range(n_shared))
        self.obj_buildids = tuple(
            f"{i:040x}" for i in range(1, len(self.obj_paths) + 1))

    def _before_window(self, index: int) -> None:
        """What the mix does to the population before window ``index``."""

    @staticmethod
    def exe_bases(rng, n: int) -> np.ndarray:
        return np.uint64(_EXE_BASE) + (
            rng.integers(0, 1 << 20, n, dtype=np.uint64) << np.uint64(12))

    def _mappings(self):
        pop = self.pop
        per = pop.mappings_per_pid
        order = np.argsort(self.pid_of_slot, kind="stable")
        pid = self.pid_of_slot[order]
        exe = self.exe_of_slot[order]
        m = len(pid) * per
        starts = np.zeros(m, np.uint64)
        objs = np.zeros(m, np.int32)
        offsets = np.zeros(m, np.uint64)
        for j in range(per):
            sl = slice(j, m, per)
            starts[sl] = exe if j == 0 else np.uint64(_SHARED_BASE) + (
                np.uint64(j - 1) << np.uint64(28))
            objs[sl] = j
            offsets[sl] = np.uint64(0x1000 * j)
        # The executable's base (0x55..) sorts below the shared objects'
        # (0x7f..), so (pid, start) order is the order built above.
        return (np.repeat(pid, per).astype(np.int32), starts,
                starts + np.uint64(_MAP_SIZE), offsets, objs)

    def next(self) -> Window:
        self._index += 1
        k = self._index
        self._before_window(k)
        counts = _draw_counts(np.random.default_rng([self.seed, k, 2]),
                              self.weights, self.pop.samples_per_window)
        mp, ms, me, mo, mj = self._mappings()
        pop = self.pop
        window_ns = int(pop.window_s * 1e9)
        return Window(
            index=k, time_ns=_TIME0_NS + k * window_ns,
            period_ns=int(1e9 // pop.sample_hz), window_ns=window_ns,
            pids=self.pid_of_slot[self.slot_of_row], counts=counts,
            user_len=self.user_len, kernel_len=self.kernel_len,
            stacks=self.stacks, map_pids=mp, map_starts=ms, map_ends=me,
            map_offsets=mo, map_objs=mj, obj_paths=self.obj_paths,
            obj_buildids=self.obj_buildids)
