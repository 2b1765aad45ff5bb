"""A population whose pids turn over: before every window but the first,
a share ``turnover`` of the pid slots (at least one) is replaced: new
pid numbers, a new executable base, new stacks, taking over the left
pids' ranks in the weight vector. Slots leave in a seeded order. How
many stacks a leaving pid owned is as the seed drew it, so the number of
new stacks differs from window to window. Every window is distinct."""

import numpy as np

from .. import generate


class TurnoverSequence(generate.PopulationSequence):
    def __init__(self, pop, seed: int, turnover: float):
        super().__init__(pop, seed)
        self.turnover_pids = max(1, round(pop.pids * turnover))
        self._leave_pos = 0
        self._next_pid = int(self.pid_of_slot.max()) + 1

    def _before_window(self, index: int) -> None:
        if index == 0:
            return
        turn, pop = self.turnover_pids, self.pop
        rng = np.random.default_rng([self.seed, index, 1])
        pos = (self._leave_pos + np.arange(turn)) % pop.pids
        slots = self.slot_order[pos]
        self._leave_pos = int((self._leave_pos + turn) % pop.pids)
        self.pid_of_slot[slots] = np.arange(
            self._next_pid, self._next_pid + turn, dtype=np.int32)
        self._next_pid += turn
        self.exe_of_slot[slots] = self.exe_bases(rng, turn)
        rows = np.flatnonzero(np.isin(self.slot_of_row, slots))
        st, ul, kl = generate.new_rows(
            rng, pop, self.exe_of_slot[self.slot_of_row[rows]])
        self.stacks[rows] = st
        self.user_len[rows] = ul
        self.kernel_len[rows] = kl


def sequence(pop, args: dict, seed: int):
    return TurnoverSequence(pop, seed, float(args["turnover"]))


def distinct_windows(args: dict, needed: int) -> int:
    return needed


def replay_order(args: dict, needed: int) -> list[int]:
    return list(range(needed))
