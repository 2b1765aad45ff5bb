"""The reference on a hand-made window, and the comparison's controls."""

import numpy as np
import pytest

from lib import compare, reference
from lib.pprof_write import make_pprof
from lib.generate import Population, Window
from lib.mixes import Mix

STEADY = {"generator": "ring", "args": {"ring": 4}}
ROLLOUT = {"generator": "turnover", "args": {"turnover": 0.01}}

K = 0xFFFF_8000_0000_1000


def _window():
    """Three rows of pid 7 (two of them the same stack), one of pid 9."""
    stacks = np.zeros((4, 128), np.uint64)
    stacks[0, :3] = (0x5500_0000_1040, 0x7F00_0000_2040, K)
    stacks[1, :2] = (0x5500_0000_3040, 0x7F00_0000_2040)
    stacks[2, :3] = stacks[0, :3]
    stacks[3, :1] = (0x5500_0000_1040,)
    return Window(
        index=0, time_ns=1_700_000_000_000_000_000, period_ns=10_000_000,
        window_ns=10_000_000_000,
        pids=np.array([7, 7, 7, 9], np.int32),
        counts=np.array([5, 300, 2, 11], np.int64),
        user_len=np.array([2, 2, 2, 1], np.int32),
        kernel_len=np.array([1, 0, 1, 0], np.int32), stacks=stacks,
        map_pids=np.array([7, 7, 9, 9], np.int32),
        map_starts=np.array([0x5500_0000_0000, 0x7F00_0000_0000] * 2, np.uint64),
        map_ends=np.array([0x5500_0100_0000, 0x7F00_0100_0000] * 2, np.uint64),
        map_offsets=np.array([0, 0x1000] * 2, np.uint64),
        map_objs=np.array([0, 1, 0, 1], np.int32),
        obj_paths=("/app/bin/worker", "/usr/lib/libshared0.so"),
        obj_buildids=("01", "02"))


def test_reference_on_a_hand_made_window():
    w = _window()
    assert reference.pid_totals(w) == {7: 307, 9: 11}
    got = reference.group_by(w, [7, 9])
    assert got == {
        7: {(0x5500_0000_1040, 0x7F00_0000_2040, K): 7,
            (0x5500_0000_3040, 0x7F00_0000_2040): 300},
        9: {(0x5500_0000_1040,): 11}}


def ship(w, alter=None):
    """{pid: pprof} of the window's true aggregate, optionally altered."""
    truth = reference.group_by(w, np.unique(w.pids).tolist())
    if alter is not None:
        truth = alter(truth)
    return {pid: make_pprof(w, pid, stacks) for pid, stacks in truth.items()}


def test_a_faithful_shipment_is_correct():
    w = _window()
    numbers = compare.compare_window(w, ship(w), seed=1, n_sampled=256)
    assert numbers == {k: 0 for k in compare.LIMITS}
    assert compare.verdict(numbers)


def _off_by_one(truth):
    pid = min(truth)
    stack = next(iter(truth[pid]))
    truth[pid][stack] += 1
    return truth


def _merged_on_a_shorter_key(truth):
    """Two stacks of one pid that agree on their second frame become
    one: a key shorter than the stack."""
    out = {}
    for pid, stacks in truth.items():
        merged: dict = {}
        for stack, c in stacks.items():
            key = next((k for k in merged if k[1:2] == stack[1:2]), stack)
            merged[key] = merged.get(key, 0) + c
        out[pid] = merged
    return out


def _dropped_pid(truth):
    truth.pop(max(truth))
    return truth


@pytest.mark.parametrize("alter, moved", [
    (_off_by_one, {"pid_total_mismatches", "mass_gap", "stack_mismatches"}),
    (_merged_on_a_shorter_key, {"stack_mismatches"}),
    (_dropped_pid, {"profiles_missing", "mass_gap", "stack_mismatches"}),
])
def test_the_comparison_fails_on_a_wrong_shipment(alter, moved):
    w = _window()
    numbers = compare.compare_window(w, ship(w, alter), seed=1, n_sampled=256)
    assert not compare.verdict(numbers)
    assert {k for k, v in numbers.items() if v} == moved


def test_a_profile_of_another_window_fails_the_time_check():
    w = _window()
    blobs = ship(w)
    import dataclasses

    later = dataclasses.replace(w, time_ns=w.time_ns + w.window_ns)
    numbers = compare.compare_window(later, blobs, seed=1, n_sampled=256)
    assert numbers["time_mismatches"] == 2 and not compare.verdict(numbers)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
@pytest.mark.parametrize("mix", [STEADY, ROLLOUT], ids=["steady", "rollout"])
def test_control_counts_in_eight_bits_come_out_not_correct(seed, mix):
    """The control at a size a test run can hold: the reference put in
    the program's place with every count carried in 8 bits (the close's
    packed fetch without its overflow sideband). The sound shipment reads
    0 on every number; the control has to fail one."""
    pop = Population(pids=100, stacks=2560, samples_per_window=16000)
    seq = Mix(mix).sequence(pop, seed)
    seq.next()
    w = seq.next()

    def eight_bits(truth):
        return {p: reference.lower_precision(s, 8) for p, s in truth.items()}

    sound = compare.compare_window(w, ship(w), seed, n_sampled=64)
    assert sound == {k: 0 for k in compare.LIMITS}
    control = compare.compare_window(w, ship(w, eight_bits), seed, n_sampled=64)
    assert not compare.verdict(control)
    assert control["mass_gap"] > 0 and control["pid_total_mismatches"] > 0
    # ... and carried only through the sampled pids' stacks, the per-stack
    # comparison alone catches it when a heavy stack is in the sample.
    heavy = int(w.pids[np.argmax(w.counts)])
    truth = reference.group_by(w, [heavy])
    assert compare.stack_mismatches(
        truth, {heavy: reference.lower_precision(truth[heavy], 8)}) > 0
