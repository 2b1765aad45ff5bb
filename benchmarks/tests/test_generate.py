import numpy as np
import pytest

from lib.generate import Population
from lib.mixes import Mix
from lib.snapfile import snapshot_bytes

STEADY = Mix({"generator": "ring", "args": {"ring": 4}})
ROLLOUT = Mix({"generator": "turnover", "args": {"turnover": 0.01}})
MIXES = pytest.mark.parametrize("mix", [STEADY, ROLLOUT],
                                ids=["steady", "rollout"])

POP = Population(pids=200, stacks=4100, samples_per_window=30000)
BIG_SEED = 2**31 + 12345


def _copy(w):
    return {k: np.array(getattr(w, k), copy=True) for k in
            ("pids", "counts", "user_len", "kernel_len", "stacks",
             "map_pids", "map_starts")}


@MIXES
def test_same_seed_same_windows(mix):
    a, b = mix.sequence(POP, BIG_SEED), mix.sequence(POP, BIG_SEED)
    for _ in range(4):
        wa, wb = a.next(), b.next()
        assert snapshot_bytes(wa) == snapshot_bytes(wb)
    other = mix.sequence(POP, BIG_SEED + 1).next()
    assert not np.array_equal(other.stacks,
                              mix.sequence(POP, BIG_SEED).next().stacks)


@MIXES
def test_mass_shape_and_no_empty_row(mix):
    seq = mix.sequence(POP, 7)
    for _ in range(5):
        w = seq.next()
        assert w.total_samples() == POP.samples_per_window
        assert w.counts.min() >= 1
        assert w.stacks.shape == (POP.stacks, 128)
        assert len(np.unique(w.pids)) == POP.pids
        depth = w.user_len + w.kernel_len
        assert depth.max() <= 127
        live = np.arange(128)[None, :] < depth[:, None]
        assert not np.any(np.where(live, 0, w.stacks))     # zero padding
        assert np.all(np.where(live, w.stacks, 1) != 0)
        # rows are distinct (pid, stack) pairs
        key = np.concatenate([w.pids[:, None].astype(np.uint64), w.stacks], 1)
        assert len(np.unique(key, axis=0)) == POP.stacks
        order = np.lexsort((w.map_starts, w.map_pids))
        assert np.array_equal(order, np.arange(len(order)))


def test_steady_redraws_counts_and_keeps_stacks():
    seq = STEADY.sequence(POP, 11)
    w0 = _copy(seq.next())
    w1 = _copy(seq.next())
    assert np.array_equal(w0["stacks"], w1["stacks"])
    assert np.array_equal(w0["pids"], w1["pids"])
    assert not np.array_equal(w0["counts"], w1["counts"])


@pytest.mark.parametrize("seed", [3, BIG_SEED])
def test_rollout_turnover_is_exact(seed):
    seq = ROLLOUT.sequence(POP, seed)
    turn = seq.turnover_pids
    assert turn == 2
    prev = _copy(seq.next())
    seen = set(np.unique(prev["pids"]).tolist())
    for _ in range(1, 8):
        cur = _copy(seq.next())
        gone = set(np.unique(prev["pids"])) - set(np.unique(cur["pids"]))
        new = set(np.unique(cur["pids"])) - set(np.unique(prev["pids"]))
        assert len(gone) == len(new) == turn
        assert not (new & seen)                   # pid numbers never reused
        seen |= new
        changed = np.flatnonzero(np.any(prev["stacks"] != cur["stacks"], 1))
        # every stack of a pid that left is replaced, and nothing else
        assert set(cur["pids"][changed].tolist()) == new
        assert len(changed) == np.isin(prev["pids"], list(gone)).sum()
        prev = cur


def test_rollout_new_stacks_fall_as_the_seed_draws_them():
    """How many stacks turn over differs from window to window and from
    seed to seed; nothing fits it to the sizes."""
    def new_stacks(seed):
        seq = ROLLOUT.sequence(POP, seed)
        prev = seq.next().stacks.copy()
        out = []
        for _ in range(12):
            cur = seq.next().stacks
            out.append(int(np.any(prev != cur, 1).sum()))
            prev = cur.copy()
        return out

    a, b = new_stacks(3), new_stacks(4)
    assert len(set(a)) > 3 and a != b
    assert a == new_stacks(3)


def test_replay_order_never_repeats_a_window_back_to_back():
    order = STEADY.replay_order(50)
    assert STEADY.distinct_windows(50) == 4
    assert all(a != b for a, b in zip(order, order[1:]))
    assert ROLLOUT.replay_order(5) == [0, 1, 2, 3, 4]


def test_the_agents_loader_reads_the_container():
    formats = pytest.importorskip("parca_agent_tpu.capture.formats")
    import io

    w = ROLLOUT.sequence(POP, 5).next()
    snap = formats.load_snapshot(io.BytesIO(snapshot_bytes(w)))
    snap.validate_padding()
    assert snap.total_samples() == POP.samples_per_window
    assert np.array_equal(snap.stacks, w.stacks)
    assert np.array_equal(snap.mappings.bases, w.map_starts - w.map_offsets)
    assert snap.time_ns == w.time_ns and snap.window_ns == w.window_ns
