import io

import numpy as np
import pytest

from parca_agent_tpu.capture.formats import (
    MAX_STACK_DEPTH,
    STACK_SLOTS,
    MappingTable,
    WindowSnapshot,
    load_snapshot,
    save_snapshot,
)
from parca_agent_tpu.capture.replay import ReplaySource
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate


def tiny_snapshot() -> WindowSnapshot:
    stacks = np.zeros((2, STACK_SLOTS), np.uint64)
    stacks[0, :3] = [0x1000, 0x2000, 0x3000]
    stacks[1, :2] = [0x1000, 0xFFFF_8000_0000_1000]
    table = MappingTable(
        pids=[7, 7],
        starts=[0x0, 0x10000],
        ends=[0x10000, 0x20000],
        offsets=[0, 0],
        objs=[0, 0],
        obj_paths=("/bin/x",),
        obj_buildids=("ab" * 20,),
    )
    return WindowSnapshot(
        pids=[7, 7], tids=[7, 8], counts=[5, 1],
        user_len=[3, 1], kernel_len=[0, 1], stacks=stacks, mappings=table,
    )


def test_roundtrip_bytes():
    snap = tiny_snapshot()
    buf = io.BytesIO()
    save_snapshot(snap, buf)
    got = load_snapshot(io.BytesIO(buf.getvalue()))
    assert np.array_equal(got.pids, snap.pids)
    assert np.array_equal(got.counts, snap.counts)
    assert np.array_equal(got.stacks, snap.stacks)
    assert got.mappings.obj_paths == ("/bin/x",)
    assert got.period_ns == snap.period_ns
    got.validate_padding()


def test_roundtrip_file(tmp_path):
    snap = tiny_snapshot()
    p = tmp_path / "w0.snap"
    save_snapshot(snap, p)
    got = load_snapshot(p)
    assert got.total_samples() == 6
    assert np.array_equal(got.mappings.starts, snap.mappings.starts)


def test_shape_validation():
    with pytest.raises(ValueError):
        WindowSnapshot(
            pids=[1], tids=[1], counts=[1], user_len=[1], kernel_len=[0],
            stacks=np.zeros((1, 64), np.uint64), mappings=MappingTable.empty(),
        )
    with pytest.raises(ValueError):
        WindowSnapshot(
            pids=[1], tids=[1], counts=[1],
            user_len=[MAX_STACK_DEPTH], kernel_len=[1],
            stacks=np.zeros((1, STACK_SLOTS), np.uint64),
            mappings=MappingTable.empty(),
        )


def test_mapping_sort_enforced():
    with pytest.raises(ValueError):
        MappingTable(
            pids=[2, 1], starts=[0, 0], ends=[1, 1], offsets=[0, 0], objs=[0, 0]
        )


def test_mapping_overlap_rejected():
    with pytest.raises(ValueError, match="overlap"):
        MappingTable(
            pids=[1, 1], starts=[0x1000, 0x2000], ends=[0x3000, 0x4000],
            offsets=[0, 0], objs=[0, 0],
        )
    with pytest.raises(ValueError, match="precedes"):
        MappingTable(pids=[1], starts=[0x2000], ends=[0x1000], offsets=[0], objs=[0])
    # different pids may reuse overlapping ranges (shared libraries do)
    MappingTable(
        pids=[1, 2], starts=[0x1000, 0x1000], ends=[0x3000, 0x3000],
        offsets=[0, 0], objs=[0, 0],
    )


def test_bad_magic():
    with pytest.raises(ValueError):
        load_snapshot(io.BytesIO(b"NOTASNAP" + b"\x00" * 16))


def test_synthetic_deterministic_and_valid():
    spec = SyntheticSpec(n_pids=20, n_unique_stacks=200, total_samples=5000, seed=3)
    a = generate(spec)
    b = generate(spec)
    assert np.array_equal(a.stacks, b.stacks)
    assert np.array_equal(a.counts, b.counts)
    a.validate_padding()
    assert len(a) <= 200
    assert a.total_samples() == 5000
    # every user frame falls inside some mapping of its pid
    mt = a.mappings
    for i in range(min(len(a), 32)):
        pid = int(a.pids[i])
        rows = mt.rows_for_pid(pid)
        for j in range(int(a.user_len[i])):
            addr = int(a.stacks[i, j])
            assert any(
                int(mt.starts[r]) <= addr < int(mt.ends[r]) for r in rows
            ), f"row {i} frame {j} addr {addr:#x} unmapped"


def test_synthetic_n_funcs_controls_location_entropy():
    """The n_funcs knob sets per-object function-pool size: small pools
    model real hosts (a pid's hot frames repeat across its stacks),
    large pools are the adversarial near-all-unique case for location
    dedup (docs/perf.md "Secondary boundaries")."""

    def uniq_pid_frames(snap):
        pids = np.repeat(snap.pids.astype(np.uint64), snap.stacks.shape[1])
        frames = snap.stacks.reshape(-1)
        live = frames != 0
        return len(np.unique(
            (pids[live] << np.uint64(1)) ^ frames[live] * np.uint64(3)))

    base = dict(n_pids=50, n_unique_stacks=2000, total_samples=10000,
                mean_depth=16, seed=5)
    shared = generate(SyntheticSpec(n_funcs=16, **base))
    advers = generate(SyntheticSpec(n_funcs=4096, **base))
    assert uniq_pid_frames(shared) * 4 < uniq_pid_frames(advers)
    shared.validate_padding()


def test_synthetic_kernel_frames_live_high():
    a = generate(SyntheticSpec(n_pids=10, n_unique_stacks=100, kernel_fraction=1.0, seed=1))
    assert (a.kernel_len > 0).any()
    for i in range(len(a)):
        ul, kl = int(a.user_len[i]), int(a.kernel_len[i])
        assert all(int(a.stacks[i, ul + j]) >= 0xFFFF_8000_0000_0000 for j in range(kl))
        assert all(int(a.stacks[i, j]) < 0xFFFF_8000_0000_0000 for j in range(ul))


def test_replay_source(tmp_path):
    snap = tiny_snapshot()
    p = tmp_path / "a.snap"
    save_snapshot(snap, p)
    src = ReplaySource([p, snap])
    outs = list(src)
    assert len(outs) == 2
    assert src.poll() is None
    assert np.array_equal(outs[0].stacks, outs[1].stacks)
