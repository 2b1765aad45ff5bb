"""Device (TPU/XLA) aggregation backend — the flagship kernel.

Re-expresses the reference's per-interval profile build (the `obtainProfiles`
hot loop, reference pkg/profiler/cpu/cpu.go:505-718) as ONE jit-compiled XLA
program batched over all PIDs at once:

  1. row hash      — two independent multilinear hashes over the padded
                     stack row (pid, user_len, kernel_len, 128 frames);
  2. stack dedup   — `lax.sort` by (pid, h1, h2), then FULL row comparison
                     between neighbors (a hash collision can therefore never
                     merge two distinct stacks), `segment_sum` of counts;
  3. location dedup— flatten live frames of the unique stacks, sort by
                     (pid, addr_hi, addr_lo), boundary-scan to per-PID
                     1-based location ids, scatter-compact the unique
                     locations into a bounded [L_cap] table (the same
                     bounded-memory role the reference's 250k-row unwind
                     shards play, reference pkg/profiler/cpu/maps.go:40-43);
  4. mapping join  — branchless vectorized binary search of every unique
                     location against the (pid, start)-sorted mapping table
                     (the data-parallel analog of `find_offset_for_pc`,
                     reference bpf/cpu/cpu.bpf.c:302-341).

Addresses travel as (hi, lo) uint32 pairs — TPUs have no native 64-bit
integer datapath, and JAX x64 stays off. The host wrapper does only what
cannot or should not live on device: u64 normalization arithmetic
(addr - start + offset), per-PID profile splitting, and string tables.

Shapes are static per (N_pad, M_pad, L_cap) bucket so recompilation stops
after the first few windows; N is padded to the next power of two.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

from parca_agent_tpu.aggregator.base import PidProfile
from parca_agent_tpu.aggregator.cpu import _pid_mappings
from parca_agent_tpu.capture.formats import (
    KERNEL_ADDR_START,
    STACK_SLOTS,
    MappingTable,
    WindowSnapshot,
    fold_rows_first_seen,
)
from parca_agent_tpu.ops.hashing import fold_u64_rows, multilinear_hash_u32
from parca_agent_tpu.runtime import device_telemetry as dtel

_U32_MAX = 0xFFFFFFFF


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _shift_down(a, fill):
    """[a0, a1, ...] -> [fill, a0, a1, ...] dropping the last element."""
    import jax.numpy as jnp

    return jnp.concatenate([jnp.full(a.shape[:0] + (1,), fill, a.dtype), a[:-1]])


def _lex_le3(a1, a2, a3, b1, b2, b3):
    """(a1,a2,a3) <= (b1,b2,b3) lexicographically, elementwise uint32."""
    return (a1 < b1) | ((a1 == b1) & ((a2 < b2) | ((a2 == b2) & (a3 <= b3))))


@functools.lru_cache(maxsize=4)
def _jitted_kernel():
    import jax

    return jax.jit(
        _window_kernel,
        static_argnames=("n_pad", "l_cap", "m_pad", "f_cap", "hash_locs",
                         "interpret"),
    )


def _window_kernel(
    pid,        # uint32 [N]   (padding rows = U32_MAX)
    cnt,        # int32  [N]   (padding rows = 0)
    ulen,       # int32  [N]
    klen,       # int32  [N]
    shi,        # uint32 [N,S] stack address high halves
    slo,        # uint32 [N,S] stack address low halves
    valid,      # bool   [N]
    map_pid,    # uint32 [M]   (padding rows = U32_MAX)
    map_shi,    # uint32 [M]   mapping start hi
    map_slo,    # uint32 [M]   mapping start lo
    map_ehi,    # uint32 [M]   mapping end hi
    map_elo,    # uint32 [M]   mapping end lo
    *,
    n_pad: int,
    l_cap: int,
    m_pad: int,
    f_cap: int,
    hash_locs: bool = False,
    interpret: bool = True,
):
    import jax
    import jax.numpy as jnp

    n, s = shi.shape

    # ---- 1. row hash ------------------------------------------------------
    lanes = fold_u64_rows(
        shi, slo, extra=[pid, ulen.astype(jnp.uint32), klen.astype(jnp.uint32)]
    )
    h1 = multilinear_hash_u32(lanes, 0)
    h2 = multilinear_hash_u32(lanes, 1)

    # ---- 2. exact stack dedup --------------------------------------------
    pid_s, h1_s, h2_s, perm = jax.lax.sort(
        (pid, h1, h2, jnp.arange(n, dtype=jnp.int32)), num_keys=3, is_stable=True
    )
    cnt_s = cnt[perm]
    ulen_s = ulen[perm]
    klen_s = klen[perm]
    shi_s = shi[perm]
    slo_s = slo[perm]
    valid_s = valid[perm]

    same_meta = (
        (pid_s == _shift_down(pid_s, jnp.uint32(_U32_MAX)))
        & (ulen_s == _shift_down(ulen_s, jnp.int32(-1)))
        & (klen_s == _shift_down(klen_s, jnp.int32(-1)))
    )
    same_stack = jnp.all(
        (shi_s == jnp.concatenate([shi_s[:1], shi_s[:-1]]))
        & (slo_s == jnp.concatenate([slo_s[:1], slo_s[:-1]])),
        axis=1,
    )
    same_stack = same_stack.at[0].set(False)
    new_group = (~(same_meta & same_stack)) & valid_s
    new_group = new_group.at[0].set(valid_s[0])

    group = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    group = jnp.maximum(group, 0)
    n_groups = new_group.astype(jnp.int32).sum()

    values = jax.ops.segment_sum(cnt_s, group, num_segments=n_pad)
    rep_pos = jax.ops.segment_min(
        jnp.arange(n, dtype=jnp.int32), group, num_segments=n_pad
    )
    rep_pos = jnp.minimum(rep_pos, n - 1)  # padded groups -> harmless gather

    out_pid = pid_s[rep_pos]
    out_ulen = ulen_s[rep_pos]
    out_klen = klen_s[rep_pos]
    out_shi = shi_s[rep_pos]
    out_slo = slo_s[rep_pos]
    group_live = jnp.arange(n, dtype=jnp.int32) < n_groups

    # ---- 3. location dedup ------------------------------------------------
    depth = out_ulen + out_klen
    slot = jnp.arange(s, dtype=jnp.int32)[None, :]
    frame_live = (slot < depth[:, None]) & group_live[:, None]

    # Compact the live frames of the unique stacks into a [f_cap] buffer
    # before sorting: the padded [n, 128] frame matrix is ~4-5x dead slots
    # at real stack depths, and sort cost is the kernel's dominant term.
    # f_cap is sized from the EXACT host-side frame count (pack_window_
    # inputs), so the scatter never drops a live frame.
    flat_live = frame_live.reshape(-1)
    tgt = jnp.where(flat_live,
                    jnp.cumsum(flat_live.astype(jnp.int32)) - 1,
                    jnp.int32(f_cap))
    fpid = jnp.full((f_cap,), _U32_MAX, jnp.uint32).at[tgt].set(
        jnp.broadcast_to(out_pid[:, None], (n, s)).reshape(-1), mode="drop")
    fhi = jnp.full((f_cap,), _U32_MAX, jnp.uint32).at[tgt].set(
        out_shi.reshape(-1), mode="drop")
    flo = jnp.full((f_cap,), _U32_MAX, jnp.uint32).at[tgt].set(
        out_slo.reshape(-1), mode="drop")
    fsrc = jnp.full((f_cap,), n * s, jnp.int32).at[tgt].set(
        jnp.arange(n * s, dtype=jnp.int32), mode="drop")

    if hash_locs:
        # Hash-table location dedup (the sub-RTT close PR): every live
        # frame's raw 96-bit (pid, hi, lo) key probes/claims an
        # open-addressing table (the Pallas batch-probe kernel,
        # aggregator/pallas_probe.py) instead of riding the f_cap-lane
        # bitonic sort — the stateless kernel's dominant cost. The sort
        # that remains runs over the cap_loc TABLE entries (~2x unique
        # locations), restoring the sort path's exact output order, so
        # the pprof bytes are identical. Identity is the full key —
        # a probe-base hash collision only lengthens a chain.
        from parca_agent_tpu.aggregator.pallas_probe import (
            make_loc_table_builder,
        )

        cap_loc = 2 * l_cap  # load factor <= 0.5 once l_cap fits n_locs
        base = multilinear_hash_u32(
            jnp.stack([fpid, fhi, flo], axis=-1), 3)
        builder = make_loc_table_builder(f_cap, cap_loc,
                                         interpret=interpret)
        slot, tpid_t, thi_t, tlo_t = builder(fpid, fhi, flo, base)
        flive = fpid != jnp.uint32(_U32_MAX)
        # A live frame that could not place means the table is full
        # (l_cap undersized): report n_locs = l_cap + 1 so the caller's
        # existing doubling retry fires — same contract as the sort
        # path's overflow.
        overflowed = (flive & (slot < 0)).any()
        tslot = jnp.arange(cap_loc, dtype=jnp.int32)
        spid, shi2, slo2, sslot = jax.lax.sort(
            (tpid_t, thi_t, tlo_t, tslot), num_keys=3, is_stable=True)
        tlive = spid != jnp.uint32(_U32_MAX)
        n_locs = jnp.where(overflowed, jnp.int32(l_cap + 1),
                           tlive.astype(jnp.int32).sum())
        loc_seq = jnp.cumsum(tlive.astype(jnp.int32))
        new_pid = (spid != _shift_down(spid, jnp.uint32(_U32_MAX))) & tlive
        new_pid = new_pid.at[0].set(tlive[0])
        pid_seg = jnp.maximum(jnp.cumsum(new_pid.astype(jnp.int32)) - 1, 0)
        pid_first_seq = jax.ops.segment_min(
            jnp.where(tlive, loc_seq, jnp.int32(2**31 - 1)),
            pid_seg,
            num_segments=cap_loc,
        )
        rank_sorted = jnp.where(tlive, loc_seq - pid_first_seq[pid_seg] + 1,
                                0)
        # slot -> per-pid rank (sslot is a permutation of the table), then
        # frame -> rank via each frame's claimed slot.
        rank_by_slot = jnp.zeros((cap_loc,), jnp.int32).at[sslot].set(
            rank_sorted)
        frame_rank = jnp.where(slot >= 0,
                               rank_by_slot[jnp.maximum(slot, 0)], 0)
        loc_ids = (
            jnp.zeros((n * s,), jnp.int32).at[fsrc].set(
                frame_rank, mode="drop").reshape(n, s)
        )
        # Sorted live prefix == the sort path's compacted table (dead
        # entries: pid U32_MAX, hi/lo 0 — identical fills).
        loc_pid = spid[:l_cap]
        loc_hi = shi2[:l_cap]
        loc_lo = slo2[:l_cap]
    else:
        fpid_s, fhi_s, flo_s, fidx = jax.lax.sort(
            (fpid, fhi, flo, fsrc),
            num_keys=3,
            is_stable=True,
        )
        # Liveness is derivable (dead f_cap slots carry the U32_MAX fill
        # pid, real pids are int32-ranged), so it does not ride the sort —
        # the f_cap-lane bitonic sort is this kernel's dominant cost and
        # every dropped array is ~20% of its traffic.
        flive_s = fpid_s != jnp.uint32(_U32_MAX)

        same_loc = (
            (fpid_s == _shift_down(fpid_s, jnp.uint32(_U32_MAX)))
            & (fhi_s == _shift_down(fhi_s, jnp.uint32(0)))
            & (flo_s == _shift_down(flo_s, jnp.uint32(0)))
        )
        same_loc = same_loc.at[0].set(False)
        new_loc = (~same_loc) & flive_s
        new_loc = new_loc.at[0].set(flive_s[0])
        n_locs = new_loc.astype(jnp.int32).sum()

        # Global 1-based location sequence number, constant within a group.
        loc_seq = jnp.cumsum(new_loc.astype(jnp.int32))

        # First loc sequence number within each pid segment -> per-pid rank.
        new_pid = (fpid_s != _shift_down(fpid_s, jnp.uint32(_U32_MAX))) \
            & flive_s
        new_pid = new_pid.at[0].set(flive_s[0])
        pid_seg = jnp.maximum(jnp.cumsum(new_pid.astype(jnp.int32)) - 1, 0)
        pid_first_seq = jax.ops.segment_min(
            jnp.where(flive_s, loc_seq, jnp.int32(2**31 - 1)),
            pid_seg,
            num_segments=n_pad,
        )
        rank = jnp.where(flive_s, loc_seq - pid_first_seq[pid_seg] + 1, 0)

        # Scatter per-frame ranks back to representative-row layout [N, S]
        # (padding entries carry fidx == n*s and drop out).
        loc_ids = (
            jnp.zeros((n * s,), jnp.int32).at[fidx].set(rank, mode="drop")
            .reshape(n, s)
        )

        # Compact the unique locations into the bounded [L_cap] table.
        tgt = jnp.where(new_loc, loc_seq - 1, jnp.int32(l_cap))
        loc_pid = (
            jnp.full((l_cap,), _U32_MAX, jnp.uint32).at[tgt].set(
                fpid_s, mode="drop")
        )
        loc_hi = jnp.zeros((l_cap,), jnp.uint32).at[tgt].set(fhi_s,
                                                             mode="drop")
        loc_lo = jnp.zeros((l_cap,), jnp.uint32).at[tgt].set(flo_s,
                                                             mode="drop")

    # ---- 4. mapping join --------------------------------------------------
    # rank_le[q] = number of mapping rows with key <= (pid, addr); candidate
    # row = rank_le - 1. Branchless binary search, all queries in lockstep.
    steps = max(1, math.ceil(math.log2(m_pad + 1)))

    def body(_, lohi):
        lo_b, hi_b = lohi
        cont = lo_b < hi_b
        mid = jnp.minimum((lo_b + hi_b) // 2, m_pad - 1)
        le = _lex_le3(
            map_pid[mid], map_shi[mid], map_slo[mid], loc_pid, loc_hi, loc_lo
        )
        new_lo = jnp.where(le, mid + 1, lo_b)
        new_hi = jnp.where(le, hi_b, mid)
        return jnp.where(cont, new_lo, lo_b), jnp.where(cont, new_hi, hi_b)

    lo_b = jnp.zeros((l_cap,), jnp.int32)
    hi_b = jnp.full((l_cap,), m_pad, jnp.int32)
    lo_b, hi_b = jax.lax.fori_loop(0, steps, body, (lo_b, hi_b))
    cand = lo_b - 1
    safe = jnp.maximum(cand, 0)
    addr_lt_end = (loc_hi < map_ehi[safe]) | (
        (loc_hi == map_ehi[safe]) & (loc_lo < map_elo[safe])
    )
    hit = (cand >= 0) & (map_pid[safe] == loc_pid) & addr_lt_end
    loc_map_row = jnp.where(hit, safe, jnp.int32(-1))

    return (
        n_groups,
        n_locs,
        out_pid,
        depth,
        values,
        loc_ids,
        loc_pid,
        loc_hi,
        loc_lo,
        loc_map_row,
    )


def shadow_compare(device_profiles, cpu_profiles) -> bool:
    """A/B correctness gate between two aggregations of the SAME window
    (the device-health registry's shadow-window promotion check,
    runtime/device_health.py — the same invariants the bench's A/B
    phases assert): per pid, total sample mass and unique-stack count
    must agree, order-insensitively. A backend that answers promptly but
    WRONGLY (a half-reset dict table after a wedge, a corrupted transfer)
    fails here and stays demoted."""
    def digest(profiles):
        return {int(p.pid): (int(p.total()), int(len(p.values)))
                for p in profiles}

    return digest(device_profiles) == digest(cpu_profiles)


def _coalesce_snapshot_rows(snapshot: WindowSnapshot) -> WindowSnapshot:
    """Fold rows that are EXACT duplicates in everything the kernel
    consumes — (pid, user_len, kernel_len, full padded stack row) — into
    one row with summed counts, in first-occurrence order (capture/
    formats.py fold_rows_first_seen; docs/perf.md "ingest wall").
    Cross-tid repetition is the common source: a 100-thread service
    hands the drain one row per (pid, tid, stack) but the kernel keys
    on (pid, stack), so the fold shrinks the padded upload and every
    sort lane behind it. Identity-preserving by construction — the
    kernel's own dedup would have merged exactly these rows (full-row
    compare), summing the same counts; tids are not packed at all."""
    n = len(snapshot)
    if n < 2:
        return snapshot
    rec = np.empty((n, STACK_SLOTS + 1), np.uint64)
    # pid fits 32 bits, user/kernel lens fit 8 each: one header word.
    rec[:, 0] = (snapshot.pids.astype(np.uint64) << np.uint64(32)) \
        | (snapshot.user_len.astype(np.uint64) << np.uint64(8)) \
        | snapshot.kernel_len.astype(np.uint64)
    rec[:, 1:] = snapshot.stacks
    folded = fold_rows_first_seen(
        np.ascontiguousarray(rec).view(
            np.dtype((np.void, (STACK_SLOTS + 1) * 8))).ravel(),
        snapshot.counts)
    if folded is None:
        return snapshot
    rep, _inv, weights = folded
    return dataclasses.replace(
        snapshot, pids=snapshot.pids[rep], tids=snapshot.tids[rep],
        counts=weights, user_len=snapshot.user_len[rep],
        kernel_len=snapshot.kernel_len[rep], stacks=snapshot.stacks[rep])


def pack_window_inputs(snapshot: WindowSnapshot, l_cap: int | None = None):
    """Pad a WindowSnapshot into the kernel's uint32 operand layout.

    Returns (host_arrays, dims): the 12 kernel operands as host numpy
    arrays, and the static shape bucket {n_pad, l_cap, m_pad}. Single
    source of truth for the layout — used by TPUAggregator.aggregate, the
    benchmark, and the driver entry point.
    """
    n = len(snapshot)
    n_pad = _next_pow2(max(1, n))
    table = snapshot.mappings
    m = len(table)
    m_pad = max(1, _next_pow2(m))

    # Counts ride int32 lanes on device; guard the whole window's total (an
    # upper bound on any merged group's sum) before the astype below wraps.
    if int(snapshot.counts.sum()) >= 2**31:
        raise ValueError("window sample total exceeds int32")
    # The kernel uses pid == U32_MAX as its dead-row/dead-frame sentinel
    # (liveness is derived from it, not carried through the sort). pid -1
    # (perf's unattributable context) would alias it after the uint32
    # cast and silently lose that profile — reject it loudly here; the
    # capture layer attributes samples to real tgids.
    if n and int(snapshot.pids.min()) < 0:
        raise ValueError("negative pid in snapshot (would alias the "
                         "kernel's dead-row sentinel)")

    pid = np.full(n_pad, _U32_MAX, np.uint32)
    pid[:n] = snapshot.pids.astype(np.uint32)
    cnt = np.zeros(n_pad, np.int32)
    cnt[:n] = snapshot.counts.astype(np.int32)
    ulen = np.zeros(n_pad, np.int32)
    ulen[:n] = snapshot.user_len
    klen = np.zeros(n_pad, np.int32)
    klen[:n] = snapshot.kernel_len
    shi = np.zeros((n_pad, STACK_SLOTS), np.uint32)
    slo = np.zeros((n_pad, STACK_SLOTS), np.uint32)
    shi[:n] = (snapshot.stacks >> np.uint64(32)).astype(np.uint32)
    slo[:n] = snapshot.stacks.astype(np.uint32)
    valid = np.zeros(n_pad, bool)
    valid[:n] = True

    map_pid = np.full(m_pad, _U32_MAX, np.uint32)
    map_shi = np.full(m_pad, _U32_MAX, np.uint32)
    map_slo = np.full(m_pad, _U32_MAX, np.uint32)
    map_ehi = np.zeros(m_pad, np.uint32)
    map_elo = np.zeros(m_pad, np.uint32)
    map_pid[:m] = table.pids.astype(np.uint32)
    map_shi[:m] = (table.starts >> np.uint64(32)).astype(np.uint32)
    map_slo[:m] = table.starts.astype(np.uint32)
    map_ehi[:m] = (table.ends >> np.uint64(32)).astype(np.uint32)
    map_elo[:m] = table.ends.astype(np.uint32)

    total_frames = int((snapshot.user_len + snapshot.kernel_len).sum())
    if l_cap is None:
        # Exact unique-(pid, frame) count, an upper bound on the kernel's
        # deduplicated location count: every l_cap overflow costs the
        # caller a full recompile (~20-40s on a TPU), while this host
        # count is sub-second even at 1M rows. Vectorized (no per-row
        # Python): col j of row i enumerates that row's live frames.
        depth = (snapshot.user_len.astype(np.int64)
                 + snapshot.kernel_len.astype(np.int64))
        row_idx = np.repeat(np.arange(n, dtype=np.int64), depth)
        col_idx = np.arange(total_frames, dtype=np.int64) - \
            np.repeat(np.cumsum(depth) - depth, depth)
        key = np.empty((total_frames, 2), np.uint64)
        key[:, 0] = snapshot.pids[row_idx].astype(np.uint64)
        key[:, 1] = snapshot.stacks[row_idx, col_idx]
        n_locs = len(np.unique(
            np.ascontiguousarray(key).view(
                np.dtype((np.void, 16))).ravel()))
        l_cap = max(16, _next_pow2(max(1, n_locs)))
    # Frame-compaction buffer: sized from the exact frame count, so the
    # kernel's compaction scatter can never drop a live frame.
    f_cap = max(16, _next_pow2(max(1, total_frames)))

    args = (pid, cnt, ulen, klen, shi, slo, valid,
            map_pid, map_shi, map_slo, map_ehi, map_elo)
    return args, {"n_pad": n_pad, "l_cap": l_cap, "m_pad": m_pad,
                  "f_cap": f_cap}


@dataclasses.dataclass
class TPUAggregator:
    """Aggregation backend running the window kernel on the default JAX
    backend (TPU in production; CPU in tests via JAX_PLATFORMS=cpu).

    The unique-location table is a bounded buffer: the first attempt sizes
    it at next_pow2(total_live_frames / 4) — profiling windows dedup far
    below their frame count — and if the kernel reports n_locs above the
    cap, the window is re-run with the cap doubled. Results are therefore
    always exact; the cap bounds memory, it never truncates.
    """

    name: str = "tpu"

    # Location dedup implementation: "hash" re-expresses the dominant
    # f_cap-lane sort as a hash-table build+probe (the Pallas kernel,
    # aggregator/pallas_probe.py — the full-rebuild/backfill fix, docs/
    # perf.md "sub-RTT close"); "sort" is the proven lax pipeline;
    # "auto" (default) uses hash where the Pallas kernel runs
    # interpreted and sort on a TPU, where Mosaic refuses the kernel
    # (pallas_probe module docs); an explicit "hash" still falls back to
    # sort at runtime, loudly, if the kernel fails to build. Output
    # bytes are identical either way (enforced by tests and the bench's
    # close_overlap phase).
    dedup: str = "auto"

    # Unique-location count beyond which the one-shot kernel is the wrong
    # tool (the location dedup sort dominates: ~45 s at the adversarial
    # 26.5 M-location synthetic, docs/perf.md) and the streaming dict
    # aggregator should be used instead. Advisory only — results stay
    # exact either way.
    LOC_WARN_THRESHOLD = 1 << 22
    _loc_warned: bool = False
    _hash_disabled: bool = False

    def _use_hash(self) -> bool:
        if self._hash_disabled or self.dedup == "sort":
            dtel.note_backend("loc_dedup", requested=self.dedup,
                              resolved="lax",
                              fallback=self._hash_disabled)
            return False
        from parca_agent_tpu.aggregator import pallas_probe

        if self.dedup == "auto":
            # Chosen from the platform, not a fallback: Mosaic refuses
            # the builder on a TPU (pallas_probe module docs).
            use = pallas_probe.auto_uses_pallas()
            dtel.note_backend("loc_dedup", requested="auto",
                              resolved="pallas" if use else "lax",
                              fallback=False)
            return use
        if pallas_probe.pallas_available():
            dtel.note_backend("loc_dedup", requested=self.dedup,
                              resolved="pallas", fallback=False)
            return True
        from parca_agent_tpu.utils.log import get_logger

        get_logger("aggregator.tpu").warn(
            "hash dedup requested but Pallas is unavailable; using "
            "the lax sort kernel")
        self._hash_disabled = True
        # Pallas asked for by name but unavailable: the latched
        # fallback the one-hot gauge surfaces.
        dtel.note_backend("loc_dedup", requested=self.dedup,
                          resolved="lax", fallback=True)
        return False

    def aggregate(self, snapshot: WindowSnapshot) -> list[PidProfile]:
        import jax.numpy as jnp

        n = len(snapshot)
        if n == 0:
            return []
        snapshot = _coalesce_snapshot_rows(snapshot)
        table = snapshot.mappings
        host_args, dims = pack_window_inputs(snapshot)
        dev_args = tuple(jnp.asarray(a) for a in host_args)
        use_hash = self._use_hash()

        while True:
            try:
                import time as _time

                from parca_agent_tpu.aggregator.pallas_probe import (
                    default_interpret,
                )

                interp = default_interpret()
                t0 = _time.perf_counter()
                out = _jitted_kernel()(*dev_args, hash_locs=use_hash,
                                       interpret=interp, **dims)
            except Exception as e:  # noqa: BLE001 - hash path only
                if not use_hash:
                    raise
                # Automatic fallback: a Pallas build/lowering failure on
                # this backend degrades to the lax sort kernel — never a
                # lost window, at worst the old speed. Latched so the
                # per-window hot path does not retry a broken lowering.
                self._hash_disabled = True
                use_hash = False
                dtel.note_backend("loc_dedup", resolved="lax",
                                  fallback=True)
                from parca_agent_tpu.utils.log import get_logger

                get_logger("aggregator.tpu").warn(
                    "hash location dedup failed; falling back to the lax "
                    "sort kernel", error=repr(e)[:200])
                continue
            outs = tuple(map(np.asarray, out))
            (n_groups, n_locs, out_pid, depth, values, loc_ids,
             loc_pid, loc_hi, loc_lo, loc_map_row) = outs
            # One observation covers dispatch + fetch (the one-shot
            # kernel is synchronous by design); the jit static key is
            # the shape signature, so every l_cap doubling retry reads
            # as the recompile it really is.
            dtel.record(
                "loc_dedup", _time.perf_counter() - t0,
                shape=(dims["n_pad"], dims["l_cap"], dims["m_pad"],
                       dims["f_cap"], use_hash, interp),
                h2d_bytes=sum(int(a.nbytes) for a in host_args),
                d2h_bytes=sum(int(a.nbytes) for a in outs))
            dtel.note_backend("loc_dedup", interpret=interp)
            if int(n_locs) <= dims["l_cap"]:
                break
            dims["l_cap"] *= 2

        if int(n_locs) > self.LOC_WARN_THRESHOLD and not self._loc_warned:
            # Keyed on the MEASURED unique-location count (known only
            # after the kernel ran), once per aggregator: the per-window
            # hot path must not log every window.
            self._loc_warned = True
            from parca_agent_tpu.utils.log import get_logger

            get_logger("aggregator.tpu").warn(
                "window location entropy is in the one-shot kernel's "
                "adversarial regime; --aggregator dict (the streaming "
                "dictionary) aggregates such windows orders of magnitude "
                "faster", unique_locations=int(n_locs),
                threshold=self.LOC_WARN_THRESHOLD)

        return self._build_profiles(
            snapshot, table,
            int(n_groups), int(n_locs), out_pid, depth, values, loc_ids,
            loc_pid, loc_hi, loc_lo, loc_map_row,
        )

    def _build_profiles(
        self, snapshot, table, n_groups, n_locs, out_pid, depth, values,
        loc_ids, loc_pid, loc_hi, loc_lo, loc_map_row,
    ) -> list[PidProfile]:
        u_pid = out_pid[:n_groups].astype(np.int64)
        u_depth = depth[:n_groups].astype(np.int32)
        u_values = values[:n_groups].astype(np.int64)
        u_loc_ids = loc_ids[:n_groups]

        l_pid = loc_pid[:n_locs].astype(np.int64)
        l_addr = (loc_hi[:n_locs].astype(np.uint64) << np.uint64(32)) | loc_lo[
            :n_locs
        ].astype(np.uint64)
        l_row = loc_map_row[:n_locs]

        l_kernel = l_addr >= np.uint64(KERNEL_ADDR_START)
        # u64 arithmetic + per-pid mapping ranks stay on host. Kernel text
        # is never normalized through the mapping table, even if a mapping
        # (e.g. [vsyscall]) covers it — matches the CPU oracle and the
        # formats.py contract.
        hit = (l_row >= 0) & ~l_kernel
        safe = np.maximum(l_row, 0)
        if len(table):
            l_norm = np.where(hit, l_addr - table.bases[safe], l_addr)
            # Global mapping row -> 1-based rank within its pid (rows are
            # sorted by (pid, start): rank = row - first row of pid's block).
            pid_first_row = np.searchsorted(table.pids, table.pids[safe], "left")
            l_map_id = np.where(hit, safe - pid_first_row + 1, 0).astype(np.int32)
        else:
            l_norm = l_addr.copy()
            l_map_id = np.zeros(n_locs, np.int32)

        # Both tables arrive pid-contiguous (device sort order); split them.
        profiles: list[PidProfile] = []
        stack_bounds = np.flatnonzero(np.diff(u_pid)) + 1
        s_starts = np.concatenate(([0], stack_bounds))
        s_ends = np.concatenate((stack_bounds, [n_groups]))
        loc_starts = np.searchsorted(l_pid, u_pid[s_starts], "left")
        loc_ends = np.searchsorted(l_pid, u_pid[s_starts], "right")

        for i, (lo, hi) in enumerate(zip(s_starts, s_ends)):
            pid = int(u_pid[lo])
            llo, lhi = int(loc_starts[i]), int(loc_ends[i])
            profiles.append(
                PidProfile(
                    pid=pid,
                    stack_loc_ids=u_loc_ids[lo:hi],
                    stack_depths=u_depth[lo:hi],
                    values=u_values[lo:hi],
                    loc_address=l_addr[llo:lhi],
                    loc_normalized=l_norm[llo:lhi].astype(np.uint64),
                    loc_mapping_id=l_map_id[llo:lhi],
                    loc_is_kernel=l_kernel[llo:lhi],
                    mappings=_pid_mappings(table, pid),
                    period_ns=snapshot.period_ns,
                    time_ns=snapshot.time_ns,
                    duration_ns=snapshot.window_ns,
                )
            )
        return profiles
