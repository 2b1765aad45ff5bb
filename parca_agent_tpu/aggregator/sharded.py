"""Data-parallel dict aggregation: the stack dictionary sharded over a
device mesh (SURVEY.md section 2.12 — sharding pids/stack-ids across TPU
cores via shard_map inside the aggregation, not only at fleet merge).

Design (the TPU-native analog of the reference's 3-way unwind-table shard
partition, pkg/profiler/cpu/maps.go:40-43, applied to the hot table):

  * Every key has a HOME SHARD, h2 % n_shards; shard d owns a private
    sub-table of capacity/n_shards slots, and the open-addressing probe
    (h1-based linear chain) runs entirely within the home sub-table. The
    device table is [n_shards, cap_s, 4] sharded over axis 0 of the mesh.
  * The packed feed buffer is PARTITIONED host-side by home shard (the
    home hash h2 % n_shards is already computed for every row): shard d
    receives only its own rows, padded to a shared quarter-pow2 lane
    count sized to the max per-shard row count (~total/N for a uniform
    hash), plus each row's original packed-buffer position so miss
    reports need no reverse mapping. Probe work, H2D bytes, and table
    memory all split N ways — an earlier design replicated the buffer
    and masked, which split memory but MULTIPLIED probe FLOPs by N.
  * The accumulator is PARTIAL per shard ([n_shards, id_cap], sharded):
    shard d accumulates only its keys' counts under the global dense stack
    ids. Window close is ONE collective: psum over the shard axis, then
    the same pack-to-uint{4,8,16} + overflow sideband as the single-chip
    close, fetched once.

The host mirror reuses DictAggregator's arrays with slot = shard * cap_s +
within-shard index, so insertion, rotation, eviction, sketch degradation,
and the unreachable-key prefilter all inherit unchanged; only the slot
placement rule and the four device dispatch hooks differ.
"""

from __future__ import annotations

import functools

import numpy as np

from parca_agent_tpu.aggregator.dict import (
    DictAggregator,
    make_close,
    make_probe,
    prefix_sum,
)
from parca_agent_tpu.parallel.mesh import FLEET_AXIS, fleet_mesh
from parca_agent_tpu.runtime import device_telemetry as dtel
from parca_agent_tpu.runtime import trace


def route_h2(h2: np.ndarray, pids, shard_of_pid, n_shards: int
             ) -> np.ndarray:
    """Rewrite each row's h2 so ``h2 % n_shards == shard_of_pid(pid)``
    while keeping the rest of the hash: the home-shard rule (everywhere
    ``h2 % n_shards`` is consulted — the host mirror's ``_home_shard``
    and the feed partition) then routes by TENANT instead of by raw
    hash, so one tenant's registry growth lands on its home sub-table
    and parallelizes across chips per tenant (docs/robustness.md
    "multi-tenant admission"). Key identity stays per-(stack, pid):
    every row of a pid carries the same replacement residue, so equal
    stacks still collide into one key and different pids already
    differed in h1/h3. Exact for any n_shards: computed in int64 with
    the top partial block stepped down one stride instead of wrapping
    (a uint32 wrap would break the residue for non-power-of-two shard
    counts)."""
    n = int(n_shards)
    upids, inverse = np.unique(np.asarray(pids, np.int64),
                               return_inverse=True)
    residues = np.array([int(shard_of_pid(int(p))) % n for p in upids],
                        np.int64)
    out = (np.asarray(h2, np.uint32).astype(np.int64) // n) * n \
        + residues[inverse]
    out = np.where(out > 0xFFFFFFFF, out - n, out)
    return out.astype(np.uint32)


@functools.lru_cache(maxsize=8)
def _sharded_feed_program(mesh, n_shards: int, cap_s: int, id_cap: int,
                          n_pad_s: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    probe = make_probe(cap_s, n_pad_s)

    def node_fn(table, acc, packed, reset):
        # table [1, cap_s, 4]; acc [1, id_cap]; packed [1, 5, n_pad_s] —
        # THIS shard's rows only (host-partitioned by home shard), rows
        # being (h1, h2, h3, count, original packed-buffer position).
        t = table[0]
        a = jnp.where(reset != 0, 0, acc[0])
        cnt = packed[0, 3].astype(jnp.int32)
        orig = packed[0, 4].astype(jnp.int32)
        live = cnt > 0  # pad lanes carry count 0
        # The single-chip feed's probe, over this shard's sub-table.
        found_id, _ = probe(t, packed[0, :3].T, live)

        hit = (found_id >= 0) & live
        a = a.at[jnp.where(hit, found_id, id_cap)].add(
            jnp.where(live, cnt, 0), mode="drop")
        miss = live & ~hit
        mtgt = jnp.where(miss, prefix_sum(miss.astype(jnp.int32)) - 1,
                         jnp.int32(n_pad_s))
        # Report ORIGINAL packed-buffer positions (the host partitioned
        # the rows, so local lane indices would be meaningless to it).
        miss_rows = jnp.full((n_pad_s,), -1, jnp.int32).at[mtgt].set(
            orig, mode="drop")
        n_miss = miss.astype(jnp.int32).sum()
        return a[None], n_miss[None], miss_rows[None]

    fn = jax.shard_map(
        node_fn,
        mesh=mesh,
        in_specs=(P(FLEET_AXIS, None, None), P(FLEET_AXIS, None),
                  P(FLEET_AXIS, None, None), P()),
        out_specs=(P(FLEET_AXIS, None), P(FLEET_AXIS), P(FLEET_AXIS, None)),
        # The probe's loops start from literals and end on values read
        # from the node-sharded table; every output is per shard, so
        # nothing rests on the replication check.
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(1,))


@functools.lru_cache(maxsize=24)
def _sharded_close_program(mesh, n_shards: int, id_cap: int, n_fetch: int,
                           width: int, n_over_buf: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    pack = make_close(id_cap, n_fetch, width, n_over_buf)

    def node_fn(acc):
        total = jax.lax.psum(acc[0], FLEET_AXIS)  # [id_cap] on every shard
        # Every shard packs the same psum'd total. This is deliberate,
        # not waste: under SPMD lockstep all shards run the pack
        # SIMULTANEOUSLY, so close wall-clock equals one shard packing;
        # serializing the pack onto one shard would idle the rest for the
        # same latency while adding a broadcast. The host fetches one
        # shard's copy (one D2H of the packed buffer, not N).
        return pack(total)[None]

    fn = jax.shard_map(node_fn, mesh=mesh, in_specs=(P(FLEET_AXIS, None),),
                       out_specs=P(FLEET_AXIS, None))
    return jax.jit(fn)


@functools.lru_cache(maxsize=4)
def _sharded_scatter_program(mesh):
    """``table[shard, slot] = vals`` over one chunk of newly inserted
    rows, in place, the table staying sharded over the mesh (dict.py
    _scatter_program's twin: one shape whatever the count)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def miss_scatter(table, shard, slot, vals):
        return table.at[shard, slot].set(vals, mode="drop")

    return jax.jit(miss_scatter, donate_argnums=0,
                   out_shardings=NamedSharding(
                       mesh, P(FLEET_AXIS, None, None)))


class ShardedDictAggregator(DictAggregator):
    """DictAggregator with the device table and probe work sharded over an
    n-device mesh. Semantics (exact counts, miss/insert protocol, sketch
    degradation, rotation) are identical to the single-chip dict; only
    placement and dispatch differ. aggregate()/window_counts run through
    the streaming feed/close protocol (closing any open window first)."""

    name = "sharded-dict"

    def __init__(self, capacity: int = 1 << 21, id_cap: int | None = None,
                 mesh=None, n_shards: int | None = None,
                 shard_of_pid=None, **kw):
        if mesh is None:
            import jax

            mesh = fleet_mesh(n_shards or len(jax.devices()))
        self._mesh = mesh
        self._n_shards = mesh.devices.size
        if capacity % self._n_shards:
            raise ValueError("capacity must divide by the shard count")
        cap_s = capacity // self._n_shards
        if cap_s & (cap_s - 1):
            raise ValueError("per-shard capacity must be a power of two")
        self._cap_s = cap_s
        # Optional pid -> home-shard router (the admission layer's
        # tenant placement, runtime/admission.py shard_of): with it set,
        # hash_rows rewrites h2's shard residue per pid (route_h2) so
        # both the host mirror's _home_shard and the feed partition
        # place by tenant. Must be stable per pid across windows — a
        # re-route would mint a second key for the same stack (harmless
        # mass-wise, wasteful registry-wise; rotation reclaims it).
        self._shard_of_pid = shard_of_pid
        # n_pad_s -> [buf_a, buf_b, flip]: double-buffered pack scratch
        # (pack N+1 must not overwrite the buffer dispatch N may still
        # be reading through an async H2D).
        self._part_bufs: dict[int, list] = {}
        super().__init__(capacity=capacity, id_cap=id_cap, **kw)
        # Delta-fetch touch tracking is single-chip for now: the sharded
        # close psums partial accumulators across the mesh and fetches
        # the packed full prefix once; its feed program carries no touch
        # flags. Double-buffering (the flip) inherits unchanged.
        self._blk = 0
        self._n_blocks = 0
        self._touch = None
        self._touch_spare = None

    def set_shard_router(self, shard_of_pid) -> None:
        """Install the pid router (tenant placement) at wiring time —
        BEFORE the first feed: keys already inserted under the raw-hash
        rule keep their placement (rotation reclaims them), so a mid-run
        install only fragments the registry, it never corrupts it."""
        self._shard_of_pid = shard_of_pid

    def hash_rows(self, snapshot):
        h1, h2, h3 = super().hash_rows(snapshot)
        return h1, self._route_hashes(h1, h2, h3, snapshot.pids), h3

    def _route_hashes(self, h1, h2, h3, pids):
        # The single source of the h2 shard-residue rewrite: hash_rows
        # above and every externally-computed triple (capture-carried
        # hashes, the feed's post-fold representative hashing) route
        # through here, so identity stays bit-identical regardless of
        # where the triple was computed.
        if self._shard_of_pid is not None:
            return route_h2(h2, pids, self._shard_of_pid, self._n_shards)
        return h2

    # -- host-mirror placement: probe within the key's home sub-table -------

    def _home_shard(self, key: tuple) -> int:
        return key[1] % self._n_shards

    def _shard_free(self) -> np.ndarray:
        """Free slots per shard sub-table (occupancy is per home shard,
        which the GLOBAL capacity check cannot see: a skewed h2
        distribution can fill one sub-table while the table as a whole is
        half empty)."""
        occ = self._occ.reshape(self._n_shards, self._cap_s)
        return self._cap_s - occ.sum(axis=1)

    def _check_shard_demand(self, demand: np.ndarray) -> None:
        """Shared raise tail of both insert-room checks (scalar and
        vectorized): per-sub-table new-key demand vs free slots."""
        free = self._shard_free()
        over = np.flatnonzero(demand > free)
        if len(over):
            s = int(over[0])
            raise RuntimeError(
                f"shard sub-table {s} exhausted ({int(demand[s])} new keys "
                f"vs {int(free[s])} free of {self._cap_s} slots); construct "
                f"with a larger capacity or overflow='sketch'")

    def _check_insert_room(self, classified, seen_batch) -> None:
        if self._overflow != "raise" or not seen_batch:
            return  # sketch mode degrades per key in _try_insert_slot
        demand = np.zeros(self._n_shards, np.int64)
        for key in seen_batch:
            demand[self._home_shard(key)] += 1
        self._check_shard_demand(demand)

    def _try_insert_slot(self, key: tuple) -> int | None:
        base = self._home_shard(key) * self._cap_s
        mask = self._cap_s - 1
        idx = key[0] & mask
        for _ in range(self._cap_s):
            if not self._occ[base + idx]:
                return base + idx
            idx = (idx + 1) & mask
        return None  # sub-table full: caller degrades to the sketch

    def _host_insert_slot(self, key: tuple) -> int:
        # Reached only from rotation rebuild (survivor re-insertion, which
        # can never overflow a sub-table: survivors fit where they sat)
        # and from _try_insert_slot above via the base class.
        slot = self._try_insert_slot(key)
        if slot is None:
            raise RuntimeError("shard sub-table unexpectedly full")
        return slot

    def _chain_dist(self, key: tuple, slot: int) -> int:
        mask = self._cap_s - 1
        within = slot - self._home_shard(key) * self._cap_s
        return (within - (key[0] & mask)) & mask

    def _probe_geometry_vec(self, h1u, h2u):
        # The vectorized settle's probe geometry: chains live entirely
        # within the key's home sub-table (base = home * cap_s), exactly
        # as _try_insert_slot/_chain_dist walk them per key.
        mask = self._cap_s - 1
        base = (h2u.astype(np.int64) % self._n_shards) * self._cap_s
        return base, h1u.astype(np.int64) & mask, mask

    def _check_insert_room_vec(self, h1n, h2n, h3n) -> None:
        # Vectorized twin of _check_insert_room: pre-mutation,
        # raise-mode only (sketch mode degrades per key via the
        # placement overrun fallback); the raise tail is shared.
        if self._overflow != "raise" or not len(h2n):
            return
        self._check_shard_demand(
            np.bincount(h2n.astype(np.int64) % self._n_shards,
                        minlength=self._n_shards))

    # -- device dispatch ------------------------------------------------------

    def _ensure_device(self) -> None:
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        if self._dev is None:
            table = np.zeros((self._cap, 4), np.uint32)
            table[:, 0] = self._h1
            table[:, 1] = self._h2
            table[:, 2] = self._h3
            table[:, 3] = np.where(self._occ, self._ids + 1, 0).astype(
                np.uint32)
            table = table.reshape(self._n_shards, self._cap_s, 4)
            self._dev = jax.device_put(
                table, NamedSharding(self._mesh, P(FLEET_AXIS, None, None)))

    def _new_acc(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return jax.device_put(
            jnp.zeros((self._n_shards, self._id_cap), jnp.int32),
            NamedSharding(self._mesh, P(FLEET_AXIS, None)))

    def _partition_packed(self, packed: np.ndarray) -> np.ndarray:
        """Split the [4, n_pad] packed buffer into [n_shards, 5, n_pad_s]
        by home shard (h2 % n_shards), appending each row's original
        position as channel 4. Pad lanes are zero (count 0 = dead).

        One vectorized scatter per channel (the per-shard Python slice
        loop this replaces walked the shard axis serially — at 8+ shards
        the loop overhead was a visible slice of the per-drain host
        cost), into a DOUBLE-BUFFERED scratch: the previous drain's
        partition buffer stays untouched while this one packs, so
        pack(N+1) can overlap dispatch(N)'s H2D reads even on backends
        that consume host memory asynchronously."""
        cnt = packed[3]
        live = np.flatnonzero(cnt > 0)
        shard = (packed[1, live] % np.uint32(self._n_shards)).astype(np.int64)
        # Stable sort keeps ascending packed order within each shard, so
        # miss (and therefore id-assignment) order is deterministic.
        order = np.argsort(shard, kind="stable")
        rows = live[order]
        per = np.bincount(shard, minlength=self._n_shards)
        n_max = max(int(per.max(initial=0)), 1)
        # Quarter-pow2 padding (16, 20, 24, 28, 32, 40, ...): full pow2
        # rounding wastes up to 2x probe lanes per shard (a near-uniform
        # hash puts ~total/N rows on each shard, just past a pow2
        # boundary), while still bounding distinct compiled shapes to
        # ~4 per octave of drain size.
        if n_max <= 16:
            n_pad_s = 16
        else:
            step = 1 << max(2, n_max.bit_length() - 3)
            n_pad_s = -(-n_max // step) * step
        # Reuse TWO buffers per lane count, alternating (same rationale
        # as the base feed's _feed_bufs — fresh multi-MB zeroed
        # allocations per drain are pure churn — plus the double-buffer
        # contract above); quarter-pow2 lane sizing bounds the distinct
        # shapes to ~4 per octave of drain size. LRU, not
        # evict-smallest: quarter-pow2 sizing yields ~4 shapes per
        # octave (vs pow2's 1), so a size-ordered policy both thrashes
        # when drains jitter across an octave boundary and pins large
        # stale buffers forever after a burst. 8 recently-used shape
        # slots track the actual working set; re-insertion on hit keeps
        # dict order = recency order.
        pair = self._part_bufs.pop(n_pad_s, None)
        if pair is None:
            if len(self._part_bufs) >= 8:
                self._part_bufs.pop(next(iter(self._part_bufs)))  # LRU
            pair = [None, None, 0]
        flip = pair[2]
        pair[2] = flip ^ 1
        out = pair[flip]
        if out is None:
            out = pair[flip] = np.zeros((self._n_shards, 5, n_pad_s),
                                        np.uint32)
        else:
            out[:] = 0
        self._part_bufs[n_pad_s] = pair
        bounds = np.zeros(self._n_shards + 1, np.int64)
        np.cumsum(per, out=bounds[1:])
        shard_sorted = shard[order]
        lane = np.arange(len(rows), dtype=np.int64) - bounds[shard_sorted]
        for c in range(4):
            out[shard_sorted, c, lane] = packed[c, rows]
        out[shard_sorted, 4, lane] = rows.astype(np.uint32)
        return out

    def _device_put_sharded(self, part: np.ndarray):
        """Ship the partitioned batch: one per-shard device_put per mesh
        device, assembled into the global sharded array — the transfers
        are dispatched back-to-back WITHOUT waiting on each other, so
        the sub-batches travel concurrently instead of through one
        serially-staged global copy. Counted fallback to the single
        staged device_put on any runtime refusal (layouts, committed
        device sets) — never a lost feed."""
        import time as _time

        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        sharding = NamedSharding(self._mesh, P(FLEET_AXIS, None, None))
        t0 = _time.perf_counter()
        try:
            devs = list(self._mesh.devices.reshape(-1))
            shards = [jax.device_put(part[s:s + 1], d)
                      for s, d in enumerate(devs)]
            out = jax.make_array_from_single_device_arrays(
                part.shape, sharding, shards)
        except Exception as e:  # noqa: BLE001 - counted fallback
            self.stats["shard_put_fallbacks"] = \
                self.stats.get("shard_put_fallbacks", 0) + 1
            from parca_agent_tpu.utils.log import get_logger

            get_logger("aggregator.sharded").warn(
                "per-shard concurrent device_put failed; using the "
                "staged global copy", error=repr(e)[:200])
            out = jax.device_put(part, sharding)
        dtel.record("shard_put", _time.perf_counter() - t0,
                    shape=tuple(part.shape), h2d_bytes=part.nbytes)
        return out

    # palint: capture-path — the sharded override of the dispatch-only
    # feed (the base seed's call graph stops at file scope, so the
    # override seeds itself). Device state (one line, no continuations):
    # palint: device-state: _dev, _acc, _touch, _acc_spare, _touch_spare
    def _feed_dispatch_async(self, packed: np.ndarray, n_pad: int,
                             reset: int):
        part = self._partition_packed(packed)
        prog = _sharded_feed_program(self._mesh, self._n_shards, self._cap_s,
                                     self._id_cap, part.shape[2])
        dev_packed = self._device_put_sharded(part)
        acc = self._acc
        self._acc = None  # donated: invalid if the call throws
        with trace.child("feed_dispatch") as sp:
            acc, n_miss, miss_rows = prog(self._dev, acc, dev_packed,
                                          np.uint32(reset))
        self.timings["feed_dispatch"] = sp.duration_s
        dtel.record("feed_probe", sp.duration_s,
                    shape=("sharded", self._n_shards, self._cap_s,
                           self._id_cap, part.shape[2]))
        self._acc = acc
        return (n_miss, miss_rows)

    # palint: sync-ok — the sharded twin of the base settle boundary.
    def _settle_dispatch(self, handle) -> np.ndarray:
        n_miss, miss_rows = handle
        per_shard = np.asarray(n_miss)  # device sync point
        if not per_shard.any():
            return np.empty(0, np.int64)
        # Each row has exactly one home shard, so the per-shard miss lists
        # are disjoint; concatenate them (original-position indices).
        rows_all = np.asarray(miss_rows)
        return np.concatenate([
            rows_all[s, : int(k)] for s, k in enumerate(per_shard) if k
        ]).astype(np.int64)

    def _close_pack_dispatch(self, acc, n_fetch: int, width: int,
                             n_over_buf: int):
        prog = _sharded_close_program(self._mesh, self._n_shards,
                                      self._id_cap, n_fetch, width,
                                      n_over_buf)
        with trace.child("close_dispatch") as sp:
            out = prog(acc)[0]  # every shard holds the same packed copy
        self.timings["close_dispatch"] = sp.duration_s
        dtel.record("close_pack", sp.duration_s,
                    shape=("sharded", self._n_shards, self._id_cap,
                           n_fetch, width, n_over_buf))
        return out

    def _scatter_cold(self, slots: np.ndarray, vals: np.ndarray) -> None:
        """The base class's one eager scatter, addressed (shard, slot in
        shard)."""
        import jax.numpy as jnp

        s_idx = (slots // self._cap_s).astype(np.int32)
        w_idx = (slots % self._cap_s).astype(np.int32)
        self._dev = self._dev.at[jnp.asarray(s_idx), jnp.asarray(w_idx)].set(
            jnp.asarray(vals))

    def _scatter_chunk(self, slots_c: np.ndarray, vals_c: np.ndarray) -> None:
        """The base class's one-shape chunk, addressed (shard, slot in
        shard); the padding slot lands on a shard that does not exist
        and is dropped."""
        dev, self._dev = self._dev, None
        self._dev = _sharded_scatter_program(self._mesh)(
            dev, slots_c // self._cap_s, slots_c % self._cap_s, vals_c)
