"""Pallas re-expression of the hash-table probe loops.

Two kernels, both with an ``interpret=True`` path (exercised by tier-1
tests on every CPU-only run) and a fallback to the lax implementations
when Pallas is unavailable or fails to build (docs/perf.md "sub-RTT
close").

The chip's verdict (TPU v5 lite, jax 0.9.0, libtpu 0.0.34; compiled
non-interpret, outside the callers' try/except, at a toy shape and at
BASELINE config #4 shapes): Mosaic REFUSES both, at lowering, whatever
the size —

  * batch probe (cap 4096 x 1024 rows; cap 4,194,304 x 131,072 and
    x 1,048,576 rows): ``RuntimeError: `broadcast_to` is a
    Triton-specific primitive. Please consider using `jnp.broadcast_to`
    instead.`` (the vector-indexed ``table_ref[idx, c]`` loads);
  * loc-table builder (f_cap 1024 / cap_l 2048; f_cap 2^25 / cap_l
    2^26): ``NotImplementedError: Only 2D gather is supported`` (the
    1-D ``tpid[pos]`` gathers inside the while_loop).

So on a TPU these kernels do not exist, and ``"auto"`` does not select
them there (:func:`auto_uses_pallas`): a default that pays a failed
Mosaic compile and then quietly runs the reference is a hidden
fallback. Asking for them by name (``probe_backend="pallas"``,
``dedup="hash"``) still tries, fails loudly and latches lax. Making them
lower, or deleting them, is ROADMAP A6/C3.

  * :func:`make_batch_probe` — the stack dictionary's bounded linear
    probe (``aggregator/dict.py`` ``make_feed``'s inner ``fori_loop``):
    batched lookup of every row's 96-bit identity against the resident
    ``[cap, 4]`` table. As a single Pallas kernel the 16 probe steps
    fuse into one pass over the row block — XLA's lax lowering
    materializes a full gathered ``[n, 4]`` intermediate per probe step,
     16x the traffic the probe actually needs.
  * :func:`make_loc_table_builder` — the one-shot batch kernel's
    location dedup re-expressed as hash-table build + probe
    (``aggregator/tpu.py``): every live frame's (pid, addr_hi, addr_lo)
    key probes an open-addressing table, claims empty slots (min-lane
    arbitration, deterministic), and records its slot. This replaces
    the f_cap-lane bitonic sort that dominates the stateless kernel
    (~45 s at 26.5 M unique locations, docs/perf.md): the sort that
    remains downstream runs over the cap_l unique TABLE entries, not
    over every frame.

Exactness: identity is compared on the full key in both kernels (the
dict's 96-bit triple; the raw 96-bit (pid, hi, lo) for locations), and
the callers re-sort the deduplicated outputs into the lax paths' exact
output order — byte-identical pprof, enforced by tests and the bench's
``close_overlap`` phase.

Both kernels run whole-array (grid=1) with the operands in
compiler-chosen memory.
"""

from __future__ import annotations

import functools

_U32_MAX = 0xFFFFFFFF


@functools.lru_cache(maxsize=1)
def pallas_available() -> bool:
    """True when jax.experimental.pallas imports AND a tiny interpret-mode
    probe round-trips correctly. Cached: the check is per-process."""
    try:
        import numpy as np

        probe = make_batch_probe(8, probes=2, interpret=True)
        import jax.numpy as jnp

        table = np.zeros((8, 4), np.uint32)
        table[3] = (3, 1, 2, 5)  # id 4 at its home slot
        got = np.asarray(probe(jnp.asarray(table),
                               jnp.asarray(np.array([3], np.uint32)),
                               jnp.asarray(np.array([1], np.uint32)),
                               jnp.asarray(np.array([2], np.uint32))))
        return int(got[0]) == 4
    except Exception:  # noqa: BLE001 - any failure means "not available"
        return False


def auto_uses_pallas() -> bool:
    """What ``"auto"`` (``DictAggregator.probe_backend``,
    ``TPUAggregator.dedup``) resolves to: the Pallas kernels wherever
    they run interpreted, the lax programs on a TPU — where Mosaic
    refuses both kernels (module docs), so selecting them would only
    buy a failed compile followed by the reference path."""
    return default_interpret() and pallas_available()


def default_interpret() -> bool:
    """Interpret mode everywhere except a real TPU backend: the CPU
    backend (tests, fallback hosts) runs the kernels through the Pallas
    interpreter, a TPU compiles them via Mosaic."""
    try:
        import jax

        return jax.default_backend() != "tpu"
    except Exception:  # noqa: BLE001 - no backend at all: interpret
        return True


def make_batch_probe(cap: int, probes: int, interpret: bool | None = None):
    """Pallas twin of the dict feed's probe loop: returns
    ``probe(table_u32[cap,4], h1, h2, h3) -> found_id int32`` with
    identical semantics (hit => stored id, miss/empty-slot stop => -1;
    chains past the probe bound stay misses, absorbed host-side)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from parca_agent_tpu.runtime import device_telemetry as dtel

    if interpret is None:
        interpret = default_interpret()
    # The interpret decision is made here, so the flight recorder's
    # per-kernel interpret gauge is latched here (free when telemetry
    # is off; the call sites latch requested/resolved/fallback).
    dtel.note_backend("feed_probe", interpret=interpret)

    def kernel(table_ref, h1_ref, h2_ref, h3_ref, out_ref):
        # Scalar constants are built INSIDE the kernel: a jnp scalar
        # closed over from the wrapper would be a captured constant,
        # which pallas_call rejects.
        mask = jnp.uint32(cap - 1)
        h1 = h1_ref[:]
        h2 = h2_ref[:]
        h3 = h3_ref[:]

        def body(k, state):
            found_id, done = state
            idx = ((h1 + jnp.uint32(k)) & mask).astype(jnp.int32)
            r_h1 = table_ref[idx, 0]
            r_h2 = table_ref[idx, 1]
            r_h3 = table_ref[idx, 2]
            r_id = table_ref[idx, 3]
            occ = r_id > 0
            hit = occ & (r_h1 == h1) & (r_h2 == h2) & (r_h3 == h3)
            stop = hit | ~occ
            found_id = jnp.where(hit & ~done,
                                 r_id.astype(jnp.int32) - 1, found_id)
            return found_id, done | stop

        found_id = jnp.full(h1.shape, -1, jnp.int32)
        done = jnp.zeros(h1.shape, bool)
        found_id, _ = jax.lax.fori_loop(0, probes, body, (found_id, done))
        out_ref[:] = found_id

    def probe(table, h1, h2, h3):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(h1.shape, jnp.int32),
            interpret=interpret,
        )(table, h1, h2, h3)

    return probe


def make_loc_table_builder(f_cap: int, cap_l: int,
                           interpret: bool | None = None):
    """Hash-table build+probe for the batch kernel's location dedup:
    ``build(kpid, khi, klo, base) -> (slot, tpid, thi, tlo)``.

    Every lane carries one (pid, hi, lo) key (dead lanes: pid ==
    U32_MAX) and its probe base hash. The claim loop is deterministic
    (min-lane arbitration on empty slots) and exact (full 96-bit key
    compare — a base-hash collision only lengthens a chain). ``slot`` is
    -1 for dead lanes AND for lanes that could not place within the
    iteration bound (table effectively full) — the caller treats any
    live -1 as table overflow and retries with a doubled cap, exactly
    like the sort path's l_cap retry."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from parca_agent_tpu.runtime import device_telemetry as dtel

    if interpret is None:
        interpret = default_interpret()
    dtel.note_backend("loc_dedup", interpret=interpret)
    # Any unplaced lane advances at least once per two iterations (one
    # iteration may be spent re-reading a slot a claim winner just
    # filled), so 2*cap_l + 2 bounds every terminating run; a genuinely
    # full table exits here with live -1 slots for the caller's retry.
    iter_cap = 2 * cap_l + 2

    def kernel(kpid_ref, khi_ref, klo_ref, base_ref,
               slot_ref, tpid_ref, thi_ref, tlo_ref):
        # Built inside the kernel (captured jnp constants are rejected
        # by pallas_call).
        u32max = jnp.uint32(_U32_MAX)
        mask = jnp.uint32(cap_l - 1)
        kpid = kpid_ref[:]
        khi = khi_ref[:]
        klo = klo_ref[:]
        base = base_ref[:]
        lane = jnp.arange(f_cap, dtype=jnp.int32)
        live = kpid != u32max

        def cond(st):
            it, _pos, placed, _slot, _tp, _th, _tl = st
            return (~placed.all()) & (it < iter_cap)

        def body(st):
            it, pos, placed, slot, tpid, thi, tlo = st
            occ_pid = tpid[pos]
            occ = occ_pid != u32max
            match = occ & (occ_pid == kpid) & (thi[pos] == khi) \
                & (tlo[pos] == klo)
            newly = match & ~placed
            slot = jnp.where(newly, pos, slot)
            placed = placed | newly
            # Empty slot: claim it. Min-lane arbitration makes insertion
            # deterministic; losers re-read the slot next iteration (the
            # winner may hold THEIR key) instead of advancing.
            want = ~placed & ~occ
            claim = jnp.full((cap_l,), f_cap, jnp.int32).at[
                jnp.where(want, pos, cap_l)].min(lane, mode="drop")
            won = want & (claim[pos] == lane)
            wtgt = jnp.where(won, pos, cap_l)
            tpid = tpid.at[wtgt].set(kpid, mode="drop")
            thi = thi.at[wtgt].set(khi, mode="drop")
            tlo = tlo.at[wtgt].set(klo, mode="drop")
            slot = jnp.where(won, pos, slot)
            placed = placed | won
            # Advance ONLY past an occupied mismatch (linear chain).
            adv = ~placed & occ & ~match
            pos = jnp.where(adv, (pos + 1) & jnp.int32(cap_l - 1), pos)
            return it + 1, pos, placed, slot, tpid, thi, tlo

        st0 = (
            jnp.int32(0),
            (base & mask).astype(jnp.int32),
            ~live,
            jnp.full((f_cap,), -1, jnp.int32),
            jnp.full((cap_l,), u32max),
            jnp.zeros((cap_l,), jnp.uint32),
            jnp.zeros((cap_l,), jnp.uint32),
        )
        _, _, _, slot, tpid, thi, tlo = jax.lax.while_loop(cond, body, st0)
        slot_ref[:] = slot
        tpid_ref[:] = tpid
        thi_ref[:] = thi
        tlo_ref[:] = tlo

    def build(kpid, khi, klo, base):
        return pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct((f_cap,), jnp.int32),
                jax.ShapeDtypeStruct((cap_l,), jnp.uint32),
                jax.ShapeDtypeStruct((cap_l,), jnp.uint32),
                jax.ShapeDtypeStruct((cap_l,), jnp.uint32),
            ),
            interpret=interpret,
        )(kpid, khi, klo, base)

    return build
