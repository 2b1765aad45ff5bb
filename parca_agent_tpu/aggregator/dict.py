"""Incremental aggregation with a device-resident stack dictionary.

The TPU-first production design, and the answer to the transfer-cost wall
the batch kernel hits (SURVEY.md section 7 hard part #3): an always-on
profiler sees an almost-stationary stack population, so re-shipping and
re-deduplicating every stack every 10 s window — which is what the
reference's obtainProfiles does (pkg/profiler/cpu/cpu.go:505-718), and
what our batch kernel faithfully accelerates — wastes nearly all of its
work. Instead the device keeps a persistent open-addressing hash table of
every stack ever seen:

  device state   h1/h2/h3 uint32 [C] (96-bit identity), occupied bool [C],
                 stack_id int32 [C] (dense insertion order)
  per window     one jit call: batched linear-probe LOOKUP of all rows,
                 scatter-add counts by stack_id -> counts[C]; fetch is one
                 int32 [id_cap] buffer, independent of stack width.

Misses (stacks not yet in the table) come back in a fixed-width miss
buffer; the HOST owns insertion: it keeps an exact mirror (the same probe
sequence on the same arrays), assigns dense ids, resolves the new stacks'
locations/mappings once (numpy, incremental), and scatters the few new
entries into the device table. First window pays full insertion; steady
state inserts ~nothing.

Identity is the 96-bit triple (h1,h2,h3) of the full padded row: collision
probability over 1M stacks is ~1e-17 (the reference accepts 32-bit
MurmurHash identity for its DWARF stacks, cpu.bpf.c:438-448 — this is 64
bits stronger). The profile outputs are therefore exact per-stack counts;
the one contract deviation from the batch backends is that each PidProfile
lists the pid's full location registry (every location seen so far), a
superset of the window's — valid pprof, same samples.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from parca_agent_tpu.aggregator.base import PidProfile, ProfileMapping
from parca_agent_tpu.capture.formats import (
    KERNEL_ADDR_START,
    STACK_SLOTS,
    MappingTable,
    WindowSnapshot,
    fold_rows_first_seen,
)
from parca_agent_tpu.ops.hashing import native_hash_available, row_hash_np
from parca_agent_tpu.runtime import device_telemetry as dtel
from parca_agent_tpu.runtime import trace
from parca_agent_tpu.utils import faults

# Linear-probe bound. The capacity guard keeps load factor <= 0.5, and at
# the default table sizing (2x the id capacity) it stays <= 0.25, where
# chains beyond 16 are rare enough that whole windows see none — which
# matters because ANY overflow miss costs one extra device->host fetch of
# the miss buffer. Chains that do exceed the bound are absorbed by the
# host as overflow misses; exactness is unaffected either way.
_PROBES = 16

# Miss batches at or above this size take the vectorized settle path
# (plan-then-commit over the host mirror, one registry append per batch);
# below it the scalar loop's constant factors win and the batch is noise.
_VEC_MISS_MIN = 512

# Live frames of new stacks that the register step takes at once (whole
# pids; _register_stacks_bulk). The sorts' working set and, above all,
# the Python integers that one tolist() a column makes of a group (~130
# bytes a fresh address over the four columns) stay in the tens of MB
# whatever the batch: a first window of 1M stacks is ~25M frames. A
# steady-state window's misses (a build node's ~220,000 frames) fit in
# one group. A constant, not a setting: results do not depend on it.
_REGISTER_FRAME_BUDGET = 1 << 18


# Block length of the two-level prefix sum below.
_SCAN_BLOCK = 1024


def prefix_sum(x):
    """Inclusive prefix sum of a 1-D integer array: the values of
    ``jnp.cumsum(x)``, in a shape XLA:TPU can compile.

    XLA:TPU's compile time for a long 1-D cumsum grows much faster than
    its length: 0.2 s at 4,096 lanes, 1.1 s at 65,536, 33.7 s at
    1,048,576 (TPU v5 lite, jax 0.9.0) — and every feed/close program
    here compacts with one, so at the flagship size each new shape
    bucket cost ~30 s of compiler inside a 60 s device watchdog. Summing
    1024-lane blocks along a second axis and adding the blocks' own
    (recursive) prefix compiles in 0.8 s at 1,048,576 lanes and gives
    the same integers."""
    import jax.numpy as jnp

    n = x.shape[0]
    if n <= 4 * _SCAN_BLOCK or n % _SCAN_BLOCK:
        return jnp.cumsum(x)
    inner = jnp.cumsum(x.reshape(n // _SCAN_BLOCK, _SCAN_BLOCK), axis=1)
    totals = inner[:, -1]
    return (inner + (prefix_sum(totals) - totals)[:, None]).reshape(n)


# The probe's narrow stages, for a feed of at least _PROBE_NARROW_MIN
# lanes: (divisor of n_pad, steps probed at that width). Step 0 gathers a
# dictionary row for every lane; a gather costs per lane, not per byte
# (docs/perf.md "The probe"), and at the load the capacity guard admits
# most lanes resolve at once, so the lanes that still search are
# compacted into a buffer a quarter as wide for steps 1-3 and one a
# sixty-fourth as wide for the twelve left. Constants of n_pad: what the
# data decides is how many steps run and how many rounds a stage takes.
_PROBE_STAGES = ((4, 3), (64, _PROBES - 4))
_PROBE_NARROW_MIN = 1 << 15


def make_probe(cap: int, n: int):
    """Pure (unjitted) batched linear-probe lookup: for each of ``n``
    lanes the id of the first slot among ``h1 .. h1 + _PROBES - 1`` (mod
    ``cap``) that holds its ``(h1, h2, h3)``, the search ending at the
    first empty slot; -1 where there is none, and for lanes not
    ``live``. ``probe(table, keys, live)`` takes the lanes' keys as rows
    (``[n, >= 3]``: h1, h2, h3) and returns ``(found_id, gathers)``,
    ``gathers`` the dictionary rows it gathered (a gather's width, summed
    over the gathers run; 16 * n when every step runs at full width).

    A gather is issued only for buffers that hold a lane still searching:
    every loop ends when none is left, and from ``_PROBE_NARROW_MIN``
    lanes up the lanes left after step 0 are compacted, in lane order,
    into the narrower buffers of ``_PROBE_STAGES``. A stage that is
    handed more lanes than its buffer holds takes them in further
    rounds: no lane is dropped at any load."""
    import jax
    import jax.numpy as jnp

    mask = jnp.uint32(cap - 1)
    plan = _PROBE_STAGES if n >= _PROBE_NARROW_MIN else ()

    def steps(table, keys, k0, k1, found, active, gathers):
        """Steps k0 .. k1 - 1 over the whole buffer, while a lane of it
        searches."""
        h1, h2, h3 = keys[:, 0], keys[:, 1], keys[:, 2]

        def step(state):
            k, found, active, gathers = state
            row = table[((h1 + k) & mask).astype(jnp.int32)]
            occ = row[:, 3] > 0
            hit = occ & (row[:, 0] == h1) & (row[:, 1] == h2) \
                & (row[:, 2] == h3)
            found = jnp.where(active & hit, row[:, 3].astype(jnp.int32) - 1,
                              found)
            return k + 1, found, active & occ & ~hit, gathers + h1.shape[0]

        _, found, active, gathers = jax.lax.while_loop(
            lambda s: (s[0] < k1) & s[2].any(), step,
            (jnp.uint32(k0), found, active, gathers))
        return found, active, gathers

    def narrow(table, keys, k0, stages, found, active, gathers):
        """The ``active`` lanes of a buffer, probed from step k0 on in
        buffers of the stages' widths; their ids land in ``found``."""
        (div, n_steps), rest = stages[0], stages[1:]
        n_w, w = keys.shape[0], n // div
        ones = active.astype(jnp.int32)
        pos = prefix_sum(ones) - 1
        total = ones.sum()

        def one_round(state):
            r, found, gathers = state
            at = pos - r * w
            lanes = jnp.full((w,), n_w, jnp.int32).at[
                jnp.where(active & (at >= 0), at, w)].set(
                jnp.arange(n_w, dtype=jnp.int32), mode="drop")
            sub_keys = keys[jnp.minimum(lanes, n_w - 1)]
            sub_found, sub_active, gathers = steps(
                table, sub_keys, k0, k0 + n_steps,
                jnp.full((w,), -1, jnp.int32), lanes < n_w, gathers)
            if rest:
                sub_found, gathers = narrow(
                    table, sub_keys, k0 + n_steps, rest, sub_found,
                    sub_active, gathers)
            return r + 1, found.at[lanes].set(sub_found, mode="drop"), \
                gathers

        _, found, gathers = jax.lax.while_loop(
            lambda s: s[0] * w < total, one_round,
            (jnp.int32(0), found, gathers))
        return found, gathers

    def probe(table, keys, live):
        found, active, gathers = steps(
            table, keys, 0, 1 if plan else _PROBES,
            jnp.full((n,), -1, jnp.int32), live, jnp.int32(0))
        if plan:
            found, gathers = narrow(table, keys, 1, plan, found, active,
                                    gathers)
        return found, gathers

    return probe


def make_feed(cap: int, id_cap: int, n_pad: int, n_blocks: int = 0,
              blk: int = 0):
    """Pure (unjitted) streaming-window accumulate: batched linear-probe
    lookup of all rows against the device stack dictionary, scatter-adding
    hits into a persistent device accumulator.

    The TPU-native answer to the reference's in-kernel accumulation (its
    BPF stack_counts map absorbs samples DURING the window so window close
    is cheap, bpf/cpu/cpu.bpf.c:110-116): capture drains feed the device
    once a second, so the host<->device traffic rides the idle window and
    close only has to pack + fetch.

    With n_blocks > 0 the feed also maintains a touched-block flag array
    (one int32 per `blk` consecutive stack ids): every accumulated hit
    marks its id's block, and the delta close (make_close_delta) fetches
    only marked blocks."""
    import jax
    import jax.numpy as jnp

    probe = make_probe(cap, n_pad)

    def feed(table, acc, touch, packed, reset):
        # reset != 0: this is the first feed of a new window; the previous
        # window's accumulator contents (kept across close for lossless
        # retry) are discarded here, on device — touch flags with them.
        acc = jnp.where(reset != 0, 0, acc)
        if n_blocks:
            touch = jnp.where(reset != 0, 0, touch)
        h1 = packed[0]
        cnt = packed[3].astype(jnp.int32)
        live = cnt > 0

        # The named scopes are what an operator reads in the profiler's
        # trace (``probe`` where the op list says ``while``); the
        # module's own name (``jit_feed``) is not theirs to change.
        # Lanes with no count (padding, rows the host settled as
        # unreachable) never search.
        with jax.named_scope("probe"):
            found_id, gathers = probe(table, packed.T, live)

        with jax.named_scope("accumulate"):
            hit = (found_id >= 0) & live
            acc = acc.at[jnp.where(hit, found_id, id_cap)].add(
                cnt, mode="drop")
            if n_blocks:
                touch = touch.at[jnp.where(hit, found_id // blk,
                                           n_blocks)].set(1, mode="drop")
        with jax.named_scope("miss_compaction"):
            miss = live & ~hit
            mtgt = jnp.where(miss, prefix_sum(miss.astype(jnp.int32)) - 1,
                             jnp.int32(n_pad))
            miss_rows = jnp.full((n_pad,), -1, jnp.int32).at[mtgt].set(
                jnp.arange(h1.shape[0], dtype=jnp.int32), mode="drop")
            n_miss = miss.astype(jnp.int32).sum()
        # One small buffer for the settle's one fetch: the miss count and
        # the dictionary rows the probe gathered.
        return acc, touch, jnp.stack([n_miss, gathers]), miss_rows

    return feed


# Room for every power of two a run's first feed meets (1,024 up to the
# 262,144 rows of a firehose window fed in one piece are nine): a program
# that fell out of this cache would be traced and compiled again.
@functools.lru_cache(maxsize=16)
def _feed_program(cap: int, id_cap: int, n_pad: int, n_blocks: int,
                  blk: int):
    import jax

    return jax.jit(make_feed(cap, id_cap, n_pad, n_blocks, blk),
                   donate_argnums=(1, 2))


# The fewest rows a feed is padded to. A feed's program has one shape per
# power of two of its rows, and a streamed window's later drains ship only
# the stacks the carry cache has not met: a handful, whose count differs
# from drain to drain. Up to this floor they share ONE program; without
# it every count from 16 rows up compiled its own, on the feed thread and
# under its 3 s watchdog, in whichever window first brought it. 1,024
# rows are 16 KB of H2D a feed and ~0.3 ms of the probe loop where 16
# rows are ~0.04 (PERF.md section 6, PR 37). Above the floor a carrying
# aggregator's first feed runs every power of two down from its own
# (feed), so the shapes a run's later feeds fall through are compiled
# once, under the first feed's long budget.
_FEED_PAD_MIN = 1 << 10

# What a deferred settle records, by where it runs. A feed's settle runs
# inside the next feed (or, one-shot, inside the close of its own
# window); a streamed window's LAST feed is settled by the close, on
# another thread and under another parent than the nine before it, and
# a stage name stands under one parent only (the benchmark's readers key
# a window's spans by stage), so that settle has names of its own.
_SETTLE_STAGES = {k: k for k in (
    "feed_settle", "feed_miss", "miss_plan", "miss_register",
    "miss_scatter", "carry_admit")}
_CLOSE_SETTLE_STAGES = {
    "feed_settle": "close_settle", "feed_miss": "close_miss",
    "miss_plan": "close_miss_plan", "miss_register": "close_miss_register",
    "miss_scatter": "close_miss_scatter", "carry_admit": "close_carry_admit"}

# Rows a miss-scatter call writes. A window's newly inserted rows go to
# the device table in chunks of this many, the last one padded with a
# slot beyond the table (dropped), so the program has ONE shape whatever
# the count of new stacks: it compiles in the first window that misses
# on a dictionary that holds something and never again (an eager
# ``.at[slots].set(vals)`` compiled one set of programs per count).
# 8,192 rows are 160 KB a call: a rollout window (80 to 10,000 new
# stacks) is one or two calls.
_SCATTER_CHUNK = 1 << 13


@functools.lru_cache(maxsize=4)
def _scatter_program(cap: int):
    """``table[slots] = vals`` over one chunk, in place (the table is
    donated); a slot >= cap is padding. XLA names it
    ``jit_miss_scatter`` (the benchmark's roofline reader finds it by
    that name)."""
    import jax

    def miss_scatter(table, slots, vals):
        return table.at[slots].set(vals, mode="drop")

    return jax.jit(miss_scatter, donate_argnums=0)


# Overflow sideband caps for the packed close fetch: ids whose window
# count exceeds the packing sentinel. The accumulator is NOT cleared by
# close (it resets on the next window's first feed), so a sideband overrun
# is recoverable: the host just re-runs close at a wider packing and/or a
# larger sideband. Width 16 at the max sideband is the lossless backstop —
# any window total < 2^31 yields at most 2^31/65535 = 32768 overflows,
# exactly its max sideband size. The sideband actually FETCHED is sized
# predictively from the previous window (stationary count distributions
# make overflow populations stable), floored at _OVER_MIN — at the max
# cap the sideband is 1/3 of the whole close buffer, so shipping only the
# needed prefix is a real fraction of close latency on a thin link.
_CLOSE_OVERS = {4: 1 << 15, 8: 1 << 15, 16: 1 << 15}
_OVER_MIN = 1 << 12


def make_close(id_cap: int, n_fetch: int, width: int,
               n_over_buf: int):
    """Pure (unjitted) window close: pack the accumulator's first n_fetch lanes to
    uint{width} (width 4 packs two counts per byte) with an exact
    (id, count) overflow sideband. The accumulator is left intact.

    Output is ONE uint32 buffer (D2H round trips dominate at close):
      [ n_fetch*width/32 lanes : packed counts, little-endian within u32
      | n_over_buf             : overflow ids (u32; n_fetch = none)
      | n_over_buf             : overflow counts
      | 1                      : n_overflow (may exceed n_over_buf: retry)
      | 1                      : count mass beyond n_fetch (guard; 0) ]
    """
    import jax
    import jax.numpy as jnp

    assert width in (4, 8, 16)
    sentinel = (1 << width) - 1
    per32 = 32 // width

    def close(acc):
        with jax.named_scope("pack"):
            head = acc[:n_fetch]
            over = head > (sentinel - 1)
            vals = jnp.where(over, sentinel, head).astype(jnp.uint32)
            shifts = (jnp.arange(per32, dtype=jnp.uint32) * width)[None, :]
            lanes = (vals.reshape(-1, per32) << shifts).sum(
                axis=1, dtype=jnp.uint32)
        with jax.named_scope("overflow_sideband"):
            tgt = jnp.where(over, prefix_sum(over.astype(jnp.int32)) - 1,
                            jnp.int32(n_over_buf))
            ids = jnp.arange(n_fetch, dtype=jnp.uint32)
            over_id = jnp.full((n_over_buf,),
                               jnp.uint32(n_fetch)).at[tgt].set(
                ids, mode="drop")
            over_val = jnp.zeros((n_over_buf,), jnp.uint32).at[tgt].set(
                head.astype(jnp.uint32), mode="drop")
            n_over = over.astype(jnp.uint32).sum()
            tail_total = acc[n_fetch:].sum().astype(jnp.uint32)
        out = jnp.concatenate([
            lanes, over_id, over_val, n_over[None], tail_total[None]])
        return out

    return close


@functools.lru_cache(maxsize=24)
def _close_program(id_cap: int, n_fetch: int, width: int,
                   n_over_buf: int):
    import jax

    return jax.jit(make_close(id_cap, n_fetch, width, n_over_buf))


# Delta-fetch granularity: stack ids per touched-block flag. A multiple
# of every pack width's per32 (8 at width 4), small enough that a hot
# working set with the usual insertion-order locality (a pid's stacks
# get consecutive ids) fetches tight block runs, large enough that the
# flag array stays trivial (id_cap/128 int32s = 32 KB at 1M ids).
_DELTA_BLOCK = 128
# Delta fetch must move strictly less than half the full fetch's rows to
# be worth its second buffer dimension; past this the full close is used.
_DELTA_MAX_FRAC = 0.5
# The fewest blocks a delta close fetches, as a share of the full
# fetch's: 1/64 of it (64 blocks = 4 KB at width 4 beside a full fetch
# of 256 KB at 524,288 ids), and never under 8. The block buffer is a
# shape of the close program, a power of two over twice the window
# before's touched blocks; a streamed window touches only the blocks of
# the few keys the carry cache leaves to the device (a firehose
# population's h1 collisions: a handful, one more every few windows),
# and a buffer sized by that count alone crossed from 8 to 16 to 32
# inside a run, each a compile of ~0.4 s on the window's path (PERF.md
# section 6, PR 44). Under the floor the count moves no shape.
_DELTA_MIN_DIV = 64


def make_close_delta(id_cap: int, n_fetch: int, width: int,
                     n_over_buf: int, n_blk_buf: int, blk: int):
    """Pure (unjitted) delta window close: pack ONLY the touched blocks
    of the accumulator (rows written since the window opened — the feed
    marks them, make_feed) at uint{width}, with the same exact
    (id, count) overflow sideband as make_close. The accumulator is left
    intact, so every misprediction retries against it losslessly.

    Output is ONE uint32 buffer:
      [ n_blk_buf*blk*width/32 lanes : packed counts of touched blocks
      | n_blk_buf                    : touched block ids (nb_prefix = none)
      | n_over_buf                   : overflow GLOBAL ids (n_fetch = none)
      | n_over_buf                   : overflow counts
      | 1 : n_touched blocks (may exceed n_blk_buf: grow / full retry)
      | 1 : n_overflow (may exceed n_over_buf: grow-then-widen retry)
      | 1 : count mass in UNTOUCHED prefix blocks (exactness guard; 0)
      | 1 : count mass beyond n_fetch (guard; 0) ]
    """
    import jax
    import jax.numpy as jnp

    assert width in (4, 8, 16)
    assert n_fetch % blk == 0
    sentinel = (1 << width) - 1
    per32 = 32 // width
    nb_prefix = n_fetch // blk

    def close(acc, touch):
        with jax.named_scope("touched_blocks"):
            t = touch[:nb_prefix] > 0
            n_touched = t.astype(jnp.uint32).sum()
            tgt = jnp.where(t, prefix_sum(t.astype(jnp.int32)) - 1,
                            jnp.int32(n_blk_buf))
            blk_ids = jnp.full((n_blk_buf,),
                               jnp.uint32(nb_prefix)).at[tgt].set(
                jnp.arange(nb_prefix, dtype=jnp.uint32), mode="drop")
            live_b = blk_ids < nb_prefix
            safe = jnp.minimum(blk_ids, nb_prefix - 1).astype(jnp.int32)
            gidx = safe[:, None] * blk \
                + jnp.arange(blk, dtype=jnp.int32)[None, :]
            vals = jnp.where(live_b[:, None], acc[gidx], 0).reshape(-1)
        with jax.named_scope("pack"):
            over = vals > (sentinel - 1)
            pk = jnp.where(over, sentinel, vals).astype(jnp.uint32)
            shifts = (jnp.arange(per32, dtype=jnp.uint32) * width)[None, :]
            lanes = (pk.reshape(-1, per32) << shifts).sum(axis=1,
                                                          dtype=jnp.uint32)
        with jax.named_scope("overflow_sideband"):
            gid = gidx.reshape(-1).astype(jnp.uint32)
            otgt = jnp.where(over, prefix_sum(over.astype(jnp.int32)) - 1,
                             jnp.int32(n_over_buf))
            over_id = jnp.full((n_over_buf,),
                               jnp.uint32(n_fetch)).at[otgt].set(
                gid, mode="drop")
            over_val = jnp.zeros((n_over_buf,), jnp.uint32).at[otgt].set(
                vals.astype(jnp.uint32), mode="drop")
            n_over = over.astype(jnp.uint32).sum()
        # Exactness guards: untouched prefix blocks and the tail beyond
        # n_fetch must both carry zero mass (the acc resets at window
        # open and the feed marks every add). A nonzero guard means the
        # touch tracking missed a write — the host falls back to the
        # full fetch, so a guard trip can degrade speed, never counts.
        blk_mass = acc[:n_fetch].reshape(nb_prefix, blk).sum(axis=1)
        untouched = jnp.where(~t, blk_mass, 0).sum().astype(jnp.uint32)
        tail = acc[n_fetch:].sum().astype(jnp.uint32)
        return jnp.concatenate([
            lanes, blk_ids, over_id, over_val,
            n_touched[None], n_over[None], untouched[None], tail[None]])

    return close


@functools.lru_cache(maxsize=24)
def _close_program_delta(id_cap: int, n_fetch: int, width: int,
                         n_over_buf: int, n_blk_buf: int, blk: int):
    import jax

    return jax.jit(make_close_delta(id_cap, n_fetch, width, n_over_buf,
                                    n_blk_buf, blk))


def _obj_name(names: tuple, obj: int) -> str:
    """A mapping row's object path or build id ("" where the table has
    none for it)."""
    return names[obj] if 0 <= obj < len(names) else ""


def _table_mappings(table, rows: np.ndarray, ids: np.ndarray):
    """The mappings that first-seen pids' registries start with, for
    all such pids of a group at once (cpu.py's ``_pid_mappings``, one
    tolist() a column): the ``ProfileMapping`` of every table row in
    ``rows``, its 1-based position in its pid's table as id, and their
    ``mapping_index`` keys."""
    starts, ends, offsets = (c[rows].tolist() for c in (
        table.starts, table.ends, table.offsets))
    objs = table.objs[rows].tolist()
    maps = [ProfileMapping(*m) for m in zip(
        ids.tolist(), starts, ends, offsets,
        [_obj_name(table.obj_paths, o) for o in objs],
        [_obj_name(table.obj_buildids, o) for o in objs],
        table.bases[rows].tolist())]
    return maps, list(zip(starts, ends, offsets))


def _scatter_chunks(slots: np.ndarray, vals: np.ndarray, pad_slot: int):
    """(slots int32 [_SCATTER_CHUNK], vals uint32 [_SCATTER_CHUNK, 4])
    pieces of a batch of new rows; the last piece is padded with
    ``pad_slot`` (out of the table's range: the scatter drops it)."""
    for lo in range(0, len(slots), _SCATTER_CHUNK):
        n = min(_SCATTER_CHUNK, len(slots) - lo)
        slots_c = np.full(_SCATTER_CHUNK, pad_slot, np.int32)
        slots_c[:n] = slots[lo:lo + n]
        vals_c = np.zeros((_SCATTER_CHUNK, 4), np.uint32)
        vals_c[:n] = vals[lo:lo + n]
        yield slots_c, vals_c


class _CloseHandle:
    """One dispatched-but-uncollected window close (close_dispatch). The
    accumulator/touch references are the PRE-FLIP buffers: immutable jax
    arrays the retry loop can re-pack any number of times while the next
    window's feeds land in the flipped twin."""

    __slots__ = ("acc", "touch", "fed_total", "pending", "pending_vec",
                 "n_ids", "n_fetch", "width", "n_over_buf", "delta_blks",
                 "out_dev")

    def __init__(self):
        self.acc = None
        self.touch = None
        self.fed_total = 0
        self.pending = []
        # The carry cache's window flush: (sids int64, counts int64)
        # arrays, applied once at collect (same lifecycle as pending).
        self.pending_vec = None
        self.n_ids = 0
        self.n_fetch = 0
        self.width = 0
        self.n_over_buf = 0
        self.delta_blks = 0
        self.out_dev = None


def registry_content_digest(mappings, loc_address, loc_normalized,
                            loc_mapping_id, loc_is_kernel) -> bytes:
    """16-byte digest of one pid registry's full content — mappings (all
    fields, including the normalization base) and every location row.
    This is the content-addressing identity the statics snapshot uses
    (pprof/statics_store.py): a record whose stored digest does not match
    the digest recomputed from its decoded content is discarded as
    corrupt, and the pprof statics cached against this content are valid
    exactly as long as the content is byte-identical."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    for m in mappings:
        h.update(("%d,%d,%d,%d,%d,%s\0%s\0" % (
            m.id, m.start, m.end, m.offset, m.base, m.path,
            m.build_id)).encode())
    h.update(b";")
    h.update(np.asarray(loc_address, np.uint64).tobytes())
    h.update(np.asarray(loc_normalized, np.uint64).tobytes())
    h.update(np.asarray(loc_mapping_id, np.int32).tobytes())
    h.update(np.asarray(loc_is_kernel, bool).tobytes())
    return h.digest()


def _frozen(view: np.ndarray) -> np.ndarray:
    view.flags.writeable = False
    return view


def _grown(buf: np.ndarray, n: int, room: int) -> np.ndarray:
    """A buffer of ``room`` rows whose first ``n`` are ``buf``'s."""
    grown = np.empty(room, buf.dtype)
    grown[:n] = buf[:n]
    return grown


class _PidRegistry:
    """Per-pid incremental location registry (grows, never shrinks).

    The location table is four numpy columns of ONE published length,
    ``n_locs``: ``loc_address`` (uint64), ``loc_normalized`` (uint64),
    ``loc_mapping_id`` (int32), ``loc_is_kernel`` (bool). Each reads as
    a view of the published rows (``len()``, ``col[i]`` and ``col[:n]``
    as a list would, a slice without a copy; nobody but ``append_locs``
    writes). Rows below a published length never change;
    ``append_locs`` writes the rows first and the length last, and a
    growth publishes buffers whose prefix is equal before it publishes
    the length, so a reader on another thread that froze ``n_locs`` (the
    encoder's caps, the statics store) may read ``col[:n]`` while the
    owner registers the next window.

    No address look-up is kept for a pid that is never asked:
    ``index()`` builds one from the address column the first time a
    known pid brings addresses to test (a dictionary, address -> 1-based
    location id: the tests are a few addresses each of many pids, which
    a sorted order searched a pid at a time loses to), and
    ``append_locs`` extends it from there.

    Mappings are append-only with registry-stable 1-based ids: when a
    later window brings a changed mapping table (dlopen, remap), new
    ranges get NEW ids; existing loc_mapping_id values stay valid against
    this registry's list rather than dangling into the new window's table.
    """

    __slots__ = ("n_locs", "_address", "_normalized", "_mapping_id",
                 "_is_kernel", "_index", "mappings", "mapping_index")

    def __init__(self, loc_address: np.ndarray, loc_normalized: np.ndarray,
                 loc_mapping_id: np.ndarray, loc_is_kernel: np.ndarray,
                 mappings: list, mapping_index: dict):
        # The four arrays become the registry's own: the caller hands
        # over copies (a run cut from a group's arrays would pin them
        # for as long as the pid lives; a restored record's buffer is
        # read-only).
        assert (loc_address.dtype, loc_normalized.dtype,
                loc_mapping_id.dtype, loc_is_kernel.dtype) == (
            np.uint64, np.uint64, np.int32, np.bool_)
        self._address = loc_address
        self._normalized = loc_normalized
        self._mapping_id = loc_mapping_id
        self._is_kernel = loc_is_kernel
        self.n_locs = len(loc_address)
        self._index = None  # int address -> location id, once asked
        self.mappings = mappings  # ProfileMapping, registry-stable ids
        self.mapping_index = mapping_index  # (start, end, offset) -> id

    # The length is read BEFORE the buffer: a buffer read after a length
    # holds at least that many finished rows.
    @property
    def loc_address(self) -> np.ndarray:
        n = self.n_locs
        return self._address[:n]

    @property
    def loc_normalized(self) -> np.ndarray:
        n = self.n_locs
        return self._normalized[:n]

    @property
    def loc_mapping_id(self) -> np.ndarray:
        n = self.n_locs
        return self._mapping_id[:n]

    @property
    def loc_is_kernel(self) -> np.ndarray:
        n = self.n_locs
        return self._is_kernel[:n]

    @property
    def nbytes(self) -> int:
        """What the columns hold, spare rows included, and an estimate
        of the look-up where one was built (~96 B an entry: its slot,
        a boxed address, a boxed id)."""
        n = (self._address.nbytes + self._normalized.nbytes
             + self._mapping_id.nbytes + self._is_kernel.nbytes)
        if self._index is not None:
            n += 96 * len(self._index)
        return n

    def append_locs(self, address, normalized, mapping_id,
                    is_kernel) -> None:
        """Append a run of locations (``address`` ascending and in none
        of the rows before). Owner thread only."""
        n0 = self.n_locs
        n1 = n0 + len(address)
        a, b, c, d = (self._address, self._normalized, self._mapping_id,
                      self._is_kernel)
        if n1 > len(a):
            # Room for as much again: a pid that grows once tends to
            # grow again (a streamed window's new pids, in each of its
            # later drains).
            a, b, c, d = (_grown(buf, n0, 2 * n1) for buf in (a, b, c, d))
        a[n0:n1] = address
        b[n0:n1] = normalized
        c[n0:n1] = mapping_id
        d[n0:n1] = is_kernel
        self._address, self._normalized = a, b
        self._mapping_id, self._is_kernel = c, d
        if self._index is not None:
            self._index.update(zip(address.tolist(), range(n0 + 1, n1 + 1)))
        self.n_locs = n1          # published last

    def index(self) -> tuple[dict, bool]:
        """The address look-up (plain ints: an np.uint64 key would miss
        every look-up), and whether this call had to build it."""
        built = self._index is None
        if built:
            n = self.n_locs
            self._index = dict(zip(self._address[:n].tolist(),
                                   range(1, n + 1)))
        return self._index, built


class DictAggregator:
    """Stateful exact aggregation; reuse one instance across windows.

    Bounded memory (the role the reference's hard 10,240-entry BPF map cap
    plays, bpf/cpu/cpu.bpf.c:28-34, which silently DROPS new stacks when
    full): with overflow="sketch" (default), stacks that arrive once the
    dictionary is full are absorbed into a host count-min sketch + HLL
    (approximate counts with known bounds instead of silent loss), and at
    the next window boundary cold stacks — unseen for rotate_min_age
    windows — are evicted and their ids recycled, so an always-on agent on
    a stack-churny host runs in bounded memory indefinitely.
    overflow="raise" keeps the old fail-fast contract for fixed-population
    benchmarks."""

    name = "dict"

    def __init__(self, capacity: int = 1 << 21, id_cap: int | None = None,
                 overflow: str = "sketch",
                 cm_spec: "CountMinSpec | None" = None,
                 rotate_min_age: int = 6,
                 delta_fetch: bool = True,
                 coalesce: bool = True,
                 carry: bool = False):
        from parca_agent_tpu.ops.sketch import CountMinSpec, HLLSpec

        if capacity & (capacity - 1):
            raise ValueError("capacity must be a power of two")
        if overflow not in ("sketch", "raise"):
            raise ValueError("overflow must be 'sketch' or 'raise'")
        self._cap = capacity
        self._id_cap = id_cap or capacity // 2
        self._overflow = overflow
        # Host-side feed coalescing (docs/perf.md "ingest wall"): dedupe
        # each feed batch into (stack, weight) pairs on the (h1, h2, h3)
        # identity BEFORE packing, so dispatch rows track unique stacks,
        # not sample rows. Exact by the same 96-bit identity the whole
        # aggregator keys on (equal triples accumulate into one id
        # anyway; coalescing just sums their counts one boundary
        # earlier), and first-occurrence ordered so miss order — and
        # therefore id assignment and pprof bytes — is bit-identical to
        # the uncoalesced stream. A coalesce failure (chaos site
        # feed.coalesce) is counted and degrades to the uncoalesced
        # path, never a lost feed.
        self._coalesce = coalesce
        # Cross-drain carry cache (docs/perf.md "feed endgame"): an
        # h1-sorted host map key -> (stack id, accumulated weight). A
        # stack's FIRST dispatch admits its key; every later drain that
        # sees the key folds its mass host-side instead of shipping a
        # dispatch row, and the close flushes the accumulated (sid,
        # weight) pairs alongside the pending corrections. With a
        # stationary population the steady-state window dispatches ~no
        # rows at all — one dispatch row per unique NEW stack, ever.
        # Weights are zeroed at every window boundary (close flush,
        # discard), so corrections never leak across windows; the
        # key->sid entries persist until rotation remaps the id space.
        # Bounded by construction: at most one entry per live stack id.
        self._carry = carry
        self._carry_h1 = np.zeros(0, np.uint32)  # sorted, unique
        self._carry_h2 = np.zeros(0, np.uint32)
        self._carry_h3 = np.zeros(0, np.uint32)
        self._carry_sid = np.zeros(0, np.int64)
        self._carry_w = np.zeros(0, np.int64)
        # Prefix-bucket index over _carry_h1: starts[p] .. starts[p+1]
        # bounds the entries whose top carry_shift-complement bits equal
        # p. Binary search over a million needles is cache-hostile
        # (measured 115 ms of a 145 ms steady feed at the 500k-pid
        # tier); the direct-indexed bucket walk is ~O(1) probes per
        # needle at <=0.5 load. Rebuilt only at admission.
        self._carry_shift = 32
        self._carry_starts = np.zeros(2, np.int64)
        self._carry_open_mass = 0   # mass carried for the open window
        self._carry_disabled = False  # fault: match off until boundary
        self._shapes_met = False    # the first feed has run its shapes
        self._cm_spec = cm_spec or CountMinSpec()
        self._hll_spec = HLLSpec()
        self._cm = None                  # lazy [depth, width] int64
        self._over_hll = None            # lazy [m] int32 registers
        self._rotate_min_age = rotate_min_age
        self._rotate_pending = False
        # stats["inserts"] at the last window boundary: what the window
        # just closed inserted is the churn _maybe_reclaim sizes room by.
        self._inserts_mark = 0
        # Pids whose invalidate_pid arrived while a close/miss was in
        # flight; drained at the next window boundary (same safety
        # contract as rotation).
        self._invalidate_pending: set[int] = set()
        # The last compaction's (registry_epoch it led to, old id -> new
        # id or -1), for the mirrors that follow it (id_remap).
        self._remap: tuple[int, np.ndarray] | None = None
        # Per-id window number the id last had samples (eviction clock).
        self._last_seen = np.zeros(self._id_cap, np.int32)
        # Host mirror (source of truth).
        self._h1 = np.zeros(capacity, np.uint32)
        self._h2 = np.zeros(capacity, np.uint32)
        self._h3 = np.zeros(capacity, np.uint32)
        self._occ = np.zeros(capacity, bool)
        self._ids = np.full(capacity, -1, np.int32)
        self._key_to_id: dict[tuple, int] = {}
        self._next_id = 0
        # Publication watermark for CONCURRENT READERS (the encode
        # pipeline's worker thread): _next_id advances per-key inside
        # _resolve_misses BEFORE the per-id metadata and per-pid
        # registries are written, so a reader pacing itself by _next_id
        # could index half-written rows. _published advances only after
        # _append_id_meta lands the batch (and at rotation), so ids
        # [0, _published) always have complete, immutable metadata —
        # the encoder's mirrors sync against this, never _next_id.
        self._published = 0
        # Per-id metadata, ragged numpy (appended at insertion): stack id i
        # has pid _id_pid[i] and 1-based per-pid loc ids
        # _loc_flat[_loc_off[i]:_loc_off[i+1]] (depth == run length). Flat
        # arrays instead of a list-of-arrays: profile assembly and the
        # window pprof encoder gather whole windows with single fancy
        # indexes instead of per-id Python loops.
        self._id_pid = np.empty(1024, np.int32)
        self._loc_off = np.zeros(1025, np.int64)
        self._loc_flat = np.empty(4096, np.int32)
        # Per-id content hashes (the h1/h2 identity lanes of the key
        # tuple, in id order): the cross-node join key. The fleet merge
        # and the hotspot rollups (runtime/hotspots.py) key summaries by
        # (h1 << 32 | h2) — content-stable across hosts — and reading
        # them per id here costs one vectorized copy at insert time
        # instead of an O(dict) inversion of _key_to_id per window.
        # Published under the same _published watermark as _id_pid.
        self._id_h1 = np.empty(1024, np.uint32)
        self._id_h2 = np.empty(1024, np.uint32)
        self._pids: dict[int, _PidRegistry] = {}
        # Bumped whenever any per-pid registry may have changed (insert
        # batches, adoption, rotation). Statics consumers use it to skip
        # the O(pids) staleness scan when nothing could be dirty — the
        # scan used to run on EVERY drain-tick prebuild.
        self._reg_version = 0
        # The pids behind those bumps, for the one reader that keeps
        # per-pid state from window to window (take_touched_pids):
        # noted at every site that creates, grows or drops a registry.
        # None until a reader has asked once, so an aggregator nobody
        # reads this from keeps no set of every pid it ever saw.
        self._touched_pids: set | None = None
        self._touched_token = 0
        # Device twin (created lazily; None until first window).
        self._dev = None
        # Streaming-window state (feed/close_window protocol). The
        # accumulator (and its touched-block flags) are DOUBLE-BUFFERED:
        # close_dispatch() flips active<->spare, so window N+1's feeds
        # land in one buffer while window N's pack/fetch (and any
        # grow-then-widen retry) runs against the other. The spare holds
        # the PREVIOUS window's closed accumulator until the flip after
        # next, strictly extending the old keep-until-next-feed retry
        # contract.
        self._acc = None            # active device int32 [id_cap] acc
        self._acc_spare = None      # the other buffer (last closed window)
        self._touch = None          # active int32 [n_blocks] touch flags
        self._touch_spare = None
        self._fed_total = 0         # sample mass fed into the open window
        self._needs_reset = False   # first feed of next window clears acc
        self._prev_counts = None    # last closed window (width prediction)
        self._prev_n_over = 0       # last close's overflow population
        # Delta-fetch state: block granularity (0 = tracking disabled —
        # the id space must divide into _DELTA_BLOCK blocks), and the
        # previous window's touched-block population (None = no history:
        # the next close fetches full and probes the flags host-side).
        self._blk = _DELTA_BLOCK if (
            delta_fetch and self._id_cap % _DELTA_BLOCK == 0) else 0
        self._n_blocks = (self._id_cap // self._blk) if self._blk else 0
        self._prev_touched: int | None = None
        # Deferred feed-miss settle: _feed_dispatch_async returns device
        # handles without a host sync; the miss check settles at the NEXT
        # feed (or at close), by which time the kernel has long finished —
        # the capture thread stops paying the probe kernel's latency.
        # (handle, packed, snapshot, rows_map, w64, h1, h2, h3) — all
        # DISPATCH-row aligned: rows_map maps each dispatched row to its
        # representative snapshot row, w64 is its (possibly folded)
        # mass, h1/h2/h3 its identity triple.
        self._miss_inflight = None
        # The span names of the settle in progress (_settle_misses).
        self._stages = _SETTLE_STAGES
        # Dispatched-but-uncollected close (close_dispatch/close_collect).
        self._close_handle: _CloseHandle | None = None
        # Keys at probe-chain positions >= _PROBES: device lookups can
        # never find them, so feeds settle them host-side pre-ship.
        self._unreachable: dict[tuple, int] = {}
        self._unreach_h1: np.ndarray | None = None
        # Reused host buffers. Fresh multi-MB allocations per feed/close
        # cost kernel page-reclaim time on memory-pressured hosts (each
        # new anonymous page is a zero-fill fault; measured 7 ms -> 75 ms
        # unpack inflation at 1M ids on a loaded 1-core host); warm pages
        # are free. The counts buffer is DOUBLE-buffered because the
        # previous window's array (_prev_counts, and any caller still
        # reading the last close's result) must survive one more close.
        self._feed_bufs: dict[int, np.ndarray] = {}
        self._unpack_bufs: dict[tuple, np.ndarray] = {}
        self._counts_bufs: list = [None, None]
        self._counts_flip = 0
        self._pending: list[tuple[int, int]] = []  # host-side corrections
        self.stats = {"windows": 0, "inserts": 0, "overflow_misses": 0}
        self.timings: dict[str, float] = {}

    # -- public -------------------------------------------------------------

    def aggregate(self, snapshot: WindowSnapshot,
                  hashes=None) -> list[PidProfile]:
        counts = self.window_counts(snapshot, hashes)
        return self._build_profiles(snapshot, counts)

    def hash_rows(self, snapshot: WindowSnapshot):
        """The capture-side identity triple. In production the capture
        source computes/carries this (the reference's BPF maps are KEYED by
        the stack hash — cpu.bpf.c:438-448 — so its hot loop never hashes
        either); replay/synthetic paths call this explicitly. A large
        batch is hashed as row ranges on several threads (ops/hashing.py
        "The row hash across cores"); how this one went is counted here:
        on the open window's `meta` and in `stats`. A ranged hash that
        raised (chaos site feed.hash) was redone by the serial call,
        the same bits, and counts as a fallback."""
        facts: dict = {}
        hashes = row_hash_np(snapshot.stacks, snapshot.pids,
                             snapshot.user_len, snapshot.kernel_len,
                             n_hashes=3, facts=facts)
        if facts:
            trace.count(hash_ranges=facts["ranges"],
                        hash_threads=facts["threads"])
            self.stats["hash_parallel_batches"] = \
                self.stats.get("hash_parallel_batches", 0) \
                + (facts["ranges"] > 1)
            self.stats["hash_parallel_fallbacks"] = \
                self.stats.get("hash_parallel_fallbacks", 0) \
                + ("fallback" in facts)
        return hashes

    def window_counts(self, snapshot: WindowSnapshot,
                      hashes=None) -> np.ndarray:
        """The aggregation core: int64 counts indexed by stack id
        (length == number of stacks known after this window).

        One-shot semantics over the SAME feed/close programs the streaming
        protocol uses (a separate lookup program would be one more
        compile for an 8 MB unpacked fetch; feed + packed close ships the
        window once and fetches ~0.6 MB). Any partially-fed open window is
        discarded first — callers don't mix the two protocols mid-window.
        Id assignment order matches the miss order of a single whole-window
        feed, so results are deterministic for a given snapshot."""
        if len(snapshot) == 0:
            return np.zeros(self._next_id, np.int64)
        self.discard_open_window()
        self.feed(snapshot, hashes)
        return self.close_window(copy=True)

    def discard_open_window(self) -> None:
        """Drop every trace of a partially-fed open window — device mass
        (via the reset flag), host-side pending corrections, and any
        un-settled deferred miss check — without touching the registry.
        The swap-aware recovery entry point: the streaming feeder calls
        this when a one-shot died mid-window or a re-probe needs a clean
        accumulator, and it must stay correct across buffer flips."""
        inflight, self._miss_inflight = self._miss_inflight, None
        if inflight is not None:
            # The dropped feed may still be EXECUTING and (on backends
            # that zero-copy host numpy) aliasing its pack buffer: retire
            # that buffer from the reuse pool rather than sync a device
            # that may be the very thing being recovered from. Dropping
            # the miss check is exact — the discarded window's new stacks
            # were never inserted, so they simply miss again later.
            packed = inflight[1]
            for k, v in list(self._feed_bufs.items()):
                if v is packed:
                    del self._feed_bufs[k]
        self._fed_total = 0
        self._pending = []
        self._needs_reset = True
        # Carried mass of the aborted window must not leak into the
        # next one's flush; the cache itself (key -> sid) stays warm.
        self._carry_disabled = False
        if self._carry_open_mass:
            self._carry_w[:] = 0
            self._carry_open_mass = 0
            self.stats["carry_discards"] = \
                self.stats.get("carry_discards", 0) + 1

    # -- registry identity (statics snapshot support) ------------------------

    def id_hashes(self, n: int | None = None):
        """Per-id content hashes (h1, h2) for ids [0, n) — the host/
        device-stable identity lanes every cross-node consumer keys on
        (fleet merge, hotspot rollups). ``n`` defaults to the published
        watermark; callers off the mutating thread must pass ids they
        observed at or below a _published they read earlier (the same
        contract as every other per-id mirror read)."""
        if n is None:
            n = self._published
        return self._id_h1[:n], self._id_h2[:n]

    @property
    def registry_epoch(self) -> int:
        """Rotation epoch of the id space: bumped whenever a cold-stack
        rotation, a pid-identity invalidation compaction or a reclaim
        (_maybe_reclaim) remaps stack ids wholesale. Mirrors consumers
        (the window encoder, the statics snapshot header) key their
        validity on this."""
        return (self.stats.get("rotations", 0)
                + self.stats.get("invalidation_compactions", 0)
                + self.stats.get("reclaims", 0))

    def id_remap(self, since_epoch: int) -> np.ndarray | None:
        """Where the compaction that took the id space from epoch
        `since_epoch` to the current one put every id: an int64 array
        over the old id space, the new id of a survivor and -1 for an
        id given away; survivors keep their order. One remap is kept, the
        last boundary's: a mirror that is further behind than that (it
        missed a compaction, or was never synced) gets None and
        rebuilds from nothing. The array is never written again."""
        remap = self._remap
        if remap is not None and remap[0] == self.registry_epoch \
                and since_epoch == remap[0] - 1:
            return remap[1]
        return None

    def take_touched_pids(self, token) -> tuple[int, set | None]:
        """The pids whose registry was created, grown or dropped since
        the take that returned `token`, and the token of this take. None
        for the pids when `token` is not the last take's (the first
        take, or another reader took in between): the caller then knows
        nothing of what changed. An id-space compaction is not reported
        here: it bumps registry_epoch. Call on the thread that owns
        aggregator mutation."""
        pids = self._touched_pids if token == self._touched_token else None
        self._touched_pids = set()
        self._touched_token += 1
        return self._touched_token, pids

    def _note_touched(self, pids) -> None:
        if self._touched_pids is not None:
            self._touched_pids.update(pids)

    def footprint_bytes(self) -> dict:
        """Per-lane host-memory accounting for the endurance sentinel
        (bench_zoo/soak.py) and the /healthz ``endurance`` section:
        in a stationary workload every lane must go flat (or sit at its
        construction-time cap) once warm — a lane that keeps climbing is
        the leak the soak verdict fails on. The per-pid registries'
        location columns are arrays and counted as such; their mapping
        objects (and the interned key tuples) are counted at a fixed
        per-entry estimate; the soak bars care about GROWTH, not about
        allocator-exact totals."""
        carry = int(self._carry_h1.nbytes + self._carry_h2.nbytes
                    + self._carry_h3.nbytes + self._carry_sid.nbytes
                    + self._carry_w.nbytes + self._carry_starts.nbytes)
        table = int(self._h1.nbytes + self._h2.nbytes + self._h3.nbytes
                    + self._occ.nbytes + self._ids.nbytes
                    + self._last_seen.nbytes)
        id_meta = int(self._id_pid.nbytes + self._loc_off.nbytes
                      + self._loc_flat.nbytes + self._id_h1.nbytes
                      + self._id_h2.nbytes)
        # ~56 B per interned key tuple entry; ~120 B per mapping row; a
        # registry's location columns (and its address look-up, where
        # one was built) by their arrays' bytes.
        keys = 56 * len(self._key_to_id)
        regs = 0
        for reg in self._pids.values():
            regs += reg.nbytes + 120 * len(reg.mappings)
        return {
            "carry_bytes": carry,
            "table_bytes": table,
            "id_meta_bytes": id_meta,
            "key_index_bytes": int(keys),
            "pid_registry_bytes": int(regs),
        }

    def registry_digest(self, pid: int, n_mappings: int | None = None,
                        n_locs: int | None = None) -> bytes | None:
        """Content digest of one pid's location registry (bounded reads
        for encoder-thread callers, like _reg_cap); None for an unknown
        pid. This is the PUBLIC identity exposure (tests pin that an
        adopted registry digests equal to a replay-built one); internal
        writers digest their loop-local registry object directly via
        registry_content_digest to stay race-free against rotation."""
        reg = self._pids.get(pid)
        if reg is None:
            return None
        nl = reg.n_locs           # locations first: see _reg_cap
        nm = len(reg.mappings) if n_mappings is None else n_mappings
        if n_locs is not None:
            nl = min(nl, n_locs)
        return registry_content_digest(
            reg.mappings[:nm], reg.loc_address[:nl],
            reg.loc_normalized[:nl], reg.loc_mapping_id[:nl],
            reg.loc_is_kernel[:nl])

    def adopt_registry(self, pid: int, mappings, loc_address,
                       loc_normalized, loc_mapping_id,
                       loc_is_kernel) -> bool:
        """Install a snapshot-restored per-pid location registry (the
        statics store's warm-restart path). Cold-start only: refused
        (False) once the pid has a registry — adoption must never alias
        or reorder live loc ids. Adopted content is a valid append-only
        prefix: the pid's first live window translates re-seen addresses
        to their restored ids and appends only the genuinely new ones,
        which is exactly what keeps the restored statics blobs valid."""
        if pid in self._pids:
            return False
        self._pids[pid] = _PidRegistry(
            np.array(loc_address, np.uint64),
            np.array(loc_normalized, np.uint64),
            np.array(loc_mapping_id, np.int32),
            np.array(loc_is_kernel, bool), list(mappings),
            {(m.start, m.end, m.offset): m.id for m in mappings})
        self._reg_version += 1
        self._note_touched((pid,))
        return True

    # -- streaming window protocol -------------------------------------------
    #
    # The production window shape (and the reason close is fast): capture
    # drains arrive once a second, each drain is fed to the device as it
    # lands (H2D + probe kernel ride the otherwise-idle window, exactly as
    # the reference's BPF map absorbs samples in-kernel during the window,
    # bpf/cpu/cpu.bpf.c:110-116), and window close only packs + fetches the
    # accumulated counts. window_counts() remains the one-shot batch path.

    # palint: capture-path — the feed is the capture thread's dispatch-
    # only hot path (docs/perf.md "sub-RTT close"): device work must
    # OVERLAP capture, so no host sync may ride here. Device state for
    # the checker (one line — the grammar does not parse continuations):
    # palint: device-state: _dev, _acc, _touch, _acc_spare, _touch_spare
    def feed(self, snapshot: WindowSnapshot, hashes=None,
             lo: int = 0, hi: int | None = None) -> None:
        """Accumulate snapshot rows [lo, hi) into the open window.

        ``hashes`` is the capture-carried identity triple (h1, h2, h3)
        over ALL snapshot rows — the sampler's dedup drain computes it
        once per unique record (docs/perf.md "feed endgame"); None
        self-hashes here."""

        import jax.numpy as jnp

        hi = len(snapshot) if hi is None else hi
        n = hi - lo
        if n <= 0:
            return
        self.timings.pop("feed_carry", None)
        # Settle the PREVIOUS feed's deferred miss check first: (a) its
        # pack buffer may be reused below and the device may alias host
        # numpy zero-copy, (b) miss resolution (= id assignment) must
        # stay in feed order. Between drains the kernel has long
        # finished, so this sync is a cheap completion check, not the
        # kernel-latency stall the old inline sync paid.
        self._settle_misses()
        chunk_total = int(snapshot.counts[lo:hi].sum())
        if self._fed_total + self._carry_open_mass + chunk_total >= 2**31:
            raise ValueError("window sample total exceeds int32")
        if self._needs_reset:
            # First feed of a new window: the boundary where cold-id
            # rotation (and any deferred pid-identity invalidation) is
            # safe — nothing live indexes stack ids.
            self._apply_pending_invalidations()
            self._maybe_rotate()
            self._maybe_reclaim()
        # Dispatch-row state: `rows_map` maps each dispatch row back to
        # a representative snapshot row (absolute index) for miss
        # resolution; `w64` carries its exact (possibly folded) mass.
        # Carry matches and coalesce folds below filter/fold both in
        # lockstep with the hash lanes.
        w64 = np.asarray(snapshot.counts[lo:hi], np.int64)
        if hashes is not None:
            h1, h2, h3 = hashes
            h1c = np.asarray(h1[lo:hi], np.uint32)
            h2c = np.asarray(h2[lo:hi], np.uint32)
            h3c = np.asarray(h3[lo:hi], np.uint32)
            h2c = self._route_hashes(h1c, h2c, h3c, snapshot.pids[lo:hi])
            # Carry BEFORE the fold: carried rows are known stacks whose
            # mass accumulates host-side; only the remainder pays the
            # fold and the dispatch (rows_map is built lazily — the
            # fully-carried steady-state feed never materializes it).
            keep = self._carry_match(h1c, h2c, h3c, w64)
            if keep is not None:
                h1c, h2c, h3c = h1c[keep], h2c[keep], h3c[keep]
                w64 = w64[keep]
                rows_map = np.flatnonzero(keep) + lo
            else:
                rows_map = np.arange(lo, hi, dtype=np.int64)
            if self._coalesce and len(h1c) > 1:
                h1c, h2c, h3c, w64, rows_map = self._coalesce_triples(
                    h1c, h2c, h3c, w64, rows_map)
        else:
            rows_map = np.arange(lo, hi, dtype=np.int64)
            # Self-hash. The work order depends on the hash backend: the
            # native kernel walks only live depth, so hashing every row
            # then folding by triple is cheapest; the numpy lane-matrix
            # fallback pays O(rows x lanes) per hashed row, so there the
            # fold runs FIRST — on raw row content, the same equality
            # the triple keys (modulo hash collisions the aggregator
            # already tolerates) — and only representatives get hashed.
            fold_first = self._coalesce and n > 1 and (
                bool(os.environ.get("PARCA_NO_NATIVE_HASH"))
                or not native_hash_available())
            rep = None
            if fold_first:
                with trace.child("feed_coalesce") as sp:
                    try:
                        faults.inject("feed.coalesce")
                        sl = slice(lo, hi)
                        depth = (np.asarray(snapshot.user_len[sl], np.int64)
                                 + np.asarray(snapshot.kernel_len[sl],
                                              np.int64))
                        md = int(depth.max(initial=0))
                        rec = np.empty((n, 3 + md), np.uint64)
                        rec[:, 0] = np.asarray(snapshot.pids[sl],
                                               np.int64).view(np.uint64)
                        rec[:, 1] = np.asarray(snapshot.user_len[sl],
                                               np.uint64)
                        rec[:, 2] = np.asarray(snapshot.kernel_len[sl],
                                               np.uint64)
                        if md:
                            rec[:, 3:] = snapshot.stacks[sl, :md]
                        folded = fold_rows_first_seen(
                            rec.view(np.dtype(
                                (np.void, (3 + md) * 8))).ravel(), w64)
                        if folded is not None:
                            rep, _inv, fw = folded
                            w64 = fw
                            rows_map = rows_map[rep]
                        self.stats["coalesce_rows_in"] = \
                            self.stats.get("coalesce_rows_in", 0) + n
                        self.stats["coalesce_rows_out"] = \
                            self.stats.get("coalesce_rows_out", 0) \
                            + len(rows_map)
                    except Exception as e:  # noqa: BLE001 - counted fallback
                        # Fail-open to the unfolded batch (locals are only
                        # rebound on success above, so rows_map/w64 are
                        # intact); the triple fold is NOT retried — one fold
                        # attempt per feed, like the hash-then-fold order.
                        rep = None
                        self.stats["coalesce_fallbacks"] = \
                            self.stats.get("coalesce_fallbacks", 0) + 1
                        from parca_agent_tpu.utils.log import get_logger

                        get_logger("aggregator.dict").warn(
                            "feed coalesce failed; dispatching the "
                            "uncoalesced batch", error=repr(e)[:200])
                self.timings["feed_coalesce"] = sp.duration_s
            with trace.child("feed_hash") as sp:
                if rep is None:
                    h1, h2, h3 = self.hash_rows(snapshot)
                    h1c, h2c, h3c = h1[lo:hi], h2[lo:hi], h3[lo:hi]
                else:
                    h1c, h2c, h3c = row_hash_np(
                        np.ascontiguousarray(snapshot.stacks[rows_map]),
                        snapshot.pids[rows_map],
                        snapshot.user_len[rows_map],
                        snapshot.kernel_len[rows_map], n_hashes=3)
                    h2c = self._route_hashes(h1c, h2c, h3c,
                                             snapshot.pids[rows_map])
            self.timings["feed_hash"] = sp.duration_s
            if not fold_first and self._coalesce and n > 1:
                h1c, h2c, h3c, w64, rows_map = self._coalesce_triples(
                    h1c, h2c, h3c, w64, rows_map)
            keep = self._carry_match(h1c, h2c, h3c, w64)
            if keep is not None:
                h1c, h2c, h3c = h1c[keep], h2c[keep], h3c[keep]
                w64, rows_map = w64[keep], rows_map[keep]
        nd = len(h1c)
        trace.count(rows_fed=nd)
        self.stats["rows_fed"] = self.stats.get("rows_fed", 0) + nd
        if not nd:
            # The whole batch carried: nothing to dispatch — its mass
            # rides the carry cache to the close flush.
            return
        counts_c = w64.astype(np.uint32)
        with trace.child("feed_pack") as sp:
            counts_c, corrections = self._prefilter_unreachable(
                h1c, h2c, h3c, counts_c)
            # (corrections join _pending only after the device call succeeds,
            # mirroring the miss path: a failed feed must not leave partial
            # host-side mass that a recovery close would emit as a window.)
            n_pad = max(_FEED_PAD_MIN, 1 << (nd - 1).bit_length())
            # LRU (dict order = recency order via pop/re-insert): an
            # evict-smallest policy would pin stale large buffers after a
            # burst while current small sizes churn through one slot.
            packed = self._feed_bufs.pop(n_pad, None)
            if packed is None:
                if len(self._feed_bufs) >= 4:  # bounded cache
                    self._feed_bufs.pop(next(iter(self._feed_bufs)))
                packed = np.zeros((4, n_pad), np.uint32)
            else:
                packed[:, nd:] = 0  # stale tail from a previous, larger chunk
            self._feed_bufs[n_pad] = packed
            packed[0, :nd] = h1c
            packed[1, :nd] = h2c
            packed[2, :nd] = h3c
            packed[3, :nd] = counts_c
        self.timings["feed_pack"] = sp.duration_s

        self._ensure_device()
        if self._acc is None:
            self._acc = self._new_acc()
        if self._blk and self._touch is None:
            self._touch = self._new_touch()
        handle = self._feed_dispatch_async(packed, n_pad,
                                           1 if self._needs_reset else 0)
        if self._carry and not self._shapes_met:
            # With the carry cache every later feed is smaller: as the
            # cache fills, a run's drains fall through the powers of two
            # under this one, as far as its churn leaves them, and which
            # drain first brings which shape follows the draw. Meet them
            # all now, in the process's first feed (the one the feeder
            # gives its long budget), not one compile a drain under the
            # short one, in whichever window: an all-padding batch counts
            # nothing.
            self._shapes_met = True
            pad = n_pad >> 1
            while pad >= _FEED_PAD_MIN:
                self._feed_dispatch_async(
                    np.zeros((4, pad), np.uint32), pad, 0)
                pad >>= 1
        self._needs_reset = False
        self._pending.extend(corrections)
        # _fed_total means "mass in the DEVICE accumulator" (the close
        # gate and width prediction read it); host-settled corrections
        # and carried mass are not part of it.
        self._fed_total += int(w64.sum()) - sum(c for _, c in corrections)
        # Dispatch-only cost (timings["feed_dispatch"], set where the
        # program is called): the miss sync that used to ride here (and
        # block the capture thread for the kernel's full latency) is
        # deferred to the next feed / the close, where the kernel has
        # already completed and the sync is ~free — the feed's device
        # work OVERLAPS capture instead of stalling it.
        self._miss_inflight = (handle, packed, snapshot, rows_map, w64,
                               h1c, h2c, h3c)

    # palint: sync-ok — THE deferred sync boundary: by the next feed (or
    # the close) the kernel has completed, so this is a completion
    # check, not the kernel-latency stall the old inline sync paid.
    def _settle_misses(self, stages: dict = _SETTLE_STAGES) -> None:
        """Settle the deferred miss check of the last dispatched feed:
        sync the miss count, resolve any misses (insert new stacks,
        queue host-side count corrections), then admit the dispatched
        keys into the carry cache so later drains fold against them.
        Runs at the next feed and at close — always before the window's
        counts are read. ``stages`` names its spans (the close of a
        streamed window passes its own: _CLOSE_SETTLE_STAGES)."""
        inflight, self._miss_inflight = self._miss_inflight, None
        if inflight is None:
            return
        self._stages = stages
        handle, _packed, snapshot, rows_map, w64, h1d, h2d, h3d = inflight
        # The wait for the kernel.
        with trace.child(stages["feed_settle"]) as sp:
            miss_rel = self._settle_dispatch(handle)
        self.timings["feed_settle"] = sp.duration_s
        trace.count(misses=len(miss_rel), registered_pids=0,
                    registered_first_seen=0)  # _register_stacks_bulk adds
        if len(miss_rel):
            self.stats["misses"] = self.stats.get("misses", 0) \
                + len(miss_rel)
            # Miss indices address dispatch rows: rows_map translates
            # back to representative snapshot rows, and the dispatch-
            # row-aligned hash lanes and FOLDED weights (a
            # representative's own count would drop its duplicates'
            # mass) ride the inflight tuple with them.
            with trace.child(stages["feed_miss"]) as sp:
                self._pending.extend(self._resolve_misses(
                    snapshot, rows_map[miss_rel], h1d[miss_rel],
                    h2d[miss_rel], h3d[miss_rel], w64[miss_rel]))
            self.timings["feed_miss"] = sp.duration_s
        if self._carry and not self._carry_disabled:
            # A span of its own: feed_carry is the match of a feed's own
            # rows, this the admission of the feed before it.
            with trace.child(stages["carry_admit"]) as sp:
                self._carry_admit(h1d, h2d, h3d)
            self.timings["feed_carry"] = \
                self.timings.get("feed_carry", 0.0) + sp.duration_s

    # -- cross-drain carry cache (docs/perf.md "feed endgame") ---------------

    def _route_hashes(self, h1, h2, h3, pids):
        """Rewrite hook for identity triples computed OUTSIDE hash_rows
        (capture-carried hashes, post-fold representative hashing):
        subclasses that re-route identity lanes (the sharded
        aggregator's per-pid h2 shard residue) apply the same rewrite
        here so carried and self-hashed triples agree bit-for-bit.
        Returns the (possibly rewritten) h2 lane."""
        return h2

    def _coalesce_triples(self, h1c, h2c, h3c, w64, rows_map):
        """Coalesce dispatch rows to (stack, weight) pairs on the
        (h1, h2, h3) identity: dispatch rows track uniques, not samples
        (the accumulate kernel takes counts, so summed weights ride for
        free). Exact by the same 96-bit identity the whole aggregator
        keys on, and first-occurrence ordered so miss order — and
        therefore id assignment and pprof bytes — is bit-identical to
        the unfolded stream. A fold failure (chaos site feed.coalesce)
        is counted and degrades to the unfolded batch, never a lost
        feed."""

        n = len(h1c)
        with trace.child("feed_coalesce") as sp:
            try:
                faults.inject("feed.coalesce")
                folded = self._fold_triples(h1c, h2c, h3c, w64)
                if folded is not None:
                    rep, fw = folded
                    h1c, h2c, h3c = h1c[rep], h2c[rep], h3c[rep]
                    w64 = fw
                    rows_map = rows_map[rep]
                    trace.count(coalesce_folded=n - len(rep))
                self.stats["coalesce_rows_in"] = \
                    self.stats.get("coalesce_rows_in", 0) + n
                self.stats["coalesce_rows_out"] = \
                    self.stats.get("coalesce_rows_out", 0) + len(h1c)
            except Exception as e:  # noqa: BLE001 - counted fallback
                # Fail-open to the unfolded batch: the feed must never be
                # lost to the optimization riding it. Locals are only
                # rebound on success above, so the input rows are intact.
                self.stats["coalesce_fallbacks"] = \
                    self.stats.get("coalesce_fallbacks", 0) + 1
                from parca_agent_tpu.utils.log import get_logger

                get_logger("aggregator.dict").warn(
                    "feed coalesce failed; dispatching the uncoalesced "
                    "batch", error=repr(e)[:200])
        self.timings["feed_coalesce"] = sp.duration_s
        return h1c, h2c, h3c, w64, rows_map

    def _fold_triples(self, h1c, h2c, h3c, w64):
        """The triple fold on 64-bit integer keys. Returns None when no
        triple repeats, else ``(rep, weights)`` as fold_rows_first_seen
        gives them: the first row of each distinct triple in first-
        occurrence order, and the exact int64 sum of its rows' counts.

        If no two rows share (h1, h2), no two share (h1, h2, h3): one
        value sort of ``h1 << 32 | h2`` answers "does anything fold?"
        for the window whose rows are already distinct (the steady
        state) without a record sort, an index or an inverse. A batch
        with repeats pays a stable integer argsort; a run of equal
        (h1, h2) that holds more than one h3 (a 64-bit collision
        between different stacks) is not grouped by that argsort, so
        that batch goes whole to the record fold, which is exact."""
        k = (h1c.astype(np.uint64) << np.uint64(32)) | h2c
        s = np.sort(k)
        same_k = s[1:] == s[:-1]
        if not same_k.any():
            self.stats["coalesce_unique_batches"] = \
                self.stats.get("coalesce_unique_batches", 0) + 1
            trace.count(coalesce_unique=1)
            return None
        order = np.argsort(k, kind="stable")  # k[order] is s
        h3s = h3c[order]
        new_h3 = h3s[1:] != h3s[:-1]
        if (same_k & new_h3).any():
            self.stats["coalesce_wide_folds"] = \
                self.stats.get("coalesce_wide_folds", 0) + 1
            key = np.empty((len(k), 3), np.uint32)
            key[:, 0] = h1c
            key[:, 1] = h2c
            key[:, 2] = h3c
            folded = fold_rows_first_seen(
                key.view(np.dtype((np.void, 12))).ravel(), w64)
            if folded is None:  # (h1, h2) repeats, no triple does
                return None
            rep, _inv, fw = folded
            return rep, fw
        # Every run of equal k is one triple; the stable argsort put its
        # lowest original index first.
        start = np.flatnonzero(np.concatenate(([True], ~same_k)))
        first = order[start]
        fw = np.add.reduceat(np.asarray(w64, np.int64)[order], start)
        seen = np.argsort(first)  # groups back in first-occurrence order
        return first[seen], fw[seen]

    def _carry_match(self, h1c, h2c, h3c, w64):
        """Cross-drain fold: batch rows whose keys already sit in the
        carry cache accumulate their mass host-side instead of shipping
        dispatch rows — a stack pays ONE dispatch on first sight and
        rides the cache for every later drain (and, population
        stationary, every later window). Returns the keep mask (False =
        carried) or None when nothing matched. A match failure (chaos
        site feed.carry) is counted and disables matching until the
        window boundary: the batch dispatches whole and mass already
        accumulated still flushes at close, so counts stay exact."""
        if not self._carry or self._carry_disabled \
                or not len(self._carry_h1) or not len(h1c):
            return None
        with trace.child("feed_carry") as sp:
            keep = self._carry_match_rows(h1c, h2c, h3c, w64)
        self.timings["feed_carry"] = \
            self.timings.get("feed_carry", 0.0) + sp.duration_s
        return keep

    def _carry_match_rows(self, h1c, h2c, h3c, w64):
        """The match itself (_carry_match times it)."""
        try:
            faults.inject("feed.carry")
            # Bucket walk: each needle scans its prefix bucket (sorted,
            # h1-unique, load <= 0.5 so almost always one probe) with
            # the still-unresolved subset shrinking per pass.
            pref = (h1c >> self._carry_shift).astype(np.int64)
            cur = self._carry_starts[pref]
            end = self._carry_starts[pref + 1]
            pos = np.full(len(h1c), -1, np.int64)
            act = np.flatnonzero(cur < end)
            while len(act):
                c = cur[act]
                cand = self._carry_h1[c]
                eq = cand == h1c[act]
                pos[act[eq]] = c[eq]
                # Bucket entries are ascending: passing the needle's
                # value ends its scan (absent key).
                more = ~eq & (cand < h1c[act])
                act = act[more]
                cur[act] += 1
                act = act[cur[act] < end[act]]
            hit = pos >= 0
            if hit.all():
                # Steady-state fast path (every row a candidate): the
                # verify runs without sub-index gathers.
                hit = ((self._carry_h2[pos] == h2c)
                       & (self._carry_h3[pos] == h3c))
            elif hit.any():
                sub = np.flatnonzero(hit)
                e = pos[sub]
                ok = ((self._carry_h2[e] == h2c[sub])
                      & (self._carry_h3[e] == h3c[sub]))
                hit[sub[~ok]] = False  # h1 collision: not cached
            self.stats["carry_rows_in"] = \
                self.stats.get("carry_rows_in", 0) + len(h1c)
            n_hit = int(hit.sum())
            if not n_hit:
                return None
            if n_hit == len(hit):
                eidx, w = pos, w64
            else:
                eidx, w = pos[hit], w64[hit]
            # float64 bincount is exact below 2^53 total mass (same
            # guard as fold_rows_first_seen; window mass < 2^31).
            add = np.bincount(eidx, weights=w.astype(np.float64),
                              minlength=len(self._carry_w)).astype(
                                  np.int64)
            carried = int(w.sum())
            self.stats["carry_hits"] = \
                self.stats.get("carry_hits", 0) + n_hit
            self.stats["carry_mass"] = \
                self.stats.get("carry_mass", 0) + carried
            # Mutate LAST: an exception past this point could not be
            # failed open without double-counting the batch.
            self._carry_w += add
            self._carry_open_mass += carried
            trace.count(carry_matched_rows=n_hit)
            return ~hit
        except Exception as e:  # noqa: BLE001 - counted fallback
            self._carry_disabled = True
            self.stats["carry_fallbacks"] = \
                self.stats.get("carry_fallbacks", 0) + 1
            from parca_agent_tpu.utils.log import get_logger

            get_logger("aggregator.dict").warn(
                "feed carry match failed; dispatching per drain for "
                "the rest of the window", error=repr(e)[:200])
            return None

    def _carry_admit(self, h1d, h2d, h3d) -> None:
        """Admit a dispatch's keys into the carry cache. h1 stays
        UNIQUE in the cache (sorted membership tests stay one
        searchsorted; a same-h1 different-key collision simply keeps
        dispatching per drain — exact either way), and only keys with
        live ids in the host mirror are admitted: sketch-absorbed
        overflow keys must keep riding the sketch, never an exact
        host-side flush. Runs after miss resolution, so a drain's new
        inserts are admittable immediately."""
        if not len(h1d):
            return
        u, ui = np.unique(h1d, return_index=True)
        if len(self._carry_h1):
            pos = np.minimum(np.searchsorted(self._carry_h1, u),
                             len(self._carry_h1) - 1)
            fresh = self._carry_h1[pos] != u
            u, ui = u[fresh], ui[fresh]
        if not len(u):
            return
        h1n = np.ascontiguousarray(h1d[ui], np.uint32)
        h2n = np.ascontiguousarray(h2d[ui], np.uint32)
        h3n = np.ascontiguousarray(h3d[ui], np.uint32)
        ids, _stop, overrun = self._classify_keys_vec(h1n, h2n, h3n)
        if overrun:
            return  # wrapped probe chain: skip admission this drain
        ok = ids >= 0
        n_new = int(ok.sum())
        if not n_new:
            return
        nh1 = np.concatenate([self._carry_h1, h1n[ok]])
        order = np.argsort(nh1, kind="stable")
        self._carry_h1 = nh1[order]
        self._carry_h2 = np.concatenate([self._carry_h2, h2n[ok]])[order]
        self._carry_h3 = np.concatenate([self._carry_h3, h3n[ok]])[order]
        self._carry_sid = np.concatenate(
            [self._carry_sid, ids[ok]])[order]
        self._carry_w = np.concatenate(
            [self._carry_w, np.zeros(n_new, np.int64)])[order]
        self._carry_reindex()
        self.stats["carry_admitted"] = \
            self.stats.get("carry_admitted", 0) + n_new
        self.stats["carry_entries"] = len(self._carry_h1)

    def _carry_reindex(self) -> None:
        """Rebuild the prefix-bucket index (~2 buckets per entry,
        clamped to [2^12, 2^22])."""
        n = len(self._carry_h1)
        k = max(12, min(22, int(2 * n - 1).bit_length()))
        self._carry_shift = 32 - k
        counts = np.bincount(
            (self._carry_h1 >> self._carry_shift).astype(np.int64),
            minlength=1 << k)
        starts = np.zeros((1 << k) + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        self._carry_starts = starts

    def _carry_take(self):
        """Flush the open window's carried mass: (sids, counts) int64
        arrays, or (None, None) when nothing was carried. Zeroes the
        accumulated weights and re-arms matching — this is the window
        boundary, and carried corrections must never leak across it."""
        self._carry_disabled = False
        if not self._carry_open_mass:
            return None, None
        nz = np.flatnonzero(self._carry_w)
        sids = self._carry_sid[nz].copy()
        cnts = self._carry_w[nz].copy()
        self._carry_w[nz] = 0
        self._carry_open_mass = 0
        self.stats["carry_flushes"] = \
            self.stats.get("carry_flushes", 0) + 1
        return sids, cnts

    def _new_acc(self):
        """Fresh device accumulator (subclasses shard it)."""
        import jax.numpy as jnp

        return jnp.zeros(self._id_cap, jnp.int32)

    def _new_touch(self):
        """Fresh touched-block flag array (delta-fetch tracking)."""
        import jax.numpy as jnp

        return jnp.zeros(self._n_blocks, jnp.int32)

    def _feed_dispatch_async(self, packed: np.ndarray, n_pad: int,
                             reset: int):
        """Dispatch the feed program over the device state WITHOUT a host
        sync; returns an opaque handle for _settle_dispatch. The
        accumulator donation contract: self._acc/_touch are None while
        the dispatch is in flight (invalid if it throws)."""

        import jax.numpy as jnp

        prog = _feed_program(self._cap, self._id_cap, n_pad,
                             self._n_blocks, self._blk)
        # The feed program's jit cache key doubles as the telemetry
        # shape signature: a new key is the dispatch that pays compile.
        sig = (self._cap, self._id_cap, n_pad, self._n_blocks, self._blk)
        acc = self._acc
        touch = self._touch if self._blk else jnp.zeros(1, jnp.int32)
        self._acc = None    # donated: invalid if the call throws
        self._touch = None
        # One clock pair for the span, timings[...] and the telemetry.
        with trace.child("feed_dispatch") as sp:
            acc, touch, counts, miss_rows = prog(
                self._dev, acc, touch, jnp.asarray(packed),
                jnp.uint32(reset))
        self.timings["feed_dispatch"] = sp.duration_s
        dtel.record("feed_probe", sp.duration_s, shape=sig,
                    h2d_bytes=packed.nbytes)
        self._acc = acc
        self._touch = touch if self._blk else None
        return (counts, miss_rows)

    # palint: sync-ok — reached only through _settle_misses (same
    # boundary); the fetch of counts IS the documented sync point.
    def _settle_dispatch(self, handle) -> np.ndarray:
        """Sync one dispatched feed's miss outputs; returns chunk-relative
        miss row indices (empty in steady state). The same two-word
        fetch brings how many dictionary rows the probe gathered."""
        counts, miss_rows = handle
        # device sync point (kernel completion)
        nm, gathers = np.asarray(counts).tolist()
        trace.count(probe_gathers=gathers)
        self.stats["probe_gathers"] = \
            self.stats.get("probe_gathers", 0) + gathers
        if not nm:
            return np.empty(0, np.int64)
        return np.asarray(miss_rows)[:nm].astype(np.int64)

    def _close_pack_dispatch(self, acc, n_fetch: int, width: int,
                             n_over_buf: int):
        """Dispatch the full close pack program (no host sync)."""

        prog = _close_program(self._id_cap, n_fetch, width, n_over_buf)
        with trace.child("close_dispatch") as sp:
            out = prog(acc)
        self.timings["close_dispatch"] = sp.duration_s
        dtel.record("close_pack", sp.duration_s,
                    shape=(self._id_cap, n_fetch, width, n_over_buf))
        return out

    def _close_pack_collect(self, out_dev) -> np.ndarray:
        """Fetch a dispatched close pack's packed buffer."""

        with trace.child("close_fetch") as sp:  # the D2H wait
            host = np.asarray(out_dev)
        self.timings["close_fetch"] = sp.duration_s
        # Execute-only (shape=None): the fetch is a collect, not a
        # dispatch — its compile truth already lives in the pack/delta
        # signatures above, and latching the output shape here would
        # re-report every legitimate delta<->full geometry switch as a
        # recompile storm.
        dtel.record("close_fetch", sp.duration_s, d2h_bytes=host.nbytes)
        return host

    def _close_delta_dispatch(self, acc, touch, n_fetch: int, width: int,
                              n_over_buf: int, n_blk_buf: int):
        """Dispatch the delta close pack program (no host sync)."""

        prog = _close_program_delta(self._id_cap, n_fetch, width,
                                    n_over_buf, n_blk_buf, self._blk)
        with trace.child("close_dispatch") as sp:
            out = prog(acc, touch)
        self.timings["close_dispatch"] = sp.duration_s
        dtel.record("close_delta", sp.duration_s,
                    shape=(self._id_cap, n_fetch, width, n_over_buf,
                           n_blk_buf, self._blk))
        return out

    def _pick_close_width(self) -> int:
        """Packing width for this close: the narrowest that provably (from
        the fed total) or predictably (from the last window's stationary
        count distribution) keeps the overflow sideband within bounds. A
        misprediction is detected and retried wider — never lossy."""
        total = self._fed_total
        if total // 15 <= _CLOSE_OVERS[4] // 2:
            return 4
        if self._prev_counts is not None and total // 255 <= _CLOSE_OVERS[8]:
            if int((self._prev_counts > 14).sum()) <= _CLOSE_OVERS[4] // 2:
                return 4
        if total // 255 <= _CLOSE_OVERS[8]:
            return 8
        return 16

    def close_window(self, copy: bool = True,
                     streamed: bool = False) -> np.ndarray:
        """Finish the open window: fetch exact int64 counts indexed by
        stack id (length == number of stacks known after this window).
        ``streamed`` says the window was fed while it was open, by
        another caller than this close's (close_dispatch).

        Internally close_dispatch() + close_collect(): the accumulator
        flips at dispatch, so the pack/fetch (and any retry) runs against
        the closed buffer while the next window's feeds land in the
        other — callers that want the overlap explicitly use the split
        API; this convenience form collects immediately.

        Returns an owned copy by default. copy=False returns a view into a
        double-buffered reusable allocation — valid through the NEXT close,
        overwritten by the one after; only for callers that provably finish
        with it within their own window (the bench's measured close does;
        library consumers should take the default). A caller that must
        hold the view longer transfers ownership via pin_counts()."""
        return self.close_collect(self.close_dispatch(streamed), copy=copy)

    # palint: capture-path — dispatch half of the split close: pack
    # kernel launch + buffer flip only; the fetch belongs to
    # close_collect, off this path.
    def close_dispatch(self, streamed: bool = False) -> "_CloseHandle | None":
        """First half of the window close: settle deferred feed misses,
        dispatch the pack kernel against the open accumulator (no host
        sync), and FLIP the double buffers — from here on, feeds belong
        to the next window and land in the other accumulator while this
        window's pack/fetch proceeds. Returns None for an empty window
        (nothing fed, nothing pending) after counting it, matching the
        old close_window fast path. Closing a ``streamed`` window, the
        settle of its last feed records under the close's own stage
        names (_CLOSE_SETTLE_STAGES); one-shot, under the feed's."""

        if self._close_handle is not None:
            raise RuntimeError("previous close not collected")
        self._settle_misses(
            _CLOSE_SETTLE_STAGES if streamed else _SETTLE_STAGES)
        carry_sids, carry_cnts = self._carry_take()
        if self._fed_total == 0 and not self._pending \
                and carry_sids is None:
            self.stats["windows"] += 1
            # No flip, no fetch: drop the previous close's timings so a
            # trace-span reader can't attribute them to this window.
            self.timings.pop("buffer_flip", None)
            self.timings.pop("delta_fetch", None)
            return None
        h = _CloseHandle()
        h.pending, self._pending = self._pending, []
        if carry_sids is not None:
            h.pending_vec = (carry_sids, carry_cnts)
        h.fed_total = self._fed_total
        h.n_ids = self._next_id
        if self._acc is not None and self._fed_total:
            h.acc = self._acc
            h.touch = self._touch
            grain = 1 << 18
            h.n_fetch = min(self._id_cap,
                            max(grain, -(-h.n_ids // grain) * grain))
            h.width = self._pick_close_width()
            # Predictive sideband: cover 2x the previous window's overflow
            # population (stationary distributions keep it stable), floored
            # at _OVER_MIN; a misprediction is caught by the n_over counter
            # and retried larger — never lossy. A delta close shrinks the
            # floor 8x (and caps at the fetched row count): the sideband
            # would otherwise dominate the small delta buffer and erase
            # the byte win the delta exists for.
            h.delta_blks = self._delta_plan(h.n_fetch)
            predicted = max(_OVER_MIN, 2 * self._prev_n_over)
            if h.delta_blks:
                predicted = min(max(_OVER_MIN // 8, 2 * self._prev_n_over),
                                h.delta_blks * self._blk)
            h.n_over_buf = min(_CLOSE_OVERS[h.width],
                               1 << (predicted - 1).bit_length())
            if h.delta_blks:
                h.out_dev = self._close_delta_dispatch(
                    h.acc, h.touch, h.n_fetch, h.width, h.n_over_buf,
                    h.delta_blks)
            else:
                h.out_dev = self._close_pack_dispatch(
                    h.acc, h.n_fetch, h.width, h.n_over_buf)
        # The flip: the closed window's buffers stay intact inside the
        # handle (retries re-pack them); the next window's first feed
        # resets the flipped-in twin (stale by two windows) on device.
        with trace.child("buffer_flip", histogram=True) as sp:
            self._acc, self._acc_spare = self._acc_spare, self._acc
            self._touch, self._touch_spare = self._touch_spare, self._touch
            self._fed_total = 0
            self._needs_reset = True
            self.stats["buffer_flips"] = \
                self.stats.get("buffer_flips", 0) + 1
        self.timings["buffer_flip"] = sp.duration_s
        self._close_handle = h
        return h

    def _delta_plan(self, n_fetch: int) -> int:
        """Blocks to fetch for a delta close, or 0 for a full fetch.
        Sized predictively at 2x the previous window's touched-block
        population (floor 8 blocks = 1k rows, or the full fetch's blocks
        over _DELTA_MIN_DIV); delta engages only when that moves less
        than _DELTA_MAX_FRAC of the full fetch's rows."""
        if not self._blk or self._touch is None \
                or self._prev_touched is None:
            return 0
        nb_prefix = n_fetch // self._blk
        want = min(nb_prefix, max(8, nb_prefix // _DELTA_MIN_DIV,
                                  2 * self._prev_touched))
        n_blk_buf = 1 << max(0, (want - 1).bit_length())
        if n_blk_buf * self._blk > _DELTA_MAX_FRAC * n_fetch:
            return 0
        return n_blk_buf

    def close_collect(self, handle: "_CloseHandle | None",
                      copy: bool = True) -> np.ndarray:
        """Second half of the window close: fetch the packed buffer
        dispatched by close_dispatch, retrying against the handle's
        intact (pre-flip) accumulator on any misprediction — touched
        blocks grown first, then the full fetch as the exact fallback,
        then the sideband's grow-then-widen ladder, all lossless."""

        if handle is None:  # empty window (already counted)
            return np.zeros(self._next_id, np.int64)
        h = handle
        if h is self._close_handle:
            self._close_handle = None
        if h.acc is not None:
            n_fetch, width, n_over_buf = h.n_fetch, h.width, h.n_over_buf
            n_blk_buf = h.delta_blks
            out_dev = h.out_dev
            h.out_dev = None
            nb_prefix = n_fetch // self._blk if self._blk else 0
            fetch_s = 0.0  # the D2H waits of this close, retries and all
            while True:
                per32 = 32 // width
                if out_dev is None:  # a retry: re-pack the intact acc
                    if n_blk_buf:
                        out_dev = self._close_delta_dispatch(
                            h.acc, h.touch, n_fetch, width, n_over_buf,
                            n_blk_buf)
                    else:
                        out_dev = self._close_pack_dispatch(
                            h.acc, n_fetch, width, n_over_buf)
                host = self._close_pack_collect(out_dev)
                fetch_s += self.timings["close_fetch"]
                out_dev = None
                if int(host[-1]) != 0:
                    raise AssertionError("count mass beyond fetched prefix")
                if n_blk_buf:
                    n_touched = int(host[-4])
                    if int(host[-2]) != 0:
                        # Untouched-block mass: the touch tracking missed
                        # a write. Impossible by construction; degrade to
                        # the exact full fetch rather than trust it.
                        self.stats["delta_guard_trips"] = \
                            self.stats.get("delta_guard_trips", 0) + 1
                        n_blk_buf = 0
                        continue
                    if n_touched > n_blk_buf:
                        # More blocks touched than predicted: grow to the
                        # reported population, or fall back to the full
                        # fetch once delta stops being a win.
                        self.stats["delta_retries"] = \
                            self.stats.get("delta_retries", 0) + 1
                        need = 1 << max(0, (n_touched - 1).bit_length())
                        if need * self._blk > _DELTA_MAX_FRAC * n_fetch:
                            self.stats["delta_fallbacks"] = \
                                self.stats.get("delta_fallbacks", 0) + 1
                            n_blk_buf = 0
                        else:
                            n_blk_buf = need
                        continue
                n_over = int(host[-3] if n_blk_buf else host[-2])
                if n_over <= n_over_buf:
                    break
                # Sideband overran: acc is intact, retry. Grow the buffer
                # to cover the reported population first; only then go
                # wider (width 16 at the max cap cannot overrun for int32
                # totals).
                self.stats["close_retries"] = \
                    self.stats.get("close_retries", 0) + 1
                if n_over <= _CLOSE_OVERS[width]:
                    # The population fits this width: grow to cover it.
                    n_over_buf = 1 << (n_over - 1).bit_length()
                else:
                    # Even the max sideband can't hold it: widening is
                    # the only retry that can succeed — don't waste a
                    # doomed max-cap fetch first.
                    width = 8 if width == 4 else 16
                    n_over_buf = _CLOSE_OVERS[width]
            self._prev_n_over = n_over
            self.timings["close_fetch"] = fetch_s
            if n_blk_buf:
                self.timings["delta_fetch"] = fetch_s
                trace.note("delta_fetch", fetch_s, histogram=True)
            else:
                # A full close must not leave the previous DELTA close's
                # timing behind: the profiler records a delta_fetch trace
                # span only when the key is present for THIS window.
                self.timings.pop("delta_fetch", None)
            with trace.child("close_unpack") as sp:
                sentinel = (1 << width) - 1
                shifts = (np.arange(per32, dtype=np.uint32) * width)[None, :]
                if n_blk_buf:
                    lanes_n = n_blk_buf * self._blk // per32
                    wb_key = (1, n_blk_buf * self._blk, width)
                else:
                    lanes_n = n_fetch // per32
                    wb_key = (0, n_fetch, width)
                lanes = host[:lanes_n]
                wb = self._unpack_bufs.get(wb_key)
                if wb is None:
                    if len(self._unpack_bufs) >= 4:  # bounded: evict smallest
                        self._unpack_bufs.pop(
                            min(self._unpack_bufs,
                                key=lambda k: self._unpack_bufs[k].nbytes))
                    wb = self._unpack_bufs[wb_key] = np.empty(
                        (lanes_n, per32), np.uint32)
                np.right_shift(lanes[:, None], shifts, out=wb)
                np.bitwise_and(wb, np.uint32(sentinel), out=wb)
                self._counts_flip ^= 1
                counts = self._counts_bufs[self._counts_flip]
                if counts is None or len(counts) != n_fetch:
                    counts = np.empty(n_fetch, np.int64)
                    self._counts_bufs[self._counts_flip] = counts
                if n_blk_buf:
                    # Delta unpack: zero, then scatter the touched blocks
                    # back to their id ranges (block ids ride the buffer).
                    counts[:] = 0
                    n_t = n_touched
                    bids = host[lanes_n:lanes_n + n_blk_buf][:n_t].astype(
                        np.int64)
                    idx = (bids[:, None] * self._blk
                           + np.arange(self._blk, dtype=np.int64)).reshape(-1)
                    counts[idx] = wb.reshape(-1)[: n_t * self._blk]
                    over_off = lanes_n + n_blk_buf
                    self._prev_touched = n_t
                    self.stats["delta_closes"] = \
                        self.stats.get("delta_closes", 0) + 1
                    self.stats["fetch_rows_last"] = n_t * self._blk
                else:
                    counts[:] = wb.reshape(-1)
                    over_off = lanes_n
                    self.stats["full_closes"] = \
                        self.stats.get("full_closes", 0) + 1
                    self.stats["fetch_rows_last"] = n_fetch
                    if self._blk and h.touch is not None:
                        # Learn the touched population from the flags (one
                        # small fetch) so the NEXT close can go delta — full
                        # closes are the cold path, so the extra round trip
                        # amortizes away in steady state.
                        try:
                            self._prev_touched = int(
                                (np.asarray(h.touch)[:nb_prefix] > 0).sum())
                        except Exception:  # noqa: BLE001 - advisory only
                            self._prev_touched = None
                over_id = host[over_off:over_off + n_over]
                over_val = host[over_off + n_over_buf:
                                over_off + n_over_buf + n_over]
                counts[over_id] = over_val
                self.stats["fetch_bytes_last"] = int(host.nbytes)
                self.stats["fetch_bytes_total"] = \
                    self.stats.get("fetch_bytes_total", 0) + int(host.nbytes)
            self.timings["close_unpack"] = sp.duration_s
        else:
            # Pending-only close (nothing fed to the device): no fetch
            # ran, so the previous close's delta timing must not survive
            # into this window's trace spans.
            self.timings.pop("delta_fetch", None)
            counts = np.zeros(max(h.n_ids, 1), np.int64)

        if h.pending:
            sids = np.array([p[0] for p in h.pending], np.int64)
            cnts = np.array([p[1] for p in h.pending], np.int64)
            np.add.at(counts, sids, cnts)
            h.pending = []
        if h.pending_vec is not None:
            # The carry flush: vectorized (sid, count) corrections from
            # the cross-drain cache, applied exactly once per handle
            # (retries above re-pack the device buffers, never this).
            with trace.child("close_carry_flush"):
                sids, cnts = h.pending_vec
                np.add.at(counts, sids, cnts)
                h.pending_vec = None
                trace.count(carry_flush_rows=len(sids))
                self.stats["carry_flush_rows"] = \
                    self.stats.get("carry_flush_rows", 0) + len(sids)
        self.stats["windows"] += 1
        out = counts[: h.n_ids]
        self._last_seen[np.flatnonzero(out)] = self.stats["windows"]
        self._prev_counts = out
        return out.copy() if copy else out

    def pin_counts(self, counts: np.ndarray) -> None:
        """Copy-on-hand-off for the double-buffered close counts: a
        caller that must read a copy=False close result past its
        one-close validity window (the encode pipeline holding a window
        across a slow worker, tests) transfers ownership — the backing
        buffer leaves the reuse rotation, so the close after next
        allocates fresh instead of overwriting it. Zero-copy: ownership
        moves, bytes don't."""
        base = counts.base if counts.base is not None else counts
        for i, b in enumerate(self._counts_bufs):
            if b is base or b is counts:
                self._counts_bufs[i] = None

    # -- bounded-memory degradation ------------------------------------------

    def _sketch_add(self, hashes: np.ndarray, counts: np.ndarray) -> None:
        """Absorb overflow rows into the count-min table + HLL registers
        (bounded memory; overestimate-only error per CountMinSpec)."""
        from parca_agent_tpu.ops.sketch import cm_add, hll_build, hll_merge

        if self._cm is None:
            self._cm = np.zeros(
                (self._cm_spec.depth, self._cm_spec.width), np.int64)
            self._over_hll = np.zeros(self._hll_spec.m, np.int32)
        cm_add(self._cm, hashes, counts, self._cm_spec)
        self._over_hll = hll_merge(
            self._over_hll, hll_build(hashes, self._hll_spec))
        self.stats["sketch_rows"] = \
            self.stats.get("sketch_rows", 0) + len(hashes)
        self.stats["sketch_samples"] = \
            self.stats.get("sketch_samples", 0) + int(counts.sum())

    def sketch_estimate(self, h1_hashes) -> np.ndarray:
        """Point-query overflow-absorbed counts (CM overestimate bound);
        zeros when nothing has ever overflowed."""
        from parca_agent_tpu.ops.sketch import cm_query

        h1_hashes = np.asarray(h1_hashes, np.uint32)
        if self._cm is None:
            return np.zeros(len(h1_hashes), np.int64)
        return cm_query(self._cm, h1_hashes, self._cm_spec).astype(np.int64)

    def sketch_info(self) -> dict:
        """Observable degradation state (served by the agent's metrics)."""
        from parca_agent_tpu.ops.sketch import hll_estimate

        return {
            "sketch_rows": self.stats.get("sketch_rows", 0),
            "sketch_samples": self.stats.get("sketch_samples", 0),
            "sketch_distinct_est": (
                round(hll_estimate(self._over_hll, self._hll_spec))
                if self._over_hll is not None else 0),
            "rotations": self.stats.get("rotations", 0),
        }

    def _maybe_rotate(self) -> None:
        """Evict stack ids unseen for rotate_min_age windows and recycle
        their space (registry rotation). Runs only at a window boundary —
        BEFORE the new window touches the device — so no live accumulator,
        fetched counts buffer, or profile build is ever indexed by a stale
        id."""
        if not self._rotate_pending:
            return
        if self._close_handle is not None or self._miss_inflight is not None:
            # An uncollected close still references the pre-flip device
            # buffers (its fetched counts are indexed by the CURRENT id
            # space), and an unsettled feed may still insert: rotation
            # would remap ids under both. Defer to the next boundary.
            return
        self._rotate_pending = False
        w = self.stats["windows"]
        n = self._next_id
        keep = (w - self._last_seen[:n]) < self._rotate_min_age
        if int(keep.sum()) == n:
            return  # nothing cold yet; stay in sketch-degraded mode
        self._compact_ids(keep)
        self.stats["rotations"] = self.stats.get("rotations", 0) + 1

    def _maybe_reclaim(self) -> None:
        """The exact dictionary gives ids back (overflow="raise" only;
        the sketch mode rotates instead). At a window boundary, when a
        window with twice the churn of the one just closed would fail
        the room test _resolve_misses applies before it inserts, evict
        ids by last-seen age, oldest first and never one seen in the
        window just closed, until half the id space is free, and
        compact. Counts stay exact: an evicted stack that comes back
        misses and registers again, as a new stack does. Triggered by
        room and never by the clock: a compaction remaps every id, and
        every mirror of the id space (the window encoder's templates and
        statics among them) is laid out again after it. A live set that
        does not fit still raises where it is inserted."""
        if self._overflow != "raise":
            return
        if self._close_handle is not None or self._miss_inflight is not None:
            return  # as _maybe_rotate: ids are still referenced
        churn = self.stats["inserts"] - self._inserts_mark
        self._inserts_mark = self.stats["inserts"]
        n = self._next_id
        room = min(self._id_cap, self._cap // 2)
        if n + 2 * churn <= room:
            return
        last = self._last_seen[:n]
        cold = np.flatnonzero(last < self.stats["windows"])
        drop = min(len(cold), n - room // 2)
        if drop <= 0:
            return
        with trace.child("dict_reclaim"):
            with trace.child("reclaim_select"):
                keep = np.ones(n, bool)
                keep[cold[np.argsort(last[cold],
                                     kind="stable")[:drop]]] = False
            with trace.child("reclaim_compact"):
                self._compact_ids(keep)
            # The twin goes up here and not in the feed that follows, so
            # that the reclaim's span holds all that a reclaim costs.
            with trace.child("twin_upload"):
                self._ensure_device()
        self.stats["reclaims"] = self.stats.get("reclaims", 0) + 1
        self.stats["reclaimed_ids"] = \
            self.stats.get("reclaimed_ids", 0) + drop
        trace.count(reclaims=1, reclaimed_ids=drop)
        trace.annotate(ids_after=self._next_id)

    def invalidate_pid(self, pid: int) -> bool:
        """Generation-stamped identity invalidation (process/identity.py):
        the pid was RECYCLED, so every stack id and the location registry
        it owns describe a DEAD predecessor. Drop them so the new
        process's stacks re-register against its OWN mapping table
        instead of resolving through the old binary's registry (the
        cross-process attribution bug the workload zoo's pid-reuse
        scenario reproduces). Compaction is safe only at a window
        boundary — same contract as rotation — so while a close or a
        deferred miss check is in flight the pid queues and the drop
        lands at the next first-of-window reset, still before any of the
        new generation's samples resolve. Returns True when applied
        immediately, False when deferred."""
        pid = int(pid)
        if self._close_handle is not None or self._miss_inflight is not None:
            self._invalidate_pending.add(pid)
            return False
        self._invalidate_pending.discard(pid)
        self._drop_pids([pid])
        return True

    def _apply_pending_invalidations(self) -> None:
        """Deferred invalidate_pid drops, applied at the rotation
        boundary (first feed of a window: nothing live indexes stack
        ids). Sorted for a deterministic compaction order."""
        if not self._invalidate_pending:
            return
        if self._close_handle is not None or self._miss_inflight is not None:
            return
        pids = sorted(self._invalidate_pending)
        self._invalidate_pending.clear()
        self._drop_pids(pids)

    def _drop_pids(self, pids) -> None:
        n = self._next_id
        keep = ~np.isin(self._id_pid[:n],
                        np.asarray(sorted(pids), np.int64).astype(np.int32))
        for p in pids:
            self._pids.pop(int(p), None)
        # Registry content changed even when the pid owned no stack ids
        # yet (an adopted-but-never-fed registry still must not survive).
        self._reg_version += 1
        self._note_touched(int(p) for p in pids)
        self.stats["pid_invalidations"] = \
            self.stats.get("pid_invalidations", 0) + len(pids)
        if int(keep.sum()) != n:
            self._compact_ids(keep)
            # Bumps registry_epoch (mirrors key validity on it) — an id
            # remap without an epoch bump would let the window encoder
            # serve stale statics for the recycled pid.
            self.stats["invalidation_compactions"] = \
                self.stats.get("invalidation_compactions", 0) + 1

    def _compact_ids(self, keep: np.ndarray) -> None:
        """Remap the id space to the `keep` survivors and rebuild every
        structure keyed by stack id (shared by rotation and pid
        invalidation; callers bump their own epoch stat). Window-boundary
        only: no live accumulator, fetched counts buffer, or profile
        build may index ids across this call."""
        n = self._next_id
        kept = np.flatnonzero(keep)
        old_to_new = np.full(n, -1, np.int64)
        old_to_new[kept] = np.arange(len(kept))
        # Compact the ragged per-id metadata to the survivors.
        from parca_agent_tpu.pprof.vec import ragged_gather

        off = self._loc_off
        lens = off[kept + 1] - off[kept]
        new_flat, new_off = ragged_gather(self._loc_flat, off[kept], lens)
        self._id_pid = self._id_pid[:n][kept].copy()
        self._id_h1 = self._id_h1[:n][kept].copy()
        self._id_h2 = self._id_h2[:n][kept].copy()
        self._loc_flat = new_flat
        self._loc_off = new_off
        new_last = np.zeros(self._id_cap, np.int32)
        new_last[: len(kept)] = self._last_seen[kept]
        self._last_seen = new_last
        # The carry cache maps keys to the OLD id space: drop it
        # wholesale (live keys re-admit at their next dispatch; the
        # accumulated weights are zero at a boundary).
        self._carry_h1 = np.zeros(0, np.uint32)
        self._carry_h2 = np.zeros(0, np.uint32)
        self._carry_h3 = np.zeros(0, np.uint32)
        self._carry_sid = np.zeros(0, np.int64)
        self._carry_w = np.zeros(0, np.int64)
        self._carry_shift = 32
        self._carry_starts = np.zeros(2, np.int64)
        # Rebuild the key map and the host probe table for the survivors.
        remap = old_to_new.tolist()
        new_map = {key: nid for key, sid in self._key_to_id.items()
                   if (nid := remap[sid]) >= 0}
        if not self._rebuild_table_vec(kept):
            self._rebuild_table_scalar(new_map)
        self._key_to_id = new_map
        self._next_id = len(kept)
        self._published = self._next_id
        # Per-pid registries with no surviving stacks go too (memory bound).
        live_pids = set(self._id_pid[: self._next_id].tolist())
        self._pids = {p: r for p, r in self._pids.items() if p in live_pids}
        # Device twin is rebuilt lazily from the host mirror; the open
        # accumulator is empty at a boundary. BOTH double buffers go (the
        # spare indexes the old id space too), as do the touch flags and
        # the delta history. The width and sideband predictions follow
        # the survivors: they only predict (a close that they misjudge
        # is retried wider, never lossy), and without them the close
        # after a compaction would pack at the cold start's width, a
        # program that a process which has grown since never compiled.
        self._dev = None
        self._acc = None
        self._acc_spare = None
        self._touch = None
        self._touch_spare = None
        self._prev_touched = None
        prev = self._prev_counts
        self._prev_counts = prev[kept] \
            if prev is not None and len(prev) == n else None
        if self._prev_counts is None:
            self._prev_n_over = 0
        self._reg_version += 1
        # Every caller bumps its own epoch stat next, by one.
        old_to_new.flags.writeable = False
        self._remap = (self.registry_epoch + 1, old_to_new)

    def _rebuild_table_vec(self, kept: np.ndarray) -> bool:
        """The host probe table for the surviving ids, as array
        operations: the keys are read off the table as it stands (every
        id sits in exactly one occupied slot), the table is emptied, and
        the survivors are placed in new-id order by the miss plan's slot
        arbitration (_place_new_keys_vec), every key from its home slot.
        That is a valid linear-probe layout over the slots a one-by-one
        insert would fill, though not key for key the one-by-one
        layout (a contested slot goes to the lowest id among the keys
        that reached it in the same round); _unreachable is the keys 16 or
        more steps from home in THIS layout. False when the table does
        not hold one slot an id or the arbitration overran: the scalar
        rebuild, which reads the key map, then takes it."""
        n = self._next_id
        slots_old = np.flatnonzero(self._occ)
        sid_old = self._ids[slots_old].astype(np.int64)
        at = np.full(n, -1, np.int64)   # old id -> its slot
        if len(slots_old) == n and n and 0 <= int(sid_old.min()) \
                and int(sid_old.max()) < n:
            at[sid_old] = slots_old
        if n and int(at.min()) < 0:
            return False                # an id in no slot
        at = at[kept]
        h1k, h2k, h3k = self._h1[at], self._h2[at], self._h3[at]
        self._occ[:] = False
        base, start, _mask = self._probe_geometry_vec(h1k, h2k)
        slots = self._place_new_keys_vec(h1k, h2k, base + start)
        if slots is None:
            return False
        self._ids[:] = -1
        self._unreachable = {}  # chains change wholesale with the rebuild
        self._write_slots_vec(slots, h1k, h2k, h3k,
                              np.arange(len(kept), dtype=np.int64))
        return True

    def _write_slots_vec(self, slots, h1, h2, h3, sids) -> None:
        """A batch of placed keys into the host probe table, and into
        _unreachable those that sit beyond the device probe's reach."""
        self._occ[slots] = True
        self._h1[slots] = h1
        self._h2[slots] = h2
        self._h3[slots] = h3
        self._ids[slots] = sids
        base, start, mask = self._probe_geometry_vec(h1, h2)
        for j in np.flatnonzero(
                ((slots - base - start) & mask) >= _PROBES).tolist():
            self._unreachable[(int(h1[j]), int(h2[j]), int(h3[j]))] = \
                int(sids[j])
            self._unreach_h1 = None

    def _rebuild_table_scalar(self, new_map: dict) -> None:
        """The host probe table for `new_map`'s keys, inserted one by
        one in the map's order: the vectorised rebuild's fall-back and
        the tests' reference."""
        self._occ[:] = False
        self._ids[:] = -1
        self._unreachable = {}  # chains change wholesale with the rebuild
        self._unreach_h1 = None
        for key, nid in new_map.items():
            slot = self._host_insert_slot(key)
            self._occ[slot] = True
            self._h1[slot], self._h2[slot], self._h3[slot] = key
            self._ids[slot] = nid
            self._mark_if_unreachable(key, slot, nid)

    # -- internals ----------------------------------------------------------

    def _ensure_device(self) -> None:
        import jax.numpy as jnp

        if self._dev is None:
            table = np.zeros((self._cap, 4), np.uint32)
            table[:, 0] = self._h1
            table[:, 1] = self._h2
            table[:, 2] = self._h3
            table[:, 3] = np.where(self._occ, self._ids + 1, 0).astype(np.uint32)
            self._dev = jnp.asarray(table)

    def _resolve_misses(self, snapshot, rows, h1, h2, h3, weights=None
                        ) -> list[tuple[int, int]]:
        """Absorb device-miss rows: insert genuinely new stacks (host mirror
        + device table), and return (stack_id, count) corrections the caller
        must add to the window's counts. ``h1/h2/h3`` are MISS-ALIGNED
        lanes (one per ``rows`` entry — the feed keeps its dispatch-row
        hashes and passes the missed subset); ``weights`` overrides
        ``snapshot.counts[rows]`` (the coalesced feed's folded masses).
        Large clean batches take the vectorized plan-then-commit path,
        every degradation case falls back to this scalar loop."""
        rows = np.asarray(rows, np.int64)
        wts = (np.asarray(weights, np.int64) if weights is not None
               else snapshot.counts[rows].astype(np.int64))
        if len(rows) >= _VEC_MISS_MIN:
            import time as _time

            t0 = _time.perf_counter()
            out = self._resolve_misses_vec(snapshot, rows, h1, h2, h3, wts)
            if out is not None:
                # Shape class = the miss batch's pow2 envelope: the
                # commit's device scatter compiles per insert-count, so
                # the exact count would read every varied batch as a
                # recompile; the envelope keeps the latch meaningful.
                dtel.record("miss_settle", _time.perf_counter() - t0,
                            shape=(1 << max(0, (len(rows)
                                                - 1).bit_length()),))
                return out
            self.stats["miss_vec_fallbacks"] = \
                self.stats.get("miss_vec_fallbacks", 0) + 1
        return self._resolve_misses_scalar(snapshot, rows, h1, h2, h3, wts)

    def _resolve_misses_scalar(self, snapshot, rows, h1, h2, h3, wts
                               ) -> list[tuple[int, int]]:
        """The reference miss loop: handles every degradation case
        (sketch absorb, rotation request, per-key placement refusal)."""
        # Classify first, mutate second: capacity is validated against the
        # ACTUAL number of new keys before anything is inserted — raising
        # mid-loop would leave keys in _key_to_id without per-id metadata
        # or device-table entries, corrupting every later window. (Device
        # misses that are merely probe-bound overflows of known keys cost
        # nothing here.)
        with trace.child(self._stages["miss_plan"]):
            plan = self._plan_misses_scalar(rows, h1, h2, h3, wts)
        return self._commit_misses_scalar(snapshot, *plan)

    def _plan_misses_scalar(self, rows, h1, h2, h3, wts):
        """Classify the miss rows, validate room, then take slots and
        ids on the host mirror for the new keys (see the caller)."""
        classified: list[tuple[int, int, tuple, int | None]] = []
        n_new = 0
        seen_batch: set = set()
        for pos, r in enumerate(map(int, rows)):
            key = (int(h1[pos]), int(h2[pos]), int(h3[pos]))
            existing = self._key_to_id.get(key)
            if existing is None and key not in seen_batch:
                seen_batch.add(key)
                n_new += 1
            classified.append((pos, r, key, existing))
        worst = self._next_id + n_new
        budget = n_new
        if worst > self._id_cap or worst * 2 > self._cap:
            if self._overflow == "raise":
                raise RuntimeError(
                    f"stack dictionary capacity exhausted "
                    f"({self._next_id} ids + {n_new} new stacks vs "
                    f"id_cap {self._id_cap}, table {self._cap}); "
                    f"construct with a larger capacity"
                )
            # Degrade instead of dying: insert what fits, absorb the rest
            # into the count-min/HLL sideband, and ask for a cold-stack
            # rotation at the next window boundary.
            budget = max(0, min(self._id_cap, self._cap // 2) - self._next_id)
            self._rotate_pending = True
        # Subclass room validation (e.g. per-shard sub-table occupancy) —
        # must run BEFORE any mutation so a raise leaves state consistent.
        self._check_insert_room(classified, seen_batch)

        new_slots: list[int] = []
        new_rows: list[int] = []
        absorb_h: list[int] = []
        absorb_c: list[int] = []
        pending: list[tuple[int, int]] = []  # (sid, count) corrections
        for pos, r, key, existing in classified:
            w = int(wts[pos])
            if existing is None:
                existing = self._key_to_id.get(key)  # set earlier this loop?
            if existing is not None:
                # Probe-bound overflow on device; host resolves it.
                self.stats["overflow_misses"] += 1
                pending.append((existing, w))
                continue
            if budget <= 0:
                absorb_h.append(key[0])
                absorb_c.append(w)
                continue
            slot = self._try_insert_slot(key)
            if slot is None:
                # No placement room for this key (a subclass constraint,
                # e.g. its home sub-table is full) even though the global
                # budget allows it: degrade exactly like budget
                # exhaustion. raise-mode configurations never reach here —
                # _check_insert_room validated pre-mutation.
                self._rotate_pending = True
                absorb_h.append(key[0])
                absorb_c.append(w)
                continue
            budget -= 1
            sid = self._next_id
            self._next_id += 1
            self._key_to_id[key] = sid
            self._occ[slot] = True
            self._h1[slot], self._h2[slot], self._h3[slot] = key
            self._ids[slot] = sid
            self._mark_if_unreachable(key, slot, sid)
            self._last_seen[sid] = self.stats["windows"] + 1
            new_slots.append(slot)
            new_rows.append(r)
            pending.append((sid, w))
            self.stats["inserts"] += 1
        return new_slots, new_rows, absorb_h, absorb_c, pending

    def _commit_misses_scalar(self, snapshot, new_slots, new_rows,
                              absorb_h, absorb_c, pending):
        if absorb_h:
            self._sketch_add(np.array(absorb_h, np.uint32),
                             np.array(absorb_c, np.int64))

        if new_slots:
            # Per-id hash lanes land BEFORE _register_stacks_bulk
            # publishes the batch (_append_id_meta advances _published),
            # so concurrent readers pacing by the watermark never see an
            # id without its hashes.
            base = self._next_id - len(new_slots)
            self._grow_id_hashes(base)
            self._id_h1[base:self._next_id] = self._h1[new_slots]
            self._id_h2[base:self._next_id] = self._h2[new_slots]
            with trace.child(self._stages["miss_register"]):
                self._register_stacks_bulk(snapshot,
                                           np.array(new_rows, np.int64))
            slots = np.array(new_slots, np.int64)
            vals = np.zeros((len(new_slots), 4), np.uint32)
            vals[:, 0] = self._h1[new_slots]
            vals[:, 1] = self._h2[new_slots]
            vals[:, 2] = self._h3[new_slots]
            vals[:, 3] = (self._ids[new_slots] + 1).astype(np.uint32)
            self._dev_scatter(slots, vals)
        return pending

    def _grow_id_hashes(self, keep: int) -> None:
        """Grow the per-id hash mirrors to hold [0, _next_id), copying
        the first `keep` published lanes (both settle paths' commit
        tails share this so the growth policy cannot drift)."""
        if self._next_id <= len(self._id_h1):
            return
        for name in ("_id_h1", "_id_h2"):
            old = getattr(self, name)
            grown = np.empty(max(self._next_id, 2 * len(old)), np.uint32)
            grown[:keep] = old[:keep]
            setattr(self, name, grown)

    # -- vectorized miss settle (docs/perf.md "ingest wall") ------------------
    #
    # The first window of a cold tier (and every churn burst) resolves
    # 100k+ misses; the scalar loop above pays per-row Python — tuple
    # construction, dict probes, per-element numpy reads — which dwarfs
    # the device work it follows. The vectorized twin PLANS with pure
    # array reads (classification probe + first-empty-slot arbitration
    # over the host mirror), then COMMITS the whole batch as one
    # vectorized registry append. Any degradation case (capacity
    # shortfall, unplaceable keys, arbitration overrun) falls back to
    # the scalar loop BEFORE any mutation, so the degrade ladder stays
    # single-sourced.

    def _probe_geometry_vec(self, h1u, h2u):
        """(base, start, mask) per key for the vectorized host-mirror
        probe: slot(k) = base + ((start + k) & mask). The base table
        probes the whole table from h1 & mask."""
        mask = self._cap - 1
        return (np.zeros(len(h1u), np.int64),
                h1u.astype(np.int64) & mask, mask)

    def _check_insert_room_vec(self, h1n, h2n, h3n) -> None:
        """Vectorized twin of _check_insert_room (pre-mutation, may
        raise). No-op here: the global capacity gate already ran."""

    def _classify_keys_vec(self, h1u, h2u, h3u):
        """Probe every unique key against the host mirror in lockstep:
        returns (ids, stop, overrun) — ids[j] >= 0 for a known key,
        stop[j] = first empty slot on a new key's chain, overrun True
        when any chain wrapped a full (sub-)table (caller falls back)."""
        base, start, mask = self._probe_geometry_vec(h1u, h2u)
        m = len(h1u)
        ids = np.full(m, -1, np.int64)
        stop = np.full(m, -1, np.int64)
        alive = np.arange(m, dtype=np.int64)
        k = 0
        while len(alive):
            if k > mask:
                return ids, stop, True
            idx = base[alive] + ((start[alive] + k) & mask)
            occ = self._occ[idx]
            empty = np.flatnonzero(~occ)
            stop[alive[empty]] = idx[empty]
            hit = occ & (self._h1[idx] == h1u[alive]) \
                & (self._h2[idx] == h2u[alive]) \
                & (self._h3[idx] == h3u[alive])
            hsel = np.flatnonzero(hit)
            ids[alive[hsel]] = self._ids[idx[hsel]]
            alive = alive[occ & ~hit]
            k += 1
        return ids, stop, False

    def _place_new_keys_vec(self, h1n, h2n, stop):
        """First-empty-slot arbitration for a batch of new keys: every
        key starts at its chain's first pre-batch empty slot; contested
        slots go to the lowest batch rank (deterministic) and
        losers walk forward past slots occupied pre-batch or claimed
        this batch. The result is a valid linear-probe layout (a key
        only ever stops where its whole chain prefix is occupied), so
        lookups — device and host — find every key or report it
        unreachable exactly as a sequential insert order would. Returns
        slots, or None on overrun (caller falls back to scalar)."""
        base, start, mask = self._probe_geometry_vec(h1n, h2n)
        n = len(h1n)
        slots = stop.copy()
        off = (slots - base - start) & mask
        overlay = np.zeros(self._cap, bool)  # slots claimed this batch
        unplaced = np.arange(n, dtype=np.int64)
        rounds = 0
        while len(unplaced):
            rounds += 1
            if rounds > 64 + 4 * _PROBES:
                return None
            s = slots[unplaced]
            order = np.lexsort((unplaced, s))
            ss = s[order]
            firsts = np.ones(len(order), bool)
            firsts[1:] = ss[1:] != ss[:-1]
            win = unplaced[order[firsts]]
            overlay[slots[win]] = True
            unplaced = unplaced[order[~firsts]]
            active = unplaced
            while len(active):
                off[active] += 1
                if int(off[active].max(initial=0)) > mask:
                    return None
                nxt = base[active] + ((start[active] + off[active]) & mask)
                slots[active] = nxt
                blocked = self._occ[nxt] | overlay[nxt]
                active = active[blocked]
        return slots

    def _plan_misses_vec(self, rows, h1, h2, h3, wts):
        """The plan half: fold the miss rows to unique keys, classify
        them against the host mirror and arbitrate slots for the new
        ones. Pure reads; None when the scalar path has to take the
        batch (an overrun, a capacity shortfall)."""
        h1m = np.ascontiguousarray(h1, np.uint32)
        h2m = np.ascontiguousarray(h2, np.uint32)
        h3m = np.ascontiguousarray(h3, np.uint32)
        key = np.empty((len(rows), 3), np.uint32)
        key[:, 0] = h1m
        key[:, 1] = h2m
        key[:, 2] = h3m
        folded = fold_rows_first_seen(
            key.view(np.dtype((np.void, 12))).ravel(), wts)
        if folded is None:
            urep = np.arange(len(rows), dtype=np.int64)
            uw = wts
            row_mult = None  # every unique key came from exactly one row
        else:
            urep, inv, uw = folded
            row_mult = np.bincount(inv, minlength=len(urep))
        h1u, h2u, h3u = h1m[urep], h2m[urep], h3m[urep]
        ids, stop, overrun = self._classify_keys_vec(h1u, h2u, h3u)
        if overrun:
            return None
        new = np.flatnonzero(ids < 0)
        slots = None
        if len(new):
            worst = self._next_id + len(new)
            if worst > self._id_cap or worst * 2 > self._cap:
                return None  # degradation: the scalar path owns it
            # Subclass pre-mutation room validation (raise-mode sharded).
            self._check_insert_room_vec(h1u[new], h2u[new], h3u[new])
            slots = self._place_new_keys_vec(h1u[new], h2u[new], stop[new])
            if slots is None:
                return None
        return urep, uw, row_mult, ids, new, (h1u, h2u, h3u), slots

    def _resolve_misses_vec(self, snapshot, rows, h1, h2, h3, wts):
        """Plan-then-commit vectorized twin of the scalar miss loop.
        Returns the pending corrections, or None to fall back (nothing
        mutated). Id assignment stays in first-occurrence row order, so
        output bytes are identical to the scalar path's."""
        with trace.child(self._stages["miss_plan"]):
            plan = self._plan_misses_vec(rows, h1, h2, h3, wts)
        if plan is None:
            return None
        urep, uw, row_mult, ids, new, (h1u, h2u, h3u), slots = plan
        n_new = len(new)
        pending: list[tuple[int, int]] = []
        if n_new:
            h1n, h2n, h3n = h1u[new], h2u[new], h3u[new]
            # -- commit (mirrors the scalar tail, batch-at-once) --------
            base_sid = self._next_id
            sids = np.arange(base_sid, base_sid + n_new, dtype=np.int64)
            self._next_id = base_sid + n_new
            self._key_to_id.update(zip(
                zip(h1n.tolist(), h2n.tolist(), h3n.tolist()),
                sids.tolist()))
            self._write_slots_vec(slots, h1n, h2n, h3n, sids)
            self._last_seen[sids] = self.stats["windows"] + 1
            self.stats["inserts"] += n_new
            self.stats["miss_vec_inserts"] = \
                self.stats.get("miss_vec_inserts", 0) + n_new
            # Per-id hash lanes land BEFORE _register_stacks_bulk
            # publishes the batch (same ordering contract as the scalar
            # path: readers pacing by _published never see an id
            # without its hashes).
            self._grow_id_hashes(base_sid)
            self._id_h1[base_sid:self._next_id] = h1n
            self._id_h2[base_sid:self._next_id] = h2n
            with trace.child(self._stages["miss_register"]):
                self._register_stacks_bulk(snapshot, rows[urep[new]])
            vals = np.zeros((n_new, 4), np.uint32)
            vals[:, 0] = h1n
            vals[:, 1] = h2n
            vals[:, 2] = h3n
            vals[:, 3] = (sids + 1).astype(np.uint32)
            self._dev_scatter(slots, vals)
            pending.extend(zip(sids.tolist(), uw[new].tolist()))
            if row_mult is not None:
                # The scalar loop counts every duplicate row of a key
                # inserted earlier in the same batch as an overflow
                # miss (it resolves via the just-updated _key_to_id);
                # the fold collapsed those rows — count them back so
                # the stat keeps one unit across both paths.
                self.stats["overflow_misses"] += \
                    int((row_mult[new] - 1).sum())
        exist = np.flatnonzero(ids >= 0)
        if len(exist):
            # Counted per MISS ROW (folded multiplicity), matching the
            # scalar loop's meaning exactly — the stat must not change
            # units with the batch size that picked the path.
            self.stats["overflow_misses"] += (
                int(row_mult[exist].sum()) if row_mult is not None
                else len(exist))
            pending.extend(zip(ids[exist].tolist(),
                               uw[exist].astype(np.int64).tolist()))
        return pending

    def _dev_scatter(self, slots: np.ndarray, vals: np.ndarray) -> None:
        """Write newly inserted rows into the device table twin, in
        fixed-size chunks (_scatter_program: no compile per count). The
        cold insert of a population into an empty dictionary goes as one
        scatter at its own count (_scatter_cold)."""
        if self._dev is None:
            return  # no twin to patch: the next feed builds it whole
        with trace.child(self._stages["miss_scatter"]):
            if self._next_id == len(slots):
                self._scatter_cold(slots, vals)
                shipped = 4 * len(slots) + vals.nbytes
            else:
                shipped = 0
                for slots_c, vals_c in _scatter_chunks(slots, vals,
                                                       self._cap):
                    self._scatter_chunk(slots_c, vals_c)
                    shipped += slots_c.nbytes + vals_c.nbytes  # padding too
        dtel.transfer("miss_settle", "h2d", shipped)

    def _scatter_cold(self, slots: np.ndarray, vals: np.ndarray) -> None:
        """The whole batch in one eager scatter (subclasses shard it):
        a program per count, which a process pays once, at set-up, and
        whose result is a buffer of its own. On the table that chunks
        written in place leave where the first transfer put it, node's
        probe loop read 5.8 ms a window against 4.8 on this one
        (PERF.md section 6, PR 32)."""
        import jax.numpy as jnp

        self._dev = self._dev.at[jnp.asarray(slots.astype(np.int32))].set(
            jnp.asarray(vals))

    def _scatter_chunk(self, slots_c: np.ndarray, vals_c: np.ndarray) -> None:
        """One chunk into the device table (subclasses shard it). The
        table is donated to the call, so the twin is None while it is in
        flight: a call that throws leaves it to be rebuilt from the host
        mirror (_ensure_device), which already holds the batch."""
        dev, self._dev = self._dev, None
        self._dev = _scatter_program(self._cap)(dev, slots_c, vals_c)

    def _check_insert_room(self, classified, seen_batch) -> None:
        """Pre-mutation room validation hook for subclasses with placement
        constraints beyond the global capacity check (no-op here)."""

    def _try_insert_slot(self, key: tuple) -> int | None:
        """Slot for a new key, or None when the key cannot be placed
        (subclass placement constraints). The base table has no such
        constraint: the global capacity check guarantees a free slot."""
        return self._host_insert_slot(key)

    def _host_insert_slot(self, key: tuple) -> int:
        # Capacity was validated batch-wide by _resolve_misses.
        mask = self._cap - 1
        idx = key[0] & mask
        # Unbounded on host (correctness); a key landing beyond the device
        # probe bound is recorded by the CALLER in _unreachable so later
        # windows short-circuit it host-side instead of paying a
        # device-miss fetch every feed.
        while self._occ[idx]:
            idx = (idx + 1) & mask
        return idx

    def _chain_dist(self, key: tuple, slot: int) -> int:
        mask = self._cap - 1
        return (slot - (key[0] & mask)) & mask

    def _mark_if_unreachable(self, key: tuple, slot: int, sid: int) -> None:
        """Keys at probe-chain positions the device lookup cannot reach
        (>= _PROBES) would miss on EVERY window — a fixed extra D2H fetch
        plus host resolution per feed, forever. Register them so the feed
        path settles them host-side before shipping."""
        if self._chain_dist(key, slot) >= _PROBES:
            self._unreachable[key] = sid
            self._unreach_h1 = None  # sorted-cache invalidated

    def _prefilter_unreachable(self, h1c, h2c, h3c, counts_c):
        """Zero out rows whose keys the device probe bound cannot reach,
        returning (filtered_counts, [(sid, count) corrections]). The
        candidate scan is a sorted-array membership test on h1 (a few
        dozen unreachable keys vs 100k+ rows), then exact-key
        confirmation on the handful of candidates."""
        if not self._unreachable:
            return counts_c, []
        if self._unreach_h1 is None:
            self._unreach_h1 = np.sort(np.fromiter(
                (k[0] for k in self._unreachable), np.uint32,
                len(self._unreachable)))
        pos = np.searchsorted(self._unreach_h1, h1c)
        pos = np.minimum(pos, len(self._unreach_h1) - 1)
        cand = np.flatnonzero((self._unreach_h1[pos] == h1c)
                              & (counts_c > 0))
        if not len(cand):
            return counts_c, []
        corrections = []
        counts_c = counts_c.copy()
        for r in map(int, cand):
            sid = self._unreachable.get(
                (int(h1c[r]), int(h2c[r]), int(h3c[r])))
            if sid is not None:
                corrections.append((sid, int(counts_c[r])))
                counts_c[r] = 0
        if corrections:
            self.stats["unreachable_rows"] = \
                self.stats.get("unreachable_rows", 0) + len(corrections)
        return counts_c, corrections

    def _append_id_meta(self, pids: np.ndarray, depths: np.ndarray,
                        flat_vals: np.ndarray) -> None:
        """Append a batch of per-id metadata (pid, ragged loc-id runs whose
        lengths are `depths`, concatenated in id order in `flat_vals`)."""
        n = self._next_id - len(pids)  # ids were assigned before this call
        need_ids = n + len(pids)
        if need_ids > len(self._id_pid):
            grown = np.empty(max(need_ids, 2 * len(self._id_pid)), np.int32)
            grown[:n] = self._id_pid[:n]
            self._id_pid = grown
            goff = np.zeros(len(grown) + 1, np.int64)
            goff[: n + 1] = self._loc_off[: n + 1]
            self._loc_off = goff
        self._id_pid[n:need_ids] = pids
        base = int(self._loc_off[n])
        np.cumsum(depths, out=self._loc_off[n + 1: need_ids + 1])
        self._loc_off[n + 1: need_ids + 1] += base
        need_flat = base + len(flat_vals)
        if need_flat > len(self._loc_flat):
            grown = np.empty(max(need_flat, 2 * len(self._loc_flat)),
                             np.int32)
            grown[:base] = self._loc_flat[:base]
            self._loc_flat = grown
        self._loc_flat[base:need_flat] = flat_vals
        # Metadata (and the per-pid registries, written by the caller
        # before this) is complete for every id below need_ids: publish.
        self._published = need_ids

    def _register_stacks_bulk(self, snapshot, rows: np.ndarray) -> None:
        """Per-pid location registration for a batch of newly inserted
        stacks, in one pass over all of the batch's pids (the first
        window inserts everything, and a window of short-lived processes
        brings thousands of pids of a few stacks each: numpy work per
        pid, or Python work per frame, would dwarf the device work it
        feeds).

        The bytes are what a pass per pid in ascending pid order would
        leave: a pid's fresh addresses take location ids in ascending
        address order after its existing ones, new mapping ranges are
        appended in ascending table-row order, registries enter
        ``_pids`` in ascending pid order, and all of them are complete
        before ``_append_id_meta`` publishes the batch."""
        from parca_agent_tpu.pprof.vec import ragged_gather

        pids = snapshot.pids[rows]
        depths = (snapshot.user_len + snapshot.kernel_len)[rows].astype(
            np.int64)
        nb = len(rows)
        # Batch outputs indexed by position in `rows` — positions correspond
        # 1:1 to the contiguous sids the caller just assigned, so the global
        # per-id arrays stay aligned with stack ids.
        boff = np.zeros(nb + 1, np.int64)
        np.cumsum(depths, out=boff[1:])
        flat_vals = np.empty(int(boff[-1]), np.int32)

        # The batch's rows by ascending pid, in batch order within a pid.
        order = np.argsort(pids, kind="stable")
        spids = pids[order]
        head = np.ones(nb, bool)
        head[1:] = spids[1:] != spids[:-1]
        first = np.flatnonzero(head)
        row_off = np.append(first, nb)        # rows of pid k in `order`
        upids = spids[first]
        frames = np.add.reduceat(depths[order], first)  # live, by pid
        table = snapshot.mappings
        tlo = np.searchsorted(table.pids, upids, "left")
        thi = np.searchsorted(table.pids, upids, "right")
        stacks_flat = snapshot.stacks.reshape(-1)

        # Whole pids, taken in groups of about _REGISTER_FRAME_BUDGET
        # live frames: a pid goes with the group its first frame falls
        # in and is never split, so the grouping changes no result.
        frames_before = np.cumsum(frames) - frames
        gid = frames_before // _REGISTER_FRAME_BUDGET
        cuts = np.flatnonzero(gid[1:] != gid[:-1]) + 1
        n_first_seen = 0
        for p0, p1 in zip(np.concatenate(([0], cuts)).tolist(),
                          np.append(cuts, len(upids)).tolist()):
            sel = order[row_off[p0]:row_off[p1]]
            d = depths[sel]
            frame_ids, n_new = self._register_pid_group(
                table, upids[p0:p1], frames[p0:p1], tlo[p0:p1], thi[p0:p1],
                ragged_gather(stacks_flat, rows[sel] * STACK_SLOTS, d)[0])
            n_first_seen += n_new
            ragged_gather(frame_ids, np.cumsum(d) - d, d,
                          out=flat_vals, out_starts=boff[sel])

        self._append_id_meta(pids.astype(np.int32), depths, flat_vals)
        self._reg_version += 1
        self._note_touched(upids.tolist())
        trace.count(registered_pids=len(upids),
                    registered_first_seen=n_first_seen)

    def _register_pid_group(self, table, gpids, frames, tlo, thi, addrs):
        """Register one group of whole pids (ascending ``gpids``):
        ``addrs`` are their live frames, pid-major, ``frames[k]`` of them
        pid k's; ``[tlo[k], thi[k])`` its rows of the window's mapping
        table. Returns every frame's 1-based location id (int32, in
        ``addrs``' order) and how many of the pids were first seen."""
        n_pids = len(gpids)
        pid_list = gpids.tolist()
        regs = [self._pids.get(p) for p in pid_list]
        known = np.array([r is not None for r in regs], bool)

        # Distinct (pid, address) pairs, by pid then address: one sort by
        # address (equal pairs are one pair, so it need not be stable),
        # then a stable one by the pid's index in the group, whose dtype
        # is as narrow as the group allows (16 bits take a radix sort).
        # The frames arrive pid-major, so that index, sorted, is itself.
        pidx = np.repeat(np.arange(n_pids, dtype=np.uint16
                                   if n_pids <= 1 << 16 else np.uint32),
                         frames)
        by = np.argsort(addrs)
        by = by[np.argsort(pidx[by], kind="stable")]
        sa = addrs[by]
        head = np.ones(len(sa), bool)
        head[1:] = (sa[1:] != sa[:-1]) | (pidx[1:] != pidx[:-1])
        pair_of_frame = np.empty(len(sa), np.int64)
        pair_of_frame[by] = np.cumsum(head) - 1
        ua = sa[head]
        upi = pidx[head].astype(np.int64)

        # A first-seen pid's addresses are all fresh; a known pid's are
        # tested against its registry, which builds its look-up the
        # first time it is asked.
        loc = np.zeros(len(ua), np.int64)
        if known.any():
            index, built = zip(*(r.index() if r is not None
                                 else (None, False) for r in regs))
            kp = np.flatnonzero(known[upi])
            loc[kp] = [index[k].get(a, 0)
                       for k, a in zip(upi[kp].tolist(), ua[kp].tolist())]
            self.stats["registry_index_builds"] = \
                self.stats.get("registry_index_builds", 0) + sum(built)
        fresh = loc == 0
        fa = ua[fresh]
        fpi = upi[fresh]

        n_fresh = np.bincount(fpi, minlength=n_pids)
        fresh_off = np.zeros(n_pids + 1, np.int64)
        np.cumsum(n_fresh, out=fresh_off[1:])

        # Every fresh address against its pid's rows of the mapping
        # table: a merge of the two, both sorted by (pid, value), taken
        # from the side of the rows, which are the few. Each row finds
        # where its start falls among its own pid's fresh addresses (a
        # bisection of all the rows at once, each inside its pid's run),
        # and the running count of those positions is, at every address,
        # the rows that start at or below it: its own pid's, after all
        # the rows of the pids before. Addresses use all 64 bits, so no
        # key of (pid, address) fits one integer; this compares them as
        # they are.
        n_rows = thi - tlo
        row_off = np.zeros(n_pids + 1, np.int64)
        np.cumsum(n_rows, out=row_off[1:])
        rpi = np.repeat(np.arange(n_pids), n_rows)
        within = np.arange(len(rpi), dtype=np.int64) - row_off[rpi]
        trows = tlo[rpi] + within        # the group's rows of the table
        starts = table.starts[trows]
        lo, hi = fresh_off[rpi], fresh_off[rpi + 1]
        for _ in range(int(n_fresh.max(initial=0)).bit_length()):
            mid = (lo + hi) >> 1
            open_ = lo < hi
            right = open_ & (fa[np.minimum(mid, len(fa) - 1)] < starts)
            hi = np.where(open_ & ~right, mid, hi)
            lo = np.where(right, mid + 1, lo)
        below = np.cumsum(np.bincount(lo, minlength=len(fa) + 1))[:len(fa)]
        is_kernel = fa >= np.uint64(KERNEL_ADDR_START)
        hit = (below > row_off[fpi]) & ~is_kernel
        if len(trows):
            trow = trows[np.maximum(below - 1, 0)]
            hit &= fa < table.ends[trow]
            norm = np.where(hit, fa - table.bases[trow], fa)
        else:
            norm = fa
        # Table rows -> registry-stable mapping ids. A first-seen pid's
        # registry starts as the window's table, so its ids are the rows'
        # positions there; a known pid's go through its mapping_index.
        map_id = np.where(hit, below - row_off[fpi], 0).astype(np.int32)
        kh = np.flatnonzero(hit & known[fpi])
        if len(kh):
            krows = np.unique(trow[kh])
            map_id[kh] = np.array(
                self._known_mapping_ids(table, krows), np.int32)[
                    np.searchsorted(krows, trow[kh])]

        # Location ids: a pid's fresh addresses, ascending, after the
        # locations it has.
        base = np.array([r.n_locs if r is not None else 0
                         for r in regs], np.int64)
        loc[fresh] = (base - fresh_off[:-1])[fpi] \
            + np.arange(1, len(fa) + 1, dtype=np.int64)

        # What is left per pid: a first-seen pid gets a registry that
        # owns a copy of its run of the four columns (and its mapping
        # objects); a known pid's run is appended to its columns.
        first_seen = np.flatnonzero(~known[rpi])
        new_maps, new_keys = _table_mappings(
            table, trows[first_seen], within[first_seen] + 1)
        # (A first-seen pid's rows in new_maps: the rows before it less
        # those of known pids; a known pid's entry is not read.)
        map_off = (row_off[:-1] - np.cumsum(n_rows * known)).tolist()
        map_len = n_rows.tolist()
        offs = fresh_off.tolist()
        for k, (pid, reg) in enumerate(zip(pid_list, regs)):
            f0, f1 = offs[k], offs[k + 1]
            if reg is None:
                m0, m1 = map_off[k], map_off[k] + map_len[k]
                self._pids[pid] = _PidRegistry(
                    fa[f0:f1].copy(), norm[f0:f1].copy(),
                    map_id[f0:f1].copy(), is_kernel[f0:f1].copy(),
                    new_maps[m0:m1],
                    dict(zip(new_keys[m0:m1], range(1, m1 - m0 + 1))))
            elif f1 > f0:
                reg.append_locs(fa[f0:f1], norm[f0:f1], map_id[f0:f1],
                                is_kernel[f0:f1])
        return loc[pair_of_frame].astype(np.int32), \
            n_pids - int(known.sum())

    def _known_mapping_ids(self, table, krows: np.ndarray) -> list[int]:
        """Registry mapping ids of the table rows ``krows`` (ascending,
        all of pids that have a registry), appending to a registry each
        range it has not seen yet, in that order."""
        cols = [c[krows].tolist() for c in (
            table.pids, table.starts, table.ends, table.offsets, table.objs,
            table.bases)]
        rids = []
        for pid, start, end, offset, obj, base in zip(*cols):
            reg = self._pids[pid]
            rid = reg.mapping_index.get((start, end, offset))
            if rid is None:
                rid = len(reg.mappings) + 1
                reg.mappings.append(ProfileMapping(
                    rid, start, end, offset, _obj_name(table.obj_paths, obj),
                    _obj_name(table.obj_buildids, obj), base))
                reg.mapping_index[(start, end, offset)] = rid
            rids.append(rid)
        return rids

    def _build_profiles(self, snapshot: WindowSnapshot,
                        counts: np.ndarray) -> list[PidProfile]:
        from parca_agent_tpu.pprof.vec import ragged_gather

        ids = np.flatnonzero(counts)
        if not len(ids):
            return []
        vals = counts[ids]
        id_pid = self._id_pid[: self._next_id].astype(np.int64)[ids]
        order = np.argsort(id_pid, kind="stable")
        ids, vals, id_pid = ids[order], vals[order], id_pid[order]
        bounds = np.flatnonzero(np.diff(id_pid)) + 1
        starts = np.concatenate(([0], bounds))
        ends = np.concatenate((bounds, [len(ids)]))
        all_depths = (self._loc_off[ids + 1] - self._loc_off[ids]).astype(
            np.int32)

        profiles = []
        for lo, hi in zip(starts, ends):
            pid = int(id_pid[lo])
            reg = self._pids[pid]
            sel = ids[lo:hi]
            s = len(sel)
            depths = all_depths[lo:hi]
            loc_rows = np.zeros((s, STACK_SLOTS), np.int32)
            flat, _ = ragged_gather(self._loc_flat, self._loc_off[sel],
                                    depths)
            loc_rows[np.arange(STACK_SLOTS)[None, :] < depths[:, None]] = flat
            profiles.append(PidProfile(
                pid=pid,
                stack_loc_ids=loc_rows,
                stack_depths=depths.copy(),
                values=vals[lo:hi].copy(),
                # Views of the registry's columns, read-only: a profile
                # leaves the aggregator, its registry's rows never change.
                loc_address=_frozen(reg.loc_address),
                loc_normalized=_frozen(reg.loc_normalized),
                loc_mapping_id=_frozen(reg.loc_mapping_id),
                loc_is_kernel=_frozen(reg.loc_is_kernel),
                mappings=reg.mappings,
                period_ns=snapshot.period_ns,
                time_ns=snapshot.time_ns,
                duration_ns=snapshot.window_ns,
            ))
        return profiles
