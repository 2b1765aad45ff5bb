"""The feed's probe against the loop it replaced.

Until PR 40 ``make_feed`` probed with one ``fori_loop`` of ``_PROBES``
steps that gathered a dictionary row for every lane at every step. That
loop is kept here as the plain reference: ``make_feed`` (a step 0 over
all lanes, the lanes that still search compacted into narrower buffers,
every loop ending when no lane searches) has to give the same
accumulator, touch flags, miss count and miss rows on every input, and
gather no more dictionary rows than a numpy count of its own staging
rule says the input needs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parca_agent_tpu.aggregator import dict as dict_mod
from parca_agent_tpu.aggregator.dict import _PROBES, make_feed, prefix_sum

BLK = 128


def loop_feed(cap, id_cap, n_pad, n_blocks, blk):
    """``make_feed`` as it was before PR 40 (its probe an inline
    ``fori_loop`` over all lanes), returning ``found_id`` as well."""

    def feed(table, acc, touch, packed, reset):
        acc = jnp.where(reset != 0, 0, acc)
        touch = jnp.where(reset != 0, 0, touch)
        h1, h2, h3 = packed[0], packed[1], packed[2]
        cnt = packed[3].astype(jnp.int32)
        mask = jnp.uint32(cap - 1)

        def step(k, state):
            found_id, done = state
            idx = ((h1 + jnp.uint32(k)) & mask).astype(jnp.int32)
            row = table[idx]
            occ = row[:, 3] > 0
            hit = occ & (row[:, 0] == h1) & (row[:, 1] == h2) \
                & (row[:, 2] == h3)
            stop = hit | ~occ
            found_id = jnp.where(hit & ~done,
                                 row[:, 3].astype(jnp.int32) - 1, found_id)
            return found_id, done | stop

        found_id = jnp.full(h1.shape, -1, jnp.int32)
        done = jnp.zeros(h1.shape, bool)
        found_id, _ = jax.lax.fori_loop(0, _PROBES, step, (found_id, done))

        live = cnt > 0
        hit = (found_id >= 0) & live
        acc = acc.at[jnp.where(hit, found_id, id_cap)].add(cnt, mode="drop")
        touch = touch.at[jnp.where(hit, found_id // blk,
                                   n_blocks)].set(1, mode="drop")
        miss = live & ~hit
        mtgt = jnp.where(miss, prefix_sum(miss.astype(jnp.int32)) - 1,
                         jnp.int32(n_pad))
        miss_rows = jnp.full((n_pad,), -1, jnp.int32).at[mtgt].set(
            jnp.arange(h1.shape[0], dtype=jnp.int32), mode="drop")
        n_miss = miss.astype(jnp.int32).sum()
        return acc, touch, n_miss, miss_rows, found_id

    return feed


def insert(table, keys):
    """The host's insert rule on a ``[cap, 4]`` table: linear probing
    from ``h1``, first empty slot; the id is the key's position in
    ``keys`` (after the ids already there). Batched: a key moves on only
    from a slot that is taken, so no chain has a gap."""
    cap = len(table)
    base = int(table[:, 3].max())
    pending = np.arange(len(keys))
    k = np.zeros(len(keys), np.int64)
    while len(pending):
        slot = (keys[pending, 0].astype(np.int64) + k[pending]) & (cap - 1)
        free = table[slot, 3] == 0
        u, first = np.unique(slot[free], return_index=True)
        won = pending[free][first]
        table[u, :3] = keys[won]
        table[u, 3] = base + won + 1
        pending = np.setdiff1d(pending, won, assume_unique=True)
        k[pending] += 1
    return table


def random_keys(rng, n):
    return rng.integers(1, 2**32, size=(n, 3), dtype=np.uint64).astype(
        np.uint32)


def probes_needed(table, packed):
    """Per lane, how many slots the lookup has to read: up to the first
    that holds the key or is empty, ``_PROBES`` at most; 0 for a lane
    with no count."""
    cap = len(table)
    h = packed[:3].T
    need = np.full(packed.shape[1], _PROBES, np.int64)
    open_ = np.ones(packed.shape[1], bool)
    for k in range(_PROBES):
        row = table[(h[:, 0].astype(np.int64) + k) & (cap - 1)]
        stop = (row[:, 3] == 0) | (row[:, :3] == h).all(axis=1)
        need[open_ & stop] = k + 1
        open_ &= ~stop
    return np.where(packed[3] > 0, need, 0)


def gathers_needed(need, n_pad):
    """Dictionary rows ``make_probe`` gathers for lanes that need
    ``need`` probes each, by its staging rule: a loop over a buffer runs
    while one of its lanes searches, a narrow stage takes the lanes it
    is handed ``n_pad // divisor`` at a time."""
    if n_pad < dict_mod._PROBE_NARROW_MIN:
        return n_pad * int(need.max(initial=0))

    def stage(need, k0, plan):
        if not plan:
            return 0
        (div, n_steps), rest = plan[0], plan[1:]
        w, total = n_pad // div, 0
        lanes = need[need > k0]
        for lo in range(0, len(lanes), w):
            part = lanes[lo:lo + w]
            total += w * min(n_steps, int(part.max()) - k0)
            total += stage(part, k0 + n_steps, rest)
        return total

    return n_pad * bool(need.max(initial=0)) \
        + stage(need, 1, dict_mod._PROBE_STAGES)


def pack(n_pad, keys, counts):
    packed = np.zeros((4, n_pad), np.uint32)
    packed[:3, :len(keys)] = np.asarray(keys, np.uint32).T
    packed[3, :len(keys)] = counts
    return packed


def chain_case(cap, base, n_chain=17):
    """``n_chain`` keys that all start at slot ``base``: key j sits j
    slots on (mod cap). The device finds the first ``_PROBES``."""
    keys = np.stack([np.full(n_chain, base, np.uint32),
                     np.arange(1, n_chain + 1, dtype=np.uint32),
                     np.full(n_chain, 7, np.uint32)], axis=1)
    return insert(np.zeros((cap, 4), np.uint32), keys), keys


def random_case(seed, cap, n_pad, load, n_rows, miss_share,
                zero_share=0.0, repeat=False):
    rng = np.random.default_rng(seed)
    keys = random_keys(rng, int(cap * load))
    table = insert(np.zeros((cap, 4), np.uint32), keys)
    n_miss = int(n_rows * miss_share)
    n_hit = n_rows - n_miss if repeat else min(n_rows - n_miss, len(keys))
    rows = np.concatenate([
        keys[rng.integers(0, len(keys), n_hit)] if repeat
        else keys[rng.choice(len(keys), n_hit, replace=False)],
        random_keys(rng, n_miss)])
    rows = rows[rng.permutation(len(rows))]
    counts = rng.integers(1, 1000, len(rows))
    counts[rng.random(len(rows)) < zero_share] = 0
    return table, pack(n_pad, rows, counts)


def _case(name):
    """(table, packed, narrow_min): narrow_min None leaves the tree's
    constant, else the narrow stages engage from that many lanes."""
    cap = 1 << 12
    if name.startswith("load_"):
        load = float(name[5:])
        return random_case(1, 1 << 14, 2048, load, 1800, 0.1), 256
    if name == "chain_wraps_the_end":
        table, keys = chain_case(cap, cap - 5)
        assert table[:12, 3].all() and table[cap - 5:, 3].all()
        return (table, pack(256, keys, 3)), 64
    if name.startswith("chain_"):
        # a chain of 15, 16, 17 keys: the 17th lies beyond the reach
        n_chain = int(name[6:])
        table, keys = chain_case(cap, 100, n_chain)
        return (table, pack(256, keys, 3)), 64
    if name == "empty_slot_before_a_match":
        table, keys = chain_case(cap, 200, 6)
        table[202] = 0          # the host never leaves one; the search stops
        return (table, pack(256, keys, 5)), 64
    if name == "lanes_with_no_count":
        return random_case(2, 1 << 13, 1024, 0.4, 900, 0.2,
                           zero_share=0.3), 256
    if name == "no_live_lane":
        return random_case(3, 1 << 13, 1024, 0.4, 900, 0.2,
                           zero_share=1.0), 256
    if name == "duplicate_keys":
        return random_case(4, 1 << 13, 1024, 0.25, 1000, 0.1,
                           repeat=True), 256
    if name == "all_misses":
        return random_case(5, 1 << 13, 1024, 0.5, 1000, 1.0), 256
    if name == "all_misses_one_width":
        return random_case(5, 1 << 13, 1024, 0.5, 1000, 1.0), None
    if name == "more_lanes_than_the_narrow_buffers":
        # load 0.5 and half the rows new: over n_pad / 4 lanes pass step
        # 0 and over n_pad / 64 pass step 3, so both stages take rounds
        case = random_case(6, 1 << 13, 2048, 0.5, 2048, 0.5)
        need = probes_needed(*case)
        assert (need > 1).sum() > 2048 // 4 and (need > 4).sum() > 2048 // 64
        return case, 256
    raise AssertionError(name)


CASES = ["load_0.005", "load_0.25", "load_0.5", "chain_15", "chain_16",
         "chain_17", "chain_wraps_the_end", "empty_slot_before_a_match",
         "lanes_with_no_count", "no_live_lane", "duplicate_keys",
         "all_misses", "all_misses_one_width",
         "more_lanes_than_the_narrow_buffers"]


def _check(table, packed, reset=0):
    cap, n_pad = len(table), packed.shape[1]
    id_cap = cap // 2
    n_blocks = id_cap // BLK
    rng = np.random.default_rng(9)
    # an accumulator and flags that already hold a window's earlier feeds
    acc = rng.integers(0, 50, id_cap).astype(np.int32)
    touch = (rng.random(n_blocks) < 0.2).astype(np.int32)
    args = (jnp.asarray(table), jnp.asarray(acc), jnp.asarray(touch),
            jnp.asarray(packed), jnp.uint32(reset))
    want = jax.jit(loop_feed(cap, id_cap, n_pad, n_blocks, BLK))(*args)
    got = jax.jit(make_feed(cap, id_cap, n_pad, n_blocks, BLK))(*args)
    w_acc, w_touch, w_n_miss, w_rows, w_found = map(np.asarray, want)
    g_acc, g_touch, g_counts, g_rows = map(np.asarray, got)
    np.testing.assert_array_equal(g_acc, w_acc)
    np.testing.assert_array_equal(g_touch, w_touch)
    assert g_counts[0] == w_n_miss
    np.testing.assert_array_equal(g_rows, w_rows)
    live = packed[3] > 0
    found, _ = jax.jit(dict_mod.make_probe(cap, n_pad))(
        args[0], args[3].T, jnp.asarray(live))
    np.testing.assert_array_equal(np.asarray(found),
                                  np.where(live, w_found, -1))
    need = probes_needed(table, packed)
    assert g_counts[1] == gathers_needed(need, n_pad) <= _PROBES * n_pad
    return need, int(g_counts[1])


@pytest.mark.parametrize("name", CASES)
def test_the_probe_gives_what_the_sixteen_step_loop_gave(name, monkeypatch):
    (table, packed), narrow_min = _case(name)
    if narrow_min is not None:
        monkeypatch.setattr(dict_mod, "_PROBE_NARROW_MIN", narrow_min)
    need, gathers = _check(table, packed, reset=name == "duplicate_keys")
    if name.startswith("chain_") and name[6:].isdigit():
        # the chain's last key is found up to 16 and a miss at 17
        n_chain = int(name[6:])
        assert need.max() == min(n_chain, _PROBES)
    if name == "no_live_lane":
        assert gathers == 0
    if name == "load_0.005":
        assert gathers < 2 * packed.shape[1]


# Every feed shape a cell of the benchmark runs: firehose's 262,144 lanes
# (load 0.25), the node cells' and build-node's 16,384 (load 0.005 and up
# to 0.5), the streamed cell's 8,192 down to the 1,024-lane floor.
@pytest.mark.parametrize("n_pad, cap_log, load, miss_share", [
    (1024, 15, 0.005, 0.1), (2048, 15, 0.005, 0.1), (4096, 15, 0.005, 0.02),
    (8192, 15, 0.1, 0.02), (16384, 17, 0.005, 0.0), (16384, 17, 0.5, 0.9),
    (262144, 20, 0.25, 0.0), (262144, 20, 0.29, 0.01)])
def test_the_probe_at_every_width_the_cells_feed(n_pad, cap_log, load,
                                                 miss_share):
    cap = 1 << cap_log
    n_rows = min(n_pad, int(cap * load)) * 5 // 8 if n_pad < 262144 \
        else n_pad
    need, gathers = _check(*random_case(
        n_pad, cap, n_pad, load, n_rows, miss_share))
    if n_pad == 262144:
        # what the issue counted on: most lanes resolve at their first
        # slot, so the probe reads about a quarter of the loop's rows
        assert gathers < _PROBES * n_pad // 3
    elif load <= 0.1:
        assert gathers <= 4 * n_pad


def test_the_settle_counts_the_gathers_on_the_window_and_on_metrics():
    """The count rides the settle's one fetch: on the window's ``meta``,
    in ``stats`` and on ``/metrics``."""
    from parca_agent_tpu.capture.replay import ReplaySource
    from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
    from parca_agent_tpu.profiler.cpu import CPUProfiler
    from parca_agent_tpu.runtime.trace import FlightRecorder
    from parca_agent_tpu.web import render_metrics

    agg = dict_mod.DictAggregator(capacity=1 << 14, overflow="raise")
    snap = generate(SyntheticSpec(n_pids=20, n_unique_stacks=700, seed=5))
    rec = FlightRecorder()
    per_window = []
    for _ in range(2):
        tr = rec.begin()
        with tr.span("close"):
            agg.window_counts(snap)
        per_window.append(tr.meta["probe_gathers"])
    n_pad = 1024
    # an empty dictionary ends every search at its first slot; at load
    # 0.04 a window of hits walks a few steps, whole ones at its width
    assert per_window[0] == n_pad
    assert n_pad <= per_window[1] <= 6 * n_pad and not per_window[1] % n_pad
    assert agg.stats["probe_gathers"] == sum(per_window)
    text = render_metrics([CPUProfiler(source=ReplaySource([]),
                                       aggregator=agg)])
    assert 'parca_agent_dict_probe_gathers_total{profiler="cpu"} %d' \
        % sum(per_window) in text.splitlines()
