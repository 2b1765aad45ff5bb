"""Windows that take a fast-encoder template through every site that
lays down, moves or rewrites a blob's static span, shared by
tests/test_window_encoder.py (the span table) and
tests/test_agent_transport.py (the gzip member spliced from it); and
(`streamed_profiler`, `turnover_windows`) windows that arrive as drains,
for the flight recorder's span tree of a streamed window
(tests/test_replay_drains.py).

`run(site)` yields one step per encoded window:
(encoder, views=True output (its `span_blobs()` are what the writer is
given), pids whose span was rewritten by that window or None for "all
of them", note). RELAID is the note of the one step that lays every
span down again with the bytes it held: it rewrites none, and the new
layout's piece cache takes the pieces over."""

from __future__ import annotations

import dataclasses

import numpy as np

from parca_agent_tpu.aggregator.dict import DictAggregator
from parca_agent_tpu.capture.synthetic import SyntheticSpec, generate
from parca_agent_tpu.pprof.window_encoder import WindowEncoder

SHAPES = {
    # pids, stacks, mean depth: one stack a pid; the firehose's 21 a pid
    # at depth 24; and (no_locations) a pid whose only stack is empty.
    "one_stack_a_pid": (16, 16, 8),
    "firehose_21_at_24": (12, 252, 24),
    "no_locations": (6, 60, 6),
}

SITES = ("counts", "slack_append", "new_pid", "new_locations",
         "relocated", "head_tail", "relayout", "reset", "rotation")
RELAID = "full relayout"


def spec(seed=7, n_pids=12, rows=400, depth=10):
    return SyntheticSpec(
        n_pids=n_pids, n_unique_stacks=rows, n_rows=rows,
        total_samples=rows * 4, mean_depth=depth, kernel_fraction=0.25,
        seed=seed)


def rows_of(snap, mask):
    """The snapshot cut to the rows `mask` keeps (same mapping table)."""
    return dataclasses.replace(
        snap, pids=snap.pids[mask], tids=snap.tids[mask],
        counts=snap.counts[mask], user_len=snap.user_len[mask],
        kernel_len=snap.kernel_len[mask], stacks=snap.stacks[mask])


def shape_snapshot(shape: str):
    n_pids, rows, depth = SHAPES[shape]
    snap = generate(spec(seed=61, n_pids=n_pids, rows=rows, depth=depth))
    if shape == "no_locations":
        # One more pid whose single row has no frame at all: its blob's
        # static span is head + tail alone.
        snap = dataclasses.replace(
            snap, pids=np.append(snap.pids, 999_999),
            tids=np.append(snap.tids, 1), counts=np.append(snap.counts, 5),
            user_len=np.append(snap.user_len, 0),
            kernel_len=np.append(snap.kernel_len, 0),
            stacks=np.concatenate(
                (snap.stacks, np.zeros((1, snap.stacks.shape[1]),
                                       np.uint64))))
    return snap


def turnover_windows(n: int, pids: int = 12, stacks: int = 300,
                     turnover: float = 0.0, seed: int = 2147483659):
    """``n`` windows of the benchmark's ``turnover`` generator (at 0.0,
    of the stationary population its ``steady`` mix draws from), as the
    agent's replay source would load them, and beside them as the
    benchmark's reference reads them."""
    import io
    import os
    import sys

    from parca_agent_tpu.capture.formats import load_snapshot

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench)
    try:
        from lib import generate as bgen
        from lib import snapfile
        from lib.mixes import turnover as mix
    finally:
        sys.path.remove(bench)
    pop = bgen.Population(pids=pids, stacks=stacks,
                          samples_per_window=8 * stacks, mean_depth=8)
    seq = mix.sequence(pop, {"turnover": turnover}, seed) if turnover \
        else bgen.PopulationSequence(pop, seed)
    raw = []
    for _ in range(n):
        w = seq.next()   # the sequence may rewrite its rows in place
        raw.append(dataclasses.replace(
            w, pids=w.pids.copy(), counts=w.counts.copy(),
            stacks=w.stacks.copy(), user_len=w.user_len.copy(),
            kernel_len=w.kernel_len.copy()))
    return [load_snapshot(io.BytesIO(snapfile.snapshot_bytes(w)))
            for w in raw], raw


class PidSink:
    """A profile writer that keeps what each window shipped."""

    def __init__(self):
        self.windows = [{}]

    def write(self, labels, blob):
        self.windows[-1][int(labels["pid"])] = bytes(blob)


def streamed_profiler(snaps, drains=10, overflow="sketch", recorder=None,
                      capacity=1 << 13, **feeder_kw):
    """The DaemonSet's path over fixtures: a replay source that drains,
    the streaming feeder fed from it, a dict aggregator with the carry
    cache and an inline fast encoder. Returns (profiler, feeder,
    aggregator, sink)."""
    from parca_agent_tpu.aggregator.cpu import CPUAggregator
    from parca_agent_tpu.capture.replay import ReplaySource
    from parca_agent_tpu.profiler.cpu import CPUProfiler
    from parca_agent_tpu.profiler.streaming import StreamingWindowFeeder

    source = ReplaySource(snaps, drains=drains)
    agg = DictAggregator(capacity=capacity, overflow=overflow, carry=True)
    feeder = StreamingWindowFeeder(agg, source, **feeder_kw)
    source.on_drain = feeder.on_drain
    sink = PidSink()
    prof = CPUProfiler(source=source, aggregator=agg,
                       fallback_aggregator=CPUAggregator(),
                       profile_writer=sink, fast_encode=True,
                       encode_pipeline=False, streaming_feeder=feeder,
                       duration_s=0.0, trace_recorder=recorder)
    return prof, feeder, agg, sink


def run_windows(prof, sink, n):
    """``n`` more windows through the profiler; what each shipped,
    ``{pid: bytes}``."""
    out = []
    for _ in range(n):
        assert prof.run_iteration()
        assert prof.last_error is None
        out.append(sink.windows[-1])
        sink.windows.append({})
    return out


def _enc(snap, counts, t, enc):
    return enc.encode(counts, snap.time_ns + t, snap.window_ns,
                      snap.period_ns, views=True)


def run(site: str):
    """Generator of (enc, out, rewritten pids | None, note) steps: the
    first step is always the cold layout (None: every span is new), the
    second is the site's own window, the third a steady window after
    it (no span rewritten)."""
    snap = generate(spec(seed=21, n_pids=10, rows=800 if site == "relocated"
                         else 500))
    agg = DictAggregator(capacity=1 << 13,
                         **({"rotate_min_age": 1} if site == "rotation"
                            else {}))
    enc = WindowEncoder(agg)
    rng = np.random.default_rng(6)
    pid_of_row = snap.pids
    victim = int(pid_of_row[0])
    first = snap
    if site == "new_locations":
        # Half of every pid's rows: the other half's locations reach the
        # registry with the second window.
        first = rows_of(snap, rng.random(len(snap)) < 0.5)
    elif site == "head_tail":
        # The victim shows half its rows under a mapping table that
        # lists one of its mappings: the others are registered (head and
        # string table grow) when the second window brings addresses in
        # them (a dlopen between two windows).
        keep = pid_of_row != victim
        rows_v = np.flatnonzero(~keep)
        keep[rows_v[::2]] = True
        tab = snap.mappings
        km = tab.pids != victim
        km[np.flatnonzero(~km)[0]] = True
        first = dataclasses.replace(
            rows_of(snap, keep),
            mappings=dataclasses.replace(
                tab, pids=tab.pids[km], starts=tab.starts[km],
                ends=tab.ends[km], offsets=tab.offsets[km],
                objs=tab.objs[km], bases=tab.bases[km]))
    c1 = np.asarray(agg.window_counts(first))
    pids_of_id = agg._id_pid[: len(c1)]
    if site == "slack_append":
        c1 = c1.copy()
        c1[rng.random(len(c1)) < 0.15] = 0
    elif site == "new_pid":
        c1 = c1.copy()
        c1[pids_of_id == victim] = 0
    elif site == "relocated":
        victim = int(np.bincount(pids_of_id.astype(np.int64)).argmax())
        c1 = c1.copy()
        c1[np.flatnonzero(pids_of_id == victim)[2:]] = 0
    yield enc, _enc(snap, c1, 0, enc), None, "cold layout"

    enc.timings.clear()
    if site == "counts":
        c2 = c1 + 3
        yield enc, _enc(snap, c2, 1, enc), set(), "counts redrawn"
    elif site in ("slack_append", "relocated"):
        c2 = np.asarray(agg.window_counts(snap))
        waste0 = enc._tmpl.waste
        out = _enc(snap, c2, 1, enc)
        assert "encode_build" not in enc.timings
        if site == "relocated":
            assert enc._tmpl.waste > waste0
        else:
            assert enc.stats["append_fast_groups"] > 0
        yield enc, out, set(), site
    elif site == "new_pid":
        c2 = np.asarray(agg.window_counts(snap))
        out = _enc(snap, c2, 1, enc)
        assert "encode_build" not in enc.timings
        yield enc, out, {victim}, "a pid the template never saw"
    elif site == "new_locations":
        n0 = {p: len(r.mappings) for p, r in agg._pids.items()}
        c2 = np.asarray(agg.window_counts(snap))
        assert all(len(r.mappings) == n0[p] for p, r in agg._pids.items())
        out = _enc(snap, c2, 1, enc)
        assert "encode_build" not in enc.timings
        t = enc._tmpl
        # The delta went in BEHIND the time tail: g_loc_len counts it,
        # the span does not.
        assert (t.g_head_len + t.g_loc_len + t.g_tail_len
                > t.span_len).all()
        yield enc, out, set(), "location delta behind the time tail"
    elif site == "head_tail":
        n0 = len(agg._pids[victim].mappings)
        c2 = np.asarray(agg.window_counts(snap))
        assert len(agg._pids[victim].mappings) > n0
        out = _enc(snap, c2, 1, enc)
        assert "encode_build" not in enc.timings
        yield enc, out, {victim}, "head and tail rebuilt for one pid"
    elif site == "relayout":
        c2 = c1.copy()
        c2[np.arange(len(c2)) % 3 != 0] = 0
        out = _enc(snap, c2, 1, enc)
        assert "encode_build" in enc.timings
        yield enc, out, set(), RELAID
    elif site == "reset":
        enc.reset()
        c2 = c1
        yield enc, _enc(snap, c2, 1, enc), None, "after reset()"
    elif site == "rotation":
        snap2 = generate(spec(seed=5, n_pids=10, rows=500))
        agg.window_counts(snap2)
        agg._rotate_pending = True
        c2 = np.asarray(agg.window_counts(snap2))
        assert agg.stats.get("rotations", 0) == 1
        snap = snap2
        yield enc, _enc(snap, c2, 1, enc), None, "registry rotation"
    else:
        raise ValueError(site)

    c3 = c2.copy()
    c3[c3 > 0] += 7
    enc.timings.clear()
    out = _enc(snap, c3, 2, enc)
    assert "encode_build" not in enc.timings
    yield enc, out, set(), "steady window after"
