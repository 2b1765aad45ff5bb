"""The readers of the program's span tree and of its ``pa/*``
annotations in the profiler's trace.

``data/trace_pa.json`` is cut from a chip trace of PR 24 (node-steady,
a TPU v5 lite): two whole windows, the device's operations and the host
lines that hold ``pa/*`` events, in the form ``trace_reduce.read_xplane``
returns them."""

import json
import os
import types

import pytest

from lib.readers import (span_gap_mean, span_gap_uncovered, span_overlap,
                         span_self, trace_idle_by_host)

HERE = os.path.dirname(os.path.abspath(__file__))
END = ["encode", "ship"]


def _row(seq, at=100.0, **spans):
    """A window whose stages ran, by the harness's clock, where the
    spans say (``at`` seconds into the run): [start, end) pairs."""
    return {"seq": seq, "complete": "total" in spans, "path": "pipeline",
            "spans": {k: (a, b, b - a) for k, (a, b) in spans.items()},
            "ended": {k: at + b for k, (a, b) in spans.items()}}


def _ctx(rows, all_rows=None):
    return types.SimpleNamespace(rows=rows, all_rows=all_rows or rows)


def test_self_time_is_the_span_less_the_children_it_has():
    r = _row(1, close=(2.0, 2.2), feed_hash=(2.0, 2.05),
             close_fetch=(2.1, 2.18), total=(0.0, 3.0))
    kids = ["feed_hash", "feed_miss", "close_fetch"]
    assert span_self.read(_ctx([r]), "close", kids, 50) \
        == pytest.approx(200.0 - 50.0 - 80.0)
    assert span_self.read(_ctx([r]), "encode", kids, 50) is None


def test_uncovered_is_the_gap_less_the_stages_that_ended_inside_it():
    fast = _row(1, drain=(0.0, 1.5), identity=(1.5, 2.0), close=(2.0, 2.2),
                prepare=(2.21, 2.25), encode=(2.26, 2.3), ship=(2.3, 4.5),
                total=(0.0, 4.5))
    stages = ["identity", "close", "prepare", "encode", "ship", "drain"]
    left = span_gap_uncovered.read(_ctx([fast]), "drain", END, stages)
    # 800 ms from drain's end to encode's; identity, close, prepare and
    # encode cover 780 of them. drain ended at the near edge and ship
    # after the far one: neither is inside.
    assert left == pytest.approx(800.0 - 500.0 - 200.0 - 40.0 - 40.0)
    assert span_gap_mean.read(_ctx([fast]), "drain", END) \
        == pytest.approx(800.0)
    stalled = _row(2, drain=(0.0, 1.5), close=(2.0, 2.4), ship=(2.4, 32.5),
                   total=(0.0, 32.6))
    both = span_gap_uncovered.read(_ctx([fast, stalled]), "drain", END, stages)
    assert both == pytest.approx((20.0 + (31000.0 - 400.0 - 30100.0)) / 2)
    stuck = _row(3, drain=(0.0, 1.5))
    assert span_gap_uncovered.read(_ctx([fast, stuck]), "drain", END,
                                   stages) is None


def test_overlap_with_the_window_before_is_by_the_harness_clock():
    # Window 1's ship runs from 102.3 to 105.0 and window 2's drain ends
    # at 104.5: 0.5 s of that ship lies inside window 2's
    # drain-end-to-encode-end.
    w1 = _row(1, at=100.0, drain=(0.0, 1.5), encode=(2.2, 2.3),
              ship=(2.3, 5.0), total=(0.0, 5.0))
    w2 = _row(2, at=103.0, drain=(0.0, 1.5), encode=(2.2, 2.3),
              ship=(2.3, 4.0), total=(0.0, 4.0))
    w3 = _row(3, at=108.0, drain=(0.0, 1.5), encode=(2.2, 2.3),
              ship=(2.3, 4.0), total=(0.0, 4.0))
    ctx = _ctx([w2, w3], all_rows=[w1, w2, w3])
    # w2: [104.5, 105.3] against w1's ship [102.3, 105.0] -> 0.5 s;
    # w3: [109.5, 110.3] against w2's ship [105.3, 107.0] -> nothing.
    assert span_overlap.read(ctx, "ship", of_previous=True) \
        == pytest.approx((500.0 + 0.0) / 2)
    # The first measured window's neighbour fell off the ring: left out.
    assert span_overlap.read(_ctx([w2], all_rows=[w2]), "ship",
                             of_previous=True) is None
    # A window's own ship begins where its encode ends: no overlap.
    assert span_overlap.read(ctx, "ship") == pytest.approx(0.0)


def _planes(capture, ops):
    return [("/device:TPU:0", [("XLA Modules", []), ("XLA Ops", ops)]),
            ("/host:CPU", [("python3", [("other", 0.0, 5.0)]),
                           ("python3", capture)])]


def test_idle_time_is_shared_out_among_the_capture_threads_stages():
    ops = [("a", 0.0, 10.0), ("b", 110.0, 10.0), ("c", 1000.0, 10.0)]
    capture = [("pa/close", 5.0, 100.0), ("pa/sleep", 120.0, 480.0),
               ("pa/drain", 600.0, 300.0), ("$other", 0.0, 1.0)]
    shares = trace_idle_by_host.idle_shares(_planes(capture, ops))
    # Idle: [10, 110) and [120, 1000) = 980 ns. close covers [10, 105) of
    # it, sleep [120, 600), drain [600, 900); [105, 110) and [900, 1000)
    # are nobody's.
    assert shares["close"] == pytest.approx(100 * 95 / 980)
    assert shares["sleep"] == pytest.approx(100 * 480 / 980)
    assert shares["drain"] == pytest.approx(100 * 300 / 980)
    assert 100 - sum(shares.values()) == pytest.approx(100 * 105 / 980)


def test_a_trace_without_annotations_or_without_a_device_gives_nothing():
    ops = [("a", 0.0, 10.0), ("b", 110.0, 10.0)]
    assert trace_idle_by_host.idle_shares(
        _planes([("pa/close", 5.0, 100.0)], ops)) is None   # no pa/sleep
    assert trace_idle_by_host.idle_shares(
        [("/host:CPU", [("python3", [("pa/sleep", 0.0, 5.0)])])]) is None
    ctx = types.SimpleNamespace(trace=None,
                                cell=types.SimpleNamespace(name="no-cell"))
    assert trace_idle_by_host.read(ctx, stage="sleep") is None


@pytest.fixture(scope="module")
def chip_trace():
    with open(os.path.join(HERE, "data", "trace_pa.json")) as f:
        d = json.load(f)
    planes = [(pn, [(ln, [tuple(e) for e in ev]) for ln, ev in lines])
              for pn, lines in d["planes"]]
    return planes, d["expect"]


def test_the_chips_programs_run_inside_the_hosts_close_annotation(chip_trace):
    """The annotations and the device's events are on one clock: every
    program of the two windows lies inside a ``pa/close`` of the capture
    thread (the close dispatches and waits for all three)."""
    planes, _expect = chip_trace
    programs = [e for pn, lines in planes for ln, ev in lines
                if ln == "XLA Modules" for e in ev]
    closes = [(s, s + d) for pn, lines in planes if pn == "/host:CPU"
              for _ln, ev in lines for n, s, d in ev if n == "pa/close"]
    assert len(programs) == 6 and len(closes) == 2
    for _n, s, d in programs:
        assert any(a <= s and s + d <= b for a, b in closes)


def test_idle_shares_of_a_chip_trace(chip_trace):
    planes, expect = chip_trace
    shares = trace_idle_by_host.idle_shares(planes)
    for stage, want in expect["shares"].items():
        assert shares[stage] == pytest.approx(want)
    # A node window every 0.5 s: the chip idles under the sleep most of
    # all, and the named stages leave under 1% of the idle time over.
    assert shares["sleep"] > 85 > 10 > shares["identity"] > 1
    named = sum(shares[s] for s in ("sleep", "drain", "identity", "close"))
    assert 99 < named <= 100
