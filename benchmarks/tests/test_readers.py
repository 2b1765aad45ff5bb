"""The span readers count every measured window."""

import types

from lib.readers import counter, span_gap, span_gap_mean

END = ["encode", "ship"]


def _row(seq, **spans):
    """A window whose stages ended, by the harness's clock, where the
    spans say (100 s into the run)."""
    return {"seq": seq, "complete": "total" in spans, "path": "pipeline",
            "ended": {k: 100.0 + b for k, (a, b) in spans.items()}}


def _ctx(rows):
    return types.SimpleNamespace(rows=rows)


def test_a_window_the_fast_encoder_did_not_take_counts_to_its_ship():
    fast = _row(1, drain=(0.0, 1.5), close=(2.0, 2.2), encode=(2.2, 2.3),
                ship=(2.3, 4.5), total=(0.0, 4.5))
    stalled = _row(2, drain=(0.0, 1.5), close=(2.0, 2.4), ship=(2.4, 32.5),
                   total=(0.0, 32.6))
    mean = span_gap_mean.read(_ctx([fast, stalled]), "drain", END)
    assert abs(mean - (800.0 + 31000.0) / 2) < 1e-6
    assert span_gap_mean.read(_ctx([fast]), "drain", END) \
        == span_gap.read(_ctx([fast]), "drain", END, 50)


def test_a_window_that_never_completed_takes_the_metric_away():
    fast = _row(1, drain=(0.0, 1.5), encode=(2.2, 2.3), total=(0.0, 4.5))
    stuck = _row(2, drain=(0.0, 1.5))
    assert span_gap_mean.read(_ctx([fast, stuck]), "drain", END) is None
    assert span_gap.read(_ctx([fast, stuck]), "drain", END, 90) is None
    assert span_gap.read(_ctx([]), "drain", END, 90) is None


def test_a_counter_that_did_not_rise_gives_nothing():
    class M:
        def __init__(self, v):
            self.v = v

        def total(self, name, **labels):
            return self.v

    ctx = types.SimpleNamespace(metrics0=M(5.0), metrics1=M(5.0),
                                windows_closed=4)
    assert counter.read(ctx, "x") is None
    ctx.metrics1 = M(13.0)
    assert counter.read(ctx, "x", scale=0.5) == 1.0


def test_a_roofline_share_finds_its_bytes_by_the_names_in_the_metric_file():
    from lib import roofline
    from lib.readers import trace_roofline

    config = {"stacks": 262144}
    ctx = types.SimpleNamespace(
        trace={"programs": {"jit_feed": {"seconds": 0.15},
                            "jit_close_delta": {"seconds": 0.01}}},
        trace_windows=3, peaks={"hbm_bytes_per_s": 819e9},
        cell=types.SimpleNamespace(config=config))
    share = trace_roofline.read(ctx, "^jit_feed$", "roofline", "feed_bytes")
    least = roofline.feed_bytes(config) / 819e9
    assert abs(share - 100.0 * least / 0.05) < 1e-9
    assert trace_roofline.read(ctx, "^jit_nothing$", "roofline",
                               "feed_bytes") is None
