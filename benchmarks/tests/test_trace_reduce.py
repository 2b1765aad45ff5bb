"""The trace reduction on one small recorded trace.

``data/trace_small.json`` holds the planes of a trace in the form
``trace_reduce.read_xplane`` returns them (times in nanoseconds)."""

import json
import os

import pytest

from lib import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def planes():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        return json.load(f)["planes"]


def test_busy_is_the_union_of_op_intervals(planes):
    out = trace_reduce.reduce_planes(planes)
    expect = json.load(open(os.path.join(HERE, "data", "trace_small.json")))["expect"]
    assert out["chips"] == expect["chips"]
    assert out["window_s"] == pytest.approx(expect["window_s"])
    assert out["busy_s"] == pytest.approx(expect["busy_s"])
    assert 0 < out["busy_s"] < out["window_s"]
    for name, want in expect["programs"].items():
        assert out["programs"][name]["calls"] == want["calls"]
        assert out["programs"][name]["seconds"] == pytest.approx(want["seconds"])
    assert out["device_ops"][0][0] == expect["top_op"]
    assert out["idle_gaps"][0][1] == pytest.approx(expect["longest_gap_s"])
    assert out["idle_gaps"][0][0] == expect["longest_gap_name"]


def test_overlapping_ops_are_not_counted_twice():
    planes = [("/device:TPU:0", [
        ("XLA Modules", [("jit_feed(1)", 0.0, 100.0)]),
        ("XLA Ops", [("a", 0.0, 60.0), ("b", 40.0, 60.0)])]),
        ("/host:CPU", [("t", [("x", 0.0, 1000.0)])])]
    out = trace_reduce.reduce_planes(planes)
    assert out["busy_s"] == pytest.approx(100e-9)
    assert out["window_s"] == pytest.approx(1000e-9)


def test_a_trace_without_a_device_plane_has_no_device_number():
    assert trace_reduce.reduce_planes(
        [("/host:CPU", [("t", [("x", 0.0, 10.0)])])]) is None
