"""The rest of a run without the look for a chip: the CPU rehearsal
drives every step of ``harness.run_cell`` on XLA:CPU at a tiny size.
Sound, its line says ``correct: true``; with the timed path broken
underneath (one count altered where the aggregator produces it), it has
to say ``correct: false``."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, CHECKOUT

SIZES = ["--pids", "40", "--stacks", "1024", "--samples", "8000",
         "--capacity", "16384"]
ARGS = ["--config", "node", "--traffic", "steady", "--seconds", "2", *SIZES]

BROKEN = '''
import sys
sys.path[:0] = [{checkout!r}, {bench!r}]
import numpy as np
from parca_agent_tpu.aggregator import dict as dict_aggregator

sound = dict_aggregator.DictAggregator.window_counts

def off_by_one(self, snapshot, *a, **kw):
    counts = np.array(sound(self, snapshot, *a, **kw), copy=True)
    hot = np.flatnonzero(counts)
    if len(hot):
        counts[hot[0]] += 1
    return counts

dict_aggregator.DictAggregator.window_counts = off_by_one
import rehearse
sys.exit(rehearse.main({args!r}))
'''


def _line(cmd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=300, cwd=CHECKOUT)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("config, traffic, seconds, trace", [
    ("node", "steady", 2, 0), ("firehose", "rollout", 5, 0),
    ("node", "steady", 2, 1)])
def test_a_sound_rehearsal_is_correct(config, traffic, seconds, trace):
    args = ["--config", config, "--traffic", traffic, "--seconds",
            str(seconds), *SIZES, "--trace", str(trace)]
    line, stdout = _line([sys.executable,
                          os.path.join(BENCH, "rehearse.py"), *args])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert "compared stack_mismatches = 0 (limit 0)" in stdout
    names = set(line["metrics"])
    if trace:
        # no device plane in a CPU trace: no device metric in the line
        assert not names & {"device_idle_share", "device_ms_per_window",
                            "feed_probe_roofline", "close_roofline"}
        assert "busy_s" not in line["device"]
        assert {"close_ms.p50", "encode_ms.p50", "ship_ms.p50"} <= names
    else:
        assert {"window_to_pprof_ms.mean", "agent_cpu_ms_per_window",
                "setup_s"} <= names


def test_a_broken_timed_path_comes_out_not_correct():
    code = BROKEN.format(checkout=CHECKOUT, bench=BENCH, args=ARGS)
    line, stdout = _line([sys.executable, "-c", code])
    assert line["correct"] is False
    assert "compared pid_total_mismatches = 2 (limit 0)" in stdout
    assert "compared mass_gap = 2 (limit 0)" in stdout


def test_the_control_inside_the_agent_comes_out_not_correct():
    """``control.py --in-agent``: the system itself with the aggregator's
    counts narrowed to 8 bits, at a size a test run can hold."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), *ARGS,
         "--seeds", "5", "--in-agent"], env=env, capture_output=True,
        text=True, timeout=300, cwd=CHECKOUT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])["control_in_agent"]
    assert line["correct"] is False and line["attempted"] >= 2
    assert "compared mass_gap = 0 " not in out.stdout
