"""utils/heap.py: the allocator settings the agent applies at start-up."""

from __future__ import annotations

import subprocess
import sys

import pytest

CODE = ("from parca_agent_tpu.utils.heap import hold_heap\n"
        "print(hold_heap(), hold_heap())\n")


@pytest.mark.parametrize("env", [
    {},                                     # glibc takes them, twice
    {"MALLOC_ARENA_MAX": "2"},              # no switch in the environment
    {"MALLOC_TRIM_THRESHOLD_": "131072"}])
def test_hold_heap_applies_whatever_the_environment_says(env):
    import os

    out = subprocess.run([sys.executable, "-c", CODE],
                         env={**os.environ, **env},
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-500:]
    assert out.stdout.strip() == "True True"


def test_hold_heap_fails_open_without_a_mallopt(monkeypatch):
    import ctypes

    from parca_agent_tpu.utils import heap

    monkeypatch.setattr(ctypes, "CDLL", lambda *_a, **_k: object())
    assert heap.hold_heap() is False
