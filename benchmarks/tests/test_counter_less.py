"""The remainder reader: one counter's rise less the rises of others."""

import types

from lib import scrape
from lib.readers import counter_less


def _scrape(process, profiler, native):
    lines = ["# TYPE parca_agent_process_cpu_seconds_total counter",
             f"parca_agent_process_cpu_seconds_total {process}",
             'parca_agent_thread_cpu_seconds_total{thread="actor-profiler"} '
             f"{profiler}",
             f'parca_agent_thread_cpu_seconds_total{{thread="native"}} {native}']
    return scrape.Metrics("\n".join(lines) + "\n")


def test_a_remainder_is_one_counters_rise_less_the_others():
    thread = "parca_agent_thread_cpu_seconds_total"
    less = [{"name": thread, "labels": {"thread": "actor-profiler"}},
            {"name": thread, "labels": {"thread": "native"}},
            # Not in this process's scrape: takes nothing off.
            {"name": thread, "labels": {"thread": "stream-feed"}}]
    ctx = types.SimpleNamespace(metrics0=_scrape(10.0, 4.0, 1.0),
                                metrics1=_scrape(12.0, 5.0, 1.5),
                                windows_closed=5)
    got = counter_less.read(ctx, "parca_agent_process_cpu_seconds_total",
                            less, scale=1000.0)
    assert abs(got - 1000.0 * (2.0 - 1.0 - 0.5) / 5) < 1e-9
    # A remainder of nothing is a reading, not an absence.
    ctx.metrics1 = _scrape(11.5, 5.0, 1.5)
    assert counter_less.read(
        ctx, "parca_agent_process_cpu_seconds_total", less) == 0.0


def test_a_remainder_of_a_missing_counter_is_nothing():
    parent = scrape.Metrics("parca_agent_profiler_attempts_total 3\n")
    ctx = types.SimpleNamespace(metrics0=parent, metrics1=parent,
                                windows_closed=5)
    assert counter_less.read(ctx, "parca_agent_process_cpu_seconds_total",
                             [{"name": "parca_agent_thread_cpu_seconds_total",
                               "labels": {"thread": "native"}}]) is None
    ctx = types.SimpleNamespace(metrics0=None, metrics1=None, windows_closed=5)
    assert counter_less.read(ctx, "x", []) is None
    # The first counter under labels that no sample carries is missing too.
    ctx = types.SimpleNamespace(metrics0=_scrape(1, 1, 1),
                                metrics1=_scrape(2, 1, 1), windows_closed=1)
    assert counter_less.read(
        ctx, "parca_agent_thread_cpu_seconds_total", [],
        labels={"thread": "row-hash"}) is None
