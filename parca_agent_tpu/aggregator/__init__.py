"""Pluggable window aggregation (the reference's hot loop, re-designed).

The reference folds drained stack counts into per-PID profiles one map entry
at a time inside `obtainProfiles` (reference pkg/profiler/cpu/cpu.go:505-718).
Here aggregation is a pluggable `Aggregator` with four implementations:

  NaiveAggregator        dict-based spec oracle; the executable definition
                         of the semantics, used only in tests (cpu.py)
  CPUAggregator          vectorized numpy path; the default backend, the
                         device backends' fallback and the tests' reference
                         (cpu.py)
  DictAggregator         the device backend: stateful device-resident stack
                         dictionary; a steady-state window is one batched
                         lookup+count program with one inline probe loop
                         (dict.py; `--aggregator dict` and `dict+cm`)
  ShardedDictAggregator  DictAggregator with the table and the probe work
                         sharded over a device mesh (sharded.py;
                         `--aggregator sharded`)

The two device aggregators import jax lazily; CPU-only deployments never
pay for it.
"""

from parca_agent_tpu.aggregator.base import (  # noqa: F401
    Aggregator,
    PidProfile,
    ProfileMapping,
    WindowProfiles,
)
from parca_agent_tpu.aggregator.cpu import CPUAggregator, NaiveAggregator  # noqa: F401
