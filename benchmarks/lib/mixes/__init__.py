"""Traffic mixes. ``traffic/<mix>.json`` names a generator module of
this package and gives its arguments; the harness knows no mix by name.

A generator module has three functions:

  sequence(pop, args, seed)      the windows, in order: an object whose
                                 ``next()`` returns a ``generate.Window``
  distinct_windows(args, needed) how many distinct windows a run of
                                 ``needed`` handed windows generates
  replay_order(args, needed)     which distinct window each handed one is
"""

import importlib


class Mix:
    """One traffic file, bound to its generator module."""

    def __init__(self, traffic: dict):
        self.args = dict(traffic.get("args", {}))
        self._mod = importlib.import_module(
            f"lib.mixes.{traffic['generator']}")

    def sequence(self, pop, seed: int):
        return self._mod.sequence(pop, self.args, seed)

    def distinct_windows(self, needed: int) -> int:
        return self._mod.distinct_windows(self.args, needed)

    def replay_order(self, needed: int) -> list[int]:
        return self._mod.replay_order(self.args, needed)
