"""Capture sources for feeder tests: the streaming half of the
capture-source protocol (``mapping_table(pids)``) over two fake caches,
built the way the perf sampler builds it."""

from __future__ import annotations

from parca_agent_tpu.capture.live import mapping_table_for_pids


class CacheSource:
    """What ``PerfEventSampler.mapping_table`` is over its own caches."""

    def __init__(self, maps_cache, objs_cache, quarantine=None):
        self._maps, self._objs = maps_cache, objs_cache
        self.quarantine = quarantine
        self.on_drain = None

    def mapping_table(self, pids):
        return mapping_table_for_pids(self._maps, self._objs, pids,
                                      quarantine=self.quarantine)
