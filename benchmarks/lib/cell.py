"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell is one entry of ``workloads``: a configuration under a traffic
mix. Everything that belongs to one configuration, one mix or one metric
sits in a file of its own (``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.json``,
``lib/readers/<reader>.py``); nothing here branches on a name.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    reader: str
    args: dict

    def read(self, ctx) -> float | None:
        """The metric's value from what the run collected, or None when
        its reader finds nothing to read."""
        mod = importlib.import_module(f"lib.readers.{self.reader}")
        return mod.read(ctx, **self.args)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]


def _metric(entry: dict) -> Metric:
    spec = _load(os.path.join(BENCH_DIR, "metrics", entry["name"] + ".json"))
    return Metric(entry["name"], entry["unit"], spec["reader"],
                  spec.get("args", {}))


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def trial_cell(config: str, traffic: str) -> Cell:
    """A configuration under a mix that ``BENCHMARK.json`` does not list
    as a cell (a rehearsal, or a cell tried before it is added): one
    chip, and every metric of the benchmark, each reader finding what it
    finds."""
    bench = _load(os.path.join(CHECKOUT, "BENCHMARK.json"))
    return Cell(
        name=f"{config}-{traffic}", chips=1, config_name=config,
        traffic_name=traffic,
        config=_load(os.path.join(BENCH_DIR, "configs", config + ".json")),
        traffic=_load(os.path.join(BENCH_DIR, "traffic", traffic + ".json")),
        end_to_end=[_metric(m) for m in bench["end_to_end"]],
        per_layer=[_metric(m) for m in bench["per_layer"]])


def load_cell(name: str) -> Cell:
    bench = _load(os.path.join(CHECKOUT, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(by_name)})")
    w = by_name[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=_load(os.path.join(CHECKOUT, cfg_entry["file"])),
        traffic=_load(os.path.join(BENCH_DIR, "traffic",
                                   w["traffic"] + ".json")),
        end_to_end=[_metric(m) for m in bench["end_to_end"]
                    if _applies(m, name)],
        per_layer=[_metric(m) for m in bench["per_layer"]
                   if _applies(m, name)])


def load_peaks(device_kind: str) -> dict:
    """The peaks of this kind of device; an unknown kind is an error."""
    peaks = _load(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in peaks:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         "benchmarks/peaks.json")
    return peaks[device_kind]
