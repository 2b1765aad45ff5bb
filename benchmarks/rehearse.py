#!/usr/bin/env python3
"""Try a configuration under a traffic mix without storing a cell.

  JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py --config node \\
      --traffic steady --pids 40 --stacks 1024 --samples 8000 \\
      --capacity 16384 --seconds 4

walks every step of a run on XLA:CPU at a tiny size, for debugging the
harness without a chip: the line names the platform ``cpu`` and carries
no device metric (a trace of the CPU backend has no device plane, so the
trace readers find nothing), and its timings are XLA:CPU's and mean
nothing. With ``--platform tpu`` and no size it is a trial, on the chip
and at the configuration's own size, of a cell before it is added. The
sizes come from the command line and are never stored; the line carries
``"rehearsal": true`` and every metric of the benchmark that finds
something to read.
"""

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SIZES = {"pids": "pids", "stacks": "stacks", "samples": "samples_per_window",
         "capacity": "aggregator_capacity"}


def add_arguments(p: argparse.ArgumentParser) -> None:
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--platform", choices=("cpu", "tpu"), default="cpu")
    p.add_argument("--seconds", type=float, default=4.0)
    for flag in SIZES:
        p.add_argument(f"--{flag}", type=int)


def sizes_of(args) -> dict | None:
    given = {key: getattr(args, flag) for flag, key in SIZES.items()
             if getattr(args, flag) is not None}
    return given or None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    add_arguments(p)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from lib import cell, harness

    code, line = harness.run_cell(
        cell.trial_cell(args.config, args.traffic), args.seed, args.seconds,
        bool(args.trace), _T_START, platform=args.platform,
        sizes=sizes_of(args))
    if line is not None:
        line["rehearsal"] = True
        print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    raise SystemExit(main())
