#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

  python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The agent runs through ``parca_agent_tpu.cli.run()`` in this process on
the chip this machine holds (``lib/harness.py``). The last line of
stdout is the result: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics) and ``device``. Without an accelerator, or without the program
beside the benchmark, nothing is printed and the exit code is not 0.
"""

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from lib import cell, harness

    code, line = harness.run_cell(
        cell.load_cell(args.workload), args.seed, args.seconds,
        bool(args.trace), _T_START, platform="tpu")
    if line is not None:
        print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    raise SystemExit(main())
