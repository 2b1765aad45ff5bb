#!/usr/bin/env python3
"""The control of the output check: counts carried in fewer bits.

The configurations state exact 64-bit counts. The nearest thing below
is a count carried in ``--bits`` bits, saturating: what the close's
packed fetch would give without its overflow sideband. Two ways, and
both have to come out as not correct:

  python3 benchmarks/control.py --config firehose --traffic steady --seeds 1 2 3

puts the plain reference in the program's place: for each seed it
generates the second window, aggregates it with the reference, ships
that aggregate as pprofs (``lib/pprof_write.py``) and compares it as a
run does (``lib/compare.py``), once as it is (which has to read 0 on
every number) and once with the counts narrowed. No device is touched.

  python3 benchmarks/control.py --config node --traffic steady --seeds 1 2 3 \\
      --in-agent --platform tpu --seconds 10

runs the system itself, as ``run.py`` does and at the configuration's own
size, with the agent's aggregator narrowing every count it produces: one
process per seed, since one process holds the chip. A benchmark run
never runs this. Prints one JSON line per seed and exits 1 if a control
passed or a sound shipment failed.
"""

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402


def reference_in_place(c, seeds, bits: int) -> int:
    import numpy as np

    from lib import compare, generate, mixes, reference
    from lib.pprof_write import make_pprof

    pop = generate.Population.from_config(c.config)
    mix = mixes.Mix(c.traffic)
    n_sampled = int(c.config["check"]["sampled_pids"])
    bad = 0
    for seed in seeds:
        seq = mix.sequence(pop, seed)
        seq.next()
        w = seq.next()
        truth = reference.group_by(w, np.unique(w.pids).tolist())
        out = {"cell": c.name, "seed": seed, "bits": bits}
        for name, narrow in (("sound", None), ("control", bits)):
            blobs = {pid: make_pprof(
                w, pid, stacks if narrow is None
                else reference.lower_precision(stacks, narrow))
                for pid, stacks in truth.items()}
            numbers = compare.compare_window(w, blobs, seed, n_sampled)
            out[name] = {**numbers, "correct": compare.verdict(numbers)}
        bad += out["sound"]["correct"] is not True \
            or out["control"]["correct"] is not False
        print(json.dumps(out), flush=True)
    return bad


def narrow_the_agents_counts(bits: int) -> None:
    """Every count the agent's aggregator hands to the encoder, in
    ``bits`` bits, saturating."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np
    from parca_agent_tpu.aggregator.dict import DictAggregator

    sound = DictAggregator.window_counts

    def narrowed(self, snapshot, *a, **kw):
        return np.minimum(sound(self, snapshot, *a, **kw), (1 << bits) - 1)

    DictAggregator.window_counts = narrowed


def in_agent(args, seed: int) -> int:
    """This process is one run with the agent's counts narrowed."""
    from lib import cell, harness

    narrow_the_agents_counts(args.bits)
    code, line = harness.run_cell(
        cell.trial_cell(args.config, args.traffic), seed, args.seconds,
        False, _T_START, platform=args.platform,
        sizes=rehearse.sizes_of(args))
    if line is not None:
        print(json.dumps({"seed": seed, "bits": args.bits,
                          "control_in_agent": line}), flush=True)
    return code


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    rehearse.add_arguments(p)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--bits", type=int, default=8)
    p.add_argument("--in-agent", action="store_true")
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.child:
        return in_agent(args, args.seeds[0])
    if not args.in_agent:
        from lib import cell

        return 1 if reference_in_place(
            cell.trial_cell(args.config, args.traffic), args.seeds,
            args.bits) else 0
    bad = 0
    rest = ["--config", args.config, "--traffic", args.traffic,
            "--platform", args.platform, "--seconds", str(args.seconds),
            "--bits", str(args.bits)]
    for flag in rehearse.SIZES:
        if getattr(args, flag) is not None:
            rest += [f"--{flag}", str(getattr(args, flag))]
    for seed in args.seeds:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *rest, "--child",
             "--seeds", str(seed)], stdout=subprocess.PIPE, text=True)
        sys.stdout.write(out.stdout)
        sys.stdout.flush()
        lines = out.stdout.strip().splitlines()
        try:
            failed_it = json.loads(lines[-1])["control_in_agent"]["correct"] \
                is False
        except (IndexError, KeyError, ValueError):
            failed_it = True       # a control that gives no number has failed
        bad += not failed_it
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
