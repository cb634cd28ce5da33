#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from, in one process.

    python benchmark/calibrate.py --workload <cell> --seeds 12 --controls 3

For each seed: the inputs, one call of the timed path, and the cell's
numbers against the plain reference (the lower readings).  On the first
``--controls`` seeds also the control — the reference put in the
program's place at each precision below the one the configuration
states — and the cell's faults.  One JSON line a seed on standard output.
Not run by the driver; PERF.md holds what it read.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROL_PRECISIONS = ("high", "bfloat16")


def main(argv):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=2_500_000_001)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args(argv)
    from benchmark import harness
    ctx = harness.open_cell(ROOT, args.workload, args.first_seed,
                            rehearsal=args.rehearsal)
    for j in range(args.seeds):
        ctx.seed = args.first_seed + 7919 * j
        t = time.perf_counter()
        driver = harness.make_driver(ctx)
        driver.make_data()
        driver.call(0)
        driver.release()
        row = {"cell": args.workload, "seed": ctx.seed,
               "program": driver.check()}
        if j < args.controls:
            row["control"] = {prec: driver.check(precision=prec)
                              for prec in CONTROL_PRECISIONS}
            row["faults"] = driver.faults()
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        del driver
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
