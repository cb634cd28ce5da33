#!/usr/bin/env python3
"""The benchmark's command: ``python benchmark/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` from the root of a checkout.
See ``benchmark/harness.py``."""

import os
import sys
import time

T0 = time.perf_counter()        # set-up is counted from here
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark import harness
    sys.exit(harness.main(sys.argv[1:], t0=T0))
