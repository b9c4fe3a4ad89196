#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cells, configurations, mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The last line of
standard output is the run's result as one JSON object; the numbers the
check compared, each with its limit, are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

if __name__ == "__main__":
    import harness
    sys.exit(harness.main(t_start=T_START))
