#!/usr/bin/env python3
"""Runs one benchmark cell once, from the root of a checkout:

    python3 perfbench/run.py --workload lj_peel --seed 1 --seconds 10 --trace 0

The cells, their configurations and metrics are in ``BENCHMARK.json``;
``perfbench/harness.py`` says how a run goes.  Without a TPU (or with fewer
chips than the cell asks for) it prints no result and exits 3.
"""

import time

_T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from perfbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], _T_START))
