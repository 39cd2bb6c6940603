#!/usr/bin/env python3
"""Reads the correctness numbers of the program and of its control on the
chip, at the cell's own size, for several seeds in one process:

    python3 perfbench/control.py --workload lj_peel --seeds 11,12,13 --seconds 1

The control is the plain reference computed in bfloat16 instead of the
configuration's float32, put in the program's place; it has to come out as
not correct.  Each seed prints one JSON line with both sets of readings
beside their limits.  The benchmark's own runs never run this.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from perfbench import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.with_pending(harness.load_benchmark()),
                           args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            res = harness.run_cell(cell, seed, args.seconds, False,
                                   time.perf_counter(), control=True)
        except harness.NoChip as e:
            print(f"no chip: {e}", file=sys.stderr)
            return 3
        print(json.dumps({
            "workload": cell.name,
            "seed": seed,
            "program": res["checks"],
            "program_correct": res["correct"],
            "control": res["control_checks"],
            "control_correct": all(
                c["value"] <= c["limit"] for c in res["control_checks"].values()
            ),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
