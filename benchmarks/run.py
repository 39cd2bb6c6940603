"""Benchmark runner: ``python -m benchmarks.run [names...]``.

Runs every paper-table/figure benchmark, prints CSV blocks, and writes
experiments/bench/<name>.csv for EXPERIMENTS.md.
"""

from __future__ import annotations

import os
import sys
import time


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    from benchmarks.paper_benches import ALL, _rows_to_csv
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    names = [a for a in argv if not a.startswith("-")] or list(ALL)
    out_dir = os.path.join("experiments", "bench")
    os.makedirs(out_dir, exist_ok=True)
    failures = 0
    for name in names:
        fn = ALL[name]
        t0 = time.time()
        print(f"== {name} ==", flush=True)
        try:
            rows = fn()
            csv = _rows_to_csv(rows)
            print(csv)
            with open(os.path.join(out_dir, f"{name}.csv"), "w") as f:
                f.write(csv + "\n")
            print(f"-- {name}: {len(rows)} rows in {time.time()-t0:.1f}s\n", flush=True)
        except Exception as e:  # keep going; report at the end
            import traceback

            failures += 1
            print(f"-- {name} FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
