#!/usr/bin/env python3
"""Finds the highest rate an open-loop serving cell sustains, once, on the
chip: builds the cell's graph and engine, then for each rate runs one
window of the cell's traffic at that rate and prints one JSON line with
the tail and how long the queue took to drain after the last arrival.

    python3 perfbench/sweep.py --workload lj_serve_local --seed 5 \\
        --seconds 8 --rates 25,50,100,200
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import numpy as np  # noqa: E402

from perfbench import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated queries/s")
    args = ap.parse_args(argv)
    import jax

    from repro.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    cell = harness.resolve(harness.with_pending(harness.load_benchmark()),
                           args.workload)
    driver = harness.driver_for(cell)
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds)
    ctx.graph = harness.make_graph(cell.config, args.seed)
    ctx.state = driver.setup(ctx)
    engine, nodes = ctx.state["engine"], ctx.state["nodes"]
    print(json.dumps({"setup_s": time.perf_counter() - _T_START}), flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate_qps=rate)
        ctx.state = driver.prepare(ctx, engine, nodes)
        ctx.window = driver.window(ctx)
        lat_ms = 1e3 * ctx.window["latency_s"]
        c = ctx.window["counters"]
        print(json.dumps({
            "rate_qps": rate,
            "queries": int(ctx.window["attempted"]),
            "failed": int(ctx.window["failed"]),
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p95_ms": float(np.percentile(lat_ms, 95)),
            "p99_ms": float(np.percentile(lat_ms, 99)),
            "drain_after_last_arrival_s": ctx.window["closed_s"] - args.seconds,
            "lag_max_ms": 1e3 * ctx.window["lag_max_s"],
            "queries_per_batch": c["queries_answered"] / max(c["batches_flushed"], 1),
            "edges_scanned_per_query": c["local_edges_scanned"] / max(c["queries_answered"], 1),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
