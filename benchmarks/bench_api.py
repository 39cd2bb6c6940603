"""Front-door benchmark: Solver compile caching + solve_batch throughput.

    PYTHONPATH=src python -m benchmarks.bench_api [--n 100000] [--avg-deg 8]

Measures
  * cold-compile vs cached ``solve`` latency (the Solver's program cache is
    what lets a serving tier skip retracing at request rates), including a
    same-shape DIFFERENT graph (the production request pattern), and
  * ``solve_batch`` eps-sweep throughput vs sequential per-eps ``solve``
    calls (the ROADMAP batched driver).

Writes experiments/bench/BENCH_api.json.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

import jax

from repro.compile_cache import enable_compile_cache
from repro.core import Problem, Solver
from repro.graph.edgelist import EdgeList
from repro.graph.generators import chung_lu_power_law


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return time.perf_counter() - t0, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--avg-deg", type=float, default=8.0)
    ap.add_argument("--eps", type=float, default=0.5)
    ap.add_argument("--max-passes", type=int, default=48)
    ap.add_argument("--grid", type=int, default=8, help="eps sweep size")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=os.path.join("experiments", "bench", "BENCH_api.json"))
    args = ap.parse_args(argv)
    enable_compile_cache()

    edges = chung_lu_power_law(args.n, exponent=2.0, avg_deg=args.avg_deg, seed=0)
    perm = np.random.default_rng(1).permutation(edges.src.shape[0])
    other = EdgeList(
        src=edges.src[perm], dst=edges.dst[perm], weight=edges.weight[perm],
        mask=edges.mask[perm], n_nodes=edges.n_nodes,
    )
    m = int(edges.num_real_edges())
    # compaction pinned off: this bench tracks the ONE-program cache path
    # (cold vs cached latency, retrace counts); the ladder default would
    # add per-rung programs.  The ladder has its own tracked baseline in
    # bench_peel_compaction.py.
    prob = Problem.undirected(eps=args.eps, max_passes=args.max_passes,
                              compaction="off")
    report = {
        "n_nodes": args.n,
        "n_edges": m,
        "eps": args.eps,
        "max_passes": args.max_passes,
    }

    # ---- cold vs cached solve -------------------------------------------
    solver = Solver()
    cold_s, _ = _timed(lambda: solver.solve(edges, prob))
    warm = min(_timed(lambda: solver.solve(edges, prob))[0] for _ in range(args.repeats))
    same_shape = min(
        _timed(lambda: solver.solve(other, prob))[0] for _ in range(args.repeats)
    )
    report["solve"] = {
        "cold_compile_s": round(cold_s, 4),
        "cached_same_graph_s": round(warm, 4),
        "cached_same_shape_new_graph_s": round(same_shape, 4),
        "compile_overhead_x": round(cold_s / max(warm, 1e-9), 1),
        "trace_count": solver.trace_count,
        "cache_hits": solver.cache_hits,
        "cache_misses": solver.cache_misses,
    }
    print("solve:", report["solve"])
    assert solver.trace_count == 1, "same-shape solves must not retrace"

    # ---- batched sweep vs sequential ------------------------------------
    eps_grid = [round(0.1 + 0.1 * i, 3) for i in range(args.grid)]
    batch_solver = Solver()
    batch_cold, _ = _timed(
        lambda: batch_solver.solve_batch(
            edges, Problem.undirected(max_passes=args.max_passes), eps=eps_grid
        )
    )
    batch_warm = min(
        _timed(
            lambda: batch_solver.solve_batch(
                edges, Problem.undirected(max_passes=args.max_passes), eps=eps_grid
            )
        )[0]
        for _ in range(args.repeats)
    )

    seq_solver = Solver()
    probs = [
        Problem.undirected(eps=e, max_passes=args.max_passes, compaction="off")
        for e in eps_grid
    ]
    for p in probs:  # warm every per-eps program
        seq_solver.solve(edges, p)

    def run_seq():
        return [seq_solver.solve(edges, p) for p in probs]

    seq_warm = min(_timed(run_seq)[0] for _ in range(args.repeats))
    report["solve_batch"] = {
        "eps_grid": eps_grid,
        "batch_cold_s": round(batch_cold, 4),
        "batch_warm_s": round(batch_warm, 4),
        "sequential_warm_s": round(seq_warm, 4),
        "batch_speedup_x": round(seq_warm / max(batch_warm, 1e-9), 2),
        "batch_trace_count": batch_solver.trace_count,
    }
    print("solve_batch:", report["solve_batch"])

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
