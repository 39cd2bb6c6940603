"""Degree-backend benchmark for the PeelEngine: exact vs Count-Sketch vs
Pallas tiled, same policy and graph — the perf baseline future PRs compare
against.  Run with::

    PYTHONPATH=src python -m benchmarks.bench_engine [--n 200000] [--avg-deg 10]

Writes experiments/bench/engine_backends.csv.
"""

from __future__ import annotations

import argparse
import os
import time

import jax

from repro.compile_cache import enable_compile_cache
from repro.core.countsketch import SketchBackend, make_sketch_params
from repro.core.engine import ExactBackend, UndirectedThreshold, run_peel
from repro.graph.generators import chung_lu_power_law


def _time(fn, *args, repeats: int = 3):
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--avg-deg", type=float, default=10.0)
    ap.add_argument("--eps", type=float, default=0.5)
    ap.add_argument("--max-passes", type=int, default=64)
    ap.add_argument("--sketch-b", type=int, default=1 << 15)
    ap.add_argument("--tile-size", type=int, default=2048)
    args = ap.parse_args(argv)
    enable_compile_cache()

    edges = chung_lu_power_law(args.n, exponent=2.0, avg_deg=args.avg_deg, seed=0)
    m = int(edges.num_real_edges())
    policy = UndirectedThreshold(args.eps)
    mp = args.max_passes

    backends = {"exact": ExactBackend()}
    backends["sketch"] = SketchBackend(
        make_sketch_params(t=5, b=args.sketch_b, seed=1)
    )
    try:
        from repro.kernels.peel_degree.ops import (
            degree_backend_from_tiling,
            tiling_for_edges,
        )

        backends["pallas"] = degree_backend_from_tiling(
            tiling_for_edges(edges, tile_size=args.tile_size)
        )
    except Exception as e:  # kernel path unavailable on this platform
        print(f"pallas backend skipped: {type(e).__name__}: {e}")

    rows = []
    ref_rho = None
    for name, backend in backends.items():
        fn = jax.jit(lambda e, b=backend: run_peel(e, policy, b, mp))
        wall, res = _time(fn, edges)
        passes = int(res.passes)
        rho = float(res.best_density)
        if name == "exact":
            ref_rho = rho
        rows.append(
            {
                "backend": name,
                "nodes": args.n,
                "edges": m,
                "passes": passes,
                "wall_s": round(wall, 4),
                "s_per_pass": round(wall / max(passes, 1), 5),
                "edges_per_s": int(m * passes / wall) if wall > 0 else 0,
                "rho": round(rho, 4),
                "rho_vs_exact": round(rho / ref_rho, 4) if ref_rho else 1.0,
            }
        )
        print(rows[-1])

    out_dir = os.path.join("experiments", "bench")
    os.makedirs(out_dir, exist_ok=True)
    keys = list(rows[0])
    csv = "\n".join(
        [",".join(keys)] + [",".join(str(r[k]) for k in keys) for r in rows]
    )
    path = os.path.join(out_dir, "engine_backends.csv")
    with open(path, "w") as f:
        f.write(csv + "\n")
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
