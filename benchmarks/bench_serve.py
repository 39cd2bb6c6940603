"""Serving-tier benchmark: seed-batched query engine + persistent cache.

    PYTHONPATH=src python -m benchmarks.bench_serve [--n 30000] [--queries 64]

Measures the three serving claims (ISSUE acceptance criteria):

  * **batched vs sequential throughput** — the same per-seed query stream
    answered three ways:
      - ``sequential_exact``: the pre-engine pattern — extract the ego-net,
        build an exact-shape EdgeList, call ``solve()``.  Every distinct
        (n_ego, m_ego) is a new program shape, so the stream pays a
        compile per distinct shape (THE failure mode the engine's pow2
        bucketing removes);
      - ``sequential_bucketed``: ablation — the engine's bucketed
        extraction with warm programs, but one ``solve()`` per query
        (bucketing without batching);
      - ``batched``: the engine (bucketing + coalesced ``solve_batch``).
    Reports p50/p99 latency and qps for each; the headline
    ``batched_vs_sequential_qps_x`` compares the engine against
    ``sequential_exact``.
  * **bit-identity** — every batched answer is checked against a
    standalone ``solve()`` of the same extracted buffer before any number
    is reported.
  * **cold-start** — first-query latency in a FRESH subprocess, uncached
    (traces + XLA-compiles) vs with a warm ``cache_dir``
    (``core/progcache.py`` disk tier; the child asserts it compiled
    NOTHING), plus the populate cost.  This is the replica-restart /
    autoscale path the persistent cache exists for.
  * **local-vs-BFS scaling sweep** — per-query work (nodes touched, p50
    latency) of ``extraction='local'`` (Andersen pruned-frontier,
    core/local.py) stays flat across a 30k->300k node sweep while the
    untruncated radius-2 BFS ego-net grows with the graph
    (``--skip-sweep`` for smoke runs).

Writes experiments/bench/BENCH_serve.json (committed baseline).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import Problem, Solver
from repro.graph.generators import chung_lu_power_law
from repro.serve.densest import DensestQueryEngine

# Runs in the parent (measurement) and in each subprocess (cold-start
# protocol): build the same graph/problem/engine from the same argv knobs.
_CHILD = """
import json, time
import numpy as np
from repro.core import Problem
from repro.graph.generators import chung_lu_power_law
from repro.serve.densest import DensestQueryEngine

cfg = json.loads({cfg!r})
# Program acquisition is what this child measures: JAX's own persistent
# compilation cache stays off, so "uncached" really compiles.
import jax
jax.config.update("jax_enable_compilation_cache", False)
edges = chung_lu_power_law(cfg["n"], exponent=2.0, avg_deg=cfg["avg_deg"], seed=0)
prob = Problem.undirected(eps=cfg["eps"], max_passes=cfg["max_passes"],
                          compaction="off")
eng = DensestQueryEngine(
    edges, prob, cache_dir=cfg["cache_dir"], radius=cfg["radius"],
    max_ego_nodes=cfg["max_ego_nodes"], max_wait_ms=0.0,
)
# Backend init happens at replica startup either way; keep it out of the
# first-query measurement so cold vs warm isolates program ACQUISITION
# (trace + XLA compile vs disk load).
import jax.numpy as jnp
jnp.zeros(4).block_until_ready()
t0 = time.perf_counter()
r = eng.query(cfg["seed"])
first = time.perf_counter() - t0
if cfg["expect_warm"]:
    assert eng.solver.trace_count == 0, (
        "warm-cache child traced %d programs" % eng.solver.trace_count)
    assert eng.solver.disk_hits >= 1, "warm-cache child never hit disk"
print("BENCH_CHILD " + json.dumps({{
    "first_query_s": first,
    "density": r.density,
    "trace_count": eng.solver.trace_count,
    "disk_hits": eng.solver.disk_hits,
    "disk_misses": eng.solver.disk_misses,
}}))
"""


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q))


def _lat_stats(lat_s, wall_s, n):
    return {
        "p50_ms": round(_pct(lat_s, 50) * 1e3, 3),
        "p99_ms": round(_pct(lat_s, 99) * 1e3, 3),
        "wall_s": round(wall_s, 4),
        "qps": round(n / wall_s, 2),
    }


def _run_child(cfg):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(cfg=json.dumps(cfg))],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    if out.returncode != 0:
        raise RuntimeError(f"bench child failed:\n{out.stderr[-3000:]}")
    line = [l for l in out.stdout.splitlines() if l.startswith("BENCH_CHILD ")]
    return json.loads(line[-1][len("BENCH_CHILD "):])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=30_000)
    ap.add_argument("--avg-deg", type=float, default=8.0)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--radius", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-ego-nodes", type=int, default=128)
    ap.add_argument("--eps", type=float, default=0.5)
    ap.add_argument("--max-passes", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--cache-dir", default=os.path.join(
                        "experiments", "serve_cache"),
                    help="Solver disk cache for the cold-start protocol "
                         "(a fixed path: a second run's populate child "
                         "finds the first run's entries)")
    ap.add_argument("--skip-cold-start", action="store_true",
                    help="skip the subprocess cold-start measurements")
    ap.add_argument("--skip-sweep", action="store_true",
                    help="skip the local-vs-BFS extraction scaling sweep")
    ap.add_argument("--sweep-sizes", default="30000,95000,300000",
                    help="comma-separated graph sizes for the scaling sweep")
    ap.add_argument("--sweep-queries", type=int, default=32)
    ap.add_argument("--skip-naive", action="store_true",
                    help="skip the compile-per-shape sequential_exact "
                         "baseline (it dominates wall time)")
    ap.add_argument("--out", default=os.path.join(
        "experiments", "bench", "BENCH_serve.json"))
    args = ap.parse_args(argv)

    seeds = np.random.default_rng(7).integers(0, args.n, args.queries).tolist()

    # ---- cold start: fresh subprocess, uncached vs warm disk cache ------
    # Runs FIRST, before this process touches jax: each child must be the
    # only process holding the accelerator while it runs.
    cold_start = None
    if not args.skip_cold_start:
        base = {
            "n": args.n, "avg_deg": args.avg_deg, "eps": args.eps,
            "max_passes": args.max_passes, "radius": args.radius,
            "max_ego_nodes": args.max_ego_nodes, "seed": seeds[0],
        }
        cold = _run_child(dict(base, cache_dir=None, expect_warm=False))
        t0 = time.perf_counter()
        populate = _run_child(
            dict(base, cache_dir=args.cache_dir, expect_warm=False)
        )
        populate_wall = time.perf_counter() - t0
        warm = _run_child(
            dict(base, cache_dir=args.cache_dir, expect_warm=True)
        )
        assert warm["density"] == cold["density"], "cold/warm mismatch"
        cold_start = {
            "uncached_first_query_s": round(cold["first_query_s"], 4),
            "uncached_programs_compiled": cold["trace_count"],
            "populate_first_query_s": round(populate["first_query_s"], 4),
            "populate_child_wall_s": round(populate_wall, 4),
            "warm_disk_first_query_s": round(warm["first_query_s"], 4),
            "warm_disk_programs_compiled": warm["trace_count"],
            "warm_disk_hits": warm["disk_hits"],
            "cold_start_speedup_x": round(
                cold["first_query_s"] / max(warm["first_query_s"], 1e-9), 1
            ),
        }
        print("cold_start:", cold_start)

    enable_compile_cache()

    edges = chung_lu_power_law(
        args.n, exponent=2.0, avg_deg=args.avg_deg, seed=0
    )
    # compaction pinned off: solve_batch's stacked-lane path requires it,
    # and the sequential baseline must run the IDENTICAL program family.
    prob = Problem.undirected(
        eps=args.eps, max_passes=args.max_passes, compaction="off"
    )
    def fresh_engine(**kw):
        return DensestQueryEngine(
            edges, prob, radius=args.radius, max_batch=args.max_batch,
            max_ego_nodes=args.max_ego_nodes, max_wait_ms=0.0, **kw
        )

    report = {
        "config": {
            "n_nodes": args.n,
            "n_edges": int(edges.num_real_edges()),
            "queries": args.queries,
            "radius": args.radius,
            "max_batch": args.max_batch,
            "max_ego_nodes": args.max_ego_nodes,
            "eps": args.eps,
            "max_passes": args.max_passes,
        }
    }
    if cold_start is not None:
        report["cold_start"] = cold_start

    # ---- batched engine (the serving path) ------------------------------
    eng = fresh_engine()
    eng.query_many(seeds)  # warm every bucket program once
    best = None
    for _ in range(args.repeats):
        t0 = time.perf_counter()
        results = eng.query_many(seeds)
        wall = time.perf_counter() - t0
        if best is None or wall < best[0]:
            best = (wall, results)
    wall, results = best
    report["batched"] = _lat_stats(
        [r.latency_s for r in results], wall, args.queries
    )
    report["batched"].update(
        distinct_buckets=len(eng.bucket_histogram),
        lanes_solved=eng.lanes_solved,
        pad_lanes=eng.pad_lanes,
        programs_compiled=eng.solver.trace_count,
    )
    print("batched:", report["batched"])

    # ---- bit-identity gate ----------------------------------------------
    check = Solver()
    for r in results:
        padded, nodes = eng.extract(r.seed)
        ref = check.solve(padded, prob)
        assert float(ref.best_density) == r.density, (r.seed, r.density)
        ba = np.nonzero(np.asarray(ref.best_alive))[0]
        assert np.array_equal(nodes[ba[ba < len(nodes)]], r.nodes), r.seed
    report["bit_identical_to_solve"] = True
    print(f"bit-identity: {len(results)} answers == sequential solve()")

    # ---- sequential_exact: the pre-engine pattern -----------------------
    # Extract the ego-net, build an EXACT-shape EdgeList, call solve().
    # Distinct (n_ego, m_ego) pairs are distinct program shapes, so the
    # stream compiles per shape — the compile storm pow2 bucketing removes.
    if not args.skip_naive:
        from repro.graph.edgelist import EdgeList

        def exact_subgraph(seed):
            padded, nodes = eng.extract(seed)
            m = max(int(np.asarray(padded.mask).sum()), 1)
            return EdgeList(
                src=np.asarray(padded.src)[:m],
                dst=np.asarray(padded.dst)[:m],
                weight=np.asarray(padded.weight)[:m],
                mask=np.asarray(padded.mask)[:m],
                n_nodes=max(len(nodes), 1),
            )

        naive = Solver()
        lat = []
        t0 = time.perf_counter()
        for s in seeds:
            q0 = time.perf_counter()
            out = naive.solve(exact_subgraph(s), prob)
            float(out.best_density)  # block
            lat.append(time.perf_counter() - q0)
        wall = time.perf_counter() - t0
        report["sequential_exact"] = _lat_stats(lat, wall, args.queries)
        report["sequential_exact"]["programs_compiled"] = naive.trace_count
        print("sequential_exact:", report["sequential_exact"])

    # ---- sequential_bucketed: bucketing without batching (ablation) -----
    seq = Solver()
    for s in seeds:  # warm every per-bucket program once
        seq.solve(eng.extract(s)[0], prob)
    best = None
    for _ in range(args.repeats):
        lat = []
        t0 = time.perf_counter()
        for s in seeds:
            q0 = time.perf_counter()
            out = seq.solve(eng.extract(s)[0], prob)
            float(out.best_density)  # block
            lat.append(time.perf_counter() - q0)
        wall = time.perf_counter() - t0
        if best is None or wall < best[0]:
            best = (wall, lat)
    wall, lat = best
    report["sequential_bucketed"] = _lat_stats(lat, wall, args.queries)
    report["sequential_bucketed"]["programs_compiled"] = seq.trace_count
    print("sequential_bucketed:", report["sequential_bucketed"])

    if "sequential_exact" in report:
        ratio = report["batched"]["qps"] / report["sequential_exact"]["qps"]
        report["batched_vs_sequential_qps_x"] = round(ratio, 2)
        print(f"batched vs sequential(exact) qps: {ratio:.2f}x")
    ab = report["batched"]["qps"] / report["sequential_bucketed"]["qps"]
    report["batched_vs_bucketed_qps_x"] = round(ab, 2)
    print(f"batched vs sequential(bucketed, warm) qps: {ab:.2f}x")

    # ---- local-vs-BFS extraction scaling sweep --------------------------
    # THE substrate='local' claim (ISSUE 10): per-query work of the
    # Andersen extraction is governed by the budget, not by n, so nodes
    # touched and p50 latency stay FLAT across a 10x graph sweep while the
    # radius-2 BFS ego-net (untruncated, the honest comparison) grows with
    # the graph.  Both modes answer through the identical engine surface.
    if not args.skip_sweep:
        sizes = [int(s) for s in args.sweep_sizes.split(",")]
        sweep = {
            "sizes": sizes,
            "queries": args.sweep_queries,
            "bfs_radius": 2,
            "local_budget": None,  # engine default (constants.LOCAL_BUDGET)
            "bfs": [],
            "local": [],
        }
        for n in sizes:
            g = chung_lu_power_law(
                n, exponent=2.0, avg_deg=args.avg_deg, seed=1
            )
            ss = np.random.default_rng(11).integers(
                0, n, args.sweep_queries
            ).tolist()
            for mode in ("bfs", "local"):
                kw = (
                    {"radius": 2, "max_ego_nodes": None}
                    if mode == "bfs"
                    else {"extraction": "local"}
                )
                e = DensestQueryEngine(
                    g, prob, max_batch=args.max_batch, max_wait_ms=0.0, **kw
                )
                e.query_many(ss)  # warm every bucket program once
                t0 = time.perf_counter()
                rs = e.query_many(ss)
                wall = time.perf_counter() - t0
                point = {
                    "n": n,
                    "mean_extracted_nodes": round(
                        float(np.mean([r.n_ego for r in rs])), 1
                    ),
                    "p50_ms": round(
                        _pct([r.latency_s for r in rs], 50) * 1e3, 3
                    ),
                    "qps": round(len(ss) / wall, 2),
                }
                if mode == "local":
                    sweep["local_budget"] = e.local_budget
                    # counters span warm + measured passes: per-query mean.
                    point["mean_nodes_touched"] = round(
                        e.local_nodes_touched / (2 * len(ss)), 1
                    )
                    point["mean_edges_scanned"] = round(
                        e.local_edges_scanned / (2 * len(ss)), 1
                    )
                sweep[mode].append(point)
                print(f"sweep n={n} {mode}: {point}")
        first, last = sweep["local"][0], sweep["local"][-1]
        sweep["local_work_growth_x"] = round(
            last["mean_nodes_touched"] / max(first["mean_nodes_touched"], 1e-9),
            2,
        )
        fb, lb = sweep["bfs"][0], sweep["bfs"][-1]
        sweep["bfs_work_growth_x"] = round(
            lb["mean_extracted_nodes"]
            / max(fb["mean_extracted_nodes"], 1e-9),
            2,
        )
        report["local_vs_bfs_sweep"] = sweep
        print(
            "sweep work growth over "
            f"{sizes[0]}->{sizes[-1]}: local "
            f"{sweep['local_work_growth_x']}x, "
            f"bfs {sweep['bfs_work_growth_x']}x"
        )

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
