"""Resilience-layer benchmark: fault-free overhead + fault-storm behavior.

    PYTHONPATH=src python -m benchmarks.bench_resilience [--n 30000] [--queries 64]

Measures the PR's two acceptance numbers (ISSUE 8):

  * **fault-free overhead** — the bench_serve query stream answered by a
    plain engine vs an engine with a full ResilienceConfig (deadlines,
    retry budget, breaker, shedding, every degrade rung enabled) and NO
    FaultPlan installed.  Target: < 2% wall-clock overhead.  Also reports
    the raw cost of an uninstalled ``faults.fire`` hook (ns/call).
  * **fault storm** — the same stream under a seeded FaultPlan that fails
    a fraction of all ``serve.solve`` dispatches (primary solves, retries
    AND fallback solves alike).  Reports the outcome histogram, p50/p99,
    and the ``answered_fraction`` (status ok or degraded).  Target:
    >= 99% answered with ZERO fabricated results — every answer is
    verified against an independent solve (ok: same bucket program;
    degraded radius:r — a real solve of the smaller ego-net; last_good —
    the previously verified healthy answer).

Writes experiments/bench/BENCH_resilience.json (committed baseline).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro import faults
from repro.core import Problem, Solver
from repro.faults import FaultPlan
from repro.graph.generators import chung_lu_power_law
from repro.serve.densest import DensestQueryEngine
from repro.serve.resilience import ResilienceConfig


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q))


def _lat_stats(lat_s, wall_s, n):
    return {
        "p50_ms": round(_pct(lat_s, 50) * 1e3, 3),
        "p99_ms": round(_pct(lat_s, 99) * 1e3, 3),
        "wall_s": round(wall_s, 4),
        "qps": round(n / wall_s, 2),
    }


def _best_wall(engine, seeds, repeats):
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        results = engine.query_many(seeds)
        wall = time.perf_counter() - t0
        if best is None or wall < best[0]:
            best = (wall, results)
    return best


def _members_of(res, nodes):
    alive = np.nonzero(np.asarray(res.best_alive))[0]
    return nodes[alive[alive < len(nodes)]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=30_000)
    ap.add_argument("--avg-deg", type=float, default=8.0)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--radius", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-ego-nodes", type=int, default=128)
    ap.add_argument("--eps", type=float, default=0.5)
    ap.add_argument("--max-passes", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--storm-prob", type=float, default=0.35)
    ap.add_argument("--storm-seed", type=int, default=1202)
    ap.add_argument("--out", default=os.path.join(
        "experiments", "bench", "BENCH_resilience.json"))
    args = ap.parse_args(argv)
    enable_compile_cache()

    edges = chung_lu_power_law(
        args.n, exponent=2.0, avg_deg=args.avg_deg, seed=0
    )
    prob = Problem.undirected(
        eps=args.eps, max_passes=args.max_passes, compaction="off"
    )
    seeds = np.random.default_rng(7).integers(0, args.n, args.queries).tolist()
    cfg = ResilienceConfig(
        deadline_ms=250.0,
        max_retries=2,
        backoff_base_ms=0.5,
        breaker_threshold=8,
        breaker_cooldown_s=5.0,
        max_queue=4096,
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
            "resilience": {
                "deadline_ms": cfg.deadline_ms,
                "max_retries": cfg.max_retries,
                "breaker_threshold": cfg.breaker_threshold,
                "max_queue": cfg.max_queue,
            },
        }
    }

    # ---- raw hook cost: an uninstalled fire() is one global read --------
    assert faults.installed() is None
    reps = 1_000_000
    t0 = time.perf_counter()
    for _ in range(reps):
        faults.fire("bench.site", key=0)
    per_call_ns = (time.perf_counter() - t0) / reps * 1e9
    report["uninstalled_fire_ns_per_call"] = round(per_call_ns, 1)
    print(f"uninstalled fire(): {per_call_ns:.0f} ns/call")

    # ---- fault-free overhead: plain vs resilience-enabled ---------------
    plain = fresh_engine()
    resilient = fresh_engine(resilience=cfg)
    plain.query_many(seeds)  # warm every bucket program once
    resilient.query_many(seeds)
    wall_p, res_p = _best_wall(plain, seeds, args.repeats)
    wall_r, res_r = _best_wall(resilient, seeds, args.repeats)
    report["fault_free_plain"] = _lat_stats(
        [r.latency_s for r in res_p], wall_p, args.queries
    )
    report["fault_free_resilient"] = _lat_stats(
        [r.latency_s for r in res_r], wall_r, args.queries
    )
    overhead = (wall_r - wall_p) / wall_p * 100.0
    report["fault_free_overhead_pct"] = round(overhead, 2)
    print("fault_free plain:    ", report["fault_free_plain"])
    print("fault_free resilient:", report["fault_free_resilient"])
    print(f"fault-free overhead: {overhead:+.2f}%")

    # Bit-identity across the two engines (the zero-cost contract).
    for a, b in zip(res_p, res_r):
        assert a.density == b.density and b.status == "ok", a.seed
        assert np.array_equal(a.nodes, b.nodes), a.seed
    report["fault_free_bit_identical"] = True

    # ---- fault storm ----------------------------------------------------
    # Healthy reference answers (also primes the storm engine's last-good
    # cache) + reference solves for degraded-answer verification.
    storm_eng = fresh_engine(resilience=cfg)
    healthy = {r.seed: r for r in storm_eng.query_many(seeds)}
    check = Solver()

    plan = FaultPlan(seed=args.storm_seed).fail_prob(
        "serve.solve", args.storm_prob
    )
    with faults.active(plan):
        t0 = time.perf_counter()
        storm = storm_eng.query_many(seeds)
        storm_wall = time.perf_counter() - t0

    outcomes = {}
    fabricated = 0
    answered = 0
    for r in storm:
        key = r.fallback if r.status == "degraded" else r.status
        key = key.split(":")[0] if key and key.startswith("radius") else key
        outcomes[key] = outcomes.get(key, 0) + 1
        if r.answered:
            answered += 1
        # Verify NOTHING was fabricated: every answer must re-derive from
        # an independent computation of real data.
        if r.status == "ok":
            padded, nodes = storm_eng.extract(r.seed, args.radius)
            ref = check.solve(padded, prob)
            if not (
                float(ref.best_density) == r.density
                and np.array_equal(_members_of(ref, nodes), r.nodes)
            ):
                fabricated += 1
        elif r.status == "degraded" and r.fallback.startswith("radius:"):
            rr = int(r.fallback.split(":")[1])
            padded, nodes = storm_eng.extract(r.seed, rr)
            ref = check.solve(padded, prob)
            if not (
                float(ref.best_density) == r.density
                and np.array_equal(_members_of(ref, nodes), r.nodes)
            ):
                fabricated += 1
        elif r.status == "degraded" and r.fallback == "last_good":
            h = healthy[r.seed]
            if not (
                h.density == r.density and np.array_equal(h.nodes, r.nodes)
            ):
                fabricated += 1

    frac = answered / len(storm)
    report["fault_storm"] = {
        "storm_seed": args.storm_seed,
        "fail_prob": args.storm_prob,
        "injected_failures": plan.failures_at("serve.solve"),
        "solve_hits": plan.hits_at("serve.solve"),
        "outcomes": outcomes,
        "answered_fraction": round(frac, 4),
        "fabricated_results": fabricated,
        "solve_retries": storm_eng.solve_retries,
        "deadline_stops": storm_eng.deadline_stops,
        "breaker_open_skips": storm_eng.breaker_open_skips,
        "latency": _lat_stats(
            [r.latency_s for r in storm], storm_wall, len(storm)
        ),
    }
    print("fault_storm:", json.dumps(report["fault_storm"], indent=2))
    assert fabricated == 0, "a storm answer failed independent verification"

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
