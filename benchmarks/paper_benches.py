"""One benchmark per paper table/figure (Bahmani et al., VLDB'12), at
CPU-tractable scales with the same shapes as the paper's plots.

  table2    §6.2 Table 2  approximation factor rho*/rho~ vs exact, per eps
  fig61     §6.3 Fig 6.1  eps -> (passes, density rel. to eps=0)
  fig62_63  §6.3 Fig 6.2/6.3  per-pass density / |V| / |E| trajectories
  table3    §6.4 Table 3  directed: rho for (eps, delta) grid
  fig64_66  §6.4 Fig 6.4/6.6  directed c-sweep at delta=2
  table4    §6.5 Table 4  sketch-to-exact density ratio vs (eps, b)
  fig67     §6.6 Fig 6.7  distributed per-pass wall time (MapReduce analogue)
  kernels   per-kernel micro-bench (XLA ref path wall time + work stats)
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    Problem,
    c_grid,
    charikar_greedy,
    densest_subgraph_exact,
    solve,
)
from repro.graph import generators as gen


def _peel(edges, eps, track_history=True, **kw):
    """Algorithm 1 on the uncompacted loop (per-pass history kept)."""
    return solve(
        edges,
        Problem.undirected(
            eps=eps, track_history=track_history, compaction="off", **kw
        ),
    )


def _directed_search(edges, eps, delta):
    """Algorithm 3 over the geometric c grid: (result, best c, per-c
    densities, per-c passes)."""
    res = solve(
        edges,
        Problem.directed(c=None, eps=eps, c_delta=delta, compaction="off"),
    )
    ex = res.extras
    return res, ex["best_c"], np.asarray(ex["c_density"]), np.asarray(ex["c_passes"])


def _rows_to_csv(rows: List[Dict[str, Any]]) -> str:
    if not rows:
        return ""
    keys = list(rows[0].keys())
    out = [",".join(keys)]
    for r in rows:
        out.append(",".join(str(r.get(k, "")) for k in keys))
    return "\n".join(out)


# ---------------------------------------------------------------------------
# Table 2: quality of approximation vs exact optimum
# ---------------------------------------------------------------------------

def table2(eps_list=(0.001, 0.1, 1.0)) -> List[Dict[str, Any]]:
    graphs = {
        "as-like": gen.erdos_renyi(n=1500, avg_deg=4.0, seed=1),
        "collab-pl": gen.chung_lu_power_law(n=1500, exponent=2.1, avg_deg=8.0, seed=2),
        "dense-core": gen.planted_dense_subgraph(
            n=1200, avg_deg=4.0, k=60, p_dense=0.5, seed=3
        )[0],
        "ba": gen.barabasi_albert(n=1500, m_attach=5, seed=4),
    }
    rows = []
    for name, edges in graphs.items():
        _, rho_star = densest_subgraph_exact(edges)
        _, rho_greedy = charikar_greedy(edges)
        row = {
            "graph": name,
            "n": edges.n_nodes,
            "m": int(edges.num_real_edges()),
            "rho_star": round(rho_star, 4),
            "charikar_ratio": round(rho_star / max(rho_greedy, 1e-9), 4),
        }
        for eps in eps_list:
            res = _peel(edges, eps, track_history=False)
            ratio = rho_star / max(float(res.best_density), 1e-9)
            row[f"ratio_eps{eps}"] = round(ratio, 4)
            row[f"passes_eps{eps}"] = int(res.passes)
            assert ratio <= 2 * (1 + eps) + 1e-6, (name, eps, ratio)
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# Fig 6.1: eps vs approximation + passes
# ---------------------------------------------------------------------------


def fig61(eps_list=(0.001, 0.01, 0.1, 0.5, 1.0, 2.0, 4.0)) -> List[Dict[str, Any]]:
    edges = gen.chung_lu_power_law(n=200_000, exponent=2.0, avg_deg=12.0, seed=7)
    base = None
    rows = []
    for eps in eps_list:
        t0 = time.time()
        res = _peel(edges, eps, track_history=False)
        jax.block_until_ready(res.best_density)
        rho = float(res.best_density)
        if base is None:
            base = rho
        rows.append(
            {
                "eps": eps,
                "density": round(rho, 3),
                "rel_density": round(rho / base, 4),
                "passes": int(res.passes),
                "wall_s": round(time.time() - t0, 2),
            }
        )
    return rows


# ---------------------------------------------------------------------------
# Fig 6.2 / 6.3: per-pass trajectories
# ---------------------------------------------------------------------------


def fig62_63(eps=0.5) -> List[Dict[str, Any]]:
    edges = gen.chung_lu_power_law(n=100_000, exponent=2.0, avg_deg=10.0, seed=8)
    res = _peel(edges, eps)
    rows = []
    hn = np.asarray(res.history_n)
    hm = np.asarray(res.history_m)
    hr = np.asarray(res.history_rho)
    for t in range(int(res.passes)):
        rows.append(
            {
                "pass": t,
                "nodes": int(hn[t]),
                "edges": int(hm[t]),
                "density": round(float(hr[t]), 3),
            }
        )
    return rows


# ---------------------------------------------------------------------------
# Table 3 + Fig 6.4/6.6: directed
# ---------------------------------------------------------------------------


def _directed_graph():
    return gen.directed_planted(
        n=30_000, avg_deg=8.0, ks=150, kt=60, p_dense=0.4, seed=9
    )[0]


def table3() -> List[Dict[str, Any]]:
    edges = _directed_graph()
    rows = []
    for eps in (0.0, 1.0, 2.0):
        for delta in (2.0, 10.0, 100.0):
            best, best_c, rhos, passes = _directed_search(
                edges, max(eps, 1e-9), delta
            )
            rows.append(
                {
                    "eps": eps,
                    "delta": delta,
                    "rho": round(float(best.best_density), 3),
                    "best_c": round(best_c, 4),
                    "total_passes": int(passes.sum()),
                }
            )
    return rows


def fig64_66(eps=1.0, delta=2.0) -> List[Dict[str, Any]]:
    edges = _directed_graph()
    best, best_c, rhos, passes = _directed_search(edges, eps, delta)
    rows = []
    for c, rho, p in zip(c_grid(edges.n_nodes, delta), rhos, passes):
        rows.append(
            {"c": round(float(c), 4), "rho": round(float(rho), 3), "passes": int(p)}
        )
    return rows


# ---------------------------------------------------------------------------
# Table 4: Count-Sketch quality/memory trade-off
# ---------------------------------------------------------------------------


def table4(t: int = 5) -> List[Dict[str, Any]]:
    edges = gen.chung_lu_power_law(n=97_600, exponent=2.0, avg_deg=16.0, seed=10)
    n = edges.n_nodes
    rows = []
    for eps in (0.0, 0.5, 1.0, 1.5, 2.0):
        # eps=0 row: threshold exactly 2*rho (paper's Table 4 top row);
        # cap passes so the while_loop bound stays sane.
        exact = _peel(
            edges, max(eps, 1e-9), max_passes=256, track_history=False
        )
        row = {"eps": eps, "rho_exact_counts": round(float(exact.best_density), 3)}
        for b in (3000, 4000, 5000):
            sk = solve(
                edges,
                Problem.undirected(
                    eps=max(eps, 1e-9), max_passes=256, track_history=True,
                    backend="sketch", sketch_tables=t, sketch_buckets=b,
                    sketch_seed=11,
                ),
            )
            row[f"ratio_b{b}"] = round(
                float(sk.best_density) / max(float(exact.best_density), 1e-9), 4
            )
            row[f"mem_frac_b{b}"] = round(t * b / n, 3)
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# Fig 6.7: distributed per-pass wall time (the MapReduce analogue)
# ---------------------------------------------------------------------------


def fig67() -> List[Dict[str, Any]]:
    """Per-pass wall time of the edge-sharded shard_map peel over every
    visible device, for growing graph sizes (the Hadoop plot's shape).

    On a CPU host, give the process several devices yourself
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) so the
    collectives are real; one device runs the same program unsharded."""
    from jax.sharding import Mesh

    n_dev = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()).reshape(n_dev), ("data",))
    rows = []
    for n, avg in ((50_000, 8.0), (200_000, 10.0), (500_000, 12.0)):
        edges = gen.chung_lu_power_law(n=n, exponent=2.0, avg_deg=avg, seed=12)
        t0 = time.time()
        res = solve(
            edges,
            Problem.undirected(
                eps=0.5, substrate="mesh", edge_axes=("data",),
                compaction="off",
            ),
            mesh=mesh,
        )
        jax.block_until_ready(res.best_density)
        wall = time.time() - t0
        passes = int(res.passes)
        rows.append(
            {
                "nodes": n,
                "edges": int(edges.num_real_edges()),
                "devices": n_dev,
                "passes": passes,
                "wall_s": round(wall, 2),
                "s_per_pass": round(wall / max(passes, 1), 3),
                "edges_per_s": int(int(edges.num_real_edges()) * passes / wall),
            }
        )
    return rows


# ---------------------------------------------------------------------------
# Kernel micro-benches (wall time of the jitted XLA ref vs pallas-interpret
# correctness cost is meaningless on CPU; we report ref wall time + work)
# ---------------------------------------------------------------------------


def kernels() -> List[Dict[str, Any]]:
    from repro.graph.partition import bucket_edges_by_tile
    from repro.kernels.peel_degree.ref import tiled_degrees_ref

    rows = []
    rng = np.random.default_rng(0)
    for n, e in ((100_000, 800_000),):
        src = rng.integers(0, n, e).astype(np.int32)
        dst = rng.integers(0, n, e).astype(np.int32)
        t0 = time.time()
        tiled = bucket_edges_by_tile(src, dst, n, tile_size=1024, block=512)
        t_shuffle = time.time() - t0
        w = jnp.asarray((tiled.edge_index >= 0).astype(np.float32))
        tl = jnp.asarray(tiled.target_local)
        f = jax.jit(lambda tl, w: tiled_degrees_ref(tl, w, tile_size=1024))
        jax.block_until_ready(f(tl, w))
        t0 = time.time()
        for _ in range(5):
            out = f(tl, w)
        jax.block_until_ready(out)
        rows.append(
            {
                "kernel": "peel_degree(ref-xla)",
                "nodes": n,
                "edge_slots": int(tiled.target_local.size),
                "one_time_shuffle_s": round(t_shuffle, 2),
                "us_per_pass": round((time.time() - t0) / 5 * 1e6, 0),
            }
        )
    return rows


def lemma5(k_values=(4, 5, 6, 7)) -> List[Dict[str, Any]]:
    """Lemma 5 lower-bound instances: the k-block construction forces
    Omega(log n / log log n) passes; measured passes must grow ~k/log k."""
    rows = []
    for k in k_values:
        edges = gen.lemma5_instance(k)
        res = _peel(edges, 0.05, track_history=False)
        rows.append(
            {
                "k": k,
                "n": edges.n_nodes,
                "m": int(edges.num_real_edges()),
                "passes": int(res.passes),
                "k_over_logk": round(k / np.log2(max(k, 2)), 2),
            }
        )
    # passes should be increasing in k (the lower-bound family bites)
    ps = [r["passes"] for r in rows]
    assert all(b >= a for a, b in zip(ps, ps[1:])), ps
    return rows


ALL = {
    "table2": table2,
    "fig61": fig61,
    "fig62_63": fig62_63,
    "table3": table3,
    "fig64_66": fig64_66,
    "table4": table4,
    "fig67": fig67,
    "lemma5": lemma5,
    "kernels": kernels,
}
