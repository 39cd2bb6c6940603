#!/usr/bin/env python3
"""Chip smoke: the densest-subgraph main path, once, on one TPU chip.

    python3 chip_smoke.py                      # one chip, livejournal_md
    python3 chip_smoke.py --mesh               # four chips: mesh ladder vs jit
    JAX_PLATFORMS=cpu python3 chip_smoke.py --allow-cpu --shape flickr_sm  # rehearsal

Every graph is generated from ``--seed`` with the repository's own
generators; nothing is read from disk.  One process runs four phases, each
through the entry points a user calls, and checks each against a reference
written independently of the code under test:

1. whole-graph peel: ``solve(edges, Problem.undirected(eps=0.5,
   backend='exact'))`` on a Chung-Lu graph of the chosen Table 1 shape,
   against a plain numpy float32 Algorithm 1 peel;
2. Pallas degree backend: ``backend='pallas'`` on a 200k-node Chung-Lu
   graph against the exact backend, with proof that the kernel compiled;
3. serving: ``DensestQueryEngine(extraction='local')`` answers a batch of
   seeds on the phase-1 graph; every answer must be ``ok`` and its density
   must match a float64 recount over its node set;
4. turnstile: ``Problem(stream_mode='turnstile')`` absorbs insert batches
   and a 25%-delete churn batch through the Pallas l0-sampler kernel; its
   sketch must be bit-identical to the XLA segment-sum reference, and one
   density query must be answered.

``--mesh`` runs only the four-chip check: the single-program mesh ladder
(``substrate='mesh'``) against ``substrate='jit'`` on device 0.

Each phase prints one line with its numbers, its compile seconds (XLA
backend compiles, persistent-cache hits included) and its wall seconds.
The last line of standard output is, on success only,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check or exception exits 1; a platform other than TPU exits 2
(or, with ``--allow-cpu``, runs the phases and exits 1).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
import types

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.densest_mapreduce import SHAPES  # noqa: E402
from repro.core import Problem, solve  # noqa: E402
from repro.graph.generators import (  # noqa: E402
    chung_lu_power_law,
    planted_dense_subgraph,
)

# Graph sizes of phases 2-4.  Off-TPU the Pallas kernel runs in interpret
# mode, which only a small graph gets through in a rehearsal's time.
_PALLAS_NODES = 200_000
_PALLAS_NODES_INTERPRETED = 5_000
_TURNSTILE_NODES = 100_000
_QUERIES = 32

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HITS = "/jax/compilation_cache/cache_hits"


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


class CompileMeter:
    """Sums XLA backend-compile seconds and persistent-cache hits, read
    from JAX's monitoring events (a hit's duration is its cache load)."""

    def __init__(self):
        self.secs = 0.0
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.secs += secs
            self.count += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HITS:
            self.hits += 1

    def snapshot(self):
        return self.secs, self.count, self.hits


def rel_diff(a: float, b: float) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def host_edges(edges):
    """Real (unpadded) edge endpoints on the host."""
    mask = np.asarray(edges.mask)
    return np.asarray(edges.src)[mask], np.asarray(edges.dst)[mask]


def density64(src, dst, members: np.ndarray, n: int) -> float:
    """float64 density |E(S)| / |S| of an unweighted node set, recounted
    from the edge list."""
    if len(members) == 0:
        return 0.0
    inside = np.zeros(n, bool)
    inside[members] = True
    return float(np.count_nonzero(inside[src] & inside[dst])) / len(members)


def reference_peel(src, dst, n: int, eps: float, max_passes: int):
    """Algorithm 1 (Bahmani et al. §4.1) in plain numpy float32: each pass
    counts induced degrees with ``np.bincount``, records the densest set
    seen, and removes every node with degree <= 2(1+eps)·rho(S) (or equal
    to the minimum degree, so every pass makes progress).  Returns
    ``(passes, best_density, best_set_mask)``."""
    alive = np.ones(n, bool)
    s, d = src, dst
    best_rho = np.float32(-np.inf)
    best = alive.copy()
    scale = np.float32(2.0 * (1.0 + eps))
    passes = 0
    while passes < max_passes and alive.any():
        deg = (
            np.bincount(s, minlength=n) + np.bincount(d, minlength=n)
        ).astype(np.float32)
        rho = np.float32(len(s)) / np.float32(np.count_nonzero(alive))
        if rho > best_rho:
            best_rho, best = rho, alive.copy()
        min_deg = deg[alive].min()
        alive &= ~((deg <= scale * rho) | (deg <= min_deg))
        keep = alive[s] & alive[d]
        s, d = s[keep], d[keep]
        passes += 1
    return passes, float(best_rho), best


def graph_for_shape(shape: str, seed: int):
    dims = SHAPES[shape].params
    return chung_lu_power_law(
        dims["n_nodes"], exponent=2.2, seed=seed, n_edges=dims["n_edges"]
    )


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Phases: each returns a dict of numbers or raises.
# ---------------------------------------------------------------------------


def phase_peel(ctx):
    t0 = time.perf_counter()
    edges = graph_for_shape(ctx.args.shape, ctx.args.seed)
    src, dst = host_edges(edges)
    ctx.graph, ctx.src, ctx.dst = edges, src, dst
    gen_s = time.perf_counter() - t0
    n, m = edges.n_nodes, len(src)

    t0 = time.perf_counter()
    res = solve(edges, Problem.undirected(eps=0.5, backend="exact"))
    rho = float(res.best_density)
    solve_s = time.perf_counter() - t0
    prov = res.provenance
    ladder = res.extras["compaction"]
    best = np.asarray(res.best_alive)

    t0 = time.perf_counter()
    ref_passes, ref_rho, ref_best = reference_peel(
        src, dst, n, 0.5, prov.max_passes
    )
    ref_s = time.perf_counter() - t0
    host_rho = density64(src, dst, np.nonzero(best)[0], n)
    out = {
        "shape": ctx.args.shape,
        "n": n,
        "m": m,
        "cell": f"{prov.policy} x {prov.backend} x {prov.substrate}",
        "compaction": prov.compaction,
        "passes": int(res.passes),
        "ref_passes": ref_passes,
        "rungs": len(ladder["segments"]),
        "host_round_trips": ladder["host_round_trips"],
        "edge_slots_scanned": ladder["edge_slots_scanned"],
        "best_density": rho,
        "ref_density": ref_rho,
        "host64_density": host_rho,
        "best_size": int(res.best_size),
        "same_set_as_ref": bool(np.array_equal(best, ref_best)),
        "gen_s": gen_s,
        "solve_s": solve_s,
        "ref_s": ref_s,
    }
    check(out["passes"] == ref_passes, f"passes {out['passes']} != ref {ref_passes}")
    check(rel_diff(rho, ref_rho) <= 1e-5, f"density {rho} vs ref {ref_rho}")
    check(
        rel_diff(rho, host_rho) <= 1e-6,
        f"reported density {rho} vs float64 recount {host_rho}",
    )
    return out


def phase_pallas(ctx):
    from repro.kernels import resolve_interpret
    from repro.kernels.peel_degree.ops import tiled_degrees, tiling_for_edges

    n = _PALLAS_NODES if on_tpu() else _PALLAS_NODES_INTERPRETED
    t0 = time.perf_counter()
    g = chung_lu_power_law(n, exponent=2.2, avg_deg=8.0, seed=ctx.args.seed)
    src, _ = host_edges(g)
    m = len(src)
    prob = Problem.undirected(eps=0.5, backend="pallas")
    tiled = tiling_for_edges(g, tile_size=prob.tile_size, block=prob.tile_block)
    rung0 = tiling_for_edges(
        g, tile_size=prob.tile_size, block=prob.tile_block, pow2_pad=True
    )
    gen_s = time.perf_counter() - t0

    # Proof the kernel is compiled, not interpreted: the dispatch rule, and
    # a Mosaic custom call in the lowered degree program at rung 0's shape.
    sds = lambda a, dt: jax.ShapeDtypeStruct(a.shape, dt)
    lowered = jax.jit(
        lambda tl, ei, w: tiled_degrees(
            tl, ei, w, tile_size=prob.tile_size, n_nodes=g.n_nodes
        )
    ).lower(
        sds(rung0.target_local, jnp.int32),
        sds(rung0.edge_index, jnp.int32),
        jax.ShapeDtypeStruct((g.n_edges_padded,), jnp.float32),
    )
    interpreted = resolve_interpret(None)
    custom_call = "tpu_custom_call" in lowered.as_text()

    t0 = time.perf_counter()
    res_p = solve(g, prob)
    rho_p = float(res_p.best_density)
    pallas_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_e = solve(g, Problem.undirected(eps=0.5, backend="exact"))
    rho_e = float(res_e.best_density)
    exact_s = time.perf_counter() - t0
    out = {
        "n": g.n_nodes,
        "m": m,
        "cell": f"{res_p.provenance.policy} x {res_p.provenance.backend} x "
        f"{res_p.provenance.substrate}",
        "tiling": list(tiled.target_local.shape),
        "tile_slots": int(tiled.target_local.size),
        "tile_slots_over_2m": tiled.target_local.size / (2 * m),
        "rung0_tiling": list(rung0.target_local.shape),
        "interpreted": interpreted,
        "tpu_custom_call": custom_call,
        "passes": int(res_p.passes),
        "exact_passes": int(res_e.passes),
        "best_density": rho_p,
        "exact_density": rho_e,
        "rungs": len(res_p.extras["compaction"]["segments"]),
        "gen_s": gen_s,
        "pallas_solve_s": pallas_s,
        "exact_solve_s": exact_s,
    }
    if on_tpu():
        check(not interpreted, "Pallas kernels would run interpreted")
        check(custom_call, "no tpu_custom_call in the lowered degree program")
    check(out["passes"] == out["exact_passes"], "pallas/exact pass counts differ")
    check(rel_diff(rho_p, rho_e) <= 1e-5, f"pallas {rho_p} vs exact {rho_e}")
    check(
        np.array_equal(np.asarray(res_p.best_alive), np.asarray(res_e.best_alive)),
        "pallas and exact best sets differ",
    )
    return out


def phase_serving(ctx):
    from repro.serve.densest import DensestQueryEngine

    check(ctx.graph is not None, "phase 1 built no graph")
    src, dst, n = ctx.src, ctx.dst, ctx.graph.n_nodes
    t0 = time.perf_counter()
    eng = DensestQueryEngine(
        ctx.graph,
        Problem.undirected(eps=0.5, substrate="local"),
        extraction="local",
        max_batch=_QUERIES,
    )
    build_s = time.perf_counter() - t0
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    rng = np.random.default_rng(ctx.args.seed + 1)
    seeds = rng.choice(np.nonzero(deg)[0], size=_QUERIES, replace=False)

    t0 = time.perf_counter()
    results = eng.query_many(seeds.tolist())
    query_s = time.perf_counter() - t0
    statuses = sorted({r.status for r in results})
    worst = 0.0
    for r in results:
        check(
            r.status == "ok" and r.fallback is None,
            f"seed {r.seed}: status={r.status} fallback={r.fallback} "
            f"error={r.error}",
        )
        host = density64(src, dst, r.nodes, n)
        err = rel_diff(r.density, host) if host else abs(r.density)
        worst = max(worst, err)
        check(err <= 1e-6, f"seed {r.seed}: density {r.density} vs float64 recount {host}")
        check(
            r.seed_in_set == bool(np.isin(r.seed, r.nodes)),
            f"seed {r.seed}: seed_in_set={r.seed_in_set} is not truthful",
        )
    stats = eng.stats()
    return {
        "queries": len(results),
        "statuses": statuses,
        "batches": stats["batches_flushed"],
        "lanes_solved": stats["lanes_solved"],
        "buckets": len(eng.bucket_histogram),
        "mean_set_size": float(np.mean([r.size for r in results])),
        "seed_in_set": int(sum(r.seed_in_set for r in results)),
        "max_density_rel_err": worst,
        "engine_build_s": build_s,
        "query_many_s": query_s,
    }


def phase_turnstile(ctx):
    from repro.core.turnstile import TurnstileSketch
    from repro.graph.edgelist import apply_updates, from_numpy
    from repro.kernels import resolve_interpret
    from repro.serve.turnstile import TurnstileDensityService

    n, batch, tau, eps = _TURNSTILE_NODES, 1 << 16, 1 << 14, 0.3
    t0 = time.perf_counter()
    g, _ = planted_dense_subgraph(n, 8.0, 300, 0.4, seed=ctx.args.seed)
    src, dst = host_edges(g)
    m = len(src)
    rng = np.random.default_rng(ctx.args.seed + 2)
    del_idx = rng.choice(m, size=m // 4, replace=False)
    deletes = np.stack([src[del_idx], dst[del_idx]], axis=1)
    live, _ = apply_updates(from_numpy(src, dst, n), deletes=deletes)
    gen_s = time.perf_counter() - t0

    prob = Problem.undirected(eps=eps, stream_mode="turnstile", sample_edges=tau)
    svc = TurnstileDensityService(n, prob)
    ref = TurnstileSketch(n, tau, seed=prob.sketch_seed, use_pallas=False)
    t0 = time.perf_counter()
    for lo in range(0, m, batch):
        ins = (src[lo:lo + batch], dst[lo:lo + batch])
        svc.apply(insert_edges=ins)
        ref.apply(insert_edges=ins)
    svc.apply(delete_edges=deletes)
    ref.apply(delete_edges=deletes)
    tables = np.asarray(svc.driver.sketch.tables)
    update_s = time.perf_counter() - t0
    ref_tables = np.asarray(ref.tables)

    t0 = time.perf_counter()
    rho = svc.density()
    query_s = time.perf_counter() - t0
    info = svc.result().extras["turnstile"]
    exact = float(
        solve(live, Problem.undirected(eps=eps, compaction="off")).best_density
    )
    envelope = (1 + eps) * (2 + 2 * eps)
    stats = svc.stats()
    out = {
        "n": n,
        "m_inserted": m,
        "m_deleted": len(deletes),
        "batches": stats["batches_applied"],
        "pallas_sketch": svc.driver.sketch._use_pallas,
        "interpreted": resolve_interpret(None),
        "tables_bit_identical": bool(np.array_equal(tables, ref_tables)),
        "sample_level": info["level"],
        "sample_edges": info["sample_edges_recovered"],
        "density": rho,
        "exact_peel_density": exact,
        "queries_failed": stats["queries_failed"],
        "stale_served": stats["stale_results_served"],
        "gen_s": gen_s,
        "update_s": update_s,
        "query_s": query_s,
    }
    if on_tpu():
        check(out["pallas_sketch"], "the sketch did not select the Pallas kernel")
        check(not out["interpreted"], "Pallas kernels would run interpreted")
    check(out["tables_bit_identical"], "Pallas sketch != XLA segment-sum reference")
    check(
        stats["queries_failed"] == 0 and stats["stale_results_served"] == 0,
        f"turnstile query not ok: {stats['last_error']}",
    )
    check(np.isfinite(rho) and rho > 0, f"density {rho}")
    check(
        exact / envelope <= rho <= exact * envelope,
        f"density {rho} outside the (1+eps)(2+2eps) envelope of {exact}",
    )
    return out


def phase_mesh(ctx):
    from jax.sharding import Mesh

    from repro.core.mapreduce import shard_edges

    devs = jax.devices()
    check(len(devs) >= 4, f"--mesh needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.asarray(devs[:4]), ("data",))
    t0 = time.perf_counter()
    edges = graph_for_shape(ctx.args.shape, ctx.args.seed)
    src, _ = host_edges(edges)
    gen_s = time.perf_counter() - t0
    placement = {
        str(s.device.id): list(s.data.shape)
        for s in shard_edges(edges, mesh, ("data",)).src.addressable_shards
    }

    t0 = time.perf_counter()
    res_m = solve(
        edges,
        Problem.undirected(eps=0.5, backend="exact", substrate="mesh"),
        mesh=mesh,
    )
    rho_m = float(res_m.best_density)
    mesh_s = time.perf_counter() - t0
    peak = {
        str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in devs[:4]
    }
    t0 = time.perf_counter()
    with jax.default_device(devs[0]):
        res_j = solve(
            edges, Problem.undirected(eps=0.5, backend="exact", substrate="jit")
        )
        rho_j = float(res_j.best_density)
    jit_s = time.perf_counter() - t0
    ladder = res_m.extras["compaction"]
    same_set = np.array_equal(
        np.asarray(res_m.best_alive), np.asarray(res_j.best_alive)
    )
    out = {
        "shape": ctx.args.shape,
        "n": edges.n_nodes,
        "m": len(src),
        "devices": [d.id for d in devs[:4]],
        "shard_placement": placement,
        "peak_bytes_in_use": peak,
        "single_program": ladder["single_program"],
        "host_round_trips": ladder["host_round_trips"],
        "rungs": len(ladder["segments"]),
        "passes": int(res_m.passes),
        "jit_passes": int(res_j.passes),
        "best_density": rho_m,
        "jit_density": rho_j,
        "bit_identical": bool(rho_m == rho_j and same_set),
        "gen_s": gen_s,
        "mesh_solve_s": mesh_s,
        "jit_solve_s": jit_s,
    }
    check(len(placement) == 4, f"edge shards landed on {sorted(placement)}")
    check(out["passes"] == out["jit_passes"], "mesh/jit pass counts differ")
    check(rel_diff(rho_m, rho_j) <= 1e-6, f"mesh {rho_m} vs jit {rho_j}")
    return out


def run_phase(name, fn, ctx, meter) -> bool:
    c0, n0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    try:
        info, ok = fn(ctx), True
    except Exception as e:  # noqa: BLE001 — every failure is reported, then fails the run
        traceback.print_exc()
        info, ok = {"error": f"{type(e).__name__}: {e}"}, False
    c1, n1, h1 = meter.snapshot()
    line = dict(info)
    line.update(
        ok=ok,
        compile_s=c1 - c0,
        compiles=n1 - n0,
        compile_cache_hits=h1 - h0,
        wall_s=time.perf_counter() - t0,
    )
    print(f"[{name}] {json.dumps(line, default=str)}", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="livejournal_md",
                    choices=["flickr_sm", "livejournal_md"],
                    help="Table 1 shape of the phase-1/3 (and --mesh) graph")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="run only the four-chip mesh ladder vs jit check")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse the phases off-TPU (never reports ok)")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(f"[device] {json.dumps(device)}", flush=True)
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    print(f"[compile cache] {cache_dir}", flush=True)
    meter = CompileMeter()
    # Phase 1 leaves its graph here for phase 3.
    ctx = types.SimpleNamespace(args=args, graph=None, src=None, dst=None)
    if args.mesh:
        phases = [("mesh ladder vs jit", phase_mesh)]
    else:
        phases = [
            ("1 peel", phase_peel),
            ("2 pallas", phase_pallas),
            ("3 serving", phase_serving),
            ("4 turnstile", phase_turnstile),
        ]
    ok = True
    for name, fn in phases:
        ok = run_phase(name, fn, ctx, meter) and ok
    if not ok or dev.platform != "tpu":
        print("chip smoke FAILED" if not ok else "chip smoke: not a TPU run",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
