"""Compaction-runtime benchmark: passes x edge-slots-scanned and wall-clock
for ``compaction in (off, twophase, geometric)`` on power-law graphs, plus
the MESH substrate's two geometric schedules (host gather/reshard ladder vs
the single-program collective-only ladder).

This is the repo's tracked perf-trajectory point for the peel hot path:
the geometric ladder's claim is that pass k scans O(m_k) edge slots
instead of O(m) (amortized O(m) total, the Lemma-4 shrink made operational),
with bit-identical results; the mesh-ladder claim (PR 5) is that the whole
schedule runs as ONE compiled ``shard_map`` program — zero host round-trips
between rungs — at no wall-clock regression vs the host ladder it replaces.
Run with::

    PYTHONPATH=src python -m benchmarks.bench_peel_compaction [--n 200000]

Writes experiments/bench/BENCH_peel.json with, per eps:
  * per-mode passes, total edge slots scanned, warm wall-clock (jit
    substrate; ladder programs pre-compiled, min over repeats),
  * slots/wall reduction factors vs 'off',
  * a bit-identity flag (best_alive/best_density/passes equal across modes),
  * a ``mesh`` block: host-ladder vs single-program ladder host_round_trips,
    wall, and bit-identity (1-device mesh unless more devices are visible).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

import jax
from jax.sharding import Mesh

from repro.compile_cache import enable_compile_cache
from repro.core import Problem, Solver
from repro.graph.generators import chung_lu_power_law


def _timed(fn, repeats: int):
    out = fn()  # warm: compiles every ladder rung once
    jax.block_until_ready(out.best_alive)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out.best_alive)
        best = min(best, time.perf_counter() - t0)
    return best, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--avg-deg", type=float, default=10.0)
    ap.add_argument("--exponent", type=float, default=2.0)
    ap.add_argument("--eps", type=float, nargs="+", default=[0.1, 0.5])
    # 5 repeats since the mesh-ladder entry landed: the host-ladder vs
    # single-program comparison sits within run-to-run noise at 3.
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument(
        "--out", default=os.path.join("experiments", "bench", "BENCH_peel.json")
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    edges = chung_lu_power_law(
        args.n, exponent=args.exponent, avg_deg=args.avg_deg, seed=0
    )
    m_pad = edges.n_edges_padded
    report = {
        "graph": {
            "family": "chung_lu_power_law",
            "n_nodes": args.n,
            "n_edges": int(edges.num_real_edges()),
            "n_edges_padded": m_pad,
            "exponent": args.exponent,
            "avg_deg": args.avg_deg,
        },
        "backend": "exact",
        "substrate": "jit",
        "platform": jax.default_backend(),
        "eps": {},
    }

    for eps in args.eps:
        solver = Solver()
        rows = {}
        ref = None
        for mode in ("off", "twophase", "geometric"):
            prob = Problem.undirected(eps=eps, compaction=mode)
            wall, res = _timed(lambda p=prob: solver.solve(edges, p), args.repeats)
            passes = int(res.passes)
            if mode == "off":
                slots = passes * m_pad
                segments = 1
            else:
                lad = res.extras["compaction"]
                slots = int(lad["edge_slots_scanned"])
                segments = len(lad["segments"])
            if ref is None:
                ref = res
                identical = True
            else:
                identical = (
                    np.array_equal(
                        np.asarray(res.best_alive), np.asarray(ref.best_alive)
                    )
                    and float(res.best_density) == float(ref.best_density)
                    and int(res.passes) == int(ref.passes)
                )
            rows[mode] = {
                "passes": passes,
                "segments": segments,
                "edge_slots_scanned": slots,
                "wall_s": round(wall, 4),
                "rho": round(float(res.best_density), 4),
                "bit_identical_to_off": identical,
            }
            print(f"eps={eps} {mode}: {rows[mode]}")
        off = rows["off"]
        for mode in ("twophase", "geometric"):
            rows[mode]["slots_reduction_x"] = round(
                off["edge_slots_scanned"] / max(rows[mode]["edge_slots_scanned"], 1), 2
            )
            rows[mode]["wall_speedup_x"] = round(
                off["wall_s"] / max(rows[mode]["wall_s"], 1e-9), 2
            )

        # ---- mesh substrate: host gather/reshard ladder vs the single-
        # program collective-only ladder that replaced it (PR 5) ----
        devs = jax.devices()
        mesh = Mesh(np.asarray(devs).reshape(len(devs)), ("data",))
        prob_mesh = Problem.undirected(
            eps=eps, substrate="mesh", compaction="geometric"
        )
        resolved = prob_mesh.resolve(edges.n_nodes, have_mesh=True)

        def host_ladder():
            # _run_compacted is the retained host schedule (twophase's
            # machinery); invoking it directly is the replaced baseline.
            out, ladder, _ = solver._run_compacted(edges, resolved, mesh, None)
            host_ladder.ladder = ladder
            return out

        wall_host, out_host = _timed(host_ladder, args.repeats)
        wall_prog, res_prog = _timed(
            lambda: solver.solve(edges, prob_mesh, mesh=mesh), args.repeats
        )
        wall_moff, _ = _timed(
            lambda: solver.solve(
                edges,
                Problem.undirected(eps=eps, substrate="mesh", compaction="off"),
                mesh=mesh,
            ),
            args.repeats,
        )
        lad_prog = res_prog.extras["compaction"]
        mesh_identical = (
            np.array_equal(
                np.asarray(res_prog.best_alive), np.asarray(ref.best_alive)
            )
            and float(res_prog.best_density) == float(ref.best_density)
            and int(res_prog.passes) == int(ref.passes)
            and np.array_equal(
                np.asarray(out_host.best_alive), np.asarray(res_prog.best_alive)
            )
        )
        rows["mesh"] = {
            "n_devices": len(devs),
            "off": {"wall_s": round(wall_moff, 4)},
            "host_ladder": {
                "wall_s": round(wall_host, 4),
                "host_round_trips": host_ladder.ladder["host_round_trips"],
                "segments": len(host_ladder.ladder["segments"]),
            },
            "single_program_ladder": {
                "wall_s": round(wall_prog, 4),
                "host_round_trips": lad_prog["host_round_trips"],
                "segments": len(lad_prog["segments"]),
                "edge_slots_scanned": int(lad_prog["edge_slots_scanned"]),
            },
            "wall_vs_host_ladder_x": round(
                wall_host / max(wall_prog, 1e-9), 2
            ),
            "wall_vs_mesh_off_x": round(wall_moff / max(wall_prog, 1e-9), 2),
            "bit_identical_to_off": mesh_identical,
        }
        print(f"eps={eps} mesh: {rows['mesh']}")
        report["eps"][str(eps)] = rows

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
