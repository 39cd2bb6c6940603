"""Out-of-core streaming benchmark: async-pipeline overlap win and ladder
spill residency for the semi-streaming substrate.

The substrate's two claims after the out-of-core overhaul:

  * the bounded-prefetch async pipeline (chunk reads + device degree
    kernels + in-order host reduction overlapped) beats the synchronous
    one-chunk-at-a-time pass, bit-identically;
  * the geometric ladder with ``spill_dir`` completes with bounded host
    residency (pipeline window only — rebuilt survivor streams live on
    disk), still bit-identical to ``compaction='off'``.

Run with::

    PYTHONPATH=src python -m benchmarks.bench_stream [--n 100000]

Writes experiments/bench/BENCH_stream.json with, per mode (sync, async,
async+geometric in-RAM, async+geometric spilled): wall-clock (min over
repeats), passes, peak resident chunks/edges, compactions/spill rungs, and
bit-identity vs the synchronous baseline; plus the overlap speedup factor.
The stream itself is memmap-backed (written once to a scratch edge store),
so edges never sit in host RAM whole.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time

import numpy as np

import jax

from repro.compile_cache import enable_compile_cache
from repro.core.streaming import StreamingDensest, chunked_from_memmap
from repro.graph.edgelist import save_edges_memmap
from repro.graph.generators import chung_lu_power_law


def _run(make_drv, repeats: int):
    st = make_drv().run(resume=False)  # warm: compiles the chunk kernels
    best = float("inf")
    drv = None
    for _ in range(repeats):
        drv = make_drv()
        t0 = time.perf_counter()
        st = drv.run(resume=False)
        best = min(best, time.perf_counter() - t0)
    return best, st, drv


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    # Defaults reproduce the COMMITTED baseline: running with no flags must
    # regenerate a comparable BENCH_stream.json, never silently overwrite
    # it with a different configuration.
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--avg-deg", type=float, default=8.0)
    ap.add_argument("--exponent", type=float, default=2.0)
    ap.add_argument("--eps", type=float, default=0.1)
    ap.add_argument("--chunk", type=int, default=1 << 13)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--prefetch", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument(
        "--out", default=os.path.join("experiments", "bench", "BENCH_stream.json")
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    edges = chung_lu_power_law(
        args.n, exponent=args.exponent, avg_deg=args.avg_deg, seed=0
    )
    mask = np.asarray(edges.mask)
    src = np.asarray(edges.src)[mask]
    dst = np.asarray(edges.dst)[mask]
    w = np.asarray(edges.weight)[mask]
    scratch = tempfile.mkdtemp(prefix="bench_stream_")
    store = save_edges_memmap(os.path.join(scratch, "store"), src, dst, w)
    stream = chunked_from_memmap(store, chunk=args.chunk)
    n_chunks = -(-len(src) // args.chunk)

    # Speculation is a fault-tolerance knob (it DUPLICATES tail chunks); it
    # stays off in the timed modes so the numbers isolate pipeline overlap.
    modes = {
        "sync": dict(n_workers=1, prefetch=1, speculative=False),
        "async": dict(
            n_workers=args.workers, prefetch=args.prefetch, speculative=False
        ),
        "geometric_ram": dict(
            n_workers=args.workers, prefetch=args.prefetch, speculative=False,
            compaction="geometric",
        ),
        "geometric_spill": dict(
            n_workers=args.workers, prefetch=args.prefetch, speculative=False,
            compaction="geometric",
            spill_dir=os.path.join(scratch, "spill"),
        ),
    }
    report = {
        "graph": {
            "family": "chung_lu_power_law",
            "n_nodes": args.n,
            "n_edges": int(len(src)),
            "exponent": args.exponent,
            "avg_deg": args.avg_deg,
        },
        "eps": args.eps,
        "chunk": args.chunk,
        "n_chunks": n_chunks,
        "workers": args.workers,
        "prefetch": args.prefetch,
        "platform": jax.default_backend(),
        "modes": {},
    }
    ref = None
    try:
        for name, kw in modes.items():
            wall, st, drv = _run(
                lambda kw=kw: StreamingDensest(
                    stream, n_nodes=args.n, eps=args.eps, **kw
                ),
                args.repeats,
            )
            if ref is None:
                ref = st
                identical = True
            else:
                identical = (
                    st.best_rho == ref.best_rho
                    and (st.best_alive == ref.best_alive).all()
                    and st.pass_idx == ref.pass_idx
                    and st.history == ref.history
                )
            report["modes"][name] = {
                "wall_s": round(wall, 4),
                "passes": st.pass_idx,
                "rho": round(st.best_rho, 4),
                "peak_resident_chunks": drv.peak_resident_chunks,
                "peak_resident_edges": drv.peak_resident_edges,
                "compactions": drv.compactions,
                "spill_rungs": drv.spill_rungs,
                "speculative_reissues": drv.speculative_reissues,
                "bit_identical_to_sync": identical,
            }
            print(f"{name}: {report['modes'][name]}")
        sync_w = report["modes"]["sync"]["wall_s"]
        for name in ("async", "geometric_ram", "geometric_spill"):
            report["modes"][name]["speedup_vs_sync_x"] = round(
                sync_w / max(report["modes"][name]["wall_s"], 1e-9), 2
            )
        ram = report["modes"]["geometric_ram"]["peak_resident_edges"]
        sp = report["modes"]["geometric_spill"]["peak_resident_edges"]
        report["spill_residency_reduction_x"] = round(ram / max(sp, 1), 2)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
