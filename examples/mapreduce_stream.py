"""END-TO-END driver — the paper's kind of workload at example scale.

    PYTHONPATH=src python examples/mapreduce_stream.py [--n 2000000]

A power-law graph (2M nodes / ~8M edges by default) is processed four ways:

  1. SEMI-STREAMING (paper §4.1): multi-pass chunked edge stream with O(n)
     state, per-pass atomic checkpoints, straggler-aware speculative chunk
     re-issue — then KILLED mid-run and RESUMED from the checkpoint.
  2. OUT-OF-CORE SPILL LADDER: the same stream written once to an on-disk
     memmap edge store and run through the geometric compaction ladder with
     a residency cap SMALLER than the ladder's survivors — the rebuilt
     survivor streams spill to disk, so host RAM holds only the async
     pipeline's prefetch window.
  3. MAPREDUCE-ANALOGUE (paper §5.2): the whole O(log n)-pass algorithm as
     ONE compiled XLA program over an edge-sharded device mesh (this process
     forces 8 host devices to make the collectives real).
  4. COMPACTION LADDER on the mesh (beyond-paper): same answer, with the
     Lemma-4 bucket ladder shrinking the sharded edge buffers rung by rung
     inside one compiled program; plus the Count-Sketch memory mode (§5.1).

All exact modes must agree (and the Count-Sketch variant within its
approximation).
"""

import argparse
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import time

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core import Problem, StreamingDensest, solve
from repro.core.streaming import chunked_from_arrays, chunked_from_memmap
from repro.graph.edgelist import save_edges_memmap
from repro.graph.generators import chung_lu_power_law


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2_000_000)
    ap.add_argument("--avg-deg", type=float, default=8.0)
    ap.add_argument("--chunk", type=int, default=None,
                    help="stream chunk size (default: ~m/8)")
    ap.add_argument("--scratch", default="experiments/stream_ckpt",
                    help="checkpoint / edge-store / spill scratch dir")
    args = ap.parse_args(argv)

    edges = chung_lu_power_law(
        n=args.n, exponent=2.0, avg_deg=args.avg_deg, seed=42
    )
    n, m = edges.n_nodes, int(edges.num_real_edges())
    chunk = args.chunk or max(m // 8, 1024)
    print(f"graph: n={n:,} m={m:,} chunk={chunk:,}")
    src = np.asarray(edges.src)
    dst = np.asarray(edges.dst)

    # ---- 1. semi-streaming with checkpoint/restart + stragglers ----------
    ckpt_dir = args.scratch
    if os.path.exists(os.path.join(ckpt_dir, "stream_state.npz")):
        os.unlink(os.path.join(ckpt_dir, "stream_state.npz"))
    stream = chunked_from_arrays(src, dst, None, chunk=chunk)

    t0 = time.time()
    sd = StreamingDensest(stream, n, eps=0.5, checkpoint_dir=ckpt_dir)
    st = sd.run(max_passes=4)  # simulate preemption after 4 passes
    print(
        f"[stream] preempted at pass {st.pass_idx}, "
        f"best rho so far {st.best_rho:.3f} (checkpoint saved)"
    )
    sd2 = StreamingDensest(stream, n, eps=0.5, checkpoint_dir=ckpt_dir)
    st = sd2.run(resume=True)  # picks up at pass 4
    rho_stream = st.best_rho
    print(
        f"[stream] resumed + finished: rho={rho_stream:.4f} "
        f"passes={st.pass_idx} wall={time.time() - t0:.1f}s "
        f"speculative_reissues={sd2.speculative_reissues}"
    )

    # ---- 1b. out-of-core: memmap store + spilled compaction ladder -------
    # The edge store lives on disk; the residency cap is far below the
    # ladder's survivor count, so every rebuilt survivor stream spills to
    # memmaps under spill_dir and host RAM holds only the prefetch window.
    store = save_edges_memmap(
        os.path.join(args.scratch, "edge_store"), src, dst
    )
    chunk_ooc = max(m // 64, 256)
    # Rebuilt spill chunks are pow2-padded (<= 2x the input chunk), so the
    # pipeline's 4-chunk window is bounded by 8 x chunk_ooc ~ m/8 — far
    # below the ladder's survivor count (just under m/2 at first trigger).
    cap = 8 * chunk_ooc
    t0 = time.time()
    ooc = StreamingDensest(
        chunked_from_memmap(store, chunk=chunk_ooc), n, eps=0.5,
        compaction="geometric", prefetch=4,
        spill_dir=os.path.join(args.scratch, "spill"),
        residency_cap_edges=cap,
    )
    st_ooc = ooc.run(resume=False)
    print(
        f"[out-of-core] rho={st_ooc.best_rho:.4f} passes={st_ooc.pass_idx} "
        f"wall={time.time() - t0:.1f}s spill_rungs={ooc.spill_rungs} "
        f"peak_resident={ooc.peak_resident_edges:,}/{m:,} edges "
        f"(cap {cap:,})"
    )
    assert ooc.peak_resident_edges <= cap
    assert st_ooc.best_rho == rho_stream
    assert (st_ooc.best_alive == st.best_alive).all()

    # ---- 2. one-XLA-program MapReduce analogue on the device mesh --------
    n_dev = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()).reshape(n_dev), ("data",))
    t0 = time.time()
    res = solve(
        edges,
        Problem.undirected(eps=0.5, substrate="mesh", compaction="off"),
        mesh=mesh,
    )
    jax.block_until_ready(res.best_density)
    rho_dist = float(res.best_density)
    print(
        f"[mapreduce x{n_dev}dev] rho={rho_dist:.4f} passes={int(res.passes)} "
        f"wall={time.time() - t0:.1f}s (one compiled while_loop)"
    )

    # ---- 3. the compaction ladder as one mesh program (beyond-paper) ------
    t0 = time.time()
    r2 = solve(
        edges,
        Problem.undirected(eps=0.5, substrate="mesh", compaction="geometric"),
        mesh=mesh,
    )
    jax.block_until_ready(r2.best_density)
    rungs = len(r2.extras["compaction"]["segments"])
    print(
        f"[mesh ladder] rho={float(r2.best_density):.4f} passes={int(r2.passes)} "
        f"wall={time.time() - t0:.1f}s ({rungs} rungs, one compiled program)"
    )

    # ---- 4. Count-Sketch memory mode (paper §5.1) -------------------------
    sk = solve(
        edges,
        Problem.undirected(eps=0.5, backend="sketch", sketch_tables=5,
                           sketch_buckets=1 << 16),
    )
    print(
        f"[sketch t=5 b=65536] rho={float(sk.best_density):.4f} "
        f"(node-state memory {5 * (1 << 16) / n:.1%} of exact)"
    )

    assert abs(rho_stream - rho_dist) < 1e-3
    assert abs(float(r2.best_density) - rho_dist) < 1e-3
    print("\nall exact modes agree ✓")


if __name__ == "__main__":
    main()
