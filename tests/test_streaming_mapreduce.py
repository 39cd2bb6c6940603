"""Streaming driver + MapReduce (shard_map) equivalence and fault tolerance.

Multi-device shard_map equivalence runs in a subprocess with
XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT=8 so the main test process keeps seeing
one device (per the project rule).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import Problem, StreamingDensest, solve
from repro.core.streaming import chunked_from_arrays
from repro.graph.generators import erdos_renyi, planted_dense_subgraph


def _peel(edges, eps):
    """Algorithm 1 through the front door, on the uncompacted jit loop."""
    return solve(
        edges, Problem.undirected(eps=eps, track_history=True, compaction="off")
    )


def _edges_np(edges):
    mask = np.asarray(edges.mask)
    return (
        np.asarray(edges.src)[mask],
        np.asarray(edges.dst)[mask],
        np.asarray(edges.weight)[mask],
    )


def test_streaming_matches_in_memory():
    edges, _ = planted_dense_subgraph(800, avg_deg=4, k=30, p_dense=0.8, seed=0)
    ref = _peel(edges, eps=0.5)
    src, dst, w = _edges_np(edges)
    drv = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=257),
        n_nodes=edges.n_nodes,
        eps=0.5,
        n_workers=3,
    )
    st = drv.run(resume=False)
    assert st.best_rho == pytest.approx(float(ref.best_density), rel=1e-5)
    assert (st.best_alive == np.asarray(ref.best_alive)).all()
    assert st.pass_idx == int(ref.passes)


def test_streaming_checkpoint_restart(tmp_path):
    """Kill the run after a few passes; resuming must give identical output."""
    edges = erdos_renyi(600, avg_deg=8, seed=1)
    src, dst, w = _edges_np(edges)
    ref = _peel(edges, eps=0.5)

    ckpt = str(tmp_path / "ck")
    drv = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=1000),
        n_nodes=edges.n_nodes,
        eps=0.5,
        checkpoint_dir=ckpt,
        n_workers=2,
    )
    # Simulated crash: run only 2 passes.
    st_partial = drv.run(max_passes=2, resume=False)
    assert st_partial.pass_idx == 2

    drv2 = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=1000),
        n_nodes=edges.n_nodes,
        eps=0.5,
        checkpoint_dir=ckpt,
        n_workers=2,
    )
    st = drv2.run(resume=True)  # resumes from pass 2
    assert st.best_rho == pytest.approx(float(ref.best_density), rel=1e-5)
    assert (st.best_alive == np.asarray(ref.best_alive)).all()


def test_streaming_speculative_reissue_is_idempotent():
    edges = erdos_renyi(400, avg_deg=6, seed=2)
    src, dst, w = _edges_np(edges)
    ref = _peel(edges, eps=1.0)
    drv = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=64),
        n_nodes=edges.n_nodes,
        eps=1.0,
        n_workers=4,
        speculative=True,
        speculate_tail_frac=0.5,  # aggressively re-issue half the chunks
    )
    st = drv.run(resume=False)
    assert st.best_rho == pytest.approx(float(ref.best_density), rel=1e-5)


_MAPREDUCE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.core import Problem, solve
    from repro.graph.generators import planted_dense_subgraph, directed_planted

    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((8,), ("data",))
    mesh_prob = Problem.undirected(
        eps=0.5, substrate="mesh", edge_axes=("data",), compaction="off")

    # Undirected equivalence: identical best set + density for any sharding.
    edges, _ = planted_dense_subgraph(500, avg_deg=4, k=25, p_dense=0.8, seed=0)
    ref = solve(edges, Problem.undirected(
        eps=0.5, track_history=True, compaction="off"))
    res = solve(edges, mesh_prob, mesh=mesh)
    assert abs(float(res.best_density) - float(ref.best_density)) < 1e-5
    assert (np.asarray(res.best_alive) == np.asarray(ref.best_alive)).all()
    assert int(res.passes) == int(ref.passes)

    # Permuted edge order must give identical results (order independence).
    perm = np.random.default_rng(0).permutation(edges.src.shape[0])
    from repro.graph.edgelist import EdgeList
    import jax.numpy as jnp
    edges_p = EdgeList(
        src=edges.src[perm], dst=edges.dst[perm], weight=edges.weight[perm],
        mask=edges.mask[perm], n_nodes=edges.n_nodes)
    res_p = solve(edges_p, mesh_prob, mesh=mesh)
    assert (np.asarray(res_p.best_alive) == np.asarray(ref.best_alive)).all()

    # Directed equivalence.
    dg, _, _ = directed_planted(300, avg_deg=3, ks=15, kt=15, p_dense=0.9, seed=1)
    dref = solve(dg, Problem.directed(c=1.0, eps=0.5, compaction="off"))
    dres = solve(dg, Problem.directed(
        c=1.0, eps=0.5, substrate="mesh", edge_axes=("data",),
        compaction="off"), mesh=mesh)
    assert abs(float(dres.best_density) - float(dref.best_density)) < 1e-5
    assert (np.asarray(dres.best_s) == np.asarray(dref.best_s)).all()
    print("MAPREDUCE_EQUIV_OK")
    """
)


def test_mapreduce_equivalence_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", _MAPREDUCE_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MAPREDUCE_EQUIV_OK" in out.stdout
