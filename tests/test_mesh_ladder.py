"""Single-program mesh compaction ladder (mesh × compaction='geometric').

The tentpole contract: the WHOLE geometric ladder — every peel segment and
every inter-rung edge compaction — runs inside ONE compiled
``jit(shard_map)`` program, collective-only (no host gather/reshard per
rung), bit-identical to the host-ladder and ``compaction='off'`` paths for
integer-valued weights.

Single-device degeneracy and schedule/report shape run in-process; the
multi-device cases (uneven survivor counts across devices, a rung whose
survivors all land on one device, permuted shard order) run in a subprocess
with ``--xla_force_host_platform_device_count=8`` so the main test process
keeps seeing one device (per the project rule).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import repro.core.api as api_mod
from repro.core import Problem, Solver
from repro.core.mapreduce import shard_edges
from repro.graph.partition import ladder_schedule, pow2_bucket
from repro.graph.generators import directed_planted, planted_dense_subgraph


def _und():
    return planted_dense_subgraph(260, avg_deg=4, k=25, p_dense=0.8, seed=3)[0]


def _dir():
    return directed_planted(200, avg_deg=3, ks=15, kt=12, p_dense=0.9, seed=5)[0]


def _mesh1():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))


@pytest.fixture
def small_ladder_floor(monkeypatch):
    """Shrink the ladder's bucket floor so the few-hundred-edge test graphs
    actually exercise multi-rung schedules (the production floor of 4096
    global edges would collapse them to one rung)."""
    monkeypatch.setattr(api_mod, "_LADDER_MIN_EDGES", 64)


def _same_full(a, b):
    np.testing.assert_array_equal(np.asarray(a.best_alive), np.asarray(b.best_alive))
    assert float(a.best_density) == float(b.best_density)
    assert int(a.passes) == int(b.passes)
    assert int(a.best_size) == int(b.best_size)
    np.testing.assert_array_equal(np.asarray(a.alive), np.asarray(b.alive))
    if np.asarray(a.best_t).size:
        np.testing.assert_array_equal(np.asarray(a.best_t), np.asarray(b.best_t))
        np.testing.assert_array_equal(np.asarray(a.t_alive), np.asarray(b.t_alive))


# ---------------------------------------------------------------------------
# Static schedule (graph/partition.ladder_schedule)
# ---------------------------------------------------------------------------


def test_ladder_schedule_is_static_halving():
    assert ladder_schedule(1024, floor=256) == (1024, 512, 256)
    assert ladder_schedule(1000, floor=256) == (1024, 512, 256)  # pow2 bucketed
    assert ladder_schedule(256, floor=256) == (256,)  # floor -> single rung
    assert ladder_schedule(100, floor=256) == (128,)  # floor clamps to top
    assert ladder_schedule(1, floor=1) == (1,)
    sched = ladder_schedule(1 << 20, floor=256)
    assert all(a == 2 * b for a, b in zip(sched, sched[1:]))
    assert sched[0] == 1 << 20 and sched[-1] == 256
    # Every rung is a pow2_bucket fixed point: one compile per bucket.
    assert all(pow2_bucket(c) == c for c in sched)
    # Coarser strides shrink faster (fewer compaction collectives).
    assert ladder_schedule(1 << 20, floor=256, stride=4) == (
        1 << 20, 1 << 18, 1 << 16, 1 << 14, 1 << 12, 1 << 10, 1 << 8
    )
    with pytest.raises(ValueError):
        ladder_schedule(1024, stride=1)


# ---------------------------------------------------------------------------
# Single-device degeneracy: mesh ladder == jit host ladder == off, to the bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("eps", [0.1, 0.5])
def test_single_device_mesh_ladder_degenerates_to_jit_ladder(eps, small_ladder_floor):
    edges = _und()
    mesh = _mesh1()
    s = Solver()
    off = s.solve(
        edges, Problem.undirected(eps=eps, track_history=True, compaction="off")
    )
    jit_ladder = s.solve(
        edges, Problem.undirected(eps=eps, track_history=True, compaction="geometric")
    )
    mesh_ladder = s.solve(
        edges,
        Problem.undirected(
            eps=eps, track_history=True, compaction="geometric", substrate="mesh"
        ),
        mesh=mesh,
    )
    _same_full(off, jit_ladder)
    _same_full(off, mesh_ladder)
    np.testing.assert_array_equal(
        np.asarray(jit_ladder.history_n), np.asarray(mesh_ladder.history_n)
    )
    np.testing.assert_array_equal(
        np.asarray(jit_ladder.history_rho), np.asarray(mesh_ladder.history_rho)
    )
    lad = mesh_ladder.extras["compaction"]
    assert lad["single_program"] is True
    assert lad["host_round_trips"] == 0
    # Only the per-rung pass counts and the pass total come back.
    assert lad["bytes_to_host"] == 4 * len(lad["segments"]) + 4
    assert lad["bytes_to_device"] == 0
    assert sum(seg["passes"] for seg in lad["segments"]) == int(off.passes)
    # The in-program compaction must actually run (multi-rung schedule);
    # with the production floor these graphs would be one trivial rung.
    assert len(lad["segments"]) > 1
    # The host jit ladder, by contrast, pays one round-trip per rung.
    jl = jit_ladder.extras["compaction"]
    assert jl["single_program"] is False
    assert jl["host_round_trips"] == len(jl["segments"]) >= 1


@pytest.mark.parametrize("c", [0.5, 1.0, None])
def test_mesh_ladder_directed_matches_host_ladder(c, small_ladder_floor):
    edges = _dir()
    mesh = _mesh1()
    s = Solver()
    off = s.solve(
        edges, Problem.directed(c=c, eps=0.5, substrate="mesh", compaction="off"),
        mesh=mesh,
    )
    on = s.solve(
        edges,
        Problem.directed(c=c, eps=0.5, substrate="mesh", compaction="geometric"),
        mesh=mesh,
    )
    _same_full(off, on)
    if c is None:
        assert on.extras["best_c"] == off.extras["best_c"]
        np.testing.assert_array_equal(
            on.extras["c_density"], off.extras["c_density"]
        )


def test_mesh_ladder_at_least_k_and_zero_pass_runs(small_ladder_floor):
    edges = _und()
    mesh = _mesh1()
    s = Solver()
    for k in (30, edges.n_nodes + 10):  # k > n: the zero-pass degenerate run
        off = s.solve(
            edges,
            Problem.at_least_k(k=k, eps=0.5, substrate="mesh", compaction="off"),
            mesh=mesh,
        )
        on = s.solve(
            edges,
            Problem.at_least_k(
                k=k, eps=0.5, substrate="mesh", compaction="geometric"
            ),
            mesh=mesh,
        )
        _same_full(off, on)


def test_mesh_ladder_program_is_cached_and_shares_across_c(small_ladder_floor):
    """Re-solves hit the one cached ladder program; c is a runtime scalar so
    fixed-c ladders and the grid share it too."""
    edges = _und()
    mesh = _mesh1()
    s = Solver()
    s.solve(
        edges,
        Problem.undirected(eps=0.25, substrate="mesh", compaction="geometric"),
        mesh=mesh,
    )
    traces = s.trace_count
    r2 = s.solve(
        edges,
        Problem.undirected(eps=0.25, substrate="mesh", compaction="geometric"),
        mesh=mesh,
    )
    assert s.trace_count == traces
    assert r2.provenance.cache_hit
    dg = _dir()
    s.solve(
        dg, Problem.directed(c=0.5, eps=0.5, substrate="mesh",
                             compaction="geometric"),
        mesh=mesh,
    )
    t2 = s.trace_count
    s.solve(
        dg, Problem.directed(c=2.0, eps=0.5, substrate="mesh",
                             compaction="geometric"),
        mesh=mesh,
    )
    assert s.trace_count == t2  # same single program, new c


def test_make_distributed_peel_ladder_builder_single_device(small_ladder_floor):
    """The cached single program, launched by hand on arrays the caller
    shards (``Solver.mesh_ladder_program``)."""
    edges = _und()
    mesh = _mesh1()
    fn, schedule, n_shards, _ = Solver().mesh_ladder_program(
        Problem.undirected(eps=0.5, substrate="mesh", compaction="geometric"),
        mesh, edges.n_nodes, edges.n_edges_padded,
    )
    assert n_shards == 1
    # Rung 0 is the exact input buffer; the tail is pow2-bucketed and
    # strictly descending.
    assert all(a > b for a, b in zip(schedule, schedule[1:]))
    assert all(pow2_bucket(c) == c for c in schedule[1:])
    padded = edges.with_padding(schedule[0] * n_shards)
    sh = shard_edges(padded, mesh, ("data",))
    out, rung_t = fn(sh.src, sh.dst, sh.weight, sh.mask)
    assert rung_t.shape == (len(schedule),) and int(rung_t[-1]) == int(out.passes)
    ref = Solver().solve(edges, Problem.undirected(eps=0.5, compaction="off"))
    np.testing.assert_array_equal(
        np.asarray(out.best_alive), np.asarray(ref.best_alive)
    )
    assert float(out.best_density) == float(ref.best_density)
    assert int(out.passes) == int(ref.passes)


# ---------------------------------------------------------------------------
# Multi-device: uneven survivors, one-device rungs, shard-order independence
# ---------------------------------------------------------------------------

_LADDER_8DEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp
    import repro.core.api as api_mod
    from repro.core import Problem, Solver
    from repro.core.mapreduce import shard_edges
    from repro.graph.edgelist import EdgeList
    from repro.graph.generators import planted_dense_subgraph

    # Small floor so these few-thousand-edge graphs build multi-rung
    # ladders (production floor: 4096 global edges).
    api_mod._LADDER_MIN_EDGES = 64
    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((8,), ("data",))
    s = Solver()

    def same(a, b):
        assert np.array_equal(np.asarray(a.best_alive), np.asarray(b.best_alive))
        assert float(a.best_density) == float(b.best_density)
        assert int(a.passes) == int(b.passes)
        assert np.array_equal(np.asarray(a.alive), np.asarray(b.alive))

    # Uneven survivor counts across devices: a planted block scatters its
    # survivors nonuniformly over the 8 edge shards.
    edges, _ = planted_dense_subgraph(500, avg_deg=4, k=25, p_dense=0.8, seed=0)
    off = s.solve(edges, Problem.undirected(eps=0.2, compaction="off"))
    on = s.solve(
        edges,
        Problem.undirected(eps=0.2, substrate="mesh", compaction="geometric"),
        mesh=mesh,
    )
    same(off, on)
    lad = on.extras["compaction"]
    assert lad["single_program"] and lad["host_round_trips"] == 0
    assert sum(g["passes"] for g in lad["segments"]) == int(off.passes)
    assert len(lad["segments"]) > 1  # the collective compaction really ran

    # A rung whose survivors all land on ONE device: the dense block's edges
    # occupy the first slots of the edge array, i.e. shard 0; after the
    # sparse background peels away, every surviving edge lives on device 0.
    rng = np.random.default_rng(1)
    n = 400
    ks, kd = np.triu_indices(40, k=1)            # 780 clique edges, shard 0
    bs = rng.integers(40, n, 1200); bd = rng.integers(40, n, 1200)
    keep = bs != bd
    src = np.concatenate([ks, bs[keep]]).astype(np.int32)
    dst = np.concatenate([kd, bd[keep]]).astype(np.int32)
    g = EdgeList(
        src=jnp.asarray(src), dst=jnp.asarray(dst),
        weight=jnp.ones(src.size, jnp.float32),
        mask=jnp.ones(src.size, bool), n_nodes=n,
    )
    off2 = s.solve(g, Problem.undirected(eps=0.1, compaction="off"))
    on2 = s.solve(
        g, Problem.undirected(eps=0.1, substrate="mesh", compaction="geometric"),
        mesh=mesh,
    )
    same(off2, on2)
    assert int(off2.best_size) >= 40 * 0.9  # the clique survives the peel

    # Shard-order independence: permuting the edge array (hence which shard
    # holds what) must not change anything (unit weights).
    perm = rng.permutation(src.size)
    gp = EdgeList(
        src=g.src[perm], dst=g.dst[perm], weight=g.weight[perm],
        mask=g.mask[perm], n_nodes=n,
    )
    onp_ = s.solve(
        gp, Problem.undirected(eps=0.1, substrate="mesh", compaction="geometric"),
        mesh=mesh,
    )
    same(off2, onp_)

    # The cached single program launched by hand: parity.
    fn, schedule, n_shards, _ = s.mesh_ladder_program(
        Problem.undirected(eps=0.2, substrate="mesh", compaction="geometric"),
        mesh, edges.n_nodes, edges.n_edges_padded,
    )
    padded = edges.with_padding(schedule[0] * n_shards)
    sh = shard_edges(padded, mesh, ("data",))
    out, _ = fn(sh.src, sh.dst, sh.weight, sh.mask)
    assert np.array_equal(np.asarray(out.best_alive), np.asarray(off.best_alive))
    assert int(out.passes) == int(off.passes)
    print("MESH_LADDER_8DEV_OK")
    """
)


@pytest.mark.slow
def test_mesh_ladder_equivalence_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", _LADDER_8DEV_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_LADDER_8DEV_OK" in out.stdout
