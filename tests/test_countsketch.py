"""Count-Sketch (§5.1) tests: estimator accuracy on heavy nodes, sketched
peeling quality (Table 4 analogue), and the sketched front door held to the
plain numpy reference of the benchmark (``perfbench/sketch_reference.py``)
with its spans and counters."""

import re
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import graphgen, sketch_reference
from repro.core import Problem, Solver, api, countsketch, solve
from repro.core.countsketch import (
    SketchCounters,
    make_sketch_params,
    query_degrees,
    sketch_degrees_from_edges,
)
from repro.core.density import alive_edge_weight
from repro.graph.edgelist import EdgeList
from repro.graph.generators import chung_lu_power_law, planted_dense_subgraph


def _exact_degrees_np(edges):
    mask = np.asarray(edges.mask)
    src = np.asarray(edges.src)[mask]
    dst = np.asarray(edges.dst)[mask]
    deg = np.zeros(edges.n_nodes)
    np.add.at(deg, src, 1)
    np.add.at(deg, dst, 1)
    return deg


def test_sketch_accurate_on_heavy_nodes():
    edges = chung_lu_power_law(2000, avg_deg=10, seed=0)
    deg = _exact_degrees_np(edges)
    p = make_sketch_params(t=5, b=1 << 12, seed=1)
    alive = jnp.ones((edges.n_nodes,), bool)
    counters = sketch_degrees_from_edges(p, edges, alive_edge_weight(edges, alive))
    est = np.asarray(query_degrees(p, counters, jnp.arange(edges.n_nodes)))
    heavy = deg >= np.quantile(deg, 0.99)
    rel_err = np.abs(est[heavy] - deg[heavy]) / np.maximum(deg[heavy], 1)
    # Count-Sketch guarantee: heavy hitters estimated well.
    assert np.median(rel_err) < 0.15


def test_sketch_error_decreases_with_buckets():
    edges = chung_lu_power_law(2000, avg_deg=10, seed=0)
    deg = _exact_degrees_np(edges)
    alive = jnp.ones((edges.n_nodes,), bool)
    errs = []
    for b in (1 << 8, 1 << 10, 1 << 13):
        p = make_sketch_params(t=5, b=b, seed=2)
        counters = sketch_degrees_from_edges(p, edges, alive_edge_weight(edges, alive))
        est = np.asarray(query_degrees(p, counters, jnp.arange(edges.n_nodes)))
        errs.append(np.mean(np.abs(est - deg)))
    assert errs[2] < errs[1] < errs[0]


def test_sketched_peeling_close_to_exact():
    """Table 4 analogue: sketched density within a modest factor of exact."""
    edges, _ = planted_dense_subgraph(1500, avg_deg=4, k=40, p_dense=0.8, seed=4)
    exact = float(solve(edges, Problem.undirected(
        eps=0.5, track_history=True, compaction="off")).best_density)
    sk = float(solve(edges, Problem.undirected(
        eps=0.5, track_history=True, backend="sketch", sketch_tables=5,
        sketch_buckets=1 << 12, sketch_seed=0)).best_density)
    assert sk >= 0.75 * exact  # paper sees 0.89-1.05 at eps<=1
    assert sk <= 1.25 * exact


def test_sketch_memory_is_sublinear():
    p = make_sketch_params(t=5, b=1 << 10)
    # 5 * 1024 counters vs n=100k degree floats.
    assert p.n_tables * p.n_buckets < 100_000 // 2


# ---------------------------------------------------------------------------
# The sketched front door against the plain numpy reference
# (perfbench/sketch_reference.py), on the benchmark's own Chung-Lu graphs.
# ---------------------------------------------------------------------------

N_NODES, N_EDGES, T = 3000, 15000, 5


def _chung_lu(seed):
    src, dst = graphgen.generate(N_NODES, N_EDGES, 2.2, seed)
    g = EdgeList(src=src, dst=dst, weight=jnp.ones((N_EDGES,), jnp.float32),
                 mask=jnp.ones((N_EDGES,), bool), n_nodes=N_NODES)
    return g, np.asarray(src), np.asarray(dst)


def _sketch_problem(b, **kw):
    kw.setdefault("compaction", "auto")
    return Problem(objective="undirected", eps=0.5, backend="sketch",
                   sketch_tables=T, sketch_buckets=b, sketch_seed=0, **kw)


@pytest.mark.parametrize("seed", [5, 7])
@pytest.mark.parametrize("b", [16, 256, 4096])
def test_sketched_solve_equals_the_numpy_reference(seed, b):
    """Equal passes, best sets and densities.  At 16 buckets hub collisions
    keep most estimates above the threshold and the peel runs to the pass
    cap; at 4096 it ends in a few passes."""
    g, src, dst = _chung_lu(seed)
    res = Solver().solve(g, _sketch_problem(b))
    ref = sketch_reference.peel(src, dst, N_NODES, 0.5, T, b, 0)
    assert int(res.passes) == ref["passes"]
    np.testing.assert_array_equal(np.asarray(res.best_alive), ref["best"])
    assert float(res.best_density) == pytest.approx(ref["density"], rel=1e-5)
    cap = sketch_reference.pass_cap(N_NODES, 0.5)
    assert cap == res.provenance.max_passes
    if b == 16:
        assert ref["passes"] == cap
    if b == 4096:
        assert ref["passes"] < cap // 2


def test_sketched_solve_counts_its_hashed_slots():
    """``slots_hashed`` counts the uncompacted schedule on and off the
    ladder; the ladder's rungs scan fewer slots than that."""
    g, _, _ = _chung_lu(5)
    on = Solver().solve(g, _sketch_problem(256))
    off = Solver().solve(g, _sketch_problem(256, compaction="off"))
    for res in (on, off):
        assert dict(res.extras["sketch"]) == {
            "tables": T, "buckets": 256, "counter_bytes": 4 * T * 256,
            "slots_hashed": int(res.passes) * 2 * T * g.n_edges_padded,
        }
    assert "compaction" not in off.extras
    ladder = on.extras["compaction"]
    assert len(ladder["segments"]) > 1
    assert ladder["edge_slots_scanned"] < int(on.passes) * g.n_edges_padded
    assert Solver().solve(g, Problem.undirected(eps=0.5)).extras.get("sketch") is None


def test_sketch_counters_wait_for_nothing_until_slots_are_read():
    class Unread:
        def __int__(self):
            raise AssertionError("the pass count was read")

    sk = SketchCounters(5, 1 << 18, 1000, Unread())
    assert (sk["tables"], sk["buckets"], sk["counter_bytes"]) == (5, 1 << 18, 5 << 20)
    assert sorted(sk) == sorted(SketchCounters.KEYS) and len(sk) == 4
    with pytest.raises(AssertionError):
        sk["slots_hashed"]
    assert SketchCounters(5, 8, 1000, jnp.int32(3))["slots_hashed"] == 30000


# ---------------------------------------------------------------------------
# Sketched solves on the host compaction ladder: the rungs keep node ids, so
# every counter, and with it every decision, is the uncompacted loop's.
# ---------------------------------------------------------------------------

LADDER_FIELDS = ("best_alive", "best_t", "best_density", "best_size", "passes",
                 "alive", "t_alive", "history_n", "history_m", "history_rho")
OBJECTIVES = {
    "undirected": lambda **kw: Problem.undirected(eps=0.5, **kw),
    "at_least_k": lambda **kw: Problem.at_least_k(k=300, eps=0.5, **kw),
    "directed": lambda **kw: Problem.directed(c=1.0, eps=0.5, **kw),
}


def _weighted(seed, directed):
    """A Chung-Lu graph with integer weights 1-4 and padded slots."""
    src, dst = graphgen.generate(N_NODES, N_EDGES, 2.2, seed)
    w = np.random.default_rng(seed).integers(1, 5, N_EDGES).astype(np.float32)
    g = EdgeList(src=src, dst=dst, weight=jnp.asarray(w),
                 mask=jnp.ones((N_EDGES,), bool), n_nodes=N_NODES,
                 directed=directed)
    return g.with_padding(1 << 14)


def _sketched(objective, **kw):
    return OBJECTIVES[objective](
        backend="sketch", sketch_tables=T, sketch_buckets=64, sketch_seed=3,
        track_history=True, **kw)


@pytest.mark.parametrize("seed", [5, 11])
@pytest.mark.parametrize("objective", sorted(OBJECTIVES))
def test_sketched_ladder_answers_as_the_uncompacted_loop(objective, seed):
    """64 buckets for 3000 nodes: counters collide, estimates are far off,
    and the ladder still answers bit for bit as ``compaction='off'``."""
    g = _weighted(seed, objective == "directed")
    solver = Solver()
    on = solver.solve(g, _sketched(objective))
    off = solver.solve(g, _sketched(objective, compaction="off"))
    assert on.provenance.compaction == "geometric"
    assert len(on.extras["compaction"]["segments"]) > 1
    for field in LADDER_FIELDS:
        a, b = np.asarray(getattr(on, field)), np.asarray(getattr(off, field))
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), field


KEEP_ID_GRAPHS = {
    # 256 buckets on the benchmark's degree law.
    "chung_lu": lambda: (_chung_lu(7)[0], 256),
    # Integer weights, colliding counters (about 12 nodes a bucket), down
    # to the edge-bucket floor.
    "weighted_floor": lambda: (_weighted(11, False), 256),
}


@pytest.mark.parametrize("graph", sorted(KEEP_ID_GRAPHS))
def test_sketched_ladder_keeps_node_ids(graph):
    g, b = KEEP_ID_GRAPHS[graph]()
    solver = Solver()
    res = solver.solve(g, _sketch_problem(b, compaction="geometric"))
    segs = res.extras["compaction"]["segments"]
    assert len(segs) > 1
    assert all(s["n_buf"] == N_NODES for s in segs)
    assert all(b["m_buf"] < a["m_buf"] for a, b in zip(segs, segs[1:]))
    assert res.extras["compaction"]["edge_slots_scanned"] == \
        sum(s["passes"] * s["m_buf"] for s in segs)
    if graph == "weighted_floor":
        # The smallest edge bucket gets no trigger: the ladder ends there.
        assert segs[-1]["m_buf"] == api._COMPACT_MIN_EDGES
        assert segs[-1]["compact_below"] is None
    off = solver.solve(g, _sketch_problem(b, compaction="off"))
    for field in LADDER_FIELDS:
        x, y = np.asarray(getattr(res, field)), np.asarray(getattr(off, field))
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), field


@pytest.mark.parametrize("objective", ["undirected", "directed"])
def test_sketch_on_the_mesh_keeps_its_ladder_off(objective):
    """The mesh ladder counts exact degrees only, so a sketch there stays
    off, and an explicit ladder is refused."""
    prob = OBJECTIVES[objective](backend="sketch", substrate="mesh")
    assert prob.resolve(100).compaction == "off"
    with pytest.raises(ValueError, match="only the jit ladder keeps them"):
        OBJECTIVES[objective](
            backend="sketch", substrate="mesh", compaction="geometric"
        ).resolve(100)


def test_reference_hashes_are_the_programs():
    """Parameters drawn in ``make_sketch_params``' order, and the same
    bucket and sign for every node id."""
    p = make_sketch_params(t=T, b=1 << 18, seed=11)
    ref = sketch_reference.sketch_params(T, 1 << 18, 11)
    for name in ("a_h", "c_h", "a_g", "c_g"):
        np.testing.assert_array_equal(np.asarray(getattr(p, name)), ref[name])
    ids = jnp.arange(N_NODES, dtype=jnp.int32)
    bucket, sign = sketch_reference.node_hashes(ref, N_NODES)
    np.testing.assert_array_equal(np.asarray(countsketch._hash_bucket(p, ids)), bucket)
    np.testing.assert_array_equal(np.asarray(countsketch._hash_sign(p, ids)), sign)


def _lowered_sketch_peel():
    g, _, _ = _chung_lu(5)
    prob = _sketch_problem(256).resolve(N_NODES)
    return jax.jit(lambda e: api.run_cell(e, prob, max_passes=24)).lower(g)


def test_lowered_sketch_carries_its_two_scopes():
    text = _lowered_sketch_peel().as_text(debug_info=True)
    for scope in ("sketch_update", "sketch_query"):
        assert re.search(rf"peel/[^\"]*\bdegree_count/{scope}/", text), scope
    names = re.findall(r'op_name="([^"]*)"',
                       _lowered_sketch_peel().compile().as_text())
    assert any(n.endswith("degree_count/sketch_update/scatter-add") for n in names)
    assert any("degree_count/sketch_query/" in n and "gather" in n for n in names)


def test_sketch_scopes_leave_the_compiled_program_unchanged(monkeypatch):
    def strip(hlo):
        tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
        blocks = [b for b in hlo.split("\n\n") if not b.startswith(tables)]
        return re.sub(r", metadata=\{[^}]*\}", "", "\n\n".join(blocks))

    scoped = strip(_lowered_sketch_peel().compile().as_text())
    monkeypatch.setattr(jax, "named_scope", lambda name: nullcontext())
    plain = strip(_lowered_sketch_peel().compile().as_text())
    assert "sketch_update" not in scoped
    assert scoped == plain
