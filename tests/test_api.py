"""Front-door redesign tests (core/api.py).

Three guarantees:
  1. EQUIVALENCE MATRIX — ``solve()`` is bit-identical to a direct engine
     lowering across the policy × backend × substrate cells.
  2. BATCHING — ``solve_batch`` (multi-eps / multi-c / stacked graphs) is
     bit-identical to a Python loop of per-item ``solve`` calls and runs as
     ONE traced program.
  3. CACHING — a repeated same-shape ``solve`` hits the Solver's program
     cache and does not retrace.

eps values in batched comparisons are f32-exact (dyadic) so that the python
float scalar folding of the unbatched path and the traced-f32 arithmetic of
the vmapped path agree to the bit.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import (
    DenseSubgraphResult,
    Problem,
    Solver,
    StreamingDensest,
    c_grid,
    solve,
    solve_batch,
)
from repro.core.countsketch import SketchBackend, make_sketch_params
from repro.core.engine import (
    AtLeastKFraction,
    DirectedST,
    ExactBackend,
    UndirectedThreshold,
    run_peel,
)
from repro.core.streaming import chunked_from_arrays
from repro.graph.edgelist import EdgeList
from repro.graph.generators import (
    chung_lu_power_law,
    directed_planted,
    erdos_renyi,
    planted_dense_subgraph,
)


def _und():
    return planted_dense_subgraph(260, avg_deg=4, k=25, p_dense=0.8, seed=3)[0]


def _dir():
    return directed_planted(200, avg_deg=3, ks=15, kt=12, p_dense=0.9, seed=5)[0]


def _same(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _same_result(r, l):
    """Bit-identical best set / density / passes (and T side if present)."""
    _same(r.best_alive, l.best_alive)
    assert float(r.best_density) == float(l.best_density)
    assert int(r.passes) == int(l.passes)
    assert int(r.best_size) == int(l.best_size)
    if np.asarray(r.best_t).size:
        _same(r.best_t, l.best_t)


# ---------------------------------------------------------------------------
# 1. Equivalence matrix: solve() vs a direct lowering onto the engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("eps", [0.1, 0.5])
def test_solve_undirected_matches_legacy_and_engine(eps):
    edges = _und()
    r = solve(edges, Problem.undirected(eps=eps, track_history=True))
    # Independent lowering straight onto the engine.
    mp = Problem.undirected(eps=eps).resolved_max_passes(edges.n_nodes)
    ref = jax.jit(
        lambda e: run_peel(
            e, UndirectedThreshold(eps), ExactBackend(), mp, track_history=True
        )
    )(edges)
    _same_result(r, ref)
    _same(r.history_n, ref.history_n)
    assert r.provenance.backend == "exact"
    assert r.provenance.substrate == "jit"


@pytest.mark.parametrize("variant", ["floor_fallback", "ceil_plain"])
def test_solve_at_least_k_matches_legacy_and_engine(variant):
    edges = _und()
    k, eps = 30, 0.5
    fallback = variant == "floor_fallback"
    prob = Problem.at_least_k(
        k=k, eps=eps, min_deg_fallback=fallback, ceil_count=not fallback
    )
    r = solve(edges, prob)
    mp = prob.resolved_max_passes(edges.n_nodes)
    ref = jax.jit(
        lambda e: run_peel(
            e,
            AtLeastKFraction(
                k=k, eps=eps, min_deg_fallback=fallback, ceil_count=not fallback
            ),
            ExactBackend(),
            mp,
        )
    )(edges)
    _same_result(r, ref)


@pytest.mark.parametrize("c", [0.5, 1.0, 2.0])
def test_solve_directed_matches_legacy_and_engine(c):
    edges = _dir()
    eps = 0.5
    prob = Problem.directed(c=c, eps=eps)
    r = solve(edges, prob)
    mp = prob.resolved_max_passes(edges.n_nodes)
    ref = jax.jit(
        lambda e: run_peel(
            e, DirectedST(eps=eps, c=jnp.float32(c)), ExactBackend(), mp
        )
    )(edges)
    _same_result(r, ref)


def test_solve_directed_grid_matches_legacy_search():
    """The c-grid search is the best of one engine lowering per grid c."""
    edges = _dir()
    prob = Problem.directed(c=None, eps=0.5)
    r = solve(edges, prob)
    mp = prob.resolved_max_passes(edges.n_nodes)
    peel = jax.jit(
        lambda e, c: run_peel(e, DirectedST(eps=0.5, c=c), ExactBackend(), mp)
    )
    grid = c_grid(edges.n_nodes, prob.c_delta)
    refs = [peel(edges, jnp.float32(c)) for c in grid]
    rhos = [float(o.best_density) for o in refs]
    best = int(np.argmax(rhos))  # the first of equal densities, like solve
    assert r.extras["best_c"] == float(grid[best])
    np.testing.assert_array_equal(r.extras["c_density"], rhos)
    np.testing.assert_array_equal(r.extras["c_passes"], [int(o.passes) for o in refs])
    _same_result(r, refs[best])


def test_solve_sketch_matches_legacy_sketched():
    edges = _und()
    t, b, seed = 5, 1 << 12, 7
    prob = Problem.undirected(
        eps=0.5, backend="sketch", sketch_tables=t, sketch_buckets=b,
        sketch_seed=seed, track_history=True,
    )
    r = solve(edges, prob)
    mp = prob.resolved_max_passes(edges.n_nodes)
    ref = jax.jit(
        lambda e: run_peel(
            e, UndirectedThreshold(0.5),
            SketchBackend(make_sketch_params(t, b, seed)), mp, track_history=True,
        )
    )(edges)
    _same_result(r, ref)
    _same(r.history_n, ref.history_n)
    assert r.provenance.backend == "sketch"


def test_solve_pallas_matches_exact():
    edges = erdos_renyi(300, avg_deg=6, seed=4)
    rp = solve(
        edges, Problem.undirected(eps=0.5, backend="pallas", tile_size=128, tile_block=128)
    )
    re = solve(edges, Problem.undirected(eps=0.5))
    _same_result(rp, re)  # tiled degrees are exact arithmetic
    assert rp.provenance.backend == "pallas"


def test_solve_mesh_matches_jit():
    edges = _und()
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    rm = solve(edges, Problem.undirected(eps=0.5, substrate="mesh"), mesh=mesh)
    rj = solve(edges, Problem.undirected(eps=0.5))
    _same_result(rm, rj)
    assert rm.provenance.substrate == "mesh"


MESH_CELLS = {
    "at_least_k": (_und, lambda **kw: Problem.at_least_k(k=30, eps=0.5, **kw)),
    "directed": (_dir, lambda **kw: Problem.directed(c=1.0, eps=0.5, **kw)),
    "sketch": (_und, lambda **kw: Problem.undirected(
        eps=0.5, backend="sketch", sketch_tables=5, sketch_buckets=1 << 6,
        sketch_seed=7, **kw)),
}


@pytest.mark.parametrize("cell", sorted(MESH_CELLS))
def test_solve_mesh_cells_match_jit(cell):
    """The mesh cells of Algorithm 2, Algorithm 3 at c=1 and the §5.1
    sketch (64 buckets: colliding counters) answer as the jit substrate's
    uncompacted loop, bit for bit, on a one-device mesh."""
    graph, problem = MESH_CELLS[cell]
    edges = graph()
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    rm = solve(edges, problem(substrate="mesh"), mesh=mesh)
    rj = solve(edges, problem(compaction="off"))
    assert rm.provenance.substrate == "mesh"
    _same_full(rm, rj)


def test_solve_streaming_matches_jit_and_driver():
    edges = _und()
    rs = solve(
        edges,
        Problem.undirected(eps=0.5, substrate="streaming", stream_chunk=257,
                           stream_workers=2),
    )
    rj = solve(edges, Problem.undirected(eps=0.5))
    _same(rs.best_alive, rj.best_alive)
    assert float(rs.best_density) == pytest.approx(float(rj.best_density), rel=1e-5)
    assert int(rs.passes) == int(rj.passes)
    # And it is the StreamingDensest driver run directly.
    mask = np.asarray(edges.mask)
    st = StreamingDensest(
        chunked_from_arrays(
            np.asarray(edges.src)[mask], np.asarray(edges.dst)[mask],
            np.asarray(edges.weight)[mask], chunk=257,
        ),
        n_nodes=edges.n_nodes, eps=0.5, n_workers=2,
    ).run(resume=False)
    _same(rs.best_alive, st.best_alive)
    assert float(rs.best_density) == pytest.approx(st.best_rho, rel=1e-6)


# ---------------------------------------------------------------------------
# 2. solve_batch == loop of per-item solve, in one traced program
# ---------------------------------------------------------------------------


def test_solve_batch_eps_matches_loop():
    edges = _und()
    grid = [0.125, 0.25, 0.5, 1.0]  # f32-exact eps values
    prob = Problem.undirected(max_passes=48, track_history=True)
    s = Solver()
    rb = s.solve_batch(edges, prob, eps=grid)
    assert rb.provenance.batch == "eps"
    assert rb.best_alive.shape == (len(grid), edges.n_nodes)
    for i, e in enumerate(grid):
        ri = s.solve(edges, Problem.undirected(eps=e, max_passes=48, track_history=True))
        _same(rb.best_alive[i], ri.best_alive)
        assert float(rb.best_density[i]) == float(ri.best_density)
        assert int(rb.passes[i]) == int(ri.passes)
        _same(rb.history_n[i], ri.history_n)


def test_solve_batch_eps_at_least_k_matches_loop():
    edges = _und()
    grid = [0.25, 0.5, 1.0]
    s = Solver()
    rb = s.solve_batch(edges, Problem.at_least_k(k=30, max_passes=48), eps=grid)
    for i, e in enumerate(grid):
        ri = s.solve(edges, Problem.at_least_k(k=30, eps=e, max_passes=48))
        _same(rb.best_alive[i], ri.best_alive)
        assert float(rb.best_density[i]) == float(ri.best_density)


def test_solve_batch_c_matches_loop():
    edges = _dir()
    cs = [0.5, 1.0, 2.0, 4.0]
    s = Solver()
    rb = s.solve_batch(edges, Problem.directed(eps=0.5, max_passes=48), c=cs)
    assert rb.provenance.batch == "c"
    for i, c in enumerate(cs):
        ri = s.solve(edges, Problem.directed(c=c, eps=0.5, max_passes=48))
        _same(rb.best_alive[i], ri.best_alive)
        _same(rb.best_t[i], ri.best_t)
        assert float(rb.best_density[i]) == float(ri.best_density)
        assert int(rb.passes[i]) == int(ri.passes)


def test_solve_batch_graphs_matches_loop():
    g1 = erdos_renyi(250, avg_deg=6, seed=4)
    perm = np.random.default_rng(1).permutation(g1.src.shape[0])
    g2 = EdgeList(
        src=g1.src[perm], dst=g1.dst[perm], weight=g1.weight[perm],
        mask=g1.mask[perm], n_nodes=g1.n_nodes,
    )
    prob = Problem.undirected(eps=0.5, max_passes=32)
    s = Solver()
    rb = s.solve_batch([g1, g2], prob)
    assert rb.provenance.batch == "graphs"
    for i, g in enumerate((g1, g2)):
        ri = s.solve(g, prob)
        _same(rb.best_alive[i], ri.best_alive)
        assert float(rb.best_density[i]) == float(ri.best_density)


def test_solve_batch_is_one_program():
    """A 4-point eps sweep traces exactly once (one XLA program)."""
    edges = _und()
    s = Solver()
    s.solve_batch(edges, Problem.undirected(max_passes=32), eps=[0.25, 0.5, 1.0, 2.0])
    assert s.trace_count == 1
    assert s.cache_misses == 1
    # Same-shape re-run: cache hit, still no retrace.
    s.solve_batch(edges, Problem.undirected(max_passes=32), eps=[0.25, 0.5, 1.0, 2.0])
    assert s.trace_count == 1
    assert s.cache_hits == 1


def test_solve_batch_needs_exactly_one_axis():
    edges = _und()
    with pytest.raises(ValueError):
        solve_batch(edges, Problem.undirected())
    with pytest.raises(ValueError):
        solve_batch(edges, Problem.directed(c=1.0), eps=[0.5], c=[1.0])


# ---------------------------------------------------------------------------
# 3. Compile caching: repeated same-shape solves never retrace
# ---------------------------------------------------------------------------


def test_solver_cache_no_retrace_same_shape():
    """The off path compiles exactly one program per (shape, statics).
    (compaction defaults to 'auto' since the ROADMAP flip, so the one-
    program expectation needs the explicit 'off'; the ladder-default cache
    behavior is test_default_compaction_ladder_caches below.)"""
    edges = _und()
    perm = np.random.default_rng(0).permutation(edges.src.shape[0])
    other = EdgeList(
        src=edges.src[perm], dst=edges.dst[perm], weight=edges.weight[perm],
        mask=edges.mask[perm], n_nodes=edges.n_nodes,
    )
    s = Solver()
    prob = Problem.undirected(eps=0.5, compaction="off")
    s.solve(edges, prob)
    assert (s.trace_count, s.cache_misses, s.cache_hits) == (1, 1, 0)
    s.solve(other, prob)  # same shapes, different data
    assert (s.trace_count, s.cache_misses, s.cache_hits) == (1, 1, 1)
    s.solve(edges, prob)
    assert (s.trace_count, s.cache_misses, s.cache_hits) == (1, 1, 2)
    # A different static field is a different program.
    s.solve(edges, Problem.undirected(eps=0.25, compaction="off"))
    assert s.cache_misses == 2 and s.trace_count == 2


def test_default_compaction_ladder_caches():
    """The DEFAULT Problem now rides the geometric ladder (compaction='auto'
    -> geometric for exact backends, the ROADMAP flip): the first solve
    compiles one program per pow2 rung bucket; same-shape re-solves hit the
    program cache everywhere (no retrace anywhere in the ladder)."""
    edges = _und()
    s = Solver()
    r1 = s.solve(edges, Problem.undirected(eps=0.5))
    assert r1.provenance.compaction == "geometric"
    rungs = len(r1.extras["compaction"]["segments"])
    assert rungs >= 1
    assert s.trace_count == s.cache_misses  # one trace per rung bucket
    traces, misses = s.trace_count, s.cache_misses
    r2 = s.solve(edges, Problem.undirected(eps=0.5))
    assert (s.trace_count, s.cache_misses) == (traces, misses)
    assert s.cache_hits == rungs
    assert r2.provenance.cache_hit


def test_solve_batch_eps_keys_fixed_directed_c():
    """eps-sweep programs bake the fixed directed c into the closure, so a
    different c must be a cache MISS (regression: c was excluded from every
    key and the second c silently reused the first c's program)."""
    edges = _dir()
    s = Solver()
    r1 = s.solve_batch(edges, Problem.directed(c=1.0, max_passes=48), eps=[0.5])
    r8 = s.solve_batch(edges, Problem.directed(c=8.0, max_passes=48), eps=[0.5])
    assert s.cache_misses == 2
    for c, rb in ((1.0, r1), (8.0, r8)):
        ri = s.solve(edges, Problem.directed(c=c, eps=0.5, max_passes=48))
        _same(rb.best_alive[0], ri.best_alive)
        assert float(rb.best_density[0]) == float(ri.best_density)


def test_solve_batch_accepts_prestacked_edgelist():
    from repro.core import stack_graphs

    g1 = erdos_renyi(250, avg_deg=6, seed=4)
    perm = np.random.default_rng(1).permutation(g1.src.shape[0])
    g2 = EdgeList(
        src=g1.src[perm], dst=g1.dst[perm], weight=g1.weight[perm],
        mask=g1.mask[perm], n_nodes=g1.n_nodes,
    )
    prob = Problem.undirected(eps=0.5, max_passes=32)
    s = Solver()
    rb = s.solve_batch(stack_graphs([g1, g2]), prob)
    for i, g in enumerate((g1, g2)):
        _same(rb.best_alive[i], s.solve(g, prob).best_alive)


def test_cache_ignores_fields_the_program_never_reads():
    """Knobs of cells that are not running (streaming params on a jit solve,
    tile params on an exact backend) must not force a recompile — on the
    off path AND on the default ladder's per-rung programs."""
    edges = _und()
    s = Solver()
    s.solve(edges, Problem.undirected(eps=0.5, compaction="off"))
    s.solve(edges, Problem.undirected(eps=0.5, compaction="off",
                                      stream_workers=8, stream_chunk=64))
    s.solve(edges, Problem.undirected(eps=0.5, compaction="off",
                                      tile_size=256, wire_dtype="bf16"))
    s.solve(edges, Problem.undirected(eps=0.5, compaction="off",
                                      c_delta=3.0, sketch_buckets=1 << 8))
    assert s.cache_misses == 1 and s.cache_hits == 3 and s.trace_count == 1
    # Default (auto -> geometric) path: irrelevant knobs may not recompile
    # any ladder rung either.
    s2 = Solver()
    s2.solve(edges, Problem.undirected(eps=0.5))
    misses, traces = s2.cache_misses, s2.trace_count
    s2.solve(edges, Problem.undirected(eps=0.5, stream_workers=8, stream_chunk=64))
    s2.solve(edges, Problem.undirected(eps=0.5, tile_size=256))
    assert (s2.cache_misses, s2.trace_count) == (misses, traces)
    assert s2.cache_hits == 2 * misses


def test_solve_rejects_silently_dropped_kwargs():
    edges = _und()
    with pytest.raises(ValueError):
        solve(edges, Problem.undirected(), checkpoint_dir="/tmp/nope")


def test_auto_substrate_without_mesh_is_jit():
    """substrate='auto' with no mesh supplied must run (jit), whatever the
    host device count."""
    edges = _und()
    r = solve(edges, Problem.undirected(substrate="auto"))
    assert r.provenance.substrate == "jit"


def test_c_delta_validated():
    with pytest.raises(ValueError):
        Problem.directed(c_delta=1.0)


def test_auto_backend_resolves_exact_for_streaming():
    """streaming + backend='auto' must pick the exact cell even above the
    auto-sketch node threshold (the only cell the driver implements)."""
    p = Problem.undirected(backend="auto", substrate="streaming").resolve(5_000_000)
    assert p.backend == "exact"


def test_solver_cache_directed_shares_program_across_c():
    """c is a runtime scalar: the whole grid (and any fixed c) reuses ONE
    compiled program — the paper's ~35-min-per-c cost collapses.  (Pinned
    to compaction='off'; the ladder-path analogue is
    test_compaction_ladder_shares_programs_across_c.)"""
    edges = _dir()
    s = Solver()
    s.solve(edges, Problem.directed(c=1.0, eps=0.5, compaction="off"))
    s.solve(edges, Problem.directed(c=2.0, eps=0.5, compaction="off"))
    s.solve(edges, Problem.directed(c=None, eps=0.5, compaction="off"))  # grid
    assert s.trace_count == 1
    assert s.cache_misses == 1
    assert s.cache_hits == 2


# ---------------------------------------------------------------------------
# Compaction runtime: bit-identity matrix vs compaction='off'
# ---------------------------------------------------------------------------


def _same_full(a, b):
    """Every outcome array bit-identical (best/final sets, scalars, history)."""
    _same_result(a, b)
    _same(a.alive, b.alive)
    if np.asarray(a.t_alive).size:
        _same(a.t_alive, b.t_alive)


def _chung_lu():
    """The benchmark cells' degree law at exponent 2.2: a hub-dominated
    core (perfbench/graphgen.py)."""
    return chung_lu_power_law(n=2000, exponent=2.2, avg_deg=8.0, seed=6)


def _int_weighted(g, seed=0):
    """``g`` with integer edge weights from 2 to 5."""
    w = np.random.default_rng(seed).integers(2, 6, g.n_edges_padded)
    return dataclasses.replace(g, weight=jnp.asarray(w, jnp.float32))


def _floor():
    """A graph whose host ladder ends at its bucket floor: the last rung
    triggers and cannot compact, so it finishes uncompacted."""
    return planted_dense_subgraph(600, avg_deg=3, k=14, p_dense=0.9, seed=2)[0]


UNDIRECTED_GRAPHS = {"planted": _und, "chung_lu": _chung_lu, "floor": _floor}
WEIGHTED_GRAPHS = {
    "planted": (_und, _dir),
    "int_weights": (lambda: _int_weighted(_und()), lambda: _int_weighted(_dir())),
}


@pytest.mark.parametrize("graph", sorted(UNDIRECTED_GRAPHS))
@pytest.mark.parametrize("eps", [0.1, 0.5])
def test_compaction_undirected_jit_bit_identical(graph, eps):
    edges = UNDIRECTED_GRAPHS[graph]()
    s = Solver()
    off = s.solve(
        edges, Problem.undirected(eps=eps, track_history=True, compaction="off")
    )
    on = s.solve(
        edges,
        Problem.undirected(eps=eps, track_history=True, compaction="geometric"),
    )
    _same_full(off, on)
    _same(off.history_n, on.history_n)
    _same(off.history_rho, on.history_rho)
    assert on.provenance.compaction == "geometric"
    lad = on.extras["compaction"]
    assert lad["passes"] == int(off.passes)
    assert sum(seg["passes"] for seg in lad["segments"]) == int(off.passes)
    if graph == "floor":
        last, before = lad["segments"][-1], lad["segments"][-2]
        assert last["compact_below"] is None and last["passes"] > 0
        assert (last["n_buf"], last["m_buf"]) == (before["n_buf"], before["m_buf"])


@pytest.mark.parametrize("graph", sorted(WEIGHTED_GRAPHS))
def test_compaction_at_least_k_jit_bit_identical(graph):
    edges = WEIGHTED_GRAPHS[graph][0]()
    s = Solver()
    off = s.solve(edges, Problem.at_least_k(k=30, eps=0.5, compaction="off"))
    on = s.solve(edges, Problem.at_least_k(k=30, eps=0.5, compaction="geometric"))
    _same_full(off, on)


@pytest.mark.parametrize("graph", sorted(WEIGHTED_GRAPHS))
@pytest.mark.parametrize("c", [0.5, 1.0, 2.0])
def test_compaction_directed_jit_bit_identical(graph, c):
    edges = WEIGHTED_GRAPHS[graph][1]()
    s = Solver()
    off = s.solve(edges, Problem.directed(c=c, eps=0.5, compaction="off"))
    on = s.solve(edges, Problem.directed(c=c, eps=0.5, compaction="geometric"))
    _same_full(off, on)


def test_compaction_directed_grid_matches_off():
    edges = _dir()
    s = Solver()
    off = s.solve(edges, Problem.directed(c=None, eps=0.5))
    on = s.solve(edges, Problem.directed(c=None, eps=0.5, compaction="geometric"))
    assert on.extras["best_c"] == off.extras["best_c"]
    np.testing.assert_array_equal(on.extras["c_density"], off.extras["c_density"])
    np.testing.assert_array_equal(on.extras["c_passes"], off.extras["c_passes"])
    _same_result(on, off)


def test_compaction_pallas_backend_rides_the_ladder():
    edges = erdos_renyi(300, avg_deg=6, seed=4)
    s = Solver()
    prob = Problem.undirected(eps=0.5, backend="pallas", tile_size=128, tile_block=128)
    off = s.solve(edges, prob)
    on = s.solve(edges, dataclasses.replace(prob, compaction="geometric"))
    _same_full(off, on)


def test_compaction_mesh_substrate_bit_identical():
    edges = _und()
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    s = Solver()
    off = s.solve(edges, Problem.undirected(eps=0.2, substrate="mesh"), mesh=mesh)
    on = s.solve(
        edges,
        Problem.undirected(eps=0.2, substrate="mesh", compaction="geometric"),
        mesh=mesh,
    )
    _same_full(off, on)


def test_compaction_streaming_substrate_bit_identical():
    edges = _und()
    s = Solver()
    off = s.solve(
        edges,
        Problem.undirected(eps=0.5, substrate="streaming", stream_chunk=257,
                           stream_workers=2),
    )
    on = s.solve(
        edges,
        Problem.undirected(eps=0.5, substrate="streaming", stream_chunk=257,
                           stream_workers=2, compaction="geometric"),
    )
    _same(off.best_alive, on.best_alive)
    _same(off.alive, on.alive)
    assert float(off.best_density) == float(on.best_density)
    assert int(off.passes) == int(on.passes)


def test_compaction_scans_fewer_edge_slots():
    """The acceptance metric in miniature: the geometric ladder scans
    strictly fewer edge slots than passes x padded-m."""
    edges = _und()
    s = Solver()
    on = s.solve(edges, Problem.undirected(eps=0.1, compaction="geometric"))
    lad = on.extras["compaction"]
    off_slots = int(on.passes) * edges.n_edges_padded
    assert lad["edge_slots_scanned"] < off_slots


def test_compaction_ladder_shares_programs_across_c():
    """c is a runtime argument of segment programs too: rung cache keys for
    two fixed c values must be IDENTICAL (regression: c keyed the rungs and
    every fixed c recompiled the whole ladder)."""
    s = Solver()
    edges = _dir()
    p1 = Problem.directed(c=0.5, eps=0.5, compaction="geometric").resolve(edges.n_nodes)
    p2 = Problem.directed(c=1.0, eps=0.5, compaction="geometric").resolve(edges.n_nodes)
    for kind in ("cseg", "ladder_mesh"):
        k1 = s._key(kind, p1, 32, 128, 1024, "float32", (64,))
        k2 = s._key(kind, p2, 32, 128, 1024, "float32", (64,))
        assert k1 == k2
    a = s.solve(edges, Problem.directed(c=0.5, eps=0.5, compaction="geometric"))
    b = s.solve(edges, Problem.directed(c=1.0, eps=0.5, compaction="geometric"))
    _same_full(s.solve(edges, Problem.directed(c=0.5, eps=0.5)), a)
    _same_full(s.solve(edges, Problem.directed(c=1.0, eps=0.5)), b)


def test_compaction_ladder_programs_are_cached():
    """Same graph re-solved: every ladder rung must be a program-cache hit
    (the Solver keys rungs on bucket shape, not graph content)."""
    edges = _und()
    s = Solver()
    s.solve(edges, Problem.undirected(eps=0.25, compaction="geometric"))
    traces = s.trace_count
    r2 = s.solve(edges, Problem.undirected(eps=0.25, compaction="geometric"))
    assert s.trace_count == traces  # no retrace anywhere in the ladder
    assert r2.provenance.cache_hit


ZERO_PASS_GRAPHS = {
    "erdos_renyi": lambda: erdos_renyi(50, avg_deg=4, seed=0),
    "chung_lu": _chung_lu,
}


@pytest.mark.parametrize("graph", sorted(ZERO_PASS_GRAPHS))
def test_compaction_zero_pass_runs_match_off(graph):
    """Degenerate runs where the loop never executes a pass (k > n, or
    max_passes=0) must still match 'off', which returns the full initial
    set (regression: the ladder used to return an all-empty best set)."""
    edges = ZERO_PASS_GRAPHS[graph]()
    s = Solver()
    for prob in (
        Problem.at_least_k(k=edges.n_nodes + 10, eps=0.5, compaction="off"),
        Problem.undirected(eps=0.5, max_passes=0, compaction="off"),
    ):
        off = s.solve(edges, prob)
        on = s.solve(edges, dataclasses.replace(prob, compaction="geometric"))
        _same_full(off, on)


def test_compaction_auto_resolution_and_validation():
    # 'auto' is the DEFAULT since the ROADMAP flip (PR 5).
    assert Problem().compaction == "auto"
    # auto -> geometric for exact, and for sketch on the jit substrate.
    assert Problem.undirected(compaction="auto").resolve(100).compaction == "geometric"
    # An explicit ladder steers backend='auto' to exact even above the
    # sketch threshold.
    big = Problem.undirected(backend="auto", compaction="geometric").resolve(2_000_000)
    assert big.backend == "exact" and big.compaction == "geometric"
    assert (
        Problem.undirected(backend="sketch", compaction="auto").resolve(100).compaction
        == "geometric"
    )
    sk = Problem.undirected(backend="sketch", compaction="geometric").resolve(100)
    assert (sk.backend, sk.substrate, sk.compaction) == ("sketch", "jit", "geometric")
    # The mesh ladder counts exact degrees only: a sketch there stays off.
    mesh_sk = Problem.undirected(backend="sketch", substrate="mesh")
    assert mesh_sk.resolve(100).compaction == "off"
    with pytest.raises(ValueError):
        dataclasses.replace(mesh_sk, compaction="geometric").resolve(100)
    with pytest.raises(ValueError):
        Problem(compaction="nope")
    # Explicit ladder modes are rejected by the batched driver; auto is not.
    edges = _und()
    with pytest.raises(ValueError):
        solve_batch(
            edges, Problem.undirected(max_passes=16, compaction="geometric"),
            eps=[0.5],
        )
    rb = solve_batch(
        edges, Problem.undirected(max_passes=16, compaction="auto"), eps=[0.5]
    )
    assert rb.provenance.compaction == "off"


def test_twophase_schedule_is_rejected():
    """The one compaction schedule is the geometric ladder: 'twophase' is
    an unknown mode, and Problem has no phase-1 pass budget."""
    with pytest.raises(ValueError, match="compaction='twophase' not in"):
        Problem(compaction="twophase")
    with pytest.raises(TypeError):
        Problem(twophase_passes=8)


@pytest.mark.parametrize("method", ["solve", "solve_batch"])
def test_degree_fn_hook_is_rejected(method):
    """Degrees come from ``Problem.backend``; there is no custom-degree
    hook on the front door."""
    edges = _und()
    kw = {"eps": [0.5]} if method == "solve_batch" else {}
    with pytest.raises(TypeError, match="degree_fn"):
        getattr(Solver(), method)(
            edges, Problem.undirected(compaction="off"),
            degree_fn=lambda e, w: w, **kw,
        )


# ---------------------------------------------------------------------------
# Result type
# ---------------------------------------------------------------------------


def test_result_is_pytree_with_static_provenance():
    edges = _und()
    r = solve(edges, Problem.undirected(eps=0.5))
    jax.block_until_ready(r)
    leaves = jax.tree_util.tree_leaves(r)
    assert any(l.shape == (edges.n_nodes,) for l in leaves)
    mapped = jax.tree_util.tree_map(lambda x: x, r)
    assert mapped.provenance == r.provenance  # static metadata survives
    assert r.nodes().size == int(r.best_size)
    assert isinstance(r, DenseSubgraphResult)


def test_problem_validation():
    with pytest.raises(ValueError):
        Problem(objective="nope")
    with pytest.raises(ValueError):
        Problem(objective="at_least_k")  # k missing
    with pytest.raises(ValueError):
        Problem.directed(c=1.0, backend="pallas").resolve(100)
    with pytest.raises(ValueError):
        Problem.undirected(substrate="streaming", backend="sketch").resolve(100)
    # auto axes resolve to concrete cells.
    p = Problem.undirected(backend="auto", substrate="auto").resolve(100)
    assert p.backend == "exact" and p.substrate in ("jit", "mesh")


# ---------------------------------------------------------------------------
# Satellite: streaming chunk reducer dtype stability
# ---------------------------------------------------------------------------


def test_chunk_stats_accumulates_float32():
    from repro.core.streaming import _chunk_stats

    src = jnp.asarray([0, 1, 2, 0], jnp.int32)
    dst = jnp.asarray([1, 2, 3, 2], jnp.int32)
    alive = jnp.ones((4,), bool)
    for dtype in (jnp.bfloat16, jnp.float16, jnp.float32):
        deg, total, n_ok = _chunk_stats(src, dst, jnp.ones((4,), dtype), alive)
        assert deg.dtype == jnp.float32
        assert total.dtype == jnp.float32
        assert float(total) == 4.0
        assert int(n_ok) == 4  # the geometric-compaction trigger count
        np.testing.assert_array_equal(np.asarray(deg), [2.0, 2.0, 3.0, 1.0])
    # Dead-endpoint edges drop out of the alive count.
    deg, total, n_ok = _chunk_stats(
        src, dst, jnp.ones((4,), jnp.float32), alive.at[3].set(False)
    )
    assert int(n_ok) == 3 and float(total) == 3.0
