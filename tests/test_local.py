"""Andersen local exploration (core/local.py) + the ``substrate='local'``
lowering (core/api.py) + the serving engine's ``extraction='local'`` mode
(serve/densest.py).

Contracts under test:

  * **explorer invariants** — candidates are sorted, unique, contain the
    seed, respect the budget; repeated queries on one explorer are
    deterministic and leave the scratch arrays clean;
  * **pruning semantics** — a clique closes over itself while a pendant
    path hanging off it is pruned away (``frontier_exhausted``); budget=1
    returns exactly the seed; an isolated seed exhausts immediately;
  * **api lowering** — ``Problem(substrate='local')`` resolution
    (exact backend, compaction forced off), the validation matrix
    (directed objective, sketch/pallas backend, turnstile, mesh, missing
    seed, seed on a whole-graph substrate), provenance + ``extras['local']``
    counters, and the surviving guarantee (result nodes ⊆ candidates,
    density <= exact optimum);
  * **program-cache reuse** — repeated local queries at one bucket never
    retrace;
  * **serving parity** — ``DensestQueryEngine(extraction='local')``
    answers bit-identically to the api front door and the budget-halving
    degrade rung returns REAL (recomputed) data.
"""

import dataclasses

import numpy as np
import pytest

from repro import faults
from repro.core import Problem, Solver, densest_subgraph_brute, solve
from repro.core.local import LocalExplorer, check_count, check_seed
from repro.faults import FaultPlan
from repro.graph.edgelist import from_numpy
from repro.graph.generators import planted_dense_subgraph
from repro.serve.densest import DensestQueryEngine, ResilienceConfig

EPS = 0.5
PROB = Problem.undirected(eps=EPS)
PROB_LOCAL = dataclasses.replace(PROB, substrate="local")


def _planted(n=400, k=30, seed=7):
    return planted_dense_subgraph(n, 4.0, k, 0.6, seed=seed)


def _clique_plus_path(kq=6, path_len=5):
    """A kq-clique with a pendant path hanging off node 0."""
    src, dst = [], []
    for u in range(kq):
        for v in range(u + 1, kq):
            src.append(u)
            dst.append(v)
    for i in range(path_len):
        a = 0 if i == 0 else kq + i - 1
        src.append(a)
        dst.append(kq + i)
    n = kq + path_len
    return from_numpy(np.asarray(src), np.asarray(dst), n), n


# ---------------------------------------------------------------------------
# explorer invariants
# ---------------------------------------------------------------------------


def test_explore_invariants_and_determinism():
    g, planted = _planted()
    ex = LocalExplorer.from_edgelist(g)
    for s in [int(planted[0]), 0, 17]:
        a = ex.explore(s, budget=64)
        b = ex.explore(s, budget=64)  # same explorer: scratch must be clean
        np.testing.assert_array_equal(a.candidates, b.candidates)
        c = a.candidates
        assert s in c
        assert len(c) <= 64
        assert np.array_equal(c, np.unique(c))  # sorted + unique
        assert a.nodes_touched >= len(c)
        assert a.edges_scanned > 0
    # Scratch arrays are fully reset after queries.
    assert not ex._member.any()
    assert not ex._deg_t.any()


def test_budget_one_returns_exactly_the_seed():
    g, _ = _planted()
    ex = LocalExplorer.from_edgelist(g)
    a = ex.explore(5, budget=1)
    np.testing.assert_array_equal(a.candidates, [5])
    assert a.rounds == 0


def test_isolated_seed_exhausts_immediately():
    g = from_numpy(np.asarray([0]), np.asarray([1]), 4)  # nodes 2,3 isolated
    ex = LocalExplorer.from_edgelist(g)
    a = ex.explore(3, budget=8)
    np.testing.assert_array_equal(a.candidates, [3])
    assert a.frontier_exhausted


def test_pruning_keeps_clique_drops_pendant_path():
    g, n = _clique_plus_path(kq=6, path_len=5)
    ex = LocalExplorer.from_edgelist(g)
    a = ex.explore(1, budget=n)
    # The clique closes over itself; every path vertex beyond the first
    # has deg 1 into T < rho(T), so the pruning stops the walk down the
    # path and reports the set as closed.
    assert set(range(6)) <= set(a.candidates.tolist())
    assert a.frontier_exhausted
    assert (6 + 4) not in a.candidates  # path tail never admitted
    assert len(a.candidates) < n


def test_volume_cap_skips_hub_rows():
    """A power-law hub one hop from the seed is never admitted (so never
    scanned) when its row does not fit in the work budget, while the small
    rows around it still are — total work stays <= budget * volume_factor
    by construction."""
    # seed 0 -- {1 (hub, degree 1001), 2, 3, 4, 5}; the hub's other edges
    # fan out to 1000 fresh nodes.
    src = [0, 0, 0, 0, 0] + [1] * 1000
    dst = [1, 2, 3, 4, 5] + list(range(6, 1006))
    g = from_numpy(np.asarray(src), np.asarray(dst), 1006)
    ex = LocalExplorer.from_edgelist(g)
    a = ex.explore(0, budget=50, volume_factor=2)  # cap = 100 slots
    assert 1 not in a.candidates  # hub skipped, not scanned
    assert {2, 3, 4, 5} <= set(a.candidates.tolist())
    assert a.edges_scanned <= 100
    # With room for the hub's row the same exploration admits it.
    b = ex.explore(0, budget=50, volume_factor=50)
    assert 1 in b.candidates


def test_alpha_zero_disables_density_pruning():
    g, n = _clique_plus_path(kq=6, path_len=5)
    ex = LocalExplorer.from_edgelist(g)
    # alpha=0 admits any frontier vertex with >= 1 tie: plain BFS growth,
    # so the whole connected component is eventually swallowed.
    a = ex.explore(1, budget=n, max_rounds=n, alpha=0.0)
    assert len(a.candidates) == n


def test_seed_and_count_validation():
    g, _ = _planted(n=50, k=8)
    ex = LocalExplorer.from_edgelist(g)
    with pytest.raises(TypeError):
        check_seed(2.5, 50)
    with pytest.raises(TypeError):
        check_seed(True, 50)
    with pytest.raises(TypeError):
        check_seed("5", 50)
    with pytest.raises(ValueError):
        check_seed(-1, 50)
    with pytest.raises(ValueError):
        check_seed(50, 50)
    assert check_seed(np.int64(5), 50) == 5
    with pytest.raises(ValueError):
        ex.explore(5, budget=0)
    with pytest.raises(TypeError):
        ex.explore(5, budget=2.0)
    with pytest.raises(ValueError):
        ex.explore(5, alpha=-0.5)
    with pytest.raises(ValueError):
        check_count(0, "radius")
    directed = from_numpy(
        np.asarray([0]), np.asarray([1]), 3, directed=True
    )
    with pytest.raises(ValueError, match="undirected"):
        LocalExplorer.from_edgelist(directed)


# ---------------------------------------------------------------------------
# api lowering: Problem(substrate='local')
# ---------------------------------------------------------------------------


def test_resolve_forces_exact_backend_and_no_compaction():
    r = PROB_LOCAL.resolve(1000)
    assert r.substrate == "local"
    assert r.backend == "exact"
    assert r.compaction == "off"


def test_problem_validation_matrix():
    with pytest.raises(ValueError, match="undirected"):
        dataclasses.replace(Problem.directed(), substrate="local").resolve(10)
    with pytest.raises(ValueError, match="candidate"):
        dataclasses.replace(PROB_LOCAL, backend="sketch").resolve(10)
    with pytest.raises(ValueError, match="turnstile"):
        dataclasses.replace(PROB_LOCAL, stream_mode="turnstile").resolve(10)
    with pytest.raises(ValueError):
        dataclasses.replace(PROB_LOCAL, local_budget=0)
    with pytest.raises(ValueError):
        dataclasses.replace(PROB_LOCAL, local_rounds=0)
    with pytest.raises(ValueError):
        dataclasses.replace(PROB_LOCAL, local_alpha=-1.0)


def test_solve_validation_matrix():
    g, _ = _planted(n=60, k=8)
    with pytest.raises(ValueError, match="seed"):
        solve(g, PROB_LOCAL)  # missing seed
    with pytest.raises(ValueError, match="per-seed"):
        solve(g, PROB, seed=3)  # seed on a whole-graph substrate
    with pytest.raises(ValueError, match="mesh"):
        Solver().solve(g, PROB_LOCAL, seed=3, mesh=object())
    with pytest.raises(TypeError, match="degree_fn"):
        Solver().solve(g, PROB_LOCAL, seed=3, degree_fn=lambda *a: None)


def test_solve_local_provenance_extras_and_guarantee():
    g, planted = _planted()
    s = int(planted[0])
    res = solve(g, PROB_LOCAL, seed=s)
    assert res.provenance.substrate == "local"
    assert res.provenance.backend == "exact"
    assert res.provenance.compaction == "off"
    info = res.extras["local"]
    assert info["seed"] == s
    cand = info["candidates"]
    assert s in cand
    assert info["n_candidates"] == len(cand)
    assert info["nodes_touched"] >= info["n_candidates"]
    # The answer is a genuine subgraph of the candidate set...
    nodes = res.nodes()
    assert set(nodes.tolist()) <= set(np.asarray(cand).tolist())
    assert int(res.best_size) == len(nodes)
    # ...so its density never exceeds the exact optimum of a small graph.
    small, sp = _planted(n=18, k=6, seed=3)
    _, rho_star = densest_subgraph_brute(small)
    r2 = solve(small, PROB_LOCAL, seed=int(sp[0]))
    assert float(r2.best_density) <= rho_star + 1e-5


def test_local_queries_share_one_cached_program():
    g, planted = _planted()
    solver = Solver()
    r1 = solver.solve(g, PROB_LOCAL, seed=int(planted[0]))
    n_traces = solver.trace_count
    r2 = solver.solve(g, PROB_LOCAL, seed=int(planted[1]))
    assert solver.trace_count == n_traces  # same pow2 bucket: no retrace
    assert r2.provenance.cache_hit
    assert r1.extras["local"]["bucket"] == r2.extras["local"]["bucket"]


# ---------------------------------------------------------------------------
# serving parity: extraction='local'
# ---------------------------------------------------------------------------


def test_engine_local_matches_api_bitwise():
    g, planted = _planted()
    eng = DensestQueryEngine(
        g, PROB, solver=Solver(), extraction="local", max_wait_ms=0.0
    )
    solver = Solver()
    for s in [int(planted[0]), 0, 17]:
        r = eng.query(s)
        assert r.status == "ok"
        api = solver.solve(g, PROB_LOCAL, seed=s)
        assert r.density == float(api.best_density)
        np.testing.assert_array_equal(
            r.nodes, np.flatnonzero(np.asarray(api.best_alive))
        )
    st = eng.stats()
    assert st["local_nodes_touched"] > 0
    assert st["local_edges_scanned"] > 0


def test_engine_accepts_local_substrate_problem():
    g, planted = _planted()
    eng = DensestQueryEngine(
        g,
        dataclasses.replace(PROB_LOCAL, local_budget=128),
        solver=Solver(),
        max_wait_ms=0.0,
    )
    assert eng.extraction == "local"
    assert eng.local_budget == 128
    r = eng.query(int(planted[0]))
    assert r.status == "ok"
    assert r.density == float(
        Solver()
        .solve(
            g,
            dataclasses.replace(PROB_LOCAL, local_budget=128),
            seed=int(planted[0]),
        )
        .best_density
    )


def test_engine_knob_validation():
    g, _ = _planted(n=60, k=8)
    bfs = DensestQueryEngine(g, PROB, solver=Solver(), max_wait_ms=0.0)
    loc = DensestQueryEngine(
        g, PROB, solver=Solver(), extraction="local", max_wait_ms=0.0
    )
    with pytest.raises(ValueError, match="radius"):
        loc.query(3, 2)  # radius on a local engine
    with pytest.raises(ValueError, match="budget"):
        bfs.query(3, budget=16)  # budget on a bfs engine
    with pytest.raises(ValueError, match="extraction"):
        DensestQueryEngine(g, PROB, solver=Solver(), extraction="dfs")
    directed_prob = Problem.directed()
    with pytest.raises(ValueError):
        DensestQueryEngine(
            g, directed_prob, solver=Solver(), extraction="local"
        )


def test_engine_budget_override_and_degrade_rung():
    g, planted = _planted()
    s = int(planted[0])
    cfg = ResilienceConfig(
        max_retries=0, degrade_turnstile=False, degrade_last_good=False
    )
    eng = DensestQueryEngine(
        g,
        PROB,
        solver=Solver(),
        extraction="local",
        max_wait_ms=0.0,
        resilience=cfg,
    )
    # Per-query budget override answers normally.
    r = eng.query(s, budget=128)
    assert r.status == "ok" and r.n_ego <= 128
    # Poison the default-budget bucket: the first degrade rung halves the
    # budget and answers with REAL data (identical to the direct solve).
    padded, _ = eng.extract(s, budget=eng.local_budget)
    gkey = (padded.n_nodes, padded.n_edges_padded)
    plan = FaultPlan().fail_prob("serve.solve", 1.0, key=gkey)
    with faults.active(plan):
        res = eng.query(s)
    assert res.status == "degraded"
    assert res.fallback == "budget:256"
    small, _ = eng.extract(s, budget=256)
    want = Solver().solve(small, PROB.resolve(small.n_nodes))
    assert res.density == float(want.best_density)
