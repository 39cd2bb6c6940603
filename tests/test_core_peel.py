"""Algorithm 1 behaviour tests: approximation guarantee, pass bound, best-set
semantics, weighted graphs, planted-structure recovery."""

import numpy as np
import pytest

from repro.core import (
    charikar_greedy,
    densest_subgraph,
    densest_subgraph_brute,
    densest_subgraph_exact,
    density_of,
    max_passes_bound,
)
from repro.graph import from_numpy
from repro.graph.generators import (
    chung_lu_power_law,
    erdos_renyi,
    lemma5_instance,
    planted_dense_subgraph,
    weighted_preferential,
)


def _density_np(edges, nodes):
    mask = np.asarray(edges.mask)
    src = np.asarray(edges.src)[mask]
    dst = np.asarray(edges.dst)[mask]
    w = np.asarray(edges.weight)[mask]
    inset = np.zeros(edges.n_nodes, bool)
    inset[nodes] = True
    return float(np.sum(w * (inset[src] & inset[dst]))) / max(len(nodes), 1)


def test_k4_plus_pendant():
    # K4 on {0,1,2,3} plus pendant 4: densest subgraph is K4 (rho=1.5).
    src = [0, 0, 0, 1, 1, 2, 3]
    dst = [1, 2, 3, 2, 3, 3, 4]
    edges = from_numpy(src, dst, 5)
    res = densest_subgraph(edges, eps=0.001)
    alive = np.nonzero(np.asarray(res.best_alive))[0]
    assert set(alive.tolist()) == {0, 1, 2, 3}
    assert float(res.best_density) == pytest.approx(1.5)


def test_reported_density_matches_recomputation():
    edges = erdos_renyi(200, avg_deg=8, seed=1)
    res = densest_subgraph(edges, eps=0.3)
    nodes = np.nonzero(np.asarray(res.best_alive))[0]
    assert float(res.best_density) == pytest.approx(_density_np(edges, nodes), rel=1e-5)
    assert float(density_of(edges, res.best_alive)) == pytest.approx(
        float(res.best_density), rel=1e-5
    )


@pytest.mark.parametrize("eps", [0.001, 0.1, 0.5, 1.0])
@pytest.mark.parametrize("seed", [0, 1])
def test_approximation_guarantee_vs_exact(eps, seed):
    """Lemma 3: output density >= rho* / (2+2eps) — mirrors paper Table 2."""
    edges = erdos_renyi(120, avg_deg=10, seed=seed)
    _, rho_star = densest_subgraph_exact(edges)
    res = densest_subgraph(edges, eps=eps)
    assert float(res.best_density) >= rho_star / (2 * (1 + eps)) - 1e-6
    assert float(res.best_density) <= rho_star + 1e-6


def test_pass_bound_lemma4():
    """Lemma 4: O(log_{1+eps} n) passes."""
    for eps in (0.1, 0.5, 1.0):
        edges = chung_lu_power_law(3000, avg_deg=10, seed=0)
        res = densest_subgraph(edges, eps=eps)
        assert int(res.passes) <= max_passes_bound(3000, eps)


@pytest.mark.parametrize("n,m", [(2000, 9000), (500, 4000)])
def test_chung_lu_exact_edge_count(n, m):
    """``n_edges`` gives exactly m distinct loop-free undirected edges (the
    Table 1 shapes name n and m, not an average degree)."""
    edges = chung_lu_power_law(n, seed=3, n_edges=m)
    src = np.asarray(edges.src).astype(np.int64)
    dst = np.asarray(edges.dst).astype(np.int64)
    assert len(src) == m and edges.n_nodes == n
    assert np.all(src < dst)
    assert len(np.unique(src * n + dst)) == m


def test_planted_dense_block_recovered():
    edges, planted = planted_dense_subgraph(500, avg_deg=4, k=30, p_dense=0.8, seed=3)
    res = densest_subgraph(edges, eps=0.25)
    found = set(np.nonzero(np.asarray(res.best_alive))[0].tolist())
    # The dense block dominates; recovered set should be mostly the planted one.
    overlap = len(found & set(planted.tolist()))
    assert overlap >= 0.8 * len(planted)
    assert len(found) <= 3 * len(planted)


def test_weighted_graph_support():
    # Two triangles; one has weight-10 edges -> must win.
    src = np.array([0, 1, 0, 3, 4, 3])
    dst = np.array([1, 2, 2, 4, 5, 5])
    w = np.array([1, 1, 1, 10, 10, 10], np.float32)
    edges = from_numpy(src, dst, 6, weight=w)
    res = densest_subgraph(edges, eps=0.1)
    alive = set(np.nonzero(np.asarray(res.best_alive))[0].tolist())
    assert alive == {3, 4, 5}
    assert float(res.best_density) == pytest.approx(10.0)


def test_weighted_preferential_lemma6_runs_many_passes():
    """Lemma 6's weighted preferential-attachment instance forces more passes
    than a comparable ER graph at the same eps."""
    g_w = weighted_preferential(256)
    g_er = erdos_renyi(256, avg_deg=16, seed=0)
    p_w = int(densest_subgraph(g_w, eps=0.5).passes)
    p_er = int(densest_subgraph(g_er, eps=0.5).passes)
    assert p_w >= p_er


def test_lemma5_instance_pass_count_grows():
    """Lemma 5 construction: passes grow with k (Omega(k/log k))."""
    p_small = int(densest_subgraph(lemma5_instance(3), eps=0.5).passes)
    p_big = int(densest_subgraph(lemma5_instance(5), eps=0.5).passes)
    assert p_big > p_small >= 2


def test_matches_brute_force_on_tiny_graphs():
    rng = np.random.default_rng(0)
    for trial in range(5):
        n = 9
        m = 14
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        keep = src != dst
        edges = from_numpy(src[keep], dst[keep], n)
        _, rho_star = densest_subgraph_brute(edges)
        res = densest_subgraph(edges, eps=0.05)
        assert float(res.best_density) >= rho_star / 2.1 - 1e-6
        assert float(res.best_density) <= rho_star + 1e-6


def test_history_trajectory_is_consistent():
    edges = erdos_renyi(300, avg_deg=8, seed=5)
    res = densest_subgraph(edges, eps=0.5)
    t = int(res.passes)
    hn = np.asarray(res.history_n)[:t]
    # Node count strictly decreases (at least one removal per pass).
    assert (np.diff(hn) < 0).all()
    assert hn[0] == 300
    # Density history contains the best density.
    hr = np.asarray(res.history_rho)[:t]
    assert float(res.best_density) == pytest.approx(float(hr.max()), rel=1e-6)


def test_charikar_baseline_quality():
    """The paper's [10] baseline: our eps->0 run should be close to it."""
    edges = erdos_renyi(150, avg_deg=10, seed=2)
    _, rho_greedy = charikar_greedy(edges)
    res = densest_subgraph(edges, eps=0.001)
    # Batched removal with tiny eps ~ Charikar; allow small slack.
    assert float(res.best_density) >= 0.9 * rho_greedy
