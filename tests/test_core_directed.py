"""Algorithm 3 (directed) tests: approximation vs brute force, c-grid search,
pass bound, planted S->T recovery."""

import numpy as np
import pytest

from repro.core import Problem, c_grid, densest_directed_brute, solve, solve_batch
from repro.graph import from_numpy
from repro.graph.generators import directed_planted, erdos_renyi


def _directed(edges, c, eps):
    """Algorithm 3 at one c, on the uncompacted loop."""
    return solve(edges, Problem.directed(c=c, eps=eps, compaction="off"))


def _search(edges, eps, delta=2.0):
    """Algorithm 3 over the geometric c grid, on the uncompacted loop:
    (result, best c, per-c densities, per-c passes)."""
    res = solve(
        edges, Problem.directed(c=None, eps=eps, c_delta=delta, compaction="off")
    )
    ex = res.extras
    return res, ex["best_c"], np.asarray(ex["c_density"]), np.asarray(ex["c_passes"])


def test_directed_brute_comparison_tiny():
    rng = np.random.default_rng(0)
    for seed in range(4):
        n = 7
        src = rng.integers(0, n, 16)
        dst = rng.integers(0, n, 16)
        keep = src != dst
        edges = from_numpy(src[keep], dst[keep], n, directed=True)
        _, _, rho_star = densest_directed_brute(edges)
        res, best_c, _, _ = _search(edges, eps=0.05, delta=1.3)
        # (2+2eps) * delta guarantee.
        bound = rho_star / (2 * 1.05 * 1.3)
        assert float(res.best_density) >= bound - 1e-6
        assert float(res.best_density) <= rho_star + 1e-6


def test_planted_directed_block():
    edges, s_ids, t_ids = directed_planted(
        300, avg_deg=3, ks=20, kt=15, p_dense=0.9, seed=1
    )
    res, best_c, rhos, passes = _search(edges, eps=0.5, delta=2.0)
    s_found = set(np.nonzero(np.asarray(res.best_s))[0].tolist())
    t_found = set(np.nonzero(np.asarray(res.best_t))[0].tolist())
    assert len(s_found & set(s_ids.tolist())) >= 0.7 * len(s_ids)
    assert len(t_found & set(t_ids.tolist())) >= 0.7 * len(t_ids)
    # Planted block has ~sqrt(20*15)*0.9 density; background ~3.
    assert float(res.best_density) > 5.0


def test_directed_pass_bound():
    edges = erdos_renyi(500, avg_deg=6, seed=2, directed=True)
    r = _directed(edges, c=1.0, eps=0.5)
    # Lemma 13: O(log_{1+eps} n) for each of S and T.
    import math

    bound = 2 * (math.ceil(math.log(500) / math.log(1.5)) + 4)
    assert int(r.passes) <= bound


def test_c_grid_covers_range():
    grid = c_grid(1000, delta=2.0)
    assert grid.min() <= 1.0 / 1000
    assert grid.max() >= 1000
    # Geometric spacing.
    ratios = grid[1:] / grid[:-1]
    assert np.allclose(ratios, 2.0, rtol=1e-5)


def test_best_pair_density_matches_recomputation():
    edges, _, _ = directed_planted(200, avg_deg=3, ks=12, kt=12, p_dense=0.8, seed=5)
    res = _directed(edges, c=1.0, eps=0.5)
    s = np.asarray(res.best_s)
    t = np.asarray(res.best_t)
    mask = np.asarray(edges.mask)
    src = np.asarray(edges.src)[mask]
    dst = np.asarray(edges.dst)[mask]
    m_in = np.sum(s[src] & t[dst])
    expect = m_in / np.sqrt(s.sum() * t.sum())
    assert float(res.best_density) == pytest.approx(float(expect), rel=1e-5)


def test_vmapped_c_search_matches_loop():
    """One-program vmapped c-grid == the python-loop search (same densities
    for every c, same winner)."""
    edges, _, _ = directed_planted(
        n=2000, avg_deg=5.0, ks=40, kt=16, p_dense=0.5, seed=4
    )
    best, best_c, rhos, passes = _search(edges, eps=0.5)
    cs = c_grid(edges.n_nodes, 2.0)
    sweep = solve_batch(edges, Problem.directed(eps=0.5), c=cs)
    vrhos = np.asarray(sweep.best_density)
    np.testing.assert_allclose(vrhos, rhos, rtol=1e-5)
    best_i = int(np.argmax(vrhos))
    assert float(cs[best_i]) == best_c
    assert float(vrhos[best_i]) == pytest.approx(float(best.best_density), rel=1e-6)
