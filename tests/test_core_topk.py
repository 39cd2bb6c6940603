"""Algorithm 2 (size >= k) tests: size constraint, approximation, pass count."""

import numpy as np
import pytest

from repro.core import Problem, densest_subgraph_exact, solve
from repro.graph.generators import erdos_renyi, planted_dense_subgraph


def _peel_k(edges, k, eps):
    """Algorithm 2 through the front door, on the uncompacted loop."""
    return solve(edges, Problem.at_least_k(k=k, eps=eps, compaction="off"))


@pytest.mark.parametrize("k", [5, 20, 60])
def test_size_constraint_respected(k):
    edges = erdos_renyi(150, avg_deg=8, seed=0)
    res = _peel_k(edges, k=k, eps=0.5)
    assert int(res.best_size) >= k
    alive = np.asarray(res.best_alive)
    assert alive.sum() == int(res.best_size)


def test_matches_unconstrained_when_k_small():
    """Lemma 10 regime: if the optimum has more than k nodes, Algorithm 2
    achieves the same (2+2eps) guarantee."""
    edges = erdos_renyi(150, avg_deg=10, seed=1)
    nodes_star, rho_star = densest_subgraph_exact(edges)
    k = max(2, len(nodes_star) // 2)
    res = _peel_k(edges, k=k, eps=0.25)
    assert float(res.best_density) >= rho_star / (2 * 1.25) - 1e-6


def test_theorem9_bound_when_k_large():
    """(3+3eps) guarantee vs the size-constrained optimum (checked against the
    unconstrained optimum which upper-bounds it)."""
    edges, _ = planted_dense_subgraph(300, avg_deg=4, k=25, p_dense=0.9, seed=2)
    k = 100  # force a set bigger than the planted block
    res = _peel_k(edges, k=k, eps=0.5)
    assert int(res.best_size) >= k
    _, rho_star = densest_subgraph_exact(edges)
    # rho*_{>=k} <= rho*; the bound below is necessary, not sufficient, but
    # catches gross regressions.
    assert float(res.best_density) <= rho_star + 1e-5
    assert float(res.best_density) > 0.0


def test_fractional_removal_makes_more_passes():
    """Algorithm 2 removes fewer nodes per pass than Algorithm 1 =>
    at least as many passes."""
    edges = erdos_renyi(400, avg_deg=8, seed=3)
    p1 = int(solve(edges, Problem.undirected(eps=0.5, compaction="off")).passes)
    p2 = int(_peel_k(edges, k=2, eps=0.5).passes)
    assert p2 >= p1
