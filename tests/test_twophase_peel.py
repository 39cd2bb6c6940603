"""Compacted mesh peels == the uncompacted mesh peel (same best density and
set): compaction is pure re-bucketing, Lemma 4 bounds the shrink.  Runs on
a 1-device mesh (the collective structure is identical)."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import repro.core.api as api_mod
from repro.core import Problem, solve
from repro.graph import generators as gen


def _mesh():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))


@pytest.mark.parametrize("seed,eps", [(0, 0.5), (1, 1.0), (2, 0.3)])
def test_twophase_matches_single_phase(seed, eps, monkeypatch):
    """The single-program mesh ladder (mesh × geometric) against the
    uncompacted mesh loop (mesh × off), bit for bit.  A small bucket floor
    gives these few-hundred-edge graphs several rungs."""
    monkeypatch.setattr(api_mod, "_LADDER_MIN_EDGES", 64)
    edges, _ = gen.planted_dense_subgraph(
        n=400, avg_deg=4.0, k=40, p_dense=0.6, seed=seed
    )
    mesh = _mesh()
    one = solve(
        edges, Problem.undirected(eps=eps, substrate="mesh", compaction="off"),
        mesh=mesh,
    )
    lad = solve(
        edges,
        Problem.undirected(eps=eps, substrate="mesh", compaction="geometric"),
        mesh=mesh,
    )
    assert lad.extras["compaction"]["single_program"]
    assert len(lad.extras["compaction"]["segments"]) > 1
    for field in ("best_alive", "best_density", "best_size", "passes", "alive"):
        a, b = np.asarray(getattr(one, field)), np.asarray(getattr(lad, field))
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), field


def test_twophase_lemma4_bound_holds():
    """After k passes the alive count is below n/(1+eps)^k (the static size
    the compaction relies on)."""
    edges = gen.chung_lu_power_law(n=5000, exponent=2.0, avg_deg=10.0, seed=3)
    eps = 0.5
    res = solve(
        edges, Problem.undirected(eps=eps, track_history=True, compaction="off")
    )
    hn = np.asarray(res.history_n)[: int(res.passes)]
    for k in range(1, len(hn)):
        assert hn[k] <= edges.n_nodes / (1 + eps) ** k + 1e-9


def test_distributed_topk_meets_guarantee():
    """Distributed Algorithm 2: |S~| >= k and rho(S~) within (3+3eps) of the
    best-known >=k density (checked against exhaustive peel candidates)."""
    from repro.core.density import density_of

    eps, k = 0.5, 30
    edges, _ = gen.planted_dense_subgraph(
        n=300, avg_deg=4.0, k=25, p_dense=0.8, seed=7
    )
    r = solve(
        edges,
        Problem.at_least_k(
            k=k, eps=eps, substrate="mesh", min_deg_fallback=False,
            ceil_count=True, compaction="off",
        ),
        mesh=_mesh(),
    )
    n_sel = int(np.asarray(r.best_alive).sum())
    assert n_sel >= k
    # density of the returned set really is its density
    assert float(density_of(edges, r.best_alive)) == pytest.approx(
        float(r.best_density), rel=1e-5
    )
    # agrees with the single-device Algorithm 2 within the approximation
    ref = solve(edges, Problem.at_least_k(k=k, eps=eps, compaction="off"))
    assert float(r.best_density) >= float(ref.best_density) / (3 * (1 + eps))
