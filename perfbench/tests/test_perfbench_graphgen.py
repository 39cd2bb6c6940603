"""The device Chung-Lu generator: exactly n nodes and m distinct undirected
edges, no self-loops, sorted, and the same graph for the same seed."""

import numpy as np
import pytest

from perfbench import graphgen

N, M = 4000, 24000


def _edges(seed, n=N, m=M):
    src, dst = graphgen.generate(n, m, 2.2, seed)
    return np.asarray(src), np.asarray(dst)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_exact_distinct_undirected_edges(seed):
    src, dst = _edges(seed)
    assert src.dtype == np.int32 and dst.dtype == np.int32
    assert len(src) == M and len(dst) == M
    assert (src < dst).all()  # no self-loops, each edge stored once
    assert src.min() >= 0 and dst.max() < N
    key = src.astype(np.int64) * N + dst
    assert (np.diff(key) > 0).all()  # sorted and distinct


def test_same_seed_same_graph_other_seed_other_graph():
    a, b = _edges(3), _edges(3)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    c = _edges(4)
    assert not np.array_equal(a[0], c[0])
    # Seeds that share their low 32 bits still give different graphs.
    d = _edges(3 + 2**32)
    assert not np.array_equal(a[0], d[0])


def test_degree_law_matches_host_generator():
    """The same degree law as ``chung_lu_power_law``: edge ends per band of
    node ids agree with the host generator's to a few percent."""
    from repro.graph.generators import chung_lu_power_law

    n, m = 20000, 150000
    src, dst = _edges(1, n=n, m=m)
    host = chung_lu_power_law(n, exponent=2.2, seed=1, n_edges=m)
    ours = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    theirs = np.bincount(np.asarray(host.src), minlength=n) + np.bincount(
        np.asarray(host.dst), minlength=n
    )
    for lo, hi in [(0, 10), (10, 100), (100, 1000), (1000, 20000)]:
        assert ours[lo:hi].sum() == pytest.approx(theirs[lo:hi].sum(), rel=0.05)


def test_draw_count_clears_m():
    for n, m in [(N, M), (976_000, 7_600_000)]:
        draws = graphgen.draws_for(n, m, 2.2)
        assert m < draws < 1.2 * m
