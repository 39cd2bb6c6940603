"""The sorted-source degree count on the front door.

On a graph whose real slots come first with ``src`` non-decreasing and unit
weights, the exact undirected rule reads the ``src`` half of the degree
count off a prefix sum at CSR offsets (``engine.SortedSrcBackend``).  The
host builds the offsets once per buffer: on rung 0 of the jit ladder from
the downloaded graph, on every later rung inside ``_compact_rung``'s
chunked gather, and once per ``solve_batch(eps=...)`` call.  Answers are
bit for bit the scatter path's; ``extras["degree_count"]`` and each
ladder segment's ``src_reduce`` say which route ran.
"""

import numpy as np
import pytest

from repro.core import Problem, Solver, api
from repro.graph.edgelist import EdgeList, from_numpy
from repro.graph.generators import chung_lu_power_law, directed_planted

FIELDS = ("best_alive", "best_t", "best_density", "best_size", "passes",
          "alive", "history_n", "history_m", "history_rho")
EPS = [0.125, 0.5, 1.0, 2.0]


@pytest.fixture(scope="module")
def graph():
    """A src-sorted unit-weight Chung-Lu graph (``dedup_edges`` sorts)."""
    return chung_lu_power_law(240_000, avg_deg=8, seed=7)


def _host(g):
    return tuple(np.asarray(a) for a in (g.src, g.dst, g.weight, g.mask))


def _permuted(g, seed=0):
    src, dst, w, _ = _host(g)
    p = np.random.default_rng(seed).permutation(len(src))
    return from_numpy(src[p], dst[p], g.n_nodes, weight=w[p])


def _reference_offsets(src, msk, n):
    return np.searchsorted(src[: int(msk.sum())], np.arange(n + 1))


def _assert_same(a, b):
    for f in FIELDS:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), f


def _scatter_only(monkeypatch):
    """The scatter path on the same input: no buffer gets offsets."""
    monkeypatch.setattr(api, "_src_offsets", lambda *a, **k: None)


# ---------------------------------------------------------------------------
# Host offsets
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("parallel", [False, True])
def test_every_rung_offsets_equal_searchsorted(graph, monkeypatch, parallel):
    """The offsets of rung 0 and of every compacted rung equal a numpy
    ``searchsorted`` of the rung's real ``src``, with one chunk and with
    the pool's many."""
    if parallel:
        monkeypatch.setattr(api, "_COMPACT_PARALLEL_MIN_EDGES", 1)
    seen = []
    src_offsets, compact_rung = api._src_offsets, api._compact_rung

    def rung0(src, w, msk, n):
        off = src_offsets(src, w, msk, n)
        seen.append((api._compact_chunks(int(msk.sum())), n, src, msk, off))
        return off

    def compact(*args, **kw):
        nxt = compact_rung(*args, **kw)
        if nxt is not None:
            seen.append((args[7], len(nxt.s_al), nxt.src, nxt.msk, nxt.src_offsets))
        return nxt

    monkeypatch.setattr(api, "_src_offsets", rung0)
    monkeypatch.setattr(api, "_compact_rung", compact)
    res = Solver().solve(graph, Problem.undirected(eps=0.5))
    assert len(seen) == len(res.extras["compaction"]["segments"]) >= 4
    for chunks, n, src, msk, off in seen:
        assert (chunks > 1) == parallel
        assert off.dtype == np.int32 and len(off) == n + 1
        np.testing.assert_array_equal(off, _reference_offsets(src, msk, n))


@pytest.mark.parametrize("parallel", [False, True])
@pytest.mark.parametrize("case", ["tail_padding", "interior_padding",
                                  "unsorted", "weighted", "out_of_range"])
def test_src_offsets_apply_only_to_sorted_unit_prefix_buffers(
        case, parallel, monkeypatch):
    if parallel:
        monkeypatch.setattr(api, "_COMPACT_PARALLEL_MIN_EDGES", 1)
    rng = np.random.default_rng(1)
    n, m = 50, 400
    src = np.sort(rng.integers(0, n - 3, m)).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = np.ones(m, np.float32)
    msk = np.ones(m, bool)
    if case == "tail_padding":
        src, dst = np.r_[src, np.zeros(9, np.int32)], np.r_[dst, np.zeros(9, np.int32)]
        w, msk = np.r_[w, np.zeros(9, np.float32)], np.r_[msk, np.zeros(9, bool)]
    elif case == "interior_padding":
        msk[100] = False
    elif case == "unsorted":
        src[[10, 300]] = src[[300, 10]]
    elif case == "weighted":
        w[5] = 2.0
    else:
        src[-1] = n
    off = api._src_offsets(src, w, msk, n)
    if case == "tail_padding":
        np.testing.assert_array_equal(off, _reference_offsets(src, msk, n))
        assert off[-4:].tolist() == [m] * 4  # empty last nodes
    else:
        assert off is None


# ---------------------------------------------------------------------------
# Answers and counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("objective", ["undirected", "at_least_k"])
def test_ladder_solve_is_bit_identical_to_the_scatter_path(
        graph, monkeypatch, objective):
    prob = (Problem.undirected(eps=0.5, track_history=True)
            if objective == "undirected"
            else Problem.at_least_k(k=200, eps=0.5, track_history=True))
    fast = Solver().solve(graph, prob)
    segs = fast.extras["compaction"]["segments"]
    assert len(segs) > 1 and {s["src_reduce"] for s in segs} == {"sorted"}
    assert dict(fast.extras["degree_count"]) == {
        "sorted_src_passes": int(fast.passes), "scatter_src_passes": 0}
    _scatter_only(monkeypatch)
    slow = Solver().solve(graph, prob)
    assert {s["src_reduce"] for s in slow.extras["compaction"]["segments"]} == {"scatter"}
    _assert_same(fast, slow)
    assert [s["passes"] for s in segs] == [
        s["passes"] for s in slow.extras["compaction"]["segments"]]


def test_eps_sweep_is_bit_identical_to_the_scatter_path(graph, monkeypatch):
    prob = Problem.undirected(track_history=True)
    solver = Solver()
    fast = solver.solve_batch(graph, prob, eps=EPS)
    assert dict(fast.extras["degree_count"]) == {
        "sorted_src_passes": int(np.sum(fast.passes)), "scatter_src_passes": 0}
    _scatter_only(monkeypatch)
    slow = solver.solve_batch(graph, prob, eps=EPS)
    assert dict(slow.extras["degree_count"])["sorted_src_passes"] == 0
    assert solver.cache_misses == 2  # the route keys its own program
    _assert_same(fast, slow)


def _directed():
    return directed_planted(400, avg_deg=3, ks=15, kt=12, p_dense=0.9, seed=5)[0]


def _weighted(g):
    src, dst, _, _ = _host(g)
    w = 1.0 + (np.arange(len(src)) % 3).astype(np.float32)
    return from_numpy(src, dst, g.n_nodes, weight=w)


def _interior_padding(g):
    src, dst, w, msk = _host(g)
    msk = msk.copy()
    msk[len(msk) // 2] = False
    return EdgeList(src=src, dst=dst, weight=w, mask=msk, n_nodes=g.n_nodes)


SCATTER_CASES = {
    "unsorted": (_permuted, Problem.undirected(eps=0.5)),
    "weighted": (_weighted, Problem.undirected(eps=0.5)),
    "interior_padding": (_interior_padding, Problem.undirected(eps=0.5)),
    "sketch": (lambda g: g, Problem.undirected(eps=0.5, backend="sketch",
                                               sketch_buckets=4096)),
    "directed": (lambda g: _directed(), Problem.directed(eps=0.5, c=1.0)),
}


@pytest.mark.parametrize("case", sorted(SCATTER_CASES))
def test_counter_reads_all_scatter_where_the_count_does_not_apply(
        graph, monkeypatch, case):
    make, prob = SCATTER_CASES[case]
    g = make(graph)
    res = Solver().solve(g, prob)
    assert {s["src_reduce"] for s in res.extras["compaction"]["segments"]} == {"scatter"}
    assert dict(res.extras["degree_count"]) == {
        "sorted_src_passes": 0, "scatter_src_passes": int(res.passes)}
    if case in ("unsorted", "interior_padding"):
        # Integer degrees: the same edges in any order, or one fewer real
        # slot, peel exactly as the scatter path always has.
        _scatter_only(monkeypatch)
        _assert_same(res, Solver().solve(g, prob))
    if case == "unsorted":
        _assert_same(res, Solver().solve(graph, prob))


def test_eps_sweep_counter_reads_all_scatter_on_an_unsorted_graph(graph):
    g = _permuted(graph)
    res = Solver().solve_batch(g, Problem.undirected(), eps=EPS)
    assert dict(res.extras["degree_count"]) == {
        "sorted_src_passes": 0, "scatter_src_passes": int(np.sum(res.passes))}
    _assert_same(res, Solver().solve_batch(graph, Problem.undirected(), eps=EPS))
