"""The host ladder's compaction between rungs (``api._compact_rung``).

The edge range is cut into chunks that are filtered and gathered apart, on a
thread pool when there are several; the buffers must be those of one plain
pass over the whole range, bit for bit, for every chunk count.  A solve whose
rungs take the chunked path must answer as the one-chunk ladder and
``compaction='off'`` do.
"""

import dataclasses
import threading

import numpy as np
import pytest

from repro import constants
from repro.core import Problem, Solver, api
from repro.graph.generators import directed_planted, planted_dense_subgraph
from repro.graph.partition import pow2_bucket

CHUNKS = (1, 2, 3, 7, 64)
M, N = 3000, 700


def _reference(src, dst, w, msk, s_al, t_al, id_map):
    """The compaction as one numpy statement over the whole edge range."""
    surv = s_al if t_al is None else s_al | t_al
    ta = s_al if t_al is None else t_al
    ok = msk & s_al[src] & ta[dst]
    e_alive, n_alive = int(ok.sum()), int(surv.sum())
    new_m = pow2_bucket(max(e_alive, 1), constants.COMPACT_MIN_EDGES)
    new_n = pow2_bucket(max(n_alive, 1), constants.COMPACT_MIN_NODES)
    if new_m >= len(src) and new_n >= len(s_al):
        return None
    relabel = np.cumsum(surv) - 1
    keep = np.nonzero(ok)[0]
    out = [np.zeros(new_m, a.dtype) for a in (src, dst, w)]
    out[0][: len(keep)] = relabel[src[keep]]
    out[1][: len(keep)] = relabel[dst[keep]]
    out[2][: len(keep)] = w[keep]
    new_msk = np.zeros(new_m, bool)
    new_msk[: len(keep)] = True
    new_s = np.zeros(new_n, bool)
    new_s[:n_alive] = s_al[surv]
    new_t = None
    if t_al is not None:
        new_t = np.zeros(new_n, bool)
        new_t[:n_alive] = t_al[surv]
    id_map = id_map[surv[: len(id_map)]]
    return (*out, new_msk, new_s, new_t, id_map, e_alive, n_alive)


def _rung(case, seed=0):
    """Host buffers of one rung: ``(src, dst, w, msk, s_al, t_al, id_map)``."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N, M).astype(np.int32)
    dst = rng.integers(0, N, M).astype(np.int32)
    w = rng.integers(1, 5, M).astype(np.float32)
    msk = np.ones(M, bool)
    s_al = rng.random(N) < 0.5
    t_al = None
    id_map = np.sort(rng.choice(10 * N, N, replace=False)).astype(np.int64)
    if case == "empty_chunks":
        # Survivors only in the first 40 slots: most chunks keep nothing.
        s_al = np.zeros(N, bool)
        s_al[:40] = True
        src[:40] = dst[:40] = np.arange(40)
    elif case == "all_edges":
        # Every edge among the first 300 nodes, and those nodes alive.
        src %= 300
        dst %= 300
        s_al = np.zeros(N, bool)
        s_al[:300] = True
    elif case == "no_edge":
        s_al = np.zeros(N, bool)
        s_al[::7] = True
        dst[:] = src[:] = 1  # node 1 is dead
    elif case == "directed":
        t_al = rng.random(N) < 0.3
    elif case == "padded":
        msk[M - 700:] = False
        msk[::5] = False
    elif case == "floor":
        # Nearly every edge survives: the next bucket is no smaller.
        s_al = np.ones(N, bool)
        s_al[-3:] = False
    return src, dst, w, msk, s_al, t_al, id_map


CASES = ("empty_chunks", "all_edges", "no_edge", "directed", "padded", "floor")


@pytest.mark.parametrize("chunks", CHUNKS)
@pytest.mark.parametrize("case", CASES)
def test_chunked_compaction_is_the_plain_compaction(case, chunks):
    rung = _rung(case)
    want = _reference(*rung)
    got = api._compact_rung(*rung, chunks=chunks)
    if case == "floor":
        assert want is None and got is None
        return
    assert got is not None
    if case == "all_edges":
        assert got.e_alive == int(rung[3].sum())
    if case == "no_edge":
        assert got.e_alive == 0
    for name, a, b in zip(api._Compacted._fields, got, want):
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name
        else:
            assert a == b, name


def _reference_keeping_ids(src, dst, w, msk, s_al, t_al, id_map):
    """The edges-only compaction as one numpy statement."""
    surv = s_al if t_al is None else s_al | t_al
    ta = s_al if t_al is None else t_al
    ok = msk & s_al[src] & ta[dst]
    e_alive, n_alive = int(ok.sum()), int(surv.sum())
    new_m = pow2_bucket(max(e_alive, 1), constants.COMPACT_MIN_EDGES)
    if new_m >= len(src):
        return None
    keep = np.nonzero(ok)[0]
    out = [np.zeros(new_m, a.dtype) for a in (src, dst, w)]
    for o, a in zip(out, (src, dst, w)):
        o[: len(keep)] = a[keep]
    new_msk = np.zeros(new_m, bool)
    new_msk[: len(keep)] = True
    return (*out, new_msk, s_al, t_al, id_map, e_alive, n_alive)


@pytest.mark.parametrize("chunks", CHUNKS)
@pytest.mark.parametrize("case", CASES)
def test_compaction_keeping_ids_gathers_edges_only(case, chunks):
    """Endpoints keep their ids and the node arrays come back as they are;
    a rung whose edges fill the next bucket (``all_edges`` too, where only
    nodes would shrink) is the floor."""
    rung = _rung(case)
    want = _reference_keeping_ids(*rung)
    got = api._compact_rung(*rung, chunks=chunks, keep_ids=True)
    if case in ("floor", "all_edges"):
        assert want is None and got is None
        return
    assert got is not None
    for name, a, b in zip(api._Compacted._fields, got, want):
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name
        else:
            assert a == b, name


@pytest.mark.parametrize("m_buf, parallel", [
    (constants.COMPACT_PARALLEL_MIN_EDGES - 1, False),
    (constants.COMPACT_PARALLEL_MIN_EDGES, True),
    (2**26, True),
])
def test_only_large_rungs_are_chunked(m_buf, parallel):
    chunks = api._compact_chunks(m_buf)
    assert (chunks > 1) == parallel
    if parallel:
        assert chunks == api._COMPACT_WORKERS * constants.COMPACT_CHUNKS_PER_WORKER
    assert 1 <= api._COMPACT_WORKERS <= constants.COMPACT_MAX_WORKERS


def _und():
    return planted_dense_subgraph(260, avg_deg=4, k=25, p_dense=0.8, seed=3)[0]


def _dir():
    return directed_planted(200, avg_deg=3, ks=15, kt=12, p_dense=0.9, seed=5)[0]


FIELDS = ("best_alive", "best_t", "best_density", "best_size", "passes",
          "alive", "t_alive", "history_n", "history_m", "history_rho")


@pytest.mark.parametrize("directed", [False, True])
def test_chunked_ladder_answers_as_one_chunk_and_off(monkeypatch, directed):
    graph = _dir() if directed else _und()
    if directed:
        prob = Problem.directed(c=1.0, eps=0.5, track_history=True)
    else:
        prob = Problem.undirected(eps=0.1, track_history=True)
    geo = dataclasses.replace(prob, compaction="geometric")
    solver = Solver()
    off = solver.solve(graph, dataclasses.replace(prob, compaction="off"))
    one = solver.solve(graph, geo)
    monkeypatch.setattr(api, "_COMPACT_PARALLEL_MIN_EDGES", 1)
    chunked = solver.solve(graph, geo)
    segs = [s for s in chunked.extras["compaction"]["segments"] if "compact_chunks" in s]
    assert segs, "the ladder never compacted"
    assert all(s["compact_chunks"] > 1 for s in segs)
    assert all(s.get("compact_chunks", 1) == 1
               for s in one.extras["compaction"]["segments"])
    for field in FIELDS:
        a = np.asarray(getattr(chunked, field))
        for ref in (one, off):
            b = np.asarray(getattr(ref, field))
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), field
    assert chunked.extras["compaction"]["edge_slots_scanned"] == \
        one.extras["compaction"]["edge_slots_scanned"]


def test_pool_threads_do_not_outlive_the_interpreter():
    """The pool's threads are joined at exit: none is a daemon that the
    interpreter would abandon mid-gather."""
    rung = _rung("padded")
    api._compact_rung(*rung, chunks=7)
    pool = [t for t in threading.enumerate() if t.name.startswith("densest-compact")]
    assert pool and not any(t.daemon for t in pool)
