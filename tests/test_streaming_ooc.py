"""Out-of-core streaming runtime: async pipeline, exception safety, spill
ladder.

Covers the exception-safe chunk pipeline (real errors propagate,
first-success-wins under speculation), the bounded-prefetch residency
contract, bit-identity of the async pipeline vs the synchronous path, the
memmap spill ladder (bit-identity + checkpoint/resume mid-ladder), and the
rung-trigger accounting regression.
"""

import numpy as np
import pytest

from repro.core import Problem, Solver
from repro.core.streaming import (
    StreamingDensest,
    chunked_from_arrays,
    chunked_from_memmap,
)
from repro.graph.edgelist import (
    EdgeSpillWriter,
    open_edge_spill,
    open_edges_memmap,
    save_edges_memmap,
)
from repro.graph.generators import erdos_renyi, planted_dense_subgraph


def _edges_np(edges):
    mask = np.asarray(edges.mask)
    return (
        np.asarray(edges.src)[mask],
        np.asarray(edges.dst)[mask],
        np.asarray(edges.weight)[mask],
    )


@pytest.fixture(scope="module")
def graph():
    edges = erdos_renyi(500, avg_deg=8, seed=3)
    return edges, _edges_np(edges)


# ---------------------------------------------------------------------------
# Exception safety
# ---------------------------------------------------------------------------


def test_failing_chunk_stream_raises_real_error(graph):
    """A chunk stream that raises on one chunk surfaces ITS error (the seed
    bug swallowed it into a downstream ``KeyError: idx``)."""
    edges, (src, dst, w) = graph
    base = chunked_from_arrays(src, dst, w, chunk=97)

    def bad_stream():
        for i, c in enumerate(base()):
            if i == 3:
                raise RuntimeError("chunk 3 exploded")
            yield c

    drv = StreamingDensest(bad_stream, n_nodes=edges.n_nodes, n_workers=3)
    with pytest.raises(RuntimeError, match="chunk 3 exploded"):
        drv.run(resume=False)


def test_failing_chunk_worker_raises_real_error(graph):
    """A chunk whose WORKER fails (bad payload) raises the worker's real
    exception, not KeyError — with and without speculation."""
    edges, (src, dst, w) = graph
    base = chunked_from_arrays(src, dst, w, chunk=97)

    def poisoned():
        for i, (s, d, ww) in enumerate(base()):
            if i == 2:
                yield s, d, np.array(["boom"] * len(ww), object)
            else:
                yield s, d, ww

    for speculative in (False, True):
        drv = StreamingDensest(
            poisoned, n_nodes=edges.n_nodes, n_workers=3,
            speculative=speculative,
        )
        with pytest.raises(TypeError):
            drv.run(resume=False)


def test_flaky_chunk_first_success_wins(graph, monkeypatch):
    """A transiently failing chunk is retried (speculative duplicate of a
    failed attempt) and the pass completes with the successful result."""
    import repro.core.streaming as sm

    edges, (src, dst, w) = graph
    ref = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=97), n_nodes=edges.n_nodes
    ).run(resume=False)

    orig = sm._chunk_stats
    state = {"failed": False}

    def flaky(s, d, ww, alive):
        if not state["failed"]:
            state["failed"] = True
            raise OSError("transient chunk read error")
        return orig(s, d, ww, alive)

    monkeypatch.setattr(sm, "_chunk_stats", flaky)
    drv = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=97),
        n_nodes=edges.n_nodes, n_workers=3, speculative=True,
    )
    st = drv.run(resume=False)
    assert drv.speculative_reissues >= 1
    assert st.best_rho == ref.best_rho
    assert (st.best_alive == ref.best_alive).all()


def test_failed_pass_keeps_previous_checkpoint(graph, tmp_path):
    """Exception safety of the deferred finalization: a pass that explodes
    must not lose the previously completed pass's checkpoint."""
    edges, (src, dst, w) = graph
    base = chunked_from_arrays(src, dst, w, chunk=200)
    calls = {"n": 0}

    def explode_on_third_pass():
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("pass 3 stream lost")
        yield from base()

    ck = str(tmp_path / "ck")
    drv = StreamingDensest(
        explode_on_third_pass, n_nodes=edges.n_nodes, checkpoint_dir=ck
    )
    with pytest.raises(RuntimeError, match="pass 3 stream lost"):
        drv.run(resume=False)
    st = drv._load()
    assert st is not None and st.pass_idx == 2  # both completed passes saved


# ---------------------------------------------------------------------------
# Async pipeline: residency bound + bit-identity vs the synchronous path
# ---------------------------------------------------------------------------


def test_prefetch_bounds_resident_chunks(graph):
    edges, (src, dst, w) = graph
    for prefetch in (1, 2, 5):
        drv = StreamingDensest(
            chunked_from_arrays(src, dst, w, chunk=64),  # ~30+ chunks
            n_nodes=edges.n_nodes, n_workers=4, prefetch=prefetch,
        )
        drv.run(resume=False)
        assert 0 < drv.peak_resident_chunks <= prefetch
        assert drv.peak_resident_edges <= prefetch * 64


@pytest.mark.parametrize("chunk", [64, 257, 1000])
def test_async_pipeline_bit_identical_to_sync(graph, chunk):
    """The in-order reduce frontier makes the async pipeline bit-identical
    to a synchronous one-chunk-at-a-time pass, for every chunk size."""
    edges, (src, dst, w) = graph
    sync = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=chunk),
        n_nodes=edges.n_nodes, n_workers=1, prefetch=1, speculative=False,
    ).run(resume=False)
    drv = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=chunk),
        n_nodes=edges.n_nodes, n_workers=4, prefetch=6,
        speculative=True, speculate_tail_frac=0.5,
    )
    st = drv.run(resume=False)
    assert st.best_rho == sync.best_rho  # exact, not approx
    assert (st.best_alive == sync.best_alive).all()
    assert (st.alive == sync.alive).all()
    assert st.pass_idx == sync.pass_idx
    assert st.history == sync.history


# ---------------------------------------------------------------------------
# History record: (n_alive, e_alive, rho) — not total weight
# ---------------------------------------------------------------------------


def test_history_records_alive_edge_count(tmp_path):
    """With non-unit weights the middle history slot is the alive EDGE
    COUNT (the seed recorded total weight against the documented (n, m,
    rho) contract), and the checkpoint reshape(-1, 3) round-trips."""
    edges = erdos_renyi(300, avg_deg=6, seed=7)
    src, dst, w = _edges_np(edges)
    w = w * 3.5  # make weight != edge count
    ck = str(tmp_path / "ck")
    drv = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=128),
        n_nodes=edges.n_nodes, checkpoint_dir=ck,
    )
    st = drv.run(resume=False)
    n0, m0, rho0 = st.history[0]
    assert n0 == edges.n_nodes
    assert m0 == len(src)  # alive edge count, not 3.5x the weight
    assert rho0 == pytest.approx(3.5 * len(src) / edges.n_nodes)
    loaded = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=128),
        n_nodes=edges.n_nodes, checkpoint_dir=ck,
    )._load()
    assert [tuple(map(float, h)) for h in loaded.history] == [
        tuple(map(float, h)) for h in st.history
    ]


# ---------------------------------------------------------------------------
# Compaction ladder: rung-trigger accounting + spill
# ---------------------------------------------------------------------------


def test_compact_stream_returns_padded_slot_total(graph):
    """Regression: ``_compact_stream`` must return the PADDED slot total of
    the rebuilt stream (the quantity the rung trigger compares against and
    the next ``_pass_stats`` reports), not the unpadded kept-edge count."""
    edges, (src, dst, w) = graph
    drv = StreamingDensest(
        chunked_from_arrays(src, dst, w, chunk=100),
        n_nodes=edges.n_nodes, compaction="geometric",
    )
    alive_c = np.zeros(edges.n_nodes, bool)
    alive_c[: edges.n_nodes // 3] = True  # kill 2/3 of the nodes
    id_map = np.arange(edges.n_nodes, dtype=np.int64)
    stream, new_alive, new_id_map, n_slots = drv._compact_stream(
        chunked_from_arrays(src, dst, w, chunk=100), alive_c, id_map, 1
    )
    from repro.graph.partition import pow2_bucket

    rebuilt = list(stream())
    assert n_slots == sum(len(c[0]) for c in rebuilt)  # what a pass streams
    kept = int((alive_c[src] & alive_c[dst]).sum())
    assert n_slots >= kept  # pow2 padding
    per_chunk_kept = [
        int((alive_c[s] & alive_c[d]).sum())
        for s, d, _ in chunked_from_arrays(src, dst, w, chunk=100)()
    ]
    assert n_slots == sum(
        pow2_bucket(k, floor=256) for k in per_chunk_kept if k > 0
    )


def _run_geo(stream, n_nodes, eps=0.2, **kw):
    drv = StreamingDensest(
        stream, n_nodes=n_nodes, eps=eps, compaction="geometric", **kw
    )
    return drv.run(resume=False), drv


def test_spill_ladder_bit_identical_and_out_of_core(tmp_path):
    """The acceptance criterion: a memmap-backed stream whose ladder
    survivors exceed the residency cap completes via ``spill_dir``,
    bit-identical to ``compaction='off'``, with bounded host residency."""
    edges, _ = planted_dense_subgraph(800, avg_deg=6, k=40, p_dense=0.8, seed=0)
    src, dst, w = _edges_np(edges)
    store = save_edges_memmap(str(tmp_path / "store"), src, dst, w)
    stream = chunked_from_memmap(store, chunk=512)

    off = StreamingDensest(stream, n_nodes=edges.n_nodes, eps=0.2).run(
        resume=False
    )
    cap = 600  # pipeline window (1 x 512) fits; ladder survivors do not
    with pytest.raises(RuntimeError, match="spill_dir"):
        # Proof the scenario is real: without a spill the survivors of the
        # first rung overflow this cap.
        _run_geo(stream, edges.n_nodes, residency_cap_edges=cap, prefetch=1)
    st, drv = _run_geo(
        stream, edges.n_nodes,
        spill_dir=str(tmp_path / "spill"), residency_cap_edges=cap,
        prefetch=1,
    )
    assert drv.compactions >= 1 and drv.spill_rungs == drv.compactions
    assert st.best_rho == off.best_rho
    assert (st.best_alive == off.best_alive).all()
    assert st.pass_idx == off.pass_idx
    assert st.history == off.history
    # Host residency never exceeded the pipeline window (the rebuilt
    # streams lived on disk): cap >> window, so this bounds both.
    assert drv.peak_resident_edges <= cap
    # The final rung is on disk and finalized.
    assert drv._cur_rung_dir is not None
    assert open_edge_spill(drv._cur_rung_dir) is not None


def test_residency_cap_without_spill_raises(tmp_path):
    edges, _ = planted_dense_subgraph(800, avg_deg=6, k=40, p_dense=0.8, seed=0)
    src, dst, w = _edges_np(edges)
    stream = chunked_from_arrays(src, dst, w, chunk=512)
    with pytest.raises(RuntimeError, match="spill_dir"):
        _run_geo(stream, edges.n_nodes, residency_cap_edges=64)


@pytest.mark.parametrize("spill", [False, True])
def test_resume_mid_ladder_equivalence(tmp_path, spill):
    """Kill a geometric run mid-ladder; resuming (with or without a spill)
    must reproduce the uninterrupted run exactly."""
    edges = erdos_renyi(600, avg_deg=8, seed=1)
    src, dst, w = _edges_np(edges)
    stream = chunked_from_arrays(src, dst, w, chunk=500)
    ref, ref_drv = _run_geo(stream, edges.n_nodes)
    assert ref_drv.compactions >= 1  # the scenario really is mid-ladder

    kw = dict(checkpoint_dir=str(tmp_path / "ck"))
    if spill:
        kw["spill_dir"] = str(tmp_path / "spill")
    # Run from scratch, stop mid-ladder, then resume to completion.
    drv1 = StreamingDensest(
        stream, n_nodes=edges.n_nodes, eps=0.2, compaction="geometric", **kw
    )
    st1 = drv1.run(max_passes=4, resume=False)
    assert st1.pass_idx == 4
    drv2 = StreamingDensest(
        stream, n_nodes=edges.n_nodes, eps=0.2, compaction="geometric", **kw
    )
    st = drv2.run(resume=True)
    assert st.best_rho == ref.best_rho
    assert (st.best_alive == ref.best_alive).all()
    assert st.pass_idx == ref.pass_idx
    assert st.history == ref.history
    if spill:
        assert drv1.spill_rungs >= 1  # the interrupted run spilled


def test_resume_never_adopts_foreign_spill_rung(tmp_path):
    """Regression: a spill_dir shared with an earlier, different-eps run
    must not leak that run's final rung into a later resume (fresh starts
    clear foreign rungs; manifests are stamped with eps)."""
    edges = erdos_renyi(600, avg_deg=8, seed=1)
    src, dst, w = _edges_np(edges)
    stream = chunked_from_arrays(src, dst, w, chunk=500)
    kw = dict(
        checkpoint_dir=str(tmp_path / "ck"), spill_dir=str(tmp_path / "spill")
    )

    # Run A (eps=0.3) completes, leaving its final rung in spill_dir.
    a = StreamingDensest(
        stream, n_nodes=edges.n_nodes, eps=0.3, compaction="geometric", **kw
    )
    a.run(resume=False)
    assert a.spill_rungs >= 1

    # Run B (eps=0.2) starts fresh in the SAME spill_dir, dies mid-ladder,
    # then resumes — it must reproduce the uninterrupted eps=0.2 run, not a
    # hybrid seeded from run A's survivor stream.
    ref, _ = _run_geo(stream, edges.n_nodes)  # eps=0.2, no spill
    b1 = StreamingDensest(
        stream, n_nodes=edges.n_nodes, eps=0.2, compaction="geometric", **kw
    )
    b1.run(max_passes=4, resume=False)
    b2 = StreamingDensest(
        stream, n_nodes=edges.n_nodes, eps=0.2, compaction="geometric", **kw
    )
    st = b2.run(resume=True)
    assert st.best_rho == ref.best_rho
    assert (st.best_alive == ref.best_alive).all()
    assert st.pass_idx == ref.pass_idx
    assert st.history == ref.history


# ---------------------------------------------------------------------------
# Memmap edge stores + spill writer primitives
# ---------------------------------------------------------------------------


def test_edge_store_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    src = rng.integers(0, 100, 1000).astype(np.int32)
    dst = rng.integers(0, 100, 1000).astype(np.int32)
    w = rng.random(1000).astype(np.float32)
    store = save_edges_memmap(str(tmp_path / "store"), src, dst, w)
    s, d, ww = open_edges_memmap(store)
    np.testing.assert_array_equal(np.asarray(s), src)
    np.testing.assert_array_equal(np.asarray(d), dst)
    np.testing.assert_array_equal(np.asarray(ww), w)
    # Chunk stream over the store slices the memmaps without materializing.
    chunks = list(chunked_from_memmap(store, 300)())
    assert [len(c[0]) for c in chunks] == [300, 300, 300, 100]
    np.testing.assert_array_equal(np.concatenate([c[2] for c in chunks]), w)


def test_spill_writer_atomic_manifest(tmp_path):
    d = str(tmp_path / "spill")
    wtr = EdgeSpillWriter(d, np.float32)
    wtr.append(np.arange(4, dtype=np.int32), np.arange(4, dtype=np.int32),
               np.ones(4, np.float32))
    # Unfinalized (crash mid-spill): invisible to readers.
    assert open_edge_spill(d) is None
    wtr.finalize(caps=[4], rung=0)
    src, dst, w, man = open_edge_spill(d)
    assert man["n_slots"] == 4 and man["caps"] == [4] and man["rung"] == 0
    np.testing.assert_array_equal(np.asarray(src), np.arange(4))


# ---------------------------------------------------------------------------
# Front door: Problem knobs lower onto the driver
# ---------------------------------------------------------------------------


def test_problem_stream_knobs_lowering(tmp_path):
    edges = erdos_renyi(400, avg_deg=6, seed=5)
    s = Solver()
    ref = Solver().solve(
        edges, Problem.undirected(eps=0.5, track_history=True, compaction="off")
    )
    res = s.solve(
        edges,
        Problem.undirected(
            eps=0.5, substrate="streaming", compaction="geometric",
            stream_chunk=257, stream_prefetch=2, stream_workers=2,
            spill_dir=str(tmp_path / "spill"),
        ),
    )
    assert (np.asarray(res.best_alive) == np.asarray(ref.best_alive)).all()
    assert float(res.best_density) == pytest.approx(
        float(ref.best_density), rel=1e-6
    )
    info = res.extras["streaming"]
    assert 0 < info["peak_resident_chunks"] <= 2
    assert info["compactions"] == info["spill_rungs"]

    with pytest.raises(ValueError, match="stream_prefetch"):
        Problem.undirected(stream_prefetch=0)
    with pytest.raises(ValueError, match="residency_cap_edges"):
        Problem.undirected(residency_cap_edges=0)
    # residency_cap_edges lowers onto the driver: an impossible in-RAM cap
    # with no spill_dir must surface the driver's error.
    with pytest.raises(RuntimeError, match="residency_cap_edges"):
        s.solve(
            edges,
            Problem.undirected(
                eps=0.5, substrate="streaming", compaction="geometric",
                stream_chunk=257, residency_cap_edges=1,
            ),
        )
    # spill_dir without the geometric ladder would be a silent no-op: both
    # the front door and the driver reject it.  (Since the 'auto' default
    # flip, a default-compaction streaming Problem resolves to geometric —
    # spill_dir is then valid; only an explicit 'off' still rejects.)
    with pytest.raises(ValueError, match="spill_dir"):
        Problem.undirected(
            substrate="streaming", compaction="off", spill_dir="/x"
        ).resolve(100)
    auto_spill = Problem.undirected(substrate="streaming", spill_dir="/x").resolve(100)
    assert auto_spill.compaction == "geometric"
    with pytest.raises(ValueError, match="spill_dir"):
        StreamingDensest(lambda: iter(()), n_nodes=4, spill_dir="/x")
    # Streaming knobs never key compiled programs (no spurious recompiles).
    p1 = Problem.undirected().resolve(100)
    p2 = Problem.undirected(
        stream_prefetch=3, spill_dir="/x", stream_chunk=1, stream_workers=9
    ).resolve(100)
    assert s._key("solve", p1, 8, 100, 64, "float32") == s._key(
        "solve", p2, 8, 100, 64, "float32"
    )
