"""What the host compaction ladder and the pass body tell a profiler.

``Solver.solve`` opens a ``densest.solve`` span; ``_run_compacted`` nests
one ``densest.ladder.*`` span per stage of each rung in it, and counts the
bytes it copies across the host-device boundary in
``extras["compaction"]``.  ``run_peel`` names each phase of the pass body
with a ``jax.named_scope``.  None of it may change a result or the compiled
program.
"""

import glob
import re
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Problem, Solver, api, engine
from repro.graph.generators import planted_dense_subgraph

PHASES = ("edge_filter", "degree_count", "best_set", "removal")


def _graph():
    return planted_dense_subgraph(260, avg_deg=4, k=25, p_dense=0.8, seed=3)[0]


def _problem(**kw):
    return Problem.undirected(eps=0.1, compaction="geometric", **kw)


def _host_spans(log_dir):
    """``densest.*`` spans per host thread: ``{line: [(name, start, end,
    stats)]}``, from the one profile under ``log_dir``."""
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("densest."):
                    out.setdefault(f"{plane.name}/{line.name}", []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One warm compacted solve, captured by the profiler on the CPU."""
    g = _graph()
    solver = Solver()
    solver.solve(g, _problem())  # compile outside the trace
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        res = solver.solve(g, _problem())
    return res, _host_spans(log_dir)


def test_compacted_solve_emits_one_span_per_rung_stage(traced):
    res, by_line = traced
    (spans,) = [s for s in by_line.values() if any(n == "densest.solve" for n, *_ in s)]
    names = [n for n, *_ in spans]
    rungs = len(res.extras["compaction"]["segments"])
    assert rungs >= 2
    assert names.count("densest.solve") == 1
    assert names.count("densest.ladder.graph_to_host") == 1
    assert names.count("densest.ladder.finish") == 1
    for stage in ("upload", "rung", "readback"):
        stats = [st for n, _, _, st in spans if n == f"densest.ladder.{stage}"]
        assert [st["rung"] for st in stats] == list(range(rungs)), stage
    assert names.count("densest.ladder.compact") <= rungs - 1
    (root,) = [(a, b) for n, a, b, _ in spans if n == "densest.solve"]
    for n, a, b, _ in spans:
        assert root[0] <= a <= b <= root[1], n


def test_rung_spans_carry_the_rung_buffers(traced):
    res, by_line = traced
    segs = res.extras["compaction"]["segments"]
    rungs = [st for s in by_line.values() for n, _, _, st in s
             if n == "densest.ladder.rung"]
    assert [(st["n_buf"], st["m_buf"]) for st in rungs] == [
        (seg["n_buf"], seg["m_buf"]) for seg in segs]


def _compact_chunks(by_line):
    return [st["chunks"] for s in by_line.values() for n, _, _, st in s
            if n == "densest.ladder.compact"]


def _compacted_chunks(res):
    return [seg["compact_chunks"] for seg in res.extras["compaction"]["segments"]
            if "compact_chunks" in seg]


def test_compact_spans_carry_their_chunk_count(traced):
    """A small rung compacts as one chunk; every rung that compacted says
    so in ``segments``, the floor's last try (if any) only in its span."""
    res, by_line = traced
    chunks = _compact_chunks(by_line)
    compacted = _compacted_chunks(res)
    assert compacted and chunks[: len(compacted)] == compacted
    assert set(chunks) == {1}


def test_chunked_compaction_opens_no_span_off_the_solving_thread(
        tmp_path, monkeypatch):
    """Chunks run on the pool's threads, which open no span: the ladder's
    host seconds (the self time of its spans) count each second once."""
    monkeypatch.setattr(api, "_COMPACT_PARALLEL_MIN_EDGES", 1)
    g = _graph()
    solver = Solver()
    solver.solve(g, _problem())
    with jax.profiler.trace(str(tmp_path)):
        res = solver.solve(g, _problem())
    by_line = _host_spans(str(tmp_path))
    (spans,) = by_line.values()
    assert any(n == "densest.solve" for n, *_ in spans)
    chunks = _compact_chunks(by_line)
    compacted = _compacted_chunks(res)
    assert compacted and chunks[: len(compacted)] == compacted
    assert all(c > 1 for c in chunks)


def test_ladder_counts_the_bytes_it_moves():
    """``bytes_to_host``/``bytes_to_device`` against the copies worked out
    by hand from the rungs: the graph comes down once; each rung goes up
    with its bitmap, two int32 scalars and (the graph is src-sorted with
    unit weights) its int32 CSR offsets, and comes back as its pass count,
    final bitmap, best density and history, plus its best set and size
    when it improves on the best so far; the answer goes up at the end."""
    g = _graph()
    prob = _problem(track_history=True)
    res = Solver().solve(g, prob)
    lad = res.extras["compaction"]
    n0, m0 = g.n_nodes, g.n_edges_padded
    mp = prob.resolve(n0).resolved_max_passes(n0)
    edge_bytes = 4 + 4 + 4 + 1  # src, dst int32; weight f32; mask bool
    hist = 3 * 4 * mp  # history_n int32, history_m and history_rho f32
    rho = np.asarray(res.history_rho)
    to_host = m0 * edge_bytes
    to_device = 0
    best, t = -np.inf, 0
    for seg in lad["segments"]:
        assert seg["src_reduce"] == "sorted"
        offsets = 4 * (seg["n_buf"] + 1)
        to_device += seg["m_buf"] * edge_bytes + seg["n_buf"] + 4 + 4 + offsets
        to_host += 4 + seg["n_buf"] + 4 + hist
        seg_best = rho[t: t + seg["passes"]].max(initial=-np.inf)
        t += seg["passes"]
        if seg_best > best:
            best = seg_best
            to_host += seg["n_buf"] + 4
    to_device += 2 * n0 + 4 + 4 + 4 + hist  # best, alive; rho, size, passes
    assert lad["bytes_to_host"] == to_host
    assert lad["bytes_to_device"] == to_device


def test_a_graph_already_on_the_host_is_not_counted_again():
    """On a TPU, jax keeps the host copy of an array it has read back, so a
    graph solved again does not cross again (the CPU shares memory and
    keeps none; the cached copy is put in place here)."""
    g = _graph()
    first = Solver().solve(g, _problem()).extras["compaction"]
    for a in (g.src, g.dst, g.weight, g.mask):
        a._npy_value = np.asarray(a)
    again = Solver().solve(g, _problem()).extras["compaction"]
    assert first["bytes_to_host"] - again["bytes_to_host"] == g.n_edges_padded * 13
    assert again["bytes_to_device"] == first["bytes_to_device"]


def _lowered_peel(track_history=False, sorted_src=False):
    g = _graph()
    policy = engine.UndirectedThreshold(0.5)
    backend = engine.ExactBackend()
    if sorted_src:
        off = np.searchsorted(np.asarray(g.src), np.arange(g.n_nodes + 1))
        backend = engine.SortedSrcBackend(jnp.asarray(off, jnp.int32))

    def fn(edges):
        return engine.run_peel(
            edges, policy, backend, 32,
            compact_below=64, track_history=track_history,
        )

    return jax.jit(fn).lower(g)


def test_lowered_pass_body_carries_every_phase_scope():
    text = _lowered_peel().as_text(debug_info=True)
    for phase in PHASES:
        assert re.search(rf"peel/[^\"]*\b{phase}/", text), phase


def test_sorted_src_ops_sit_in_the_degree_count_scope():
    """The prefix sum and its offset reads are named
    ``peel/.../degree_count/sorted_src``, so ``pass.degree_count_s``
    charges them."""
    def op_names(**kw):
        hlo = _lowered_peel(**kw).compile().as_text()
        return re.findall(r'op_name="([^"]*/sorted_src/[^"]*)"', hlo)

    names = op_names(sorted_src=True)
    assert any("dot_general" in n for n in names), names
    assert any("gather" in n for n in names), names
    assert all(re.match(r"[^/]*/peel/[^\"]*\bdegree_count/sorted_src/", n)
               for n in names), names
    assert not op_names()


def test_named_scopes_leave_the_compiled_program_unchanged(monkeypatch):
    def strip(hlo):
        """The program without its debug info: op metadata and the source
        location tables."""
        tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
        blocks = [b for b in hlo.split("\n\n") if not b.startswith(tables)]
        return re.sub(r", metadata=\{[^}]*\}", "", "\n\n".join(blocks))

    scoped = strip(_lowered_peel(track_history=True).compile().as_text())
    monkeypatch.setattr(jax, "named_scope", lambda name: nullcontext())
    plain = strip(_lowered_peel(track_history=True).compile().as_text())
    assert "peel/" not in scoped
    assert scoped == plain


@pytest.mark.parametrize("batch", [False, True])
def test_results_are_bit_identical_under_an_active_trace(tmp_path, batch):
    g = _graph()
    solver = Solver()
    if batch:
        call = lambda: solver.solve_batch(  # noqa: E731
            g, Problem.undirected(), eps=[0.25, 0.5, 1.0])
    else:
        call = lambda: solver.solve(g, _problem(track_history=True))  # noqa: E731
    plain = call()
    with jax.profiler.trace(str(tmp_path)):
        traced = call()
    for field in ("best_alive", "best_density", "best_size", "passes",
                  "alive", "history_n", "history_m", "history_rho"):
        a, b = np.asarray(getattr(plain, field)), np.asarray(getattr(traced, field))
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), field
    assert isinstance(traced.best_density, jax.Array)
    assert jnp.asarray(traced.passes).dtype == jnp.int32
