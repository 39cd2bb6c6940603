"""The plain references agree with the program on small graphs, and the
bfloat16 control does not."""

import numpy as np
import pytest

from perfbench import graphgen, reference
from repro import constants
from repro.core import Problem, Solver, solve
from repro.graph.edgelist import from_numpy
from repro.serve.densest import DensestQueryEngine


def _graph(seed, n=3000, m=18000):
    src, dst = graphgen.generate(n, m, 2.2, seed)
    return np.asarray(src), np.asarray(dst), n


@pytest.mark.parametrize("eps", [0.125, 0.5, 2.0])
def test_reference_peel_equals_solve(eps):
    src, dst, n = _graph(2)
    res = solve(from_numpy(src, dst, n), Problem.undirected(eps=eps, backend="exact"))
    ref = reference.peel(src, dst, n, eps)
    assert int(res.passes) == ref["passes"]
    assert float(res.best_density) == ref["density"]
    assert np.array_equal(np.asarray(res.best_alive), ref["best"])
    assert ref["density"] == pytest.approx(
        reference.density64(src, dst, np.nonzero(ref["best"])[0], n), rel=1e-6
    )


def test_reference_sweep_equals_solve_batch():
    src, dst, n = _graph(3)
    grid = [0.125, 0.5, 1.5]
    res = Solver().solve_batch(from_numpy(src, dst, n), Problem.undirected(), eps=grid)
    for lane, eps in enumerate(grid):
        ref = reference.peel(src, dst, n, eps)
        assert int(res.passes[lane]) == ref["passes"]
        assert float(res.best_density[lane]) == ref["density"]
        assert np.array_equal(np.asarray(res.best_alive[lane]), ref["best"])


def test_reference_local_answers_equal_engine():
    src, dst, n = _graph(4)
    eng = DensestQueryEngine(
        from_numpy(src, dst, n), Problem.undirected(eps=0.5, substrate="local"),
        extraction="local", local_budget=64,
    )
    adj = reference.csr(src, dst, n)
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    seeds = np.nonzero(deg)[0][::97][:24]
    for r in eng.query_many(seeds.tolist()):
        members, rho = reference.local_answer(
            adj, r.seed, eps=0.5, budget=64, rounds=8, alpha=1.0,
            node_floor=64,
        )
        assert np.array_equal(np.sort(r.nodes), members)
        assert r.density == rho


def test_reference_volume_cap_is_the_documented_one():
    assert reference.VOLUME_FACTOR == constants.LOCAL_VOLUME_FACTOR


def test_device_csr_equals_host_csr():
    src, dst, n = _graph(5)
    a = reference.csr(src, dst, n)
    b = reference.csr_on_device(src, dst, n)
    assert np.array_equal(a.indptr, b.indptr)
    row = np.repeat(np.arange(n), np.diff(b.indptr))
    assert np.array_equal(a.indices, b.indices[np.lexsort((b.indices, row))])


def test_bf16_control_departs_from_float32():
    src, dst, n = _graph(6, n=20000, m=200000)
    ref = reference.peel(src, dst, n, 0.5)
    low = reference.peel(src, dst, n, 0.5, reference.BF16)
    assert reference.rel_gap(low["density"], ref["density"]) > 1e-4


def test_pass_bytes_counts_alive_edges_and_nodes():
    assert reference.pass_bytes([10, 4], [6, 3]) == 12 * 14 + 8 * 9
