"""PeelEngine equivalence: every policy × backend combination must return
bit-identical best sets (and equal densities) to independent float32 numpy
references implementing the PRE-refactor pass bodies, plus approximation
property tests against the exact max-flow oracle.

The numpy references replicate the old loops' float32 arithmetic exactly
(unweighted graphs keep all degree/total sums integer-valued, so summation
order cannot perturb the threshold comparisons); any drift in the engine's
pass body shows up as a set difference here.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import densest_subgraph_exact, density_of
from repro.core.countsketch import SketchBackend, make_sketch_params, sketched_degree_fn
from repro.core.engine import (
    AtLeastKFraction,
    DirectedST,
    ExactBackend,
    FnBackend,
    MeshSegmentSumBackend,
    UndirectedThreshold,
    run_peel,
    undirected_pass_step,
)
from repro.graph import from_numpy
from repro.graph.generators import directed_planted, erdos_renyi, planted_dense_subgraph

f32 = np.float32


# ---------------------------------------------------------------------------
# Pre-refactor reference implementations (numpy, float32 arithmetic)
# ---------------------------------------------------------------------------


def _np_edges(edges):
    mask = np.asarray(edges.mask)
    return (
        np.asarray(edges.src)[mask],
        np.asarray(edges.dst)[mask],
        np.asarray(edges.weight)[mask].astype(f32),
    )


def _deg(src, dst, w, alive, n):
    ok = alive[src] & alive[dst]
    deg = np.zeros(n, f32)
    np.add.at(deg, src, np.where(ok, w, f32(0)))
    np.add.at(deg, dst, np.where(ok, w, f32(0)))
    return deg, f32(np.sum(np.where(ok, w, f32(0))))


def ref_undirected(edges, eps, max_passes):
    """Old core/peel.py body (Algorithm 1)."""
    src, dst, w = _np_edges(edges)
    n = edges.n_nodes
    alive = np.ones(n, bool)
    best_alive, best_rho = alive.copy(), -np.inf
    t = 0
    while alive.any() and t < max_passes:
        deg, total = _deg(src, dst, w, alive, n)
        n_alive = int(alive.sum())
        rho = f32(total / f32(max(n_alive, 1)))
        if rho > best_rho:
            best_alive, best_rho = alive.copy(), rho
        thresh = f32(f32(2.0 * (1.0 + eps)) * rho)
        deg_alive = np.where(alive, deg, np.inf)
        remove = alive & ((deg <= thresh) | (deg <= deg_alive.min()))
        alive = alive & ~remove
        t += 1
    return best_alive, float(best_rho), t


def ref_at_least_k(edges, k, eps, max_passes, *, min_deg_fallback=True, ceil_count=False):
    """Old core/peel_topk.py / mapreduce topk body (Algorithm 2)."""
    src, dst, w = _np_edges(edges)
    n = edges.n_nodes
    alive = np.ones(n, bool)
    best_alive, best_rho, best_size = alive.copy(), -np.inf, 0
    t = 0
    while int(alive.sum()) >= k and t < max_passes:
        deg, total = _deg(src, dst, w, alive, n)
        n_alive = int(alive.sum())
        rho = f32(total / f32(max(n_alive, 1)))
        if n_alive >= k and rho > best_rho:
            best_alive, best_rho, best_size = alive.copy(), rho, n_alive
        thresh = f32(f32(2.0 * (1.0 + eps)) * rho)
        if min_deg_fallback:
            deg_alive = np.where(alive, deg, np.inf)
            cand = alive & ((deg <= thresh) | (deg <= deg_alive.min()))
        else:
            cand = alive & (deg <= thresh)
        if ceil_count:
            r = int(np.ceil(f32(f32(f32(n_alive) * f32(eps)) / f32(1.0 + eps))))
        else:
            r = int(f32(f32(eps / (1.0 + eps)) * f32(n_alive)))
        r = max(r, 1)
        key = np.where(cand, deg, np.inf)
        order = np.argsort(key, kind="stable")
        rank = np.empty(n, np.int64)
        rank[order] = np.arange(n)
        alive = alive & ~(cand & (rank < r))
        t += 1
    return best_alive, float(best_rho), best_size, t


def ref_directed(edges, c, eps, max_passes):
    """Old core/peel_directed.py body (Algorithm 3)."""
    mask = np.asarray(edges.mask)
    src = np.asarray(edges.src)[mask]
    dst = np.asarray(edges.dst)[mask]
    w = np.asarray(edges.weight)[mask].astype(f32)
    n = edges.n_nodes
    s_alive = np.ones(n, bool)
    t_alive = np.ones(n, bool)
    best_s, best_t, best_rho = s_alive.copy(), t_alive.copy(), -np.inf
    t = 0
    while s_alive.any() and t_alive.any() and t < max_passes:
        ok = s_alive[src] & t_alive[dst]
        wa = np.where(ok, w, f32(0))
        out_deg = np.zeros(n, f32)
        in_deg = np.zeros(n, f32)
        np.add.at(out_deg, src, wa)
        np.add.at(in_deg, dst, wa)
        total = f32(wa.sum())
        ns, nt = int(s_alive.sum()), int(t_alive.sum())
        ns_f, nt_f = f32(max(ns, 1)), f32(max(nt, 1))
        rho = f32(total / f32(np.sqrt(f32(ns_f * nt_f))))
        if rho > best_rho:
            best_s, best_t, best_rho = s_alive.copy(), t_alive.copy(), rho
        if ns_f / nt_f >= c:
            thr = f32(f32(f32(1.0 + eps) * total) / ns_f)
            outd = np.where(s_alive, out_deg, np.inf)
            rm = s_alive & ((out_deg <= thr) | (out_deg <= outd.min()))
            s_alive = s_alive & ~rm
        else:
            thr = f32(f32(f32(1.0 + eps) * total) / nt_f)
            ind = np.where(t_alive, in_deg, np.inf)
            rm = t_alive & ((in_deg <= thr) | (in_deg <= ind.min()))
            t_alive = t_alive & ~rm
        t += 1
    return best_s, best_t, float(best_rho), t


# ---------------------------------------------------------------------------
# Backends under test
# ---------------------------------------------------------------------------


def _mesh():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))


def _backend(name):
    if name == "exact":
        return ExactBackend()
    if name == "mesh":
        return MeshSegmentSumBackend(("data",))
    raise ValueError(name)


def _run(edges, policy, backend_name, max_passes):
    """run_peel on the jit substrate (exact) or the shard_map substrate
    (mesh, 1 device — the collective structure is identical)."""
    if backend_name == "exact":
        fn = jax.jit(
            lambda e: run_peel(e, policy, ExactBackend(), max_passes)
        )
        return fn(edges)
    assert backend_name == "mesh"
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.mapreduce import shard_edges
    from repro.graph.edgelist import EdgeList

    mesh = _mesh()
    sh = shard_edges(edges, mesh, ("data",))
    backend = MeshSegmentSumBackend(("data",))

    def local(src, dst, weight, mask):
        e = EdgeList(src=src, dst=dst, weight=weight, mask=mask, n_nodes=sh.n_nodes)
        return run_peel(e, policy, backend, max_passes)

    fn = jax.jit(
        shard_map(
            local, mesh=mesh, in_specs=(P(("data",)),) * 4, out_specs=P(),
            check_vma=False,
        )
    )
    return fn(sh.src, sh.dst, sh.weight, sh.mask)


GRAPHS = [
    ("er", lambda: erdos_renyi(180, avg_deg=8, seed=0)),
    ("planted", lambda: planted_dense_subgraph(250, avg_deg=4, k=25, p_dense=0.8, seed=3)[0]),
]


# ---------------------------------------------------------------------------
# Policy × backend matrix vs the pre-refactor references
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["exact", "mesh"])
@pytest.mark.parametrize("graph", [g for g, _ in GRAPHS])
@pytest.mark.parametrize("eps", [0.1, 0.5])
def test_matrix_undirected_threshold(graph, backend, eps):
    edges = dict(GRAPHS)[graph]()
    mp = 64
    res = _run(edges, UndirectedThreshold(eps), backend, mp)
    ref_alive, ref_rho, ref_passes = ref_undirected(edges, eps, mp)
    np.testing.assert_array_equal(np.asarray(res.best_alive), ref_alive)
    assert float(res.best_density) == pytest.approx(ref_rho, rel=1e-6)
    assert int(res.passes) == ref_passes


@pytest.mark.parametrize("backend", ["exact", "mesh"])
@pytest.mark.parametrize("variant", ["floor_fallback", "ceil_plain"])
def test_matrix_at_least_k(backend, variant):
    edges = dict(GRAPHS)["planted"]()
    k, eps, mp = 30, 0.5, 64
    fallback = variant == "floor_fallback"
    policy = AtLeastKFraction(
        k=k, eps=eps, min_deg_fallback=fallback, ceil_count=not fallback
    )
    res = _run(edges, policy, backend, mp)
    ref_alive, ref_rho, ref_size, ref_passes = ref_at_least_k(
        edges, k, eps, mp, min_deg_fallback=fallback, ceil_count=not fallback
    )
    np.testing.assert_array_equal(np.asarray(res.best_alive), ref_alive)
    assert float(res.best_density) == pytest.approx(ref_rho, rel=1e-6)
    assert int(res.best_size) == ref_size
    assert int(res.passes) == ref_passes


@pytest.mark.parametrize("backend", ["exact", "mesh"])
@pytest.mark.parametrize("c", [0.5, 1.0, 2.0])
def test_matrix_directed_st(backend, c):
    edges, _, _ = directed_planted(200, avg_deg=3, ks=15, kt=12, p_dense=0.9, seed=5)
    eps, mp = 0.5, 64
    res = _run(edges, DirectedST(eps=eps, c=jnp.float32(c)), backend, mp)
    ref_s, ref_t, ref_rho, ref_passes = ref_directed(edges, c, eps, mp)
    np.testing.assert_array_equal(np.asarray(res.best_alive), ref_s)
    np.testing.assert_array_equal(np.asarray(res.best_t), ref_t)
    assert float(res.best_density) == pytest.approx(ref_rho, rel=1e-6)
    assert int(res.passes) == ref_passes


# ---------------------------------------------------------------------------
# Approximate backends: sketch (class == legacy degree_fn hook) and Pallas
# ---------------------------------------------------------------------------


def test_sketch_backend_matches_degree_fn_hook():
    """SketchBackend through the engine == the pre-refactor degree_fn path."""
    edges, _ = planted_dense_subgraph(600, avg_deg=4, k=30, p_dense=0.8, seed=1)
    params = make_sketch_params(t=5, b=1 << 12, seed=7)
    mp = 64
    a = jax.jit(
        lambda e: run_peel(e, UndirectedThreshold(0.5), SketchBackend(params), mp)
    )(edges)
    b = jax.jit(
        lambda e: run_peel(
            e, UndirectedThreshold(0.5), FnBackend(sketched_degree_fn(params)), mp
        )
    )(edges)
    np.testing.assert_array_equal(np.asarray(a.best_alive), np.asarray(b.best_alive))
    assert float(a.best_density) == float(b.best_density)
    assert int(a.passes) == int(b.passes)


def test_sketch_backend_directed_runs_and_is_sane():
    """DirectedST × SketchBackend: per-endpoint counter tables give a dense
    pair close to the exact-backend answer on a strongly planted block."""
    edges, _, _ = directed_planted(300, avg_deg=3, ks=20, kt=15, p_dense=0.95, seed=2)
    params = make_sketch_params(t=5, b=1 << 13, seed=3)
    mp = 64
    policy = DirectedST(eps=0.5, c=jnp.float32(1.0))
    sk = jax.jit(lambda e: run_peel(e, policy, SketchBackend(params), mp))(edges)
    ex = jax.jit(lambda e: run_peel(e, policy, ExactBackend(), mp))(edges)
    assert float(sk.best_density) >= 0.5 * float(ex.best_density)


def test_pallas_backend_matches_exact():
    """The tiled-degree kernel backend is exact arithmetic -> identical sets."""
    from repro.kernels.peel_degree.ops import (
        degree_backend_from_tiling,
        tiling_for_edges,
    )

    edges = erdos_renyi(300, avg_deg=6, seed=4)
    tiled = tiling_for_edges(edges, tile_size=128, block=128)
    backend = degree_backend_from_tiling(tiled, use_pallas=True)
    mp = 64
    a = jax.jit(lambda e: run_peel(e, UndirectedThreshold(0.5), backend, mp))(edges)
    b = jax.jit(lambda e: run_peel(e, UndirectedThreshold(0.5), ExactBackend(), mp))(edges)
    np.testing.assert_array_equal(np.asarray(a.best_alive), np.asarray(b.best_alive))
    assert float(a.best_density) == pytest.approx(float(b.best_density), rel=1e-6)


# ---------------------------------------------------------------------------
# Streaming substrate shares the policy step
# ---------------------------------------------------------------------------


def test_undirected_pass_step_equals_engine_pass():
    """One undirected_pass_step == one engine pass (same removal bitmap)."""
    edges = erdos_renyi(150, avg_deg=8, seed=6)
    res1 = jax.jit(lambda e: run_peel(e, UndirectedThreshold(0.5), ExactBackend(), 1))(
        edges
    )
    alive = jnp.ones((edges.n_nodes,), bool)
    ok = edges.mask & alive[edges.src] & alive[edges.dst]
    w_alive = jnp.where(ok, edges.weight, 0.0)
    deg, total = ExactBackend().undirected(edges, w_alive)
    new_alive, rho = undirected_pass_step(alive, deg, float(total), 0.5)
    np.testing.assert_array_equal(np.asarray(new_alive), np.asarray(res1.alive))
    assert float(rho) == float(res1.best_density)


# ---------------------------------------------------------------------------
# Segmented runs (the compaction runtime's engine contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("eps", [0.1, 0.5])
def test_segmented_run_equals_single_run(eps):
    """compact_below + init_alive/init_t re-entry == one uncompacted run:
    same best set (earliest-wins tie merge), density, pass count, history."""
    edges = erdos_renyi(220, avg_deg=8, seed=2)
    mp = 64
    policy = UndirectedThreshold(eps)
    full = jax.jit(
        lambda e: run_peel(e, policy, ExactBackend(), mp, track_history=True)
    )(edges)
    m = int(edges.num_real_edges())
    seg1 = jax.jit(
        lambda e: run_peel(
            e, policy, ExactBackend(), mp, track_history=True,
            compact_below=m // 2, init_best_empty=True,
        )
    )(edges)
    assert int(seg1.passes) < int(full.passes)  # the trigger actually fired
    seg2 = jax.jit(
        lambda e, a, t: run_peel(
            e, policy, ExactBackend(), mp, track_history=True,
            init_alive=a, init_t=t, init_best_empty=True,
        )
    )(edges, seg1.alive, seg1.passes)
    use2 = float(seg2.best_density) > float(seg1.best_density)
    best = seg2.best_alive if use2 else seg1.best_alive
    np.testing.assert_array_equal(np.asarray(best), np.asarray(full.best_alive))
    assert max(float(seg1.best_density), float(seg2.best_density)) == float(
        full.best_density
    )
    assert int(seg2.passes) == int(full.passes)
    np.testing.assert_array_equal(np.asarray(seg2.alive), np.asarray(full.alive))
    hn1 = np.asarray(seg1.history_n)
    merged = np.where(hn1 >= 0, hn1, np.asarray(seg2.history_n))
    np.testing.assert_array_equal(merged, np.asarray(full.history_n))


def test_compact_below_none_is_classic_loop():
    """compact_below=None must not change anything (the off path)."""
    edges = erdos_renyi(150, avg_deg=6, seed=9)
    a = jax.jit(
        lambda e: run_peel(e, UndirectedThreshold(0.5), ExactBackend(), 64)
    )(edges)
    b = jax.jit(
        lambda e: run_peel(
            e, UndirectedThreshold(0.5), ExactBackend(), 64, compact_below=None
        )
    )(edges)
    np.testing.assert_array_equal(np.asarray(a.best_alive), np.asarray(b.best_alive))
    assert int(a.passes) == int(b.passes)


def test_compact_edges_prefix_sum_relabeling():
    """The in-program compact step (engine.compact_edges): surviving slots
    move to the front, original order preserved, everything else drops —
    including survivors past a too-small capacity (the terminated-segment
    overflow case, whose edges are never peeled again)."""
    from repro.core.engine import compact_edges

    ok = jnp.asarray([False, True, False, True, True, False, True])
    src = jnp.arange(7, dtype=jnp.int32) * 10
    w = jnp.arange(7, dtype=jnp.float32)
    csrc, cw = jax.jit(lambda o, a, b: compact_edges(o, (a, b), 4))(ok, src, w)
    np.testing.assert_array_equal(np.asarray(csrc), [10, 30, 40, 60])
    np.testing.assert_array_equal(np.asarray(cw), [1.0, 3.0, 4.0, 6.0])
    # Capacity 2: the first two survivors (in order) are kept, extras drop.
    (csrc2,) = jax.jit(lambda o, a: compact_edges(o, (a,), 2))(ok, src)
    np.testing.assert_array_equal(np.asarray(csrc2), [10, 30])
    # Capacity beyond the survivor count zero-fills the tail.
    (csrc8,) = jax.jit(lambda o, a: compact_edges(o, (a,), 8))(ok, src)
    np.testing.assert_array_equal(np.asarray(csrc8), [10, 30, 40, 60, 0, 0, 0, 0])


def _relabel_graph(edges, perm):
    """Applies a node permutation and keeps edge order (a stable relabel)."""
    p = jnp.asarray(perm, jnp.int32)
    from repro.graph.edgelist import EdgeList

    return EdgeList(
        src=p[edges.src], dst=p[edges.dst], weight=edges.weight,
        mask=edges.mask, n_nodes=edges.n_nodes,
    )


@pytest.mark.parametrize("seed", range(4))
def test_relabel_peel_unrelabel_roundtrip_seeded(seed):
    """Seeded variant of the relabel round-trip (runs without hypothesis)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 40))
    src = rng.integers(0, n, 3 * n)
    dst = rng.integers(0, n, 3 * n)
    keep = src != dst
    edges = from_numpy(src[keep], dst[keep], n)
    perm = rng.permutation(n)
    base = jax.jit(
        lambda e: run_peel(e, UndirectedThreshold(0.5), ExactBackend(), 64)
    )(edges)
    rel = jax.jit(
        lambda e: run_peel(e, UndirectedThreshold(0.5), ExactBackend(), 64)
    )(_relabel_graph(edges, perm))
    np.testing.assert_array_equal(
        np.asarray(rel.best_alive)[perm], np.asarray(base.best_alive)
    )
    assert float(rel.best_density) == float(base.best_density)
    assert int(rel.passes) == int(base.passes)


def test_relabel_peel_unrelabel_roundtrip_hypothesis():
    """The compaction ladder's core assumption, as a property: relabeling
    nodes, peeling, and mapping the best-set bitmap back is EXACTLY the
    peel of the original graph (Algorithm 1's removal rule is id-free)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    mp = 64

    @st.composite
    def cases(draw):
        n = draw(st.integers(5, 24))
        m = draw(st.integers(4, 60))
        rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        keep = src != dst
        if keep.sum() == 0:
            src, dst, keep = np.asarray([0]), np.asarray([1]), np.asarray([True])
        perm = rng.permutation(n)
        return from_numpy(src[keep], dst[keep], n), perm

    @given(cases(), st.sampled_from([0.1, 0.5]))
    @settings(max_examples=25, deadline=None)
    def check(case, eps):
        edges, perm = case
        base = jax.jit(
            lambda e: run_peel(e, UndirectedThreshold(eps), ExactBackend(), mp)
        )(edges)
        rel = jax.jit(
            lambda e: run_peel(e, UndirectedThreshold(eps), ExactBackend(), mp)
        )(_relabel_graph(edges, perm))
        back = np.asarray(rel.best_alive)[perm]  # unrelabel the bitmap
        np.testing.assert_array_equal(back, np.asarray(base.best_alive))
        assert float(rel.best_density) == float(base.best_density)
        assert int(rel.passes) == int(base.passes)

    check()


# ---------------------------------------------------------------------------
# Approximation property: engine density >= rho* / (2(1+eps))
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("eps", [0.1, 0.5, 1.0])
def test_property_guarantee_vs_exact_seeded(seed, eps):
    """Lemma 3 on random small graphs, through the engine directly."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 40))
    m = int(rng.integers(n, 4 * n))
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    if keep.sum() == 0:
        return
    edges = from_numpy(src[keep], dst[keep], n)
    _, rho_star = densest_subgraph_exact(edges)
    res = jax.jit(
        lambda e: run_peel(e, UndirectedThreshold(eps), ExactBackend(), 128)
    )(edges)
    assert float(res.best_density) >= rho_star / (2 * (1 + eps)) - 1e-5
    assert float(res.best_density) <= rho_star + 1e-5
    assert float(density_of(edges, res.best_alive)) == pytest.approx(
        float(res.best_density), rel=1e-5, abs=1e-6
    )


def test_property_guarantee_hypothesis():
    """Hypothesis variant of the Lemma-3 property (skips if unavailable)."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @st.composite
    def graphs(draw):
        n = draw(st.integers(4, 16))
        m = draw(st.integers(3, 40))
        src = np.asarray(draw(st.lists(st.integers(0, n - 1), min_size=m, max_size=m)))
        dst = np.asarray(draw(st.lists(st.integers(0, n - 1), min_size=m, max_size=m)))
        keep = src != dst
        if keep.sum() == 0:
            src, dst, keep = np.asarray([0]), np.asarray([1]), np.asarray([True])
        return from_numpy(src[keep], dst[keep], n)

    @given(graphs(), st.sampled_from([0.1, 0.5, 1.0]))
    @settings(max_examples=20, deadline=None)
    def check(edges, eps):
        _, rho_star = densest_subgraph_exact(edges)
        res = jax.jit(
            lambda e: run_peel(e, UndirectedThreshold(eps), ExactBackend(), 64)
        )(edges)
        assert float(res.best_density) >= rho_star / (2 * (1 + eps)) - 1e-5

    check()
