"""Turnstile runtime: ℓ0-sampling sketches + dynamic-stream maintenance.

Contracts under test:

  * **hashing dedup regression** — ``kernels/hashing.py`` is bit-identical
    to the original Count-Sketch inline formula on fixed seeds (the
    refactor must not move any bucket or flip any sign);
  * **sketch linearity** — delta(A) + delta(B) == delta(A ∪ B) bitwise,
    sketch merge equivalence, insert-then-delete restores exact zeros;
  * **recovery** — level 0 when the live graph fits the budget (the
    sample IS the graph), fingerprint validation never admits a false
    edge even at tiny cell counts, numpy decoder mirrors == XLA hashes;
  * **accuracy** — sampled-peel density on a churned stream (>= 20 %
    deletions, planted dense block) stays inside the MTVV
    (1+eps)(2+2eps) envelope, seed for seed, with
    :func:`repro.graph.edgelist.apply_updates` as the exact reference;
  * **compile economics** — same-bucket update batches reuse ONE traced
    program (``trace_count``);
  * **front door** — ``Problem(stream_mode='turnstile')`` validation
    matrix, one-shot ``solve()`` equivalence, serve-layer caching.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import Problem, Solver, TurnstileDensest, solve
from repro.core import countsketch
from repro.core.countsketch import make_sketch_params
from repro.core.turnstile import (
    TurnstileSketch,
    _np_edge_cells,
    _np_edge_fingerprint,
    _np_edge_level,
)
from repro.graph.edgelist import EdgeList, apply_updates, from_numpy
from repro.graph.generators import chung_lu_power_law, planted_dense_subgraph
from repro.kernels import hashing
from repro.kernels.l0_sampler import (
    edge_cells,
    edge_fingerprint,
    edge_level,
    l0_delta,
    make_l0_params,
)


def _live_edges(g: EdgeList):
    m = int(np.asarray(g.mask).sum())
    return np.asarray(g.src)[:m].copy(), np.asarray(g.dst)[:m].copy()


def _edge_keys(u, v, n):
    lo = np.minimum(u, v).astype(np.int64)
    hi = np.maximum(u, v).astype(np.int64)
    return lo * n + hi


# -- hashing dedup regression (satellite: countsketch must not move) --------


def test_hashing_matches_original_countsketch_formula():
    """The shared mix32/bucket32/sign32 reproduce the pre-refactor inline
    Count-Sketch hash bit for bit on fixed seeds."""
    rng = np.random.default_rng(7)
    a = (rng.integers(0, 1 << 31, 4, dtype=np.uint32) * 2 + 1).astype(np.uint32)
    c = rng.integers(0, 1 << 31, 4, dtype=np.uint32)
    x = rng.integers(0, 1 << 31, 257, dtype=np.uint32)
    n_buckets = 1 << 10
    for j in range(4):
        with np.errstate(over="ignore"):
            h = np.uint32(a[j]) * x + np.uint32(c[j])
            h = h ^ (h >> np.uint32(16))
        got = np.asarray(
            hashing.mix32(jnp.uint32(a[j]), jnp.uint32(c[j]), jnp.asarray(x))
        )
        np.testing.assert_array_equal(got, h)
        np.testing.assert_array_equal(
            np.asarray(hashing.bucket32(jnp.asarray(h), n_buckets)),
            (h % np.uint32(n_buckets)).astype(np.int32),
        )
        np.testing.assert_array_equal(
            np.asarray(hashing.sign32(jnp.asarray(h))),
            np.where((h >> np.uint32(31)) == 0, 1.0, -1.0).astype(np.float32),
        )


def test_countsketch_hashes_pinned_on_fixed_seed():
    """End-to-end pin: SketchParams(seed=3) buckets/signs equal the
    original formula applied to the stored multipliers."""
    p = make_sketch_params(3, 512, seed=3)
    ids = jnp.arange(1000, dtype=jnp.int32)
    got_b = np.asarray(countsketch._hash_bucket(p, ids))
    got_s = np.asarray(countsketch._hash_sign(p, ids))
    a_h, c_h = np.asarray(p.a_h), np.asarray(p.c_h)
    a_g, c_g = np.asarray(p.a_g), np.asarray(p.c_g)
    x = np.arange(1000, dtype=np.uint32)
    for j in range(3):
        with np.errstate(over="ignore"):
            hb = a_h[j] * x + c_h[j]
            hb ^= hb >> np.uint32(16)
            hs = a_g[j] * x + c_g[j]
            hs ^= hs >> np.uint32(16)
        np.testing.assert_array_equal(got_b[j], (hb % np.uint32(512)).astype(np.int32))
        np.testing.assert_array_equal(
            got_s[j], np.where((hs >> np.uint32(31)) == 0, 1.0, -1.0)
        )


def test_decoder_numpy_mirrors_match_xla_hashes():
    """The host decoder's numpy re-hashes are bit-identical to the XLA
    ops that built the sketch (wraparound uint32 semantics match)."""
    p = make_l0_params(n_levels=16, n_cells=1 << 9, n_tables=3, seed=11)
    rng = np.random.default_rng(0)
    u = rng.integers(0, 5000, 400).astype(np.int32)
    v = (u + 1 + rng.integers(0, 100, 400)).astype(np.int32)
    uj, vj = jnp.asarray(u), jnp.asarray(v)
    np.testing.assert_array_equal(np.asarray(edge_level(p, uj, vj)), _np_edge_level(p, u, v))
    np.testing.assert_array_equal(np.asarray(edge_cells(p, uj, vj)), _np_edge_cells(p, u, v))
    np.testing.assert_array_equal(
        np.asarray(edge_fingerprint(p, uj, vj)).view(np.int32),
        _np_edge_fingerprint(p, u, v),
    )


# -- sketch linearity -------------------------------------------------------


def test_l0_delta_is_linear():
    """delta(A) + delta(B) == delta(A ∪ B) bit for bit."""
    p = make_l0_params(n_levels=12, n_cells=1 << 8, n_tables=3, seed=2)
    rng = np.random.default_rng(1)
    u = rng.integers(0, 2000, 600).astype(np.int32)
    v = rng.integers(0, 2000, 600).astype(np.int32)
    s = np.where(rng.random(600) < 0.7, 1, -1).astype(np.int32)
    half = 300
    dA = l0_delta(jnp.asarray(u[:half]), jnp.asarray(v[:half]), jnp.asarray(s[:half]), p, use_pallas=False)
    dB = l0_delta(jnp.asarray(u[half:]), jnp.asarray(v[half:]), jnp.asarray(s[half:]), p, use_pallas=False)
    dAB = l0_delta(jnp.asarray(u), jnp.asarray(v), jnp.asarray(s), p, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(dA) + np.asarray(dB), np.asarray(dAB))


def test_l0_pallas_interpret_matches_reference():
    """The Pallas kernel (interpret mode) is bit-identical to the
    segment-sum reference, including sign-0 padding rows."""
    p = make_l0_params(n_levels=8, n_cells=1 << 8, n_tables=3, seed=4)
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.integers(0, 3000, 300).astype(np.int32))
    v = jnp.asarray(rng.integers(0, 3000, 300).astype(np.int32))
    s = jnp.asarray(np.where(rng.random(300) < 0.6, 1, -1).astype(np.int32))
    ref = l0_delta(u, v, s, p, use_pallas=False)
    ker = l0_delta(u, v, s, p, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_sketch_merge_equals_union_and_validates():
    g = chung_lu_power_law(600, seed=9)
    src, dst = _live_edges(g)
    half = len(src) // 2
    sA = TurnstileSketch(600, 1 << 9, seed=5).apply((src[:half], dst[:half]))
    sB = TurnstileSketch(600, 1 << 9, seed=5).apply((src[half:], dst[half:]))
    sAB = TurnstileSketch(600, 1 << 9, seed=5).apply((src, dst))
    sA.merge(sB)
    np.testing.assert_array_equal(np.asarray(sA.tables), np.asarray(sAB.tables))
    with pytest.raises(ValueError, match="identical geometry"):
        sA.merge(TurnstileSketch(600, 1 << 9, seed=6))
    with pytest.raises(TypeError):
        sA.merge("not a sketch")


def test_insert_then_delete_restores_exact_zeros():
    g = chung_lu_power_law(500, seed=1)
    src, dst = _live_edges(g)
    sk = TurnstileSketch(500, 1 << 9, seed=0)
    sk.apply(insert_edges=(src, dst))
    assert np.asarray(sk.tables).any()
    # Delete with REVERSED endpoints: canonicalization makes them cancel.
    sk.apply(delete_edges=(dst, src))
    assert not np.asarray(sk.tables).any()
    edges, level, info = sk.recover()
    assert len(edges) == 0 and level == 0 and info["exact"]


def test_same_seed_is_bit_reproducible():
    g = chung_lu_power_law(800, seed=3)
    src, dst = _live_edges(g)
    prob = Problem.undirected(
        stream_mode="turnstile", sample_edges=1 << 10, sketch_seed=42
    )
    tds = [TurnstileDensest(800, prob, solver=Solver()) for _ in range(2)]
    for td in tds:
        td.apply(insert_edges=(src, dst))
        td.apply(delete_edges=(src[:50], dst[:50]))
    np.testing.assert_array_equal(
        np.asarray(tds[0].sketch.tables), np.asarray(tds[1].sketch.tables)
    )
    r0, r1 = tds[0].query(), tds[1].query()
    assert float(r0.best_density) == float(r1.best_density)


# -- recovery ---------------------------------------------------------------


def test_exact_recovery_when_graph_fits_budget():
    """m <= tau: level 0, the recovered sample IS the live edge set."""
    g = chung_lu_power_law(400, seed=8)
    src, dst = _live_edges(g)
    sk = TurnstileSketch(400, 1 << 11, seed=1)
    sk.apply((src, dst))
    edges, level, info = sk.recover()
    assert level == 0 and info["exact"]
    assert info["sample_rate"] == 1.0
    got = set(_edge_keys(edges[:, 0], edges[:, 1], 400).tolist())
    want = set(_edge_keys(src, dst, 400).tolist())
    assert got == want


def test_recovery_never_fabricates_edges_at_tiny_cell_count():
    """With C far below m the low levels cannot decode; whatever level
    finally decodes must contain ONLY true edges (fingerprint + cell +
    level re-hash validation)."""
    g = chung_lu_power_law(3000, avg_deg=4.0, seed=6)
    src, dst = _live_edges(g)
    sk = TurnstileSketch(3000, 256, seed=2)
    sk.apply((src, dst))
    edges, level, info = sk.recover()
    assert level > 0  # the whole graph cannot possibly fit 256 cells
    want = set(_edge_keys(src, dst, 3000).tolist())
    got = _edge_keys(edges[:, 0], edges[:, 1], 3000)
    assert set(got.tolist()) <= want
    assert info["sample_edges_recovered"] == len(edges) <= info["level_suffix_count"]


def test_corrupted_stream_degrades_but_never_fabricates():
    """Deleting a never-inserted edge leaves count -3 debris that blocks
    level 0 (it can never peel to all-zeros); recover() climbs past the
    corruption, counts the failures, and still returns only true edges."""
    sk = TurnstileSketch(100, 256, seed=0)
    sk.apply(insert_edges=np.asarray([[0, 1], [1, 2]]))
    sk.apply(delete_edges=np.asarray([[7, 9], [7, 9], [7, 9]]))  # count -3
    edges, level, info = sk.recover()
    assert sk.recovery_failures >= 1 and level >= 1  # level 0 is corrupt
    want = set(_edge_keys(np.asarray([0, 1]), np.asarray([1, 2]), 100).tolist())
    got = set(_edge_keys(edges[:, 0], edges[:, 1], 100).tolist())
    assert got <= want


# -- compile economics ------------------------------------------------------


def test_update_compiles_once_per_batch_bucket():
    sk = TurnstileSketch(2000, 1 << 9, seed=0)
    rng = np.random.default_rng(0)
    for _ in range(4):  # four same-bucket batches -> one trace
        e = rng.integers(0, 2000, (500, 2)).astype(np.int32)
        sk.apply(insert_edges=e)
    assert sk.trace_count == 1
    assert sk.batches_applied == 4 and sk.updates_applied == 2000
    sk.apply(insert_edges=rng.integers(0, 2000, (3000, 2)).astype(np.int32))
    assert sk.trace_count == 2  # new pow2 bucket -> exactly one more trace


# -- accuracy under churn (the MTVV envelope) -------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_churn_density_within_envelope(seed):
    """Power-law background + planted dense block, >= 20 % deletions:
    the sampled-peel density stays within (1+eps)(2+2eps) of the exact
    insert-mode peel on the surviving graph (apply_updates reference)."""
    n, eps = 4000, 0.3
    g, _ = planted_dense_subgraph(n, 6.0, 120, 0.6, seed=seed)
    src, dst = _live_edges(g)
    m = len(src)
    rng = np.random.default_rng(1000 + seed)
    n_del = int(0.3 * m)  # 30 % churn, above the 20 % floor
    del_idx = rng.choice(m, size=n_del, replace=False)
    deletes = np.stack([src[del_idx], dst[del_idx]], axis=1)
    base = from_numpy(src, dst, n)
    final, stats = apply_updates(base, deletes=deletes)
    assert stats["deleted"] == n_del and stats["missing_deletes"] == 0

    prob = Problem.undirected(
        eps=eps, stream_mode="turnstile", sample_edges=1 << 11, sketch_seed=seed
    )
    td = TurnstileDensest(n, prob, solver=Solver())
    td.apply(insert_edges=(src, dst))
    td.apply(delete_edges=(deletes[:, 0], deletes[:, 1]))
    res = td.query()
    info = res.extras["turnstile"]
    assert info["level"] >= 1  # ~11k live edges cannot fit 2048: a real sample

    exact = solve(final, Problem.undirected(eps=eps, compaction="off"))
    envelope = (1 + eps) * (2 + 2 * eps)
    ratio = float(res.best_density) / float(exact.best_density)
    assert 1.0 / envelope <= ratio <= envelope, (ratio, info)


# -- front door -------------------------------------------------------------


def test_problem_validation_matrix():
    with pytest.raises(ValueError, match="stream_mode"):
        Problem.undirected(stream_mode="bogus")
    with pytest.raises(ValueError, match="sample_edges"):
        Problem.undirected(stream_mode="turnstile", sample_edges=0)
    with pytest.raises(ValueError, match="objective='undirected'"):
        Problem.directed(stream_mode="turnstile").resolve(100)
    with pytest.raises(ValueError, match="sketch a sketch"):
        Problem.undirected(stream_mode="turnstile", backend="sketch").resolve(100)
    with pytest.raises(ValueError, match="substrate"):
        Problem.undirected(stream_mode="turnstile", substrate="mesh").resolve(100)
    # Compaction is an irrelevant knob: quietly forced off, never an error.
    p = Problem.undirected(stream_mode="turnstile", compaction="geometric").resolve(100)
    assert p.compaction == "off" and p.substrate == "jit" and p.backend == "exact"


def test_one_shot_solve_matches_insert_mode_when_exact():
    """m <= tau: the front-door turnstile solve recovers the WHOLE graph
    (level 0) and its density equals the plain insert-mode solve."""
    g = chung_lu_power_law(1200, seed=5)
    r_t = solve(g, Problem.undirected(stream_mode="turnstile"))
    r_i = solve(g, Problem.undirected(compaction="off"))
    assert float(r_t.best_density) == pytest.approx(float(r_i.best_density))
    info = r_t.extras["turnstile"]
    assert info["exact"] and info["level"] == 0
    assert r_t.provenance.substrate == "turnstile"


def test_solve_turnstile_rejects_directed_and_weighted():
    src = np.asarray([0, 1, 2], np.int32)
    dst = np.asarray([1, 2, 0], np.int32)
    d = from_numpy(src, dst, 3, directed=True)
    with pytest.raises(ValueError, match="undirected"):
        solve(d, Problem.undirected(stream_mode="turnstile"))
    w = from_numpy(src, dst, 3, weight=np.asarray([2.0, 1.0, 1.0], np.float32))
    with pytest.raises(ValueError, match="unweighted"):
        solve(w, Problem.undirected(stream_mode="turnstile"))


def test_solve_batch_rejects_turnstile():
    from repro.core import solve_batch

    g = chung_lu_power_law(300, seed=0)
    with pytest.raises(ValueError, match="turnstile"):
        solve_batch(
            g,
            Problem.undirected(stream_mode="turnstile"),
            eps=[0.25, 0.5],
        )


# -- exact host reference (apply_updates) -----------------------------------


def test_apply_updates_semantics():
    base = from_numpy(
        np.asarray([0, 1, 2], np.int32), np.asarray([1, 2, 3], np.int32), 5
    )
    # Reversed endpoints match; survivors keep stable order; inserts append.
    out, stats = apply_updates(
        base,
        inserts=np.asarray([[3, 4], [4, 3]]),  # within-batch dup collapses
        deletes=np.asarray([[2, 1], [0, 4]]),  # one live, one missing
    )
    assert stats == {
        "dup_inserts": 1,
        "missing_deletes": 1,
        "deleted": 1,
        "inserted": 1,
    }
    u, v = _live_edges(out)
    np.testing.assert_array_equal(u, [0, 2, 3])
    np.testing.assert_array_equal(v, [1, 3, 4])
    # Inserting a live edge is a counted no-op (set semantics).
    out2, stats2 = apply_updates(out, inserts=np.asarray([[1, 0]]))
    assert stats2["dup_inserts"] == 1 and stats2["inserted"] == 0
    np.testing.assert_array_equal(np.asarray(out2.src), np.asarray(out.src))
    # Same edge on both sides of one batch is order-ambiguous.
    with pytest.raises(ValueError, match="must not insert and delete"):
        apply_updates(base, inserts=np.asarray([[0, 1]]), deletes=np.asarray([[1, 0]]))


# -- serving ----------------------------------------------------------------


def test_serve_service_caches_between_updates():
    from repro.serve import DensestQueryEngine, TurnstileDensityService

    g = chung_lu_power_law(700, seed=2)
    src, dst = _live_edges(g)
    svc = TurnstileDensityService(
        700, Problem.undirected(stream_mode="turnstile", sample_edges=1 << 10)
    )
    svc.apply(insert_edges=(src, dst))
    d1 = svc.density()
    d2 = svc.density()  # no update in between: served from cache
    assert d1 == d2
    assert svc.stats()["queries_served"] == 2
    assert svc.stats()["queries_computed"] == 1
    svc.apply(delete_edges=(src[:40], dst[:40]))
    svc.density()
    assert svc.stats()["queries_computed"] == 2

    eng = DensestQueryEngine(g).attach_turnstile(svc)
    assert eng.current_density() == svc.density()
    assert svc.stats()["queries_computed"] == 2  # attachment reads the cache
    with pytest.raises(ValueError, match="n_nodes"):
        DensestQueryEngine(g).attach_turnstile(TurnstileDensityService(701))
    with pytest.raises(ValueError, match="attach_turnstile"):
        DensestQueryEngine(g).current_density()


def test_empty_sketch_query_is_well_defined():
    td = TurnstileDensest(
        50, Problem.undirected(stream_mode="turnstile"), solver=Solver()
    )
    res = td.query()
    assert float(res.best_density) == 0.0
    assert res.extras["turnstile"]["sample_edges_recovered"] == 0


# -- property: update-linearity on arbitrary stream splits ------------------
# Written with hypothesis when available (CI installs it), seeded
# parametrization otherwise — either way the property itself runs.

try:
    from hypothesis import given, settings, strategies as st

    _prop = lambda f: settings(max_examples=15, deadline=None)(  # noqa: E731
        given(st.integers(0, 2**31 - 1), st.integers(1, 80))(f)
    )
except ImportError:
    _prop = lambda f: pytest.mark.parametrize(  # noqa: E731
        "seed,cut", [(0, 1), (1, 37), (2, 80), (3, 50), (4, 99)]
    )(f)


@_prop
def test_property_split_invariance(seed, cut):
    """Any split of an update stream into batches yields the same sketch
    (linearity + commutativity of the donated update program)."""
    rng = np.random.default_rng(seed)
    k = 100
    e = rng.integers(0, 500, (k, 2)).astype(np.int32)
    cut = min(cut, k - 1)
    one = TurnstileSketch(500, 256, seed=9).apply(insert_edges=e)
    two = (
        TurnstileSketch(500, 256, seed=9)
        .apply(insert_edges=e[:cut])
        .apply(insert_edges=e[cut:])
    )
    np.testing.assert_array_equal(np.asarray(one.tables), np.asarray(two.tables))
