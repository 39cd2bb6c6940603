"""Per-kernel validation: pallas_call in interpret mode vs pure-jnp oracle,
sweeping shapes and dtypes, plus end-to-end equivalence inside Algorithm 1."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.countsketch import make_sketch_params
from repro.graph.generators import planted_dense_subgraph
from repro.graph.partition import bucket_edges_by_tile
from repro.kernels.count_sketch.ops import count_sketch_update
from repro.kernels.count_sketch.ref import count_sketch_update_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.peel_degree.kernel import tiled_degrees_pallas
from repro.kernels.peel_degree.ref import degrees_from_tiled, tiled_degrees_ref


# ------------------------------ peel_degree ---------------------------------


@pytest.mark.parametrize(
    "n_nodes,n_edges,tile_size,block_e",
    [
        (100, 400, 32, 64),
        (1000, 5000, 128, 128),
        (257, 1000, 64, 256),  # n_nodes not a tile multiple
        (64, 50, 64, 64),      # single tile, fewer edges than block
    ],
)
def test_peel_degree_kernel_matches_ref(n_nodes, n_edges, tile_size, block_e):
    rng = np.random.default_rng(0)
    src = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    dst = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    tiled = bucket_edges_by_tile(src, dst, n_nodes, tile_size, block_e)
    w_edges = rng.random(n_edges).astype(np.float32)
    # Route per-edge weights through the static bucketing.
    ei = tiled.edge_index
    w = np.where(ei >= 0, w_edges[np.maximum(ei, 0)], 0.0).astype(np.float32)

    got = tiled_degrees_pallas(
        jnp.asarray(tiled.target_local), jnp.asarray(w),
        tile_size=tile_size, block_e=block_e, interpret=True,
    )
    want = tiled_degrees_ref(
        jnp.asarray(tiled.target_local), jnp.asarray(w), tile_size=tile_size
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    # And against a direct numpy degree count.
    deg = np.zeros(n_nodes, np.float64)
    np.add.at(deg, src, w_edges)
    np.add.at(deg, dst, w_edges)
    got_nodes = degrees_from_tiled(got, n_nodes)
    np.testing.assert_allclose(np.asarray(got_nodes), deg, rtol=1e-4, atol=1e-4)


def test_peel_degree_weighted_dtypes():
    rng = np.random.default_rng(1)
    n, e, ts = 200, 800, 64
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    tiled = bucket_edges_by_tile(src, dst, n, ts, 128)
    for dt in (np.float32,):
        w = np.where(
            tiled.edge_index >= 0,
            rng.random(tiled.edge_index.shape).astype(dt),
            0,
        ).astype(dt)
        got = tiled_degrees_pallas(
            jnp.asarray(tiled.target_local), jnp.asarray(w),
            tile_size=ts, block_e=128,
        )
        want = tiled_degrees_ref(
            jnp.asarray(tiled.target_local), jnp.asarray(w), tile_size=ts
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_peel_with_pallas_degree_fn_matches_exact():
    """Algorithm 1 driven by the Pallas degree kernel == exact-degree run."""
    from repro.core import Problem, solve

    edges, _ = planted_dense_subgraph(n=300, avg_deg=4.0, k=25, p_dense=0.8, seed=3)
    res_pallas = solve(edges, Problem.undirected(
        eps=0.5, backend="pallas", tile_size=64, tile_block=128, compaction="off"))
    res_exact = solve(edges, Problem.undirected(eps=0.5, compaction="off"))
    assert float(res_pallas.best_density) == pytest.approx(
        float(res_exact.best_density), rel=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(res_pallas.best_alive), np.asarray(res_exact.best_alive)
    )


# ------------------------------ count_sketch --------------------------------


@pytest.mark.parametrize(
    "n_endpoints,t,b,block_e",
    [
        (1000, 3, 256, 256),
        (4096, 5, 2048, 512),
        (999, 2, 128, 128),   # padding path
        (512, 1, 4096, 512),  # single table, col chunking
    ],
)
def test_count_sketch_kernel_matches_ref(n_endpoints, t, b, block_e):
    rng = np.random.default_rng(2)
    params = make_sketch_params(t, b, seed=7)
    x = jnp.asarray(rng.integers(0, 10_000, n_endpoints, dtype=np.int32))
    w = jnp.asarray(rng.random(n_endpoints).astype(np.float32))
    got = count_sketch_update(x, w, params, use_pallas=True, block_e=block_e)
    want = count_sketch_update_ref(x, w, params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_count_sketch_query_quality_from_kernel():
    """Degrees estimated from kernel-built counters track exact degrees for
    heavy nodes (the §5.1 guarantee the peel relies on)."""
    from repro.core.countsketch import query_degrees

    edges, _ = planted_dense_subgraph(n=400, avg_deg=3.0, k=40, p_dense=0.9, seed=5)
    params = make_sketch_params(5, 1 << 11, seed=1)
    src, dst = edges.src, edges.dst
    w = jnp.where(edges.mask, edges.weight, 0.0)
    from repro.kernels.count_sketch.ops import sketch_edges

    counters = sketch_edges(src, dst, w, params, use_pallas=True)
    est = query_degrees(params, counters, jnp.arange(edges.n_nodes, dtype=jnp.int32))
    exact = np.zeros(edges.n_nodes, np.float64)
    np.add.at(exact, np.asarray(src), np.asarray(w))
    np.add.at(exact, np.asarray(dst), np.asarray(w))
    heavy = exact >= 20
    assert heavy.sum() >= 30
    err = np.abs(np.asarray(est)[heavy] - exact[heavy])
    assert np.median(err) <= 3.0


# ----------------------------- flash_attention ------------------------------


@pytest.mark.parametrize(
    "b,s,hq,hkv,d,window,dtype",
    [
        (2, 256, 4, 4, 64, None, jnp.float32),
        (1, 256, 8, 2, 64, None, jnp.float32),     # GQA
        (2, 384, 4, 2, 32, 128, jnp.float32),      # sliding window
        (1, 300, 2, 1, 64, None, jnp.float32),     # padding path
        (1, 256, 4, 4, 64, None, jnp.bfloat16),    # bf16 inputs
    ],
)
def test_flash_kernel_matches_ref(b, s, hq, hkv, d, window, dtype):
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    pos = jnp.arange(s, dtype=jnp.int32)
    got = flash_attention(
        q, k, v, q_positions=pos, kv_positions=pos, window=window,
        block_q=128, block_kv=128, interpret=True,
    )
    # Oracle on the flattened layout.
    from repro.kernels.flash_attention.ops import _to_flat_heads

    qf, kf, vf = _to_flat_heads(q, k, v)
    want = flash_attention_ref(
        qf, kf, vf, pos[None], pos[None], window=window
    ).reshape(b, hq, s, d).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_kernel_matches_gqa_attention_xla():
    """Kernel output == the model-layer dense path (end-to-end contract)."""
    from repro.models.attention import gqa_attention

    rng = np.random.default_rng(6)
    b, s, hq, hkv, d = 2, 256, 6, 3, 32
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    got = flash_attention(q, k, v, q_positions=pos, kv_positions=pos)
    want = gqa_attention(q, k, v, q_positions=pos, kv_positions=pos, impl="xla")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_trainable_grads_match_dense():
    from repro.kernels.flash_attention.ops import flash_attention_trainable
    from repro.models.attention import gqa_attention

    rng = np.random.default_rng(7)
    b, s, hq, hkv, d = 1, 256, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    w = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)

    def loss_pallas(q, k, v):
        o = flash_attention_trainable(
            q, k, v, q_positions=pos, kv_positions=pos,
            bwd_q_chunk=64, bwd_kv_chunk=64,
        )
        return jnp.mean(o * w)

    def loss_dense(q, k, v):
        o = gqa_attention(q, k, v, q_positions=pos, kv_positions=pos, impl="xla")
        return jnp.mean(o * w)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=2e-4, atol=1e-5
        )
