"""Count-Sketch degree estimation (paper §5.1).

Exactly the Charikar-Chen-Farach-Colton sketch used as a black box by the
paper: t independent tables of b signed counters; an edge (x, y) updates
counter (i, h_i(x)) by g_i(x) and (i, h_i(y)) by g_i(y); the degree estimate
of x is the median over i of c[i, h_i(x)] * g_i(x).

The sketch replaces the O(n) exact degree vector: on TPU it keeps per-pass
node state at O(t*b) so that only edges need to be sharded even for
billion-node graphs (see DESIGN.md §2).  Hashing is uint32 multiply-shift
(Dietzfelbinger-style, wrap-around multiply then high bits), fully vectorized
and int32-safe (no x64 requirement).
"""

from __future__ import annotations

import collections.abc
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.edgelist import EdgeList
from repro.kernels import hashing

__all__ = [
    "SketchBackend",
    "SketchCounters",
    "SketchParams",
    "make_sketch_params",
    "query_degrees",
    "sketch_degrees_from_edges",
    "sketch_endpoint_counters",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SketchParams:
    """Hash parameters for t tables over b buckets."""

    a_h: jax.Array  # uint32[t] odd multipliers for the bucket hash
    c_h: jax.Array  # uint32[t] offsets
    a_g: jax.Array  # uint32[t] odd multipliers for the sign hash
    c_g: jax.Array  # uint32[t] offsets
    n_buckets: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_tables(self) -> int:
        return self.a_h.shape[0]


def make_sketch_params(t: int, b: int, seed: int = 0) -> SketchParams:
    rng = np.random.default_rng(seed)
    odd = lambda: (rng.integers(0, 1 << 31, size=t, dtype=np.int64) * 2 + 1).astype(np.uint32)
    any32 = lambda: rng.integers(0, 1 << 32, size=t, dtype=np.int64).astype(np.uint32)
    return SketchParams(
        jnp.asarray(odd()), jnp.asarray(any32()), jnp.asarray(odd()), jnp.asarray(any32()), b
    )


def _mix(a: jax.Array, c: jax.Array, x: jax.Array) -> jax.Array:
    """uint32[t, ...] wrap-around multiply-shift mix of node ids.

    Broadcasting wrapper over the shared :mod:`repro.kernels.hashing`
    family (one per-table parameter row against the whole id array); the
    mix itself lives there so the ℓ0 sampler and this sketch stay one
    hash function.  Bit-identical to the historical inline spelling
    (pinned by ``tests/test_turnstile.py::test_hashing_regression``).
    """
    xu = x.astype(jnp.uint32)[None]
    a = a[(...,) + (None,) * x.ndim]
    c = c[(...,) + (None,) * x.ndim]
    return hashing.mix32(a, c, xu)


def _hash_bucket(p: SketchParams, x: jax.Array) -> jax.Array:
    return hashing.bucket32(_mix(p.a_h, p.c_h, x), p.n_buckets)


def _hash_sign(p: SketchParams, x: jax.Array) -> jax.Array:
    return hashing.sign32(_mix(p.a_g, p.c_g, x))


def sketch_endpoint_counters(
    p: SketchParams, ids: jax.Array, w_alive: jax.Array
) -> jax.Array:
    """Counter table float32[t, b] for ONE endpoint array of the edge stream
    (update counter (i, h_i(x)) by g_i(x)·w for every edge endpoint x)."""
    t, b = p.n_tables, p.n_buckets
    buckets = _hash_bucket(p, ids)  # [t, E]
    signs = _hash_sign(p, ids)  # [t, E]
    flat_idx = (buckets + (jnp.arange(t, dtype=jnp.int32) * b)[:, None]).reshape(-1)
    vals = (signs * w_alive[None, :]).reshape(-1)
    return jax.ops.segment_sum(vals, flat_idx, num_segments=t * b).reshape(t, b)


def sketch_degrees_from_edges(
    p: SketchParams, edges: EdgeList, w_alive: jax.Array
) -> jax.Array:
    """Builds the counter table float32[t, b] from the (masked) edge stream.

    Each alive edge contributes to both endpoints' counters, exactly the
    streaming update rule of §5.1 (weighted for weighted graphs).
    """
    return sketch_endpoint_counters(p, edges.src, w_alive) + sketch_endpoint_counters(
        p, edges.dst, w_alive
    )


def query_degrees(p: SketchParams, counters: jax.Array, nodes: jax.Array) -> jax.Array:
    """Median-of-t degree estimates for the given node ids."""
    buckets = _hash_bucket(p, nodes)  # [t, N]
    signs = _hash_sign(p, nodes)  # [t, N]
    est = jnp.take_along_axis(counters, buckets, axis=1) * signs  # [t, N]
    return jnp.median(est, axis=0)


class SketchBackend:
    """Engine ``DegreeBackend`` backed by the §5.1 Count-Sketch.

    Undirected degrees use the shared two-endpoint counter table; the
    directed rule keeps SEPARATE out/in tables (accumulate src endpoints
    only / dst endpoints only) so Algorithm 3's out- and in-degree
    estimates stay unbiased for their own side.

    Each half of the work carries its own ``jax.named_scope`` under the
    engine's ``degree_count`` phase: ``sketch_update`` (endpoint hashes,
    flat counter index, counter scatter-add) and ``sketch_query`` (node
    hashes, counter gather, median).  Op metadata only: the compiled
    program is the same.
    """

    def __init__(self, params: SketchParams):
        self.params = params

    def undirected(self, edges: EdgeList, w_alive: jax.Array):
        with jax.named_scope("sketch_update"):
            counters = sketch_degrees_from_edges(self.params, edges, w_alive)
        with jax.named_scope("sketch_query"):
            nodes = jnp.arange(edges.n_nodes, dtype=jnp.int32)
            deg = query_degrees(self.params, counters, nodes)
        return deg, jnp.sum(w_alive)

    def directed(self, edges: EdgeList, w_alive: jax.Array):
        with jax.named_scope("sketch_update"):
            c_out = sketch_endpoint_counters(self.params, edges.src, w_alive)
            c_in = sketch_endpoint_counters(self.params, edges.dst, w_alive)
        with jax.named_scope("sketch_query"):
            nodes = jnp.arange(edges.n_nodes, dtype=jnp.int32)
            out_deg = query_degrees(self.params, c_out, nodes)
            in_deg = query_degrees(self.params, c_in, nodes)
        return out_deg, in_deg, jnp.sum(w_alive)


class SketchCounters(collections.abc.Mapping):
    """``extras["sketch"]`` of a sketched solve, a read-only mapping:

    * ``tables``, ``buckets`` — the table geometry t × b;
    * ``counter_bytes`` — the float32 counter table, 4·t·b;
    * ``slots_hashed`` — endpoint-table updates of the uncompacted
      schedule: every pass hashes both endpoints of every padded edge slot
      of the input into all t tables (dead slots add weight 0), so
      passes × 2 × t × padded m.  A solve on the host compaction ladder
      hashes each rung's buffer only, 2 × t ×
      ``extras["compaction"]["edge_slots_scanned"]`` updates in all.

    ``passes`` may be the solve's device scalar: only ``slots_hashed``
    reads it, so building the mapping waits for nothing, and reading that
    key waits for the solve like any read of its answer.
    """

    KEYS = ("tables", "buckets", "counter_bytes", "slots_hashed")

    def __init__(self, tables: int, buckets: int, edge_slots: int, passes: Any):
        self._tables = int(tables)
        self._buckets = int(buckets)
        self._edge_slots = int(edge_slots)
        self._passes = passes

    def __getitem__(self, key: str) -> int:
        if key == "tables":
            return self._tables
        if key == "buckets":
            return self._buckets
        if key == "counter_bytes":
            return 4 * self._tables * self._buckets
        if key == "slots_hashed":
            return int(self._passes) * 2 * self._tables * self._edge_slots
        raise KeyError(key)

    def __iter__(self):
        return iter(self.KEYS)

    def __len__(self) -> int:
        return len(self.KEYS)

