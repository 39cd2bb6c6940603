"""MapReduce-model realization of Algorithms 1/2/3 on a JAX device mesh (§5.2).

The paper's per-pass MapReduce jobs become collectives over an edge-sharded
mesh:

  map  (emit <u;v>, <v;u>)          ->  per-shard segment_sum into deg[N]
  shuffle + reduce (count per key)  ->  jax.lax.psum over the edge axes
  density counters                  ->  psum of local edge weight
  node filter (2 MR passes)         ->  alive-bitmap mask, recomputed locally

This module holds the *shard_map substrate*'s pieces: edge sharding
(:func:`shard_edges`), the collective compaction step of the
single-program mesh ladder (:func:`mesh_compact_edges`) and the
Count-Sketch psum backend (:class:`_MeshSketchBackend`).  The programs
are the front door's: ``solve(graph, Problem(substrate='mesh'), mesh=mesh)``
lowers ``run_peel`` under ``shard_map`` with a psum'ing backend
(:class:`~repro.core.engine.MeshSegmentSumBackend` or
:class:`_MeshSketchBackend`), and :meth:`repro.core.api.Solver.mesh_program`
/ :meth:`~repro.core.api.Solver.mesh_ladder_program` hand the cached
programs to callers that shard and launch them.  The pass body — threshold,
best-set tracking, removal — is the engine's; nothing here re-implements it.

The *entire* O(log_{1+eps} n)-pass algorithm is one compiled XLA program: a
``lax.while_loop`` whose body contains exactly two fused collectives per pass
(degree psum + density psum — the density one rides along in the same
reduction).  Node state (alive bitmap) is replicated, edges are sharded: the
paper's semi-streaming O(n)-state assumption.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.graph.edgelist import EdgeList


def shard_edges(edges: EdgeList, mesh: Mesh, axes: Sequence[str]) -> EdgeList:
    """Pads E to a multiple of the edge-shard count and device_puts shards."""
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    padded = edges.with_padding(n_shards)
    spec = P(tuple(axes))
    sh = NamedSharding(mesh, spec)
    return EdgeList(
        src=jax.device_put(padded.src, sh),
        dst=jax.device_put(padded.dst, sh),
        weight=jax.device_put(padded.weight, sh),
        mask=jax.device_put(padded.mask, sh),
        n_nodes=padded.n_nodes,
        directed=padded.directed,
    )


def flat_shard_index(axes: Sequence[str]) -> jax.Array:
    """This device's position along the (flattened) edge-shard axis inside
    ``shard_map`` — the row-major combination of ``lax.axis_index`` over
    ``axes``, matching both ``PartitionSpec((axes,))`` block order and the
    concatenation order of ``lax.all_gather(..., axes, tiled=True)``."""
    return jax.lax.axis_index(tuple(axes))


def mesh_compact_edges(
    src: jax.Array,
    dst: jax.Array,
    weight: jax.Array,
    ok: jax.Array,
    alive_edges: jax.Array,
    new_cap: int,
    axes: Sequence[str],
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One collective compaction step of the single-program mesh ladder
    (for use INSIDE ``shard_map``): gathers every shard's edges, prefix-sum
    compacts the survivors (``ok`` — the post-removal edge filter the peel
    loop already carries; its psummed count is ``alive_edges``, the trigger
    count every device just agreed on) into the next rung's
    ``new_cap``-per-shard buffer, and hands each device its new shard — no
    host gather/reshard, just collectives, and no re-filter/re-count work.

    The all-gather is ``O(m_i)`` and rung sizes shrink geometrically, so
    the total gather TRAFFIC over the whole ladder telescopes to
    ``O(m_0)`` — the same order as ONE host round-trip, without ever
    leaving the compiled program.  Peak per-device RESIDENCY is another
    matter: the gathered arrays momentarily materialize all ``m_i`` slots
    on every device, so the rung-0 compaction needs O(m_0) per-device
    memory — fine whenever the uncompacted graph would fit one device
    (the regime the tracked benchmark measures), but NOT for graphs
    sharded precisely because they don't; such runs should keep
    ``compaction='off'`` on the mesh substrate for now (a
    balanced all_to_all exchange that keeps residency O(m_i / n_shards)
    is the ROADMAP refinement).  Shards are contiguous blocks in
    axis-index order, and the prefix-sum scatter is stable, so the
    surviving edges keep their original global order: degree sums see the
    same addends in the same order as the host ladder (bit-identical for
    integer-valued weights).

    Returns ``(src', dst', weight', mask')`` — this device's next-rung
    shard.
    """
    from repro.core.engine import compact_edges

    axes = tuple(axes)
    g_ok, g_src, g_dst, g_w = (
        jax.lax.all_gather(x, axes, tiled=True) for x in (ok, src, dst, weight)
    )
    n_shards = g_ok.shape[0] // ok.shape[0]
    total_next = new_cap * n_shards
    n_src, n_dst, n_w = compact_edges(g_ok, (g_src, g_dst, g_w), total_next)
    n_mask = jnp.arange(total_next, dtype=jnp.int32) < alive_edges
    start = flat_shard_index(axes) * new_cap
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, new_cap)
    return sl(n_src), sl(n_dst), sl(n_w), sl(n_mask)


@dataclasses.dataclass(frozen=True)
class _MeshSketchBackend:
    """Count-Sketch degrees inside shard_map (paper §5.1 at §5.2 scale).

    Per-pass cross-device traffic is the O(t*b) counter table (one fused
    psum with the density counter), NOT the O(n) degree vector; the degree
    *queries* stream over node chunks (``lax.map``) so the transient query
    footprint stays O(node_chunk) on top of the O(n) estimate vector the
    engine's removal rule consumes.
    """

    params: object  # SketchParams
    axes: Tuple[str, ...]
    node_chunk: int

    def undirected(self, edges: EdgeList, w_alive: jax.Array):
        from repro.core.countsketch import (
            query_degrees,
            sketch_degrees_from_edges,
        )

        t = self.params.n_tables
        b = self.params.n_buckets
        local = sketch_degrees_from_edges(self.params, edges, w_alive)
        packed = jnp.concatenate([local.reshape(-1), jnp.sum(w_alive)[None]])
        packed = jax.lax.psum(packed, self.axes)  # O(t*b) traffic, not O(n)
        counters = packed[:-1].reshape(t, b)
        total = packed[-1]

        n = edges.n_nodes
        n_chunks = (n + self.node_chunk - 1) // self.node_chunk

        def query_chunk(ci):
            ids = ci * self.node_chunk + jnp.arange(self.node_chunk, dtype=jnp.int32)
            return query_degrees(self.params, counters, ids)

        est = jax.lax.map(query_chunk, jnp.arange(n_chunks, dtype=jnp.int32))
        return est.reshape(-1)[:n], total

    def directed(self, edges: EdgeList, w_alive: jax.Array):
        raise NotImplementedError("use SketchBackend for directed sketched peels")
