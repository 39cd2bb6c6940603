"""MapReduce-model realization of Algorithms 1/2/3 on a JAX device mesh (§5.2).

The paper's per-pass MapReduce jobs become collectives over an edge-sharded
mesh:

  map  (emit <u;v>, <v;u>)          ->  per-shard segment_sum into deg[N]
  shuffle + reduce (count per key)  ->  jax.lax.psum over the edge axes
  density counters                  ->  psum of local edge weight
  node filter (2 MR passes)         ->  alive-bitmap mask, recomputed locally

This module is the *shard_map substrate* of the PeelEngine.  The
``make_distributed_*`` builders are thin delegations through the front
door's mesh lowering (:meth:`repro.core.api.Solver.mesh_program`): every
one constructs a ``Problem`` and receives the cached
``jit(shard_map(run_peel))`` program with a psum'ing backend
(:class:`~repro.core.engine.MeshSegmentSumBackend` or the Count-Sketch
:class:`_MeshSketchBackend`).  The pass body — threshold, best-set
tracking, removal — is the engine's; nothing here re-implements it.

The *entire* O(log_{1+eps} n)-pass algorithm is one compiled XLA program: a
``lax.while_loop`` whose body contains exactly two fused collectives per pass
(degree psum + density psum — the density one rides along in the same
reduction).  Node state (alive bitmap) is replicated, edges are sharded: the
paper's semi-streaming O(n)-state assumption.

Used by: tests (vs the single-device reference), bench_scale (Fig 6.7
analogue), and the production dry-run (``--arch densest-mapreduce``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.api import DenseSubgraphResult, Problem, default_solver, solve
from repro.core.density import max_passes_bound
from repro.core.engine import (
    MeshSegmentSumBackend,
    PeelOutcome,
    UndirectedThreshold,
    run_peel,
)
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


def _local_edges(src, dst, weight, mask, n_nodes: int) -> EdgeList:
    """The per-device EdgeList view inside shard_map."""
    return EdgeList(src=src, dst=dst, weight=weight, mask=mask, n_nodes=n_nodes)


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
    ``compaction='off'``/``'twophase'`` on the mesh substrate for now (a
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


def make_distributed_peel(
    mesh: Mesh,
    edge_axes: Tuple[str, ...] = ("data",),
    eps: float = 0.5,
    max_passes: Optional[int] = None,
    n_nodes: Optional[int] = None,
    wire_dtype: str = "f32",
):
    """Builds the jitted multi-device Algorithm 1.

    Returns fn(src, dst, weight, mask) -> PeelOutcome, where edge arrays are
    sharded over ``edge_axes`` and everything else is replicated.

    ``wire_dtype='bf16'`` halves the per-pass degree psum (the dominant
    collective): partial degrees are cast to bf16 before the reduction and
    back after.  For unweighted graphs local partials are exact integers;
    the reduced sum carries <=0.4% relative rounding — harmless to the
    threshold test because (a) the removal rule keeps the approximation
    proof's slack and (b) the min-degree progress fallback is unaffected
    (EXPERIMENTS.md Perf, densest x twitter_lg).
    """
    assert n_nodes is not None
    problem = Problem.undirected(
        eps=eps,
        max_passes=max_passes,
        substrate="mesh",
        edge_axes=tuple(edge_axes),
        wire_dtype=wire_dtype,
    )
    return default_solver.mesh_program(problem, mesh, n_nodes)


def densest_subgraph_distributed(
    edges: EdgeList,
    mesh: Mesh,
    edge_axes: Tuple[str, ...] = ("data",),
    eps: float = 0.5,
    max_passes: Optional[int] = None,
    compaction: str = "off",
) -> DenseSubgraphResult:
    """Convenience wrapper: shard + run through the front door.
    ``compaction`` is pinned off by default, like every legacy wrapper, so
    pre-flip outputs stay exact for any weights; pass ``'geometric'`` for
    the single-program mesh ladder."""
    problem = Problem.undirected(
        eps=eps, max_passes=max_passes, substrate="mesh",
        edge_axes=tuple(edge_axes), compaction=compaction,
    )
    return solve(edges, problem, mesh=mesh)


def make_distributed_peel_compacted(
    mesh: Mesh,
    edge_axes: Tuple[str, ...] = ("data",),
    eps: float = 0.5,
    max_passes: Optional[int] = None,
    n_nodes: Optional[int] = None,
    wire_dtype: str = "f32",
    compaction: str = "geometric",
):
    """Distributed Algorithm 1 on the GEOMETRIC compaction ladder.

    The multi-level generalization of :func:`make_distributed_peel_twophase`:
    whenever the (psummed) alive edge count falls below half the current
    padded buffer, survivor edges are compacted into the next power-of-two
    bucket and the SAME engine loop continues there — every edge-level cost
    shrinks with the graph, for amortized-O(m) total work.  With
    ``compaction='geometric'`` (the default) the whole ladder now runs as
    ONE compiled ``shard_map`` program via
    :func:`make_distributed_peel_ladder`'s lowering (collective-only, no
    host round-trip per rung); ``compaction='twophase'`` keeps the host
    gather/relabel schedule.  Returns ``fn(edges: EdgeList) ->
    DenseSubgraphResult`` (an EdgeList-level entry point, unlike the
    raw-array single-program builders; ``n_nodes``, if given, is validated
    against each graph for signature parity with the sibling builders).
    """
    problem = Problem.undirected(
        eps=eps,
        max_passes=max_passes,
        substrate="mesh",
        edge_axes=tuple(edge_axes),
        wire_dtype=wire_dtype,
        compaction=compaction,
    )

    def run(edges: EdgeList) -> DenseSubgraphResult:
        if n_nodes is not None and edges.n_nodes != n_nodes:
            raise ValueError(
                f"graph has n_nodes={edges.n_nodes}, builder was sized for "
                f"{n_nodes}"
            )
        return solve(edges, problem, mesh=mesh)

    return run


def make_distributed_peel_ladder(
    mesh: Mesh,
    edge_axes: Tuple[str, ...] = ("data",),
    eps: float = 0.5,
    max_passes: Optional[int] = None,
    n_nodes: Optional[int] = None,
    m_edges: Optional[int] = None,
    wire_dtype: str = "f32",
):
    """The single-program mesh compaction ladder: the WHOLE geometric
    Lemma-4 schedule — every peel segment and every inter-rung compaction —
    as ONE compiled ``jit(shard_map(...))`` program, collective-only end to
    end (degree psum + alive-edge trigger psum per pass, one all-gather
    redistribution per rung; zero host gather/reshard round-trips).

    This is the multi-level generalization of
    :func:`make_distributed_peel_twophase`'s single-XLA-program idea: the
    bucket sizes derive statically from the padded edge count — rung ``i``
    exits below the NEXT rung's capacity (the psummed trigger every device
    agrees on), so its survivors provably fit there and the full shape
    ladder is known at trace time
    (:func:`repro.graph.partition.ladder_schedule`); eps enters as the
    Lemma-4 pass budget baked into every rung.

    Returns ``run(src, dst, weight, mask) -> PeelOutcome`` over arrays
    padded to ``run.n_edge_slots`` (= ``run.schedule[0] * n_shards``) and
    sharded over ``edge_axes`` — signature parity with
    :func:`make_distributed_peel`.  ``run.schedule`` exposes the static
    per-shard bucket sizes; for per-rung pass counts and the full ladder
    report, go through the front door instead — ``solve(...,
    Problem(substrate='mesh', compaction='geometric'))`` returns it in
    ``extras['compaction']``.
    """
    assert n_nodes is not None
    assert m_edges is not None, "the static bucket schedule needs m_edges"
    problem = Problem.undirected(
        eps=eps,
        max_passes=max_passes,
        substrate="mesh",
        edge_axes=tuple(edge_axes),
        wire_dtype=wire_dtype,
        compaction="geometric",
    )
    fn, schedule, n_shards, _ = default_solver.mesh_ladder_program(
        problem, mesh, n_nodes, m_edges
    )

    def run(src, dst, weight, mask) -> PeelOutcome:
        out, _rung_t = fn(src, dst, weight, mask)
        return out

    run.schedule = schedule
    run.n_edge_slots = schedule[0] * n_shards
    return run


def make_distributed_peel_twophase(
    mesh: Mesh,
    edge_axes: Tuple[str, ...] = ("data",),
    eps: float = 0.5,
    max_passes: Optional[int] = None,
    n_nodes: Optional[int] = None,
    phase1_passes: int = 8,
    wire_dtype: str = "f32",
):
    """Algorithm 1 with PROVABLE mid-run compaction (beyond-paper perf).

    Lemma 4 guarantees |S| shrinks by >= (1+eps) every pass, so after K
    passes |S| < n/(1+eps)^K — a STATIC bound.  Phase 1 runs (up to) K
    engine passes on the full id space; the survivors are then renumbered
    into a dense range of that static size and phase 2 continues there,
    shrinking the per-pass O(n) degree psum (the dominant collective) by
    (1+eps)^K for the remaining O(log n) passes.  Semantics are identical to
    the single-phase peel (compaction is pure renumbering; tested) — both
    phases are the SAME engine loop, just on different id spaces.

    SUPERSEDED as the compaction entry point: this single-XLA-program
    two-level schedule is now a special case of the engine's compaction
    runtime — prefer ``Problem(compaction='twophase'|'geometric')`` via the
    front door (or :func:`make_distributed_peel_compacted`), which
    generalizes the renumbering into a multi-level ladder shared by all
    substrates.  Kept for callers that need the whole run as ONE compiled
    program (no host round-trip between phases).
    """
    axes = tuple(edge_axes)
    assert n_nodes is not None
    n = n_nodes
    mp = max_passes if max_passes is not None else max_passes_bound(n, eps)
    k1 = min(phase1_passes, mp)
    n2 = int(np.ceil(n / (1.0 + eps) ** k1)) + 1  # static Lemma-4 bound
    mp2 = max(mp - k1, 4)
    policy = UndirectedThreshold(eps)
    backend = MeshSegmentSumBackend(axes, wire_dtype)

    def peel_local(src, dst, weight, mask):
        # ---- phase 1: up to K passes on the full id space ----
        edges1 = _local_edges(src, dst, weight, mask, n)
        out1 = run_peel(edges1, policy, backend, k1, init_best_empty=True)
        alive1 = out1.alive

        # ---- compaction: renumber survivors into [0, n2) ----
        n_alive1 = jnp.sum(alive1.astype(jnp.int32))
        relabel = jnp.cumsum(alive1.astype(jnp.int32)) - 1  # full -> compact
        relabel = jnp.minimum(relabel, n2 - 1)  # clamp (bound is provable)
        ok_e = mask & alive1[src] & alive1[dst]
        trash = n2  # extra bucket for dead edges
        src2 = jnp.where(ok_e, relabel[src], trash)
        dst2 = jnp.where(ok_e, relabel[dst], trash)
        w2 = jnp.where(ok_e, weight, 0.0)

        # ---- phase 2: the same engine loop on the compacted ids ----
        edges2 = _local_edges(src2, dst2, w2, ok_e, n2 + 1)
        alive2_init = jnp.arange(n2 + 1, dtype=jnp.int32) < n_alive1
        out2 = run_peel(
            edges2, policy, backend, mp2,
            init_alive=alive2_init, init_best_empty=True,
        )

        # ---- merge: map the phase-2 best/final sets back to full ids ----
        best2_full = alive1 & out2.best_alive[jnp.minimum(relabel, n2 - 1)]
        use2 = out2.best_density > out1.best_density
        best_alive = jnp.where(use2, best2_full, out1.best_alive)
        best_rho = jnp.maximum(out1.best_density, out2.best_density)
        final_alive = alive1 & out2.alive[jnp.minimum(relabel, n2 - 1)]
        return best_alive, best_rho, out1.passes + out2.passes, final_alive

    sharded = shard_map(
        peel_local,
        mesh=mesh,
        in_specs=(P(axes),) * 4,
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )

    @jax.jit
    def run(src, dst, weight, mask) -> PeelOutcome:
        best_alive, best_rho, t, final_alive = sharded(src, dst, weight, mask)
        return PeelOutcome(
            best_alive=best_alive,
            best_t=jnp.zeros((0,), bool),
            best_density=best_rho,
            best_size=jnp.sum(best_alive.astype(jnp.int32)),
            passes=t,
            alive=final_alive,
            t_alive=jnp.zeros((0,), bool),
            history_n=jnp.zeros((1,), jnp.int32),
            history_m=jnp.zeros((1,), jnp.float32),
            history_rho=jnp.zeros((1,), jnp.float32),
        )

    return run


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


def make_distributed_sketched_peel(
    mesh: Mesh,
    edge_axes: Tuple[str, ...] = ("data",),
    eps: float = 0.5,
    max_passes: int = 48,
    n_nodes: Optional[int] = None,
    t: int = 5,
    b: int = 1 << 17,
    node_chunk: int = 1 << 20,
    seed: int = 0,
):
    """Distributed Algorithm 1 with Count-Sketch degrees (paper §5.1).

    This is the billion-node configuration: only edges are sharded, node
    bitmaps stay replicated, and the per-pass collective is the O(t*b)
    counter psum.  Returns fn(src, dst, weight, mask) ->
    (best_alive, best_rho, passes).
    """
    assert n_nodes is not None
    problem = Problem.undirected(
        eps=eps,
        max_passes=max_passes,
        substrate="mesh",
        backend="sketch",
        edge_axes=tuple(edge_axes),
        sketch_tables=t,
        sketch_buckets=b,
        sketch_seed=seed,
        sketch_node_chunk=node_chunk,
    )
    fn = default_solver.mesh_program(problem, mesh, n_nodes)

    def run(src, dst, weight, mask):
        out = fn(src, dst, weight, mask)
        return out.best_alive, out.best_density, out.passes

    return run


def make_distributed_topk_peel(
    mesh: Mesh,
    edge_axes: Tuple[str, ...] = ("data",),
    k: int = 1,
    eps: float = 0.5,
    max_passes: Optional[int] = None,
    n_nodes: Optional[int] = None,
):
    """Distributed Algorithm 2 (densest subgraph with |S| >= k).

    Per pass, removes exactly ceil(eps/(1+eps)·|S|) of the LOWEST-degree
    nodes among the threshold-eligible set (the paper's 'smallest number of
    nodes necessary for convergence').  Degrees are replicated after the
    psum, so the rank selection is computed identically on every device —
    no extra collective beyond Algorithm 1's.
    """
    assert n_nodes is not None
    problem = Problem.at_least_k(
        k=k,
        eps=eps,
        max_passes=max_passes,
        substrate="mesh",
        edge_axes=tuple(edge_axes),
        min_deg_fallback=False,
        ceil_count=True,
    )
    return default_solver.mesh_program(problem, mesh, n_nodes)


def make_distributed_directed_peel(
    mesh: Mesh,
    edge_axes: Tuple[str, ...] = ("data",),
    eps: float = 0.5,
    max_passes: Optional[int] = None,
    n_nodes: Optional[int] = None,
):
    """Distributed Algorithm 3 (directed) for a runtime ratio c.

    Returns fn(src, dst, weight, mask, c) -> (best_s, best_t, rho, passes).
    """
    assert n_nodes is not None
    problem = Problem.directed(
        eps=eps,
        max_passes=max_passes,
        substrate="mesh",
        edge_axes=tuple(edge_axes),
    )
    fn = default_solver.mesh_program(problem, mesh, n_nodes)

    def run(src, dst, weight, mask, c):
        out = fn(src, dst, weight, mask, c)
        return out.best_alive, out.best_t, out.best_density, out.passes

    return run
