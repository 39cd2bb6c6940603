"""PeelEngine — the single peel-pass implementation behind every algorithm.

This is the *mechanism* layer: the declarative front door that lowers onto
it lives in core/api.py (``Problem`` -> policy × backend × substrate ->
``run_peel``); prefer ``repro.core.solve`` unless you are composing engine
pieces directly.

Algorithms 1, 2 and 3 of the paper share one pass structure: count induced
degrees, compute the density, record the best intermediate set, remove the
below-threshold nodes.  This module implements that pass body EXACTLY ONCE
as a ``jax.lax.while_loop`` parameterized along two orthogonal axes:

  * a **RemovalPolicy** — which nodes leave the graph each pass, and what
    "density" and "keep going" mean;
  * a **DegreeBackend** — how induced degrees (and the total alive edge
    weight) are computed from the edge list.

A third axis, the **substrate**, is how the loop is launched: plain ``jit``
(core/peel*.py), a host-side chunked pass loop (core/streaming.py, which
reuses :func:`undirected_pass_step` so the removal rule still lives here),
or ``shard_map`` over a device mesh (core/mapreduce.py, which runs
:func:`run_peel` *inside* the mapped function with a psum'ing backend).

A fourth knob, the **compaction runtime**, is how the loop is *scheduled*
across shrinking buffers: the paper's Lemma 4 guarantees the node set
shrinks by a ``(1+eps)`` factor per pass, so scanning all ``m`` padded edge
slots every pass wastes geometrically-growing fractions of the buffer.
:func:`run_peel` therefore supports running in SEGMENTS: ``compact_below``
stops the while-loop as soon as the post-removal alive edge count falls
under the threshold, and ``init_alive`` / ``init_t_alive`` / ``init_t``
let the next segment continue the SAME loop (absolute pass counter,
best-set tracking merged by the caller) on a smaller renumbered buffer.
The host-side gather/relabel ladder lives in core/api.py
(``Problem(compaction='geometric')``); compaction is pure
renumbering, so segmented runs are bit-identical to single-segment runs
for integer-valued edge weights (and reassociation-level equal otherwise).
Pass ``k`` then costs ``O(m_k)`` instead of ``O(m)`` — amortized ``O(m)``
total work across the ladder.

Policy × backend matrix (the paper section each cell realizes)::

    policy \\ backend   | exact segsum | count-sketch | pallas tiled | mesh psum
    -------------------+--------------+--------------+--------------+-----------
    undirected_        | Alg 1 (§4.1) | §5.1, Table 4| kernels/     | §5.2 MapReduce
      threshold        |              |              | peel_degree  | (+ sketch §5.1)
    at_least_k_        | Alg 2 (§4.2) |      —*      |      —*      | §5.2 (topk)
      fraction         |              |              |              |
    directed_st        | Alg 3 (§4.3) | §5.1 per-    |      —*      | §5.2 (directed)
                       |              | endpoint     |              |

    —* = composes through the same engine but has no dedicated wrapper yet;
    any DegreeBackend works with any policy of matching directedness.
    On a src-sorted unit-weight buffer the exact column's undirected count
    reads its ``src`` half off CSR offsets (:class:`SortedSrcBackend`).

The removal threshold ``2(1+eps)·rho(S)`` exists only here
(:func:`removal_threshold`); wrappers must not re-derive it.

Adding a new backend
====================
Implement an object with

  ``undirected(edges, w_alive) -> (deg[N], total)`` and/or
  ``directed(edges, w_alive) -> (out_deg[N], in_deg[N], total)``

where ``w_alive`` is the per-edge alive weight the engine already computed
(0.0 for masked/dead edges).  Return the *global* degree vector — inside a
``shard_map`` substrate that means psum'ing your local partials (fuse the
scalar ``total`` into the same reduction; see :class:`MeshSegmentSumBackend`).
Then pass an instance to :func:`run_peel` — no loop code is needed.

Adding a new policy is the same exercise against :class:`RemovalPolicy`:
density/eligible/keep_going plus a ``removal`` rule returning the per-side
removal bitmaps.  Parallel peeling variants (shared-memory batched removal,
directed-stream policies) slot in here rather than as new loops.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp

from repro.graph.edgelist import EdgeList

# ---------------------------------------------------------------------------
# The one threshold site (acceptance: grep for "2.0 * (1.0 + eps)")
# ---------------------------------------------------------------------------


def removal_threshold(eps: float, rho: jax.Array) -> jax.Array:
    """The paper's removal threshold 2(1+eps)·rho(S) — the only place the
    expression exists in the codebase."""
    return 2.0 * (1.0 + eps) * rho


class SortedSrc(NamedTuple):
    """The ``src`` column of an edge buffer whose real slots come first
    with ``src`` non-decreasing and unit weights, given by its CSR
    offsets: node ``v`` owns slots ``[offsets[v], offsets[v + 1])`` and
    ``offsets[n_nodes]`` is the real slot count.
    :func:`segment_degree_count` takes it in place of the ids.  Like the
    ids it has the buffer's ``shape``, so code that wraps the count and
    sizes per-slot arrays from ``src`` (a fault injected into it, say)
    still runs."""

    offsets: jax.Array  # int32[n_nodes + 1]
    n_slots: int  # the buffer's slot count, real and padding

    @property
    def shape(self) -> Tuple[int]:
        return (self.n_slots,)


_SCAN_BLOCK = 128


def _slot_prefix(x: jax.Array, bound: int = 1) -> jax.Array:
    """int32 sums of ``x[:j]`` at every slot ``j`` in ``0..len(x)`` (the
    array may run past ``len(x) + 1``), for ``0 <= x <= bound``.

    Blocks of 128 slots take their prefix as a matmul with a triangle of
    ones, exact in float32 since every in-block sum stays below 2^24
    (bfloat16 inputs while ``bound`` fits its 8 bits); the block sums
    recurse.  Unlike ``jnp.cumsum``, whose reduce-window XLA's TPU
    rewrite leaves without op names, every op here keeps the caller's
    named scope."""
    n = x.shape[0]
    b = _SCAN_BLOCK
    if n < b or bound * b >= 2**24:
        return jnp.pad(jax.lax.associative_scan(jnp.add, x), (1, 0))
    rows = n // b + 1  # covers slot n
    xb = jnp.pad(x, (0, rows * b - n)).reshape(rows, b)
    dt = jnp.bfloat16 if bound <= 256 else jnp.float32
    tri = jnp.triu(jnp.ones((b, b), dt), 1)
    within = jnp.matmul(
        xb.astype(dt), tri, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    before = _slot_prefix(xb.sum(1), bound * b)[:rows]
    return (within + before[:, None]).reshape(rows * b)


def segment_degree_count(
    src, dst: jax.Array, w_alive: jax.Array, n_nodes: int
) -> Tuple[jax.Array, jax.Array]:
    """The reduce-side degree count of §5.2: both-endpoint segment_sum plus
    the total alive edge weight.  The ONLY implementation of the exact
    undirected count — :class:`ExactBackend`, :class:`SortedSrcBackend`,
    ``density.exact_degrees`` and the streaming chunk reducer all delegate
    here (like :func:`removal_threshold`, the expression exists once).

    ``src`` is the int32[E] ids, or a :class:`SortedSrc`: the ``src`` half
    is then read off an int32 prefix sum of the alive slots at the CSR
    offsets (:func:`_slot_prefix`) instead of scattered.  With unit
    weights every count is an integer below 2^24, exact in float32, so
    the two give the same bits."""
    if isinstance(src, SortedSrc):
        with jax.named_scope("sorted_src"):
            cs = _slot_prefix((w_alive != 0).astype(jnp.int32))
            at = cs.at[src.offsets].get(
                indices_are_sorted=True, mode="promise_in_bounds"
            )
            deg = (at[1:] - at[:-1]).astype(jnp.float32)
    else:
        deg = jax.ops.segment_sum(w_alive, src, num_segments=n_nodes)
    deg = deg + jax.ops.segment_sum(w_alive, dst, num_segments=n_nodes)
    return deg, jnp.sum(w_alive)


def compact_edges(
    ok: jax.Array, arrays: Tuple[jax.Array, ...], capacity: int
) -> Tuple[jax.Array, ...]:
    """The IN-PROGRAM compact step of the segment loop: masked prefix-sum
    relabeling of edge slots.  Slots where ``ok`` is True are stable-scattered
    (original order preserved) to the front of fresh ``capacity``-slot zero
    buffers; everything else lands out of bounds and is dropped.

    Pure and traceable — this is what lets a compaction ladder run entirely
    inside one compiled program (the mesh ladder pairs it with an all-gather;
    see ``core/mapreduce.mesh_compact_edges``).  Unlike the host ladder's
    gather, the target ``capacity`` is STATIC: callers must guarantee the
    survivor count fits (the ``compact_below`` trigger is exactly that
    guarantee — a segment only exits below half its buffer, and survivors of
    a terminated run are never peeled again, so overflow drops are harmless).

    Spelled as prefix-sum + rank search + gather rather than a scatter:
    ``searchsorted`` finds the k-th survivor's slot, and XLA lowers the
    gather an order of magnitude faster than the equivalent masked scatter
    on CPU (measured 8x on the tracked benchmark's rung sizes).
    """
    cs = jnp.cumsum(ok.astype(jnp.int32))
    ranks = jnp.arange(1, capacity + 1, dtype=jnp.int32)
    idx = jnp.searchsorted(cs, ranks, side="left")  # len(ok) when rank > total
    return tuple(a.at[idx].get(mode="fill", fill_value=0) for a in arrays)


# ---------------------------------------------------------------------------
# State / outcome — the single pair replacing the old per-loop families
# ---------------------------------------------------------------------------


class PassStats(NamedTuple):
    """Per-pass scalars handed to the policy's removal rule."""

    rho: jax.Array  # float32[] density of the current set
    total: jax.Array  # float32[] alive edge weight |E(S)| (or |E(S,T)|)
    n_s: jax.Array  # int32[] |S|
    n_t: jax.Array  # int32[] |T| (== |S| for undirected policies)


class PeelState(NamedTuple):
    """Loop carry.  For undirected policies the T-side arrays are empty
    ``bool[0]`` placeholders so the pytree structure stays uniform."""

    alive: jax.Array  # bool[N] current S
    t_alive: jax.Array  # bool[N] current T (directed) | bool[0]
    best_alive: jax.Array  # bool[N] best S seen
    best_t: jax.Array  # bool[N] best T seen (directed) | bool[0]
    best_rho: jax.Array  # float32[]
    best_size: jax.Array  # int32[] |S| of the best set
    t: jax.Array  # int32[] pass counter
    alive_edges: jax.Array  # int32[] post-removal alive edge count (0 if untracked)
    edge_ok: jax.Array  # bool[E] post-removal edge filter | bool[0] if untracked
    history_n: jax.Array  # int32[hist_len]
    history_m: jax.Array  # float32[hist_len]
    history_rho: jax.Array  # float32[hist_len]


class PeelOutcome(NamedTuple):
    """Result of any peel run; every public result type aliases this."""

    best_alive: jax.Array  # bool[N] the output set S~ (S side for directed)
    best_t: jax.Array  # bool[N] T side (directed) | bool[0]
    best_density: jax.Array  # float32[] rho of the best set
    best_size: jax.Array  # int32[] |S~|
    passes: jax.Array  # int32[] passes executed
    alive: jax.Array  # bool[N] FINAL S bitmap (for phased/compacted runs)
    t_alive: jax.Array  # bool[N] final T bitmap | bool[0]
    history_n: jax.Array  # int32[hist_len] per-pass |S| (-1 padding)
    history_m: jax.Array  # float32[hist_len] per-pass |E(S)|
    history_rho: jax.Array  # float32[hist_len] per-pass rho

    @property
    def best_s(self) -> jax.Array:
        """Directed-result spelling of the S-side best bitmap."""
        return self.best_alive


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------


class RemovalPolicy(Protocol):
    """What a pass removes; instances may close over traced scalars."""

    directed: bool

    def density(self, total: jax.Array, n_s: jax.Array, n_t: jax.Array) -> jax.Array:
        """rho of the current set(s)."""

    def eligible(self, n_s: jax.Array, n_t: jax.Array) -> jax.Array:
        """May the current set become the recorded best?"""

    def keep_going(self, n_s: jax.Array, n_t: jax.Array) -> jax.Array:
        """while-loop continuation test (max_passes is handled by the engine)."""

    def removal(
        self,
        s_alive: jax.Array,
        t_alive: jax.Array,
        deg_s: jax.Array,
        deg_t: jax.Array,
        stats: PassStats,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """(remove-from-S bitmap, remove-from-T bitmap or None)."""


def _undirected_density(total, n_s):
    return jnp.where(n_s > 0, total / jnp.maximum(n_s, 1), 0.0)


@dataclasses.dataclass(frozen=True)
class UndirectedThreshold:
    """Algorithm 1: drop every node with deg <= 2(1+eps)·rho(S).

    The min-degree progress fallback (remove the current minimum-degree
    nodes when rounding would make the removal set empty) preserves the
    approximation proof verbatim and guarantees termination.
    """

    eps: float
    directed: bool = dataclasses.field(default=False, init=False)

    def density(self, total, n_s, n_t):
        return _undirected_density(total, n_s)

    def eligible(self, n_s, n_t):
        return n_s > 0

    def keep_going(self, n_s, n_t):
        return n_s > 0

    def removal(self, s_alive, t_alive, deg_s, deg_t, stats):
        thresh = removal_threshold(self.eps, stats.rho)
        deg_alive = jnp.where(s_alive, deg_s, jnp.inf)
        min_deg = jnp.min(deg_alive)
        rm = s_alive & ((deg_s <= thresh) | (deg_s <= min_deg))
        return rm, None


@dataclasses.dataclass(frozen=True)
class AtLeastKFraction:
    """Algorithm 2: of the below-threshold candidates A~(S), remove only the
    eps/(1+eps)·|S| lowest-degree ones (a deterministic choice of the subset
    the paper leaves free); only sets with |S| >= k are eligible.

    ``ceil_count``/``min_deg_fallback`` select between the two historical
    realizations (single-device used floor + fallback; the distributed one
    used ceil without) so both keep their exact pre-refactor outputs.
    """

    k: int
    eps: float
    min_deg_fallback: bool = True
    ceil_count: bool = False
    directed: bool = dataclasses.field(default=False, init=False)

    def density(self, total, n_s, n_t):
        return _undirected_density(total, n_s)

    def eligible(self, n_s, n_t):
        return n_s >= self.k

    def keep_going(self, n_s, n_t):
        return n_s >= self.k

    def removal(self, s_alive, t_alive, deg_s, deg_t, stats):
        thresh = removal_threshold(self.eps, stats.rho)
        if self.min_deg_fallback:
            deg_alive = jnp.where(s_alive, deg_s, jnp.inf)
            cand = s_alive & ((deg_s <= thresh) | (deg_s <= jnp.min(deg_alive)))
        else:
            cand = s_alive & (deg_s <= thresh)
        nf = stats.n_s.astype(jnp.float32)
        if self.ceil_count:
            r = jnp.ceil(nf * self.eps / (1.0 + self.eps)).astype(jnp.int32)
        else:
            r = ((self.eps / (1.0 + self.eps)) * nf).astype(jnp.int32)
        r = jnp.maximum(r, 1)
        # Rank candidates by (degree, node id): stable argsort puts every
        # candidate ahead of non-candidates (their key is +inf).
        n = deg_s.shape[0]
        key = jnp.where(cand, deg_s, jnp.inf)
        order = jnp.argsort(key)
        rank = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
        rm = cand & (rank < r)
        return rm, None


@dataclasses.dataclass(frozen=True)
class DirectedST:
    """Algorithm 3 for a fixed ratio guess c = |S|/|T| (c may be traced):
    peel S by out-degree when |S|/|T| >= c, else peel T by in-degree."""

    eps: float
    c: Any  # float or traced float32 scalar (vmap-able over the c grid)
    directed: bool = dataclasses.field(default=True, init=False)

    def density(self, total, n_s, n_t):
        denom = jnp.sqrt(
            jnp.maximum(n_s.astype(jnp.float32), 1.0)
            * jnp.maximum(n_t.astype(jnp.float32), 1.0)
        )
        return jnp.where((n_s > 0) & (n_t > 0), total / denom, 0.0)

    def eligible(self, n_s, n_t):
        return (n_s > 0) & (n_t > 0)

    def keep_going(self, n_s, n_t):
        return (n_s > 0) & (n_t > 0)

    def removal(self, s_alive, t_alive, out_deg, in_deg, stats):
        ns_f = jnp.maximum(stats.n_s.astype(jnp.float32), 1.0)
        nt_f = jnp.maximum(stats.n_t.astype(jnp.float32), 1.0)
        peel_s = ns_f / nt_f >= self.c
        thr_s = (1.0 + self.eps) * stats.total / ns_f
        outd = jnp.where(s_alive, out_deg, jnp.inf)
        rm_s = s_alive & ((out_deg <= thr_s) | (out_deg <= jnp.min(outd)))
        thr_t = (1.0 + self.eps) * stats.total / nt_f
        ind = jnp.where(t_alive, in_deg, jnp.inf)
        rm_t = t_alive & ((in_deg <= thr_t) | (in_deg <= jnp.min(ind)))
        return rm_s & peel_s, rm_t & ~peel_s


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------


class DegreeBackend(Protocol):
    """Induced-degree computation.  ``w_alive`` is the engine-computed
    per-edge alive weight; implementations return GLOBAL degrees + total."""

    def undirected(
        self, edges: EdgeList, w_alive: jax.Array
    ) -> Tuple[jax.Array, jax.Array]: ...

    def directed(
        self, edges: EdgeList, w_alive: jax.Array
    ) -> Tuple[jax.Array, jax.Array, jax.Array]: ...


class ExactBackend:
    """segment_sum degrees — the paper's reduce-side count (§5.2, 1 device)."""

    def undirected(self, edges, w_alive):
        return segment_degree_count(edges.src, edges.dst, w_alive, edges.n_nodes)

    def directed(self, edges, w_alive):
        n = edges.n_nodes
        out_deg = jax.ops.segment_sum(w_alive, edges.src, num_segments=n)
        in_deg = jax.ops.segment_sum(w_alive, edges.dst, num_segments=n)
        return out_deg, in_deg, jnp.sum(w_alive)


class SortedSrcBackend:
    """Exact undirected degrees for an edge buffer whose real slots come
    first with ``src`` non-decreasing and unit weights: the ``src`` half
    is a prefix sum read at the buffer's CSR ``src_offsets``
    (:class:`SortedSrc`), the ``dst`` half a ``segment_sum``.
    The caller checks the buffer and builds the offsets once per buffer
    (``core/api.py``); :class:`ExactBackend`, which the mesh inherits,
    keeps both scatters."""

    def __init__(self, src_offsets: jax.Array):
        self.src_offsets = src_offsets

    def undirected(self, edges, w_alive):
        src = SortedSrc(self.src_offsets, edges.n_edges_padded)
        return segment_degree_count(src, edges.dst, w_alive, edges.n_nodes)

    def directed(self, edges, w_alive):
        raise NotImplementedError(
            "SortedSrcBackend counts undirected degrees; the directed rule "
            "keeps ExactBackend's two scatters"
        )


class FnBackend:
    """Adapts an undirected degree function ``fn(edges, w_alive) -> deg[N]``
    (the Pallas tiled-degree kernel's) into a DegreeBackend."""

    def __init__(self, fn):
        self.fn = fn

    def undirected(self, edges, w_alive):
        return self.fn(edges, w_alive), jnp.sum(w_alive)

    def directed(self, edges, w_alive):
        raise NotImplementedError(
            "FnBackend degrees are undirected; use a backend with a directed() rule"
        )


@dataclasses.dataclass(frozen=True)
class MeshSegmentSumBackend:
    """Mesh-sharded degrees for use INSIDE ``shard_map`` (paper §5.2).

    Local segment_sum partials over the edge shard, then ONE fused psum of
    ``[deg | total]`` over the edge axes — the density counter rides along
    in the same collective, so a pass costs exactly one reduction.
    ``wire_dtype='bf16'`` halves the degree psum (see core/mapreduce.py).
    """

    axes: Tuple[str, ...]
    wire_dtype: str = "f32"

    def _psum(self, packed: jax.Array) -> jax.Array:
        if self.wire_dtype == "bf16":
            return jax.lax.psum(packed.astype(jnp.bfloat16), self.axes).astype(
                jnp.float32
            )
        return jax.lax.psum(packed, self.axes)

    def undirected(self, edges, w_alive):
        deg, total = ExactBackend().undirected(edges, w_alive)
        packed = self._psum(jnp.concatenate([deg, total[None]]))
        return packed[:-1], packed[-1]

    def directed(self, edges, w_alive):
        n = edges.n_nodes
        out_deg, in_deg, total = ExactBackend().directed(edges, w_alive)
        packed = self._psum(jnp.concatenate([out_deg, in_deg, total[None]]))
        return packed[:n], packed[n : 2 * n], packed[-1]

    def count_edges(self, ok: jax.Array) -> jax.Array:
        """Global alive-edge count (the compaction trigger): local count of
        this shard's alive edges, psummed over the edge axes so every device
        agrees on when a segment ends."""
        return jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), self.axes)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


def _count_ok(backend, ok: jax.Array) -> jax.Array:
    """int32[] count of an alive-edge mask.  Backends that reduce across
    devices (shard_map substrates) expose ``count_edges`` so the segment
    boundary is a collective decision; everything else counts locally."""
    counter = getattr(backend, "count_edges", None)
    if counter is not None:
        return counter(ok)
    return jnp.sum(ok.astype(jnp.int32))


@jax.named_scope("peel")
def run_peel(
    edges: EdgeList,
    policy: RemovalPolicy,
    backend: DegreeBackend,
    max_passes: int,
    *,
    track_history: bool = False,
    init_alive: Optional[jax.Array] = None,
    init_t_alive: Optional[jax.Array] = None,
    init_best_empty: bool = False,
    init_t: Optional[jax.Array] = None,
    compact_below: Optional[int] = None,
    init_alive_edges: Optional[jax.Array] = None,
    init_ok_from_mask: bool = False,
    with_edge_state: bool = False,
) -> PeelOutcome:
    """Runs the peel loop to completion.  Pure and traceable: wrappers add
    ``jit``/``vmap``/``shard_map`` around it (substrate axis).

    Segment controls (the compaction runtime; see the module docstring):
    ``init_alive`` / ``init_t_alive`` seed S / T (default: all nodes) on a
    renumbered buffer; ``init_best_empty`` starts the best set at empty
    instead of S_0 (the recorded best is then only ever a set the loop
    actually evaluated); ``init_t`` (int32 scalar) continues the ABSOLUTE
    pass counter so ``t < max_passes`` budgets and ``track_history``
    indices span segments; ``compact_below`` stops the loop once the
    post-removal alive edge count drops under it (the caller then gathers
    survivors into a smaller buffer and re-enters with the carried state).
    The post-removal edge filter is CARRIED in the loop state and reused
    as the next pass's filter, so the trigger adds no O(m) scan per pass —
    each pass computes the mask exactly once, like the classic loop.
    Callers that already know the entry state can skip the one entry-time
    filter too: ``init_ok_from_mask`` declares that every masked edge has
    both endpoints alive initially (true for a freshly compacted buffer,
    whose gather kept exactly the alive edges), and ``init_alive_edges``
    supplies its count (the survivor count the compaction just computed).
    ``compact_below=None`` is the classic single-segment run — the count
    and the carried mask are never materialized.

    ``with_edge_state`` (requires ``compact_below``) returns ``(outcome,
    edge_ok, alive_edges)`` instead: the carried post-removal edge filter
    and its count at exit — exactly the survivor set an in-program
    compaction needs, already computed by the final pass (the single-program
    mesh ladder reuses it instead of paying another O(m) filter).

    Every op carries its phase in its name: ``peel/.../edge_filter``,
    ``degree_count``, ``best_set`` or ``removal`` (``jax.named_scope``;
    op metadata only, the compiled program is the same), so a device
    trace attributes its time to the pass body's phases.
    """
    n = edges.n_nodes
    directed = policy.directed
    hist_len = max_passes if track_history else 1
    dummy = jnp.zeros((0,), bool)

    alive0 = jnp.ones((n,), bool) if init_alive is None else init_alive
    if directed:
        ta0 = alive0 if init_t_alive is None else init_t_alive
    else:
        ta0 = dummy
    best0 = jnp.zeros_like(alive0) if init_best_empty else alive0
    t0 = jnp.asarray(0 if init_t is None else init_t, jnp.int32)

    def counts(s: PeelState):
        n_s = jnp.sum(s.alive.astype(jnp.int32))
        n_t = jnp.sum(s.t_alive.astype(jnp.int32)) if directed else n_s
        return n_s, n_t

    def cond(s: PeelState):
        n_s, n_t = counts(s)
        going = policy.keep_going(n_s, n_t) & (s.t < max_passes)
        if compact_below is not None:
            going = going & (s.alive_edges >= compact_below)
        return going

    def body(s: PeelState) -> PeelState:
        ta = s.t_alive if directed else s.alive
        # (3) of §5.2: the per-pass edge filter against the alive bitmap(s).
        # Compacted segments carry it from the previous pass's removal, so
        # it is computed exactly once per pass either way.
        with jax.named_scope("edge_filter"):
            if compact_below is not None:
                ok = s.edge_ok
            else:
                ok = edges.mask & s.alive[edges.src] & ta[edges.dst]
        # (2): the degree count — the only backend-dependent step.
        with jax.named_scope("degree_count"):
            w_alive = jnp.where(ok, edges.weight, 0.0)
            if directed:
                deg_s, deg_t, total = backend.directed(edges, w_alive)
            else:
                deg_s, total = backend.undirected(edges, w_alive)
                deg_t = deg_s
        # (1): density + best-intermediate-set tracking.
        with jax.named_scope("best_set"):
            n_s, n_t = counts(s)
            rho = policy.density(total, n_s, n_t)
            stats = PassStats(rho=rho, total=total, n_s=n_s, n_t=n_t)

            improved = policy.eligible(n_s, n_t) & (rho > s.best_rho)
            best_alive = jnp.where(improved, s.alive, s.best_alive)
            best_t = jnp.where(improved, ta, s.best_t) if directed else s.best_t
            best_rho = jnp.where(improved, rho, s.best_rho)
            best_size = jnp.where(improved, n_s, s.best_size)

        with jax.named_scope("removal"):
            rm_s, rm_t = policy.removal(s.alive, ta, deg_s, deg_t, stats)
            alive = s.alive & ~rm_s
            t_alive = (ta & ~rm_t) if directed else s.t_alive

        if compact_below is not None:
            with jax.named_scope("edge_filter"):
                ok_next = edges.mask & alive[edges.src] & (
                    t_alive if directed else alive
                )[edges.dst]
                ae = _count_ok(backend, ok_next)
        else:
            ok_next, ae = s.edge_ok, s.alive_edges

        if track_history:
            hn = s.history_n.at[s.t].set(n_s)
            hm = s.history_m.at[s.t].set(total)
            hr = s.history_rho.at[s.t].set(rho)
        else:
            hn, hm, hr = s.history_n, s.history_m, s.history_rho
        return PeelState(
            alive, t_alive, best_alive, best_t, best_rho, best_size,
            s.t + 1, ae, ok_next, hn, hm, hr,
        )

    if compact_below is not None:
        with jax.named_scope("edge_filter"):
            if init_ok_from_mask:
                ok0 = edges.mask
            else:
                # One O(m) filter at segment entry; pass 1 reuses it.
                ok0 = (
                    edges.mask
                    & alive0[edges.src]
                    & (ta0 if directed else alive0)[edges.dst]
                )
            if init_alive_edges is not None:
                ae0 = jnp.asarray(init_alive_edges, jnp.int32)
            else:
                ae0 = _count_ok(backend, ok0)
    else:
        ok0 = jnp.zeros((0,), bool)
        ae0 = jnp.asarray(0, jnp.int32)
    init = PeelState(
        alive=alive0,
        t_alive=ta0,
        best_alive=best0,
        best_t=(jnp.zeros_like(ta0) if init_best_empty else ta0) if directed else dummy,
        best_rho=jnp.asarray(-jnp.inf, jnp.float32),
        best_size=jnp.asarray(0, jnp.int32),
        t=t0,
        alive_edges=ae0,
        edge_ok=ok0,
        history_n=jnp.full((hist_len,), -1, jnp.int32),
        history_m=jnp.zeros((hist_len,), jnp.float32),
        history_rho=jnp.zeros((hist_len,), jnp.float32),
    )
    out = jax.lax.while_loop(cond, body, init)
    outcome = PeelOutcome(
        best_alive=out.best_alive,
        best_t=out.best_t,
        best_density=out.best_rho,
        best_size=out.best_size,
        passes=out.t,
        alive=out.alive,
        t_alive=out.t_alive,
        history_n=out.history_n,
        history_m=out.history_m,
        history_rho=out.history_rho,
    )
    if with_edge_state:
        if compact_below is None:
            raise ValueError("with_edge_state needs compact_below (the "
                             "carried filter is only materialized then)")
        return outcome, out.edge_ok, out.alive_edges
    return outcome


# ---------------------------------------------------------------------------
# Host-substrate policy step (the streaming driver's removal rule)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("eps",))
def undirected_pass_step(
    alive: jax.Array, deg: jax.Array, total: jax.Array, eps: float
) -> Tuple[jax.Array, jax.Array]:
    """One Algorithm-1 pass on explicit node state: ``(new_alive, rho)``.

    The semi-streaming driver accumulates ``deg``/``total`` by chunked
    passes over out-of-core edges and then applies THIS step, so the
    threshold/removal logic is shared with every in-core substrate.
    """
    policy = UndirectedThreshold(eps)
    n_alive = jnp.sum(alive.astype(jnp.int32))
    total = jnp.asarray(total, jnp.float32)
    rho = policy.density(total, n_alive, n_alive)
    stats = PassStats(rho=rho, total=total, n_s=n_alive, n_t=n_alive)
    rm, _ = policy.removal(alive, alive, deg, deg, stats)
    return alive & ~rm, rho
