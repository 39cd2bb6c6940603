"""One front door for the paper's densest-subgraph algorithms.

The public surface is three names:

  * :class:`Problem` — a frozen, hashable spec of WHAT to solve: the
    objective (Algorithm 1/2/3), eps, k, the directed ratio c (or None for
    the geometric c-grid), the degree backend (``exact | sketch | pallas |
    auto``) and the launch substrate (``jit | mesh | streaming | auto``).
  * :func:`solve` / :class:`Solver` — lowers a Problem onto the PeelEngine's
    RemovalPolicy × DegreeBackend × substrate axes (core/engine.py) and runs
    it.  A Solver memoizes the jitted programs keyed on the Problem's static
    fields plus ``(n_nodes, padded m, dtype)`` so repeated calls at
    production request rates never retrace; :data:`default_solver` backs the
    module-level helpers.
  * :func:`solve_batch` — the ROADMAP's batched driver: multi-eps, multi-c
    and stacked same-shape-graph sweeps as ONE vmapped XLA program (the
    engine is vmap-clean; the directed c-grid proved it).

Every result is a :class:`DenseSubgraphResult`: the engine's
:class:`~repro.core.engine.PeelOutcome` arrays plus a static
:class:`Provenance` recording which cell of the policy × backend × substrate
matrix actually ran.

Lowering map (Problem field -> engine axis)::

    objective  undirected   -> UndirectedThreshold(eps)           (Alg 1, §4.1)
               at_least_k   -> AtLeastKFraction(k, eps, variants) (Alg 2, §4.2)
               directed     -> DirectedST(eps, c)                 (Alg 3, §4.3)
    backend    exact        -> ExactBackend (segment_sum), or SortedSrcBackend
                               (src half off CSR offsets) on a src-sorted,
                               unit-weight buffer of the jit ladder or an
                               eps sweep
               sketch       -> SketchBackend / _MeshSketchBackend (§5.1)
               pallas       -> tiled-degree kernel via FnBackend  (kernels/)
    substrate  jit          -> jax.jit(run_peel)
               mesh         -> shard_map + psum backends          (§5.2)
               streaming    -> StreamingDensest chunked driver    (§4, semi-streaming)
    compaction geometric    -> Solver._run_compacted host ladder  (jit; amortized O(m))
                               or one single-program ladder       (mesh)
"""

from __future__ import annotations

import collections
import collections.abc
import concurrent.futures
import dataclasses
import functools
import math
import os
from typing import (
    Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple, Union,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro import constants
from repro.core.density import max_passes_bound
from repro.core.engine import (
    AtLeastKFraction,
    DirectedST,
    ExactBackend,
    FnBackend,
    MeshSegmentSumBackend,
    PeelOutcome,
    RemovalPolicy,
    SortedSrcBackend,
    UndirectedThreshold,
    run_peel,
)
from repro.graph.edgelist import EdgeList
from repro.graph.partition import ladder_schedule, pow2_bucket

__all__ = [
    "DenseSubgraphResult",
    "Problem",
    "Provenance",
    "Solver",
    "default_solver",
    "run_cell",
    "solve",
    "solve_batch",
    "stack_graphs",
]

_OBJECTIVES = ("undirected", "at_least_k", "directed")
_BACKENDS = ("exact", "sketch", "pallas", "auto")
_SUBSTRATES = ("jit", "mesh", "streaming", "local", "auto")
_COMPACTIONS = ("off", "geometric", "auto")
_STREAM_MODES = ("insert", "turnstile")

# Above this node count, "auto" trades the O(n) exact degree vector for the
# O(t*b) Count-Sketch (§5.1's memory regime).
_AUTO_SKETCH_NODES = 1_000_000

# Geometric compaction ladder floors/capacities: aliased from the one
# constants surface (repro.constants — rationale and the pow2-constants
# analysis rule live there).  Module-level aliases keep the historical
# names monkeypatch-able (tests patch api._LADDER_MIN_EDGES to force deep
# ladders at tiny sizes).
_COMPACT_MIN_EDGES = constants.COMPACT_MIN_EDGES
_COMPACT_MIN_NODES = constants.COMPACT_MIN_NODES
_COMPACT_MAX_SEGMENTS = constants.COMPACT_MAX_SEGMENTS
_COMPACT_PARALLEL_MIN_EDGES = constants.COMPACT_PARALLEL_MIN_EDGES
_LADDER_STRIDE = constants.LADDER_STRIDE
_LADDER_MIN_EDGES = constants.LADDER_MIN_EDGES
_LOCAL_BUDGET = constants.LOCAL_BUDGET
_LOCAL_ROUNDS = constants.LOCAL_ROUNDS


# ---------------------------------------------------------------------------
# Problem — the declarative spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Problem:
    """What to solve.  Frozen and hashable: the static half of a Solver
    cache key.  Use the :meth:`undirected` / :meth:`at_least_k` /
    :meth:`directed` constructors for the common cases; 30-second tour::

        from repro.core import Problem, solve
        res = solve(edges, Problem.undirected(eps=0.5))
        res.best_density, res.nodes(), res.provenance

    Field-by-field reference (fields marked *cache-key-exempt* never force
    a recompile: the Solver drops them from program-cache keys whenever the
    resolved cell does not read them — see :meth:`Solver._key`):

    **Objective** (which algorithm):

    * ``objective`` — ``'undirected'`` (Alg 1), ``'at_least_k'`` (Alg 2),
      ``'directed'`` (Alg 3).
    * ``eps`` — slack of the removal threshold ``2(1+eps)·rho``; drives
      both the approximation factor and the O(log n / eps) pass bound.
    * ``k`` — Alg 2 only: minimum ``|S|``.
    * ``c`` — Alg 3 only: the ``|S|/|T|`` ratio guess; ``None`` sweeps the
      geometric c-grid (resolution ``c_delta``), the paper's practical
      recipe.  ``c`` enters compiled programs as a RUNTIME scalar, so the
      whole grid shares one compilation (cache-key-exempt on those kinds).
    * ``c_delta`` — grid ratio (> 1); host-side only, cache-key-exempt.
    * ``max_passes`` — static trip count; ``None`` means the Lemma 4 bound
      (doubled for directed, Lemma 13).  Keys the cache via its resolved
      value.
    * ``track_history`` — record per-pass ``(|S|, edge mass, rho)``.

    **Backend** (how induced degrees are computed):

    * ``backend`` — ``'exact'`` (segment_sum), ``'sketch'`` (§5.1
      Count-Sketch), ``'pallas'`` (tiled TPU kernel), or ``'auto'``
      (sketch above ~1M nodes, exact otherwise; exact when a ladder or the
      streaming substrate constrains it).
    * ``sketch_tables`` / ``sketch_buckets`` / ``sketch_seed`` — §5.1
      table geometry; cache-key-exempt unless the sketch backend runs.
      The default width, 2^13 buckets, is a CPU-test width.  At Table 1
      scale hub collisions keep most estimates above the removal
      threshold and the peel stalls until the pass cap: on a Chung-Lu
      Flickr stand-in (976K nodes, t·b/n = 0.04) it ends at 5% of the
      exact peel's density.  The benchmark's LiveJournal stand-in (4.84M
      nodes) needs 2^18 buckets (t·b/n = 0.27) and stalls at 2^17
      (PERF.md section 4).
      A sketched solve on the jit substrate reports the table geometry and
      the endpoint-table updates of its uncompacted schedule in
      ``extras["sketch"]``
      (:class:`~repro.core.countsketch.SketchCounters`).
    * ``sketch_node_chunk`` — mesh sketch only: degree-query streaming
      chunk (bounds the transient query footprint).
    * ``tile_size`` / ``tile_block`` — Pallas tile geometry;
      cache-key-exempt unless the pallas backend runs.
    * ``pallas_interpret`` — ``None`` = compiled on TPU, interpreter
      elsewhere; ``True`` forces interpret mode.

    **Substrate** (how the loop is launched):

    * ``substrate`` — ``'jit'``, ``'mesh'`` (shard_map over an
      edge-sharded device mesh, §5.2; needs ``solve(..., mesh=...)``),
      ``'streaming'`` (host-chunked driver, O(n) node state),
      ``'local'`` (Andersen per-seed exploration, below), or
      ``'auto'`` (mesh iff a mesh was supplied and >1 device is visible).
    * ``edge_axes`` / ``wire_dtype`` — mesh only: shard axes and the
      degree-psum wire format (``'bf16'`` halves the dominant collective);
      cache-key-exempt elsewhere.
    * ``stream_chunk`` / ``stream_workers`` — streaming chunk size and
      worker pool.
    * ``stream_prefetch`` — bounds the chunks resident in the async
      pipeline (the out-of-core memory contract; bit-identical to the
      synchronous order for every setting).
    * ``spill_dir`` — sends the streaming ladder's rebuilt survivor
      streams to disk-backed memmaps (atomic manifest, resume re-enters
      mid-rung).  Needs the geometric ladder: rejected on the streaming
      substrate with an explicit ``compaction='off'``.
    * ``residency_cap_edges`` — errors a too-big IN-RAM streaming rebuild
      instead of spiking memory (the spilled path is exempt — that is its
      point); pair it with ``spill_dir`` to make the cap recoverable.
      All ``stream_*``/``spill_dir``/``residency_cap_edges`` knobs are
      host-side driver state: uniformly cache-key-exempt, and ignored on
      non-streaming substrates (the irrelevant-knob convention).

    **Turnstile runtime** (dynamic graph streams with DELETIONS — the MTVV
    ℓ0-sampling runtime, core/turnstile.py; both fields are uniformly
    cache-key-exempt: the driver is host-side and its sample peel
    re-enters the program cache as an ordinary insert-mode solve):

    * ``stream_mode`` — ``'insert'`` (default; every substrate's classic
      append-only edge stream) or ``'turnstile'``: the graph is a dynamic
      stream of ±edge update batches absorbed by an ℓ0-sampling sketch,
      peeled on a uniform edge sample with density rescaled by the sample
      rate ((1+eps)·(2+2eps) end-to-end).  ``solve()`` one-shots it
      (insert the given edges, answer one query); continuous
      update/query cycles hold a live :class:`repro.core.turnstile.
      TurnstileDensest` (or the serve/ service).  Undirected, unweighted,
      exact/pallas degree backends only — ``backend='sketch'`` is
      rejected (it would sketch a sketch); mesh/streaming substrates are
      rejected; compaction is ignored (nothing to amortize at sample
      scale).
    * ``sample_edges`` — the sample budget τ: queries recover the lowest
      sketch level holding at most this many edges (level 0 ⇒ the exact
      live graph).  Larger τ tightens the sampling (1+eps) factor at
      O(τ·log n) sketch memory.  ``sketch_seed`` (below) also seeds the
      ℓ0 hash family — same seed, bit-reproducible runs.

    **Local substrate** (Andersen's per-seed exploration, arXiv
    cs/0702078 — core/local.py; all three knobs are host-side extraction
    state, uniformly cache-key-exempt: the compiled program only ever
    sees the bucket-padded candidate subgraph):

    * ``substrate='local'`` answers PER-SEED queries: ``solve(graph,
      problem, seed=<node id>)`` grows a pruned-frontier candidate set
      around the seed (work bounded by the budget, independent of n) and
      peels its induced subgraph through the same cached jit pass body.
      Undirected objective and exact backend only; compaction is forced
      off (nothing to amortize at candidate scale).  Provenance reports
      ``substrate='local'`` and ``extras['local']`` carries the
      exploration counters.  The result's density never exceeds the
      exact optimum and is (2+2eps)-approximate FOR THE CANDIDATE SET —
      the whole-graph guarantee does not survive locality
      (docs/serving.md; pinned by tests/test_property_serve.py).
    * ``local_budget`` — candidate-set size cap (the per-query work
      knob; the serving engine's degrade ladder halves it under
      pressure).
    * ``local_rounds`` — frontier expansion round cap.
    * ``local_alpha`` — prune threshold scale: a frontier vertex joins
      only with ``deg into T >= max(local_alpha * rho(T), 1)``; 1.0
      admits exactly the vertices that cannot dilute T's density.

    **Serving** (host-side, cache-key-exempt):

    * ``cache_dir`` — backs the Solver's program cache with an on-disk tier
      of serialized compiled executables, so a fresh process (a serving
      replica, a restarted worker) skips the cold compile entirely
      (``jax.experimental.serialize_executable`` under the hood; entries
      are fingerprinted by backend + jax/jaxlib/repro versions and any
      mismatch or corruption silently falls back to a recompile — see
      core/progcache.py and docs/serving.md).  ``Solver(cache_dir=...)``
      takes precedence; jit-substrate programs only (mesh executables embed
      a device topology and stay in-memory).

    **Compaction runtime** (the scheduling knob; host/ladder state, so the
    whole group is cache-key-exempt — segment programs key on bucket
    shapes instead):

    * ``compaction`` — ``'off'``: classic single-segment loop;
      ``'geometric'``: the amortized-O(m) ladder — run in segments, gather
      survivors into the next power-of-two bucket when the alive edge
      count falls below the trigger (on the jit substrate a host ladder
      around cached per-bucket programs, :meth:`Solver._run_compacted`; on
      the mesh substrate the WHOLE ladder is one compiled collective-only
      program); ``'auto'`` (DEFAULT): geometric, but off for sketch on
      the mesh substrate.
      Compaction is pure renumbering: results are bit-identical to
      ``'off'`` for integer-valued edge weights (e.g. unweighted graphs).
      A sketched ladder (jit substrate only) keeps node ids and compacts
      edges only, since Count-Sketch estimates hash node ids; the mesh
      ladder counts exact degrees only, so mesh × sketch needs ``'off'``.
      See docs/compaction.md.
    * ``min_deg_fallback`` / ``ceil_count`` — Alg 2 realization variants
      (floor+fallback is the default; ceil without fallback removes
      ``ceil(eps/(1+eps)·|S|)`` per pass); cache-key-exempt for other
      objectives.
    """

    objective: str = "undirected"
    eps: float = 0.5
    k: Optional[int] = None  # at_least_k: minimum |S|
    c: Optional[float] = None  # directed: |S|/|T| guess; None -> grid
    c_delta: float = 2.0  # directed grid resolution (§6.4)
    backend: str = "exact"
    substrate: str = "jit"
    max_passes: Optional[int] = None  # None -> Lemma 4/13 bound
    track_history: bool = False
    # Compaction runtime (scheduling; never keys compiled programs).  The
    # default is 'auto' (ROADMAP soak item, flipped after PRs 3-4): every
    # backend rides the geometric ladder by default but mesh × sketch.
    compaction: str = "auto"  # off | geometric | auto
    # Algorithm 2 realization knobs (floor+fallback by default).
    min_deg_fallback: bool = True
    ceil_count: bool = False
    # Count-Sketch (§5.1) parameters.
    sketch_tables: int = 5
    sketch_buckets: int = 1 << 13
    sketch_seed: int = 0
    sketch_node_chunk: int = 1 << 20  # mesh sketch: query streaming chunk
    # Pallas tiled-degree kernel parameters.  ``pallas_interpret=None`` means
    # "compiled on TPU, interpreter elsewhere" (kernels resolve it against
    # jax.default_backend()); True forces interpret mode everywhere.
    tile_size: int = 1024
    tile_block: int = 512
    pallas_interpret: Optional[bool] = None
    # Mesh substrate parameters.
    edge_axes: Tuple[str, ...] = ("data",)
    wire_dtype: str = "f32"  # f32 | bf16 degree-psum wire format
    # Streaming substrate parameters.  ``stream_prefetch`` bounds the chunks
    # resident in the async pipeline; ``spill_dir`` sends the geometric
    # ladder's rebuilt survivor streams to disk-backed memmaps (out-of-core
    # compaction; None keeps survivors in host RAM).
    stream_chunk: int = 1 << 20
    stream_workers: int = 4
    stream_prefetch: int = 8
    spill_dir: Optional[str] = None
    residency_cap_edges: Optional[int] = None
    # Turnstile runtime (±edge update streams, core/turnstile.py).  Host
    # driver state, uniformly cache-key-exempt; ``sketch_seed`` above also
    # seeds the ℓ0 hash family.
    stream_mode: str = "insert"  # insert | turnstile
    sample_edges: int = 1 << 14  # ℓ0 sample budget τ (per-query peel size)
    # Local (Andersen) substrate parameters (core/local.py).  Host-side
    # exploration state, uniformly cache-key-exempt: the compiled program
    # only ever sees the bucket-padded candidate subgraph.
    local_budget: int = _LOCAL_BUDGET  # candidate-set size cap
    local_rounds: int = _LOCAL_ROUNDS  # frontier expansion round cap
    local_alpha: float = 1.0  # prune scale: deg into T >= alpha * rho(T)
    # Persistent program cache (host-side knob, uniformly cache-key-exempt):
    # directory for serialized compiled programs so a FRESH process skips the
    # cold compile (see core/progcache.py and docs/serving.md).  A
    # Solver(cache_dir=...) setting takes precedence over this field.
    cache_dir: Optional[str] = None

    def __post_init__(self):
        if self.objective not in _OBJECTIVES:
            raise ValueError(
                f"objective={self.objective!r} not in {_OBJECTIVES}"
            )
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend={self.backend!r} not in {_BACKENDS}")
        if self.substrate not in _SUBSTRATES:
            raise ValueError(
                f"substrate={self.substrate!r} not in {_SUBSTRATES}"
            )
        if self.compaction not in _COMPACTIONS:
            raise ValueError(
                f"compaction={self.compaction!r} not in {_COMPACTIONS}"
            )
        if self.objective == "at_least_k" and (self.k is None or self.k < 1):
            raise ValueError("objective='at_least_k' needs k >= 1")
        if self.c_delta <= 1.0:
            raise ValueError(
                f"c_delta={self.c_delta} must be > 1 (geometric grid ratio)"
            )
        if self.wire_dtype not in ("f32", "bf16"):
            raise ValueError(f"wire_dtype={self.wire_dtype!r} not in (f32, bf16)")
        if self.stream_prefetch < 1:
            raise ValueError(
                f"stream_prefetch={self.stream_prefetch} must be >= 1"
            )
        if self.residency_cap_edges is not None and self.residency_cap_edges < 1:
            raise ValueError(
                f"residency_cap_edges={self.residency_cap_edges} must be >= 1"
            )
        if self.stream_mode not in _STREAM_MODES:
            raise ValueError(
                f"stream_mode={self.stream_mode!r} not in {_STREAM_MODES}"
            )
        if self.sample_edges < 1:
            raise ValueError(f"sample_edges={self.sample_edges} must be >= 1")
        if self.local_budget < 1:
            raise ValueError(f"local_budget={self.local_budget} must be >= 1")
        if self.local_rounds < 1:
            raise ValueError(f"local_rounds={self.local_rounds} must be >= 1")
        if self.local_alpha < 0:
            raise ValueError(f"local_alpha={self.local_alpha} must be >= 0")
        if not isinstance(self.edge_axes, tuple):
            object.__setattr__(self, "edge_axes", tuple(self.edge_axes))

    # -- constructors -------------------------------------------------------
    @classmethod
    def undirected(cls, eps: float = 0.5, **kw) -> "Problem":
        """Algorithm 1: (2+2eps)-approximate densest subgraph."""
        return cls(objective="undirected", eps=float(eps), **kw)

    @classmethod
    def at_least_k(cls, k: int, eps: float = 0.5, **kw) -> "Problem":
        """Algorithm 2: (3+3eps)-approximate densest subgraph, |S| >= k."""
        return cls(objective="at_least_k", k=int(k), eps=float(eps), **kw)

    @classmethod
    def directed(
        cls, c: Optional[float] = None, eps: float = 0.5, **kw
    ) -> "Problem":
        """Algorithm 3: directed densest subgraph, fixed c or c-grid."""
        return cls(
            objective="directed",
            c=None if c is None else float(c),
            eps=float(eps),
            **kw,
        )

    # -- resolution ---------------------------------------------------------
    def resolve(self, n_nodes: int, have_mesh: bool = False) -> "Problem":
        """Resolves ``auto`` axes against the graph/host and validates that
        the requested matrix cell exists.  ``auto`` only picks the mesh
        substrate when the caller actually supplied a mesh (``have_mesh``)."""
        if self.stream_mode == "turnstile":
            # The turnstile runtime is its own cell: sketch updates on
            # device, sampled peel on the jit substrate (core/turnstile.py).
            if self.objective != "undirected":
                raise ValueError(
                    "stream_mode='turnstile' implements Algorithm 1 over "
                    "the MTVV edge sample; use objective='undirected'"
                )
            if self.backend == "sketch":
                raise ValueError(
                    "backend='sketch' under stream_mode='turnstile' would "
                    "sketch a sketch: the ℓ0 edge sample already bounds the "
                    "peel's degree memory — use backend='exact' or 'pallas'"
                )
            if self.substrate in ("mesh", "streaming", "local"):
                raise ValueError(
                    "stream_mode='turnstile' is its own runtime (device "
                    "sketch + sampled peel on the jit substrate); use "
                    "substrate='jit' or 'auto'"
                )
            # Compaction is an irrelevant knob at sample scale: quietly
            # ignored, like stream_* off the streaming substrate.
            return dataclasses.replace(
                self,
                backend="exact" if self.backend == "auto" else self.backend,
                substrate="jit",
                compaction="off",
            )
        if self.substrate == "local":
            # Andersen local exploration: host frontier pruning + a jit
            # solve of the bucket-padded candidate subgraph (core/local.py).
            if self.objective != "undirected":
                raise ValueError(
                    "substrate='local' prunes its frontier against the "
                    "undirected density (Andersen, arXiv cs/0702078); use "
                    "objective='undirected'"
                )
            if self.backend in ("sketch", "pallas"):
                raise ValueError(
                    "substrate='local' peels a budget-bounded candidate "
                    "subgraph — degree sketching/tiling has nothing to "
                    "amortize at that scale; use backend='exact' (or 'auto')"
                )
            # Compaction is an irrelevant knob at candidate scale: quietly
            # forced off, like the turnstile runtime.
            return dataclasses.replace(
                self,
                backend="exact" if self.backend == "auto" else self.backend,
                compaction="off",
            )
        backend = self.backend
        substrate = self.substrate
        if substrate == "auto":
            substrate = "mesh" if have_mesh and len(jax.devices()) > 1 else "jit"
        if backend == "auto":
            # The streaming driver IS the large-graph memory regime (O(n)
            # node state, out-of-core edges): its only cell is exact.
            if substrate == "streaming":
                backend = "exact"
            elif self.compaction == "geometric":
                # An explicit compaction request steers the resolution to
                # the exact backend (answers users already get; the
                # sketch rides only the jit ladder, with node ids kept).
                backend = "exact"
            else:
                backend = "sketch" if n_nodes > _AUTO_SKETCH_NODES else "exact"
        compaction = self.compaction
        # Count-Sketch estimates hash node ids.  The jit ladder keeps them
        # for a sketched solve and compacts edges only; the mesh ladder
        # counts exact degrees only.
        ladder_keeps_ids = substrate == "jit"
        if compaction == "auto":
            compaction = (
                "off" if backend == "sketch" and not ladder_keeps_ids
                else "geometric"
            )
        p = self
        if (
            backend != self.backend
            or substrate != self.substrate
            or compaction != self.compaction
        ):
            p = dataclasses.replace(
                self, backend=backend, substrate=substrate, compaction=compaction
            )
        if p.compaction != "off" and p.backend == "sketch" and not ladder_keeps_ids:
            raise ValueError(
                "Count-Sketch degree estimates hash node ids, and only the jit "
                "ladder keeps them; backend='sketch' with "
                f"substrate={p.substrate!r} needs compaction='off'"
            )
        if (
            p.spill_dir is not None
            and p.substrate == "streaming"
            and p.compaction != "geometric"
        ):
            # (On non-streaming substrates stream_* knobs — spill_dir
            # included — are uniformly ignored, per the irrelevant-knob
            # convention the program-cache keys rely on.)
            raise ValueError(
                "spill_dir is the streaming ladder's disk spill; a "
                "streaming solve needs compaction='geometric' (or 'auto') "
                "to use it"
            )
        if p.objective == "directed" and p.backend == "pallas":
            raise ValueError(
                "the tiled-degree kernel counts both endpoints (undirected); "
                "directed objectives need backend='exact' or 'sketch'"
            )
        if p.substrate == "mesh" and p.backend == "pallas":
            raise ValueError("backend='pallas' has no mesh (shard_map) cell yet")
        if p.substrate == "streaming" and (
            p.objective != "undirected" or p.backend != "exact"
        ):
            raise ValueError(
                "the streaming substrate implements Algorithm 1 with exact "
                "chunked degrees; use objective='undirected', backend='exact'"
            )
        return p

    def resolved_max_passes(self, n_nodes: int) -> int:
        """Static trip count: explicit, or the Lemma 4 bound (doubled for
        directed runs — Lemma 13 shrinks one of S/T per pass)."""
        if self.max_passes is not None:
            return int(self.max_passes)
        bound = max_passes_bound(n_nodes, self.eps)
        return 2 * bound if self.objective == "directed" else bound


# The machine-checked cache-key classification of EVERY Problem field (the
# ``cache-key-hygiene`` analysis rule parses this dict and cross-checks it
# against the dataclass — a new field that is not classified here is a
# lint error, so the contract can never silently rot):
#
#   'static'      — part of what the compiled program computes; always in
#                   the program-cache key (modulo the runtime-argument
#                   carve-outs _key documents, e.g. c / swept eps).
#   'conditional' — keys the cache only when the resolved cell reads it
#                   (sketch geometry, pallas tiles, mesh wiring); dropped
#                   otherwise so irrelevant knobs never force a recompile.
#   'exempt'      — host-side driver/scheduling state, NEVER part of a
#                   compiled program: uniformly dropped from cache keys,
#                   and reading one inside a traced program builder is a
#                   lint error (it would bake a host knob into compiled
#                   output without keying it — the cache-poisoning bug
#                   class PR 4's review caught by hand).
_FIELD_CLASS = {
    "objective": "static",
    "eps": "static",
    "k": "static",
    "c": "static",
    "backend": "static",
    "substrate": "static",
    "max_passes": "static",  # keys via its RESOLVED value (the mp slot)
    "track_history": "static",
    "min_deg_fallback": "static",
    "ceil_count": "static",
    "sketch_tables": "conditional",
    "sketch_buckets": "conditional",
    "sketch_seed": "conditional",
    "sketch_node_chunk": "conditional",
    "tile_size": "conditional",
    "tile_block": "conditional",
    "pallas_interpret": "conditional",
    "edge_axes": "conditional",
    "wire_dtype": "conditional",
    "c_delta": "exempt",
    "compaction": "exempt",
    "stream_chunk": "exempt",
    "stream_workers": "exempt",
    "stream_prefetch": "exempt",
    "spill_dir": "exempt",
    "residency_cap_edges": "exempt",
    "stream_mode": "exempt",
    "sample_edges": "exempt",
    "local_budget": "exempt",
    "local_rounds": "exempt",
    "local_alpha": "exempt",
    "cache_dir": "exempt",
}

# The uniform exclusion set _key starts from (max_passes keys separately
# through its resolved value).
_EXEMPT_FIELDS = frozenset(
    f for f, cls in _FIELD_CLASS.items() if cls == "exempt"
)


# ---------------------------------------------------------------------------
# Result type — PeelOutcome arrays + provenance
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Provenance:
    """Which cell of the policy × backend × substrate matrix produced a
    result (static metadata, hashable)."""

    objective: str
    policy: str
    backend: str
    substrate: str
    n_nodes: int
    max_passes: int
    batch: Optional[str] = None  # None | "eps" | "c" | "graphs"
    cache_hit: bool = False
    compaction: str = "off"  # off | geometric (resolved)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseSubgraphResult:
    """The one result type of the front door.

    Field-compatible with :class:`~repro.core.engine.PeelOutcome`; batched
    solves carry a leading sweep axis on every array.  ``extras`` holds
    sweep-level host data (the directed grid's per-c profile).
    """

    best_alive: jax.Array  # bool[N] the output set S~ (S side for directed)
    best_t: jax.Array  # bool[N] T side (directed) | bool[0]
    best_density: jax.Array  # float32[] rho of the best set
    best_size: jax.Array  # int32[] |S~|
    passes: jax.Array  # int32[] passes executed
    alive: jax.Array  # bool[N] final S bitmap
    t_alive: jax.Array  # bool[N] final T bitmap | bool[0]
    history_n: jax.Array  # int32[hist] per-pass |S| (-1 padding)
    # Per-pass edge mass of S.  jit/mesh record the alive WEIGHT total; the
    # streaming substrate records the alive edge COUNT (its O(n)-state
    # contract) — identical for unit weights.
    history_m: jax.Array  # float32[hist]
    history_rho: jax.Array  # float32[hist] per-pass rho
    extras: Optional[Dict[str, Any]] = None
    provenance: Optional[Provenance] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )

    @property
    def best_s(self) -> jax.Array:
        """Directed-result spelling of the S-side best bitmap."""
        return self.best_alive

    @property
    def mask(self) -> jax.Array:
        return self.best_alive

    @classmethod
    def from_outcome(
        cls,
        out: PeelOutcome,
        provenance: Optional[Provenance] = None,
        extras: Optional[Dict[str, Any]] = None,
    ) -> "DenseSubgraphResult":
        return cls(*out, extras=extras, provenance=provenance)

    # Host conveniences (not for use under tracing).
    def nodes(self) -> np.ndarray:
        """Node ids of the best set (S side for directed)."""
        return np.nonzero(np.asarray(self.best_alive))[0]

    def t_nodes(self) -> np.ndarray:
        """Node ids of the best T side (directed results)."""
        return np.nonzero(np.asarray(self.best_t))[0]

    @property
    def density(self) -> float:
        return float(self.best_density)


# ---------------------------------------------------------------------------
# Lowering: Problem -> RemovalPolicy × DegreeBackend
# ---------------------------------------------------------------------------


def _policy_for(
    problem: Problem, *, eps: Any = None, c: Any = None
) -> RemovalPolicy:
    """Problem -> RemovalPolicy.  ``eps``/``c`` may be traced scalars (the
    batched sweeps rely on it)."""
    e = problem.eps if eps is None else eps
    if problem.objective == "undirected":
        return UndirectedThreshold(e)
    if problem.objective == "at_least_k":
        return AtLeastKFraction(
            k=problem.k,
            eps=e,
            min_deg_fallback=problem.min_deg_fallback,
            ceil_count=problem.ceil_count,
        )
    cc = problem.c if c is None else c
    if cc is None:
        raise ValueError(
            "directed lowering needs a concrete or traced c; Problem.c=None "
            "(grid search) is handled by solve()/solve_batch()"
        )
    return DirectedST(eps=e, c=jnp.asarray(cc, jnp.float32))


def _backend_for(
    problem: Problem,
    n_nodes: int,
    *,
    tiling: Optional[Tuple[jax.Array, jax.Array]] = None,
    src_offsets: Optional[jax.Array] = None,
):
    """Problem -> DegreeBackend (jit substrate).  ``tiling`` carries the
    Pallas bucketing arrays as runtime values so compiled programs stay
    graph-independent; so does ``src_offsets``, the CSR offsets of a
    src-sorted unit-weight buffer (:func:`_src_offsets`), which turn the
    exact backend's undirected count into :class:`SortedSrcBackend`'s."""
    if problem.backend == "exact":
        if src_offsets is not None and problem.objective != "directed":
            return SortedSrcBackend(src_offsets)
        return ExactBackend()
    if problem.backend == "sketch":
        from repro.core.countsketch import SketchBackend, make_sketch_params

        return SketchBackend(
            make_sketch_params(
                problem.sketch_tables, problem.sketch_buckets, problem.sketch_seed
            )
        )
    if problem.backend == "pallas":
        if tiling is None:
            raise ValueError("backend='pallas' needs tiling arrays")
        from repro.kernels.peel_degree.ops import tiled_degrees

        tl, ei = tiling

        def fn(edges: EdgeList, w_alive: jax.Array) -> jax.Array:
            return tiled_degrees(
                tl, ei, w_alive,
                tile_size=problem.tile_size, n_nodes=n_nodes,
                interpret=problem.pallas_interpret,
            )

        return FnBackend(fn)
    raise ValueError(f"unresolved backend {problem.backend!r}")


def run_cell(
    edges: EdgeList,
    problem: Problem,
    *,
    eps: Any = None,
    c: Any = None,
    tiling: Optional[Tuple[jax.Array, jax.Array]] = None,
    src_offsets: Optional[jax.Array] = None,
    max_passes: Optional[int] = None,
    init_alive: Optional[jax.Array] = None,
    init_t_alive: Optional[jax.Array] = None,
    init_t: Optional[jax.Array] = None,
    init_best_empty: bool = False,
    compact_below: Optional[int] = None,
    init_alive_edges: Optional[jax.Array] = None,
    init_ok_from_mask: bool = False,
) -> PeelOutcome:
    """The pure, traceable lowering core: one Problem cell -> ``run_peel``.

    Safe under jit/vmap/shard_map; ``eps`` and ``c`` may be traced scalars.
    ``tiling`` and ``src_offsets`` are the backend's runtime arrays
    (:func:`_backend_for`).
    Every jit program of solve()/solve_batch() bottoms out here.  The
    ``init_*``/``compact_below`` segment controls are forwarded to
    :func:`~repro.core.engine.run_peel` — ``run_cell`` itself is always ONE
    segment; the host-side compaction ladder around it lives in
    :class:`Solver` (``Problem.compaction`` is ignored here).
    """
    prob = problem.resolve(edges.n_nodes)
    mp = max_passes if max_passes is not None else prob.resolved_max_passes(edges.n_nodes)
    policy = _policy_for(prob, eps=eps, c=c)
    backend = _backend_for(
        prob, edges.n_nodes, tiling=tiling, src_offsets=src_offsets
    )
    return run_peel(
        edges, policy, backend, mp, track_history=prob.track_history,
        init_alive=init_alive, init_t_alive=init_t_alive, init_t=init_t,
        init_best_empty=init_best_empty, compact_below=compact_below,
        init_alive_edges=init_alive_edges, init_ok_from_mask=init_ok_from_mask,
    )


def c_grid(n_nodes: int, delta: float = 2.0) -> np.ndarray:
    """Geometric grid of c = |S|/|T| guesses: delta^j covering [1/n, n]."""
    j_max = int(math.ceil(math.log(max(n_nodes, 2)) / math.log(delta)))
    return np.asarray([delta**j for j in range(-j_max, j_max + 1)], np.float32)


def stack_graphs(graphs: Sequence[EdgeList]) -> EdgeList:
    """Stacks same-shape EdgeLists along a leading batch axis for
    :meth:`Solver.solve_batch` (which also accepts the sequence directly).
    The result is a batched container: per-graph helpers that assume 1-D
    edge arrays (``n_edges_padded``, ``with_padding``) don't apply to it."""
    g0 = graphs[0]
    for g in graphs[1:]:
        if g.n_nodes != g0.n_nodes or g.n_edges_padded != g0.n_edges_padded:
            raise ValueError(
                "stacked sweeps need same-shape graphs: got "
                f"(n={g.n_nodes}, E={g.n_edges_padded}) vs "
                f"(n={g0.n_nodes}, E={g0.n_edges_padded})"
            )
        if g.directed != g0.directed:
            raise ValueError("stacked sweeps need uniform directedness")
    return EdgeList(
        src=jnp.stack([g.src for g in graphs]),
        dst=jnp.stack([g.dst for g in graphs]),
        weight=jnp.stack([g.weight for g in graphs]),
        mask=jnp.stack([g.mask for g in graphs]),
        n_nodes=g0.n_nodes,
        directed=g0.directed,
    )


def _tiling_arrays(edges: EdgeList, problem: Problem, pow2_pad: bool = False):
    """Host-side Pallas tile bucketing for this graph (runtime args of the
    cached program, so the compiled code is reusable across graphs).

    This is an O(E) numpy pass per call — the compiled program is cached but
    the bucketing is not (it depends on edge CONTENT, which a shape-keyed
    cache cannot see).

    ``pow2_pad`` rounds the per-tile edge capacity up to a power of two so
    the compaction ladder's re-bucketed tilings land on a bounded set of
    shapes (one compile per bucket, reused across segments and graphs)."""
    from repro.kernels.peel_degree.ops import tiling_for_edges

    tiled = tiling_for_edges(
        edges, tile_size=problem.tile_size, block=problem.tile_block,
        pow2_pad=pow2_pad,
    )
    return jnp.asarray(tiled.target_local), jnp.asarray(tiled.edge_index)


# ---------------------------------------------------------------------------
# Solver — compile caching + batched drivers
# ---------------------------------------------------------------------------


def _host_keep_going(prob: Problem, n_s: int, n_t: int) -> bool:
    """Host mirror of the policies' ``keep_going`` tests, used by the
    compaction scheduler to decide whether a segment ended by termination
    or by hitting its compaction trigger."""
    if prob.objective == "at_least_k":
        return n_s >= int(prob.k)
    if prob.objective == "directed":
        return n_s > 0 and n_t > 0
    return n_s > 0


class _Transfers:
    """Bytes one host ladder run copies across the host-device boundary,
    counted at each copy: ``down`` reads a device array back, ``up`` puts a
    host value on the device."""

    def __init__(self):
        self.to_host = 0
        self.to_device = 0

    def down(self, x) -> np.ndarray:
        # A jax.Array keeps the host copy of its first read-back, so reading
        # it again (the same graph solved again) copies nothing.
        if isinstance(x, jax.Array) and getattr(x, "_npy_value", None) is None:
            self.to_host += x.nbytes
        return np.asarray(x)

    def up(self, x, dtype=None) -> jax.Array:
        a = jnp.asarray(x, dtype)
        self.to_device += a.nbytes
        return a


# The host ladder's compaction pool: one worker per available core, capped.
# Threads start on first use and are joined at interpreter exit.
_COMPACT_WORKERS = min(
    len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
    else os.cpu_count() or 1,
    constants.COMPACT_MAX_WORKERS,
)
_COMPACT_POOL = concurrent.futures.ThreadPoolExecutor(
    _COMPACT_WORKERS, thread_name_prefix="densest-compact"
)


def _compact_chunks(m_buf: int) -> int:
    """Chunks the host compaction of an ``m_buf``-slot rung is cut into:
    one below ``_COMPACT_PARALLEL_MIN_EDGES``, else a few per pool worker."""
    if m_buf < _COMPACT_PARALLEL_MIN_EDGES:
        return 1
    return _COMPACT_WORKERS * constants.COMPACT_CHUNKS_PER_WORKER


class _DegreeCountRoutes(collections.abc.Mapping):
    """``extras["degree_count"]``, a read-only mapping: the passes whose
    ``src`` half of the degree count was read off a prefix sum at CSR
    offsets (``sorted_src_passes``, :class:`SortedSrcBackend`) and those
    that scattered it (``scatter_src_passes``).  A batched result counts
    the passes of every lane.  A value may be the solve's device pass
    count: reading its key waits for the solve like any read of its
    answer."""

    KEYS = ("sorted_src_passes", "scatter_src_passes")

    def __init__(self, sorted_passes: Any, scatter_passes: Any):
        self._passes = dict(zip(self.KEYS, (sorted_passes, scatter_passes)))

    def __getitem__(self, key: str) -> int:
        return int(np.sum(self._passes[key]))

    def __iter__(self):
        return iter(self.KEYS)

    def __len__(self) -> int:
        return len(self.KEYS)


def _src_reduce(sorted_src: bool) -> str:
    """How a program counts the ``src`` half of the degrees, as
    ``extras["compaction"]["segments"]`` names it."""
    return "sorted" if sorted_src else "scatter"


def _ladder_routes(ladder: Dict[str, Any]) -> _DegreeCountRoutes:
    """``extras["degree_count"]`` of a ladder: its segments' passes, by
    the route their program counts the ``src`` half by."""
    passes = {r: 0 for r in ("sorted", "scatter")}
    for seg in ladder["segments"]:
        passes[seg["src_reduce"]] += seg["passes"]
    return _DegreeCountRoutes(passes["sorted"], passes["scatter"])


def _id_runs(src: np.ndarray, base: int, check: bool = False):
    """Where each id's run starts in one chunk of a non-decreasing ``src``
    that begins at slot ``base``: ``(ids, first slots)``, the chunk's first
    slot always among them.  With ``check``, None if the chunk is not
    non-decreasing (compaction keeps a sorted rung sorted, so its chunks
    skip the check)."""
    if len(src) == 0:
        return src, np.zeros(0, np.int32)
    if check and not (src[1:] >= src[:-1]).all():
        return None
    starts = np.flatnonzero(src[1:] != src[:-1]) + 1  # a bool scan, not an int one
    return np.r_[src[:1], src[starts]], (np.r_[0, starts] + base).astype(np.int32)


def _offsets_from_runs(runs, n_nodes: int, n_slots: int) -> np.ndarray:
    """int32[n_nodes + 1] CSR offsets from the :func:`_id_runs` of the
    consecutive chunks of a sorted ``src`` with ``n_slots`` real slots:
    each node's first slot, or the next node's where it has none.  A run
    that crosses a chunk boundary keeps its first chunk's start."""
    off = np.full(n_nodes + 1, n_slots, np.int32)
    for ids, first in reversed(runs):
        off[ids] = first
    return np.ascontiguousarray(np.minimum.accumulate(off[::-1])[::-1])


def _src_offsets(
    src: np.ndarray, w: np.ndarray, msk: np.ndarray, n_nodes: int
) -> Optional[np.ndarray]:
    """The CSR offsets :class:`SortedSrcBackend` reads for this host
    buffer, or None where that count does not apply: it needs the real
    slots (``msk``) first, their ``src`` non-decreasing inside
    ``[0, n_nodes)``, and every real weight 1.0.  Chunks of
    :func:`_compact_chunks` run on the compaction pool."""
    k = int(np.count_nonzero(msk))
    if not msk[:k].all():
        return None
    src, w = src[:k], w[:k]
    if k and (src[0] < 0 or src[-1] >= n_nodes):
        return None
    chunks = _compact_chunks(k)
    cuts = [k * i // chunks for i in range(chunks + 1)]
    if any(src[c - 1] > src[c] for c in cuts[1:-1] if 0 < c < k):
        return None

    def sorted_run(i):
        a, b = cuts[i], cuts[i + 1]
        return _id_runs(src[a:b], a, check=True) if np.all(w[a:b] == 1) else None

    run = map if chunks == 1 else _COMPACT_POOL.map
    runs = list(run(sorted_run, range(chunks)))
    if any(r is None for r in runs):
        return None
    return _offsets_from_runs(runs, n_nodes, k)


class _Compacted(NamedTuple):
    """The next rung's host buffers, as :func:`_compact_rung` gathers them."""

    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray
    msk: np.ndarray
    s_al: np.ndarray
    t_al: Optional[np.ndarray]
    id_map: np.ndarray  # compact id -> original id
    e_alive: int
    n_alive: int
    src_offsets: Optional[np.ndarray]  # asked for with ``offsets``


def _at_floor(m: int, n: int, e_alive: int, n_alive: int, keep_ids: bool) -> bool:
    """The ladder's floor: ``e_alive`` edges and ``n_alive`` nodes would
    fill buckets no smaller than this rung's ``m`` and ``n`` (with node ids
    kept, the edge bucket alone)."""
    new_m = pow2_bucket(max(e_alive, 1), _COMPACT_MIN_EDGES)
    new_n = pow2_bucket(max(n_alive, 1), _COMPACT_MIN_NODES)
    return new_m >= m and (keep_ids or new_n >= n)


def _compact_rung(
    src, dst, w, msk, s_al, t_al, id_map, chunks: int, keep_ids: bool = False,
    offsets: bool = False,
) -> Optional[_Compacted]:
    """Gather one rung's survivors into the next power-of-two bucket.

    An edge survives if ``msk & s_al[src] & t_al[dst]`` (``t_al`` is None
    when undirected: ``s_al`` on both ends); a node if alive on either
    side.  Both keep their order, and survivors are renumbered densely.
    With ``keep_ids`` only the edges are compacted: their endpoints keep
    their ids, and the node arrays and ``id_map`` are returned as they are
    (a degree backend that hashes node ids needs the original ones).

    The edge range is cut into ``chunks`` contiguous chunks.  Phase 1 filters
    each chunk and counts its survivors; the prefix of the counts places each
    chunk's survivors in the output, where phase 2 gathers them.  So the
    buffers are the same for every ``chunks``.  More than one chunk runs on
    the pool (numpy's filters and gathers release the GIL); the node-sized
    steps stay serial.

    With ``offsets`` (a ``src``-sorted rung: the order and the relabeling
    keep it sorted) each chunk also notes where each new ``src`` id's run
    starts as it gathers it (:func:`_id_runs`), and the result carries
    the new buffer's CSR offsets (:func:`_src_offsets`).

    Returns None at the ladder's floor (:func:`_at_floor`): the rung then
    finishes uncompacted.
    """
    surv = s_al if t_al is None else s_al | t_al
    ta = s_al if t_al is None else t_al
    m = len(src)
    cuts = [m * i // chunks for i in range(chunks + 1)]
    run = map if chunks == 1 else _COMPACT_POOL.map

    def survivors(i):
        a, b = cuts[i], cuts[i + 1]
        ok = msk[a:b] & s_al[src[a:b]] & ta[dst[a:b]]
        return ok, int(np.count_nonzero(ok))

    oks, counts = zip(*run(survivors, range(chunks)))
    ends = np.cumsum(counts)
    e_alive = int(ends[-1])
    n_alive = int(np.count_nonzero(surv))
    if _at_floor(m, len(s_al), e_alive, n_alive, keep_ids):
        return None
    new_m = pow2_bucket(max(e_alive, 1), _COMPACT_MIN_EDGES)
    if not keep_ids:
        relabel = np.cumsum(surv, dtype=src.dtype) - 1  # stable: keeps id order
    new_src = np.zeros(new_m, src.dtype)
    new_dst = np.zeros(new_m, dst.dtype)
    new_w = np.zeros(new_m, w.dtype)
    new_msk = np.zeros(new_m, bool)
    new_msk[:e_alive] = True

    def gather(i):
        a, b = cuts[i], cuts[i + 1]
        out = slice(ends[i] - counts[i], ends[i])
        keep = np.flatnonzero(oks[i])
        if keep_ids:
            ids = src[a:b][keep]
            new_dst[out] = dst[a:b][keep]
        else:
            ids = relabel[src[a:b][keep]]
            new_dst[out] = relabel[dst[a:b][keep]]
        new_src[out] = ids
        new_w[out] = w[a:b][keep]
        return _id_runs(ids, out.start) if offsets else None

    runs = list(run(gather, range(chunks)))
    if keep_ids:
        src_off = _offsets_from_runs(runs, len(s_al), e_alive) if offsets else None
        return _Compacted(
            new_src, new_dst, new_w, new_msk, s_al, t_al, id_map, e_alive,
            n_alive, src_off,
        )
    # id_map covers only the real (unpadded) ids; pad nodes are never
    # alive, so slicing the survivor mask to its length is exact.
    id_map = id_map[surv[: len(id_map)]]
    new_n = pow2_bucket(max(n_alive, 1), _COMPACT_MIN_NODES)
    new_s = np.zeros(new_n, bool)
    new_s[:n_alive] = s_al[surv]
    new_t = None
    if t_al is not None:
        new_t = np.zeros(new_n, bool)
        new_t[:n_alive] = t_al[surv]
    src_off = _offsets_from_runs(runs, new_n, e_alive) if offsets else None
    return _Compacted(
        new_src, new_dst, new_w, new_msk, new_s, new_t, id_map, e_alive,
        n_alive, src_off,
    )


def _policy_name(problem: Problem) -> str:
    return {
        "undirected": "undirected_threshold",
        "at_least_k": "at_least_k_fraction",
        "directed": "directed_st",
    }[problem.objective]


def _fields_key(problem: Problem, exclude: Tuple[str, ...] = ()) -> Tuple:
    """Hashable tuple of the Problem's static fields, minus the fields a
    program takes as runtime arguments (c for directed programs, eps for
    eps-sweeps)."""
    return tuple(
        (f.name, getattr(problem, f.name))
        for f in dataclasses.fields(problem)
        if f.name not in exclude
    )


class _DiskBackedProgram:
    """A cached program with an on-disk tier: per concrete input signature,
    either loads a serialized executable from ``cache_dir`` (no trace, no
    lowering, no XLA compile) or AOT-compiles the wrapped jitted program and
    publishes it.  The signature is part of the disk key because one Solver
    key can legally serve several input shapes (e.g. the eps-sweep program
    re-specializes per eps-vector length, exactly like ``jax.jit`` would)."""

    def __init__(self, solver: "Solver", jit_fn: Callable, cache_dir: str, key: Tuple):
        self._solver = solver
        self._jit = jit_fn
        self._dir = cache_dir
        self._key = key
        self._execs: Dict[Tuple, Callable] = {}

    @staticmethod
    def _sig(args) -> Tuple:
        return tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree_util.tree_leaves(args)
        )

    def _resolve(self, sig: Tuple, args) -> Callable:
        from repro.core import progcache

        disk_key = (self._key, sig)
        path = progcache.entry_path(self._dir, disk_key)
        loaded = progcache.load(path, disk_key)
        if loaded is not None:
            self._solver.disk_hits += 1
            return loaded
        self._solver.disk_misses += 1
        compiled = self._jit.lower(*args).compile()
        if not progcache.store(path, disk_key, compiled):
            self._solver.disk_store_errors += 1
            # Rate-limited observability: warn ONCE per solver on the first
            # failed publish (every subsequent failure only counts) — a
            # full/read-only cache dir degrades cold-start, not answers.
            if self._solver.disk_store_errors == 1:
                import logging

                logging.getLogger("repro.progcache").warning(
                    "persistent program cache store failed (dir=%s); solves "
                    "continue but fresh processes will recompile — further "
                    "failures are counted in Solver.disk_store_errors "
                    "without logging",
                    self._dir,
                )
        return compiled

    def __call__(self, *args):
        sig = self._sig(args)
        fn = self._execs.get(sig)
        if fn is None:
            fn = self._resolve(sig, args)
            self._execs[sig] = fn
        return fn(*args)


# Program kinds eligible for the disk tier: single-device jit programs.
# Mesh executables (mesh/ladder_mesh) embed a device topology and
# their keys hold live Mesh objects — they stay in-memory only.
_DISK_KINDS = ("solve", "eps", "c", "graphs", "cseg")


class Solver:
    """The stateful front door: memoizes jitted programs so same-shape
    requests never retrace.

    Cache key: ``(kind, problem static fields, max_passes, n_nodes,
    padded m, weight dtype, aux shapes | mesh)``.  ``trace_count``
    counts actual retraces (incremented inside the traced Python bodies) and
    ``cache_hits``/``cache_misses`` count program-cache lookups — the
    observability hooks the retrace tests and bench_api use.

    ``cache_dir`` adds a PERSISTENT tier under the in-memory cache: compiled
    programs are serialized to disk (``core/progcache.py``) so a fresh
    process pays zero compiles for shapes any earlier process already
    served — ``disk_hits``/``disk_misses`` count that tier's lookups.  A
    ``Problem(cache_dir=...)`` enables the same per-request (the Solver
    argument wins when both are set).

    ``max_cached_programs`` bounds the in-memory cache with LRU eviction
    (``cache_evictions`` counts) so a long-lived serving process holding
    many shape buckets cannot grow without bound; the default (None) keeps
    the historical unbounded behavior.  Evicted programs that have a disk
    entry reload from it without recompiling.
    """

    def __init__(
        self,
        cache_dir: Optional[str] = None,
        max_cached_programs: Optional[int] = None,
    ):
        if max_cached_programs is not None and max_cached_programs < 1:
            raise ValueError(
                f"max_cached_programs={max_cached_programs} must be >= 1"
            )
        self._programs: Dict[Tuple, Callable] = collections.OrderedDict()
        self.cache_dir = cache_dir
        self.max_cached_programs = max_cached_programs
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_evictions = 0
        self.trace_count = 0
        self.disk_hits = 0
        self.disk_misses = 0
        self.disk_store_errors = 0

    def stats(self) -> Dict[str, int]:
        """All cache/compile counters in one dict — the observability
        surface bench_api/bench_serve and the serving stats() hooks read
        (disk_store_errors > 0 means the persistent tier is degraded:
        solves still succeed but fresh processes will recompile)."""
        return {
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_evictions": self.cache_evictions,
            "trace_count": self.trace_count,
            "disk_hits": self.disk_hits,
            "disk_misses": self.disk_misses,
            "disk_store_errors": self.disk_store_errors,
            "cached_programs": len(self._programs),
        }

    # -- cache plumbing -----------------------------------------------------
    def _mark_trace(self) -> None:
        # Runs only while jax traces the program body: a retrace counter.
        self.trace_count += 1

    def _disk_dir(self, problem: Problem) -> Optional[str]:
        """Effective persistent-cache directory: the Solver's own setting
        wins; otherwise the Problem's (cache-key-exempt) knob."""
        return self.cache_dir if self.cache_dir is not None else problem.cache_dir

    def _get(
        self,
        key: Tuple,
        build: Callable[[], Callable],
        disk_dir: Optional[str] = None,
    ):
        fn = self._programs.get(key)
        if fn is None:
            self.cache_misses += 1
            fn = build()
            if disk_dir is not None and key[0] in _DISK_KINDS:
                fn = _DiskBackedProgram(self, fn, disk_dir, key)
            self._programs[key] = fn
            if self.max_cached_programs is not None:
                while len(self._programs) > self.max_cached_programs:
                    self._programs.popitem(last=False)  # LRU
                    self.cache_evictions += 1
            return fn, False
        self.cache_hits += 1
        self._programs.move_to_end(key)
        return fn, True

    def cache_size(self) -> int:
        return len(self._programs)

    def _key(
        self,
        kind: str,
        problem: Problem,
        mp: int,
        n_nodes: int,
        m_padded: int,
        dtype,
        aux: Tuple = (),
    ) -> Tuple:
        # A field may only be dropped from the key when the program takes it
        # as a RUNTIME argument (c for per-c and c-sweep programs, eps for
        # eps-sweep programs — the eps/graphs sweeps bake a fixed directed c
        # into the closure, so c must key those) or when the resolved cell
        # never reads it (no spurious recompiles from irrelevant knobs).
        # The uniform exclusions come from _FIELD_CLASS ('exempt' = host
        # driver/scheduling state: stream_*/spill/cache_dir/turnstile knobs,
        # and compaction — segment programs key on (seg max_passes,
        # compact_below) via mp/aux instead); max_passes keys through its
        # resolved value (the mp slot).
        exclude = {"max_passes"} | _EXEMPT_FIELDS
        if kind in ("solve", "mesh", "c", "cseg", "ladder_mesh"):
            exclude.add("c")  # these programs take c as a runtime argument
        if kind == "eps":
            exclude.add("eps")
        if problem.objective != "at_least_k":
            exclude |= {"k", "min_deg_fallback", "ceil_count"}
        if problem.backend != "sketch":
            exclude |= {"sketch_tables", "sketch_buckets", "sketch_seed"}
        if not (problem.backend == "sketch" and problem.substrate == "mesh"):
            exclude.add("sketch_node_chunk")
        if problem.backend != "pallas":
            exclude |= {"tile_size", "tile_block", "pallas_interpret"}
        if problem.substrate != "mesh":
            exclude |= {"edge_axes", "wire_dtype"}
        return (
            kind,
            _fields_key(problem, exclude),
            mp,
            n_nodes,
            m_padded,
            str(dtype),
            aux,
        )

    # -- program builders ---------------------------------------------------
    def _build_jit_program(
        self,
        problem: Problem,
        mp: int,
        kind: str,
        with_tiling: bool,
        sorted_src: bool = False,
    ) -> Callable:
        solver = self
        directed = problem.objective == "directed"

        def cell(edges, *, eps=None, c=None, tiling=None, src_offsets=None):
            return run_cell(
                edges, problem, eps=eps, c=c, tiling=tiling,
                src_offsets=src_offsets, max_passes=mp,
            )

        if kind == "solve":
            if with_tiling:
                def fn(edges, tl, ei):
                    solver._mark_trace()
                    return cell(edges, tiling=(tl, ei))
            elif directed:
                def fn(edges, c):
                    solver._mark_trace()
                    return cell(edges, c=c)
            else:
                def fn(edges):
                    solver._mark_trace()
                    return cell(edges)
        elif kind == "eps":
            if sorted_src:
                # One set of offsets, unbatched, for every lane.
                def fn(edges, off, eps_vec):
                    solver._mark_trace()
                    return jax.vmap(
                        lambda e: cell(edges, eps=e, src_offsets=off)
                    )(eps_vec)
            elif with_tiling:
                def fn(edges, tl, ei, eps_vec):
                    solver._mark_trace()
                    return jax.vmap(
                        lambda e: cell(edges, eps=e, tiling=(tl, ei))
                    )(eps_vec)
            else:
                def fn(edges, eps_vec):
                    solver._mark_trace()
                    return jax.vmap(lambda e: cell(edges, eps=e))(eps_vec)
        elif kind == "c":
            def fn(edges, c_vec):
                solver._mark_trace()
                return jax.vmap(lambda c: cell(edges, c=c))(c_vec)
        elif kind == "graphs":
            def fn(edges):
                solver._mark_trace()
                return jax.vmap(lambda g: cell(g))(edges)
        else:
            raise ValueError(kind)
        return jax.jit(fn)

    def _build_segment_program(
        self,
        problem: Problem,
        seg_mp: int,
        compact_below: Optional[int],
        with_tiling: bool,
        sorted_src: bool = False,
    ) -> Callable:
        """One rung of the compaction ladder on the jit substrate:
        ``fn(edges[, tl, ei | off], alive0[, ta0], t0, ae0[, c]) ->
        PeelOutcome`` (``off``: the rung's CSR offsets, with ``sorted_src``).
        ``compact_below`` is baked in statically (it derives from the edge
        buffer size, which already keys the cache), so each power-of-two
        bucket compiles exactly once and is reused across graphs, segments
        and compaction modes.  ``ae0`` is the host-known alive-edge count
        of the entry state and the entry filter is the edge mask itself
        (a fresh bucket holds exactly the surviving alive edges), so a rung
        does NO edge work beyond its passes."""
        solver = self
        directed = problem.objective == "directed"

        def cell(edges, alive0, ta0, t0, ae0, c=None, tiling=None, off=None):
            return run_cell(
                edges, problem, c=c, tiling=tiling, src_offsets=off,
                max_passes=seg_mp,
                init_alive=alive0, init_t_alive=ta0, init_t=t0,
                init_best_empty=True, compact_below=compact_below,
                init_alive_edges=ae0, init_ok_from_mask=True,
            )

        if with_tiling:
            def fn(edges, tl, ei, alive0, t0, ae0):
                solver._mark_trace()
                return cell(edges, alive0, None, t0, ae0, tiling=(tl, ei))
        elif sorted_src:
            def fn(edges, off, alive0, t0, ae0):
                solver._mark_trace()
                return cell(edges, alive0, None, t0, ae0, off=off)
        elif directed:
            def fn(edges, alive0, ta0, t0, ae0, c):
                solver._mark_trace()
                return cell(edges, alive0, ta0, t0, ae0, c=c)
        else:
            def fn(edges, alive0, t0, ae0):
                solver._mark_trace()
                return cell(edges, alive0, None, t0, ae0)
        return jax.jit(fn)

    def _build_mesh_program(
        self,
        problem: Problem,
        mp: int,
        mesh,
        n_nodes: int,
    ) -> Callable:
        """shard_map substrate (§5.2): edges sharded over ``edge_axes``,
        node state replicated, one fused psum per pass."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        axes = tuple(problem.edge_axes)
        if problem.backend == "sketch":
            from repro.core.countsketch import make_sketch_params
            from repro.core.mapreduce import _MeshSketchBackend

            backend = _MeshSketchBackend(
                params=make_sketch_params(
                    problem.sketch_tables,
                    problem.sketch_buckets,
                    problem.sketch_seed,
                ),
                axes=axes,
                node_chunk=min(problem.sketch_node_chunk, max(n_nodes, 1)),
            )
        else:
            backend = MeshSegmentSumBackend(axes, problem.wire_dtype)
        solver = self
        directed = problem.objective == "directed"

        def _local_run(src, dst, weight, mask, c=None):
            e = EdgeList(src=src, dst=dst, weight=weight, mask=mask, n_nodes=n_nodes)
            policy = _policy_for(problem, c=c)
            return run_peel(
                e, policy, backend, mp, track_history=problem.track_history,
            )

        if directed:
            def local(src, dst, weight, mask, c):
                return _local_run(src, dst, weight, mask, c)

            in_specs = (P(axes),) * 4 + (P(),)
        else:
            def local(src, dst, weight, mask):
                return _local_run(src, dst, weight, mask)

            in_specs = (P(axes),) * 4

        mapped = shard_map(
            local, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
        )

        def fn(*args):
            solver._mark_trace()
            return mapped(*args)

        return jax.jit(fn)

    def _mesh_fn(self, prob: Problem, mesh, n_nodes: int):
        """Cached shard_map program for a RESOLVED problem.  Keyed without
        edge shapes (jit re-keys on shard shapes internally) so
        :meth:`mesh_program` warming and ``solve(substrate='mesh')``
        serving share one compilation."""
        mp = prob.resolved_max_passes(n_nodes)
        key = self._key("mesh", prob, mp, n_nodes, -1, "sharded", (mesh,))
        fn, hit = self._get(
            key, lambda: self._build_mesh_program(prob, mp, mesh, n_nodes)
        )
        return fn, hit, mp

    def mesh_program(
        self, problem: Problem, mesh, n_nodes: int
    ) -> Callable:
        """The cached shard_map program ``fn(src, dst, weight, mask[, c]) ->
        PeelOutcome`` over edge arrays sharded over ``problem.edge_axes``
        (:func:`~repro.core.mapreduce.shard_edges`) — the uncompacted mesh
        solve, for callers that shard and launch it themselves."""
        fn, _, _ = self._mesh_fn(problem.resolve(n_nodes), mesh, n_nodes)
        return fn

    # -- single-program mesh ladder (collective-only compaction) ------------
    def _build_mesh_ladder_program(
        self,
        problem: Problem,
        mp: int,
        mesh,
        n_nodes: int,
        schedule: Tuple[int, ...],
    ) -> Callable:
        """The WHOLE geometric compaction ladder as ONE ``jit(shard_map)``
        program (mesh substrate): every rung's peel segment AND the
        compaction between rungs run inside the compiled program, so a
        multi-device run is collective-only end to end — no host
        gather/reshard per rung.

        ``schedule`` is the static Lemma-4 bucket ladder
        (:func:`~repro.graph.partition.ladder_schedule`): per-shard edge
        capacities descending geometrically from the padded input (half
        first, then a stride of ``_LADDER_STRIDE``).  Rung ``i`` peels with
        its psummed alive-edge trigger at the NEXT rung's (global)
        capacity — half occupancy for rung 0, like the host ladder's
        trigger; a quarter for the stride-4 tail — so on trigger exit the
        survivors provably fit rung ``i+1``; survivor edges are then
        prefix-sum compacted and redistributed with an all-gather
        (:func:`~repro.core.mapreduce.mesh_compact_edges`).  Node bitmaps
        stay replicated in the FULL id space (no static bound exists on
        isolated-but-alive nodes, so node renumbering stays a host-ladder
        concern); since compaction is pure edge re-bucketing here, results
        are bit-identical to the host ladder and to ``compaction='off'`` for
        integer-valued weights.

        Returns ``fn(src, dst, weight, mask[, c]) -> (PeelOutcome,
        rung_t)`` where the edge arrays carry ``schedule[0] * n_shards``
        slots sharded over ``edge_axes`` and ``rung_t`` is the int32[R]
        absolute pass counter after each rung (the ladder report's
        per-rung passes, fetched with the result in the same launch).
        """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from repro.core.mapreduce import mesh_compact_edges

        axes = tuple(problem.edge_axes)
        n_shards = int(np.prod([mesh.shape[a] for a in axes]))
        backend = MeshSegmentSumBackend(axes, problem.wire_dtype)
        solver = self
        directed = problem.objective == "directed"
        n_rungs = len(schedule)
        hist_len = mp if problem.track_history else 1

        def ladder_local(src, dst, weight, mask, c=None):
            policy = _policy_for(problem, c=c)
            n = n_nodes
            empty = jnp.zeros((0,), bool)
            alive = jnp.ones((n,), bool)
            ta = jnp.ones((n,), bool) if directed else empty
            # Best-set seed matches the uncompacted loop's best0=alive0: if
            # no pass ever records an eligible set, the full set comes back.
            best_alive = jnp.ones((n,), bool)
            best_t = jnp.ones((n,), bool) if directed else empty
            best_rho = jnp.asarray(-jnp.inf, jnp.float32)
            best_size = jnp.asarray(0, jnp.int32)
            t = jnp.asarray(0, jnp.int32)
            # Entry count of rung 0: one psum over the input mask (every
            # masked edge has both endpoints alive at t=0); later rungs
            # reuse the survivor count the compaction just gathered.
            ae = backend.count_edges(mask)
            hist_n = jnp.full((hist_len,), -1, jnp.int32)
            hist_m = jnp.zeros((hist_len,), jnp.float32)
            hist_rho = jnp.zeros((hist_len,), jnp.float32)
            rung_t = []
            for i, cap in enumerate(schedule):
                last = i == n_rungs - 1
                # The trigger sits at the NEXT rung's capacity: a rung only
                # exits early once its survivors provably fit there.
                compact_below = None if last else schedule[i + 1] * n_shards
                edges_i = EdgeList(
                    src=src, dst=dst, weight=weight, mask=mask, n_nodes=n
                )
                out = run_peel(
                    edges_i, policy, backend, mp,
                    track_history=problem.track_history,
                    init_alive=alive,
                    init_t_alive=ta if directed else None,
                    init_t=t, init_best_empty=True,
                    compact_below=compact_below,
                    init_alive_edges=ae, init_ok_from_mask=True,
                    with_edge_state=not last,
                )
                if not last:
                    # The carried post-removal filter and its psummed count
                    # ARE the compaction inputs — no re-filter, no re-count.
                    out, edge_ok, ae = out
                alive = out.alive
                if directed:
                    ta = out.t_alive
                t = out.passes
                # Strict >: the earliest rung (pass) wins ties, as in the
                # single-segment loop and the host ladder.
                improved = out.best_density > best_rho
                best_alive = jnp.where(improved, out.best_alive, best_alive)
                if directed:
                    best_t = jnp.where(improved, out.best_t, best_t)
                best_rho = jnp.where(improved, out.best_density, best_rho)
                best_size = jnp.where(improved, out.best_size, best_size)
                if problem.track_history:
                    # Absolute pass indexing: rungs write disjoint slots.
                    sel = out.history_n >= 0
                    hist_n = jnp.where(sel, out.history_n, hist_n)
                    hist_m = jnp.where(sel, out.history_m, hist_m)
                    hist_rho = jnp.where(sel, out.history_rho, hist_rho)
                rung_t.append(t)
                if not last:
                    src, dst, weight, mask = mesh_compact_edges(
                        src, dst, weight, edge_ok, ae, schedule[i + 1], axes,
                    )
            outcome = PeelOutcome(
                best_alive=best_alive,
                best_t=best_t,
                best_density=best_rho,
                best_size=best_size,
                passes=t,
                alive=alive,
                t_alive=ta,
                history_n=hist_n,
                history_m=hist_m,
                history_rho=hist_rho,
            )
            return outcome, jnp.stack(rung_t)

        in_specs = (P(axes),) * 4 + ((P(),) if directed else ())
        mapped = shard_map(
            ladder_local, mesh=mesh, in_specs=in_specs,
            out_specs=(P(), P()), check_vma=False,
        )

        def fn(*args):
            solver._mark_trace()
            return mapped(*args)

        return jax.jit(fn)

    def mesh_ladder_program(
        self, problem: Problem, mesh, n_nodes: int, m_edges: int
    ) -> Tuple[Callable, Tuple[int, ...], int, bool]:
        """The cached single-program mesh ladder for a graph with ``m_edges``
        edge slots: ``(fn, schedule, n_shards, hit)`` where ``fn(src, dst,
        weight, mask[, c]) -> (PeelOutcome, rung_t)`` expects the edge
        arrays padded to ``schedule[0] * n_shards`` slots and sharded over
        ``problem.edge_axes`` — the lowering target of ``solve()`` for
        mesh × ``compaction='geometric'``.  The program
        cache key includes the static bucket schedule; rung 0 is the exact
        shard-rounded input size, so only graphs with the SAME padded edge
        count share a compilation (repeat solves and the whole directed
        c-grid do — c is a runtime scalar)."""
        prob = problem.resolve(n_nodes, have_mesh=True)
        axes = tuple(prob.edge_axes)
        n_shards = int(np.prod([mesh.shape[a] for a in axes]))
        shard_m0 = -(-max(int(m_edges), 1) // n_shards)  # ceil division
        # Rung 0 is the INPUT buffer: it keeps its exact (shard-rounded)
        # size — pow2 bucketing there would only pad the heaviest passes.
        # Its trigger fires at HALF occupancy (rung 1 = pow2(m0/2), the
        # host ladder's trigger point — low-eps runs shrink slowly and
        # need the early compact); after that the tail descends by
        # _LADDER_STRIDE, pow2-bucketed so every later rung's program is
        # shared across graphs landing on the same bucket.
        floor = pow2_bucket(max(1, _LADDER_MIN_EDGES // n_shards))
        half = pow2_bucket(-(-shard_m0 // 2), floor)
        tail = ladder_schedule(
            max(half // _LADDER_STRIDE, 1), floor=floor,
            stride=_LADDER_STRIDE,
        )
        schedule = (shard_m0,)
        schedule += (half,) if half < shard_m0 else ()
        # ladder_schedule clamps its floor down when the top is already
        # smaller; keep only tail rungs at or above the REAL floor (a
        # sub-floor rung would pay its fixed cost for a trivial pass).
        schedule += tuple(c for c in tail if c < schedule[-1] and c >= floor)
        mp = prob.resolved_max_passes(n_nodes)
        key = self._key(
            "ladder_mesh", prob, mp, n_nodes, -1, "sharded", (mesh, schedule),
        )
        fn, hit = self._get(
            key,
            lambda: self._build_mesh_ladder_program(
                prob, mp, mesh, n_nodes, schedule
            ),
        )
        return fn, schedule, n_shards, hit

    def _mesh_ladder_runner(
        self, graph: EdgeList, prob: Problem, mesh
    ) -> Callable[[Optional[float]], Tuple[PeelOutcome, Dict[str, Any], bool]]:
        """The mesh × geometric runner of :meth:`_solve_compacted`: pads and
        shards the graph ONCE, then returns ``run(c)`` launching the
        single-program ladder (collective-only; zero host gather/reshard
        round-trips between rungs) — the directed c-grid reuses both the
        sharded arrays and the compiled program across all its c values,
        like the uncompacted mesh path."""
        from repro.core.mapreduce import shard_edges

        fn, schedule, n_shards, hit = self.mesh_ladder_program(
            prob, mesh, graph.n_nodes, graph.n_edges_padded
        )
        padded = graph.with_padding(schedule[0] * n_shards)
        sh = shard_edges(padded, mesh, prob.edge_axes)
        base_args = (sh.src, sh.dst, sh.weight, sh.mask)

        def run(c: Optional[float]) -> Tuple[PeelOutcome, Dict[str, Any], bool]:
            args = base_args
            if prob.objective == "directed":
                args += (jnp.float32(c),)
            out, rung_t = fn(*args)
            rung_t = np.asarray(rung_t)
            segments = []
            slots = 0
            prev = 0
            for i, cap in enumerate(schedule):
                m_buf = cap * n_shards
                passes = int(rung_t[i]) - prev
                prev = int(rung_t[i])
                slots += passes * m_buf
                segments.append(
                    {
                        "n_buf": int(graph.n_nodes),
                        "m_buf": m_buf,
                        "passes": passes,
                        "compact_below": (
                            None if i == len(schedule) - 1
                            else schedule[i + 1] * n_shards
                        ),
                        "cache_hit": bool(hit),
                        "src_reduce": _src_reduce(False),
                    }
                )
            ladder = {
                "mode": prob.compaction,
                "segments": segments,
                "edge_slots_scanned": int(slots),
                "passes": int(out.passes),
                "single_program": True,
                "host_round_trips": 0,  # vs one gather/reshard per rung
                "schedule": [cap * n_shards for cap in schedule],
                # The graph is resharded on the device; only the rung pass
                # counts, the pass total and a directed c cross.
                "bytes_to_host": int(rung_t.nbytes + out.passes.nbytes),
                "bytes_to_device": 4 if prob.objective == "directed" else 0,
            }
            return out, ladder, hit

        return run

    # -- host compaction ladder (jit substrate) -----------------------------
    def _segment_fn(
        self,
        prob: Problem,
        seg_mp: int,
        compact_below: Optional[int],
        n_cur: int,
        m_cur: int,
        dtype,
        aux_shapes: Tuple,
        sorted_src: bool,
    ):
        """Cached program for one ladder rung."""
        with_tiling = prob.backend == "pallas"
        key = self._key(
            "cseg", prob, seg_mp, n_cur, m_cur, dtype,
            (compact_below, _src_reduce(sorted_src)) + aux_shapes,
        )
        return self._get(
            key,
            lambda: self._build_segment_program(
                prob, seg_mp, compact_below, with_tiling, sorted_src
            ),
            disk_dir=self._disk_dir(prob),
        )

    def _run_compacted(
        self, graph: EdgeList, prob: Problem, c: Optional[float]
    ) -> Tuple[PeelOutcome, Dict[str, Any], bool]:
        """The jit substrate's host compaction ladder: runs the SAME engine
        loop in segments, gathering survivors (edges and nodes) into the
        next power-of-two buffer whenever the alive edge count falls below
        half the current padded buffer — pass k then scans O(m_k) edge slots
        instead of O(m), amortized O(m) total (Lemma 4 drives the geometric
        shrink; cf. the per-round compaction in Mitrović & Pan).

        Compaction is pure renumbering (a stable gather over survivors), so
        the pass-by-pass removal decisions — and therefore best set, best
        density, final bitmaps, pass count and history — are bit-identical
        to the uncompacted loop for integer-valued edge weights, and equal
        up to float reassociation otherwise.  A sketched solve's rungs keep
        node ids and compact edges only (:func:`_compact_rung`'s
        ``keep_ids``): Count-Sketch hashes see the original ids, so its
        counters, and with them every decision, are those of the
        uncompacted loop too.  The mesh substrate runs its ladder as one
        program instead (:meth:`_mesh_ladder_runner`).

        Returns ``(outcome in the ORIGINAL id space, ladder report, all
        segment programs were cache hits)``.
        """
        directed = prob.objective == "directed"
        n0 = graph.n_nodes
        mp = prob.resolved_max_passes(n0)
        dtype = graph.weight.dtype
        span = jax.profiler.TraceAnnotation
        io = _Transfers()
        # Host-side buffers of the current rung (device arrays are rebuilt
        # per segment; each rung is half the size, so total transfer/gather
        # work telescopes to O(m)).
        with span("densest.ladder.graph_to_host", n_buf=n0, m_buf=len(graph.src)):
            src = io.down(graph.src)
            dst = io.down(graph.dst)
            w = io.down(graph.weight)
            msk = io.down(graph.mask)
            # Exact undirected rungs read the src half of the degree count
            # off CSR offsets when the graph is src-sorted with unit
            # weights; checked once here, and compaction keeps both.
            src_off = None
            if prob.backend == "exact" and not directed:
                src_off = _src_offsets(src, w, msk, n0)
        sorted_src = src_off is not None
        id_map = np.arange(n0, dtype=np.int64)  # compact id -> original id
        n_cur = n0
        s_al = np.ones(n0, bool)
        t_al = np.ones(n0, bool) if directed else None

        hist_len = mp if prob.track_history else 1
        hist_n = np.full(hist_len, -1, np.int32)
        hist_m = np.zeros(hist_len, np.float32)
        hist_rho = np.zeros(hist_len, np.float32)
        best_rho = float("-inf")
        # Seed the best set with S_0, matching the uncompacted loop's
        # best0=alive0: if NO pass ever records an eligible set (zero-pass
        # runs — k > n, max_passes=0), both paths return the full set.
        best_alive = np.ones(n0, bool)
        best_t = np.ones(n0, bool) if directed else None
        best_size = 0
        t_done = 0
        segments = []
        slots_scanned = 0
        # Alive-edge count of the entry state of the NEXT rung: all real
        # edges initially; the survivor count after each compaction.  Only
        # read by rungs entered right after (re)initialization, where it is
        # exact — terminal (compact_below=None) segments ignore it.
        cur_alive_edges = int(msk.sum())
        # Count-Sketch degrees hash node ids: its rungs compact edges only.
        keep_ids = prob.backend == "sketch"
        no_more_compact = False
        all_hit = True

        for seg_idx in range(_COMPACT_MAX_SEGMENTS):
            compact_below = None
            # With node ids kept, a rung at the floor even with no survivor
            # left gets no trigger (no 0-pass round trip).
            no_more_compact |= keep_ids and _at_floor(len(src), n_cur, 0, 0, True)
            if not no_more_compact:
                compact_below = max(len(src) // 2, 1)
            shape = dict(rung=seg_idx, n_buf=n_cur, m_buf=len(src))

            # ---- put one rung and its carried state on the device ----
            with span("densest.ladder.upload", **shape):
                edges = EdgeList(
                    src=io.up(src), dst=io.up(dst),
                    weight=io.up(w), mask=io.up(msk),
                    n_nodes=n_cur, directed=graph.directed,
                )
                aux_arrays: Tuple = ()
                if prob.backend == "pallas":
                    aux_arrays = _tiling_arrays(edges, prob, pow2_pad=True)
                    # The tiling reads the rung's endpoints back and puts
                    # its tables on the device.
                    io.to_host += src.nbytes + dst.nbytes
                    io.to_device += sum(a.nbytes for a in aux_arrays)
                elif sorted_src:
                    aux_arrays = (io.up(src_off),)
                # Carried segment state (must track the
                # _build_segment_program signature): alive bitmap(s),
                # absolute pass counter, entry alive-edge count, and the
                # runtime c for directed policies.
                carried: Tuple = (io.up(s_al),)
                if directed:
                    carried += (io.up(t_al),)
                carried += (
                    io.up(t_done, jnp.int32),
                    io.up(cur_alive_edges, jnp.int32),
                )
                if directed:
                    carried += (io.up(c, jnp.float32),)
                m_buf = edges.n_edges_padded

            # ---- run the segment; the pass count is the first sync ----
            with span("densest.ladder.rung", **shape):
                fn, hit = self._segment_fn(
                    prob, mp, compact_below, n_cur, m_buf, dtype,
                    tuple(a.shape for a in aux_arrays), sorted_src,
                )
                out = fn(edges, *aux_arrays, *carried)
                t_prev = t_done
                t_done = int(io.down(out.passes))
            all_hit = all_hit and hit

            # ---- fold the segment into the global answer ----
            with span("densest.ladder.readback", **shape):
                s_al = io.down(out.alive)
                if directed:
                    t_al = io.down(out.t_alive)
                seg_rho = float(io.down(out.best_density))
                if seg_rho > best_rho:  # strict: earliest pass wins ties, as
                    best_rho = seg_rho  # in the single-segment loop
                    ba = io.down(out.best_alive)
                    best_alive = np.zeros(n0, bool)
                    best_alive[id_map] = ba[: len(id_map)]
                    if directed:
                        bt = io.down(out.best_t)
                        best_t = np.zeros(n0, bool)
                        best_t[id_map] = bt[: len(id_map)]
                    best_size = int(io.down(out.best_size))
                if prob.track_history:
                    shn = io.down(out.history_n)
                    sel = shn >= 0
                    hist_n[: len(shn)][sel] = shn[sel]
                    hist_m[: len(shn)][sel] = io.down(out.history_m)[sel]
                    hist_rho[: len(shn)][sel] = io.down(out.history_rho)[sel]
                seg_passes = t_done - t_prev
                slots_scanned += seg_passes * m_buf
                segments.append(
                    {
                        "n_buf": int(n_cur),
                        "m_buf": int(m_buf),
                        "passes": int(seg_passes),
                        "compact_below": compact_below,
                        "cache_hit": bool(hit),
                        "src_reduce": _src_reduce(sorted_src),
                    }
                )
                n_s = int(s_al.sum())
                n_t = int(t_al.sum()) if directed else n_s
                done = t_done >= mp or not _host_keep_going(prob, n_s, n_t)
            if done:
                break

            # ---- compact survivors into the next bucket ----
            chunks = _compact_chunks(len(src))
            with span("densest.ladder.compact", **shape, chunks=chunks):
                nxt = _compact_rung(
                    src, dst, w, msk, s_al, t_al, id_map, chunks, keep_ids,
                    offsets=sorted_src,
                )
            if nxt is None:
                # Bucket floor reached: finish on this buffer uncompacted.
                no_more_compact = True
                continue
            segments[-1]["compact_chunks"] = chunks
            src, dst, w, msk, s_al, t_al, id_map = nxt[:7]
            src_off = nxt.src_offsets
            n_cur = len(s_al)
            cur_alive_edges = nxt.e_alive
        else:
            raise RuntimeError(
                f"compaction ladder exceeded {_COMPACT_MAX_SEGMENTS} segments"
            )

        # ---- map the final state back to the original id space ----
        with span("densest.ladder.finish", n_buf=n0):
            alive_full = np.zeros(n0, bool)
            alive_full[id_map] = s_al[: len(id_map)]
            if directed:
                t_full = np.zeros(n0, bool)
                t_full[id_map] = t_al[: len(id_map)]
            empty = jnp.zeros((0,), bool)
            outcome = PeelOutcome(
                best_alive=io.up(best_alive),
                best_t=io.up(best_t) if directed else empty,
                best_density=io.up(best_rho, jnp.float32),
                best_size=io.up(best_size, jnp.int32),
                passes=io.up(t_done, jnp.int32),
                alive=io.up(alive_full),
                t_alive=io.up(t_full) if directed else empty,
                history_n=io.up(hist_n),
                history_m=io.up(hist_m),
                history_rho=io.up(hist_rho),
            )
        ladder = {
            "mode": prob.compaction,
            "segments": segments,
            "edge_slots_scanned": int(slots_scanned),
            "passes": int(t_done),
            "single_program": False,
            # Each rung is its own program launch, with a host
            # gather/relabel between rungs.
            "host_round_trips": len(segments),
            "bytes_to_host": io.to_host,
            "bytes_to_device": io.to_device,
        }
        return outcome, ladder, all_hit

    def _solve_compacted(
        self, graph: EdgeList, prob: Problem, mesh
    ) -> DenseSubgraphResult:
        """solve() tail for ``compaction='geometric'`` on the jit and mesh
        substrates (streaming compacts inside its driver), with one runner
        each: mesh lowers onto the SINGLE-PROGRAM ladder
        (:meth:`_mesh_ladder_runner`, collective-only compaction; the graph
        is sharded once, reused across the c-grid); jit runs the host
        gather/relabel ladder (:meth:`_run_compacted`).
        """
        if prob.substrate == "mesh":
            if mesh is None:
                raise ValueError("substrate='mesh' needs solve(..., mesh=Mesh)")
            runner = self._mesh_ladder_runner(graph, prob, mesh)
        else:
            runner = functools.partial(self._run_compacted, graph, prob)
        n = graph.n_nodes
        mp = prob.resolved_max_passes(n)
        if prob.objective == "directed" and prob.c is None:
            # The c-grid loop, per-c through the ladder: the real cache-hit
            # flag and the winning c's ladder report survive into the result.
            grid = c_grid(n, prob.c_delta)
            best = best_c = best_ladder = None
            rhos, passes = [], []
            all_hit = True
            for cv in grid:
                out, ladder, hit = runner(float(cv))
                all_hit = all_hit and hit
                rho = float(out.best_density)
                rhos.append(rho)
                passes.append(int(out.passes))
                if best is None or rho > float(best.best_density):
                    best, best_c, best_ladder = out, float(cv), ladder
            extras = {
                "best_c": best_c,
                "c_grid": np.asarray(grid),
                "c_density": np.asarray(rhos),
                "c_passes": np.asarray(passes),
                "compaction": best_ladder,
                "degree_count": _ladder_routes(best_ladder),
            }
            return self._wrap(best, prob, n, mp, all_hit, extras=extras)
        c = prob.c if prob.objective == "directed" else None
        out, ladder, hit = runner(c)
        extras = {"compaction": ladder, "degree_count": _ladder_routes(ladder)}
        if prob.backend == "sketch":
            from repro.core.countsketch import SketchCounters

            extras["sketch"] = SketchCounters(
                prob.sketch_tables, prob.sketch_buckets,
                graph.n_edges_padded, out.passes,
            )
        return self._wrap(out, prob, n, mp, hit, extras=extras)

    # -- result wrapping ----------------------------------------------------
    def _wrap(
        self,
        out: PeelOutcome,
        problem: Problem,
        n_nodes: int,
        mp: int,
        cache_hit: bool,
        extras: Optional[Dict[str, Any]] = None,
        batch: Optional[str] = None,
    ) -> DenseSubgraphResult:
        # Programs built without src offsets scatter both halves.
        extras = dict(extras or {})
        extras.setdefault("degree_count", _DegreeCountRoutes(0, out.passes))
        prov = Provenance(
            objective=problem.objective,
            policy=_policy_name(problem),
            backend=problem.backend,
            substrate=problem.substrate,
            n_nodes=n_nodes,
            max_passes=mp,
            batch=batch,
            cache_hit=cache_hit,
            compaction=problem.compaction,
        )
        return DenseSubgraphResult.from_outcome(out, provenance=prov, extras=extras)

    # -- solve --------------------------------------------------------------
    @functools.partial(jax.profiler.annotate_function, name="densest.solve")
    def solve(
        self,
        graph: EdgeList,
        problem: Problem,
        *,
        mesh=None,
        checkpoint_dir: Optional[str] = None,
        resume: bool = False,
        seed: Optional[int] = None,
    ) -> DenseSubgraphResult:
        """Runs one Problem on one graph.

        As in ``examples/quickstart.py``::

            res = solver.solve(edges, Problem.undirected(eps=0.5))
            rho = float(res.best_density)      # density of the best set
            nodes = res.nodes()                # its node ids (host-side)
            res.provenance                     # which matrix cell ran

        ``mesh`` is required for the mesh substrate;
        ``checkpoint_dir``/``resume`` apply to streaming; ``seed`` is
        required by (and only by) ``substrate='local'`` — the node whose
        dense neighborhood is wanted.
        Repeated same-shape solves hit the program cache and never retrace
        (``trace_count``/``cache_hits`` are the observability counters).
        """
        if not isinstance(graph, EdgeList):
            raise TypeError(
                f"solve() takes an EdgeList graph, got {type(graph).__name__}"
            )
        prob = problem.resolve(graph.n_nodes, have_mesh=mesh is not None)
        if prob.substrate != "streaming" and (checkpoint_dir is not None or resume):
            raise ValueError(
                "checkpoint_dir/resume only apply to substrate='streaming'"
            )
        if prob.substrate == "local":
            if mesh is not None:
                raise ValueError(
                    "substrate='local' is a host exploration + jit solve; "
                    "a mesh does not apply"
                )
            return self._solve_local(graph, prob, seed)
        if seed is not None:
            raise ValueError(
                "seed= is the substrate='local' per-seed query knob; "
                f"substrate={prob.substrate!r} solves the whole graph"
            )
        if prob.stream_mode == "turnstile":
            return self._solve_turnstile(graph, prob)
        if prob.substrate == "streaming":
            return self._solve_streaming(graph, prob, checkpoint_dir, resume)
        if prob.compaction == "geometric":
            return self._solve_compacted(graph, prob, mesh)
        if prob.substrate == "mesh":
            return self._solve_mesh(graph, prob, mesh)

        n = graph.n_nodes
        mp = prob.resolved_max_passes(n)
        with_tiling = prob.backend == "pallas"
        aux: Tuple = ()
        if with_tiling:
            aux = _tiling_arrays(graph, prob)
        key = self._key(
            "solve", prob, mp, n, graph.n_edges_padded,
            graph.weight.dtype, tuple(a.shape for a in aux),
        )
        fn, hit = self._get(
            key,
            lambda: self._build_jit_program(prob, mp, "solve", with_tiling),
            disk_dir=self._disk_dir(prob),
        )
        if prob.objective == "directed":
            if prob.c is None:
                return self._directed_grid(graph, prob, mp, fn, hit)
            out = fn(graph, jnp.float32(prob.c))
        else:
            out = fn(graph, *aux)
        extras = None
        if prob.backend == "sketch":
            from repro.core.countsketch import SketchCounters

            extras = {"sketch": SketchCounters(
                prob.sketch_tables, prob.sketch_buckets,
                graph.n_edges_padded, out.passes,
            )}
        return self._wrap(out, prob, n, mp, hit, extras=extras)

    def _directed_grid(
        self, graph: EdgeList, prob: Problem, mp: int, fn, hit: bool
    ) -> DenseSubgraphResult:
        """The paper's practical directed recipe: sweep the geometric c-grid
        through ONE compiled per-c program (c is a runtime scalar)."""
        grid = c_grid(graph.n_nodes, prob.c_delta)
        best = None
        best_c = None
        rhos = []
        passes = []
        for c in grid:
            out = fn(graph, jnp.float32(c))
            rho = float(out.best_density)
            rhos.append(rho)
            passes.append(int(out.passes))
            if best is None or rho > float(best.best_density):
                best, best_c = out, float(c)
        extras = {
            "best_c": best_c,
            "c_grid": np.asarray(grid),
            "c_density": np.asarray(rhos),
            "c_passes": np.asarray(passes),
        }
        return self._wrap(best, prob, graph.n_nodes, mp, hit, extras=extras)

    def _solve_mesh(
        self, graph: EdgeList, prob: Problem, mesh
    ) -> DenseSubgraphResult:
        if mesh is None:
            raise ValueError("substrate='mesh' needs solve(..., mesh=Mesh)")
        from repro.core.mapreduce import shard_edges

        sh = shard_edges(graph, mesh, prob.edge_axes)
        fn, hit, mp = self._mesh_fn(prob, mesh, sh.n_nodes)
        if prob.objective == "directed":
            if prob.c is None:
                grid_fn = lambda e, c: fn(e.src, e.dst, e.weight, e.mask, c)
                return self._directed_grid(sh, prob, mp, grid_fn, hit)
            out = fn(sh.src, sh.dst, sh.weight, sh.mask, jnp.float32(prob.c))
        else:
            out = fn(sh.src, sh.dst, sh.weight, sh.mask)
        return self._wrap(out, prob, sh.n_nodes, mp, hit)

    def _solve_local(
        self, graph: EdgeList, prob: Problem, seed
    ) -> DenseSubgraphResult:
        """Andersen local substrate (``substrate='local'``): pruned-frontier
        exploration around ``seed`` (core/local.py), then the SAME jit pass
        body over the bucket-padded candidate subgraph.  The program cache
        sees an ordinary pow2-bucket 'solve' program — shared with the
        serving engine's buckets, so repeated queries never retrace.

        The result's bitmaps are scattered back to the ORIGINAL id space
        (history/passes describe the padded candidate buffer), provenance
        reports ``substrate='local'``, and ``extras['local']`` carries the
        exploration counters.  One-shot front door: the CSR build here is
        O(m) per call — request-rate serving holds a persistent
        :class:`repro.serve.densest.DensestQueryEngine` instead, which
        builds the CSR once and batches same-bucket queries."""
        from repro.core.local import LocalExplorer

        if seed is None:
            raise ValueError(
                "substrate='local' answers per-seed queries: "
                "solve(graph, problem, seed=<node id>)"
            )
        explorer = LocalExplorer.from_edgelist(graph)
        padded, ex = explorer.extract(
            seed,
            budget=prob.local_budget,
            max_rounds=prob.local_rounds,
            alpha=prob.local_alpha,
        )
        sub = self.solve(padded, dataclasses.replace(prob, substrate="jit"))
        nodes = ex.candidates
        n = graph.n_nodes

        def lift(bitmap) -> jax.Array:
            # Padded-buffer bitmap -> original id space (pad ids dropped).
            row = np.asarray(bitmap)
            local = np.nonzero(row)[0]
            local = local[local < len(nodes)]  # isolated pad nodes
            full = np.zeros(n, bool)
            full[nodes[local]] = True
            return jnp.asarray(full)

        best_alive = lift(sub.best_alive)
        out = PeelOutcome(
            best_alive=best_alive,
            best_t=sub.best_t,
            best_density=sub.best_density,
            best_size=jnp.sum(best_alive.astype(jnp.int32)),
            passes=sub.passes,
            alive=lift(sub.alive),
            t_alive=sub.t_alive,
            history_n=sub.history_n,
            history_m=sub.history_m,
            history_rho=sub.history_rho,
        )
        extras = {
            "local": {
                "seed": int(ex.seed),
                "candidates": nodes,
                "n_candidates": int(len(nodes)),
                "m_candidates": int(np.asarray(padded.mask).sum()),
                "rounds": int(ex.rounds),
                "nodes_touched": int(ex.nodes_touched),
                "edges_scanned": int(ex.edges_scanned),
                "frontier_exhausted": bool(ex.frontier_exhausted),
                "budget": int(prob.local_budget),
                "bucket": (int(padded.n_nodes), int(padded.n_edges_padded)),
            }
        }
        return self._wrap(
            out,
            prob,
            n,
            sub.provenance.max_passes,
            sub.provenance.cache_hit,
            extras=extras,
        )

    def _solve_turnstile(
        self, graph: EdgeList, prob: Problem
    ) -> DenseSubgraphResult:
        """One-shot turnstile solve: builds a
        :class:`~repro.core.turnstile.TurnstileDensest`, inserts every real
        edge of ``graph`` as one ±edge batch, and answers one query — the
        front-door lowering of ``Problem(stream_mode='turnstile')``.
        Continuous update/query cycles hold their own live driver
        (core/turnstile.py, or the serve/ density service)."""
        from repro.core.turnstile import TurnstileDensest

        if graph.directed:
            raise ValueError("stream_mode='turnstile' needs an undirected graph")
        mask = np.asarray(graph.mask)
        if not np.all(np.asarray(graph.weight)[mask] == 1.0):
            raise ValueError(
                "stream_mode='turnstile' streams are unweighted edge SETS "
                "(the ℓ0 sample has no weight field); got non-unit weights"
            )
        td = TurnstileDensest(graph.n_nodes, prob, solver=self)
        src = np.asarray(graph.src)[mask]
        dst = np.asarray(graph.dst)[mask]
        td.apply(insert_edges=(src, dst))
        return td.query()

    def _solve_streaming(
        self,
        graph: EdgeList,
        prob: Problem,
        checkpoint_dir: Optional[str],
        resume: bool,
    ) -> DenseSubgraphResult:
        """Semi-streaming substrate: chunked multi-pass driver with O(n)
        node state (StreamingDensest keeps the checkpoint/straggler logic).
        ``stream_prefetch`` bounds the async pipeline's resident chunks and
        ``spill_dir`` sends ladder rebuilds to disk-backed memmaps; the
        result's ``extras['streaming']`` reports the pipeline's residency
        and straggler/compaction counters."""
        from repro.core.streaming import StreamingDensest, chunked_from_arrays

        mask = np.asarray(graph.mask)
        src = np.asarray(graph.src)[mask]
        dst = np.asarray(graph.dst)[mask]
        w = np.asarray(graph.weight)[mask]
        drv = StreamingDensest(
            chunked_from_arrays(src, dst, w, chunk=prob.stream_chunk),
            n_nodes=graph.n_nodes,
            eps=prob.eps,
            checkpoint_dir=checkpoint_dir,
            n_workers=prob.stream_workers,
            prefetch=prob.stream_prefetch,
            spill_dir=prob.spill_dir,
            residency_cap_edges=prob.residency_cap_edges,
            compaction="geometric" if prob.compaction == "geometric" else "off",
        )
        st = drv.run(max_passes=prob.max_passes, resume=resume)
        extras = {
            "streaming": {
                "peak_resident_chunks": drv.peak_resident_chunks,
                "peak_resident_edges": drv.peak_resident_edges,
                "speculative_reissues": drv.speculative_reissues,
                "compactions": drv.compactions,
                "spill_rungs": drv.spill_rungs,
            }
        }
        mp = prob.resolved_max_passes(graph.n_nodes)
        hist = np.asarray(st.history, np.float64).reshape(-1, 3)
        best_alive = jnp.asarray(st.best_alive)
        out = PeelOutcome(
            best_alive=best_alive,
            best_t=jnp.zeros((0,), bool),
            best_density=jnp.asarray(st.best_rho, jnp.float32),
            best_size=jnp.sum(best_alive.astype(jnp.int32)),
            passes=jnp.asarray(st.pass_idx, jnp.int32),
            alive=jnp.asarray(st.alive),
            t_alive=jnp.zeros((0,), bool),
            history_n=jnp.asarray(hist[:, 0], jnp.int32),
            history_m=jnp.asarray(hist[:, 1], jnp.float32),
            history_rho=jnp.asarray(hist[:, 2], jnp.float32),
        )
        return self._wrap(out, prob, graph.n_nodes, mp, cache_hit=False, extras=extras)

    # -- solve_batch --------------------------------------------------------
    @functools.partial(jax.profiler.annotate_function, name="densest.solve_batch")
    def solve_batch(
        self,
        graph: Union[EdgeList, Sequence[EdgeList]],
        problem: Problem,
        *,
        eps=None,
        c=None,
    ) -> DenseSubgraphResult:
        """One XLA program for a whole sweep (ROADMAP batched driver).

        As in ``examples/quickstart.py``::

            sweep = solver.solve_batch(
                edges, Problem.undirected(max_passes=64), eps=[0.1, 0.5, 1.0]
            )
            sweep.best_density                 # float32[3], one per eps

        Exactly one batch axis: ``eps=`` (vector of eps values), ``c=``
        (vector of directed ratio guesses), or a sequence of same-shape
        graphs.  Every array of the result gains a leading sweep axis; the
        engine's vmapped while_loop runs to the slowest lane but each lane's
        values are bit-identical to its standalone solve (for eps values
        exactly representable in float32).

        With ``max_passes=None`` the static trip bound is taken at the
        loosest point of the sweep (min eps); pass an explicit
        ``Problem.max_passes`` to pin it.  Sweeps share ONE vmapped
        program, so there is no per-lane buffer to compact:
        ``compaction='auto'`` quietly resolves to off, an explicit ladder
        raises.
        """
        stacked = isinstance(graph, (list, tuple)) or (
            isinstance(graph, EdgeList) and graph.src.ndim == 2
        )
        if sum(x is not None for x in (eps, c)) + stacked != 1:
            raise ValueError(
                "solve_batch needs exactly one batch axis: eps=, c=, or "
                "stacked same-shape graphs (a sequence or a stack_graphs result)"
            )

        def _resolve_batchable(n_nodes: int) -> Problem:
            # Batched sweeps are ONE vmapped program: lanes shrink at
            # different rates, so there is no shared buffer to compact.
            # 'auto' quietly resolves to off; an explicit ladder is an error.
            p = problem.resolve(n_nodes)
            if p.stream_mode == "turnstile":
                raise ValueError(
                    "solve_batch sweeps are single vmapped programs; the "
                    "turnstile runtime is a host update/query driver — "
                    "query a live TurnstileDensest per sweep point instead"
                )
            if p.compaction != "off":
                if problem.compaction == "auto":
                    p = dataclasses.replace(p, compaction="off")
                else:
                    raise ValueError(
                        "solve_batch sweeps share one vmapped program; "
                        "per-lane compaction is not possible — use "
                        "compaction='off' (or 'auto')"
                    )
            return p

        if stacked:
            batched = graph if isinstance(graph, EdgeList) else stack_graphs(list(graph))
            prob = _resolve_batchable(batched.n_nodes)
            if prob.substrate != "jit":
                raise ValueError("solve_batch runs on the jit substrate")
            if prob.backend == "pallas":
                raise ValueError(
                    "stacked-graph sweeps need a graph-independent backend "
                    "(tile bucketing is per-graph); use exact or sketch"
                )
            if prob.objective == "directed" and prob.c is None:
                raise ValueError("stacked directed sweeps need a fixed c")
            mp = prob.resolved_max_passes(batched.n_nodes)
            key = self._key(
                "graphs", prob, mp, batched.n_nodes, batched.src.shape,
                batched.weight.dtype,
            )
            fn, hit = self._get(
                key,
                lambda: self._build_jit_program(prob, mp, "graphs", False),
                disk_dir=self._disk_dir(prob),
            )
            out = fn(batched)
            return self._wrap(out, prob, batched.n_nodes, mp, hit, batch="graphs")

        if not isinstance(graph, EdgeList):
            raise TypeError(
                f"solve_batch takes an EdgeList or a sequence, got {type(graph).__name__}"
            )
        prob = _resolve_batchable(graph.n_nodes)
        if prob.substrate != "jit":
            raise ValueError("solve_batch runs on the jit substrate")
        n = graph.n_nodes

        if eps is not None:
            eps_host = np.asarray(eps, np.float32).reshape(-1)
            if prob.max_passes is not None:
                mp = int(prob.max_passes)
            else:
                loosest = dataclasses.replace(prob, eps=float(eps_host.min()))
                mp = loosest.resolved_max_passes(n)
            if prob.objective == "directed" and prob.c is None:
                raise ValueError("eps sweeps over a directed Problem need a fixed c")
            with_tiling = prob.backend == "pallas"
            aux: Tuple = _tiling_arrays(graph, prob) if with_tiling else ()
            # Exact undirected lanes read the src half of the degree count
            # off CSR offsets of a src-sorted unit-weight graph, built once
            # per call on the host and shared by every lane.
            src_off = None
            if prob.backend == "exact" and prob.objective != "directed":
                src_off = _src_offsets(
                    np.asarray(graph.src), np.asarray(graph.weight),
                    np.asarray(graph.mask), n,
                )
            sorted_src = src_off is not None
            if sorted_src:
                aux = (jnp.asarray(src_off),)
            key = self._key(
                "eps", prob, mp, n, graph.n_edges_padded,
                graph.weight.dtype,
                (_src_reduce(sorted_src),) + tuple(a.shape for a in aux),
            )
            fn, hit = self._get(
                key,
                lambda: self._build_jit_program(
                    prob, mp, "eps", with_tiling, sorted_src
                ),
                disk_dir=self._disk_dir(prob),
            )
            out = fn(graph, *aux, jnp.asarray(eps_host))
            extras = None
            if sorted_src:
                extras = {"degree_count": _DegreeCountRoutes(out.passes, 0)}
            return self._wrap(out, prob, n, mp, hit, extras=extras, batch="eps")

        # c sweep (directed only)
        if prob.objective != "directed":
            raise ValueError("c sweeps only apply to the directed objective")
        c_host = np.asarray(c, np.float32).reshape(-1)
        mp = prob.resolved_max_passes(n)
        key = self._key(
            "c", prob, mp, n, graph.n_edges_padded, graph.weight.dtype,
        )
        fn, hit = self._get(
            key,
            lambda: self._build_jit_program(prob, mp, "c", False),
            disk_dir=self._disk_dir(prob),
        )
        out = fn(graph, jnp.asarray(c_host))
        return self._wrap(out, prob, n, mp, hit, batch="c")


# ---------------------------------------------------------------------------
# Module-level front door (one shared program cache)
# ---------------------------------------------------------------------------

default_solver = Solver()


def solve(graph: EdgeList, problem: Problem, **kw) -> DenseSubgraphResult:
    """``Solver.solve`` on the process-wide :data:`default_solver` (shared
    compile cache — the production entry point)."""
    return default_solver.solve(graph, problem, **kw)


def solve_batch(graph, problem: Problem, **kw) -> DenseSubgraphResult:
    """``Solver.solve_batch`` on the process-wide :data:`default_solver`."""
    return default_solver.solve_batch(graph, problem, **kw)
