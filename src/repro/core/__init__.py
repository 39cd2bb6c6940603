"""Core library: the paper's densest-subgraph algorithms.

One front door (core/api.py): declare a :class:`Problem` (objective × eps ×
backend × substrate), call :func:`solve` / :func:`solve_batch`, get a
:class:`DenseSubgraphResult`.  The :class:`Solver` memoizes compiled
programs so production request rates never retrace; ``solve_batch`` runs
multi-eps / multi-c / stacked-graph sweeps as one XLA program.

    from repro.core import Problem, solve
    res = solve(edges, Problem.undirected(eps=0.5))   # Algorithm 1
    res = solve(edges, Problem.at_least_k(k=100))     # Algorithm 2
    res = solve(edges, Problem.directed())            # Algorithm 3, c-grid

All peel variants are one engine (core/engine.py): a single pass body
parameterized by RemovalPolicy × DegreeBackend, launched on a jit, host
streaming, or shard_map substrate.  This package exports:

  Problem / Solver / solve / solve_batch   the front door (core/api.py)
  run_peel / PeelOutcome, the policies     the engine itself
    and the degree backends
  StreamingDensest                 semi-streaming driver w/ checkpoint+stragglers
  TurnstileDensest                 ℓ0-sketch dynamic-stream runtime (±edges)
  LocalExplorer                    Andersen pruned-frontier exploration
                                   (substrate='local', per-seed queries)
  densest_subgraph_exact           Goldberg max-flow exact oracle
  densest_subgraph_brute /         brute-force oracles for small graphs
    densest_directed_brute
  charikar_greedy                  node-at-a-time 2-approx baseline [10]

Everything else lives in its module (``repro.core.countsketch``,
``repro.core.mapreduce``, ``repro.core.streaming``, ...).
"""

from repro.core.api import (
    DenseSubgraphResult,
    Problem,
    Provenance,
    Solver,
    c_grid,
    default_solver,
    solve,
    solve_batch,
    stack_graphs,
)
from repro.core.charikar import charikar_greedy
from repro.core.countsketch import SketchBackend
from repro.core.engine import (
    AtLeastKFraction,
    DirectedST,
    ExactBackend,
    FnBackend,
    MeshSegmentSumBackend,
    PeelOutcome,
    PeelState,
    UndirectedThreshold,
    removal_threshold,
    run_peel,
    segment_degree_count,
    undirected_pass_step,
)
from repro.core.exact import (
    densest_directed_brute,
    densest_subgraph_brute,
    densest_subgraph_exact,
)
from repro.core.local import LocalExplorer
from repro.core.streaming import StreamingDensest
from repro.core.turnstile import TurnstileDensest

__all__ = [
    "AtLeastKFraction",
    "DenseSubgraphResult",
    "DirectedST",
    "ExactBackend",
    "FnBackend",
    "LocalExplorer",
    "MeshSegmentSumBackend",
    "PeelOutcome",
    "PeelState",
    "Problem",
    "Provenance",
    "SketchBackend",
    "Solver",
    "StreamingDensest",
    "TurnstileDensest",
    "UndirectedThreshold",
    "c_grid",
    "charikar_greedy",
    "default_solver",
    "densest_directed_brute",
    "densest_subgraph_brute",
    "densest_subgraph_exact",
    "removal_threshold",
    "run_peel",
    "segment_degree_count",
    "solve",
    "solve_batch",
    "stack_graphs",
    "undirected_pass_step",
]
