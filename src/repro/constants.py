"""The ONE surface for pow2 bucket floors and ladder capacities.

Every power-of-two padding floor and geometric-ladder capacity in the
runtime lives here, imported (or aliased) by the module that uses it —
never re-typed as a bare literal at a call site.  The ``pow2-constants``
analysis rule (src/repro/analysis/rules/pow2_constants.py, run by
``scripts/analyze.py``) enforces both directions mechanically:

  * a literal ``floor=``/capacity argument to
    :func:`repro.graph.partition.pow2_bucket` or
    :func:`repro.graph.partition.ladder_schedule` is a finding — pass a
    name defined here instead;
  * a module-level ``*_FLOOR`` / ``*_MIN_EDGES`` / ``*_MIN_NODES`` /
    ``*_STRIDE`` assignment with a literal value anywhere else under
    ``src/repro`` is a finding — aliases (``_X = constants.X``) are fine
    and keep monkeypatch-ability (tests patch ``api._LADDER_MIN_EDGES``).

Why one surface: these values couple compiled-program cache keys (every
distinct bucket shape is one compilation) to scheduling depth (every
floor bounds a ladder).  A re-typed copy that drifts from its sibling
silently doubles the compile population or unbalances a ladder — the
class of bug PR 3 and PR 5 each caught by hand in review.

No jax imports here: this module must stay importable everywhere
(including the jax-free static-analysis tooling).
"""

from __future__ import annotations

# --- host (jit-substrate) geometric compaction ladder (core/api.py) --------
# Survivors gather into the next power-of-two buffer; the floors bound the
# ladder depth and keep the smallest compiled programs non-degenerate.
COMPACT_MIN_EDGES = 256
COMPACT_MIN_NODES = 128
# Runaway guard on ladder depth; real ladders are O(log m) segments.
COMPACT_MAX_SEGMENTS = 64
# A rung with at least this many edge slots compacts chunk-parallel on a
# thread pool; a smaller one (every test graph, serving ego-net and late
# rung) compacts as one chunk, inline, where threads would cost more than
# the gather.
COMPACT_PARALLEL_MIN_EDGES = 2**20
# Pool workers: one per available core, at most this many (the gathers are
# memory-bound); a parallel rung is cut into this many chunks per worker,
# so a chunk dense in survivors does not hold up the rest.
COMPACT_MAX_WORKERS = 16
COMPACT_CHUNKS_PER_WORKER = 4

# --- single-program mesh ladder (core/api.py, §5.2) -------------------------
# Rung capacities shrink by this factor.  4 was the measured sweet spot on
# CPU — halving rungs doubles the compaction-collective count for
# edge-slot savings the pass cost no longer dominates.
LADDER_STRIDE = 4
# Bucket floor: below this many (global) edge slots a pass is trivial, but
# every extra rung still pays its fixed while-loop/compaction cost inside
# the program, so the mesh ladder stops coarser than the host schedule's
# COMPACT_MIN_EDGES.
LADDER_MIN_EDGES = 4096

# --- streaming compaction rebuild (core/streaming.py) -----------------------
# Pow2-padded node space of a rebuilt survivor stream (with one
# permanently-dead pad node), so the jitted chunk kernel sees O(log n)
# distinct degree-vector shapes across the whole ladder.
STREAM_REBUILD_NODE_FLOOR = 64
# Per-chunk pow2 slot capacity of a rebuilt (ragged) chunk, so surviving
# chunks land on a bounded set of shapes instead of one compile per chunk.
STREAM_REBUILD_CHUNK_FLOOR = 256

# --- serving ego-net buckets (serve/densest.py) -----------------------------
# Extracted ego-nets pad into pow2 (node, edge) buckets so the whole query
# population shares a handful of vmapped programs (docs/serving.md).
SERVE_NODE_FLOOR = 64
SERVE_EDGE_FLOOR = 256

# --- local (Andersen) substrate (core/local.py, serve/densest.py) -----------
# Default candidate-set size cap of the pruned-frontier exploration: per-query
# work is bounded by the budget (times the candidate volume), independent of n.
LOCAL_BUDGET = 512
# Expansion-round cap (each round scans only the newly admitted rows).
LOCAL_ROUNDS = 8
# Degrade-ladder floor: the serving engine's budget-halving fallback rung
# stops here (a smaller candidate set answers nothing a BFS rung would).
LOCAL_BUDGET_FLOOR = 64
# Work (volume) cap factor: one exploration scans at most
# budget * LOCAL_VOLUME_FACTOR CSR slots, applied at ADMISSION (a frontier
# vertex whose row does not fit in the remaining work budget is not
# admitted), so per-query work is bounded by construction even when a
# power-law hub sits next to the seed — the property BENCH_serve.json's
# local_vs_bfs_sweep holds flat across graph sizes.
LOCAL_VOLUME_FACTOR = 32

# --- turnstile runtime (core/turnstile.py) ----------------------------------
# IBLT cell count floor per level (pow2 of the sample budget tau) and the
# compact pow2 buckets the recovered sample is peeled in.
TURNSTILE_SAMPLE_EDGE_FLOOR = 256
TURNSTILE_SAMPLE_NODE_FLOOR = 256
# Update batches pad to pow2 with this floor: one donated update program
# per bucket, a handful of buckets total.
TURNSTILE_BATCH_FLOOR = 1024
