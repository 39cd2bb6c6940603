"""Plain references the benchmark holds the program's answers against.

Nothing here imports the program (``repro``) or reads anything it made:
the inputs are the benchmark's own generated edges and the answers'
node ids.

* :func:`peel` is Algorithm 1 (Bahmani, Kumar, Vassilvitskii, PVLDB 5(5)
  2012, section 4.1) in plain numpy: each pass counts induced degrees with
  ``np.bincount``, records the densest set seen, and removes every node of
  degree <= 2(1+eps)·rho(S), or equal to the minimum degree so that every
  pass makes progress.  ``dtype=float32`` is the configuration's
  precision; ``dtype=bfloat16`` is the control, the same peel with its
  degrees, density and threshold held in bfloat16.
* :func:`explore` is the per-seed candidate set of the local serving path
  (Andersen's pruned frontier, arXiv cs/0702078, as ``docs/serving.md``
  states the rule), recounted from scratch each round instead of kept
  incrementally.
* :func:`local_answer` peels that candidate set's induced subgraph padded
  with isolated nodes to the serving bucket, as the served answer does.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16
# The local explorer's scan volume, in CSR slots per unit of budget: the
# volume cap of docs/serving.md (a frontier node whose degree would take
# the scanned slots past budget * VOLUME_FACTOR is skipped).  The serving
# engine takes no such setting, so it is the reference's constant, not a
# parameter of the traffic.
VOLUME_FACTOR = 32


def peel(src, dst, n: int, eps: float, dtype=np.float32, max_passes=None):
    """Algorithm 1 on ``n`` nodes.  Returns a dict with ``passes``,
    ``density`` (of the best set, in ``dtype``, as a float), ``best``
    (bool[n]) and the per-pass alive ``edges`` and ``nodes`` counts."""
    alive = np.ones(n, bool)
    s = np.asarray(src)
    d = np.asarray(dst)
    best_rho = dtype(-np.inf)
    best = alive.copy()
    scale = dtype(2.0 * (1.0 + eps))
    edges, nodes = [], []
    while alive.any() and (max_passes is None or len(edges) < max_passes):
        n_alive = int(np.count_nonzero(alive))
        edges.append(len(s))
        nodes.append(n_alive)
        deg = (np.bincount(s, minlength=n) + np.bincount(d, minlength=n)).astype(dtype)
        rho = dtype(dtype(len(s)) / dtype(n_alive))
        if rho > best_rho:
            best_rho, best = rho, alive.copy()
        min_deg = deg[alive].min()
        alive &= ~((deg <= dtype(scale * rho)) | (deg <= min_deg))
        keep = alive[s] & alive[d]
        s, d = s[keep], d[keep]
    return {
        "passes": len(edges),
        "density": float(best_rho),
        "best": best,
        "edges": edges,
        "nodes": nodes,
    }


def density64(src, dst, members, n: int) -> float:
    """float64 |E(S)| / |S| of an unweighted node set, from the edge list."""
    if len(members) == 0:
        return 0.0
    inside = np.zeros(n, bool)
    inside[members] = True
    return float(np.count_nonzero(inside[src] & inside[dst])) / len(members)


def rel_gap(a: float, b: float) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


class Adjacency:
    """Symmetric adjacency in CSR form: ``indptr`` int64[n+1] and
    ``indices`` int32 (a row's order is of no account)."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray):
        self.indptr = np.asarray(indptr, np.int64)
        self.indices = np.asarray(indices)
        self.n = len(self.indptr) - 1

    def degree(self, v):
        return self.indptr[np.asarray(v) + 1] - self.indptr[np.asarray(v)]

    def rows(self, nodes) -> np.ndarray:
        """The concatenated neighbour lists of ``nodes``."""
        nodes = np.asarray(nodes, np.int64)
        if nodes.size == 0:
            return np.empty(0, np.int64)
        return np.concatenate(
            [self.indices[self.indptr[v]:self.indptr[v + 1]] for v in nodes]
        ).astype(np.int64)


def _pow2(x: int, floor: int) -> int:
    b = 1
    while b < x:
        b *= 2
    return max(b, floor)


def explore(adj: Adjacency, seed: int, budget: int, rounds: int,
            alpha: float) -> np.ndarray:
    """Sorted candidate set of ``seed``.  Each round: the frontier is every
    neighbour of T outside T; a frontier node with ``d`` edges into T joins
    iff ``d >= max(alpha * |E(T)|/|T|, 1)``, strongest ties first and lowest
    id on ties, at most ``budget - |T|`` of them; a node whose degree
    exceeds the scan volume still free (``budget * VOLUME_FACTOR`` minus
    the degrees of T) is skipped, and the rest join while their degrees
    fit.  Stops when nothing joins, after ``rounds`` rounds, or once T holds
    ``budget`` nodes or its degrees reach the volume."""
    volume = budget * VOLUME_FACTOR
    members = {int(seed)}
    for _ in range(rounds):
        t = np.fromiter(sorted(members), np.int64)
        scanned = int(adj.degree(t).sum())
        if len(t) >= budget or scanned >= volume:
            break
        nb = adj.rows(t)
        in_t = np.isin(nb, t)
        e_t = int(np.count_nonzero(in_t)) // 2
        outside = nb[~in_t]
        if outside.size == 0:
            break
        ids, into = np.unique(outside, return_counts=True)
        need = max(alpha * (e_t / len(t)), 1.0)
        ok = into >= need
        ids, into = ids[ok], into[ok]
        if ids.size == 0:
            break
        ids = ids[np.lexsort((ids, -into))][: budget - len(t)]
        free = volume - scanned
        sizes = adj.degree(ids)
        ids, sizes = ids[sizes <= free], sizes[sizes <= free]
        ids = ids[np.cumsum(sizes) <= free]
        if ids.size == 0:
            break
        members.update(int(v) for v in ids)
    return np.fromiter(sorted(members), np.int64)


def local_answer(adj: Adjacency, seed: int, *, eps: float, budget: int,
                 rounds: int, alpha: float, node_floor: int, dtype=np.float32):
    """(members sorted, density) of the peel of ``seed``'s candidate set,
    padded with isolated nodes to the power-of-two node bucket."""
    cand = explore(adj, seed, budget, rounds, alpha)
    nb = adj.rows(cand)
    row = np.repeat(cand, adj.degree(cand))
    inside = np.isin(nb, cand) & (nb > row)
    src = np.searchsorted(cand, row[inside])
    dst = np.searchsorted(cand, nb[inside])
    n_b = _pow2(len(cand), node_floor)
    out = peel(src, dst, n_b, eps, dtype)
    best = np.nonzero(out["best"][: len(cand)])[0]
    return cand[best], out["density"]


def csr(src: np.ndarray, dst: np.ndarray, n: int) -> Adjacency:
    """Host CSR of an undirected edge list (numpy; fine at test sizes)."""
    s = np.concatenate([src, dst]).astype(np.int64)
    d = np.concatenate([dst, src]).astype(np.int64)
    order = np.lexsort((d, s))
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(s, minlength=n), out=indptr[1:])
    return Adjacency(indptr, d[order].astype(np.int32))


def pass_bytes(edges, nodes) -> int:
    """Bytes the passes of one peel must move at least: per pass, each alive
    edge's two endpoint ids and weight (12 B) and each alive node's degree
    read and written (8 B)."""
    return int(12 * sum(edges) + 8 * sum(nodes))


def csr_on_device(src, dst, n: int) -> Adjacency:
    """:func:`csr` of device-resident edges, sorted on the device (a host
    sort of a Table 1 graph's 138M adjacency slots takes minutes)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(s, d):
        s, d = jnp.concatenate([s, d]), jnp.concatenate([d, s])
        return jnp.bincount(s, length=n), d[jnp.argsort(s)]

    counts, indices = build(src, dst)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.asarray(counts), out=indptr[1:])
    return Adjacency(indptr, np.asarray(indices))
