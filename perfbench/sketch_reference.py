"""Algorithm 1 with §5.1 Count-Sketch degrees, in plain numpy: the
reference the ``lj_sketch_peel`` cell holds the program's answers against.

Nothing here imports the program (``repro``) or reads anything it made:
the inputs are the benchmark's own generated edges and the configuration's
sketch parameters.

The sketch (Bahmani, Kumar, Vassilvitskii, PVLDB 5(5) 2012, §5.1) keeps t
tables of b signed counters.  An alive edge (x, y) adds g_i(x) to counter
(i, h_i(x)) and g_i(y) to (i, h_i(y)); the degree estimate of x is the
median over i of c[i, h_i(x)]·g_i(x).  The hash family is the
multiply-shift family that ``src/repro/kernels/hashing.py`` documents, on
uint32 with wrap-around:

    h = a·x + c (mod 2^32);  h ^= h >> 16
    bucket = h mod b;  sign = +1 if the top bit of h is 0, else -1

with one (a, c) pair per table for the bucket and another for the sign.
The parameters are drawn from ``numpy.random.default_rng(sketch_seed)`` in
the order the program's ``make_sketch_params`` draws them: t odd bucket
multipliers, t bucket offsets, t odd sign multipliers, t sign offsets (an
odd multiplier is ``2·k + 1`` for ``k`` drawn below 2^31, an offset is
drawn below 2^32).  A program that draws them in another order hashes
differently, and its answers fail the check.

A counter is a sum over endpoints, so it equals the sum over nodes of
g_i(x)·deg(x): each pass counts the exact induced degrees and then sums
them into the tables exactly; a float32 counter holds its sum exactly
while it stays below 2^24, as the program's does.  With t odd each
estimate is the middle one of its t values.  The density is alive edges over alive nodes
in float32, as in ``reference.peel``; a node goes when its estimate is at
most 2(1+eps)·rho, or equal to the least estimate over alive nodes; the
pass count stops at the Lemma 4 bound ceil(log_{1+eps} n) + 4 (at least
8, at most n + 1).  ``dtype=bfloat16`` is the control: the same peel with
its counters, estimates, density and threshold held in bfloat16.
"""

from __future__ import annotations

import math

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


def sketch_params(t: int, b: int, seed: int) -> dict:
    """The four uint32[t] parameter rows of the program's hash family."""
    rng = np.random.default_rng(seed)

    def odd():
        return (rng.integers(0, 1 << 31, size=t, dtype=np.int64) * 2 + 1).astype(np.uint32)

    def any32():
        return rng.integers(0, 1 << 32, size=t, dtype=np.int64).astype(np.uint32)

    a_h = odd()
    c_h = any32()
    a_g = odd()
    c_g = any32()
    return {"a_h": a_h, "c_h": c_h, "a_g": a_g, "c_g": c_g, "buckets": int(b)}


def _mix(a, c, x):
    h = a * x + c  # uint32: wraps mod 2^32
    return h ^ (h >> np.uint32(16))


def node_hashes(params: dict, n: int):
    """(bucket int64[t, n], sign int64[t, n]) of every node id."""
    x = np.arange(n, dtype=np.uint32)[None, :]
    col = (slice(None), None)
    hb = _mix(params["a_h"][col], params["c_h"][col], x)
    hg = _mix(params["a_g"][col], params["c_g"][col], x)
    bucket = (hb % np.uint32(params["buckets"])).astype(np.int64)
    sign = np.where((hg >> np.uint32(31)) == 0, 1, -1).astype(np.int64)
    return bucket, sign


def pass_cap(n: int, eps: float) -> int:
    """The program's static pass bound (Lemma 4)."""
    bound = int(math.ceil(math.log(max(n, 2)) / math.log1p(eps))) + 4
    return max(8, min(bound, n + 1))


def estimates(bucket, sign, deg, buckets: int, dtype=np.float32):
    """``dtype[n]`` median-of-t estimates from exact degrees ``deg``
    (int64[n]).  Each table is summed exactly (``np.bincount`` sums in
    float64, exact for integers below 2^53) and then held in ``dtype``."""
    t = bucket.shape[0]
    est = np.empty(bucket.shape, np.float64)
    for i in range(t):
        table = np.bincount(bucket[i], weights=sign[i] * deg, minlength=buckets)
        est[i] = table.astype(dtype)[bucket[i]].astype(np.float64) * sign[i]
    return np.sort(est, axis=0)[t // 2].astype(dtype)


def peel(src, dst, n: int, eps: float, t: int, b: int, seed: int,
         dtype=np.float32) -> dict:
    """The sketched Algorithm 1 on ``n`` nodes.  Returns a dict with
    ``passes``, ``density`` (of the best set, in ``dtype``, as a float),
    ``best`` (bool[n]) and the per-pass alive ``edges`` and ``nodes``
    counts."""
    if t % 2 == 0:
        raise ValueError(f"the median of t={t} tables is not one of them; take t odd")
    params = sketch_params(t, b, seed)
    bucket, sign = node_hashes(params, n)
    cap = pass_cap(n, eps)
    alive = np.ones(n, bool)
    s = np.asarray(src)
    d = np.asarray(dst)
    best_rho = dtype(-np.inf)
    best = alive.copy()
    scale = dtype(2.0 * (1.0 + eps))
    edges, nodes = [], []
    while alive.any() and len(edges) < cap:
        n_alive = int(np.count_nonzero(alive))
        edges.append(len(s))
        nodes.append(n_alive)
        deg = np.bincount(s, minlength=n) + np.bincount(d, minlength=n)
        est = estimates(bucket, sign, deg, b, dtype)
        rho = dtype(dtype(len(s)) / dtype(n_alive))
        if rho > best_rho:
            best_rho, best = rho, alive.copy()
        least = est[alive].min()
        alive &= ~((est <= dtype(scale * rho)) | (est <= least))
        keep = alive[s] & alive[d]
        s, d = s[keep], d[keep]
    return {
        "passes": len(edges),
        "density": float(best_rho),
        "best": best,
        "edges": edges,
        "nodes": nodes,
    }


def sketch_pass_bytes(edges, nodes, t: int, b: int) -> int:
    """Bytes the sketched passes of one peel must move at least, per pass:
    each alive edge's two endpoint ids and weight, read once for all t
    tables (12 B); each counter written and read once (8·t·b B); each alive
    node's t counter reads and its estimate written (4·(t+1) B).  The alive
    counts are the reference's (``peel``), not the program's padded
    buffer."""
    passes = len(edges)
    return int(12 * sum(edges) + 8 * t * b * passes + 4 * (t + 1) * sum(nodes))
