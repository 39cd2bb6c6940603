"""Synthetic graph generators (host-side numpy) used by tests and benchmarks.

These replace the paper's proprietary / large public datasets (FLICKR, IM,
LIVEJOURNAL, TWITTER are not available offline): we generate graphs with the
same structural features the paper's experiments rely on — heavy-tailed degree
distributions, planted dense communities, and the Lemma 5 pass-lower-bound
instance.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.graph.edgelist import EdgeList, dedup_edges, from_numpy


def erdos_renyi(n: int, avg_deg: float, seed: int = 0, directed: bool = False) -> EdgeList:
    rng = np.random.default_rng(seed)
    m = int(n * avg_deg / (1 if directed else 2))
    src = rng.integers(0, n, size=2 * m + 16)
    dst = rng.integers(0, n, size=2 * m + 16)
    src, dst = dedup_edges(src, dst, directed=directed)
    src, dst = src[:m], dst[:m]
    return from_numpy(src, dst, n, directed=directed)


def planted_dense_subgraph(
    n: int,
    avg_deg: float,
    k: int,
    p_dense: float,
    seed: int = 0,
) -> Tuple[EdgeList, np.ndarray]:
    """ER background + a planted dense block on the first ``k`` nodes.

    Returns the graph and the planted node-index array.
    """
    rng = np.random.default_rng(seed)
    m_bg = int(n * avg_deg / 2)
    src_bg = rng.integers(0, n, size=m_bg)
    dst_bg = rng.integers(0, n, size=m_bg)
    # Dense block: each pair kept with prob p_dense.
    iu = np.triu_indices(k, 1)
    keep = rng.random(iu[0].shape[0]) < p_dense
    src = np.concatenate([src_bg, iu[0][keep]])
    dst = np.concatenate([dst_bg, iu[1][keep]])
    src, dst = dedup_edges(src, dst, directed=False)
    return from_numpy(src, dst, n), np.arange(k)


def chung_lu_power_law(
    n: int,
    exponent: float = 2.2,
    avg_deg: float = 8.0,
    seed: int = 0,
    n_edges: int | None = None,
) -> EdgeList:
    """Chung-Lu graph with power-law expected degrees (heavy-tail, like the
    paper's social graphs).

    ``n_edges`` asks for exactly that many distinct edges (a published
    graph's n and m, e.g. the Table 1 shapes): endpoint pairs are drawn
    until at least ``n_edges`` survive deduplication, then a uniform subset
    of that size is kept, so the degree law is the same as the
    ``avg_deg`` spelling's.  ``avg_deg`` is ignored then."""
    rng = np.random.default_rng(seed)
    w = (np.arange(1, n + 1) ** (-1.0 / (exponent - 1.0))).astype(np.float64)
    w *= n * avg_deg / w.sum()
    p = w / w.sum()
    if n_edges is None:
        m = int(n * avg_deg / 2)
        src = rng.choice(n, size=m, p=p)
        dst = rng.choice(n, size=m, p=p)
        src, dst = dedup_edges(src, dst, directed=False)
        return from_numpy(src, dst, n)
    target = int(n_edges)
    src = np.zeros(0, np.int64)
    dst = np.zeros(0, np.int64)
    draw = target
    while True:
        src = np.concatenate([src, rng.choice(n, size=draw, p=p)])
        dst = np.concatenate([dst, rng.choice(n, size=draw, p=p)])
        s, d = dedup_edges(src, dst, directed=False)
        if len(s) >= target:
            break
        # Top up by the shortfall over the observed keep rate, plus margin.
        draw = int((target - len(s)) * len(src) / max(len(s), 1) * 1.1) + 1
    keep = np.sort(rng.choice(len(s), size=target, replace=False))
    return from_numpy(s[keep], d[keep], n)


def barabasi_albert(n: int, m_attach: int = 4, seed: int = 0) -> EdgeList:
    rng = np.random.default_rng(seed)
    targets = list(range(m_attach))
    repeated: list[int] = list(range(m_attach))
    src_l: list[int] = []
    dst_l: list[int] = []
    for v in range(m_attach, n):
        chosen = rng.choice(np.asarray(repeated), size=m_attach, replace=False)
        for t in set(int(c) for c in chosen):
            src_l.append(v)
            dst_l.append(t)
            repeated.append(v)
            repeated.append(t)
    src, dst = dedup_edges(np.asarray(src_l), np.asarray(dst_l), directed=False)
    del targets
    return from_numpy(src, dst, n)


def _regular_circulant(n: int, d: int, offset_nodes: int) -> Tuple[np.ndarray, np.ndarray]:
    """A d-regular graph on n nodes (circulant; d=1 => perfect matching)."""
    assert d < n
    src_l = []
    dst_l = []
    if d == 1:
        assert n % 2 == 0
        a = np.arange(0, n, 2)
        src_l.append(a)
        dst_l.append(a + 1)
    else:
        assert d % 2 == 0 or n % 2 == 0
        half = d // 2
        a = np.arange(n)
        for j in range(1, half + 1):
            src_l.append(a)
            dst_l.append((a + j) % n)
        if d % 2 == 1:
            a2 = np.arange(n // 2)
            src_l.append(a2)
            dst_l.append((a2 + n // 2) % n)
    src = np.concatenate(src_l) + offset_nodes
    dst = np.concatenate(dst_l) + offset_nodes
    return src, dst


def lemma5_instance(k: int) -> EdgeList:
    """The Lemma 5 pass-lower-bound instance.

    k disjoint subgraphs G_1..G_k where G_i is 2^{i-1}-regular on 2^{2k+1-i}
    nodes; every G_i has 2^{2k-1} edges.  Algorithm 1 provably needs
    Omega(k / log k) passes on this graph.
    """
    srcs, dsts = [], []
    offset = 0
    for i in range(1, k + 1):
        ni = 2 ** (2 * k + 1 - i)
        di = 2 ** (i - 1)
        s, d = _regular_circulant(ni, di, offset)
        srcs.append(s)
        dsts.append(d)
        offset += ni
    src = np.concatenate(srcs)
    dst = np.concatenate(dsts)
    src, dst = dedup_edges(src, dst, directed=False)
    return from_numpy(src, dst, offset)


def directed_planted(
    n: int, avg_deg: float, ks: int, kt: int, p_dense: float, seed: int = 0
) -> Tuple[EdgeList, np.ndarray, np.ndarray]:
    """Directed ER + planted dense S->T block (S = first ks nodes, T = next kt)."""
    rng = np.random.default_rng(seed)
    m_bg = int(n * avg_deg)
    src_bg = rng.integers(0, n, size=m_bg)
    dst_bg = rng.integers(0, n, size=m_bg)
    s_ids = np.arange(ks)
    t_ids = np.arange(ks, ks + kt)
    grid_s, grid_t = np.meshgrid(s_ids, t_ids, indexing="ij")
    keep = rng.random(grid_s.size) < p_dense
    src = np.concatenate([src_bg, grid_s.ravel()[keep]])
    dst = np.concatenate([dst_bg, grid_t.ravel()[keep]])
    src, dst = dedup_edges(src, dst, directed=True)
    return from_numpy(src, dst, n, directed=True), s_ids, t_ids


def bipartite_spam(
    n_users: int,
    n_items: int,
    avg_deg: float,
    spam_users: int,
    spam_items: int,
    p_spam: float,
    seed: int = 0,
) -> Tuple[EdgeList, np.ndarray, np.ndarray]:
    """User->item bipartite interaction graph with a planted spam block
    (the paper's link-spam application, adapted to recsys interactions).

    Nodes 0..n_users-1 are users; n_users..n_users+n_items-1 are items.
    Spam block: the *last* ``spam_users`` users and ``spam_items`` items.
    """
    rng = np.random.default_rng(seed)
    m_bg = int(n_users * avg_deg)
    src_bg = rng.integers(0, n_users, size=m_bg)
    dst_bg = rng.integers(0, n_items, size=m_bg) + n_users
    su = np.arange(n_users - spam_users, n_users)
    si = np.arange(n_items - spam_items, n_items) + n_users
    gs, gi = np.meshgrid(su, si, indexing="ij")
    keep = rng.random(gs.size) < p_spam
    src = np.concatenate([src_bg, gs.ravel()[keep]])
    dst = np.concatenate([dst_bg, gi.ravel()[keep]])
    src, dst = dedup_edges(src, dst, directed=True)
    n = n_users + n_items
    return from_numpy(src, dst, n, directed=True), su, si


def planted_partition(
    n: int, k: int, p_in, p_out: float, seed: int = 0
) -> Tuple[EdgeList, np.ndarray]:
    """k equal communities: edge prob p_in inside (scalar or per-community
    list — unequal densities make the peel extract them in order), p_out
    across.  Returns (graph, community labels int[n]); sampled sparsely
    (expected-count binomial per block) so large n stays cheap.
    """
    rng = np.random.default_rng(seed)
    labels = np.repeat(np.arange(k), n // k + 1)[:n]
    p_in_list = [p_in] * k if np.isscalar(p_in) else list(p_in)
    srcs, dsts = [], []
    idx_of = [np.nonzero(labels == c)[0] for c in range(k)]
    for a in range(k):
        na = len(idx_of[a])
        m_in = rng.binomial(na * (na - 1) // 2, p_in_list[a])
        srcs.append(idx_of[a][rng.integers(0, na, m_in)])
        dsts.append(idx_of[a][rng.integers(0, na, m_in)])
        for b in range(a + 1, k):
            nb = len(idx_of[b])
            m_x = rng.binomial(na * nb, p_out)
            srcs.append(idx_of[a][rng.integers(0, na, m_x)])
            dsts.append(idx_of[b][rng.integers(0, nb, m_x)])
    src = np.concatenate(srcs)
    dst = np.concatenate(dsts)
    src, dst = dedup_edges(src, dst, directed=False)
    return from_numpy(src, dst, n), labels


def weighted_preferential(n: int, seed: int = 0) -> EdgeList:
    """Deterministic weighted preferential-attachment graph from the Lemma 6
    proof sketch: node u arriving connects to all previous v with weight
    proportional to v's current (weighted) degree."""
    deg = np.zeros(n, np.float64)
    srcs, dsts, ws = [], [], []
    deg[0] = deg[1] = 1.0
    srcs.append(0)
    dsts.append(1)
    ws.append(1.0)
    for u in range(2, n):
        w_uv = deg[:u] / deg[:u].sum()
        srcs.extend([u] * u)
        dsts.extend(range(u))
        ws.extend(w_uv.tolist())
        deg[:u] += w_uv
        deg[u] = w_uv.sum()
    return from_numpy(
        np.asarray(srcs), np.asarray(dsts), n, weight=np.asarray(ws, np.float32)
    )
