"""Per-seed community queries arriving open loop at a fixed rate.

The schedule comes from the seed alone: ``round(rate_qps * seconds)``
arrivals at sorted uniform times over the window (a Poisson process with
its count fixed), each asking for a seed node drawn by Zipf popularity
(exponent ``zipf_exponent``) over a seeded random permutation of the
non-isolated nodes.  One thread pumps the engine: it submits every query
that is due, steps the engine, and sleeps briefly when nothing is due.
A query's latency runs from its scheduled arrival to the return of the
``step()`` that answered it, so time the pump spent busy counts against
the queries that waited.

Set-up builds the engine and warms up every program the window can use:
each distinct seed of the schedule is extracted once to learn its
(node, edge) bucket, and each such bucket is solved at every lane count a
batch can have.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

import jax
import numpy as np

from perfbench import harness, reference
from repro.core import Problem
from repro.graph.edgelist import EdgeList
from repro.serve.densest import DensestQueryEngine

_COUNTERS = ("queries_answered", "batches_flushed", "local_edges_scanned")


def schedule(seed: int, n_queries: int, seconds: float, nodes: np.ndarray,
             zipf_exponent: float):
    """(arrival seconds float64[n_queries] sorted, seed node ids)."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    arrivals = np.sort(rng.uniform(0.0, seconds, n_queries))
    popular = rng.permutation(nodes)
    ranks = np.arange(1, len(popular) + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -zipf_exponent)
    pick = np.searchsorted(cdf, rng.uniform(0.0, cdf[-1], n_queries), side="right")
    return arrivals, popular[np.minimum(pick, len(popular) - 1)]


def _engine(ctx):
    t = ctx.cell.traffic
    prob = dataclasses.replace(
        Problem(**ctx.cell.config["problem"]), substrate="local"
    )
    return DensestQueryEngine(
        ctx.graph, prob, extraction="local", max_batch=t["max_batch"],
        max_wait_ms=t["max_wait_ms"], local_budget=t["local_budget"],
        local_rounds=t["local_rounds"], local_alpha=t["local_alpha"],
        node_floor=t["node_floor"],
        cache_dir=(os.path.join(harness.WORK_DIR, "serve_cache")
                   if ctx.persistent_cache else None),
    )


def _lane_counts(max_batch: int):
    lanes, out = 1, []
    while lanes < max_batch:
        out.append(lanes)
        lanes *= 2
    return out + [lanes]


def setup(ctx):
    engine = _engine(ctx)
    n = ctx.graph.n_nodes
    deg = jax.numpy.bincount(ctx.graph.src, length=n) + jax.numpy.bincount(
        ctx.graph.dst, length=n
    )
    return prepare(ctx, engine, np.nonzero(np.asarray(deg))[0])


def prepare(ctx, engine, nodes: np.ndarray) -> dict:
    """The window's schedule, and every program it can use warmed up."""
    t = ctx.cell.traffic
    n_queries = int(round(t["rate_qps"] * ctx.seconds))
    arrivals, seeds = schedule(ctx.seed, n_queries, ctx.seconds, nodes,
                               t["zipf_exponent"])
    buckets = set()
    for s in np.unique(seeds):
        padded, _ = engine.extract(int(s))
        buckets.add((padded.n_nodes, padded.n_edges_padded))
    for n_b, m_b in sorted(buckets):
        for lanes in _lane_counts(engine.max_batch):
            shape = (lanes, m_b)
            blank = EdgeList(
                src=np.zeros(shape, np.int32), dst=np.zeros(shape, np.int32),
                weight=np.zeros(shape, np.float32), mask=np.zeros(shape, bool),
                n_nodes=int(n_b),
            )
            np.asarray(engine.solver.solve_batch(blank, engine.problem).best_density)
    return {"engine": engine, "nodes": nodes, "arrivals": arrivals, "seeds": seeds}


def window(ctx) -> dict:
    """Arrivals spread over ``ctx.seconds``; the window closes when the last
    query is answered (or ``drain_s`` after the arrivals end)."""
    t = ctx.cell.traffic
    eng = ctx.state["engine"]
    arrivals, seeds = ctx.state["arrivals"], ctx.state["seeds"]
    n_queries = len(arrivals)
    before = eng.stats()
    index, answers, lag = {}, {}, []
    latency = np.full(n_queries, np.nan)
    nxt = 0
    t0 = time.perf_counter()
    deadline = ctx.seconds + t["drain_s"]
    while len(answers) < n_queries:
        now = time.perf_counter() - t0
        if now > deadline:
            break
        while nxt < n_queries and arrivals[nxt] <= now:
            with jax.profiler.TraceAnnotation("perfbench.submit"):
                index[eng.submit(int(seeds[nxt]))] = nxt
            lag.append(now - arrivals[nxt])
            nxt += 1
        with jax.profiler.TraceAnnotation("perfbench.step"):
            out = eng.step()
        done = time.perf_counter() - t0
        for r in out:
            answers[index[r.qid]] = r
            latency[index[r.qid]] = done - arrivals[index[r.qid]]
        if not out:
            wait = arrivals[nxt] - now if nxt < n_queries else 0.0005
            time.sleep(min(max(wait, 0.0), 0.0005))
    closed = time.perf_counter() - t0
    after = eng.stats()
    # A query never answered counts as waiting until the window closed.
    unanswered = np.isnan(latency)
    latency[unanswered] = closed - arrivals[unanswered]
    print(f"[open_loop] {n_queries} queries at {t['rate_qps']} qps, "
          f"submit lag max {1e3 * max(lag, default=0.0):.3f} ms, "
          f"window closed at {closed:.3f} s", file=sys.stderr, flush=True)
    ok = sum(1 for r in answers.values() if r.status == "ok")
    return {
        "answers": [answers[i] for i in sorted(answers)],
        "latency_s": latency,
        "attempted": n_queries,
        "failed": n_queries - ok,
        "counters": {k: after[k] - before[k] for k in _COUNTERS},
        "closed_s": closed,
        "lag_max_s": max(lag, default=0.0),
    }


def end_to_end(ctx) -> dict:
    lat_ms = 1e3 * ctx.window["latency_s"]
    return {
        "query_p50_ms": float(np.percentile(lat_ms, 50)),
        "query_p95_ms": float(np.percentile(lat_ms, 95)),
    }


def release(ctx) -> None:
    ctx.state = {}


def _sample(ctx, answers):
    """Distinct-seed answers to hold against the reference: a sample drawn
    from the seed plus the largest candidate sets."""
    t = ctx.cell.traffic
    by_seed = {}
    for r in answers:
        by_seed.setdefault(r.seed, r)
    distinct = sorted(by_seed)
    rng = np.random.default_rng([int(ctx.seed) % (1 << 63), 11])
    take = rng.permutation(len(distinct))[: t["check_sample"]]
    chosen = {distinct[i] for i in take}
    chosen |= set(sorted(distinct, key=lambda s: -by_seed[s].n_ego)[: t["check_largest"]])
    return [by_seed[s] for s in sorted(chosen)]


def check(ctx, control: bool = False) -> dict:
    t = ctx.cell.traffic
    g = ctx.graph
    adj = reference.csr_on_device(g.src, g.dst, g.n_nodes)
    kw = dict(
        eps=ctx.cell.config["problem"]["eps"], budget=t["local_budget"],
        rounds=t["local_rounds"], alpha=t["local_alpha"],
        node_floor=t["node_floor"],
    )
    density = mismatch = 0.0
    for r in _sample(ctx, ctx.window["answers"]):
        if r.status != "ok":
            continue
        members, rho = reference.local_answer(adj, r.seed, **kw)
        if control:
            got, got_rho = reference.local_answer(adj, r.seed, dtype=reference.BF16, **kw)
        else:
            got, got_rho = np.sort(r.nodes), r.density
        density = max(density, reference.rel_gap(got_rho, rho))
        mismatch += 0 if np.array_equal(got, members) else 1
    return {
        "density_gap": density,
        "set_mismatches": mismatch,
        "unanswered": ctx.window["failed"],
    }
