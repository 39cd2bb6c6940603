"""What the two whole-graph drivers share: a closed loop of front-door
calls, each ending with its answer on the host, and the comparison of
every answer of the window with the float32 reference peel."""

from __future__ import annotations

import time

import jax
import numpy as np

from perfbench import reference
from repro.core import Problem


def problem(config: dict) -> Problem:
    return Problem(**config["problem"])


def host_answer(res) -> dict:
    """A result's answer on the host: best set(s), density, passes."""
    return {
        "best": np.asarray(res.best_alive),
        "density": np.asarray(res.best_density),
        "passes": np.asarray(res.passes),
        "ladder": (res.extras or {}).get("compaction"),
    }


def closed_loop(call, seconds: float, span: str) -> dict:
    """Calls back to back until ``seconds`` have passed; the window ends
    with the last call's answer, so every call in it is complete."""
    answers = []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation(span):
            answers.append(host_answer(call()))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    return {
        "answers": answers,
        "elapsed_s": elapsed,
        "attempted": len(answers),
        "failed": 0,
    }


def solve_s(ctx) -> float:
    w = ctx.window
    return w["elapsed_s"] / w["attempted"]


def host_edges(graph):
    return np.asarray(graph.src), np.asarray(graph.dst)


def compare(answers, refs) -> dict:
    """Worst gaps over every answer of the window; ``refs`` holds one
    reference peel per lane (a whole-graph solve is one lane)."""
    passes = density = sets = 0
    for a in answers:
        best = a["best"].reshape(len(refs), -1)
        dens = a["density"].reshape(-1)
        ps = a["passes"].reshape(-1)
        for lane, ref in enumerate(refs):
            passes = max(passes, abs(int(ps[lane]) - ref["passes"]))
            density = max(density, reference.rel_gap(dens[lane], ref["density"]))
            sets = max(sets, int(np.count_nonzero(best[lane] != ref["best"])))
    return {"passes_gap": passes, "density_gap": density, "set_diff_nodes": sets}


def check(ctx, eps_values, control: bool) -> dict:
    """Each answer of the window against the float32 reference at each eps;
    the control puts the bfloat16 reference in the program's place."""
    src, dst = host_edges(ctx.graph)
    n = ctx.graph.n_nodes
    refs = [reference.peel(src, dst, n, e) for e in eps_values]
    ctx.window["pass_bytes_per_call"] = sum(
        reference.pass_bytes(r["edges"], r["nodes"]) for r in refs
    )
    answers = ctx.window["answers"]
    if control:
        low = [reference.peel(src, dst, n, e, reference.BF16) for e in eps_values]
        answers = [{
            "best": np.stack([r["best"] for r in low]),
            "density": np.asarray([r["density"] for r in low]),
            "passes": np.asarray([r["passes"] for r in low]),
        }]
    return compare(answers, refs)
