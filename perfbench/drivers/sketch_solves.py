"""Whole-graph solves with §5.1 Count-Sketch degrees, back to back, from
one caller.

Each call is ``solve(graph, Problem(**config["problem"]))`` with
``backend='sketch'`` on the device-resident graph, ending with the answer
on the host; the sketch keeps compaction off, so every pass scans the
whole edge buffer.  Set-up warms up with one solve.  The check holds every
answer of the window against the sketched Algorithm 1 of
``perfbench/sketch_reference.py`` with the configuration's eps, tables,
buckets and sketch seed.

The window also keeps each call's ``extras["sketch"]`` counters, and
:func:`scope_seconds` reads the device time of the sketch's own scopes
(``peel/.../degree_count/sketch_update`` and ``.../sketch_query``) from a
traced run, for the ``sketch.*`` metric readers.
"""

from __future__ import annotations

import glob
import os
import sys

import numpy as np

from perfbench import sketch_reference, spans
from perfbench.drivers import solves
from repro.core import Solver

SCOPES = ("sketch_update", "sketch_query")


def setup(ctx):
    solver = Solver()
    prob = solves.problem(ctx.cell.config)
    sketches = []

    def call():
        res = solver.solve(ctx.graph, prob)
        sketches.append((res.extras or {}).get("sketch"))
        return res

    solves.host_answer(call())
    return {"call": call, "sketches": sketches}


def window(ctx) -> dict:
    sketches = ctx.state["sketches"]
    sketches.clear()
    w = solves.closed_loop(ctx.state["call"], ctx.seconds, "perfbench.solve")
    w["sketch"] = [None if s is None else dict(s) for s in sketches]
    return w


def end_to_end(ctx) -> dict:
    return {"solve_s": solves.solve_s(ctx)}


def release(ctx) -> None:
    ctx.state = {}


def check(ctx, control: bool = False) -> dict:
    """Each answer of the window against the float32 sketched reference
    peel; the control puts its bfloat16 peel in the program's place."""
    src, dst = solves.host_edges(ctx.graph)
    n = ctx.graph.n_nodes
    p = ctx.cell.config["problem"]
    kw = dict(eps=p["eps"], t=p["sketch_tables"], b=p["sketch_buckets"],
              seed=p["sketch_seed"])
    ref = sketch_reference.peel(src, dst, n, **kw)
    print(f"[sketch_solves] reference: {ref['passes']} passes, alive edges "
          f"{ref['edges']}, alive nodes {ref['nodes']}, best density "
          f"{ref['density']!r}", file=sys.stderr, flush=True)
    ctx.window["pass_bytes_per_call"] = sketch_reference.sketch_pass_bytes(
        ref["edges"], ref["nodes"], kw["t"], kw["b"])
    answers = ctx.window["answers"]
    if control:
        low = sketch_reference.peel(src, dst, n, dtype=sketch_reference.BF16, **kw)
        answers = [{
            "best": low["best"][None],
            "density": np.asarray([low["density"]]),
            "passes": np.asarray([low["passes"]]),
        }]
    return solves.compare(answers, [ref])


def _sub_scope(framework_name):
    """The sketch scope an op's framework name lies under inside the pass
    body's ``degree_count`` phase, or None."""
    if spans.phase_of(framework_name) != "degree_count":
        return None
    parts = [spans._unwrap(p) for p in framework_name.split(":", 1)[0].split("/")]
    after = parts[parts.index("degree_count") + 1:]
    return next((s for s in SCOPES if s in after), None)


def scope_seconds(ctx, scope: str):
    """Device seconds per call of the ops under ``degree_count/<scope>``
    inside the traced window, averaged over the device planes; container
    ops (``while``, ``conditional``, ``call``) are left out, as the
    ``pass.*`` readers leave them out.  None when no op carries the scope
    (a program without the sketch's scopes)."""
    log_dir = spans.trace_dir(ctx)
    window = spans.read(log_dir)["window"]
    if window is None or not ctx.window.get("attempted"):
        return None
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    with open(path, "rb") as f:
        planes = spans._device_planes(f.read())
    if not planes:
        return None
    w0, w1 = window[0], window[0] + window[1]
    total, found = 0.0, False
    for ops in planes.values():
        for hlo, fw, start, dur in ops:
            if spans.is_container(hlo) or _sub_scope(fw) != scope:
                continue
            found = True
            total += max(0.0, min(start + dur, w1) - max(start, w0))
    if not found:
        return None
    return total / len(planes) / 1e9 / ctx.window["attempted"]
