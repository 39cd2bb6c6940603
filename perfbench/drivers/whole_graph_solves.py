"""Whole-graph solves, back to back, from one caller: the paper's core job.

Each call is ``solve(graph, Problem(**config["problem"]))`` on the
device-resident graph, ending with the answer on the host.  Set-up warms
up with one solve, which builds every rung program of the compaction
ladder the graph's survivor counts lead to.
"""

from __future__ import annotations

from perfbench.drivers import solves
from repro.core import Solver


def setup(ctx):
    solver = Solver()
    prob = solves.problem(ctx.cell.config)
    call = lambda: solver.solve(ctx.graph, prob)  # noqa: E731
    solves.host_answer(call())
    return {"call": call, "eps": prob.eps}


def window(ctx) -> dict:
    return solves.closed_loop(ctx.state["call"], ctx.seconds, "perfbench.solve")


def end_to_end(ctx) -> dict:
    return {"solve_s": solves.solve_s(ctx)}


def release(ctx) -> None:
    ctx.state = {"eps": ctx.state["eps"]}


def check(ctx, control: bool = False) -> dict:
    return solves.check(ctx, [ctx.state["eps"]], control)
