"""An eps sweep, back to back, from one caller: one call is
``solve_batch(graph, Problem(**config["problem"]), eps=traffic["eps"])``,
all lanes in one vmapped program, ending with every lane's answer on the
host.  Set-up warms up with one sweep."""

from __future__ import annotations

from perfbench.drivers import solves
from repro.core import Solver


def setup(ctx):
    solver = Solver()
    prob = solves.problem(ctx.cell.config)
    grid = [float(e) for e in ctx.cell.traffic["eps"]]
    call = lambda: solver.solve_batch(ctx.graph, prob, eps=grid)  # noqa: E731
    solves.host_answer(call())
    return {"call": call, "eps": grid}


def window(ctx) -> dict:
    return solves.closed_loop(ctx.state["call"], ctx.seconds, "perfbench.solve_batch")


def end_to_end(ctx) -> dict:
    return {"solve_s": solves.solve_s(ctx)}


def release(ctx) -> None:
    ctx.state = {"eps": ctx.state["eps"]}


def check(ctx, control: bool = False) -> dict:
    return solves.check(ctx, ctx.state["eps"], control)
