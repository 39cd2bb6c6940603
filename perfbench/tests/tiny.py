"""A benchmark cell cut to a tiny graph, and one CPU run of it."""

from __future__ import annotations

import copy
import dataclasses
import time

from perfbench import harness

TINY = {"n_nodes": 3000, "n_edges": 15000}
TINY_TRAFFIC = {"lj_serve_local": {"rate_qps": 60, "drain_s": 20}}


def tiny_cell(workload: str) -> harness.Cell:
    cell = harness.resolve(harness.with_pending(harness.load_benchmark()), workload)
    config = copy.deepcopy(cell.config)
    config["graph"].update(TINY)
    traffic = dict(cell.traffic, **TINY_TRAFFIC.get(workload, {}))
    return dataclasses.replace(cell, config=config, traffic=traffic)


def run(cell: harness.Cell, seed: int = 5, seconds: float = 0.3,
        control: bool = False) -> dict:
    """One run on the CPU: no look for a chip, no persistent cache."""
    return harness.run_cell(
        cell, seed, seconds, False, time.perf_counter(),
        require_chip=False, persistent_cache=False, control=control,
    )
