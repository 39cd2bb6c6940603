"""One run of one benchmark cell, driven by the data in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is found by its name:

* ``BENCHMARK.json`` ``configs[].file``: the configuration (graph and
  problem), ``perfbench/configs/<config>.json``;
* ``perfbench/traffic/<traffic>.json``: the mix's parameters, whose
  ``driver`` names the module ``perfbench/drivers/<driver>.py`` that
  offers it;
* ``perfbench/cells/<workload>.json``: the cell's correctness limits;
* ``perfbench/metrics/<metric>.py``: the reader of a per-layer metric;
* ``perfbench/pending/<workload>.json``: the ``BENCHMARK.json`` entries of
  a cell that is prepared but not yet in the benchmark, because what it
  needs from the chip has not been measured (``PERF.md`` section 7).  The
  rate sweep, the control and the tests run it; ``run.py`` does not.

A run makes the graph on the device from the seed, lets the driver warm
up every shape its traffic uses (all of it ``setup_s``), measures one
window, reads the peak device memory, frees the program's state, and then
holds the window's answers against the plain reference
(``perfbench/reference.py``).  Its last line of standard output is one JSON
object; the numbers compared, each beside its limit, close both that line
(``checks``) and standard error.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import importlib
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
PENDING_DIR = os.path.join(BENCH_DIR, "pending")
# Fixed scratch inside the checkout: traces and the serving program cache.
WORK_DIR = os.path.join(ROOT, ".perfbench")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def with_pending(bench: dict) -> dict:
    """``bench`` with the entries of the pending cells added."""
    out = dict(bench)
    for path in sorted(glob.glob(os.path.join(PENDING_DIR, "*.json"))):
        extra = _json(path)
        for key in ("workloads", "end_to_end", "per_layer"):
            have = {e["name"] for e in out[key]}
            out[key] = out[key] + [e for e in extra[key] if e["name"] not in have]
    return out


@dataclasses.dataclass
class Cell:
    """Everything one cell's run reads, resolved from the benchmark."""

    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def resolve(bench: dict, workload: str, root: str = ROOT) -> Cell:
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = entries[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [
        m for m in bench["end_to_end"]
        if "workloads" not in m or workload in m["workloads"]
    ]
    reported = {m["name"] for m in e2e}

    def reads_here(m):
        if "workloads" in m:
            return workload in m["workloads"]
        return m["moves"] in reported

    layer = [m for m in bench["per_layer"] if reads_here(m)]
    return Cell(
        name=workload,
        workload=w,
        config=_json(os.path.join(root, cfg["file"])),
        traffic=_json(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")),
        limits=_json(os.path.join(BENCH_DIR, "cells", workload + ".json"))["limits"],
        end_to_end=e2e,
        per_layer=layer,
    )


def driver_for(cell: Cell):
    return importlib.import_module(f"perfbench.drivers.{cell.traffic['driver']}")


def reader_for(metric: str):
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in perfbench/peaks.json")
    return table[kind]


def make_graph(config: dict, seed: int):
    """The configuration's graph as a device-resident EdgeList."""
    import jax.numpy as jnp

    from perfbench import graphgen
    from repro.graph.edgelist import EdgeList

    g = config["graph"]
    src, dst = graphgen.generate(g["n_nodes"], g["n_edges"], g["exponent"], seed)
    m = g["n_edges"]
    return EdgeList(
        src=src, dst=dst, weight=jnp.ones((m,), jnp.float32),
        mask=jnp.ones((m,), bool), n_nodes=g["n_nodes"],
    )


@dataclasses.dataclass
class Context:
    """What a driver and a metric reader see of the run."""

    cell: Cell
    seed: int
    seconds: float
    persistent_cache: bool = True
    graph: Any = None
    state: Any = None
    window: Optional[dict] = None
    trace: Optional[dict] = None
    peaks: Optional[dict] = None


def _check(readings: Dict[str, float], limits: dict) -> Dict[str, dict]:
    missing = set(readings) ^ set(limits)
    if missing:
        raise KeyError(f"readings and limits differ in {sorted(missing)}")
    return {k: {"value": readings[k], "limit": limits[k]} for k in readings}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             *, require_chip: bool = True, control: bool = False,
             persistent_cache: bool = True) -> dict:
    """One run; returns the result object (``control=True`` adds the
    control's readings under ``control_checks``).  Tests pass
    ``require_chip=False, persistent_cache=False`` to drive a run on the
    CPU without touching the checkout's compilation cache."""
    import jax

    from perfbench import trace as trace_mod
    from perfbench.compile_meter import CompileMeter
    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    chips = int(cell.workload["chips"])
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(
            f"cell {cell.name} needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)"
        )
    if persistent_cache:
        enable_compile_cache()
    meter = CompileMeter()
    driver = driver_for(cell)
    ctx = Context(cell=cell, seed=seed, seconds=seconds,
                  persistent_cache=persistent_cache)
    ctx.graph = make_graph(cell.config, seed)
    ctx.state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    c0 = meter.snapshot()

    log_dir = os.path.join(WORK_DIR, "trace", cell.name)
    if trace:
        with trace_mod.capture(log_dir):
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                ctx.window = driver.window(ctx)
    else:
        ctx.window = driver.window(ctx)
    c1 = meter.snapshot()
    used = devices[:chips]
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used
    )
    e2e = driver.end_to_end(ctx)
    e2e["setup_s"] = setup_s
    driver.release(ctx)

    readings = driver.check(ctx)
    checks = _check(readings, cell.limits)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(ctx.window["attempted"]),
        "failed": int(ctx.window["failed"]),
    }
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        ctx.trace = trace_mod.reduce(trace_mod.events(log_dir))
        ctx.peaks = peaks_for(devices[0].device_kind) if require_chip else None
        values = {}
        for m in cell.per_layer:
            v = reader_for(m["name"]).read(ctx)
            if v is not None:
                values[m["name"]] = v
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
    else:
        values = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}
    result["metrics"] = {
        k: {"value": float(v), "unit": units[k]} for k, v in values.items()
    }
    result["device"] = device
    if trace:
        result["breakdown"] = {
            "device_ops": ctx.trace["device_ops"],
            "idle_gaps": ctx.trace["idle_gaps"],
        }
    result["compiles_in_window"] = c1[1] - c0[1]
    result["cache_loads_in_window"] = c1[2] - c0[2]
    result["setup_compile_s"] = c0[0]
    if control:
        result["control_checks"] = _check(driver.check(ctx, control=True), cell.limits)
    result["checks"] = checks
    return result


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Runs one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = resolve(load_benchmark(), args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 3
    print(
        f"[{cell.name}] compiles in window: {result['compiles_in_window']}, "
        f"cache loads in window: {result['cache_loads_in_window']}, "
        f"compile seconds in set-up: {result['setup_compile_s']:.3f}",
        flush=True,
    )
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    return 0
