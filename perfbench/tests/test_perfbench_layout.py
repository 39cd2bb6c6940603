"""``BENCHMARK.json`` and the files it names: every cell resolves to an
existing configuration, traffic mix, driver, limits and metric readers,
and the file keeps the benchmark contract's shape."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from perfbench import harness
from perfbench.drivers import open_loop_queries

BENCH = harness.load_benchmark()
ALL = harness.with_pending(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
PENDING = [w["name"] for w in ALL["workloads"] if w["name"] not in WORKLOADS]


def test_top_level_keys_and_command():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("bench", [BENCH, ALL], ids=["committed", "with_pending"])
def test_names_units_and_keys(bench):
    workloads = [w["name"] for w in bench["workloads"]]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", workloads)) <= set(workloads)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(workloads)


@pytest.mark.parametrize("workload", PENDING)
def test_a_pending_cell_is_not_in_the_benchmark(workload):
    with pytest.raises(KeyError):
        harness.resolve(BENCH, workload)
    assert harness.resolve(ALL, workload).workload["name"] == workload


@pytest.mark.parametrize("workload", WORKLOADS + PENDING)
def test_cell_resolves_to_its_files(workload):
    cell = harness.resolve(ALL, workload)
    assert cell.config["name"] == cell.workload["config"]
    assert cell.config["reduced"] == []
    driver = harness.driver_for(cell)
    for fn in ("setup", "window", "end_to_end", "release", "check"):
        assert callable(getattr(driver, fn))
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.reader_for(m["name"]).read)


def test_peak_table_has_the_v5e_and_refuses_others():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")


def test_open_loop_schedule_repeats_per_seed():
    nodes = np.arange(10, 5000)
    a = open_loop_queries.schedule(123, 400, 5.0, nodes, 1.0)
    b = open_loop_queries.schedule(123, 400, 5.0, nodes, 1.0)
    c = open_loop_queries.schedule(124, 400, 5.0, nodes, 1.0)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[1], c[1])
    assert len(a[0]) == 400 and (np.diff(a[0]) >= 0).all()
    assert a[0].min() >= 0 and a[0].max() < 5.0 and np.isin(a[1], nodes).all()
    big = open_loop_queries.schedule(2**33 + 5, 400, 5.0, nodes, 1.0)
    assert len(big[1]) == 400


def _run(cwd, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run(harness.ROOT, "--workload", "lj_peel", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no chip" in p.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and perfbench/ cannot run."""
    import shutil

    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", "lj_peel", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and "{" not in p.stdout
