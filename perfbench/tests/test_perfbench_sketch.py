"""The ``lj_sketch_peel`` cell on the CPU at a tiny size: a sound run is
correct, the bfloat16 control is not, a run with the timed path broken
underneath is not, and the four ``sketch.*`` readers read what the
program emits."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness, spans
from perfbench.tests import tiny
from perfbench.tests.test_perfbench_runs import _altered_answer, _keep_state
from repro.core import api, countsketch

CELL = "lj_sketch_peel"


def _half_slots(monkeypatch):
    """Every other edge slot left out of the sketch update, the updates of
    the rest doubled in their place."""
    update = countsketch.sketch_endpoint_counters

    def half(p, ids, w_alive):
        kept = (jnp.arange(ids.shape[0]) % 2 == 0).astype(w_alive.dtype)
        return update(p, ids, 2.0 * kept * w_alive)

    monkeypatch.setattr(countsketch, "sketch_endpoint_counters", half)


FAULTS = {
    "state_unchanged": _keep_state,
    "half_slots": _half_slots,
    "altered_answer": _altered_answer,
}


def test_sound_run_is_correct_and_control_is_not():
    res = tiny.run(tiny.tiny_cell(CELL), control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    control = res["control_checks"]
    assert any(c["value"] > c["limit"] for c in control.values()), control


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = tiny.run(tiny.tiny_cell(CELL))
    assert not res["correct"], res["checks"]


@pytest.fixture(scope="module")
def window_ctx():
    """A tiny window of the cell run step by step, as ``harness.run_cell``
    runs it, so that its context stays at hand."""
    cell = tiny.tiny_cell(CELL)
    ctx = harness.Context(cell=cell, seed=5, seconds=0.3, persistent_cache=False)
    ctx.graph = harness.make_graph(cell.config, ctx.seed)
    driver = harness.driver_for(cell)
    ctx.state = driver.setup(ctx)
    ctx.window = driver.window(ctx)
    driver.release(ctx)
    checks = driver.check(ctx)
    assert all(v == 0 for v in checks.values()), checks
    return ctx


def test_slots_hashed_reads_every_solve_of_the_window(window_ctx):
    w = window_ctx.window
    m = window_ctx.graph.n_edges_padded
    want = [int(a["passes"]) * 2 * 5 * m for a in w["answers"]]
    assert [s["slots_hashed"] for s in w["sketch"]] == want
    read = harness.reader_for("sketch.slots_hashed").read
    assert read(window_ctx) == sum(want) / len(want)
    assert w["pass_bytes_per_call"] > 8 * 5 * (1 << 18)


def _program_op_names(ctx):
    """The framework names of the ops of the program the cell runs, as the
    compiler keeps them."""
    prob = api.Problem(**ctx.cell.config["problem"]).resolve(ctx.graph.n_nodes)
    mp = prob.resolved_max_passes(ctx.graph.n_nodes)
    fn = jax.jit(lambda e: api.run_cell(e, prob, max_passes=mp))
    return sorted(set(re.findall(r'op_name="([^"]*)"',
                                 fn.lower(ctx.graph).compile().as_text())))


def _xspace(ops):
    """A profile in the layout of a TPU trace: the window span on the host
    from 0 to 1 ms, and ``ops`` ``(hlo name, framework name, start_ns,
    dur_ns)`` on one chip's ``XLA Ops`` line."""
    events, meta = [], []
    for i, (hlo, fw, start, dur) in enumerate(ops, 1):
        events.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000} "
                      f"duration_ps: {dur * 1000} }}")
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{hlo}" '
                    f'stats {{ metadata_id: 1 str_value: "{fw}" }} }} }}')
    return f"""
planes {{
  id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "perfbench.window" }} }}
}}
planes {{
  id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {" ".join(events)}
  }}
  {" ".join(meta)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
"""


def _write_trace(ctx, text):
    run = os.path.join(spans.trace_dir(ctx), "plugins", "profile", "r")
    os.makedirs(run, exist_ok=True)
    with open(os.path.join(run, "host.xplane.pb"), "wb") as f:
        f.write(jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))


def test_trace_readers_read_the_programs_scopes(window_ctx, tmp_path, monkeypatch):
    """Every op name of the cell's program on the chip's timeline, op ``i``
    lasting ``i + 1`` ns; a ``while`` carrying a sketch scope and an op
    after the window are left out."""
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    names = _program_op_names(window_ctx)
    ops, want = [], {"sketch_update": 0, "sketch_query": 0}
    for i, fw in enumerate(names):
        ops.append((f"fusion.{i}", f"{fw}:fusion", 1000 * i, i + 1))
        for scope in want:
            if f"degree_count/{scope}/" in fw:
                want[scope] += i + 1
    assert want["sketch_update"] and want["sketch_query"]
    update = next(n for n in names if "degree_count/sketch_update/" in n)
    ops.append(("%while.9 = (s32[]) while(%t)", update, 0, 500000))
    ops.append(("fusion.late", update, 2000000, 7))
    _write_trace(window_ctx, _xspace(ops))
    calls = window_ctx.window["attempted"]
    read = {m: harness.reader_for(m).read for m in
            ("sketch.update_s", "sketch.query_s", "sketch_roofline")}
    update_s = want["sketch_update"] / 1e9 / calls
    query_s = want["sketch_query"] / 1e9 / calls
    assert read["sketch.update_s"](window_ctx) == pytest.approx(update_s)
    assert read["sketch.query_s"](window_ctx) == pytest.approx(query_s)
    assert read["sketch_roofline"](window_ctx) is None  # no peak table on the CPU
    monkeypatch.setattr(window_ctx, "peaks", harness.peaks_for("TPU v5 lite"))
    least = window_ctx.window["pass_bytes_per_call"] / 819e9
    assert read["sketch_roofline"](window_ctx) == pytest.approx(
        100.0 * least / (update_s + query_s))


def test_readers_find_nothing_in_a_program_without_the_sketch(
        window_ctx, tmp_path, monkeypatch):
    """The parent of the sketch's spans and counters: no scope under
    ``degree_count`` and no ``extras["sketch"]``."""
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    bare = [("fusion.1", "jit(fn)/peel/while/body/degree_count/scatter-add:x", 0, 5),
            ("fusion.2", "jit(fn)/peel/while/body/removal/and:x", 10, 5)]
    _write_trace(window_ctx, _xspace(bare))
    monkeypatch.setattr(window_ctx, "peaks", harness.peaks_for("TPU v5 lite"))
    monkeypatch.setitem(window_ctx.window, "sketch",
                        [None] * len(window_ctx.window["answers"]))
    for metric in ("sketch.update_s", "sketch.query_s", "sketch_roofline",
                   "sketch.slots_hashed"):
        assert harness.reader_for(metric).read(window_ctx) is None, metric
