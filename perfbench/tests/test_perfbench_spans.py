"""The program's spans and the phase of each device op, read from a profile
in the layout of a TPU trace, and the four per-layer metrics built on them."""

import os
import types

import jax
import pytest

from perfbench import harness, spans, trace

# One solve in the window, as the program traces it: ``densest.solve`` with
# its ladder spans nested on one thread, a ``densest.solve_batch`` on
# another thread, and a solve after the window.  Two chips; the ops carry
# their framework name on the op's metadata (``fusion.1``, ``fusion.4``,
# the ``while``) or on the event itself as a reference (``fusion.2``).
_XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 60000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 58000000 }
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 8000000 duration_ps: 2000000
             stats { metadata_id: 1 int64_value: 0 } }
    events { metadata_id: 6 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 7 offset_ps: 30000000 duration_ps: 1000000 }
    events { metadata_id: 8 offset_ps: 31000000 duration_ps: 9000000 }
    events { metadata_id: 5 offset_ps: 40000000 duration_ps: 1000000
             stats { metadata_id: 1 int64_value: 1 } }
    events { metadata_id: 6 offset_ps: 41000000 duration_ps: 14000000 }
    events { metadata_id: 7 offset_ps: 55000000 duration_ps: 500000 }
    events { metadata_id: 9 offset_ps: 56000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 150000000 duration_ps: 10000000 }
    events { metadata_id: 8 offset_ps: 151000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "worker" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 70000000 duration_ps: 20000000 }
    events { metadata_id: 11 offset_ps: 71000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "perfbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "perfbench.solve" } }
  event_metadata { key: 3 value { id: 3 name: "densest.solve" } }
  event_metadata { key: 4 value { id: 4 name: "densest.ladder.graph_to_host" } }
  event_metadata { key: 5 value { id: 5 name: "densest.ladder.upload" } }
  event_metadata { key: 6 value { id: 6 name: "densest.ladder.rung" } }
  event_metadata { key: 7 value { id: 7 name: "densest.ladder.readback" } }
  event_metadata { key: 8 value { id: 8 name: "densest.ladder.compact" } }
  event_metadata { key: 9 value { id: 9 name: "densest.ladder.finish" } }
  event_metadata { key: 10 value { id: 10 name: "densest.solve_batch" } }
  event_metadata { key: 11 value { id: 11 name: "PjitFunction(fn)" } }
  stat_metadata { key: 1 value { id: 1 name: "rung" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 10000
    events { metadata_id: 6 offset_ps: 0 duration_ps: 30000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 10000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 8000000
             stats { metadata_id: 1 ref_value: 3 } }
    events { metadata_id: 4 offset_ps: 15000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 17000000 duration_ps: 2000000 }
    events { metadata_id: 7 offset_ps: 20000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 85000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%while.7 = (pred[8]{0}) while(%tuple.1), condition=%cond, body=%body"
    stats { metadata_id: 1 str_value: "jit(seg)/peel/while/body/edge_filter/while" } } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1"
    stats { metadata_id: 1 str_value: "jit(seg)/peel/while/body/edge_filter/gather" } } }
  event_metadata { key: 3 value { id: 3 name: "fusion.2" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.3" } }
  event_metadata { key: 5 value { id: 5 name: "fusion.4"
    stats { metadata_id: 1 str_value: "jit(seg)/peel/edge_filter/and" } } }
  event_metadata { key: 6 value { id: 6 name: "jit_seg(7)" } }
  event_metadata { key: 7 value { id: 7 name: "gather.9"
    stats { metadata_id: 1 str_value: "jit(other)/edge_filter/gather" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3
    name: "jit(seg)/peel/while/body/degree_count/scatter-add" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 10000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1"
    stats { metadata_id: 1 str_value: "jit(seg)/peel/while/body/edge_filter/gather" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
"""

# Self seconds of the window's ladder spans other than ``rung``:
# graph_to_host 5000, upload 2000 + 1000, readback 1000 + 500, compact 9000,
# finish 3000 (ns).
LADDER_HOST_NS = 21500
# edge_filter: fusion.1 4000 + fusion.4 2000 + fusion.1 5000 of its 10000
# inside the window on TPU:0, fusion.1 3000 on TPU:1; degree_count:
# fusion.2 8000 on TPU:0.  Averaged over the two chips.
EDGE_FILTER_NS = (11000 + 3000) / 2
DEGREE_COUNT_NS = 8000 / 2


def _write(root, text, cell="lj_peel"):
    run = os.path.join(root, "trace", cell, "plugins", "profile", "run1")
    os.makedirs(run)
    with open(os.path.join(run, "host.xplane.pb"), "wb") as f:
        f.write(jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return os.path.join(root, "trace", cell)


@pytest.fixture
def recorded(tmp_path):
    return spans.read(_write(str(tmp_path), _XSPACE))


def test_spans_nest_per_thread_and_self_time_leaves_out_children(recorded):
    sp = recorded["spans"]
    assert recorded["window"] == [0, 100000]
    names = [s[0] for s in sp]
    assert all(n.startswith(spans.PREFIX) for n in names)
    assert "PjitFunction(fn)" not in names and "perfbench.solve" not in names
    roots = [i for i, s in enumerate(sp) if s[3] == -1]
    assert [sp[i][0] for i in roots] == [
        "densest.solve", "densest.solve", "densest.solve_batch"]
    first = roots[0]
    kids = [s[0] for s in sp if s[3] == first]
    assert kids[0] == "densest.ladder.graph_to_host" and len(kids) == 9
    own = spans.self_ns(sp)
    assert own[first] == 58000 - 55500
    assert own[roots[2]] == 20000
    later = [s for s in sp if s[3] == roots[1]]
    assert [s[0] for s in later] == ["densest.ladder.compact"]


def test_ops_carry_their_phase_and_containers_none(recorded):
    ops = recorded["ops"]
    assert sorted(ops) == ["/device:TPU:0", "/device:TPU:1"]
    first = ops["/device:TPU:0"]
    assert [p for p, _, _ in first] == [
        "edge_filter", "degree_count", None, "edge_filter", None, "edge_filter"]
    assert [(s, d) for _, s, d in first] == [
        (11000, 4000), (16000, 8000), (25000, 1000), (27000, 2000),
        (30000, 1000), (95000, 10000)]


def _chip_crop():
    """Rung 0 of a compacted solve traced on one TPU v5 lite (500,000
    nodes, 5,000,000 edges), cropped to the ``XLA Ops`` line with each op's
    ``tf_op`` metadata stat and the program's host spans."""
    with open(os.path.join(os.path.dirname(__file__), "fixtures", "tpu_rung.pbtxt")) as f:
        return f.read()


def test_a_chip_trace_names_every_phase_and_leaves_out_its_while(tmp_path):
    log_dir = _write(str(tmp_path), _chip_crop())
    tr = spans.read(log_dir)
    assert [(s[0], s[3]) for s in tr["spans"]] == [
        ("densest.solve", -1), ("densest.ladder.graph_to_host", 0),
        ("densest.ladder.upload", 0), ("densest.ladder.rung", 0),
        ("densest.ladder.readback", 0)]
    ops = tr["ops"]["/device:TPU:0"]
    assert {p for p, _, _ in ops} == set(spans.PHASES) | {None}
    names = [n for n, _, _ in trace.events(log_dir)["devices"]["/device:TPU:0"]]
    (loop,) = [n for n in names if spans.is_container(n)]
    assert loop.startswith("%while = ") and len(ops) == len(names) - 1
    assert spans.phase_seconds(tr, "degree_count") > spans.phase_seconds(tr, "best_set")


@pytest.mark.parametrize("chip", [False, True], ids=["layout", "chip_crop"])
def test_op_times_agree_with_the_benchmark_trace_reader(tmp_path, chip):
    log_dir = _write(str(tmp_path), _chip_crop() if chip else _XSPACE)
    ev = trace.events(log_dir)
    mine = spans.read(log_dir)["ops"]
    for plane, ops in ev["devices"].items():
        kept = [[s, d] for name, s, d in ops if not spans.is_container(name)]
        ours = [[s, d] for _, s, d in mine[plane]]
        assert len(kept) == len(ours)  # trace.events rounds down to the ns
        for a, b in zip(kept, ours):
            assert a == pytest.approx(b, abs=1)


def test_phase_of_reads_the_scope_path():
    assert spans.phase_of("jit(f)/peel/while/body/removal/and") == "removal"
    assert spans.phase_of("jit(f)/peel/while/body/best_set/edge_filter") == "best_set"
    assert spans.phase_of("jit(fn)/peel/while/body/edge_filter/gather:") == "edge_filter"
    assert spans.phase_of("jit(f)/while/body/edge_filter/gather") is None
    assert spans.phase_of("jit(fn)/vmap(peel)/while/body/edge_filter/gather:") == "edge_filter"
    assert spans.phase_of("jit(fn)/jvp(vmap(peel))/while/body/vmap(removal)/and") == "removal"
    assert spans.phase_of("jit(fn)/vmap(peel)/while/body_pred/lt") is None
    assert spans.phase_of("jit(fn)/vmap(peeled)/while/body/edge_filter/gather") is None
    assert spans.phase_of(None) is None
    assert spans.is_container("while.25") and spans.is_container("%call.3 = f32[] call()")
    assert not spans.is_container("fusion.19") and not spans.is_container("callback.1")


def _ctx(answers=None):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name="lj_peel"),
        window={"attempted": 2, "answers": answers or []},
    )


@pytest.mark.parametrize("metric,expected", [
    ("ladder.host_s", LADDER_HOST_NS / 2 / 1e9),
    ("pass.edge_filter_s", EDGE_FILTER_NS / 2 / 1e9),
    ("pass.degree_count_s", DEGREE_COUNT_NS / 2 / 1e9),
])
def test_trace_readers(tmp_path, monkeypatch, metric, expected):
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    _write(str(tmp_path), _XSPACE)
    assert harness.reader_for(metric).read(_ctx()) == pytest.approx(expected)


# The same run as the program before it had spans or scopes: no
# ``densest.*`` span and no framework name on any op.
_BARE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 60000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "perfbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "perfbench.solve" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 10000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
"""


@pytest.mark.parametrize("metric", [
    "ladder.host_s", "pass.edge_filter_s", "pass.degree_count_s"])
def test_trace_readers_find_nothing_in_a_program_without_spans(
        tmp_path, monkeypatch, metric):
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    _write(str(tmp_path), _BARE)
    assert harness.reader_for(metric).read(_ctx()) is None


def test_bytes_moved_averages_the_ladder_counters():
    read = harness.reader_for("ladder.bytes_moved").read
    answers = [{"ladder": {"bytes_to_host": 10, "bytes_to_device": 30}},
               {"ladder": {"bytes_to_host": 20, "bytes_to_device": 40}}]
    assert read(_ctx(answers)) == 50
    assert read(_ctx([{"ladder": {"host_round_trips": 5}}])) is None
    assert read(_ctx([{"ladder": None}])) is None


def test_a_compacted_solve_on_the_cpu_gives_its_ladder_host_time(tmp_path, monkeypatch):
    """The names the program really emits, captured around one solve as a
    traced run captures its window."""
    from repro.core import Problem, Solver
    from repro.graph.generators import planted_dense_subgraph

    g = planted_dense_subgraph(260, avg_deg=4, k=25, p_dense=0.8, seed=3)[0]
    solver = Solver()
    prob = Problem.undirected(eps=0.1, compaction="geometric")
    solver.solve(g, prob)
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    log_dir = os.path.join(str(tmp_path), "trace", "lj_peel")
    with trace.capture(log_dir):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("perfbench.solve"):
                res = solver.solve(g, prob)
    tr = spans.read(log_dir)
    (root,) = [i for i, s in enumerate(tr["spans"]) if s[0] == "densest.solve"]
    kids = [s[0] for s in tr["spans"] if s[3] == root]
    rungs = len(res.extras["compaction"]["segments"])
    assert kids.count("densest.ladder.rung") == rungs >= 2
    assert len(kids) == len(tr["spans"]) - 1
    host = harness.reader_for("ladder.host_s").read(_ctx())
    assert 0 < host * 2 < tr["spans"][root][2] / 1e9
    assert harness.reader_for("pass.edge_filter_s").read(_ctx()) is None


@pytest.mark.parametrize("batch", [False, True])
def test_every_phase_of_the_compiled_pass_body_is_read(batch):
    """The op names of the programs a solve and an eps sweep run, as the
    compiler keeps them, each give all four phases."""
    import re

    from repro.core import engine
    from repro.graph.generators import planted_dense_subgraph

    g = planted_dense_subgraph(260, avg_deg=4, k=25, p_dense=0.8, seed=3)[0]

    def cell(edges, eps):
        return engine.run_peel(edges, engine.UndirectedThreshold(eps),
                               engine.ExactBackend(), 32)

    if batch:
        f = jax.jit(lambda e, v: jax.vmap(lambda x: cell(e, x))(v))
        args = (g, jax.numpy.asarray([0.25, 0.5, 1.0]))
    else:
        f, args = jax.jit(lambda e: cell(e, 0.5)), (g,)
    names = re.findall(r'op_name="([^"]*)"', f.lower(*args).compile().as_text())
    assert {spans.phase_of(n) for n in names} >= set(spans.PHASES)
