"""The reduction from a recorded trace to busy time, idle gaps and the
breakdown, on a small recorded trace kept as a fixture."""

import json
import os

import pytest

from perfbench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace_small.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def _window(ev):
    (w,) = [s for s in ev["spans"] if s[0] == trace.WINDOW_SPAN]
    return w[1], w[1] + w[2]


def _busy_by_sweep(ops, w0, w1):
    """Busy ns by a plain sweep over every op endpoint: a stretch between
    two endpoints counts when some op covers its middle."""
    cuts = sorted({w0, w1} | {x for _, s, d in ops for x in (s, s + d) if w0 < x < w1})
    busy = 0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(s <= mid <= s + d for _, s, d in ops):
            busy += b - a
    return busy


def test_window_and_busy_time(recorded):
    w0, w1 = _window(recorded)
    r = trace.reduce(recorded)
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    per_device = [_busy_by_sweep(ops, w0, w1) for ops in recorded["devices"].values()]
    assert r["busy_s"] == pytest.approx(sum(per_device) / len(per_device) / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]


def test_idle_gaps_are_the_longest_stretches_without_an_op(recorded):
    w0, w1 = _window(recorded)
    r = trace.reduce(recorded)
    first = recorded["devices"][sorted(recorded["devices"])[0]]
    idle = (w1 - w0) - _busy_by_sweep(first, w0, w1)
    gaps = [g[1] for g in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= trace.TOP
    assert sum(gaps) * 1e9 <= idle + 1
    names = {s[0] for s in recorded["spans"]} | {"between calls"}
    assert {g[0] for g in r["idle_gaps"]} <= names - {trace.WINDOW_SPAN}


def test_device_ops_are_summed_per_name(recorded):
    w0, w1 = _window(recorded)
    r = trace.reduce(recorded)
    totals = {}
    for ops in recorded["devices"].values():
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                totals[name] = totals.get(name, 0) + (b - a)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[: trace.TOP]
    assert [n for n, _ in r["device_ops"]] == [n for n, _ in top]
    assert [s for _, s in r["device_ops"]] == pytest.approx([ns / 1e9 for _, ns in top])


def test_a_trace_without_its_window_or_device_is_refused(recorded):
    no_window = dict(recorded, spans=[s for s in recorded["spans"]
                                      if s[0] != trace.WINDOW_SPAN])
    with pytest.raises(RuntimeError):
        trace.reduce(no_window)
    with pytest.raises(RuntimeError):
        trace.reduce(dict(recorded, devices={}))


# A profile in the layout of a TPU trace: one plane per chip named
# "/device:TPU:<n>" whose "XLA Ops" line holds the ops (each with its
# "hlo_module" stat), other lines and planes beside it, and the host's
# spans on a "/host:" plane.
_XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000
             stats { metadata_id: 1 ref_value: 2 } }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "jit_sweep" } }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "jit_peel(1)" } }
  event_metadata { key: 3 value { id: 3 name: "scatter.2" } }
  stat_metadata { key: 1 value { id: 1 name: "hlo_module" } }
  stat_metadata { key: 2 value { id: 2 name: "jit_peel" } }
}
planes {
  id: 2 name: "/device:TPU_SYSTEM:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "not.a.chip" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 400000 duration_ps: 7000000 }
    events { metadata_id: 3 offset_ps: 400000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "perfbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "perfbench.solve" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(peel)" } }
}
"""


def test_events_reads_the_tpu_plane_layout(tmp_path):
    import jax

    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    ev = trace.events(str(tmp_path))
    assert ev["devices"] == {"/device:TPU:0": [
        ["jit_peel/fusion.1", 1000, 5000], ["jit_sweep/scatter.2", 7000, 1000]]}
    assert sorted(ev["spans"]) == [["perfbench.solve", 900, 7000],
                                   ["perfbench.window", 500, 20000]]
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(20e-6)
    assert r["busy_s"] == pytest.approx(6e-6)
    assert r["idle_gaps"][0] == ["between calls", pytest.approx(12.5e-6)]
