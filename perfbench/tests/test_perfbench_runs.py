"""Whole runs of each cell on the CPU at a tiny size: a sound run is
correct, the bfloat16 control is not, and a run with the timed path broken
underneath comes out not correct, once for each fault the cells can have.

(The exchange between chips cannot be left out: every cell runs on one
chip and its path has no collective.)"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.tests import tiny
from repro.core import api, engine

CELLS = ["lj_peel", "lj_serve_local", "flickr_eps_sweep"]


def _keep_state(monkeypatch):
    """A pass that returns its state unchanged: it removes no node."""
    monkeypatch.setattr(
        engine.UndirectedThreshold, "removal",
        lambda self, s_alive, t_alive, deg_s, deg_t, stats: (
            jnp.zeros_like(s_alive), None),
    )


def _half_batch(monkeypatch):
    """Every other edge slot left out of the degree count, the counts of
    the rest doubled in their place."""
    count = engine.segment_degree_count

    def half(src, dst, w_alive, n_nodes):
        kept = (jnp.arange(src.shape[0]) % 2 == 0).astype(w_alive.dtype)
        return count(src, dst, 2.0 * kept * w_alive, n_nodes)

    monkeypatch.setattr(engine, "segment_degree_count", half)


def _altered_answer(monkeypatch):
    """Every answer altered where the front door produces it: its density
    one part in a thousand high and its first node flipped."""

    def alter(res):
        best = np.asarray(res.best_alive).copy()
        best[..., 0] = ~best[..., 0]
        return dataclasses.replace(
            res, best_alive=jnp.asarray(best),
            best_density=res.best_density * jnp.float32(1.001),
        )

    for name in ("solve", "solve_batch"):
        orig = getattr(api.Solver, name)
        monkeypatch.setattr(
            api.Solver, name,
            lambda self, *a, _orig=orig, **k: alter(_orig(self, *a, **k)),
        )


FAULTS = {
    "state_unchanged": _keep_state,
    "half_batch": _half_batch,
    "altered_answer": _altered_answer,
}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_control_is_not(workload):
    res = tiny.run(tiny.tiny_cell(workload), control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    control = res["control_checks"]
    assert any(c["value"] > c["limit"] for c in control.values()), control
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = tiny.run(tiny.tiny_cell(workload))
    assert not res["correct"], res["checks"]
