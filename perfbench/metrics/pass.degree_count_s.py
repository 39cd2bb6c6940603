"""Device seconds per front-door call in the pass body's ``degree_count``
phase: the ops whose framework name lies under ``peel/.../degree_count``
(``repro.core.engine.run_peel``), inside the traced window, averaged over
the device planes, over the window's calls (``perfbench/spans.py``)."""

from perfbench import spans


def read(ctx):
    s = spans.phase_seconds(spans.read(spans.trace_dir(ctx)), "degree_count")
    if s is None or not ctx.window.get("attempted"):
        return None
    return s / ctx.window["attempted"]
