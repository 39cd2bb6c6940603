"""Host seconds per solve in the host compaction ladder: the self time of
the program's ``densest.ladder.*`` spans other than ``densest.ladder.rung``
(which waits on the device), inside the traced window, over the window's
solves (``perfbench/spans.py``)."""

from perfbench import spans

WAIT = "densest.ladder.rung"


def read(ctx):
    tr = spans.read(spans.trace_dir(ctx))
    s = spans.span_seconds(
        tr, lambda name: name.startswith("densest.ladder.") and name != WAIT)
    if s is None or not ctx.window.get("attempted"):
        return None
    return s / ctx.window["attempted"]
