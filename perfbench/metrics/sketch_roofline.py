"""Share of the HBM roofline that the Count-Sketch degree backend reaches,
in percent.

The least time the chip needs for the window's sketched passes is the
bytes they must move over the HBM bandwidth of the peak table: per pass
12 B per alive edge, 8·t·b B of counters and 4·(t+1) B per alive node,
with the alive counts taken from the reference peel of the same graph
(``sketch_reference.sketch_pass_bytes``), not from the program's padded
buffer.  The share is that least time over the device time of the
``sketch_update`` and ``sketch_query`` scopes in the traced window.  It
is memory-bound: the peak table has no int32 vector peak, so the hash's
integer operations are not counted."""

from perfbench.drivers import sketch_solves


def read(ctx):
    w = ctx.window
    per_call = w.get("pass_bytes_per_call")
    if not per_call or not ctx.peaks:
        return None
    update = sketch_solves.scope_seconds(ctx, "sketch_update")
    query = sketch_solves.scope_seconds(ctx, "sketch_query")
    if update is None or query is None or update + query <= 0:
        return None
    least_s = per_call / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (update + query)
