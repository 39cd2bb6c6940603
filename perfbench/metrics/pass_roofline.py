"""Share of the HBM roofline that the peel passes reach, in percent.

The least time the chip needs for the window's passes is the bytes the
algorithm must move, over the HBM bandwidth of the peak table: per pass
12 B per alive edge and 8 B per alive node, with the alive counts taken
from the reference peel of the same graph (``reference.pass_bytes``), not
from the program's padded buffers.  The passes are memory-bound (no
arithmetic to speak of), so the HBM bound is the roofline.  The share is
that least time over the device's busy time in the traced window."""


def read(ctx):
    w, tr = ctx.window, ctx.trace
    per_call = w.get("pass_bytes_per_call")
    if not per_call or not ctx.peaks or not tr or tr["busy_s"] <= 0:
        return None
    least_s = w["attempted"] * per_call / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["busy_s"]
