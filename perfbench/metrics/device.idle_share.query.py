"""Share of the traced window in which no operation ran on the device,
in percent: 1 - busy / window, busy being the union of the device's op
intervals (``trace.reduce``)."""


def read(ctx):
    tr = ctx.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
