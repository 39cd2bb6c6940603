"""CSR slots the local explorer (``core/local.py``) scans per query over
the window (engine ``local_edges_scanned`` / queries answered)."""


def read(ctx):
    c = ctx.window.get("counters")
    if not c or not c["queries_answered"]:
        return None
    return c["local_edges_scanned"] / c["queries_answered"]
