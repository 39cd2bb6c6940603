"""Edge slots the passes of one solve scan on the host compaction ladder's
rungs (each pass scans its rung's whole padded buffer), from the result's
``extras["compaction"]``, averaged over the window's solves."""


def read(ctx):
    ladders = [a["ladder"] for a in ctx.window.get("answers", []) if a.get("ladder")]
    if not ladders:
        return None
    return sum(lad["edge_slots_scanned"] for lad in ladders) / len(ladders)
