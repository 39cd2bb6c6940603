"""Bytes the host compaction ladder copies between host and device per
solve: ``bytes_to_host + bytes_to_device`` of the result's
``extras["compaction"]``, averaged over the window's solves."""


def read(ctx):
    ladders = [a["ladder"] for a in ctx.window.get("answers", []) if a.get("ladder")]
    if not ladders or any("bytes_to_host" not in lad for lad in ladders):
        return None
    moved = [lad["bytes_to_host"] + lad["bytes_to_device"] for lad in ladders]
    return sum(moved) / len(moved)
