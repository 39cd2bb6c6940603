"""Host round trips per solve of the host compaction ladder
(``Solver._run_compacted``): one per rung, from the result's
``extras["compaction"]``, averaged over the window's solves."""


def read(ctx):
    ladders = [a["ladder"] for a in ctx.window.get("answers", []) if a.get("ladder")]
    if not ladders:
        return None
    return sum(lad["host_round_trips"] for lad in ladders) / len(ladders)
