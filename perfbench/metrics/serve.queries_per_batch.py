"""Queries per flushed batch of the serving engine over the window
(``DensestQueryEngine.stats()``: queries_answered / batches_flushed)."""


def read(ctx):
    c = ctx.window.get("counters")
    if not c or not c["batches_flushed"]:
        return None
    return c["queries_answered"] / c["batches_flushed"]
