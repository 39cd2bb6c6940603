"""Endpoint-table updates per solve of the Count-Sketch degree backend:
``extras["sketch"]["slots_hashed"]`` of each of the window's solves
(passes × 2 endpoints × t tables × padded edge slots), averaged."""


def read(ctx):
    counters = ctx.window.get("sketch")
    if not counters or any(c is None for c in counters):
        return None
    return sum(c["slots_hashed"] for c in counters) / len(counters)
