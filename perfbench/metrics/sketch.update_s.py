"""Device seconds per front-door call in the Count-Sketch update: the ops
whose framework name lies under ``peel/.../degree_count/sketch_update``
(endpoint hashes, flat counter index and the counter scatter-add of
``repro.core.countsketch.SketchBackend``), inside the traced window,
averaged over the device planes, over the window's calls
(``perfbench/drivers/sketch_solves.py``)."""

from perfbench.drivers import sketch_solves


def read(ctx):
    return sketch_solves.scope_seconds(ctx, "sketch_update")
