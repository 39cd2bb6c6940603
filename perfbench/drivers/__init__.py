"""Traffic drivers, one module per ``driver`` named in a traffic file.

Each offers ``setup(ctx)`` (build and warm up; set-up time),
``window(ctx)`` (the measured window, ``ctx.seconds`` long), ``end_to_end(ctx)``,
``release(ctx)`` (drop the program's state) and ``check(ctx,
control=False)`` (the numbers compared with the reference).
"""
