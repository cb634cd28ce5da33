"""Dispatches of library kernels (``profiling.dispatch_count()``) over
the window, per unit of work completed."""


def read(ctx, params):
    if not ctx.units:
        return None
    return ctx.dispatches / ctx.units
