"""Share of the traced window in which a collective runs on a device and
no compute op does, on the worst device.  Nothing where no collective
ran."""

from benchmark import reduce_trace


def read(ctx, params):
    if ctx.trace_data is None:
        return None
    return reduce_trace.exposed_collective_pct(ctx.trace_data)
