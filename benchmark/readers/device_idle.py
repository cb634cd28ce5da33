"""Share of the traced window in which no op ran on the device: 1 minus
the union of device-op intervals over the window, averaged over the
chips."""

from benchmark import reduce_trace


def read(ctx, params):
    if ctx.trace_data is None:
        return None
    return reduce_trace.idle_pct(ctx.trace_data)
