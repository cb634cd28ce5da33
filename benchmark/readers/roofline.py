"""A kernel's share of its roofline: the least time the chips could take
for one unit's counted operations and bytes, over the device time the
trace shows for it.  params: ``flops`` and ``bytes`` name functions of
``counts.py``; ``op`` and ``exclude`` are patterns for the ops' text as
the compiler names them, over every program the window ran (no name of
a program is matched: that is the program's to change).  The time
is summed per chip and averaged over the chips; the work is the whole
unit's, divided over the chips."""

from benchmark import counts, reduce_trace


def read(ctx, params):
    if ctx.trace_data is None or not ctx.units:
        return None
    seconds = reduce_trace.op_seconds(ctx.trace_data, params.get("op"),
                                      params.get("exclude"))
    if seconds is None:
        return None
    least, _bound = counts.least_seconds(
        counts.work(params["flops"], ctx.config),
        counts.work(params["bytes"], ctx.config), ctx.peaks_row, ctx.chips)
    return counts.share_pct(least, seconds / ctx.units)
