"""A kernel's share of its roofline where the kernel is the ops under a
scope of the program: the least time the chip could take for one unit's
counted operations and bytes (``flops`` and ``bytes`` name functions of
``counts.py``), over the device time per unit of the ops whose scope
chain matches ``scope`` (``scope_time``'s ``ms``, a chip's mean).
Nothing where no op matches."""

from benchmark import counts
from benchmark.readers import scope_time


def read(ctx, params):
    ms = scope_time.read(ctx, {"scope": params["scope"],
                               "not": params.get("not"), "per": "unit",
                               "stat": "ms"})
    if ms is None:
        return None
    least, _bound = counts.least_seconds(
        counts.work(params["flops"], ctx.config),
        counts.work(params["bytes"], ctx.config), ctx.peaks_row, ctx.chips)
    return counts.share_pct(least, ms / 1e3)
