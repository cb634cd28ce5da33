"""The whole step's share of the chip's peak: counted work of all the
units the window completed, over the window's seconds, the cell's chips
and the published peak.  The work is the configuration's own: its
``work.flops`` names a function of ``counts.py`` (a metric's ``params``
may name another under ``flops``), so the same metric reads in every cell
that reports the rate it moves."""

from benchmark import counts


def read(ctx, params):
    if not ctx.units or ctx.window_s <= 0:
        return None
    name = params.get("flops") or ctx.config["work"]["flops"]
    flops = counts.work(name, ctx.config) * ctx.units
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks_row["flops_per_s"])
