"""Time in, or the count of, the program's own host spans inside the
traced window.  params: ``span``, a pattern for the spans' names;
``minus`` (optional), a pattern for spans whose cover is taken out, which
makes the time a self time (a span's duration less what its children
cover); ``inside`` (optional), a pattern for an enclosing span that a
counted span has to lie in; ``stat``, ``ms`` or ``count``; ``per``,
``call`` or ``unit``.  A span cut by the window's edge counts with the
part inside.  Nothing where no span matches, so that a program without
the spans reads as silent and never as 0."""

import re

from benchmark import reduce_trace


def read(ctx, params):
    tr = ctx.trace_data
    n = {"call": ctx.calls, "unit": ctx.units}[params["per"]]
    if tr is None or not n:
        return None
    stat = params["stat"]
    if stat not in ("ms", "count"):
        raise ValueError(f"span_time: stat is 'ms' or 'count', not {stat!r}")
    mine = _matching(tr, params["span"])
    if params.get("inside"):
        outer = _matching(tr, params["inside"])
        mine = [(s, e) for s, e in mine
                if any(a <= s and e <= b for a, b in outer)]
    if not mine:
        return None
    if stat == "count":
        return len(mine) / n
    minus = _matching(tr, params["minus"]) if params.get("minus") else []
    return reduce_trace.union_ns(reduce_trace.subtract(mine, minus)) / 1e6 / n


def _matching(tr, pattern):
    """``[start, end)``, cut to the window, of the host spans whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    return reduce_trace.clip([e for e in tr.host if rx.search(e[0])],
                             tr.t0, tr.t1)
