"""Idle time of the device, by what the program was doing.  The idle gaps
of the first device inside the traced window (the gaps
``reduce_trace.idle_gaps`` names by host frame), split by overlap among
the program's own spans (the names that start with ``dslib.``): each
moment of a gap goes to the innermost such span that covers it, whatever
Python frame lies deeper, so a gap that runs over several spans is shared
out among them and what lies under no span goes to none.  The value is
the idle time, in ms, whose span matches ``span``, over ``per`` (``call``
or ``unit``).  0.0 where the spans are there and no idle falls in them;
nothing where the window holds no span matching ``span``."""

import itertools
import re

from benchmark import reduce_trace


def read(ctx, params):
    tr = ctx.trace_data
    n = {"call": ctx.calls, "unit": ctx.units}[params["per"]]
    if tr is None or not n or not tr.ops:
        return None
    want = re.compile(params["span"])
    # outermost first: of two that start together the longer is the outer
    spans = sorted((e for e in tr.host if e[0].startswith("dslib.")),
                   key=lambda e: (e[1], -e[2]))
    own, found = [], False      # the moments whose innermost span matches
    for i, (name, s, d) in enumerate(spans):
        mine = reduce_trace.clip([(name, s, d)], tr.t0, tr.t1)
        if not mine or not want.search(name):
            continue
        found = True
        deeper = itertools.takewhile(lambda e: e[1] < s + d,
                                     itertools.islice(spans, i + 1, None))
        own += reduce_trace.subtract(mine, [(a, a + b) for _, a, b in deeper])
    if not found:
        return None
    dev = sorted(tr.ops)[0]
    busy = reduce_trace.clip(tr.ops[dev] + tr.async_ops.get(dev, []),
                             tr.t0, tr.t1)
    return reduce_trace.union_ns(reduce_trace.subtract(own, busy)) / 1e6 / n
