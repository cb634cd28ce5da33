"""From a profiler trace to numbers: busy union, idle share, per-op sums,
the share of exposed collectives, and the breakdown the result line
carries.

The arithmetic works on plain tuples ``(name, start_ns, duration_ns)`` so
that ``tests/benchmark/test_reduce_trace.py`` checks it on hand-made
lists; :func:`load` is the only function that touches the profiler's
``.xplane.pb`` file.  Which planes are devices, which line holds the ops
and how an op's short name is cut out of its text are patterns in
``peaks.json`` under the device's ``trace`` key.
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
_COLLECTIVES = ("all-reduce|all-gather|all-to-all|reduce-scatter|"
                "collective-permute|collective-broadcast")
# a collective by its opcode: where the op's text is known, the word between
# the result's shape and the operands (an operand may be *named* all-reduce);
# else by the op's own name
COLLECTIVE_RE = re.compile(
    rf"[\]}})]\s({_COLLECTIVES})(-start|-done)?\(|^({_COLLECTIVES})")


@dataclass
class Trace:
    """Events of one traced window, already cut to it.

    ``ops`` and ``async_ops``: per device, ``(name, start, dur)`` of the
    ops that ran there (control-flow containers left out: a ``while``
    spans its body and would hide every gap inside it).  ``host``: the
    host's spans (the benchmark's own annotations and the Python
    tracer's frames).  ``text``: an op's name to its whole text as the
    profiler gives it (opcode, fusion kind), which patterns are matched
    against where it is known.  ``t0``/``t1``: the window, in the trace's
    ns."""

    ops: dict = field(default_factory=dict)
    text: dict = field(default_factory=dict)
    async_ops: dict = field(default_factory=dict)
    host: list = field(default_factory=list)
    t0: float = 0.0
    t1: float = 0.0

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9


# -- interval arithmetic ----------------------------------------------------

def merge(intervals):
    """Sorted, disjoint ``[start, end)`` pairs covering the same points."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def union_ns(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def clip(events, t0, t1):
    """``[start, end)`` of each event, cut to the window; events wholly
    outside fall away."""
    out = []
    for _, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def subtract(a, b):
    """The part of the merged intervals ``a`` that no interval of ``b``
    covers."""
    out = []
    b = merge(b)
    for s, e in merge(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


# -- the metrics' arithmetic --------------------------------------------------

def busy_seconds(trace) -> float | None:
    """Seconds in which an op ran on the device: the union of op
    intervals inside the window, averaged over the devices used."""
    if not trace.ops:
        return None
    per = [union_ns(clip(trace.ops[d] + trace.async_ops.get(d, []),
                         trace.t0, trace.t1)) for d in sorted(trace.ops)]
    return sum(per) / len(per) / 1e9


def idle_pct(trace) -> float | None:
    busy = busy_seconds(trace)
    if busy is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / trace.window_s)


def is_collective(trace, name) -> bool:
    return bool(COLLECTIVE_RE.search(trace.text.get(name, name)))


def op_seconds(trace, op_re=None, exclude_re=None):
    """Summed device time of the window's matching ops, per device and
    averaged over the devices: ops whose own text as the compiler gives it
    (their name, where no text is known) matches ``op_re`` and not
    ``exclude_re``, whatever program holds them (a program's name is the
    program's to change, so nothing here matches one).  None when no op
    matches, so that a reader reports nothing and not 0."""
    inc = re.compile(op_re) if op_re else None
    exc = re.compile(exclude_re) if exclude_re else None
    per = []
    for dev in sorted(trace.ops):
        total = 0.0
        for n, s, d in trace.ops[dev]:
            text = trace.text.get(n, n)
            if inc and not inc.search(text):
                continue
            if exc and exc.search(text):
                continue
            a, b = max(s, trace.t0), min(s + d, trace.t1)
            if b > a:
                total += b - a
        per.append(total)
    if not per or not any(per):
        return None
    return sum(per) / len(per) / 1e9


def exposed_collective_pct(trace) -> float | None:
    """Share of the window in which a collective runs on a device and no
    compute op does, on the worst device.  None where no collective ran
    (one chip), so the metric is left out and never reads 0."""
    worst = None
    for dev in sorted(trace.ops):
        every = trace.ops[dev] + trace.async_ops.get(dev, [])
        coll = [e for e in every if is_collective(trace, e[0])]
        if not coll:
            continue
        compute = [e for e in trace.ops[dev]
                   if not is_collective(trace, e[0])]
        exposed = union_ns(subtract(clip(coll, trace.t0, trace.t1),
                                    clip(compute, trace.t0, trace.t1)))
        share = 100.0 * exposed / (trace.t1 - trace.t0)
        worst = share if worst is None else max(worst, share)
    return worst


def top_ops(trace, n=10):
    """``[[name, seconds], ...]``: the ops that took most device time,
    summed by short name and averaged over the devices."""
    if not trace.ops:
        return []
    total = {}
    for rows in trace.ops.values():
        for name, s, d in rows:
            a, b = max(s, trace.t0), min(s + d, trace.t1)
            if b > a:
                total[name] = total.get(name, 0.0) + (b - a)
    k = len(trace.ops)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in rows]


def idle_gaps(trace, n=10):
    """``[[what the host was doing, seconds], ...]``: the idle gaps of the
    first device, summed by the innermost host span that covers each
    gap's middle (the shortest; of two as short, the earlier in
    ``trace.host``), longest first."""
    if not trace.ops:
        return []
    dev = sorted(trace.ops)[0]
    busy = merge(clip(trace.ops[dev] + trace.async_ops.get(dev, []),
                      trace.t0, trace.t1))
    gaps = subtract([(trace.t0, trace.t1)], busy)
    # One sweep: the gaps come in order, so their middles rise.  A span
    # that has begun by a middle waits in a heap, shortest first; one that
    # ended before a middle ended before every later one and is dropped.
    # A capture holds every frame of the Python tracer and, where its ops
    # stand a nanosecond apart, a gap at most op boundaries: a scan of the
    # spans for each gap took six minutes of the randomized SVD cell's
    # traced run on a TPU v5e host.
    spans = sorted((hs, hd, i, hn)
                   for i, (hn, hs, hd) in enumerate(trace.host)
                   if hn != WINDOW_SPAN)
    waiting, k, total = [], 0, {}
    for s, e in gaps:
        mid = (s + e) / 2.0
        while k < len(spans) and spans[k][0] <= mid:
            hs, hd, i, hn = spans[k]
            heapq.heappush(waiting, (hd, i, hs + hd, hn))
            k += 1
        while waiting and waiting[0][2] < mid:
            heapq.heappop(waiting)
        name = waiting[0][3] if waiting else "no host span"
        total[name] = total.get(name, 0.0) + (e - s)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


# -- reading the profiler's file --------------------------------------------

class MissingWindow(RuntimeError):
    """A traced run whose trace lacks the ``bench.window`` span."""


def find_xplane(logdir) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load(logdir, patterns) -> Trace:
    """Read the newest trace under ``logdir`` with the device's patterns
    (``peaks.json``: ``trace``) and cut it to the ``bench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(logdir))
    dev_rx = re.compile(patterns["device_plane"])
    op_rx = re.compile(patterns["op_line"])
    async_rx = re.compile(patterns.get("async_line") or "^$")
    host_plane_rx = re.compile(patterns["host_plane"])
    name_rx = re.compile(patterns["op_name"])
    cont_rx = re.compile(patterns["container_op"])
    need = patterns.get("require_stat")
    tr = Trace()
    n_dev = 0
    for plane in pd.planes:
        m = dev_rx.search(plane.name)
        if m:
            dev = int(m.group(1)) if m.groups() else n_dev
            n_dev += 1
            for line in plane.lines:
                if op_rx.search(line.name) or async_rx.search(line.name):
                    bucket = tr.async_ops if async_rx.search(line.name) \
                        else tr.ops
                    rows = bucket.setdefault(dev, [])
                    for ev in line.events:
                        if need and need not in dict(ev.stats):
                            continue
                        nm = name_rx.search(ev.name)
                        short = nm.group(1) if nm else ev.name[:80]
                        if cont_rx.search(short):
                            continue
                        tr.text.setdefault(short, ev.name)
                        rows.append((short, ev.start_ns, ev.duration_ns))
        if host_plane_rx.search(plane.name):
            # the caller's thread is the line that holds the window's span,
            # whatever the thread is called ("python", "python3", ...)
            for line in plane.lines:
                events = [(ev.name, ev.start_ns, ev.duration_ns)
                          for ev in line.events]
                if any(e[0] == WINDOW_SPAN for e in events):
                    tr.host.extend(events)
    spans = [e for e in tr.host if e[0] == WINDOW_SPAN]
    if not spans:
        raise MissingWindow(
            f"the trace under {logdir} holds no {WINDOW_SPAN!r} span on a "
            f"host plane matching {patterns['host_plane']!r}: the window "
            "cannot be cut, and idle would mean something else")
    tr.t0 = spans[0][1]
    tr.t1 = spans[0][1] + spans[0][2]
    return tr
