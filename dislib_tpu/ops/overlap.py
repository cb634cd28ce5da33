"""Comm–compute overlap: the library-wide panel-schedule contract.

Every collective panel loop in the library (SUMMA's broadcast/GEMM steps,
``panel_rechunk``'s exchange/assemble steps, the DBSCAN/Daura/neighbors
ring rotate/compute steps) used to be *sequential-phase*: fetch panel t,
THEN consume panel t, so the interconnect and the MXU take turns.  The
locality/overlap discipline of arXiv:1304.1835 (communication-optimal
panel schedules) and the weak-scaling analysis of arXiv:2112.09017 both
put the remaining roofline gap exactly there: at paper scale the
per-panel broadcast time is comparable to the per-panel FLOP time, so a
schedule that hides one under the other claims it back.

:func:`panel_pipeline` is the ONE implementation of that discipline — a
software pipeline with a prologue fetch and an epilogue drain:

- ``overlap=False`` (sequential): each loop body is ``fetch(t);
  consume(t)`` — the collective's result feeds the compute directly, so
  XLA serializes them into one strict chain (the pre-round-13 schedule,
  kept as the always-available fallback).
- ``overlap=True`` (double-buffered, the default): panel t+1's fetch is
  issued BEFORE panel t's consume inside each loop body.  The two are
  data-independent, so the latency-hiding scheduler may run the
  collective concurrently with the GEMM; the loop carry holds exactly
  ONE extra in-flight panel (one panel of live memory, never a copy of
  the operand — verified for the panel rechunk via
  ``compiled.memory_analysis()`` in ``tests/test_overlap.py``).

Both schedules consume panels in the identical order with identical ops,
so they are BIT-EQUAL by construction (pinned by ``tests/test_overlap``
over a schedule × mesh × dtype grid), and both remain ONE dispatch — the
pipeline lives inside the kernel's existing jitted ``shard_map``.

:func:`host_pipeline` is the same discipline for the fit drivers' HOST
loops (dispatch → blocking read per step): issue step t+1's async device
work before blocking on step t, one extra step in flight, bit-equal
orders.

Routing (``DSLIB_OVERLAP``, the ``DSLIB_MATMUL_ALGO`` pattern): ``db``
(default) = double-buffered, ``seq`` = sequential-phase, ``pallas`` =
double-buffered with the hot inner compute (SUMMA's panel GEMM, the ring
ε-pass ``distances_sq``) lowered through a Pallas kernel
(``ops/pallas_kernels``) — for backends where XLA refuses to schedule
the overlap out of the plain HLO.  A requested schedule is the schedule
that runs: on a TPU a Pallas kernel Mosaic refuses is an error at the
call site, never a quiet switch to another schedule.  The resolved
schedule threads through every kernel as a jit STATIC, so
flipping the env var retraces instead of being silently ignored (the
precision-policy contract).
"""

from __future__ import annotations

import os

from jax import lax

SCHEDULES = ("db", "seq", "pallas")

_ALIASES = {
    "": "db", "db": "db", "auto": "db", "on": "db", "1": "db",
    "overlap": "db",
    "seq": "seq", "off": "seq", "0": "seq", "sequential": "seq",
    "pallas": "pallas",
}


def resolve(explicit=None) -> str:
    """The overlap-schedule routing rule: an explicit value wins,
    otherwise ``DSLIB_OVERLAP``, otherwise the double-buffered default.
    Returns a canonical schedule name from :data:`SCHEDULES` — the
    sequential schedule never routes implicitly: it is the explicit
    opt-out."""
    raw = explicit if explicit is not None \
        else os.environ.get("DSLIB_OVERLAP", "db")
    key = _ALIASES.get(str(raw).lower())
    if key is None:
        raise ValueError(
            f"unknown overlap schedule {raw!r}: expected one of "
            f"{SCHEDULES} (DSLIB_OVERLAP accepts the same values)")
    return key


def overlapped(schedule: str) -> bool:
    """True when ``schedule`` software-pipelines the panel loop (``db``
    and ``pallas``); False for the sequential-phase fallback."""
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown overlap schedule {schedule!r}")
    return schedule != "seq"


def panel_pipeline(steps, pan0, fetch, consume, acc0, overlap):
    """THE shared panel-loop schedule (traced; runs inside the caller's
    jitted ``shard_map``).  Computes::

        acc = consume(steps-1, ... consume(1, consume(0, acc0, pan0),
                                           fetch(1, pan0)) ...)

    ``pan0`` is panel 0 (the prologue fetch — callers produce it with the
    same code path as ``fetch``); ``fetch(t, prev)`` produces panel ``t``
    from panel ``t-1`` (broadcast-style panels ignore ``prev`` and slice
    by ``t``; ring-style panels rotate ``prev``) and is only ever called
    with ``t >= 1``; ``consume(t, acc, pan)`` folds panel ``t`` into the
    accumulator pytree.  ``steps`` is static.

    ``overlap=False``: strict phase alternation — each body fetches its
    own panel then consumes it, so the collective feeds the compute in
    one dependence chain (the sequential baseline).
    ``overlap=True``: software pipeline — each body issues the NEXT
    panel's fetch before consuming the current one (independent ops, so
    the scheduler may overlap them), with consume(0) folded in-loop and
    the last panel drained in an epilogue.  Both orders consume panels
    identically, so the two schedules are bit-equal; the pipelined carry
    holds exactly one extra panel."""
    steps = int(steps)
    if steps <= 0:
        return acc0
    if overlap:
        def body(t, carry):
            acc, pan = carry
            nxt = fetch(t + 1, pan)        # issue panel t+1's collective
            acc = consume(t, acc, pan)     # ... under panel t's compute
            return acc, nxt
        acc, last = lax.fori_loop(0, steps - 1, body, (acc0, pan0))
        return consume(steps - 1, acc, last)   # epilogue drain
    acc = consume(0, acc0, pan0)
    if steps == 1:
        return acc

    def body(t, carry):
        acc, prev = carry
        pan = fetch(t, prev)               # collective ...
        acc = consume(t, acc, pan)         # ... THEN compute (strict chain)
        return acc, pan
    acc, _ = lax.fori_loop(1, steps, body, (acc, pan0))
    return acc


def host_pipeline(steps, fetch, consume, overlap=True):
    """:func:`panel_pipeline`'s discipline lifted to HOST loops — the fit
    drivers' dispatch→read sequences (the CSVM cascade's per-level node
    batches, the forest's per-level snapshot/adoption fetches), where the
    "collective" is an async device dispatch or device→host copy and the
    "compute" is the blocking host read.

    ``fetch(t)`` ISSUES step t's async work (a jitted dispatch, a
    ``copy_to_host_async``) and returns its handle without blocking;
    ``consume(t, handle)`` blocks on the handle and returns the step's
    host result.  ``overlap=True`` issues fetch(t+1) before consume(t) —
    step t's blocking read runs under step t+1's device work, with
    exactly ONE extra step in flight (panel_pipeline's carry discipline,
    so the memory gate transfers unchanged).  ``overlap=False`` is the
    strict fetch-then-consume chain.  Both orders evaluate the same
    ``consume(t, fetch(t))`` pairs in the same order, so the schedules
    are bit-equal by construction.  Returns ``[consume(0, ...), ...,
    consume(steps-1, ...)]``."""
    steps = int(steps)
    out = []
    if steps <= 0:
        return out
    if overlap:
        pending = fetch(0)
        for t in range(1, steps):
            nxt = fetch(t)                 # issue step t (async) ...
            out.append(consume(t - 1, pending))   # ... under t-1's read
            pending = nxt
        out.append(consume(steps - 1, pending))   # epilogue drain
        return out
    for t in range(steps):
        out.append(consume(t, fetch(t)))
    return out
