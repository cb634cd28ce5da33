"""Device time of the traced window by the program's own scopes.  The
TPU's capture names an op by its compiled instruction and holds no scope;
the program knows which ``dslib.`` scopes each instruction it compiled
lies under (``profiling.program_scopes()``: instruction name to the chain
of scopes, outer to inner, joined by ``/``), and is asked after the window.
The rows' maps are merged; a name that two programs give DIFFERENT chains
belongs to neither, and counts as unscoped, which is how a collision
shows.

params: ``scope``, a pattern searched in an op's whole chain (the
innermost scope of every GEMM is ``dslib.pdot``); ``not`` (optional), a
pattern that excludes; ``per``, ``call`` or ``unit``; ``stat``, ``ms``
(the window's device time of the matching ops on the op line, clipped to
the window, summed per chip and averaged over the chips, per ``per``) or
``unscoped_pct`` (device time of the ops whose chain is empty, whose name
no row knows or whose name collides, over the busy time of the same op
line, in percent, 0.0 where every op is scoped; ``scope`` and ``per``
are not read).  Both stats read the op line alone: an op of the async
line is in neither part of the share.  Nothing, not 0, where no op of
the window matches ``scope``."""

import re

from benchmark import reduce_trace


def read(ctx, params):
    tr = ctx.trace_data
    if tr is None or not tr.ops or not ctx.units or not ctx.calls:
        return None
    chains = scope_chains()
    stat = params.get("stat", "ms")
    if stat == "unscoped_pct":
        def wanted(chain):
            return not chain
    elif stat == "ms":
        inc = re.compile(params["scope"])
        exc = re.compile(params["not"]) if params.get("not") else None

        def wanted(chain):
            return bool(chain) and inc.search(chain) is not None \
                and not (exc and exc.search(chain))
    else:
        raise ValueError("scope_time: stat is 'ms' or 'unscoped_pct', "
                         f"not {stat!r}")
    per_chip = [reduce_trace.clip(
        [e for e in tr.ops[dev] if wanted(chains.get(e[0]))], tr.t0, tr.t1)
        for dev in sorted(tr.ops)]
    if stat == "unscoped_pct":
        busy = sum(reduce_trace.union_ns(reduce_trace.clip(
            tr.ops[dev], tr.t0, tr.t1)) for dev in sorted(tr.ops))
        mine = sum(reduce_trace.union_ns(c) for c in per_chip)
        return 100.0 * mine / busy if busy else None
    if not any(per_chip):
        return None
    n = {"call": ctx.calls, "unit": ctx.units}[params["per"]]
    total = sum(b - a for chip in per_chip for a, b in chip)
    return total / len(per_chip) / 1e6 / n


def scope_chains():
    """``{instruction name: chain}`` over every program the process
    compiled, ``""`` for a name that two of them place differently."""
    from dislib_tpu.utils import profiling
    chains = {}
    for row in profiling.program_scopes():
        for name, chain in row["scopes"].items():
            if chains.setdefault(name, chain) != chain:
                chains[name] = ""
    return chains
