"""Profiling / tracing (SURVEY.md §6 "Tracing / profiling").

Reference mechanism: COMPSs `runcompss --tracing` LD_PRELOADs Extrae into
master+workers and merges Paraver timelines; `--graph` dumps the task DAG.

TPU-native equivalent: the library marks its own layer boundaries, and
`jax.profiler` is the one store of what happened when.

- `trace(logdir)` — the operator's entry: a `jax.profiler` capture around
  a block; XPlane/Perfetto timelines (per-op HLO, ICI collectives) — the
  Paraver analog.
- `span(name, **stats)` — the HOST span primitive.  The library opens one
  at every layer boundary of its hot paths (the fit: estimator, fit
  runtime, the wait for the device, host reads; the product: router,
  force, wait).  A span is a `jax.profiler.TraceAnnotation`, so while a
  capture runs it lies on the profiler's clock beside the device ops
  (name, start, end; its parent is the enclosing span on the thread), and
  it always adds to a per-name tally `{count, total_s, max_s}`
  (`span_totals()`), which is what an operator reads with no profiler.
  There is no switch and no second list of spans.
- device code is named with `jax.named_scope("dslib.…")` directly: a
  scope is metadata of the compiled ops (`op_name`), it changes no
  program.
- `annotate(name)` — both at once, for user code: `jax.named_scope` +
  `span` — the Extrae user-events analog.
- `op_graph(fn, *args)` — compiled-HLO text of a jitted function — the
  `--graph` task-DAG analog.
- dispatch/retrace counters: every library kernel is wrapped by
  :func:`profiled_jit`, which counts one *dispatch* per call and one
  *trace* per (re)compilation (`dispatch_count()`, `trace_count()`: "a
  chain of ops is ONE XLA program" as a test assertion; a cache-key
  regression shows as extra traces, not as a silent recompile on chip).
- the catalogue: `profiled_jit` keeps the signature of every call that
  compiled; `program_scopes()` names a capture's ops by their scopes.

Naming rule.  `dslib.<module>.<phase>`, lower case, a fixed string at its
site: shapes, indices and iteration numbers go into `**stats`, never into
the name, so a name means the same thing in every trace and every PR.
PERF.md (section 3) holds the whole vocabulary and the metric that reads
each name.

One call, one identifier.  The span of a public entry (`dslib.kmeans.fit`,
`dslib.matmul`) carries ``call=new_call()``.  Spans on the caller's thread
are its children by nesting, which is every span of today's hot paths.  A
span opened on ANOTHER thread on behalf of a call is given that call's id
as a plain ``call=`` stat by the code that starts the thread; no thread of
the library opens one of its own yet (the chunk watchdog's thread runs
wholly inside the caller's `dslib.fitloop.wait`).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import threading
import time

import jax


@contextlib.contextmanager
def trace(logdir: str):
    """Context-managed capture: ``with dslib.utils.trace('/tmp/tb'): fit()``;
    view with TensorBoard/Perfetto.  The library's spans and scopes are in
    it under their ``dslib.`` names."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_CALL_IDS = itertools.count(1)


def new_call() -> int:
    """A fresh ``call=`` id for the span of a public entry."""
    return next(_CALL_IDS)


class span:
    """A host span: ``with span("dslib.<module>.<phase>", **stats): ...``.

    Enters a ``jax.profiler.TraceAnnotation(name, **stats)`` (a flag test
    while no capture runs) and adds the elapsed time to the per-name tally
    that :func:`span_totals` returns.  ``set(**stats)`` adds stats learned
    inside the span (the route a router picked).  See the module docstring
    for the naming rule and the threading rule."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str, **stats):
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(name, **stats)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **stats) -> None:
        self._ann.set_metadata(**stats)

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        with _COUNTERS_LOCK:
            tally = _COUNTERS.spans.get(self.name)
            if tally is None:
                _COUNTERS.spans[self.name] = [1, dt, dt]
            else:
                tally[0] += 1
                tally[1] += dt
                if dt > tally[2]:
                    tally[2] = dt
        return False


@contextlib.contextmanager
def annotate(name: str):
    """Mark a phase on both the device op names and the host timeline:
    ``jax.named_scope(name)`` for what is traced inside, :func:`span` for
    the host side."""
    with jax.named_scope(name), span(name):
        yield


def op_graph(fn, *args, **kwargs) -> str:
    """Compiled-HLO text of `fn(*args)` — the task-DAG dump analog."""
    return jax.jit(fn).lower(*args, **kwargs).compile().as_text()


# ---------------------------------------------------------------------------
# dispatch / retrace counters
# ---------------------------------------------------------------------------

class _Counters:
    """Process-wide dispatch/trace/transfer tallies, total and per kernel
    name (transfers are total-only: one per host↔device boundary crossing
    at the blessed sync points), plus the round-12 resilience tallies —
    host-side integers bumped by the fit-loop driver, the watchdog, and
    the ingest quarantine, so surfacing them costs ZERO extra dispatches
    (asserted against the dispatch counters in ``tests/test_fitloop``)."""

    __slots__ = ("dispatches", "traces", "transfers", "dispatch_by",
                 "trace_by", "resilience", "schedules", "spans")

    def __init__(self):
        self.dispatches = 0
        self.traces = 0
        self.transfers = 0
        self.dispatch_by: dict[str, int] = {}
        self.trace_by: dict[str, int] = {}
        self.resilience: dict[str, int] = {}
        self.schedules: dict[str, int] = {}
        self.spans: dict[str, list] = {}    # name -> [count, total_s, max_s]


_COUNTERS = _Counters()
_COUNTERS_LOCK = threading.Lock()


def profiled_jit(fn=None, *, name: str | None = None, before=None,
                 **jit_kwargs):
    """``jax.jit`` plus the library's dispatch/retrace counters.

    Every call of the returned function counts one dispatch; every run of
    the traced Python body (i.e. a compilation-cache miss, including AOT
    lowering) counts one trace, both under ``name`` (default: the
    function's ``__name__``).  All remaining keyword arguments —
    ``static_argnames``, ``donate_argnames``, ... — pass through to
    ``jax.jit`` unchanged.  The underlying jitted callable is exposed as
    ``.jitted`` for ``.lower()``-style AOT access.  ``before`` is a
    callable run on the host at every dispatch ahead of the jitted call:
    for what a trace may need and must not do itself (``_kmeans_fit``
    imports Pallas there, which inside a trace reads 0.4 s longer); the
    AOT accessors do not run it.
    """
    if fn is None:
        return lambda f: profiled_jit(f, name=name, before=before,
                                      **jit_kwargs)
    label = name or getattr(fn, "__name__", "jit")

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with _COUNTERS_LOCK:
            _COUNTERS.traces += 1
            _COUNTERS.trace_by[label] = _COUNTERS.trace_by.get(label, 0) + 1
        return fn(*args, **kwargs)

    jitted = jax.jit(traced, **jit_kwargs)

    @functools.wraps(fn)
    def dispatch(*args, **kwargs):
        with _COUNTERS_LOCK:
            _COUNTERS.dispatches += 1
            by, traces = _COUNTERS.dispatch_by, _COUNTERS.trace_by.get(label)
            by[label] = by.get(label, 0) + 1
        if before is not None:
            before()
        done = jitted(*args, **kwargs)  # line and column as they were: D12
        if _COUNTERS.trace_by.get(label) != traces:     # this call compiled
            _remember_program(label, jitted, args, kwargs)
        return done

    dispatch.jitted = jitted
    dispatch.lower = jitted.lower       # AOT access (HLO audits) counts a
    dispatch.eval_shape = jitted.eval_shape  # trace, never a dispatch
    dispatch.profiled_name = label
    return dispatch


def count_dispatch(name: str, n: int = 1) -> None:
    """Record ``n`` device dispatches that bypass :func:`profiled_jit` —
    the round-15 deployment-bundle path invokes DESERIALIZED compiled
    executables directly (no jit wrapper exists to count for it), and
    the serving layer's one-dispatch-per-batch invariant must stay a
    counter assertion there too.  Never counts a trace: a deserialized
    executable cannot retrace by construction."""
    with _COUNTERS_LOCK:
        _COUNTERS.dispatches += n
        _COUNTERS.dispatch_by[name] = _COUNTERS.dispatch_by.get(name, 0) + n


def count_transfer(n: int = 1) -> None:
    """Record ``n`` host↔device transfers.  Called by the library's
    blessed sync boundaries — ``runtime.fetch``, ``Array.collect``,
    ``Array.__float__``, the host tiers of ``apply_along_axis`` and
    ``repad_rows`` — so "this pipeline stage boundary costs ZERO host
    transfers" is a counter assertion, not prose (round-11 rechunk PR)."""
    with _COUNTERS_LOCK:
        _COUNTERS.transfers += n


def host_read() -> span:
    """THE host-read boundary: ``with host_read(): value = <the read>``
    counts one transfer and bounds the read with the ``dslib.host_read``
    span, in one helper so that the count and the span cannot part."""
    count_transfer()
    return span("dslib.host_read")


def transfer_count() -> int:
    """Total host↔device transfers through the library's blessed sync
    boundaries since the last `reset_counters()`."""
    return _COUNTERS.transfers


def count_resilience(key: str, n: int = 1) -> None:
    """Record ``n`` resilience events under ``key`` — the blessed keys are
    ``rollbacks``, ``chunk_retries``, ``escalations_<tier>``,
    ``mesh_shrinks`` / ``mesh_grows`` (the fit-loop driver's elastic
    resizes, escalation- or capacity-driven), ``watchdog_trips`` (the
    chunk guard), ``quarantined_rows`` (ingest), and the round-20
    membership tallies: ``rank_deaths`` / ``rank_rejoins`` (lease
    expiries confirmed and healed by ``runtime.coord.Membership``),
    ``coord_torn_reads`` (torn coordination files survived),
    ``serve_shard_drains`` (a ``PredictServer`` refusing torn fleet
    results while a peer shard is dead), and ``retrieval_rebinds``
    (an ``IVFIndex`` re-laying its device layout after a mesh change)."""
    with _COUNTERS_LOCK:
        _COUNTERS.resilience[key] = _COUNTERS.resilience.get(key, 0) + n


def resilience_counters() -> dict:
    """Resilience tallies since the last ``reset_counters()`` — rollbacks,
    chunk retries, watchdog trips, escalations per ladder tier, mesh
    shrinks/grows, quarantined rows (keys absent until their first
    event)."""
    with _COUNTERS_LOCK:
        return dict(_COUNTERS.resilience)


def count_schedule(kernel: str, schedule: str, n: int = 1) -> None:
    """Record that ``kernel`` ran under panel ``schedule`` (round-13
    overlap PR) — bumped host-side by the routing boundaries (SUMMA's
    matmul entry, ``panel_rechunk``, the ring estimators' tier pickers),
    so "which schedule did the router actually run" is a counter
    assertion, not prose.  Keys are ``f"{kernel}:{schedule}"``."""
    with _COUNTERS_LOCK:
        key = f"{kernel}:{schedule}"
        _COUNTERS.schedules[key] = _COUNTERS.schedules.get(key, 0) + n


def schedule_counters() -> dict:
    """``{"kernel:schedule": count}`` tallies since the last
    ``reset_counters()`` — the overlap router's observability surface
    (``DSLIB_OVERLAP`` routing is asserted through this in
    ``tests/test_overlap.py`` and ``tests/test_spmm.py``)."""
    with _COUNTERS_LOCK:
        return dict(_COUNTERS.schedules)


def span_totals() -> dict:
    """``{name: {"count", "total_s", "max_s"}}`` of every :func:`span`
    closed since the last ``reset_counters()`` — the spans' numbers with
    no profiler running."""
    with _COUNTERS_LOCK:
        return _span_rows()


def _span_rows() -> dict:       # the caller holds _COUNTERS_LOCK
    return {name: {"count": c, "total_s": t, "max_s": m}
            for name, (c, t, m) in _COUNTERS.spans.items()}


def dispatch_count() -> int:
    """Total library-kernel dispatches since the last `reset_counters()`."""
    return _COUNTERS.dispatches


def trace_count() -> int:
    """Total library-kernel (re)compilations since `reset_counters()`."""
    return _COUNTERS.traces


def counters() -> dict:
    """Snapshot of the tallies: ``{dispatches, traces, transfers,
    dispatch_by, trace_by, resilience, schedules, spans}`` with
    per-kernel-name breakdowns (plain dict copies)."""
    with _COUNTERS_LOCK:
        return {"dispatches": _COUNTERS.dispatches,
                "traces": _COUNTERS.traces,
                "transfers": _COUNTERS.transfers,
                "dispatch_by": dict(_COUNTERS.dispatch_by),
                "trace_by": dict(_COUNTERS.trace_by),
                "resilience": dict(_COUNTERS.resilience),
                "schedules": dict(_COUNTERS.schedules),
                "spans": _span_rows()}


def reset_counters() -> None:
    """Zero every tally, the spans' too (tests and chip_smoke.py)."""
    with _COUNTERS_LOCK:
        _COUNTERS.dispatches = 0
        _COUNTERS.traces = 0
        _COUNTERS.transfers = 0
        _COUNTERS.dispatch_by.clear()
        _COUNTERS.trace_by.clear()
        _COUNTERS.resilience.clear()
        _COUNTERS.schedules.clear()
        _COUNTERS.spans.clear()


def memory_stats():
    """Per-device memory stats (SURVEY §6 observability row — the COMPSs
    monitoring resource-load view's analog).

    Returns ``{device_str: stats_dict_or_None}``; keys of each stats dict
    are backend-defined (TPU reports e.g. ``bytes_in_use``,
    ``bytes_limit``, ``peak_bytes_in_use``), and devices whose backend
    exposes no allocator stats (CPU) map to None.
    """
    return {str(d): d.memory_stats() for d in jax.local_devices()}


# ---------------------------------------------------------------------------
# the catalogue of compiled programs
# ---------------------------------------------------------------------------

# imported here, not at the top: a program with a Pallas kernel has the lines
# of `traced` and `dispatch` in its cache key, so nothing above them moves
# (ROADMAP.md D12)
import re                   # noqa: E402
import warnings             # noqa: E402
import numpy as np          # noqa: E402

# (label, treedef, leaves) -> [jitted, row or None]: what was compiled, by the
# abstract signature of the call that compiled it.  No device buffer; it
# describes the jit cache, is bounded as JAX bounds that (the oldest
# signature goes first), and is no tally.
_PROGRAMS: dict = {}
_MAX_PROGRAMS = 4096

_INSTRUCTION_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.-]+) = ")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s.*->.*\{\s*$")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_FUSION_CALLS_RE = re.compile(r"\sfusion\(.*\bcalls=%?([\w.-]+)")
_SCOPE_RE = re.compile(r"dslib\.[\w.]+")


def _remember_program(label, jitted, args, kwargs) -> None:
    """Store the abstract signature of a call that compiled (``dispatch``
    calls this once a compilation, never once a dispatch).  A call made
    under another trace stores nothing: its ops are the outer program's."""
    leaves, tree = jax.tree_util.tree_flatten((args, kwargs))
    if any(isinstance(a, jax.core.Tracer) for a in leaves):
        return
    key = (label, tree, tuple(_abstract(a) for a in leaves))
    with _COUNTERS_LOCK:
        if key not in _PROGRAMS and len(_PROGRAMS) >= _MAX_PROGRAMS:
            del _PROGRAMS[next(iter(_PROGRAMS))]
        _PROGRAMS.setdefault(key, [jitted, None])


def _abstract(a):
    """A leaf of a call as the catalogue keeps it: an array as its shape,
    dtype and (where the caller placed it) sharding; a static as it is."""
    if isinstance(a, jax.Array):
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, weak_type=a.weak_type,
            sharding=a.sharding if a.committed else None)
    if isinstance(a, np.ndarray):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return a


def program_scopes(name: str | None = None) -> list:
    """Which ``dslib.`` scopes each compiled instruction lies under: one
    row for each program that :func:`profiled_jit` compiled in this process
    (of the label ``name``, or of every label), ``{"program": label,
    "scopes": {instruction: chain}, "temp_bytes", "argument_bytes",
    "output_bytes"}``.

    ``scopes`` maps every instruction of the compiled text, named as a
    capture's timeline names its ops, to the ``dslib.`` components of its
    ``op_name``, outer to inner, joined by ``/``
    (``dslib.rsvd.power/dslib.tsqr.gram/dslib.pdot``); ``""`` where it
    lies under none.  A fusion without metadata takes its root's (or what
    all it holds agree on, where the root has none either).  The three
    sizes are the compiler's ``memory_analysis()``: ``temp_bytes`` is
    what the program needs beyond its arguments and results, which
    :func:`memory_stats` does not show.

    On demand only, and after the work that is timed: the first call for a
    signature lowers and compiles it again (with the persistent cache on,
    a load) and the row is kept; a second call compiles nothing.  It
    dispatches nothing and touches no device buffer.  The lowering is AOT
    access as ``dispatch.lower`` is: where JAX's own caches do not answer
    it, it runs the traced body, which counts a trace under the label
    (``trace_by``, ``trace_count()``) and bumps ``schedule_counters()``
    where the body does: do not call it inside a region whose counters
    are asserted.  A
    signature lowers under the mesh and the ``jax.config`` of this call,
    not of the call that compiled it.  The persistent cache's key holds
    no scope name: an executable loaded from it names the scopes of the
    tree that compiled it, and a row whose scopes are not the ones this
    tree's lowering names comes with a warning that says so."""
    with _COUNTERS_LOCK:
        entries = [(key, entry) for key, entry in _PROGRAMS.items()
                   if name is None or key[0] == name]
    rows = []
    for (label, tree, sig), entry in entries:
        if entry[1] is None:
            args, kwargs = jax.tree_util.tree_unflatten(tree, sig)
            lowered = entry[0].lower(*args, **kwargs)
            compiled = lowered.compile()
            scopes = _scopes_of(compiled.as_text())
            _warn_if_stale(label, lowered, scopes)
            mem = compiled.memory_analysis()
            entry[1] = {"program": label, "scopes": scopes,
                        "temp_bytes": mem.temp_size_in_bytes,
                        "argument_bytes": mem.argument_size_in_bytes,
                        "output_bytes": mem.output_size_in_bytes}
        rows.append(entry[1])
    return rows


def _warn_if_stale(label, lowered, scopes) -> None:
    """The persistent cache's key holds no metadata, so an executable
    loaded from it names the scopes of the tree that compiled it.  Where
    those are not the scopes this tree's lowering names, say so."""
    emitted = set(_SCOPE_RE.findall(lowered.as_text(debug_info=True)))
    held = {name for chain in scopes.values() for name in chain.split("/")
            if name}
    if emitted != held:
        warnings.warn(
            f"program_scopes: {label!r} was compiled under other scope names "
            f"than this tree gives it (only here: {sorted(emitted - held)}, "
            f"only in the executable: {sorted(held - emitted)}); it came from "
            "the compilation cache, whose key holds no scope name: point "
            "JAX_COMPILATION_CACHE_DIR at an empty directory to read this "
            "tree's scopes", stacklevel=3)


def _scopes_of(text: str) -> dict:
    """``{instruction: scope chain}`` of a compiled module's text."""
    scopes, roots, inside, calls = {}, {}, {}, {}
    computation = None
    for line in text.splitlines():
        m = _INSTRUCTION_RE.match(line)
        if m is None:
            c = _COMPUTATION_RE.match(line)
            if c:
                computation = c.group(1)
            continue
        root, inst = m.groups()
        op_name = _OP_NAME_RE.search(line)
        if op_name:
            chain = "/".join(_SCOPE_RE.findall(op_name.group(1)))
            inside.setdefault(computation, set()).add(chain)
            if root:
                roots[computation] = chain
        else:
            chain = ""
            called = _FUSION_CALLS_RE.search(line)
            if called:
                calls[inst] = called.group(1)
        scopes[inst] = chain
    # a fusion the compiler made without metadata: its root's chain or,
    # where the root has none either (a concatenate taken apart into
    # writes), the one chain that all it holds with metadata agree on
    for inst, called in calls.items():
        agreed = inside.get(called, ())
        scopes[inst] = roots[called] if called in roots \
            else next(iter(agreed)) if len(agreed) == 1 else ""
    return scopes


def clear_programs() -> None:
    """Empty the catalogue (tests).  ``reset_counters()`` leaves it alone:
    it is no tally, it describes what the jit cache holds."""
    with _COUNTERS_LOCK:
        _PROGRAMS.clear()
