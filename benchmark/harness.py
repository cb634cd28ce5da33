"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, the metrics, the result line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips for the whole run.  The cell's
configuration, traffic, driver, per-layer metrics and their readers are
files found by the names ``BENCHMARK.json`` gives; nothing in this module
knows a cell by name.  The last line of standard output is the result
object of the contract and nothing more; the split of ``setup_s`` goes on
an earlier line, and the numbers compared, each beside its limit, are also
the last lines of standard error.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

from benchmark import counts, manifest as _manifest, reduce_trace

EXIT_NO_CHIP = 3
EXIT_COMPILED_IN_WINDOW = 4
EXIT_BAD_MANIFEST = 5
EXIT_NO_WINDOW_IN_TRACE = 6


@dataclass
class Context:
    """What a driver and a reader may know of the run."""

    manifest: object
    cell: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    chips: int
    device_kind: str = ""
    peaks_row: dict = field(default_factory=dict)
    # filled as the run goes
    window_s: float = 0.0
    units: int = 0
    calls: int = 0
    dispatches: int = 0
    trace_data: object = None
    unit: str = ""
    devices: list = field(default_factory=list)
    cache_dir: str = ""


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="tiny sizes on the CPU; the line is stamped and is "
                        "no measurement (never used by the driver)")
    return p.parse_args(argv)


def cell_config(man, cell, rehearsal):
    """The cell's configuration as it is run: its file, with the sizes of
    its ``rehearsal`` block laid over it for a rehearsal."""
    cfg = dict(man.config(man.workload(cell)["config"]))
    if rehearsal:
        over = cfg.get("rehearsal", {})
        for key, val in over.items():
            if isinstance(val, dict) and isinstance(cfg.get(key), dict):
                cfg[key] = {**cfg[key], **val}
            else:
                cfg[key] = val
    return cfg


class CompileWatch:
    """Counts what JAX traces, lowers, compiles or loads from its cache,
    through ``jax.monitoring``.  Anything counted inside the window is an
    error of the run, not a number."""

    def __init__(self):
        import jax.monitoring as mon
        self.events = 0
        self.names = []
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_args, **_kw):
        if name.startswith("/jax/core/compile") \
                or name.startswith("/jax/compilation_cache"):
            self.events += 1
            if len(self.names) < 8:
                self.names.append(name)


def device_or_exit(chips, rehearsal):
    """The devices of the run, or exit: no result is printed when JAX
    finds no accelerator or fewer chips than the cell asks for."""
    import jax
    backend = jax.default_backend()
    devices = jax.devices()
    if not rehearsal and backend != "tpu":
        log(f"no accelerator: jax.default_backend() is {backend!r}; this "
            "benchmark measures on a TPU only (--rehearsal for a CPU dry "
            "run)")
        sys.exit(EXIT_NO_CHIP)
    if len(devices) < chips:
        log(f"the cell asks for {chips} chips and JAX found "
            f"{len(devices)}")
        sys.exit(EXIT_NO_CHIP)
    return devices[:chips]


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def judge(numbers, limits):
    """``(correct, compared)``: every limit's number has to be there and
    within it.  ``compared`` holds each number beside its limit."""
    compared = {}
    ok = True
    for name, lim in limits.items():
        val = numbers.get(name)
        good = val is not None and not math.isnan(val) \
            and val <= lim["limit"]
        ok = ok and good
        compared[name] = {"value": val, "limit": lim["limit"]}
    return ok, compared


def read_per_layer(ctx):
    """``(metrics, silent)``: each per-layer metric of the cell through
    its reader.  A reader that finds nothing returns None and the metric
    is left out of the line, as the contract says; BENCHMARK.json gave
    the cell that metric, so its name goes into ``silent`` and the run
    says so aloud (the driver refuses a traced line that lacks it)."""
    out, silent = {}, []
    for m in ctx.manifest.per_layer_of(ctx.cell):
        spec = ctx.manifest.metric_file(m["name"])
        reader = importlib.import_module("benchmark.readers."
                                         + spec["reader"])
        val = reader.read(ctx, spec.get("params", {}))
        if val is not None and not math.isnan(val):
            out[m["name"]] = {"value": val, "unit": m["unit"]}
        else:
            silent.append(m["name"])
            log(f"per-layer metric {m['name']}: its reader "
                f"({spec['reader']}) found nothing to read in cell "
                f"{ctx.cell}; it is left out of the line")
    return out, silent


def open_cell(root, cell, seed, seconds=0.0, trace=False, rehearsal=False):
    """The run's context with the cell's files loaded, the devices held,
    the compile cache on and the library's mesh set to the
    configuration's.  Exits where there is no chip for it."""
    man = _manifest.Manifest(root)
    wl = man.workload(cell)
    cfg = cell_config(man, cell, rehearsal)
    ctx = Context(manifest=man, cell=cell, config=cfg,
                  traffic=man.traffic(wl["traffic"]), seed=seed,
                  seconds=seconds, trace=trace, rehearsal=rehearsal,
                  chips=wl["chips"])
    devices = device_or_exit(ctx.chips, rehearsal)
    ctx.device_kind = devices[0].device_kind
    ctx.peaks_row = counts.device_peaks(
        man.peaks(), "rehearsal" if rehearsal else ctx.device_kind)
    import dislib_tpu as ds
    from dislib_tpu.runtime import compile_cache
    ctx.cache_dir = compile_cache.enable()
    ds.init(tuple(cfg["mesh"]), devices=devices)
    ctx.devices = devices
    return ctx


def make_driver(ctx):
    driver = importlib.import_module(
        "benchmark.drivers." + ctx.traffic["driver"]).make(ctx)
    ctx.unit = driver.unit
    return driver


class CompiledInWindow(RuntimeError):
    """Something was traced, compiled or loaded inside the window."""


def run(ctx, t0, watch, marks):
    """Everything after the devices are held: data, warm-up, the window,
    the comparison, the metrics.  Returns ``(result, info)``: the result
    object of the contract and the run's earlier line."""
    import jax
    from dislib_tpu.utils import profiling
    man, cfg, devices = ctx.manifest, ctx.config, ctx.devices
    driver = make_driver(ctx)
    driver.make_data()
    marks.append(("data_s", time.perf_counter()))
    driver.warm_up()
    marks.append(("compile_and_warm_up_s", time.perf_counter()))
    first_events = watch.events

    logdir, silent = None, []
    if ctx.trace:
        tmp = os.path.join(man.root, ".bench_tmp")
        os.makedirs(tmp, exist_ok=True)
        logdir = tempfile.mkdtemp(dir=tmp)
        jax.profiler.start_trace(logdir)

    # -- the measured window: first timed call to the end of the last -----
    events0, traces0 = watch.events, profiling.trace_count()
    dispatches0 = profiling.dispatch_count()
    t_start = time.perf_counter()
    setup_s = t_start - t0
    deadline = t_start + ctx.seconds
    units = calls = 0
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                with jax.profiler.TraceAnnotation("bench.call"):
                    units += driver.call(calls)
                calls += 1
                t_end = time.perf_counter()
                if t_end >= deadline:
                    break
    finally:
        if ctx.trace:
            jax.profiler.stop_trace()
    ctx.window_s, ctx.units, ctx.calls = t_end - t_start, units, calls
    ctx.dispatches = profiling.dispatch_count() - dispatches0
    compiled = watch.events - events0
    retraced = profiling.trace_count() - traces0
    peak = memory_peak(devices)
    if compiled or retraced:
        raise CompiledInWindow(
            f"{compiled} compile events ({watch.names}) and {retraced} "
            "retraces of library kernels inside the measured window: the "
            "warm-up missed a shape; this run reports nothing")

    # -- the comparison with the plain reference, the window closed, the --
    # -- peak read and the program's state freed ---------------------------
    t_ref = time.perf_counter()
    driver.release()
    correct, compared = judge(driver.check(), cfg["limits"])
    reference_s = time.perf_counter() - t_ref

    # -- metrics -------------------------------------------------------------
    device = {"platform": devices[0].platform, "kind": ctx.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": calls, "failed": 0}
    if ctx.rehearsal:
        result["rehearsal"] = True
    if ctx.trace:
        ctx.trace_data = reduce_trace.load(logdir, ctx.peaks_row["trace"])
        shutil.rmtree(logdir, ignore_errors=True)
        result["metrics"], silent = read_per_layer(ctx)
        device["busy_s"] = reduce_trace.busy_seconds(ctx.trace_data)
        device["window_s"] = ctx.trace_data.window_s
        result["device"] = device
        result["breakdown"] = {
            "device_ops": reduce_trace.top_ops(ctx.trace_data),
            "idle_gaps": reduce_trace.idle_gaps(ctx.trace_data)}
    else:
        e2e = ctx.traffic["end_to_end"]
        units_of = {m["name"]: m["unit"] for m in man.data["end_to_end"]}
        result["metrics"] = {
            e2e: {"value": driver.end_to_end(units, calls, ctx.window_s),
                  "unit": units_of[e2e]},
            "setup_s": {"value": setup_s, "unit": units_of["setup_s"]}}
        result["device"] = device
    result["compared"] = compared        # last, as the contract asks

    split = {name: t - prev for (name, t), prev in
             zip(marks, [t0] + [t for _, t in marks[:-1]])}
    info = {"cell": ctx.cell, "seed": ctx.seed, "setup_s": setup_s,
            "setup_split": split, "compile_events_in_set_up": first_events,
            "reference_s": reference_s, "window_s": ctx.window_s,
            "calls": calls, ctx.unit: units, "cache_dir": ctx.cache_dir,
            "silent_metrics": silent}
    return result, info


def main(argv, t0=None):
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    root = _manifest.root_dir()
    bad = _manifest.problems(root)
    if bad:
        for b in bad:
            log("manifest: " + b)
        return EXIT_BAD_MANIFEST
    watch = CompileWatch()
    import dislib_tpu  # noqa: F401  (timed apart from the device's start)
    marks = [("import_s", time.perf_counter())]
    ctx = open_cell(root, args.workload, args.seed, args.seconds,
                    bool(args.trace), args.rehearsal)
    marks.append(("device_s", time.perf_counter()))
    try:
        result, info = run(ctx, t0, watch, marks)
    except CompiledInWindow as e:
        log(str(e))
        return EXIT_COMPILED_IN_WINDOW
    except reduce_trace.MissingWindow as e:
        log(str(e))
        return EXIT_NO_WINDOW_IN_TRACE
    print(json.dumps(info), flush=True)
    for name, row in result["compared"].items():
        log(f"compared {name}: {row['value']!r} against limit "
            f"{row['limit']!r}")
    log(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0
