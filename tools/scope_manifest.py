#!/usr/bin/env python3
"""A scratch copy of the benchmark in which every cell reports its device
time by the program's own scopes (``benchmark/readers/scope_time.py`` over
``profiling.program_scopes()``): the tree's ``BENCHMARK.json`` and
``benchmark/`` with those of ``ENTRIES`` appended that ``BENCHMARK.json``
does not list yet, and a metric file written for each that has none.

    python tools/scope_manifest.py [DIR]        # default .bench_tmp/scope
    PYTHONPATH=. python DIR/benchmark/run.py --workload <cell> --seed <n> \
        --seconds 10 --trace 1

``ENTRIES`` is the one list of these metrics (ROADMAP.md D11c and
``tests/benchmark/test_scope_metrics.py`` read it here); PERF.md section 7
says why ``BENCHMARK.json`` lists none of them yet.
"""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MARKER = ".scope_manifest"          # in a directory this tool wrote

_UNSCOPED = {"stat": "unscoped_pct"}


def _ms(scope, per, **more):
    return {"scope": scope, "per": per, "stat": "ms", **more}


# (name, unit, layer, cell, params; None where benchmark/metrics/ has the
# file); a metric moves its cell's rate
ENTRIES = [
    ("kmeans.step_device_ms_per_iter", "ms", "kernels",
     "kmeans_fit_sustained", None),
    ("kmeans.norms_device_ms_per_fit", "ms", "kernels",
     "kmeans_fit_sustained", None),
    ("fit.unscoped_device_pct", "%", "device", "kmeans_fit_sustained", None),
    ("summa.fetch_device_ms_per_product", "ms", "schedules",
     "matmul_summa_2x2", None),
    ("summa.gemm_device_ms_per_product", "ms", "schedules",
     "matmul_summa_2x2", None),
    ("matmul.unscoped_device_pct", "%", "device", "matmul_summa_2x2", None),
    ("gm.e_step_device_ms_per_iter", "ms", "kernels", "gmm_fit_sustained",
     _ms(r"dslib\.gm\.e_step", "unit")),
    ("gm.m_step_device_ms_per_iter", "ms", "kernels", "gmm_fit_sustained",
     _ms(r"dslib\.gm\.m_step", "unit")),
    ("gm.chol_close_device_ms_per_iter", "ms", "kernels",
     "gmm_fit_sustained", _ms(r"dslib\.gm\.(chol|close)", "unit")),
    ("gm.walk_device_ms_per_iter", "ms", "kernels", "gmm_fit_sustained",
     _ms(r"dslib\.gm\.pass$", "unit")),
    ("gmm.unscoped_device_pct", "%", "device", "gmm_fit_sustained",
     _UNSCOPED),
    ("tsqr.gram_device_ms_per_call", "ms", "kernels", "rsvd_fit_sustained",
     _ms(r"dslib\.tsqr\.gram", "call")),
    ("tsqr.apply_device_ms_per_call", "ms", "kernels", "rsvd_fit_sustained",
     _ms(r"dslib\.tsqr\.apply", "call")),
    ("tsqr.chol_device_ms_per_call", "ms", "kernels", "rsvd_fit_sustained",
     _ms(r"dslib\.tsqr\.chol", "call")),
    ("rsvd.products_device_ms_per_call", "ms", "kernels",
     "rsvd_fit_sustained",
     _ms(r"dslib\.rsvd\.(sketch|power|project)", "call",
         **{"not": r"dslib\.tsqr\."})),
    ("rsvd.lift_device_ms_per_call", "ms", "kernels", "rsvd_fit_sustained",
     _ms(r"dslib\.rsvd\.lift", "call")),
    ("rsvd.small_svd_device_ms_per_call", "ms", "kernels",
     "rsvd_fit_sustained", _ms(r"dslib\.rsvd\.small_svd", "call")),
    ("rsvd.unscoped_device_pct", "%", "device", "rsvd_fit_sustained",
     _UNSCOPED),
    ("pdot.device_ms_per_product", "ms", "kernels", "matmul_1chip_steady",
     _ms(r"dslib\.pdot", "unit")),
    ("matmul_1chip.unscoped_device_pct", "%", "device",
     "matmul_1chip_steady", _UNSCOPED),
]


def missing(bench):
    """The ``per_layer`` entries of ``ENTRIES`` that ``bench`` (a loaded
    ``BENCHMARK.json``) does not list, as it would list them."""
    have = {m["name"] for m in bench["per_layer"]}
    rate_of = {cell: m["name"] for m in bench["end_to_end"]
               for cell in m.get("workloads", [])}
    return [{"name": name, "unit": unit, "better": "lower",
             "source": "device_trace", "layer": layer,
             "moves": rate_of[cell], "workloads": [cell]}
            for name, unit, layer, cell, _ in ENTRIES if name not in have]


def build(dst, src=ROOT):
    """Write the copy under ``dst`` and return the manifest's problems,
    which should be none.  ``dst`` is new, empty, or an earlier copy of
    this tool's (it holds the marker), which is replaced; anything else is
    refused."""
    if os.path.lexists(dst) and (not os.path.isdir(dst) or (
            os.listdir(dst) and not os.path.exists(
                os.path.join(dst, MARKER)))):
        raise FileExistsError(
            f"{dst} exists and is not a copy this tool wrote (no {MARKER})")
    shutil.rmtree(dst, ignore_errors=True)
    os.makedirs(os.path.join(dst, "tests", "benchmark"))
    with open(os.path.join(dst, MARKER), "w", encoding="utf-8"):
        pass
    shutil.copytree(os.path.join(src, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(src, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    bench["per_layer"] += missing(bench)
    for name, _, _, _, params in ENTRIES:
        path = os.path.join(dst, "benchmark", "metrics", name + ".json")
        if not os.path.exists(path):
            with open(path, "w", encoding="utf-8") as f:
                json.dump({"reader": "scope_time", "params": params,
                           "what": "scratch: see tools/scope_manifest.py"},
                          f, indent=2)
    with open(os.path.join(dst, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f, indent=2)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import manifest
    return manifest.problems(dst)


if __name__ == "__main__":
    where = os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 \
        else os.path.join(ROOT, ".bench_tmp", "scope")
    bad = build(where)
    for b in bad:
        print("manifest: " + b, file=sys.stderr)
    print(where)
    sys.exit(1 if bad else 0)
