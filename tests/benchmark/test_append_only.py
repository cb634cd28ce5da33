"""The append-only rule (``append_only.py``) on edited copies of
BENCHMARK.json: what it refuses and what it lets through; and a sixth cell
appended to a copy of the benchmark as PERF.md section 7 describes, with
every check of the files and entries under tests/benchmark/ run against
that copy."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

import append_only  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)


def _edited(change):
    bench = json.loads(json.dumps(BENCH))
    change(bench)
    return bench


def _copy_of(key, i, name):
    def change(bench):
        bench[key].insert(i, dict(bench[key][-1], name=name))
    return change


def _swap(key, i):
    def change(bench):
        rows = bench[key]
        rows[i], rows[i + 1] = rows[i + 1], rows[i]
    return change


def _set(key, name, field, value):
    def change(bench):
        next(e for e in bench[key] if e["name"] == name)[field] = value
    return change


def _drop_cell(key, name, cell):
    def change(bench):
        next(e for e in bench[key] if e["name"] == name)[
            "workloads"].remove(cell)
    return change


def _remove(key, i):
    return lambda bench: bench[key].pop(i)


REFUSED = {
    "config_ahead_of_an_old_one": _copy_of("configs", 0, "a_new_config"),
    "cell_ahead_of_an_old_one": _copy_of("workloads", 4, "a_new_cell"),
    "metric_ahead_of_an_old_one": _copy_of("per_layer", 10, "a.new_metric"),
    "two_old_cells_swapped": _swap("workloads", 0),
    "two_old_metrics_swapped": _swap("per_layer", 3),
    "an_old_entry_taken_away": _remove("per_layer", 5),
    "a_bound_loosened": _set("end_to_end", "fit_iters_per_s", "bound", 0.02),
    "a_unit_changed": _set("per_layer", "pdot_roofline", "unit", "ratio"),
    "a_why_rewritten": _set("workloads", "rsvd_fit_sustained", "why", "x"),
    "a_reduced_key_added": _set("configs", "matmul_f32_24k", "reduced",
                                ["order", "rows"]),
    "a_cell_taken_out_of_a_metric": _drop_cell(
        "end_to_end", "fit_iters_per_s", "kmeans_fit_sustained"),
    "cells_given_to_a_metric_that_listed_none": _set(
        "per_layer", "fit.step_mfu_pct", "workloads",
        ["kmeans_fit_sustained"]),
}


@pytest.mark.parametrize("change", list(REFUSED.values()), ids=list(REFUSED))
def test_an_entry_put_ahead_moved_or_edited_is_refused(change):
    before, own = append_only.pin(BENCH)
    assert append_only.problems(BENCH, before, own) == []
    assert append_only.problems(_edited(change), before, own) != []


def _append_a_cell_and_its_metric(bench):
    bench["configs"].append(dict(bench["configs"][1], name="a_config"))
    bench["workloads"].append(dict(bench["workloads"][1], name="a_cell",
                                   config="a_config"))
    for m in bench["end_to_end"]:
        if m["name"] == "matmul_tflops_per_chip":
            m["workloads"].append("a_cell")
    # a later cell may join a metric that is there, at its list's end
    next(m for m in bench["per_layer"]
         if m["name"] == "pdot_roofline")["workloads"].append("a_cell")
    bench["per_layer"].append(dict(bench["per_layer"][-1], name="a.metric",
                                   workloads=["a_cell"]))


def test_a_cell_and_a_metric_appended_at_the_end_are_let_through():
    own_names = {m["name"] for m in BENCH["per_layer"][-3:]}
    before, own = append_only.pin(BENCH, own_names)
    assert [n for n, _ in own["per_layer"]] \
        == [m["name"] for m in BENCH["per_layer"][-3:]]
    grown = _edited(_append_a_cell_and_its_metric)
    assert append_only.problems(grown, before, own) == []
    # and the pin of the grown manifest holds the old one's entries first
    again, _ = append_only.pin(grown, {"a_config", "a_cell", "a.metric"})
    assert [n for n, _ in again["per_layer"]][:len(before["per_layer"])] \
        == [n for n, _ in before["per_layer"]]
    # a pin's own entries keep their order too
    swapped = _edited(_swap("per_layer", len(BENCH["per_layer"]) - 2))
    assert append_only.problems(swapped, before, own) != []


# -- a sixth cell, every check of the files ---------------------------------------

def _json(path, obj=None):
    if obj is None:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=2)
    return obj


def append_a_cell(root, cell="appended_cell"):
    """PERF.md section 7's recipe in the checkout ``root``: a copy of the
    one-chip product's configuration at the source's own order, a traffic
    file, the cell, the cell in its rate's ``workloads``, and one per-layer
    metric of its own, each a file and an entry appended at the end; the
    names are made from ``cell``."""
    config, traffic, metric = (cell + "_config", cell + "_traffic",
                               cell + ".pdot_roofline")
    bench_dir = os.path.join(root, "benchmark")
    cfg = _json(os.path.join(bench_dir, "configs", "matmul_f32_24k.json"))
    cfg["order"] = 16384
    cfg["source"] += ", at the source's own order"
    _json(os.path.join(bench_dir, "configs", config + ".json"), cfg)
    _json(os.path.join(bench_dir, "traffic", traffic + ".json"),
          _json(os.path.join(bench_dir, "traffic",
                             "product_back_to_back.json")))
    _json(os.path.join(bench_dir, "metrics", metric + ".json"),
          _json(os.path.join(bench_dir, "metrics", "pdot_roofline.json")))
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({
        "name": config, "source": cfg["source"],
        "file": f"benchmark/configs/{config}.json",
        "reduced": ["order"], "why": "the source's own order on one chip"})
    bench["workloads"].append({
        "name": cell, "config": config, "traffic": traffic, "chips": 1,
        "why": "back-to-back ds.matmul of 16 384^2 float32, one caller"})
    for m in bench["end_to_end"]:
        if m["name"] == "matmul_tflops_per_chip":
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": metric, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "matmul_tflops_per_chip", "workloads": [cell]})
    _json(os.path.join(root, "BENCHMARK.json"), bench)


def test_a_sixth_cell_appended_keeps_every_check_of_the_files(tmp_path):
    """The checks of the files and entries are the tests under
    tests/benchmark/ with ``entries`` or ``manifest`` in their names; each
    runs on the copy as pytest would run it on the tree."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for rel in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(ROOT, rel), os.path.join(root, rel),
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(root, "tools"))
    shutil.copy(os.path.join(ROOT, "tools", "scope_manifest.py"),
                os.path.join(root, "tools"))
    shutil.copy(os.path.join(ROOT, "tests", "conftest.py"),
                os.path.join(root, "tests"))
    append_a_cell(root)
    assert manifest.problems(root) == []
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("PYTEST_XDIST_WORKER", None)
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "tests/benchmark",
         "-k", "(entries or manifest) and not sixth"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    summary = done.stdout.strip().splitlines()[-1]
    assert " passed" in summary and "failed" not in summary, summary
    assert int(summary.split(" passed")[0].split()[-1]) >= 30, summary
