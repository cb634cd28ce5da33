"""PERF.md's walk-through, checked: a configuration, a traffic mix, a cell
and a per-layer metric are each added by adding files and one entry to
BENCHMARK.json, with no edit to a file that is there — and the harness
finds them by name."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import counts, harness, manifest  # noqa: E402


def _copy(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(tmp_path / "tests" / "benchmark")
    return str(tmp_path)


def _json(path, obj=None):
    if obj is None:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)
    return obj


def _snapshot(root):
    """Every file under the copy's ``benchmark/``, byte for byte."""
    before = {}
    for dirpath, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            p = os.path.join(dirpath, name)
            with open(p, "rb") as f:
                before[p] = f.read()
    return before


def _assert_untouched(before):
    for p, data in before.items():
        with open(p, "rb") as f:
            assert f.read() == data, f"{p} was edited"


def test_a_fourth_cell_and_a_tenth_metric_are_files_and_entries_only(tmp_path):
    root = _copy(tmp_path)
    before = _snapshot(root)

    # 1. a configuration: a copy of a config file under a new name
    cfg = _json(os.path.join(root, "benchmark/configs/matmul_f32_24k.json"))
    cfg["order"] = 16384
    cfg["source"] = cfg["source"] + ", at the source's own size"
    _json(os.path.join(root, "benchmark/configs/matmul_f32_16k.json"), cfg)
    # 2. a traffic mix: a copy of a traffic file under a new name
    traffic = _json(os.path.join(
        root, "benchmark/traffic/product_back_to_back.json"))
    traffic["check_rows"] = 256
    _json(os.path.join(root, "benchmark/traffic/product_few_rows.json"),
          traffic)
    # 3. a per-layer metric of the new cell alone: a metric file (reader,
    #    params, what) that reuses a reader
    _json(os.path.join(root, "benchmark/metrics/pdot_few_rows_roofline.json"),
          _json(os.path.join(root, "benchmark/metrics/pdot_roofline.json")))
    # 4. the entries in BENCHMARK.json
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({
        "name": "matmul_f32_16k", "source": cfg["source"],
        "file": "benchmark/configs/matmul_f32_16k.json",
        "reduced": ["order"], "why": "the source's own size"})
    bench["workloads"].append({
        "name": "matmul_16k_few_rows", "config": "matmul_f32_16k",
        "traffic": "product_few_rows", "chips": 1, "why": "a fourth cell"})
    for m in bench["end_to_end"]:
        if m["name"] == "matmul_tflops_per_chip":
            m["workloads"].append("matmul_16k_few_rows")
    bench["per_layer"].append({
        "name": "pdot_few_rows_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "matmul_tflops_per_chip",
        "workloads": ["matmul_16k_few_rows"]})
    _json(os.path.join(root, "BENCHMARK.json"), bench)

    assert manifest.problems(root) == []
    man = manifest.Manifest(root)
    got = harness.cell_config(man, "matmul_16k_few_rows", rehearsal=False)
    assert got["order"] == 16384
    assert counts.matmul_dims(got) == (16384, 16384, 16384)
    assert man.traffic("product_few_rows")["check_rows"] == 256
    # the new cell reports its own metric and, with no edit anywhere, every
    # metric that moves its rate and lists no cells: the whole step's mfu,
    # the dispatches, the idle share (and any that a later PR adds so)
    assert {"pdot_few_rows_roofline", "matmul.step_mfu_pct",
            "array.dispatches_per_product", "device.matmul_idle_pct"} \
        <= {m["name"] for m in man.per_layer_of("matmul_16k_few_rows")}
    assert {m["name"] for m in man.end_to_end_of("matmul_16k_few_rows")} \
        == {"setup_s", "matmul_tflops_per_chip"}
    _assert_untouched(before)


def test_the_added_cell_runs_through_the_harness(tmp_path):
    """The harness, pointed at the copy, drives the new cell end to end at
    rehearsal size: found by name, no edit to the harness."""
    root = _copy(tmp_path)
    before = _snapshot(root)
    cfg = _json(os.path.join(root, "benchmark/configs/matmul_f32_24k.json"))
    cfg["rehearsal"] = {"order": 128}
    _json(os.path.join(root, "benchmark/configs/matmul_tiny.json"), cfg)
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({
        "name": "matmul_tiny", "source": cfg["source"],
        "file": "benchmark/configs/matmul_tiny.json",
        "reduced": ["order"], "why": "a copy"})
    bench["workloads"].append({
        "name": "matmul_tiny_steady", "config": "matmul_tiny",
        "traffic": "product_back_to_back", "chips": 1, "why": "a copy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "matmul_1chip_steady" in m.get("workloads", []):
            m["workloads"].append("matmul_tiny_steady")
    _json(os.path.join(root, "BENCHMARK.json"), bench)
    assert manifest.problems(root) == []
    _assert_untouched(before)

    import time

    import jax
    from dislib_tpu.utils import profiling
    # the catalogue of compiled programs as the cell's own process has it:
    # an instruction name that an earlier test's program places elsewhere
    # would count toward no scope and quiet the scope metrics
    profiling.clear_programs()
    jax.clear_caches()
    ctx = harness.open_cell(root, "matmul_tiny_steady", seed=2_400_000_077,
                            seconds=0.2, trace=True, rehearsal=True)
    t0 = time.perf_counter()
    result, info = harness.run(ctx, t0, harness.CompileWatch(),
                               [("import_and_device_s", t0)])
    assert result["correct"] is True and result["attempted"] >= 1
    # the traced line carries every per-layer metric the manifest gives the
    # new cell: pdot_roofline, and the one-chip cell's scope metrics, because
    # their entries list the cell, the others because they move its rate
    assert {"matmul.step_mfu_pct", "pdot_roofline",
            "array.dispatches_per_product", "device.matmul_idle_pct"} \
        <= set(result["metrics"])
    assert info["cell"] == "matmul_tiny_steady"
    assert info["silent_metrics"] == []
    _assert_untouched(before)
