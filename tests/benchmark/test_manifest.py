"""BENCHMARK.json and every file it points to, held to the rules the
driver refuses a manifest over — on the CPU, in a second.

The rules live in ``benchmark/manifest.py`` (the harness refuses to run on
a breach of any); here they are shown to hold on the tree as it stands,
and each is shown to catch the slip it is for on a copy with that slip
planted.  Every rule is about whatever the manifest lists, not about
today's cells, so it holds for the files later PRs add.
"""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402


def _load():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _all_names(d):
    yield from (c["name"] for c in d["configs"])
    for w in d["workloads"]:
        yield from (w["name"], w["config"], w["traffic"])
    yield from (m["name"] for m in d["end_to_end"] + d["per_layer"])
    for c in d["configs"]:
        yield from c["reduced"]


def test_the_tree_as_it_stands_breaks_no_rule():
    assert manifest.problems(ROOT) == []


def test_every_name_matches_the_contracts_pattern():
    for name in _all_names(_load()):
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", name), name


def test_every_unit_matches_the_contracts_pattern():
    d = _load()
    for m in d["end_to_end"] + d["per_layer"]:
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"]), m


def test_a_metrics_file_repeats_nothing_of_its_manifest_entry():
    # unit, layer, moves and the cells live in BENCHMARK.json alone, so a
    # later PR that lists a new cell there edits no metric file
    for m in _load()["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               m["name"] + ".json"), encoding="utf-8") as f:
            spec = json.load(f)
        assert {"reader", "what"} <= set(spec) <= {"reader", "params",
                                                   "what"}, m["name"]


def test_every_source_why_and_layer_is_one_short_line():
    d = _load()
    lines = [c["source"] for c in d["configs"]] \
        + [c["why"] for c in d["configs"]] \
        + [w["why"] for w in d["workloads"]] \
        + [m["layer"] for m in d["per_layer"]] + d["command"]
    for s in lines:
        assert 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s, s


def test_every_per_layer_metric_moves_a_metric_each_of_its_cells_reports():
    man = manifest.Manifest(ROOT)
    cells = [w["name"] for w in man.data["workloads"]]
    for m in man.data["per_layer"]:
        for cell in m.get("workloads", cells):
            reports = {e["name"] for e in man.end_to_end_of(cell)}
            if "workloads" in m:
                assert m["moves"] in reports, (m["name"], cell)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    man = manifest.Manifest(ROOT)
    for w in man.data["workloads"]:
        e2e = {e["name"] for e in man.end_to_end_of(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert man.per_layer_of(w["name"]), w["name"]


def test_every_cells_files_exist():
    man = manifest.Manifest(ROOT)
    for w in man.data["workloads"]:
        cfg = man.config(w["config"])
        traffic = man.traffic(w["traffic"])
        assert os.path.isfile(man.bench_path("drivers",
                                             traffic["driver"] + ".py"))
        assert os.path.isfile(man.bench_path("reference",
                                             cfg["reference"] + ".py"))
    for m in man.data["per_layer"]:
        spec = man.metric_file(m["name"])
        assert os.path.isfile(man.bench_path("readers",
                                             spec["reader"] + ".py"))


def test_four_chip_cells_are_a_quarter_at_most_or_one():
    cells = _load()["workloads"]
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_the_peaks_table_names_its_source_and_every_device_has_peaks():
    peaks = manifest.Manifest(ROOT).peaks()
    assert "Google Cloud documentation" in peaks["source"]
    for row in peaks["devices"].values():
        assert row["flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0


def test_bounds_and_run_seconds_are_inside_the_contract():
    d = _load()
    assert 1 <= d["run_seconds"] <= 51
    # a full check with the full 24 cells has to fit into 43 200 s
    assert (2 + 14 * 24) * (d["run_seconds"] + 60) + 24 * 2 * 90 + 1200 \
        <= 43200
    for e in d["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1, e


# -- each rule catches the slip it is for ------------------------------------

@pytest.fixture
def copy(tmp_path):
    """A copy of the manifest and the benchmark's files to plant a slip
    in."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(tmp_path / "tests" / "benchmark")
    return tmp_path


def _edit(root, fn, rel="BENCHMARK.json"):
    path = os.path.join(root, rel)
    with open(path, encoding="utf-8") as f:
        d = json.load(f)
    fn(d)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(d, f)


def _set(section, index, key, value):
    def fn(d):
        d[section][index][key] = value
    return fn


def _metric_unit(unit):
    # PR 23's slip: a unit outside the allowed characters
    return _set("per_layer", 6, "unit", unit)


def _too_many_four_chip(d):
    for w in d["workloads"]:
        w["chips"] = 4


def _moves_to_other(d):
    # a metric that lists its cells, moved to a rate those cells lack
    entry = next(p for p in d["per_layer"] if "workloads" in p
                 and p["moves"] == "fit_iters_per_s")
    entry["moves"] = "matmul_tflops_per_chip"


SLIPS = {
    "unit_with_multiplication_sign": (_metric_unit("×"), "unit"),
    "unit_with_superscript": (_metric_unit("m²"), "unit"),
    "unit_with_middle_dot": (_metric_unit("GB·s"), "unit"),
    "unit_empty": (_metric_unit(""), "unit"),
    "unit_with_space": (_metric_unit("per product"), "unit"),
    "unit_too_long": (_metric_unit("a" * 17), "unit"),
    "unit_greek": (_metric_unit("µs"), "unit"),
    "metric_name_with_space": (_set("per_layer", 0, "name", "fit mfu"),
                               "is no name"),
    "cell_name_with_slash": (_set("workloads", 0, "name", "a/b"),
                             "is no name"),
    "name_too_long": (_set("configs", 0, "name", "x" * 65), "is no name"),
    "source_over_200": (_set("configs", 0, "source", "s" * 201),
                        "1 to 200 characters"),
    "why_with_newline": (_set("workloads", 0, "why", "a\nb"),
                         "one line"),
    "metric_with_a_why": (_set("per_layer", 0, "why", "because"),
                          "refuses"),
    "better_misspelt": (_set("end_to_end", 1, "better", "more"), "better"),
    "source_unknown": (_set("per_layer", 0, "source", "guess"), "source"),
    "end_to_end_from_a_counter": (_set("end_to_end", 1, "source",
                                       "program_counter"), "source"),
    "bound_over_a_tenth": (_set("end_to_end", 1, "bound", 0.2), "bound"),
    "bound_under_a_hundredth": (_set("end_to_end", 1, "bound", 0.001),
                                "bound"),
    "run_seconds_too_long": (lambda d: d.update(run_seconds=52),
                             "run_seconds"),
    "moves_a_metric_that_is_not_end_to_end": (
        _set("per_layer", 0, "moves", "fit.step_mfu_pct"), "moves"),
    "moves_setup": (_set("per_layer", 0, "moves", "setup_s"), "moves"),
    "moves_a_metric_its_cell_does_not_report": (_moves_to_other,
                                                "does not report"),
    "too_many_four_chip_cells": (_too_many_four_chip, "four chips"),
    "chips_two": (_set("workloads", 0, "chips", 2), "chips"),
    "cell_of_an_unknown_configuration": (
        _set("workloads", 0, "config", "nowhere"), "no configuration"),
    "cell_of_an_unknown_traffic": (
        _set("workloads", 0, "traffic", "nowhere"), "no traffic file"),
    "metric_with_no_file": (_set("per_layer", 0, "name", "new.metric"),
                            "no metric file"),
    "metric_listing_an_unknown_cell": (
        _set("per_layer", 0, "workloads", ["nowhere"]), "no cell"),
    "reduced_names_a_width": (_set("configs", 0, "reduced", ["head_dim"]),
                              "width"),
    "roofline_not_in_percent": (_set("per_layer", 1, "unit", "share"),
                                "'%'"),
    "config_file_outside_paths": (_set("configs", 0, "file",
                                       "bench.py"), "under paths"),
    "command_names_a_file_outside_paths": (
        lambda d: d.update(command=["python3", "tools/x.py"]), None),
    "a_key_too_many": (lambda d: d.update(notes="x"), "exactly the keys"),
}


@pytest.mark.parametrize("slip", sorted(SLIPS))
def test_a_planted_slip_is_caught(copy, slip):
    fn, expect = SLIPS[slip]
    if slip == "command_names_a_file_outside_paths":
        os.makedirs(copy / "tools")
        (copy / "tools" / "x.py").write_text("")
        expect = "outside paths"
    _edit(copy, fn)
    found = manifest.problems(str(copy))
    assert found, slip
    assert any(expect in p for p in found), (slip, found)


def test_a_metric_file_that_repeats_its_manifest_entry_is_caught(copy):
    _edit(copy, lambda d: d.update(unit="%", workloads=["matmul_1chip_steady"]),
          "benchmark/metrics/pdot_roofline.json")
    assert any("repeats or adds" in p for p in manifest.problems(str(copy)))


def test_a_metric_file_without_a_reader_is_caught(copy):
    _edit(copy, lambda d: d.pop("reader"),
          "benchmark/metrics/pdot_roofline.json")
    assert any("needs 'reader'" in p for p in manifest.problems(str(copy)))


def test_a_peaks_table_without_source_is_caught(copy):
    _edit(copy, lambda d: d.pop("source"), "benchmark/peaks.json")
    assert any("no source" in p for p in manifest.problems(str(copy)))


def test_a_config_file_whose_scaled_keys_differ_from_reduced_is_caught(copy):
    _edit(copy, lambda d: d.update(scaled=["rows", "features"]),
          "benchmark/configs/kmeans_12Mx100_k10.json")
    assert any("differ" in p for p in manifest.problems(str(copy)))


def test_the_copy_itself_is_sound(copy):
    assert manifest.problems(str(copy)) == []
