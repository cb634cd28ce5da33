"""The cell ``rsvd_fit_sustained``: its counted work by hand, a ``correct``
that has been shown to fail, its per-layer metrics read from a CPU trace,
and the proof that it came as files and entries only.

As in ``test_correct.py`` and ``test_em_cell.py`` every fault is planted
at the library's public boundary, ``ds.random_svd``, and never in a
private function.  The entry has no switch that lowers its precision, so
the control is the one ``benchmark/calibrate.py`` reads on the chip: the
plain reference put in the program's place one step of precision down.
On the CPU 'high' is 'highest', so the step here is bfloat16.
"""

import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import counts, harness, manifest, work_rsvd  # noqa: E402

import append_only  # noqa: E402

CELL = "rsvd_fit_sustained"
CONFIG = "rsvd_1p5Mx1024_r256"
SEED = 2_400_000_331
NEW_METRICS = {"rsvd_step_roofline", "rsvd.host_self_ms_per_call",
               "rsvd.host_reads_per_call", "rsvd.sync_idle_ms_per_call"}
NEW_FILES = ["configs/rsvd_1p5Mx1024_r256.json",
             "traffic/rsvd_back_to_back.json", "drivers/rsvd.py",
             "reference/rsvd.py", "datagen_lowrank.py", "work_rsvd.py"] \
    + [f"metrics/{name}.json" for name in sorted(NEW_METRICS)]
NUMBERS = {"singular_values_gap", "approx_rows_gap", "orthogonality_gap",
           "right_subspace_gap"}

# The cell's place in BENCHMARK.json (append_only.py): the entries that
# stood before it came, as they stood, and its own
BEFORE = {
    "configs": (
        ("kmeans_12Mx100_k10", "70eaba93929e"),
        ("matmul_f32_24k", "ab4813e974d3"),
        ("matmul_f32_40k_2x2", "b55a242c266b"),
        ("gmm_24Mx50_k16", "789a7144f48b"),
    ),
    "workloads": (
        ("kmeans_fit_sustained", "bc9155cd6ac0"),
        ("matmul_1chip_steady", "b14535d739cb"),
        ("matmul_summa_2x2", "a6fbf2a77e66"),
        ("gmm_fit_sustained", "3271a4473f1b"),
    ),
    "end_to_end": (
        ("setup_s", "f4713141c801"),
        ("fit_iters_per_s", "42ef1f0a5433"),        # the two fit cells
        ("matmul_tflops_per_chip", "d5b2d1f5e097"),
    ),
    "per_layer": (
        ("fit.step_mfu_pct", "739d158d9d3a"),
        ("kmeans_step_roofline", "e0495547e4b2"),
        ("fitloop.dispatches_per_iter", "ee9429174b47"),
        ("device.fit_idle_pct", "fbc59fce91c9"),
        ("matmul.step_mfu_pct", "7dfb3d504d4d"),
        ("pdot_roofline", "066684bb1e61"),
        ("array.dispatches_per_product", "50fe29f03dd9"),
        ("summa.collective_exposed_pct", "d2c2e8ecfa02"),
        ("device.matmul_idle_pct", "914b7632fef5"),
        ("kmeans.host_self_ms_per_fit", "e2c25ec5d9e5"),
        ("fitloop.host_self_ms_per_fit", "23b54ae412f5"),
        ("fitloop.host_reads_per_fit", "8c22bcb43f4a"),
        ("fitloop.sync_idle_ms_per_fit", "a3f36ba9188b"),
        ("array.host_self_ms_per_product", "8cab036c97de"),
        ("array.dispatch_idle_ms_per_product", "5c3691d6d400"),
        ("device.wait_idle_ms_per_product", "e09de185545c"),
        ("gmm_step_roofline", "3da3d3f9d86b"),
        ("gm.host_self_ms_per_fit", "a25c217c9005"),
        ("gm.host_reads_per_fit", "8179503905f2"),
        ("gm.sync_idle_ms_per_fit", "ed3ecaa28225"),
    ),
}
OWN = {
    "configs": (("rsvd_1p5Mx1024_r256", "fc98f459f79c"),),
    "workloads": (("rsvd_fit_sustained", "abd320849f2a"),),
    "per_layer": (
        ("rsvd_step_roofline", "736de08c69c3"),
        ("rsvd.host_self_ms_per_call", "062732921155"),
        ("rsvd.host_reads_per_call", "113a676c451a"),
        ("rsvd.sync_idle_ms_per_call", "bf2ac03f168f"),
    ),
}


def _run(trace=False, seed=SEED):
    ctx = harness.open_cell(ROOT, CELL, seed=seed, seconds=0.05,
                            trace=trace, rehearsal=True)
    t0 = time.perf_counter()
    return harness.run(ctx, t0, harness.CompileWatch(),
                       [("import_and_device_s", t0)])


# -- the counted work ---------------------------------------------------------

def test_the_work_of_a_power_iteration_by_hand():
    man = manifest.Manifest(ROOT)
    cfg = man.config(CONFIG)
    m, n, l, r, q = 1_572_864, 1024, 256, 246, 2
    assert (cfg["rows"], cfg["features"], cfg["nsv"] + cfg["oversample"],
            cfg["nsv"], cfg["iters"]) == (m, n, l, r, q)
    products = 6 * 2 * m * n * l                    # A Omega, 2 x (A^T Q, A W), Q^T A
    orth = 3 * (4 * m * l ** 2 - 4 * l ** 3 / 3)    # Householder, Q formed
    lift = 2 * m * l * r
    assert products == pytest.approx(4.948e12, rel=1e-3)
    assert orth == pytest.approx(1.237e12, rel=1e-3)
    assert lift == pytest.approx(1.98e11, rel=1e-3)
    assert work_rsvd.rsvd_iter_flops(cfg) == (products + orth + lift) / q \
        == pytest.approx(3.191e12, rel=1e-3)
    nbytes = (6 * m * n + 3 * 2 * m * l + m * l + m * r) * 4
    assert work_rsvd.rsvd_iter_bytes(cfg) == nbytes / q \
        == pytest.approx(2.574e10, rel=1e-3)
    # the readers find both by the names the configuration gives
    assert counts.work(cfg["work"]["flops"], cfg) \
        == work_rsvd.rsvd_iter_flops(cfg)
    assert counts.work(cfg["work"]["bytes"], cfg) == nbytes / q
    row = counts.device_peaks(man.peaks(), "TPU v5 lite")
    least, bound = counts.least_seconds(
        work_rsvd.rsvd_iter_flops(cfg), nbytes / q, row)
    # 16.2 ms of operations at the bf16 peak against 31.4 ms of traffic:
    # memory-bound by the published peaks; six passes make it 97 ms, so
    # the share's ceiling under the float32 policy is near 32
    assert bound == "memory" and least == pytest.approx(0.03143, rel=1e-3)
    six_pass = 6 * work_rsvd.rsvd_iter_flops(cfg) / row["flops_per_s"]
    assert 100 * least / six_pass == pytest.approx(32.3, abs=0.2)


def test_the_cell_fills_a_quarter_of_the_chip_with_its_rows_alone():
    man = manifest.Manifest(ROOT)
    cfg = man.config(CONFIG)
    held = cfg["rows"] * cfg["features"] * cfg["dtype_bytes"]
    assert held == 6_442_450_944 == 0.375 * 16 * 2 ** 30
    assert cfg["rows"] == 48 * 32_768           # the source's rows, x48
    assert man.workload(CELL)["chips"] == 1 and cfg["mesh"] == [1, 1]
    # the panel's condition number stays where CholeskyQR2 holds
    data = cfg["data"]
    sketch = cfg["nsv"] + cfg["oversample"]
    assert data["ratio"] ** -(sketch - 1) < 200
    assert data["ratio"] ** sketch < data["ratio"] ** (cfg["nsv"] - 1)
    assert data["noise"] <= 0.011 * data["ratio"] ** (cfg["nsv"] - 1)


# -- the files ----------------------------------------------------------------

def test_the_new_entries_and_files_keep_the_rules():
    assert manifest.problems(ROOT) == []
    man = manifest.Manifest(ROOT)
    mine = {m["name"]: m for m in man.per_layer_of(CELL)}
    # its own four and, with no edit anywhere, the three that move the
    # rate and list no cells; metrics appended later may list it too
    assert NEW_METRICS | {
        "fit.step_mfu_pct", "fitloop.dispatches_per_iter",
        "device.fit_idle_pct"} <= set(mine)
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "fit_iters_per_s"
        with open(man.bench_path("metrics", name + ".json"),
                  encoding="utf-8") as f:
            assert set(json.load(f)) == {"reader", "params", "what"}
    assert mine["rsvd_step_roofline"]["unit"] == "%"
    assert {m["name"] for m in man.end_to_end_of(CELL)} \
        == {"setup_s", "fit_iters_per_s"}
    # the other fit cells read none of the new ones
    for other in ("kmeans_fit_sustained", "gmm_fit_sustained"):
        assert not NEW_METRICS & {m["name"] for m in man.per_layer_of(other)}
    # every limit has its reason beside it
    for name, lim in man.config(CONFIG)["limits"].items():
        assert name in NUMBERS and len(lim["why"]) > 40, name


def test_the_cell_came_as_files_and_entries_only(tmp_path):
    """``test_add_cell.py``'s rule, for this cell: the benchmark without it
    (its files taken away, its entries cut from BENCHMARK.json, and with
    them what later came to list this cell alone) keeps the rules, and
    putting them back edits no file that was there.  Where the cell's
    entries stand is ``append_only.py``'s rule: after the entries that
    stood before it, as they stood, and ahead of whatever came later."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(tmp_path / "tests" / "benchmark")
    root = str(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        whole = json.load(f)
    assert append_only.problems(whole, BEFORE, OWN) == []
    for rel in NEW_FILES:
        os.rename(os.path.join(root, "benchmark", rel),
                  os.path.join(root, "moved_" + rel.replace("/", "_")))
    before = json.loads(json.dumps(whole))
    before["configs"] = [c for c in whole["configs"] if c["name"] != CONFIG]
    before["workloads"] = [w for w in whole["workloads"]
                           if w["name"] != CELL]
    for key in ("end_to_end", "per_layer"):
        for m in before[key]:
            if CELL in m.get("workloads", []):
                m["workloads"].remove(CELL)
        before[key] = [m for m in before[key] if m.get("workloads") != []]
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(before, f)
    assert manifest.problems(root) == []
    # without the cell the entries that stood before it still open the lists
    assert append_only.problems(before, BEFORE, {}) == []
    snapshot = {}
    for dirpath, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            p = os.path.join(dirpath, name)
            with open(p, "rb") as f:
                snapshot[p] = f.read()
    for rel in NEW_FILES:
        os.rename(os.path.join(root, "moved_" + rel.replace("/", "_")),
                  os.path.join(root, "benchmark", rel))
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(whole, f)
    assert manifest.problems(root) == []
    assert harness.cell_config(manifest.Manifest(root), CELL,
                               rehearsal=False)["rows"] == 1_572_864
    for p, data in snapshot.items():
        with open(p, "rb") as f:
            assert f.read() == data, f"{p} was edited"


def test_the_reference_imports_nothing_of_the_program_and_no_cholesky():
    with open(os.path.join(ROOT, "benchmark", "reference", "rsvd.py"),
              encoding="utf-8") as f:
        code = f.read().split('"""', 2)[2]          # past the docstring
    assert "dislib" not in code and "cholesky" not in code.lower()
    assert 'default_matmul_precision("highest")' in code


# -- correct ------------------------------------------------------------------

def test_the_sound_program_is_correct_and_every_metric_reads(monkeypatch):
    # the chip's route: on the CPU the shard-local factorisation is the
    # Householder tree, which opens neither dslib.tsqr.gram nor .chol
    monkeypatch.setenv("DSLIB_TSQR_CHOLQR", "1")
    # the catalogue of compiled programs as the cell's own process has it:
    # an instruction name that an earlier test's program places elsewhere
    # would count toward no scope and quiet the scope metrics
    import jax
    from dislib_tpu.utils import profiling
    profiling.clear_programs()
    jax.clear_caches()
    result, info = _run(trace=True)
    assert result["correct"] is True, result["compared"]
    assert all(row["value"] <= row["limit"]
               for row in result["compared"].values())
    assert set(result["compared"]) == NUMBERS
    assert info["silent_metrics"] == []
    assert NEW_METRICS <= set(result["metrics"])
    # two power iterations a call, one dispatch a call, and the caller's
    # two reads (s and v) after it
    assert info["iterations"] == 2 * info["calls"]
    assert result["metrics"]["fitloop.dispatches_per_iter"]["value"] \
        == pytest.approx(0.5)
    assert result["metrics"]["rsvd.host_reads_per_call"]["value"] == 2.0
    assert 0 < result["metrics"]["rsvd_step_roofline"]["value"] < 100
    assert result["metrics"]["rsvd.host_self_ms_per_call"]["value"] > 0


def _patched_call(monkeypatch, change):
    """``ds.random_svd`` as its callers see it, with ``change`` between
    the real call and what it hands back."""
    import dislib_tpu as ds
    real = ds.random_svd

    def broken(a, *args, **kwargs):
        return change(real, a, *args, **kwargs)

    monkeypatch.setattr(ds, "random_svd", broken)


def _half_left_out(real, a, *args, **kwargs):
    # the second half of the rows never arrives: their U is zero
    import dislib_tpu as ds
    half = a.shape[0] // 2
    u, s, v = real(a[:half], *args, **kwargs)
    grown = np.zeros((a.shape[0], u.shape[1]), np.float32)
    grown[:half] = u.collect()
    return ds.array(grown), s, v


def _altered(real, a, *args, **kwargs):
    import dislib_tpu as ds
    u, s, v = real(a, *args, **kwargs)
    moved = np.array(s.collect())
    moved[0, 0] *= 1.001            # a thousandth of itself
    return u, ds.array(moved), v


def _no_power_iterations(real, a, *args, **kwargs):
    return real(a, *args, **dict(kwargs, iters=0))


@pytest.mark.parametrize("fault,number", [
    (_half_left_out, "approx_rows_gap"), (_altered, "singular_values_gap"),
    (_no_power_iterations, "right_subspace_gap")],
    ids=["half_left_out", "answer_altered", "no_power_iterations"])
def test_a_broken_call_is_not_correct(monkeypatch, fault, number):
    _patched_call(monkeypatch, fault)
    result, _ = _run()
    assert result["correct"] is False
    row = result["compared"][number]
    assert row["value"] > row["limit"]
    if fault is _altered:
        assert row["value"] == pytest.approx(1e-3, rel=1e-2)


def test_the_reference_one_step_of_precision_down_is_not_correct():
    """The control, as ``calibrate.py`` reads it: the reference at
    bfloat16 in the program's place fails the cell's own limits, and the
    driver's planted faults each fail one."""
    ctx = harness.open_cell(ROOT, CELL, seed=SEED + 7919, rehearsal=True)
    driver = harness.make_driver(ctx)
    driver.make_data()
    driver.call(0)
    driver.release()
    limits = ctx.config["limits"]
    assert harness.judge(driver.check(), limits)[0] is True
    ok, compared = harness.judge(driver.check(precision="bfloat16"), limits)
    assert ok is False
    assert compared["approx_rows_gap"]["value"] \
        > 10 * compared["approx_rows_gap"]["limit"]
    faults = driver.faults()
    assert set(faults) == {"half_batch", "answer_altered",
                           "no_power_iterations"}
    for name, numbers in faults.items():
        assert harness.judge(numbers, limits)[0] is False, name
