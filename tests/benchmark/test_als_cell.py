"""The cell ``als_fit_sustained``: its counted work by hand, its data as the
configuration states it, a ``correct`` that has been shown to fail, its
per-layer metrics read from a CPU trace, and the proof that it came as
files and entries only.

As in ``test_rsvd_cell.py`` the faults are planted at the library's public
boundary (``ALS.fit``) or by the driver's own readings; the control is the
plain reference one step of precision down in the program's place, which
on the CPU ('high' is 'highest' there) is bfloat16.
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

from benchmark import counts, datagen_ratings, harness, manifest  # noqa: E402
from benchmark import work_als  # noqa: E402

import append_only  # noqa: E402

CELL = "als_fit_sustained"
CONFIG = "als_netflix_1p44Mx17770_f100"
SEED = 2_410_000_017
NEW_METRICS = {"als.gram_roofline_pct", "als.gram_device_ms_per_iter",
               "als.solve_device_ms_per_iter", "als.rmse_device_ms_per_iter",
               "als.unscoped_device_pct", "als.host_self_ms_per_fit",
               "als.host_reads_per_fit", "als.sync_idle_ms_per_fit"}
NEW_FILES = ["configs/als_netflix_1p44Mx17770_f100.json",
             "traffic/als_fit_back_to_back.json", "drivers/als_fit.py",
             "reference/als.py", "datagen_ratings.py", "work_als.py",
             "readers/scope_roofline.py"] \
    + [f"metrics/{name}.json" for name in sorted(NEW_METRICS)]
NUMBERS = {"users_gap", "items_gap", "rmse_gap", "n_iter_gap"}

# The cell's place in BENCHMARK.json (append_only.py): the entries that
# stood before it came, as they stood, and its own
BEFORE = {
    "configs": (
        ('kmeans_12Mx100_k10', '70eaba93929e'),
        ('matmul_f32_24k', 'ab4813e974d3'),
        ('matmul_f32_40k_2x2', 'b55a242c266b'),
        ('gmm_24Mx50_k16', '789a7144f48b'),
        ('rsvd_1p5Mx1024_r256', 'fc98f459f79c'),
    ),
    "workloads": (
        ('kmeans_fit_sustained', 'bc9155cd6ac0'),
        ('matmul_1chip_steady', 'b14535d739cb'),
        ('matmul_summa_2x2', 'a6fbf2a77e66'),
        ('gmm_fit_sustained', '3271a4473f1b'),
        ('rsvd_fit_sustained', 'abd320849f2a'),
    ),
    "end_to_end": (
        ('setup_s', 'f4713141c801'),
        ('fit_iters_per_s', '2737eaa986f7'),   # before this cell joined
        ('matmul_tflops_per_chip', 'd5b2d1f5e097'),
    ),
    "per_layer": (
        ('fit.step_mfu_pct', '739d158d9d3a'),
        ('kmeans_step_roofline', 'e0495547e4b2'),
        ('fitloop.dispatches_per_iter', 'ee9429174b47'),
        ('device.fit_idle_pct', 'fbc59fce91c9'),
        ('matmul.step_mfu_pct', '7dfb3d504d4d'),
        ('pdot_roofline', '066684bb1e61'),
        ('array.dispatches_per_product', '50fe29f03dd9'),
        ('summa.collective_exposed_pct', 'd2c2e8ecfa02'),
        ('device.matmul_idle_pct', '914b7632fef5'),
        ('kmeans.host_self_ms_per_fit', 'e2c25ec5d9e5'),
        ('fitloop.host_self_ms_per_fit', '23b54ae412f5'),
        ('fitloop.host_reads_per_fit', '8c22bcb43f4a'),
        ('fitloop.sync_idle_ms_per_fit', 'a3f36ba9188b'),
        ('array.host_self_ms_per_product', '8cab036c97de'),
        ('array.dispatch_idle_ms_per_product', '5c3691d6d400'),
        ('device.wait_idle_ms_per_product', 'e09de185545c'),
        ('gmm_step_roofline', '3da3d3f9d86b'),
        ('gm.host_self_ms_per_fit', 'a25c217c9005'),
        ('gm.host_reads_per_fit', '8179503905f2'),
        ('gm.sync_idle_ms_per_fit', 'ed3ecaa28225'),
        ('rsvd_step_roofline', '736de08c69c3'),
        ('rsvd.host_self_ms_per_call', '062732921155'),
        ('rsvd.host_reads_per_call', '113a676c451a'),
        ('rsvd.sync_idle_ms_per_call', 'bf2ac03f168f'),
        ('kmeans.step_device_ms_per_iter', '98907e3279ef'),
        ('kmeans.norms_device_ms_per_fit', 'a44ba3c211f7'),
        ('fit.unscoped_device_pct', '5874ad6a3ca2'),
        ('summa.fetch_device_ms_per_product', 'd994c8a78f58'),
        ('summa.gemm_device_ms_per_product', 'a22c74241124'),
        ('matmul.unscoped_device_pct', '47694a8f881d'),
        ('tsqr.gram_device_ms_per_call', '6f0b0114c31c'),
        ('tsqr.apply_device_ms_per_call', '74fb559e8cbd'),
        ('tsqr.chol_device_ms_per_call', '2d65742f0118'),
        ('rsvd.products_device_ms_per_call', '08efe448f36e'),
        ('rsvd.lift_device_ms_per_call', 'f6f3b9c3a6d3'),
        ('rsvd.small_svd_device_ms_per_call', '654c9477503a'),
        ('rsvd.unscoped_device_pct', '9ee1e5e07fb6'),
        ('pdot.device_ms_per_product', '0fe8d9edc339'),
        ('matmul_1chip.unscoped_device_pct', '5707bdd32c09'),
    ),
}
OWN = {
    "configs": (
        ('als_netflix_1p44Mx17770_f100', '832a1ba71c25'),
    ),
    "workloads": (
        ('als_fit_sustained', 'b4c5682f62f1'),
    ),
    "end_to_end": (
    ),
    "per_layer": (
        ('als.gram_roofline_pct', 'c064f5dd7563'),
        ('als.gram_device_ms_per_iter', '9a8995b53915'),
        ('als.solve_device_ms_per_iter', '2aa7ec6c9c03'),
        ('als.rmse_device_ms_per_iter', 'f3635d817423'),
        ('als.unscoped_device_pct', '8d12182fd4bc'),
        ('als.host_self_ms_per_fit', '9586b7ecdcd2'),
        ('als.host_reads_per_fit', '32642dcadabc'),
        ('als.sync_idle_ms_per_fit', 'a8cb1516e472'),
    ),
}


def _run(trace=False, seed=SEED):
    ctx = harness.open_cell(ROOT, CELL, seed=seed, seconds=0.05,
                            trace=trace, rehearsal=True)
    t0 = time.perf_counter()
    return harness.run(ctx, t0, harness.CompileWatch(),
                       [("import_and_device_s", t0)])


# -- the counted work and the data --------------------------------------------

def test_the_work_of_an_iteration_by_hand():
    man = manifest.Manifest(ROOT)
    cfg = man.config(CONFIG)
    nnz, m, n, f = 301_441_521, 1_440_567, 17_770, 100
    assert (cfg["ratings"], cfg["users"], cfg["items"], cfg["n_f"]) \
        == (nnz, m, n, f)
    grams = 2 * 2 * nnz * f * f + 2 * 2 * nnz * f    # both half-steps
    solves = (m + n) * (f ** 3 / 3 + 2 * f * f)
    assert work_als.als_gram_flops(cfg) == grams == pytest.approx(1.218e13,
                                                                  rel=1e-3)
    assert work_als.als_iter_flops(cfg) == grams + solves + 2 * nnz * f \
        == pytest.approx(1.2754e13, rel=1e-3)
    assert work_als.als_iter_bytes(cfg) == 3 * 12 * nnz + 3 * 4 * (m + n) * f
    assert counts.work(cfg["work"]["flops"], cfg) \
        == work_als.als_iter_flops(cfg)
    row = counts.device_peaks(man.peaks(), "TPU v5 lite")
    least, bound = counts.least_seconds(work_als.als_gram_flops(cfg),
                                        work_als.als_gram_bytes(cfg), row)
    # the Grams are compute-bound; six passes make the share's ceiling 16.7
    assert bound == "compute" and least == pytest.approx(0.0618, rel=1e-2)
    assert 100 * least / (6 * least) == pytest.approx(16.67, abs=0.01)


def test_the_cell_holds_three_times_the_source_on_one_chip():
    man = manifest.Manifest(ROOT)
    cfg = man.config(CONFIG)
    assert cfg["users"] == 3 * 480_189 and cfg["ratings"] == 3 * 100_480_507
    assert cfg["items"] == 17_770 and cfg["lambda_"] == 0.065
    assert man.workload(CELL)["chips"] == 1 and cfg["mesh"] == [1, 1]
    assert 12 * cfg["ratings"] > 0.2 * 16 * 2 ** 30   # the triplets alone


def test_the_rehearsal_data_is_the_configuration_s():
    cfg = harness.cell_config(manifest.Manifest(ROOT), CELL, rehearsal=True)
    rows, cols, vals, user_counts = datagen_ratings.ratings(SEED, cfg)
    total = cfg["ratings"]
    rows, cols, vals = (np.asarray(a)[:total] for a in (rows, cols, vals))
    assert user_counts.sum() == total and user_counts.min() >= 1
    assert user_counts.max() <= cfg["data"]["user_most"]
    assert (np.diff(rows) >= 0).all()
    np.testing.assert_array_equal(np.bincount(rows, minlength=cfg["users"]),
                                  user_counts)
    pairs = rows.astype(np.int64) * cfg["items"] + cols
    assert np.unique(pairs).size == total          # distinct items a user
    assert set(np.unique(vals)) <= {1.0, 2.0, 3.0, 4.0, 5.0}
    per_item = np.bincount(cols, minlength=cfg["items"])
    assert per_item.max() > 5 * np.median(per_item)   # a heavy tail


# -- the files ----------------------------------------------------------------

def test_the_new_entries_and_files_keep_the_rules():
    assert manifest.problems(ROOT) == []
    man = manifest.Manifest(ROOT)
    mine = {m["name"]: m for m in man.per_layer_of(CELL)}
    assert NEW_METRICS | {"fit.step_mfu_pct", "fitloop.dispatches_per_iter",
                          "device.fit_idle_pct",
                          "fitloop.host_self_ms_per_fit"} <= set(mine)
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "fit_iters_per_s"
        with open(man.bench_path("metrics", name + ".json"),
                  encoding="utf-8") as f:
            assert set(json.load(f)) == {"reader", "params", "what"}
    assert mine["als.gram_roofline_pct"]["unit"] == "%"
    assert {m["name"] for m in man.end_to_end_of(CELL)} \
        == {"setup_s", "fit_iters_per_s"}
    for other in ("kmeans_fit_sustained", "gmm_fit_sustained",
                  "rsvd_fit_sustained"):
        assert not NEW_METRICS & {m["name"] for m in man.per_layer_of(other)}
    for name, lim in man.config(CONFIG)["limits"].items():
        assert name in NUMBERS and len(lim["why"]) > 40, name


def test_the_cell_came_as_files_and_entries_only(tmp_path):
    """``test_rsvd_cell.py``'s rule, for this cell: the benchmark without
    it (its files taken away, its entries cut from BENCHMARK.json) keeps
    the rules, and putting them back edits no file that was there."""
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
    for p, data in snapshot.items():
        with open(p, "rb") as f:
            assert f.read() == data, f"{p} was edited"


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "als.py"),
              encoding="utf-8") as f:
        code = f.read().split('"""', 2)[2]          # past the docstring
    assert "dislib" not in code and "pallas" not in code
    assert "def _solve" in code and "jnp.sqrt" in code   # its own Cholesky


@pytest.mark.parametrize("n_f", [1, 7, 16])
def test_the_reference_solves_as_float64_does(n_f):
    """The reference's Cholesky, a system a lane, against numpy's solve in
    float64 on Grams of random rows; a lane with no entry gets zero."""
    import jax.numpy as jnp
    from benchmark.reference import als as ref
    rng = np.random.default_rng(n_f)
    lanes = ref.LANES
    rows = rng.standard_normal((lanes, 3 * n_f, n_f + 1))
    rows[:3] = 0.0
    g = np.einsum("lsf,lsg->fgl", rows, rows)
    count = np.full(lanes, 3.0 * n_f)
    count[:3] = 0.0
    got = np.asarray(ref._solve(jnp.asarray(g, jnp.float32),
                                jnp.asarray(count, jnp.float32), 0.065))
    a = np.transpose(g[:n_f, :n_f], (2, 0, 1)) \
        + 0.065 * np.maximum(count, 1.0)[:, None, None] * np.eye(n_f)
    want = np.linalg.solve(a, g[:n_f, n_f].T[..., None])[..., 0].T
    assert not got[:, :3].any()
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)


# -- correct ------------------------------------------------------------------

def test_the_sound_program_is_correct_and_every_metric_reads():
    import jax
    from dislib_tpu.utils import profiling
    profiling.clear_programs()
    jax.clear_caches()
    result, info = _run(trace=True)
    assert result["correct"] is True, result["compared"]
    assert set(result["compared"]) == NUMBERS
    assert info["silent_metrics"] == []
    assert NEW_METRICS <= set(result["metrics"])
    assert info["iterations"] == 3 * info["calls"]
    assert 0 < result["metrics"]["als.gram_roofline_pct"]["value"] < 100
    assert result["metrics"]["als.solve_device_ms_per_iter"]["value"] > 0


def test_a_fit_that_returns_its_start_is_not_correct(monkeypatch):
    from dislib_tpu.recommendation import ALS
    real = ALS.fit

    def broken(self, x, *args, **kwargs):
        real(self, x, *args, **kwargs)
        self.items_ = np.asarray(self.items_init, np.float32)
        return self

    monkeypatch.setattr(ALS, "fit", broken)
    result, _ = _run()
    assert result["correct"] is False
    row = result["compared"]["items_gap"]
    assert row["value"] > row["limit"]


def test_the_reference_one_step_of_precision_down_is_not_correct():
    """The control, as ``calibrate.py`` reads it: the reference at bfloat16
    in the program's place fails the cell's own limits, and the driver's
    planted faults each fail one."""
    ctx = harness.open_cell(ROOT, CELL, seed=SEED + 7919, rehearsal=True)
    driver = harness.make_driver(ctx)
    driver.make_data()
    driver.call(0)
    driver.release()
    limits = ctx.config["limits"]
    assert harness.judge(driver.check(), limits)[0] is True
    ok, compared = harness.judge(driver.check(precision="bfloat16"), limits)
    assert ok is False
    assert compared["items_gap"]["value"] > 10 * compared["items_gap"]["limit"]
    faults = driver.faults()
    assert set(faults) == {"half_batch", "answer_altered", "state_unchanged"}
    for name, numbers in faults.items():
        assert harness.judge(numbers, limits)[0] is False, name
