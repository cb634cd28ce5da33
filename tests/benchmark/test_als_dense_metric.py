"""The metric ``als.dense_device_ms_per_iter``: the device's time in the
scope ``dslib.als.dense``, the pass that builds the most-rated items' normal
equations as one product of their 0/1 rating pattern against the users'
packed outer products.  It came as one file under ``benchmark/metrics/``
and one ``per_layer`` entry appended at the end of ``BENCHMARK.json``; it
reads in the cell ``als_fit_sustained``, where that pass runs.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, manifest  # noqa: E402

import append_only  # noqa: E402

CELL = "als_fit_sustained"
METRIC = "als.dense_device_ms_per_iter"
SEED = 2_420_000_029

# The metric's place in BENCHMARK.json (append_only.py): the entries that
# stood before it, as they stood, and its own
BEFORE = {
    "configs": (
        ('kmeans_12Mx100_k10', '70eaba93929e'),
        ('matmul_f32_24k', 'ab4813e974d3'),
        ('matmul_f32_40k_2x2', 'b55a242c266b'),
        ('gmm_24Mx50_k16', '789a7144f48b'),
        ('rsvd_1p5Mx1024_r256', 'fc98f459f79c'),
        ('als_netflix_1p44Mx17770_f100', '832a1ba71c25'),
    ),
    "workloads": (
        ('kmeans_fit_sustained', 'bc9155cd6ac0'),
        ('matmul_1chip_steady', 'b14535d739cb'),
        ('matmul_summa_2x2', 'a6fbf2a77e66'),
        ('gmm_fit_sustained', '3271a4473f1b'),
        ('rsvd_fit_sustained', 'abd320849f2a'),
        ('als_fit_sustained', 'b4c5682f62f1'),
    ),
    "end_to_end": (
        ('setup_s', 'f4713141c801'),
        ('fit_iters_per_s', '72908cdee605'),
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
        ('fitloop.host_self_ms_per_fit', '05d4ab0c2e97'),
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
OWN = {
    "configs": (
    ),
    "workloads": (
    ),
    "end_to_end": (
    ),
    "per_layer": (
        ('als.dense_device_ms_per_iter', '1fdaa0a11d6b'),
    ),
}


def test_the_metric_came_by_appending_only():
    """One ``per_layer`` entry at the end and its file: every entry that
    stood before it, the cell's among them, stands as it stood."""
    man = manifest.Manifest(ROOT)
    assert append_only.problems(man.data, BEFORE, OWN) == []
    assert man.data["per_layer"][-1]["name"] == METRIC
    assert manifest.problems(ROOT) == []
    entry = next(m for m in man.per_layer_of(CELL) if m["name"] == METRIC)
    assert entry["workloads"] == [CELL] and entry["layer"] == "kernels"
    assert entry["moves"] == "fit_iters_per_s"
    assert entry["source"] == "device_trace" and entry["unit"] == "ms"
    assert entry["better"] == "lower"
    with open(man.bench_path("metrics", METRIC + ".json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    assert set(spec) == {"reader", "params", "what"}
    assert spec["reader"] == "scope_time"
    assert spec["params"] == {"scope": "dslib\\.als\\.dense", "per": "unit",
                              "stat": "ms"}


def test_the_dense_pass_reads_as_a_part_of_the_grams():
    """The rehearsal's most-rated items go dense; the traced run reads
    their pass, and it is a part of the Grams' time."""
    import jax
    from dislib_tpu.utils import profiling
    profiling.clear_programs()
    jax.clear_caches()
    ctx = harness.open_cell(ROOT, CELL, seed=SEED, seconds=0.05,
                            trace=True, rehearsal=True)
    t0 = time.perf_counter()
    result, info = harness.run(ctx, t0, harness.CompileWatch(),
                               [("import_and_device_s", t0)])
    assert result["correct"] is True, result["compared"]
    assert info["silent_metrics"] == []
    dense = result["metrics"][METRIC]["value"]
    assert 0 < dense < result["metrics"]["als.gram_device_ms_per_iter"][
        "value"]
