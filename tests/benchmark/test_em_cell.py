"""The cell ``gmm_fit_sustained``: its counted work by hand, a ``correct``
that has been shown to fail, and its per-layer metrics read from a CPU
trace.

As in ``test_correct.py`` every fault is planted at the library's public
boundary, ``GaussianMixture.fit``, and never in a private function.  The
estimator has no switch that lowers its precision, so the control is the
one ``benchmark/calibrate.py`` reads on the chip: the plain reference put
in the program's place one step of precision down.  On the CPU 'high' is
'highest', so the step here is bfloat16.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import counts, harness, manifest, work_em  # noqa: E402

CELL = "gmm_fit_sustained"
SEED = 2_400_000_321
NEW_METRICS = {"gmm_step_roofline", "gm.host_self_ms_per_fit",
               "gm.host_reads_per_fit", "gm.sync_idle_ms_per_fit"}


def _run(trace=False, seed=SEED):
    ctx = harness.open_cell(ROOT, CELL, seed=seed, seconds=0.05,
                            trace=trace, rehearsal=True)
    t0 = time.perf_counter()
    return harness.run(ctx, t0, harness.CompileWatch(),
                       [("import_and_device_s", t0)])


# -- the counted work ---------------------------------------------------------

def test_the_work_of_an_em_iteration_by_hand():
    man = manifest.Manifest(ROOT)
    cfg = man.config("gmm_24Mx50_k16")
    assert (cfg["rows"], cfg["features"], cfg["components"]) \
        == (24_000_000, 50, 16)
    # the k whitened differences and the k weighted Gram sums, 2 n k d^2 each
    assert work_em.gmm_iter_flops(cfg) == 4 * 24e6 * 16 * 50 ** 2 == 3.84e12
    # one read of X
    assert work_em.gmm_iter_bytes(cfg) == 24e6 * 50 * 4 == 4.8e9
    # the readers find both by the names the configuration gives
    assert counts.work(cfg["work"]["flops"], cfg) == 3.84e12
    assert counts.work(cfg["work"]["bytes"], cfg) == 4.8e9
    row = counts.device_peaks(man.peaks(), "TPU v5 lite")
    least, bound = counts.least_seconds(3.84e12, 4.8e9, row)
    assert bound == "compute"           # 19.5 ms against 5.9 ms
    assert least == pytest.approx(3.84e12 / 197e12)
    assert 4.8e9 / row["hbm_bytes_per_s"] < least / 3


def test_the_cell_fills_a_quarter_of_the_chip_with_its_rows_alone():
    man = manifest.Manifest(ROOT)
    cfg = man.config("gmm_24Mx50_k16")
    # as the chip holds a tall array: features-major, d padded to eights
    held = cfg["rows"] * -(-cfg["features"] // 8) * 8 * cfg["dtype_bytes"]
    assert held == 5_376_000_000
    assert held >= 0.25 * 16 * 2 ** 30
    assert man.workload(CELL)["chips"] == 1 and cfg["mesh"] == [1, 1]


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
            spec = json.load(f)
        assert set(spec) == {"reader", "params", "what"}
    assert mine["gmm_step_roofline"]["unit"] == "%"
    assert {m["name"] for m in man.end_to_end_of(CELL)} \
        == {"setup_s", "fit_iters_per_s"}
    # the KMeans cell reads none of the new ones
    assert not NEW_METRICS & {m["name"] for m in
                              man.per_layer_of("kmeans_fit_sustained")}


# -- correct ------------------------------------------------------------------

def test_the_sound_program_is_correct_and_every_metric_reads():
    result, info = _run(trace=True)
    assert result["correct"] is True, result["compared"]
    assert all(row["value"] <= row["limit"]
               for row in result["compared"].values())
    assert set(result["compared"]) == {
        "first_bound_gap", "means_gap", "covariances_gap", "weights_gap",
        "bound_gap", "n_iter_gap"}
    assert info["silent_metrics"] == []
    assert NEW_METRICS <= set(result["metrics"])
    # ten iterations a fit; five counted reads a fit (the health vector,
    # the history, weights, means, covariances); one dispatch a fit
    assert info["iterations"] == 10 * info["calls"]
    assert result["metrics"]["gm.host_reads_per_fit"]["value"] == 5.0
    assert result["metrics"]["fitloop.dispatches_per_iter"]["value"] \
        == pytest.approx(0.1)
    assert 0 < result["metrics"]["gmm_step_roofline"]["value"] < 100
    assert result["metrics"]["gm.host_self_ms_per_fit"]["value"] > 0


def _patched_fit(monkeypatch, change):
    """``GaussianMixture.fit`` as its callers see it, with ``change``
    between the real fit and what it hands back."""
    from dislib_tpu.cluster import GaussianMixture
    real = GaussianMixture.fit

    def broken(self, x, *args, **kwargs):
        return change(real, self, x, *args, **kwargs)

    monkeypatch.setattr(GaussianMixture, "fit", broken)


def _unchanged(real, gm, x, *args, **kwargs):
    out = real(gm, x, *args, **kwargs)
    gm.means_ = np.array(gm.means_init, np.float32)   # the start, handed back
    return out


def _half_left_out(real, gm, x, *args, **kwargs):
    # the second half of the rows never arrives
    return real(gm, x[: x.shape[0] // 2], *args, **kwargs)


def _altered(real, gm, x, *args, **kwargs):
    out = real(gm, x, *args, **kwargs)
    gm.means_ = np.array(gm.means_)
    gm.means_[0, 0] += 0.1          # a tenth of the data's sigma
    return out


@pytest.mark.parametrize("fault,number", [
    (_unchanged, "means_gap"), (_half_left_out, "covariances_gap"),
    (_altered, "means_gap")], ids=["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_a_broken_fit_is_not_correct(monkeypatch, fault, number):
    _patched_fit(monkeypatch, fault)
    result, _ = _run()
    assert result["correct"] is False
    row = result["compared"][number]
    assert row["value"] > row["limit"]
    if fault is _unchanged:
        assert row["value"] == pytest.approx(1.0, abs=1e-6)


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
    ok, compared = harness.judge(driver.check(precision="bfloat16"), limits)
    assert ok is False
    # bfloat16 reads 4e-5 to 9e-5 of a bound here and on the chip alike
    assert compared["bound_gap"]["value"] \
        > 10 * compared["bound_gap"]["limit"]
    for name, numbers in driver.faults().items():
        assert harness.judge(numbers, limits)[0] is False, name
