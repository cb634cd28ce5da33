"""The FLOP and byte functions against hand-worked values for the three
configurations, and the roofline arithmetic: no share computed from the
rates PR 22's check read can pass 100."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import counts, harness, manifest  # noqa: E402

MAN = manifest.Manifest(ROOT)
V5E = counts.device_peaks(MAN.peaks(), "TPU v5 lite")


def _cfg(cell):
    return harness.cell_config(MAN, cell, rehearsal=False)


def test_the_v5e_row_is_the_published_one():
    assert V5E["flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["hbm_bytes"] == 16e9


def test_a_device_that_is_not_in_the_table_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        counts.device_peaks(MAN.peaks(), "TPU v9 imaginary")


def test_an_unknown_work_function_is_an_error():
    with pytest.raises(KeyError, match="no work function"):
        counts.work("flops_by_guess", {})


def test_kmeans_counts_by_hand():
    cfg = _cfg("kmeans_fit_sustained")
    # 4 n d k = 4 * 12e6 * 100 * 10
    assert counts.work("kmeans_iter_flops", cfg) == 4.8e10
    # one read of X: 12e6 * 100 * 4 B
    assert counts.work("kmeans_iter_bytes", cfg) == 4.8e9
    least, bound = counts.least_seconds(4.8e10, 4.8e9, V5E)
    assert bound == "memory"
    assert least == pytest.approx(4.8e9 / 819e9)          # 5.86 ms


@pytest.mark.parametrize("cell,order,chips", [
    ("matmul_1chip_steady", 24576, 1), ("matmul_summa_2x2", 40960, 4)])
def test_matmul_counts_by_hand(cell, order, chips):
    cfg = _cfg(cell)
    assert counts.work("matmul_flops", cfg) == 2.0 * order ** 3
    assert counts.work("matmul_bytes", cfg) == 3 * order ** 2 * 4
    least, bound = counts.least_seconds(counts.work("matmul_flops", cfg),
                                        counts.work("matmul_bytes", cfg),
                                        V5E, chips)
    assert bound == "compute"
    assert least == pytest.approx(2.0 * order ** 3 / chips / 197e12)


def test_24k_by_hand_in_numbers():
    # 2 * 24576^3 = 2.9686813949952e13 FLOP; at 197e12 that is 150.7 ms
    assert 2.0 * 24576 ** 3 == 29686813949952.0
    least, _ = counts.least_seconds(29686813949952.0, 0.0, V5E)
    assert least == pytest.approx(0.150694, rel=1e-5)


# what PR 22's check read on the chip (PERF_LEDGER.jsonl, PR 22, set A)
PR22 = {"kmeans_fit_sustained": ("fit_iters_per_s", 68.571),
        "matmul_1chip_steady": ("matmul_tflops_per_chip", 31.4623),
        "matmul_summa_2x2": ("matmul_tflops_per_chip", 26.6879)}


@pytest.mark.parametrize("cell", sorted(PR22))
def test_no_share_from_pr22s_medians_reads_over_100(cell):
    _, rate = PR22[cell]
    cfg = _cfg(cell)
    chips = MAN.workload(cell)["chips"]
    if cell.startswith("kmeans"):
        per_unit_s = 1.0 / rate
        flops = counts.work("kmeans_iter_flops", cfg)
        nbytes = counts.work("kmeans_iter_bytes", cfg)
        mfu = 100.0 * flops * rate / (chips * V5E["flops_per_s"])
    else:
        flops = counts.work("matmul_flops", cfg)
        nbytes = counts.work("matmul_bytes", cfg)
        per_unit_s = flops / (rate * 1e12 * chips)
        mfu = 100.0 * rate * 1e12 / V5E["flops_per_s"]
    least, _ = counts.least_seconds(flops, nbytes, V5E, chips)
    roofline = counts.share_pct(least, per_unit_s)
    assert 0 < mfu < 100 and 0 < roofline < 100, (mfu, roofline)
    if not cell.startswith("kmeans"):
        # six bf16 passes: an f32 product cannot pass a sixth of the peak
        assert mfu < 100.0 / 6.0


def test_a_share_of_nothing_measured_is_nothing_and_never_zero():
    assert counts.share_pct(1.0, 0.0) is None
    assert counts.share_pct(1.0, None) is None


def test_nothing_clamps_a_share():
    assert counts.share_pct(2.0, 1.0) == 200.0
