"""``correct`` is a comparison that has been shown to fail.

Each test drives a run of the harness past its look for a chip (the
rehearsal's sizes on the CPU) with the timed path broken underneath, and
sees ``correct`` come out false: a fit that returns its start unchanged,
half of the rows left out with the mean taken over the rest, an answer
altered where it is produced, the exchange between chips left out — and
the control, the work computed one step of precision below what the
configuration states.  On the CPU 'high' is 'highest', so the control here
is bfloat16; 'high' is read on the chip by ``benchmark/calibrate.py``.

Every fault is planted at the library's public boundary — ``KMeans.fit``,
``ds.matmul``, their documented arguments and environment switches — and
never in a private function: a later PR that rewrites the inside of the
library cannot edit this file, and need not.
"""

import math
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

SEED = 2_400_000_123


def _run(cell, seed=SEED):
    ctx = harness.open_cell(ROOT, cell, seed=seed, seconds=0.05,
                            trace=False, rehearsal=True)
    t0 = time.perf_counter()
    result, _ = harness.run(ctx, t0, harness.CompileWatch(),
                            [("import_and_device_s", t0)])
    return result


@pytest.mark.parametrize("cell", ["kmeans_fit_sustained",
                                  "matmul_1chip_steady",
                                  "matmul_summa_2x2"])
def test_the_sound_program_is_correct(cell):
    result = _run(cell)
    assert result["correct"] is True, result["compared"]
    assert all(row["value"] <= row["limit"]
               for row in result["compared"].values())


# -- KMeans.fit ---------------------------------------------------------------

def _patched_fit(monkeypatch, change):
    """``KMeans.fit`` as its callers see it, with ``change`` between the
    real fit and what it hands back."""
    from dislib_tpu.cluster import KMeans
    real = KMeans.fit

    def broken(self, x, *args, **kwargs):
        return change(real, self, x, *args, **kwargs)

    monkeypatch.setattr(KMeans, "fit", broken)


def _unchanged(real, km, x, *args, **kwargs):
    out = real(km, x, *args, **kwargs)
    km.centers_ = np.array(km.init, np.float32)    # the start, handed back
    return out


def _half_left_out(real, km, x, *args, **kwargs):
    # the second half of the rows never arrives: sums, counts and inertia
    # are taken over the first half alone
    return real(km, x[: x.shape[0] // 2], *args, **kwargs)


def _altered(real, km, x, *args, **kwargs):
    out = real(km, x, *args, **kwargs)
    km.centers_ = np.array(km.centers_)
    km.centers_[0, 0] += 5.0
    return out


@pytest.mark.parametrize("fault,number", [
    (_unchanged, "centers_gap"), (_half_left_out, "inertia_gap"),
    (_altered, "centers_gap")], ids=["state_unchanged", "half_left_out",
                                     "answer_altered"])
def test_a_broken_fit_is_not_correct(monkeypatch, fault, number):
    _patched_fit(monkeypatch, fault)
    result = _run("kmeans_fit_sustained")
    assert result["correct"] is False
    row = result["compared"][number]
    assert row["value"] > row["limit"]
    if fault is _unchanged:
        assert row["value"] == pytest.approx(1.0, abs=1e-6)


@pytest.mark.parametrize("seed", [SEED, SEED + 7919, SEED + 2 * 7919])
def test_the_fit_at_the_programs_own_lower_precision_is_not_correct(
        monkeypatch, seed):
    """The control: the program with its bfloat16 distance path on, on the
    rehearsal's own data and under the cell's own limit.

    ``first_inertia_gap`` compares the first iteration's inertia, where
    both sides hold the same centres: sound CPU runs read 0 or 6.5e-8 (an
    ulp) on a dozen seeds, bfloat16 2.3e-5 to 1.9e-4, against the limit
    1e-6 that the configuration gives the cell (no rehearsal override).
    ``centers_gap`` tells bfloat16 from sound runs only at the cell's size
    (PERF.md, section 4): at 40 000 rows one row that changes sides moves a
    centre by 1/4000 of its distance, and both read either 1e-8 or one
    such step."""
    monkeypatch.setenv("DSLIB_KMEANS_FAST_DISTANCE", "1")
    result = _run("kmeans_fit_sustained", seed=seed)
    assert result["correct"] is False
    row = result["compared"]["first_inertia_gap"]
    assert row["value"] > 10 * row["limit"]
    cfg = harness.cell_config(harness._manifest.Manifest(ROOT),
                              "kmeans_fit_sustained", rehearsal=False)
    assert row["limit"] == cfg["limits"]["first_inertia_gap"]["limit"]


def test_a_fit_that_stops_early_or_loses_its_history_is_not_correct():
    from benchmark.reference import lloyd
    ref_c, init = np.ones((3, 4)), np.zeros((3, 4))
    ref_h = np.array([9.0, 5.0, 4.0])
    sound = {"centers": ref_c, "history": ref_h, "inertia": 4.0, "n_iter": 3}
    assert lloyd.compare(sound, ref_c, ref_h, init, 3) == {
        "centers_gap": 0.0, "first_inertia_gap": 0.0, "inertia_gap": 0.0,
        "n_iter_gap": 0.0}
    early = dict(sound, n_iter=2, history=ref_h[:2])
    got = lloyd.compare(early, ref_c, ref_h, init, 3)
    assert got["n_iter_gap"] == 1.0 and got["inertia_gap"] == float("inf")
    assert got["first_inertia_gap"] == float("inf")
    # a first inertia two parts in a million off, as a lost pass reads
    off = dict(sound, history=ref_h * np.array([1 + 2e-6, 1.0, 1.0]))
    assert lloyd.compare(off, ref_c, ref_h, init, 3)[
        "first_inertia_gap"] == pytest.approx(2e-6, rel=1e-3)
    limits = {"centers_gap": {"limit": 1e-3},
              "first_inertia_gap": {"limit": 1e-6},
              "inertia_gap": {"limit": 1e-3}, "n_iter_gap": {"limit": 0}}
    assert not harness.judge(
        lloyd.compare(off, ref_c, ref_h, init, 3), limits)[0]
    assert harness.judge(lloyd.compare(sound, ref_c, ref_h, init, 3),
                         limits)[0]
    assert not harness.judge(got, limits)[0]
    assert lloyd.compare(dict(sound, centers=init), ref_c, ref_h, init,
                         3)["centers_gap"] == 1.0


# -- ds.matmul ----------------------------------------------------------------

def _patched_matmul(monkeypatch, change):
    """``ds.matmul`` as its callers see it, with ``change`` around the real
    entry."""
    import dislib_tpu as ds
    real = ds.matmul
    monkeypatch.setattr(ds, "matmul",
                        lambda a, b, **kw: change(real, a, b, **kw))


def test_an_altered_entry_of_the_product_is_not_correct(monkeypatch):
    # every entry moved by a thirtieth of the entries' root mean square
    # (sqrt(k) = 16 at the rehearsal's order)
    _patched_matmul(monkeypatch,
                    lambda real, a, b, **kw: real(a, b, **kw) + 0.5)
    result = _run("matmul_1chip_steady")
    assert result["correct"] is False
    assert all(row["value"] > row["limit"]
               for row in result["compared"].values())


@pytest.mark.parametrize("cell", ["matmul_1chip_steady", "matmul_summa_2x2"])
def test_a_product_in_bfloat16_is_not_correct(monkeypatch, cell):
    """The control: the entry's own ``precision`` argument one policy
    down."""
    _patched_matmul(monkeypatch, lambda real, a, b, **kw:
                    real(a, b, **dict(kw, precision="bfloat16")))
    result = _run(cell)
    assert result["correct"] is False, cell
    assert all(row["value"] > row["limit"]
               for row in result["compared"].values())


def test_summa_without_its_exchange_is_not_correct(monkeypatch):
    """The exchange between chips left out: the second panel of the
    contraction never arrives, so every chip adds up the first alone."""
    def first_panel_only(real, a, b, **kw):
        k = a.shape[1] // 2
        return real(a[:, :k], b[:k, :], **kw)

    _patched_matmul(monkeypatch, first_panel_only)
    result = _run("matmul_summa_2x2")
    assert result["correct"] is False
    row = result["compared"]["product_rms_gap"]
    assert row["value"] > 100 * row["limit"]


# -- the judge ------------------------------------------------------------------

def test_the_judge_fails_a_missing_a_nan_and_an_over_the_limit_number():
    limits = {"a": {"limit": 1.0}, "b": {"limit": 0}}
    ok, compared = harness.judge({"a": 0.5, "b": 0.0}, limits)
    assert ok and compared == {"a": {"value": 0.5, "limit": 1.0},
                               "b": {"value": 0.0, "limit": 0}}
    assert not harness.judge({"a": 0.5}, limits)[0]
    assert not harness.judge({"a": math.nan, "b": 0.0}, limits)[0]
    assert not harness.judge({"a": 1.5, "b": 0.0}, limits)[0]
    assert not harness.judge({"a": 0.5, "b": 1e-9}, limits)[0]
