"""Trees tests (reference: test_rf_classifier.py, test_rf_regressor.py,
test_decision_tree.py — SURVEY.md §5 oracle pattern: accuracy/R² vs sklearn
on the same data)."""

import warnings

import numpy as np
import pytest

import dislib_tpu as ds
from dislib_tpu.trees import (
    RandomForestClassifier, RandomForestRegressor,
    DecisionTreeClassifier, DecisionTreeRegressor,
)


def _class_data(rng, n=300, d=6, k=3):
    centers = rng.randn(k, d) * 3
    x = np.vstack([centers[i] + rng.randn(n // k, d) * 0.7 for i in range(k)])
    y = np.repeat(np.arange(k), n // k).astype(np.float32)
    p = rng.permutation(len(y))
    return x[p].astype(np.float32), y[p]


def _reg_data(rng, n=300, d=5):
    x = rng.rand(n, d).astype(np.float32) * 4
    y = (np.sin(x[:, 0]) * 3 + x[:, 1] ** 2 - 2 * x[:, 2]).astype(np.float32)
    return x, y


class TestRandomForestClassifier:
    def test_separable_accuracy(self, rng):
        x, y = _class_data(rng)
        rf = RandomForestClassifier(n_estimators=8, random_state=0)
        rf.fit(ds.array(x), ds.array(y[:, None]))
        assert rf.score(ds.array(x), ds.array(y[:, None])) >= 0.95

    def test_vs_sklearn_holdout(self, rng):
        from sklearn.ensemble import RandomForestClassifier as SkRF
        x, y = _class_data(rng, n=400, d=5, k=2)
        xt, yt = x[:300], y[:300]
        xv, yv = x[300:], y[300:]
        rf = RandomForestClassifier(n_estimators=10, random_state=0)
        rf.fit(ds.array(xt), ds.array(yt[:, None]))
        mine = rf.score(ds.array(xv), ds.array(yv[:, None]))
        sk = SkRF(n_estimators=10, random_state=0).fit(xt, yt).score(xv, yv)
        assert mine >= sk - 0.07

    def test_hard_vote(self, rng):
        x, y = _class_data(rng, n=150, d=4, k=2)
        rf = RandomForestClassifier(n_estimators=5, hard_vote=True,
                                    random_state=0)
        rf.fit(ds.array(x), ds.array(y[:, None]))
        assert rf.score(ds.array(x), ds.array(y[:, None])) >= 0.9

    def test_predict_proba(self, rng):
        x, y = _class_data(rng, n=120, d=4, k=3)
        rf = RandomForestClassifier(n_estimators=4, random_state=0)
        rf.fit(ds.array(x), ds.array(y[:, None]))
        proba = rf.predict_proba(ds.array(x)).collect()
        assert proba.shape == (120, 3)
        assert np.allclose(proba.sum(axis=1), 1.0, atol=1e-5)

    def test_original_labels(self, rng):
        x, y = _class_data(rng, n=90, d=3, k=2)
        y2 = np.where(y > 0, 5.0, -2.0).astype(np.float32)
        rf = RandomForestClassifier(n_estimators=3, random_state=0)
        rf.fit(ds.array(x), ds.array(y2[:, None]))
        pred = rf.predict(ds.array(x)).collect().ravel()
        assert set(np.unique(pred)) <= {-2.0, 5.0}

    def test_not_fitted(self, rng):
        with pytest.raises(RuntimeError):
            RandomForestClassifier().predict(ds.array(rng.rand(4, 2)))


class TestRandomForestRegressor:
    def test_r2_train(self, rng):
        x, y = _reg_data(rng)
        rf = RandomForestRegressor(n_estimators=8, random_state=0)
        rf.fit(ds.array(x), ds.array(y[:, None]))
        assert rf.score(ds.array(x), ds.array(y[:, None])) >= 0.8

    def test_vs_sklearn_holdout(self, rng):
        from sklearn.ensemble import RandomForestRegressor as SkRF
        x, y = _reg_data(rng, n=400)
        xt, yt, xv, yv = x[:300], y[:300], x[300:], y[300:]
        rf = RandomForestRegressor(n_estimators=10, random_state=0)
        rf.fit(ds.array(xt), ds.array(yt[:, None]))
        mine = rf.score(ds.array(xv), ds.array(yv[:, None]))
        sk = SkRF(n_estimators=10, random_state=0).fit(xt, yt).score(xv, yv)
        assert mine >= sk - 0.15


class TestDecisionTree:
    def test_classifier_overfits_train(self, rng):
        x, y = _class_data(rng, n=200, d=5, k=3)
        dt = DecisionTreeClassifier(random_state=0)
        dt.fit(ds.array(x), ds.array(y[:, None]))
        assert dt.score(ds.array(x), ds.array(y[:, None])) >= 0.97

    def test_regressor_fits_train(self, rng):
        x, y = _reg_data(rng, n=200)
        dt = DecisionTreeRegressor(random_state=0)
        dt.fit(ds.array(x), ds.array(y[:, None]))
        assert dt.score(ds.array(x), ds.array(y[:, None])) >= 0.9

    def test_max_depth_limits(self, rng):
        x, y = _class_data(rng, n=100, d=3, k=2)
        dt = DecisionTreeClassifier(max_depth=2, random_state=0)
        dt.fit(ds.array(x), ds.array(y[:, None]))
        assert dt._depth == 2


class TestNBinsContract:
    """The discretisation contract (decision_tree.py module docstring):
    quantile-histogram splits at n_bins granularity, with n_bins a
    constructor knob — including a distribution where the default 32 bins
    provably lose the minority structure and n_bins=256 recovers it."""

    def _fine_boundary(self):
        # 1% minority class below x=0.01 on a uniform feature: 32 quantile
        # bins put the first edge at the ~3.1% quantile, so bin 0 mixes
        # the whole minority with twice as many majority rows — majority
        # vote erases the minority. 256 bins resolve it.
        x = np.linspace(0.0, 1.0, 10_000, dtype=np.float32)[:, None]
        y = (x[:, 0] < 0.01).astype(np.float32)[:, None]
        return x, y

    def _minority_recall(self, clf, x, y):
        pred = np.asarray(
            clf.predict(ds.array(x)).collect()).ravel()
        mask = y.ravel() == 1.0
        return float((pred[mask] == 1.0).mean())

    def test_n_bins_contract(self):
        x, y = self._fine_boundary()
        lose = DecisionTreeClassifier(max_depth=6, random_state=0)
        lose.fit(ds.array(x), ds.array(y))
        win = DecisionTreeClassifier(max_depth=6, random_state=0, n_bins=256)
        win.fit(ds.array(x), ds.array(y))
        assert self._minority_recall(lose, x, y) < 0.2   # 32 bins: erased
        assert self._minority_recall(win, x, y) > 0.7    # 256 bins: found

    def test_n_bins_forest_and_validation(self, rng):
        from dislib_tpu.trees import RandomForestClassifier
        x = rng.rand(200, 4).astype(np.float32)
        y = (x[:, 0] > 0.5).astype(np.float32)[:, None]
        rf = RandomForestClassifier(n_estimators=4, random_state=0, n_bins=64)
        rf.fit(ds.array(x), ds.array(y))
        assert rf.score(ds.array(x), ds.array(y)) > 0.9
        with pytest.raises(ValueError, match="n_bins"):
            DecisionTreeClassifier(n_bins=1).fit(ds.array(x), ds.array(y))
        with pytest.raises(ValueError, match="n_bins"):
            DecisionTreeClassifier(n_bins=0).fit(ds.array(x), ds.array(y))

    def test_depth_cap_warns(self, rng):
        x, y = _class_data(rng, n=100, d=3, k=2)
        dt = DecisionTreeClassifier(max_depth=40, random_state=0)
        with pytest.warns(UserWarning, match="depth cap"):
            dt.fit(ds.array(x), ds.array(y[:, None]))
        assert dt._depth <= 12

    def test_pre_n_bins_snapshot_refused_as_version_change(self, rng,
                                                           tmp_path):
        # a checkpoint written before n_bins joined the fingerprint (8
        # elements vs 9) must be refused with the version message, not the
        # data-mismatch one
        from dislib_tpu.utils import FitCheckpoint
        from dislib_tpu.trees import RandomForestClassifier
        x, y = _class_data(rng, n=120, d=4, k=2)
        path = str(tmp_path / "rf.npz")
        rf = RandomForestClassifier(n_estimators=2, random_state=0)
        rf.fit(ds.array(x), ds.array(y[:, None]),
               checkpoint=FitCheckpoint(path, every=1))
        from dislib_tpu.utils import checkpoint as ckm
        snap = dict(np.load(path, allow_pickle=False))
        snap.pop(ckm._CRC_KEY)
        snap["fp"] = snap["fp"][:-1]            # simulate the old 8-knob fp
        # rewrite through save() so the integrity checksum matches the
        # tampered payload — otherwise load() classifies it corrupt and
        # falls back to the rotated previous generation instead of
        # reaching the fp version check
        ck = FitCheckpoint(path, every=1)
        ck.delete()                             # drop rotated generations
        ck.save(snap)
        with pytest.raises(ValueError, match="different library version"):
            RandomForestClassifier(n_estimators=2, random_state=0).fit(
                ds.array(x), ds.array(y[:, None]),
                checkpoint=FitCheckpoint(path, every=1))


# ---------------------------------------------------------------------------
# round-17 Pallas tier two: the level histogram as a one-hot GEMM
# ---------------------------------------------------------------------------

class TestHistogramKernel:
    """The forest's (node, feature, bin) scatter-add re-expressed as a
    Pallas one-hot GEMM must be BIT-equal to the XLA scatter (the
    forest's contributions — Poisson weights × count/target stats — are
    integer-representable, so both summation orders are exact), routed
    once at the fit boundary, and counter-observable."""

    def _inputs(self, rng, m, n, n_nodes, n_bins, s, dtype=np.float32):
        node = rng.randint(0, n_nodes, m).astype(np.int32)
        bx = rng.randint(0, n_bins, (m, n)).astype(np.int32)
        w = rng.poisson(1.0, m).astype(dtype)
        stats = rng.randint(0, 3, (m, s)).astype(dtype)
        return node, bx, w, stats

    @pytest.mark.parametrize("shape", [(64, 3, 2, 4, 2),
                                       (128, 5, 4, 8, 3),
                                       (200, 2, 8, 32, 1)])
    def test_pallas_bit_equal_to_xla_scatter(self, rng, shape):
        import jax.numpy as jnp
        from dislib_tpu.trees.decision_tree import _node_histogram
        m, n, n_nodes, n_bins, s = shape
        node, bx, w, stats = self._inputs(rng, m, n, n_nodes, n_bins, s)
        outs = {}
        for sched in ("xla", "pallas"):
            outs[sched] = np.asarray(_node_histogram(
                jnp.asarray(node), jnp.asarray(bx), jnp.asarray(w),
                jnp.asarray(stats), n_nodes, n_bins, hist=sched))
        assert outs["xla"].dtype == outs["pallas"].dtype
        np.testing.assert_array_equal(outs["xla"], outs["pallas"])
        # and the histogram is the histogram: a plain numpy scatter oracle
        want = np.zeros((n_nodes, n, n_bins, s), np.float32)
        for i in range(m):
            for f in range(n):
                want[node[i], f, bx[i, f]] += w[i] * stats[i]
        np.testing.assert_array_equal(outs["xla"], want)

    def test_bit_equal_f64_x64_mode(self, rng):
        import jax
        import jax.numpy as jnp
        from dislib_tpu.trees.decision_tree import _node_histogram
        with jax.enable_x64(True):
            node, bx, w, stats = self._inputs(rng, 96, 3, 4, 8, 2,
                                              dtype=np.float64)
            a = np.asarray(_node_histogram(
                jnp.asarray(node), jnp.asarray(bx), jnp.asarray(w),
                jnp.asarray(stats), 4, 8, hist="xla"))
            b = np.asarray(_node_histogram(
                jnp.asarray(node), jnp.asarray(bx), jnp.asarray(w),
                jnp.asarray(stats), 4, 8, hist="pallas"))
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)

    def test_schedule_routed_counted_and_forest_bit_equal(self, rng,
                                                          monkeypatch):
        """DSLIB_OVERLAP resolves the histogram schedule ONCE at the fit
        boundary (`hist:<sched>` counter), and the FITTED forests agree
        bit-for-bit across schedules — same splits, same probabilities."""
        from dislib_tpu.utils import profiling as prof
        x, y = _class_data(rng, n=120, d=4, k=2)
        proba = {}
        for env, sched in (("db", "xla"), ("pallas", "pallas")):
            monkeypatch.setenv("DSLIB_OVERLAP", env)
            prof.reset_counters()
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")   # pallas warns off-TPU
                rf = RandomForestClassifier(n_estimators=4, random_state=0)
                rf.fit(ds.array(x), ds.array(y[:, None]))
                assert prof.schedule_counters().get(f"hist:{sched}", 0) >= 1
                proba[sched] = np.asarray(
                    rf.predict_proba(ds.array(x)).collect())
        assert (proba["xla"] == proba["pallas"]).all()

    def test_warm_refit_traces_nothing_new(self, rng, monkeypatch):
        """The routed kernel is a jit STATIC resolved at the fit
        boundary: a second same-shape fit under the pallas route compiles
        zero new programs (the zero-new-hot-path-traces acceptance)."""
        from dislib_tpu.utils import profiling as prof
        monkeypatch.setenv("DSLIB_OVERLAP", "pallas")
        x, y = _class_data(rng, n=120, d=4, k=2)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            RandomForestClassifier(n_estimators=4, random_state=0).fit(
                ds.array(x), ds.array(y[:, None]))      # warm
            prof.reset_counters()
            RandomForestClassifier(n_estimators=4, random_state=0).fit(
                ds.array(x), ds.array(y[:, None]))
        assert prof.trace_count() == 0
