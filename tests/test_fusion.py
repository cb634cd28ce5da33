"""Dispatch-fusion layer (`data/array.py` round-7 perf PR): chains of
Array ops build a deferred expression and run as ONE cached XLA program at
the first force point.

- correctness: fused chains bit-match the `DSLIB_EAGER=1` per-op path
  (same op bodies, so exact equality — including mixed padded canvases,
  sparse-flagged passthrough, unaries, reductions, distances);
- the acceptance claim: a >= 3-op chain is exactly 1 dispatch, asserted
  with the new `utils.profiling` counters;
- retrace guard: fitting twice with same-shape data and re-running a 3x3
  grid search add ZERO kernel traces — cache-key regressions (lost
  static_argnames, fusion-program instability) fail here, on CPU, not as
  a silent 20 s recompile on chip;
- donation: the donated fit-loop carries (ALS factors, forest nodes) are
  actually invalidated, and donated kernels survive `jax_debug_nans`.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dislib_tpu as ds
from dislib_tpu.cluster import KMeans
from dislib_tpu.utils import profiling as prof


def _x(rng, m=37, n=11):
    return rng.rand(m, n).astype(np.float32)


class TestFusionCorrectness:
    @pytest.mark.xfail(
        os.environ.get("DSLIB_TEST_TPU") != "1", strict=True,
        raises=AssertionError,
        reason="on XLA:CPU the fused program and the per-op path differ in "
               "the last place or two (8 of 12 entries, 2e-7 relative).  "
               "If this passes on the CPU, remove the marker.")
    def test_chain_bitmatches_eager(self, rng, monkeypatch):
        x = _x(rng)
        fused = ds.matmul((ds.array(x, block_size=(16, 8)) * 2.0 + 1.0).T,
                          ds.array(x, block_size=(16, 8)))[1:5, :3]
        assert fused.is_lazy
        got = fused.collect()
        monkeypatch.setenv("DSLIB_EAGER", "1")
        eager = ds.matmul((ds.array(x, block_size=(16, 8)) * 2.0 + 1.0).T,
                          ds.array(x, block_size=(16, 8)))[1:5, :3]
        assert not eager.is_lazy
        np.testing.assert_array_equal(got, eager.collect())

    def test_mixed_padded_shapes_broadcast(self, rng, monkeypatch):
        x = _x(rng, 21, 13)
        r = rng.rand(1, 13).astype(np.float32)

        def build():
            a, v = ds.array(x), ds.array(r)
            return ((a - v) / (v + 1.0)).sum(axis=0)

        got = build().collect()
        monkeypatch.setenv("DSLIB_EAGER", "1")
        np.testing.assert_array_equal(got, build().collect())

    def test_unaries_and_reductions_match_eager(self, rng, monkeypatch):
        x = _x(rng, 19, 7) + 0.25

        def build(op):
            a = ds.array(x)
            chain = {
                "abs": lambda: abs(-a),
                "sqrt": lambda: (a * 2.0).sqrt(),
                "exp": lambda: (a - 1.0).exp(),
                "sum0": lambda: (a * 3.0).sum(axis=0),
                "sum1": lambda: (a * 3.0).sum(axis=1),
                "sumN": lambda: (a * 3.0).sum(axis=None),
                "mean": lambda: (a + 1.0).mean(axis=1),
                "min": lambda: (a - 0.5).min(axis=0),
                "max": lambda: abs(a).max(axis=None),
                "norm": lambda: (a * a).norm(axis=0),
                "neg_pow": lambda: (-a) ** 2.0,
            }[op]()
            return chain

        for op in ("abs", "sqrt", "exp", "sum0", "sum1", "sumN", "mean",
                   "min", "max", "norm", "neg_pow"):
            monkeypatch.delenv("DSLIB_EAGER", raising=False)
            fused = build(op)
            got = fused.collect()
            fused_dtype = fused.dtype
            monkeypatch.setenv("DSLIB_EAGER", "1")
            eager = build(op)
            np.testing.assert_array_equal(got, eager.collect(), err_msg=op)
            assert fused_dtype == eager.dtype, op

    def test_fma_contraction_is_the_only_divergence(self, rng, monkeypatch):
        """A mul feeding an add on the same element may contract to one
        FMA inside the fused program (XLA excess precision; no barrier
        primitive stops the backend's fp-contract) — the ONE permitted
        divergence from eager, strictly bounded by 1 ulp per contraction.
        Everything else in this file asserts EXACT equality."""
        x = _x(rng, 16, 16)

        def build():
            return ds.array(x) * 1.0001 + 0.0001

        got = build().collect()
        monkeypatch.setenv("DSLIB_EAGER", "1")
        ref = build().collect()
        ulp = np.spacing(np.abs(ref).astype(np.float32))
        assert np.all(np.abs(got - ref) <= ulp), \
            "fused chain diverged from eager by more than 1 ulp"

    def test_sparse_passthrough(self, rng, monkeypatch):
        import scipy.sparse as sp
        x = _x(rng, 23, 9)
        x[x < 0.7] = 0.0

        def build():
            a = ds.array(sp.csr_matrix(x))
            return (a * 3.0).T

        fused = build()
        assert fused.is_lazy and fused._sparse
        got = fused.collect()
        assert sp.issparse(got)
        monkeypatch.setenv("DSLIB_EAGER", "1")
        ref = build().collect()
        np.testing.assert_array_equal(got.toarray(), ref.toarray())

    def test_distances_sq_is_a_graph_node(self, rng, monkeypatch):
        from dislib_tpu.ops import distances_sq
        xa, xb = _x(rng, 17, 6), _x(rng, 9, 6)

        def build():
            a, b = ds.array(xa), ds.array(xb)
            return distances_sq(a * 1.5, b, precision="highest") + 1.0

        fused = build()
        assert fused.is_lazy
        got = fused.collect()
        monkeypatch.setenv("DSLIB_EAGER", "1")
        np.testing.assert_array_equal(got, build().collect())
        ref = ((xa * 1.5)[:, None, :] - xb[None]) ** 2
        np.testing.assert_allclose(got, ref.sum(-1) + 1.0, atol=1e-4)

    def test_shared_prefix_across_arrays_runs_once(self, rng):
        """A lazy prefix consumed by SEVERAL Arrays materialises once:
        the first force emits it as an extra program output and caches
        it, so later consumers load it as a leaf (review finding — the
        naive version re-ran and re-compiled the prefix per fan-out)."""
        x = _x(rng, 20, 8)
        a = ds.array(x).force()
        shared = ds.matmul((a * 2.0 + 1.0).T, a)   # expensive prefix
        c = shared + 1.0
        d = shared * 3.0
        prof.reset_counters()
        c_host = c.collect()                       # runs prefix + its op
        d_host = d.collect()                       # prefix now a cached leaf
        s_host = shared.collect()                  # free: cached root value
        assert prof.counters()["dispatch_by"] == {"fused_chain": 2}
        base = (x * 2.0 + 1.0).T @ x
        np.testing.assert_allclose(c_host, base + 1.0, rtol=1e-5)
        np.testing.assert_allclose(d_host, base * 3.0, rtol=1e-5)
        np.testing.assert_allclose(s_host, base, rtol=1e-5)

    def test_float_of_sparse_flagged_scalar(self, rng):
        """float() on a (1, 1) slice of a sparse-flagged array reads the
        dense backing (collect() would wrap it in a csr_matrix)."""
        import scipy.sparse as sp
        x = np.zeros((6, 6), np.float32)
        x[2, 3] = 4.5
        a = ds.array(sp.csr_matrix(x))
        cell = a[2:3, 3:4]
        assert cell._sparse
        assert float(cell) == 4.5

    def test_int_scalar_div_dtype_metadata(self):
        """Lazy dtype metadata must match the forced result: int / scalar
        true-divides to float (review finding — it reported int32)."""
        a = ds.array(np.arange(12, dtype=np.int32).reshape(3, 4))
        y = a / 2.0
        lazy_dtype = y.dtype
        got = y.collect()
        assert lazy_dtype == got.dtype == np.float32

    def test_exp_drops_the_sparse_flag(self):
        """exp(0)=1 densifies — the result must not stay sparse-flagged
        (review finding: the dummy 0.0 operand slipped exp through the
        zero-preserving clause and collect() wrapped dense data in csr)."""
        import scipy.sparse as sp
        a = ds.array(sp.csr_matrix(np.eye(3, dtype=np.float32)))
        e = a.exp()
        assert not e._sparse
        out = e.collect()
        assert not sp.issparse(out)
        np.testing.assert_allclose(out, np.exp(np.eye(3, dtype=np.float32)),
                                   rtol=1e-6)

    def test_materialised_prefix_releases_its_subtree(self, rng):
        """Once a shared prefix is value-cached, its graph edges drop so
        the leaf device buffers are not pinned for the lifetime of other
        lazy consumers (review finding: an HBM leak on big leaves)."""
        x = _x(rng, 16, 8)
        a = ds.array(x).force()
        shared = (a * 2.0).T
        c = shared + 1.0
        d = shared * 3.0                 # stays lazy
        c.collect()
        assert d._lazy.args[0].args == ()   # d's prefix edge is cached+cut
        np.testing.assert_allclose(d.collect(), (x * 2.0).T * 3.0,
                                   rtol=1e-6)

    def test_diamond_tower_is_not_force_spammed(self, rng):
        """n_ops overcounts shared subexpressions exponentially; the cap
        must use the exact deduped count so a y = y + y tower stays ONE
        fused dispatch (review finding: it forced every ~7 ops)."""
        x = _x(rng, 8, 4)
        y = ds.array(x).force()
        for _ in range(20):
            y = y + y
        assert y.is_lazy, "diamond tower was forced early by the cap"
        prof.reset_counters()
        got = y.collect()
        assert prof.counters()["dispatch_by"] == {"fused_chain": 1}
        np.testing.assert_allclose(got, x * 2.0 ** 20, rtol=1e-6)

    def test_diamond_graph_evaluates_shared_node_once(self, rng):
        x = _x(rng, 12, 5)
        a = ds.array(x)
        shared = a * 2.0
        out = (shared + shared.T.T) - shared   # shared appears 3x
        prof.reset_counters()
        got = out.collect()
        assert prof.counters()["dispatch_by"] == {"fused_chain": 1}
        np.testing.assert_allclose(got, x * 2.0, rtol=1e-6)

    def test_fusion_cap_bounds_program_size(self, rng, monkeypatch):
        monkeypatch.setenv("DSLIB_FUSION_CAP", "8")
        x = _x(rng, 8, 4)
        b = ds.array(x)
        for _ in range(20):
            b = b + 1.0
        # the chain must have forced itself at least once on the way
        assert b._lazy is None or b._lazy.n_ops < 8
        np.testing.assert_allclose(b.collect(), x + 20.0, rtol=1e-5)


class TestSingleDispatch:
    def test_three_op_chain_is_one_dispatch(self, rng):
        a = ds.array(_x(rng, 24, 10)).force()     # concrete leaf
        prof.reset_counters()
        chain = ds.matmul((a * 0.5).T, a).T       # scale → T → matmul → T
        assert chain.is_lazy
        assert prof.dispatch_count() == 0, "building the chain dispatched"
        chain.collect()
        assert prof.counters()["dispatch_by"] == {"fused_chain": 1}

    def test_eager_escape_hatch_pays_per_op(self, rng, monkeypatch):
        monkeypatch.setenv("DSLIB_EAGER", "1")
        a = ds.array(_x(rng, 24, 10))
        prof.reset_counters()
        ds.matmul((a * 0.5).T, a).T
        assert prof.dispatch_count() >= 4

    def test_repeat_chain_hits_program_cache(self, rng):
        a = ds.array(_x(rng, 16, 16)).force()
        ds.matmul((a + 1.0).T, a).collect()       # compile
        prof.reset_counters()
        ds.matmul((a + 1.0).T, a).collect()
        c = prof.counters()
        assert c["dispatch_by"].get("fused_chain") == 1
        assert c["traces"] == 0, "same-structure chain retraced"

    def test_force_points(self, rng):
        from dislib_tpu.runtime import fetch
        x = _x(rng, 10, 10)
        a = ds.array(x)
        s = (a * 2.0).sum(axis=None)
        assert s.is_lazy
        assert float(s) == pytest.approx(2.0 * x.sum(), rel=1e-5)
        assert not s.is_lazy                       # float() forced it
        t = (a + 1.0).T
        v = fetch(t)                               # snapshot fetch forces
        assert not t.is_lazy
        np.testing.assert_array_equal(v[: 10, : 10], (x + 1.0).T)

    def test_metadata_does_not_force(self, rng):
        a = ds.array(_x(rng, 33, 9))
        chain = (a * 2.0).T
        assert chain.shape == (9, 33)
        assert chain.dtype == jnp.float32
        assert chain.block_size is not None
        repr(chain)
        assert chain.is_lazy, "metadata access forced the chain"


class TestRetraceGuard:
    def test_fit_twice_same_shape_adds_no_traces(self, rng):
        x = ds.array(_x(rng, 57, 7))
        kw = dict(n_clusters=3, max_iter=4, tol=0.0, random_state=0)
        KMeans(**kw).fit(x)
        before = prof.counters()["trace_by"]
        KMeans(**kw).fit(x)
        after = prof.counters()["trace_by"]
        assert after.get("kmeans_fit", 0) == before.get("kmeans_fit", 0), \
            "same-shape refit recompiled the fit kernel"
        assert after.get("fused_chain", 0) == before.get("fused_chain", 0)

    def test_grid_search_3x3_compiles_each_kernel_once(self, rng):
        from dislib_tpu.model_selection import GridSearchCV
        x = ds.array(_x(rng, 90, 6))   # 90 % 3 == 0: all folds same shape

        def search():
            gs = GridSearchCV(KMeans(random_state=0, max_iter=3, tol=0.0),
                              {"n_clusters": [2, 3, 4]}, cv=3, refit=False)
            gs.fit(x)
            return gs

        search()                                    # compile pass
        before = prof.counters()["trace_by"]
        gs = search()                               # every kernel cached
        after = prof.counters()["trace_by"]
        assert len(gs.cv_results_["mean_test_score"]) == 3
        for kernel in ("kmeans_fit", "kmeans_score", "fused_chain"):
            assert after.get(kernel, 0) == before.get(kernel, 0), \
                f"3x3 grid search recompiled {kernel} on the second run"


class TestDonation:
    def test_als_chunk_carry_is_donated(self, rng):
        """The chunked-fit path: chunk N's factor outputs feed chunk N+1
        as init_state and must be donated (their sharding matches the
        outputs, so XLA aliases them — a fresh host-built donor may not)."""
        from dislib_tpu.recommendation.als import _als_fit
        r = rng.rand(24, 12).astype(np.float32)
        r[r < 0.5] = 0.0
        a = ds.array(r)
        out1 = _als_fit(a._data, a._data, a.shape, 4, 0.1, 0.0, 2, 0)
        u1, v1 = out1[0], out1[1]
        rmse1 = float(out1[2])
        u1.block_until_ready()
        out2 = _als_fit(a._data, a._data, a.shape, 4, 0.1, 0.0, 2, 0,
                        init_state=(u1, v1, rmse1))
        out2[0].block_until_ready()
        assert u1.is_deleted() and v1.is_deleted(), \
            "init_state factors were not donated (HBM double-buffered)"

    def test_forest_node_carry_is_donated(self, rng):
        from dislib_tpu.trees import RandomForestClassifier
        import dislib_tpu.trees.decision_tree as dt
        seen = []
        real = dt._forest_level

        def spy(node, *args, **kwargs):
            out = real(node, *args, **kwargs)
            seen.append(node)
            return out

        x = ds.array(_x(rng, 60, 5))
        y = ds.array((rng.rand(60, 1) > 0.5).astype(np.float32))
        try:
            dt._forest_level = spy
            RandomForestClassifier(n_estimators=2, max_depth=3,
                                   random_state=0).fit(x, y)
        finally:
            dt._forest_level = real
        # the level-0 input is a freshly-built zeros buffer whose layout
        # may not alias the sharded output; every LATER level's input is
        # the previous level's output and must be donated in place
        assert len(seen) >= 2
        assert all(n.is_deleted() for n in seen[1:]), \
            "forest node arrays were not donated"

    def test_donated_fits_pass_debug_checks(self, rng, tmp_path):
        """The ISSUE's `jax.debug` gate: chunked (checkpointed) fits that
        exercise every donation path run clean under jax_debug_nans."""
        from dislib_tpu.cluster import GaussianMixture
        from dislib_tpu.recommendation import ALS
        from dislib_tpu.utils import FitCheckpoint
        jax.config.update("jax_debug_nans", True)
        try:
            x = ds.array(_x(rng, 60, 4))
            km = KMeans(n_clusters=3, max_iter=4, tol=0.0, random_state=0) \
                .fit(x, checkpoint=FitCheckpoint(
                    str(tmp_path / "km.npz"), every=2))
            assert np.isfinite(km.inertia_)
            gm = GaussianMixture(n_components=2, max_iter=4, tol=0.0,
                                 random_state=0) \
                .fit(x, checkpoint=FitCheckpoint(
                    str(tmp_path / "gm.npz"), every=2))
            assert np.isfinite(gm.lower_bound_)
            r = rng.rand(30, 15).astype(np.float32)
            r[r < 0.5] = 0.0
            als = ALS(n_f=4, max_iter=4, tol=0.0, random_state=0) \
                .fit(ds.array(r), checkpoint=FitCheckpoint(
                    str(tmp_path / "als.npz"), every=2))
            assert np.isfinite(als.rmse_)
        finally:
            jax.config.update("jax_debug_nans", False)


class TestEagerParityOfResults:
    def test_estimator_results_identical_with_and_without_fusion(
            self, rng, monkeypatch):
        """End-to-end: a KMeans fit produces identical centers whether the
        Array layer fuses or dispatches eagerly — the estimators' own
        kernels bypass the fusion layer, and the fusion layer's force
        points feed them identical buffers."""
        x = _x(rng, 80, 5)
        init = np.ascontiguousarray(x[[3, 40, 77]])
        fused = KMeans(n_clusters=3, init=init, max_iter=5, tol=0.0) \
            .fit(ds.array(x)).centers_
        monkeypatch.setenv("DSLIB_EAGER", "1")
        eager = KMeans(n_clusters=3, init=init, max_iter=5, tol=0.0) \
            .fit(ds.array(x)).centers_
        np.testing.assert_array_equal(fused, eager)
