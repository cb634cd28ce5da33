"""KMeans tests (reference: tests/test_kmeans.py; oracle = sklearn KMeans on
the same data/init, SURVEY.md §5)."""

import numpy as np
import pytest

import dislib_tpu as ds
from dislib_tpu.cluster import KMeans


def _blobs(rng, n=300, d=4, k=3, spread=0.15):
    centers = rng.rand(k, d) * 10
    x = np.vstack([centers[i] + spread * rng.randn(n // k, d) for i in range(k)])
    labels = np.repeat(np.arange(k), n // k)
    return x.astype(np.float32), labels, centers.astype(np.float32)


class TestKMeans:
    def test_converges_on_blobs(self, rng):
        x, true_labels, _ = _blobs(rng)
        km = KMeans(n_clusters=3, max_iter=50, tol=1e-6, random_state=0)
        labels = km.fit_predict(ds.array(x)).collect().ravel().astype(int)
        # clustering equals ground truth up to label permutation
        for c in range(3):
            assert len(np.unique(labels[true_labels == c])) == 1
        assert km.n_iter_ <= 50
        assert km.inertia_ > 0

    def test_vs_sklearn_same_init(self, rng):
        from sklearn.cluster import KMeans as SkKMeans
        x, _, _ = _blobs(rng, n=240, d=5, k=4)
        init = x[rng.choice(len(x), 4, replace=False)]
        km = KMeans(n_clusters=4, init=init.copy(), max_iter=30, tol=0.0)
        km.fit(ds.array(x))
        sk = SkKMeans(n_clusters=4, init=init.copy(), n_init=1, max_iter=30,
                      tol=0.0, algorithm="lloyd").fit(x)
        # same init + Lloyd's ⇒ same final centers (order preserved)
        np.testing.assert_allclose(km.centers_, sk.cluster_centers_, atol=1e-3)
        np.testing.assert_allclose(km.inertia_, sk.inertia_, rtol=1e-4)

    def test_predict_matches_assignment(self, rng):
        x, _, _ = _blobs(rng, n=120)
        a = ds.array(x)
        km = KMeans(n_clusters=3, max_iter=20, random_state=1).fit(a)
        labels = km.predict(a).collect().ravel().astype(int)
        d = ((x[:, None, :] - km.centers_[None]) ** 2).sum(-1)
        np.testing.assert_array_equal(labels, d.argmin(1))

    def test_deterministic_with_seed(self, rng):
        x, _, _ = _blobs(rng)
        a = ds.array(x)
        c1 = KMeans(n_clusters=3, random_state=5).fit(a).centers_
        c2 = KMeans(n_clusters=3, random_state=5).fit(a).centers_
        np.testing.assert_array_equal(c1, c2)

    def test_score_is_negative_inertia(self, rng):
        x, _, _ = _blobs(rng, n=90)
        a = ds.array(x)
        km = KMeans(n_clusters=3, max_iter=20, random_state=2).fit(a)
        assert km.score(a) == pytest.approx(-km.inertia_, rel=1e-4)

    def test_explicit_init_bad_shape(self, rng):
        with pytest.raises(ValueError):
            KMeans(n_clusters=3, init=np.zeros((2, 2))).fit(ds.array(rng.rand(10, 4)))

    def test_irregular_rows(self, rng):
        # row count not divisible by mesh: padded rows must not perturb centers
        x, _, _ = _blobs(rng, n=231, d=3, k=3)
        x = x[:231]
        init = x[:3]
        km = KMeans(n_clusters=3, init=init.copy(), max_iter=10, tol=0.0)
        km.fit(ds.array(x))
        from sklearn.cluster import KMeans as SkKMeans
        sk = SkKMeans(n_clusters=3, init=init.copy(), n_init=1, max_iter=10,
                      tol=0.0, algorithm="lloyd").fit(x)
        np.testing.assert_allclose(km.centers_, sk.cluster_centers_, atol=1e-3)


def test_fast_distance_flag_matches(monkeypatch):
    """DSLIB_KMEANS_FAST_DISTANCE stores the E-step operand as bfloat16 —
    the same input rounding the TPU MXU applies at default precision, so
    the CPU rig now exercises the fast path's true numerics.  Gate:
    centers within bf16 tolerance, inertia within 1%."""
    import dislib_tpu as ds
    from dislib_tpu.cluster import KMeans

    rng = np.random.RandomState(5)
    data = rng.rand(200, 6).astype(np.float32)
    x = ds.array(data, block_size=(32, 6))
    init = data[:4].copy()
    km_ref = KMeans(n_clusters=4, init=init, max_iter=7, tol=0.0).fit(x)
    km_fast = KMeans(n_clusters=4, init=init, max_iter=7, tol=0.0,
                     fast_distance=True).fit(x)
    monkeypatch.setenv("DSLIB_KMEANS_FAST_DISTANCE", "1")
    km_env = KMeans(n_clusters=4, init=init, max_iter=7, tol=0.0).fit(x)
    np.testing.assert_allclose(km_env.centers_, km_fast.centers_, rtol=1e-6)
    np.testing.assert_allclose(km_fast.centers_, km_ref.centers_,
                               rtol=2e-2, atol=2e-2)
    # 7 iterations on 200 points: a few bf16 boundary flips can drift the
    # trajectory to a nearby local optimum — gate on objective QUALITY (1%)
    np.testing.assert_allclose(km_fast.inertia_, km_ref.inertia_, rtol=1e-2)


# -- the fused Lloyd step (ops/base.py::lloyd_step over the Pallas kernel) ----
# On the CPU the kernel runs interpreted, and only where a test asks for it:
# the route's default here is the two-pass XLA step.

def _two_pass_step(x, w, c):
    """The two-pass step's reductions at float32-faithful precision, with
    the labels."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        d = jnp.maximum(jnp.sum(x * x, 1)[:, None] - 2.0 * x @ c.T
                        + jnp.sum(c * c, 1)[None, :], 0.0)
        labels = jnp.argmin(d, axis=1)
        onehot = jax.nn.one_hot(labels, c.shape[0], dtype=x.dtype) * w[:, None]
        return (onehot.T @ x, jnp.sum(onehot, axis=0),
                jnp.sum(jnp.min(d, axis=1) * w), labels)


def _kernel_step(x, w, c, block, chunk):
    import jax
    import jax.numpy as jnp
    from dislib_tpu.ops import pallas_kernels as pk
    return jax.jit(pk.kmeans_step, static_argnums=(4, 5))(
        x, jnp.sum(x * x, 1)[None, :], w[None, :], c, block, chunk)


@pytest.fixture
def fused_on_cpu(monkeypatch):
    """Route dense float32 fits through the fused step on this backend,
    with tiles small enough for the interpreter: blocks of 512 rows of
    d <= 104 features, chunks of 256."""
    from dislib_tpu.cluster import kmeans as km
    from dislib_tpu.ops import base
    from dislib_tpu.utils import profiling
    monkeypatch.setattr(base, "_LLOYD_BACKENDS", ("tpu", "cpu"))
    monkeypatch.setattr(base, "_LLOYD_X_VMEM", 2 * 104 * 4 * 512)
    monkeypatch.setattr(base, "_LLOYD_CHUNK", 256)
    km._kmeans_fit.jitted.clear_cache()
    profiling.reset_counters()
    yield profiling
    km._kmeans_fit.jitted.clear_cache()


class TestFusedLloydStep:
    @pytest.mark.parametrize("rows,d,k,block,chunk", [
        (1024, 100, 10, 512, 256),      # d and k off the tile, whole blocks
        (1300, 100, 10, 512, 256),      # ragged: 2 x 128 rows and 20 more
        (1100, 100, 10, 512, 128),      # ragged by less than a lane tile
        (640, 7, 3, 384, 128),          # ragged by whole lane tiles
        (512, 36, 17, 256, 256),        # k over two sublane tiles
    ])
    def test_step_matches_two_pass(self, rng, rows, d, k, block, chunk):
        import jax.numpy as jnp
        x = jnp.asarray(rng.randn(rows, d).astype(np.float32))
        c = jnp.asarray(rng.randn(k, d).astype(np.float32))
        w = jnp.ones((rows,), jnp.float32)
        sums, counts, inertia = _kernel_step(x, w, c, block, chunk)
        want_sums, want_counts, want_inertia, _ = _two_pass_step(x, w, c)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        np.testing.assert_allclose(sums, want_sums, rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(inertia, want_inertia, rtol=1e-6)

    def test_padded_rows_weigh_nothing(self, rng):
        import jax.numpy as jnp
        x = rng.randn(1024, 100).astype(np.float32)
        x[1000:] = 0.0                       # what ds.array pads with
        c = jnp.asarray(rng.randn(10, 100).astype(np.float32))
        w = jnp.asarray((np.arange(1024) < 1000).astype(np.float32))
        sums, counts, inertia = _kernel_step(jnp.asarray(x), w, c, 512, 256)
        ones = jnp.ones((1000,), jnp.float32)
        want = _two_pass_step(jnp.asarray(x[:1000]), ones, c)
        assert float(jnp.sum(counts)) == 1000.0
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(want[1]))
        np.testing.assert_allclose(sums, want[0], rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(inertia, want[2], rtol=1e-6)

    def test_ties_go_to_the_first_minimum(self):
        import jax.numpy as jnp
        # centres 1 and 3 are the same point: jnp.argmin names 1, never 3
        c = np.zeros((4, 5), np.float32)
        c[0] += 5.0
        c[2] -= 5.0
        c[1] = c[3] = 1.0
        x = jnp.asarray(np.ones((256, 5), np.float32))
        _, counts, _ = _kernel_step(x, jnp.ones((256,), jnp.float32),
                                    jnp.asarray(c), 256, 128)
        np.testing.assert_array_equal(np.asarray(counts), [0, 256, 0, 0])

    def test_tiles_follow_the_shapes(self):
        import jax.numpy as jnp
        from dislib_tpu.ops.base import lloyd_tiles
        f32 = jnp.float32
        # the benchmark's cell: 15 chunks of 3200 rows divide 12M rows
        assert lloyd_tiles(12_000_000, 100, 10, f32) == (48000, 3200)
        # chip_smoke's 1M rows: no whole block divides them, the last is ragged
        block, chunk = lloyd_tiles(1_000_000, 100, 10, f32)
        assert chunk == 3200 and block % chunk == 0 and 1_000_000 % block
        # more centres, a shorter chunk: the (k, chunk) tile stays in vregs
        assert lloyd_tiles(12_000_000, 100, 64, f32)[1] == 768
        assert lloyd_tiles(20_000, 100, 10, f32) is None      # under a block
        assert lloyd_tiles(12_000_000, 128, 10, f32) is None  # X rows-major
        assert lloyd_tiles(12_000_000, 100, 4096, f32) is None
        assert lloyd_tiles(12_000_000, 100, 10, jnp.bfloat16) is None

    def test_fit_both_ways_same_centres(self, rng, fused_on_cpu, monkeypatch):
        x, _, centres = _blobs(rng, n=6000, d=100, k=10, spread=0.5)
        x = x[rng.permutation(len(x))][:5600]   # 700 rows a device: ragged
        # a start near each component: no row sits on a boundary, where
        # the two steps' last bits would send it to different sides
        init = centres + 0.3 * rng.randn(10, 100).astype(np.float32)
        a = ds.array(x)
        fused = KMeans(n_clusters=10, init=init, max_iter=8, tol=0.0).fit(a)
        assert fused_on_cpu.schedule_counters() == {"kmeans_step:fused": 1}
        plain = KMeans(n_clusters=10, init=init, max_iter=8, tol=0.0,
                       fast_distance=False)
        from dislib_tpu.cluster import kmeans as km
        from dislib_tpu.ops import base
        monkeypatch.setattr(base, "_LLOYD_BACKENDS", ("tpu",))
        km._kmeans_fit.jitted.clear_cache()
        plain.fit(a)
        assert fused_on_cpu.schedule_counters() == {
            "kmeans_step:fused": 1, "kmeans_step:two_pass": 1}
        assert fused.n_iter_ == plain.n_iter_ == 8
        np.testing.assert_allclose(fused.centers_, plain.centers_,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(fused.history_, plain.history_, rtol=1e-5)
        np.testing.assert_allclose(fused.inertia_, plain.inertia_, rtol=1e-5)

    def test_empty_cluster_keeps_its_centre(self, rng, fused_on_cpu):
        x, _, _ = _blobs(rng, n=6000, d=100, k=3)
        init = np.vstack([x[:3], np.full((1, 100), 1e3, np.float32)])
        km = KMeans(n_clusters=4, init=init.copy(), max_iter=5, tol=0.0) \
            .fit(ds.array(x))
        assert fused_on_cpu.schedule_counters() == {"kmeans_step:fused": 1}
        np.testing.assert_array_equal(km.centers_[3], init[3])
        assert np.all(np.isfinite(km.centers_))

    def test_one_dispatch_and_three_reads_a_fit(self, rng, fused_on_cpu):
        x, _, _ = _blobs(rng, n=6000, d=100, k=3)
        a = ds.array(x)
        KMeans(n_clusters=3, init=x[:3].copy(), max_iter=4, tol=0.0).fit(a)
        prof = fused_on_cpu
        before = prof.dispatch_count(), prof.transfer_count(), \
            prof.trace_count()
        KMeans(n_clusters=3, init=x[3:6].copy(), max_iter=4, tol=0.0).fit(a)
        assert prof.dispatch_count() - before[0] == 1
        assert prof.transfer_count() - before[1] == 3
        assert prof.trace_count() == before[2]
        assert prof.schedule_counters() == {"kmeans_step:fused": 1}

    def test_one_all_reduce_on_the_rows_mesh(self, rng, fused_on_cpu):
        import jax
        import jax.numpy as jnp
        from dislib_tpu.cluster.kmeans import _kmeans_fit
        from dislib_tpu.utils.profiling import op_graph
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices for the (4, 1) mesh")
        ds.init((4, 1), devices=jax.devices()[:4])
        x = ds.array(rng.randn(4096, 100).astype(np.float32))
        c0 = jnp.asarray(rng.randn(10, 100).astype(np.float32))
        hlo = op_graph(lambda xp, c: _kmeans_fit(xp, x.shape, c, 3, 0.0),
                       x._data, c0)
        assert fused_on_cpu.schedule_counters() == {"kmeans_step:fused": 1}
        assert hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(") \
            == 1, "the step's three partials cross the mesh in one psum"
        assert "all-gather" not in hlo and "all-to-all" not in hlo
        assert "/dslib.kmeans.step/" in hlo

    def test_gaussian_mixture_start_runs(self, rng, fused_on_cpu):
        from dislib_tpu.cluster import GaussianMixture
        x, _, _ = _blobs(rng, n=6000, d=100, k=3)
        gm = GaussianMixture(n_components=3, max_iter=3, random_state=0) \
            .fit(ds.array(x))
        assert fused_on_cpu.schedule_counters().get("kmeans_step:fused") == 1
        assert np.all(np.isfinite(gm.means_))


class TestLloydStepRoute:
    """What stays on the two-pass step, by the counter the choice bumps."""

    def _route(self, prof):
        return {key: n for key, n in prof.schedule_counters().items()
                if key.startswith("kmeans_step:")}

    def test_cpu_default_is_two_pass(self, rng):
        from dislib_tpu.cluster import kmeans as km
        from dislib_tpu.utils import profiling
        km._kmeans_fit.jitted.clear_cache()
        profiling.reset_counters()
        x, _, _ = _blobs(rng, n=6000, d=20, k=3)
        KMeans(n_clusters=3, max_iter=2, random_state=0).fit(ds.array(x))
        assert self._route(profiling) == {"kmeans_step:two_pass": 1}

    def test_fast_distance_is_two_pass(self, rng, fused_on_cpu):
        x, _, _ = _blobs(rng, n=6000, d=100, k=3)
        KMeans(n_clusters=3, max_iter=2, random_state=0,
               fast_distance=True).fit(ds.array(x))
        assert self._route(fused_on_cpu) == {"kmeans_step:two_pass": 1}

    def test_tiny_input_is_two_pass(self, rng, fused_on_cpu):
        x, _, _ = _blobs(rng, n=300, d=20, k=3)
        KMeans(n_clusters=3, max_iter=2, random_state=0).fit(ds.array(x))
        assert self._route(fused_on_cpu) == {"kmeans_step:two_pass": 1}

    def test_other_dtype_is_two_pass(self, rng, fused_on_cpu):
        import jax.numpy as jnp
        from dislib_tpu.cluster.kmeans import _kmeans_fit
        x = jnp.asarray(rng.randn(8192, 100), jnp.bfloat16)
        c0 = jnp.asarray(rng.randn(3, 100), jnp.bfloat16)
        _kmeans_fit.eval_shape(x, (8192, 100), c0, 2, 0.0)
        assert self._route(fused_on_cpu) == {"kmeans_step:two_pass": 1}

    def test_sparse_path_is_untouched(self, rng, fused_on_cpu):
        import scipy.sparse as sp
        from dislib_tpu.data.sparse import SparseArray
        xs = SparseArray.from_scipy(sp.random(
            6000, 100, density=0.05, format="csr", dtype=np.float32,
            random_state=0))
        KMeans(n_clusters=3, max_iter=2, random_state=0).fit(xs)
        assert self._route(fused_on_cpu) == {}
