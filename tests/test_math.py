"""Blocked-math tests (reference: test_matmul/test_kron/test_svd/test_qr/
test_tsqr/test_randomsvd/test_lanczos/test_pca — SURVEY.md §5 oracle pattern)."""

import os

import numpy as np
import pytest

import dislib_tpu as ds


class TestMatmul:
    @pytest.mark.parametrize("shapes", [((8, 8), (8, 8)), ((17, 5), (5, 9)),
                                        ((1, 7), (7, 1)), ((33, 65), (65, 12))])
    def test_matmul(self, rng, shapes):
        (m, k), (_, n) = shapes
        x, y = rng.rand(m, k), rng.rand(k, n)
        got = ds.matmul(ds.array(x), ds.array(y)).collect()
        np.testing.assert_allclose(got, x @ y, rtol=1e-4, atol=1e-5)

    def test_transposes(self, rng):
        x, y = rng.rand(12, 7), rng.rand(12, 9)
        got = ds.matmul(ds.array(x), ds.array(y), transpose_a=True).collect()
        np.testing.assert_allclose(got, x.T @ y, rtol=1e-4)
        x, y = rng.rand(7, 12), rng.rand(9, 12)
        got = ds.matmul(ds.array(x), ds.array(y), transpose_b=True).collect()
        np.testing.assert_allclose(got, x @ y.T, rtol=1e-4)
        got = ds.matmul(ds.array(x.T), ds.array(y), transpose_a=True,
                        transpose_b=True).collect()
        np.testing.assert_allclose(got, x @ y.T, rtol=1e-4)

    def test_operator(self, rng):
        x, y = rng.rand(6, 4), rng.rand(4, 5)
        np.testing.assert_allclose((ds.array(x) @ ds.array(y)).collect(), x @ y,
                                   rtol=1e-4)

    def test_mismatch_raises(self, rng):
        with pytest.raises(ValueError):
            ds.matmul(ds.array(rng.rand(3, 4)), ds.array(rng.rand(3, 4)))


class TestKron:
    def test_kron(self, rng):
        a, b = rng.rand(3, 4), rng.rand(5, 2)
        np.testing.assert_allclose(ds.kron(ds.array(a), ds.array(b)).collect(),
                                   np.kron(a, b), rtol=1e-5)

    def test_kron_irregular(self, rng):
        a, b = rng.rand(7, 3), rng.rand(2, 9)
        np.testing.assert_allclose(ds.kron(ds.array(a), ds.array(b)).collect(),
                                   np.kron(a, b), rtol=1e-5)

    def test_kron_large_product_stays_sharded(self, rng):
        """VERDICT r2 #8: an 8192x8192 product (256 MB f32) — far past a
        single virtual device's plausible share — computes with each device
        holding only its output shard plus the (small) operands."""
        a = ds.array(rng.rand(512, 512).astype(np.float32))
        b = ds.array(rng.rand(16, 16).astype(np.float32))
        c = ds.kron(a, b)
        assert c.shape == (8192, 8192)
        total = 8192 * 8192 * 4
        ndev = len({s.device for s in c._data.addressable_shards})
        for s in c._data.addressable_shards:
            assert s.data.nbytes <= total // ndev
        # spot-check values without materialising np.kron on host
        ah, bh = a.collect(), b.collect()
        got = np.asarray(c._data[1000:1002, 2000:2004])
        want = np.stack([
            [ah[r // 16, cc // 16] * bh[r % 16, cc % 16]
             for cc in range(2000, 2004)] for r in range(1000, 1002)])
        np.testing.assert_allclose(got, want, rtol=1e-5)
        # global invariant: sum(kron(a,b)) == sum(a)·sum(b)
        np.testing.assert_allclose(
            float(c.sum(axis=None).collect()[0, 0]),
            float(ah.sum()) * float(bh.sum()), rtol=1e-3)


class TestQR:
    @pytest.mark.parametrize("shape", [(16, 16), (20, 8), (9, 9)])
    def test_full(self, rng, shape):
        x = rng.rand(*shape)
        q, r = ds.qr(ds.array(x), mode="full")
        qc, rc = q.collect(), r.collect()
        assert qc.shape == (shape[0], shape[0])
        np.testing.assert_allclose(qc @ rc, x, atol=1e-4)
        np.testing.assert_allclose(qc.T @ qc, np.eye(shape[0]), atol=1e-4)
        np.testing.assert_allclose(np.tril(rc[:, :shape[1]], -1), 0, atol=1e-5)

    def test_economic(self, rng):
        x = rng.rand(20, 6)
        q, r = ds.qr(ds.array(x), mode="economic")
        assert q.collect().shape == (20, 6)
        assert r.collect().shape == (6, 6)
        np.testing.assert_allclose(q.collect() @ r.collect(), x, atol=1e-4)

    def test_r_mode(self, rng):
        x = rng.rand(10, 4)
        r = ds.qr(ds.array(x), mode="r").collect()
        rn = np.linalg.qr(x, mode="r")
        np.testing.assert_allclose(np.abs(r), np.abs(rn), atol=1e-4)

    def test_bad_mode(self, rng):
        with pytest.raises(ValueError):
            ds.qr(ds.array(rng.rand(4, 4)), mode="zzz")


class TestBlockedQR:
    """The distributed panel-loop path (VERDICT r1 #5): tsQR panels +
    sharded trailing GEMMs, full operand never gathered."""

    @pytest.mark.parametrize("shape", [(256, 130), (300, 97), (192, 64)])
    def test_invariants_irregular(self, rng, shape, monkeypatch):
        import importlib
        qr_mod = importlib.import_module("dislib_tpu.math.qr")
        monkeypatch.setattr(qr_mod, "_PANEL", 32)
        x = rng.rand(*shape).astype(np.float32)
        q, r = ds.qr(ds.array(x, block_size=(64, 32)), mode="economic")
        qc, rc = q.collect(), r.collect()
        assert qc.shape == shape and rc.shape == (shape[1], shape[1])
        np.testing.assert_allclose(qc @ rc, x, atol=1e-3)
        np.testing.assert_allclose(qc.T @ qc, np.eye(shape[1]), atol=1e-3)
        np.testing.assert_allclose(np.tril(rc, -1), 0, atol=1e-4)

    @pytest.mark.parametrize("shape", [(256, 64), (320, 40)])
    def test_full_mode_distributed(self, rng, shape, monkeypatch):
        """VERDICT r2 #5: mode='full' runs the panel loop + random-completion
        complement at blocked sizes — Q (m, m) orthonormal, QR == A."""
        import importlib
        qr_mod = importlib.import_module("dislib_tpu.math.qr")
        monkeypatch.setattr(qr_mod, "_PANEL", 32)
        m, n = shape
        x = rng.rand(m, n).astype(np.float32)
        q, r = ds.qr(ds.array(x), mode="full")
        qc, rc = q.collect(), r.collect()
        assert qc.shape == (m, m) and rc.shape == (m, n)
        np.testing.assert_allclose(qc @ rc, x, atol=1e-3)
        np.testing.assert_allclose(qc.T @ qc, np.eye(m), atol=1e-3)
        np.testing.assert_allclose(np.tril(rc[:n, :n], -1), 0, atol=1e-4)
        assert np.allclose(rc[n:], 0)

    def test_r_mode_matches_numpy(self, rng, monkeypatch):
        import importlib
        qr_mod = importlib.import_module("dislib_tpu.math.qr")
        monkeypatch.setattr(qr_mod, "_PANEL", 32)
        x = rng.rand(256, 80).astype(np.float32)
        r = ds.qr(ds.array(x), mode="r").collect()
        rn = np.linalg.qr(x, mode="r")
        np.testing.assert_allclose(np.abs(r), np.abs(rn), atol=1e-3)

    def test_never_gathers_full_operand(self, rng):
        """Compiled-HLO assertion: on a multi-device rows mesh, no
        all-gather materialises the full (mp, n_pad) operand."""
        import jax
        import jax.numpy as jnp
        from dislib_tpu.math.qr import _qr_blocked
        from dislib_tpu.parallel import mesh as _mesh
        mesh = _mesh.get_mesh()
        p = mesh.shape[_mesh.ROWS]
        if p == 1:
            pytest.skip("needs a multi-device rows axis")
        mp, n = 2048 * p, 1024
        ap = jax.device_put(jnp.zeros((mp, n), jnp.float32),
                            _mesh.row_sharding())
        compiled = _qr_blocked.lower(ap, (mp, n), mesh, p, 256,
                                     cholqr=False).compile()
        hlo = compiled.as_text()
        full_elems = (mp * n)
        import re
        for m_ in re.finditer(r"all-gather[^\n]*f32\[([\d,]+)\]", hlo):
            dims = [int(d) for d in m_.group(1).split(",")]
            elems = 1
            for d in dims:
                elems *= d
            assert elems < full_elems, \
                f"all-gather of {dims} covers the full operand"


class TestTSQR:
    @pytest.mark.parametrize("shape", [(64, 8), (100, 13), (8, 8), (1000, 3)])
    def test_reduced(self, rng, shape):
        x = rng.rand(*shape)
        q, r = ds.tsqr(ds.array(x))
        qc, rc = q.collect(), r.collect()
        assert qc.shape == shape and rc.shape == (shape[1], shape[1])
        np.testing.assert_allclose(qc @ rc, x, atol=1e-4)
        np.testing.assert_allclose(qc.T @ qc, np.eye(shape[1]), atol=1e-4)

    def test_r_mode(self, rng):
        x = rng.rand(64, 4)
        r = ds.tsqr(ds.array(x), mode="r").collect()
        # R unique up to row signs
        rn = np.linalg.qr(x, mode="r")
        np.testing.assert_allclose(np.abs(r), np.abs(rn), atol=1e-4)

    def test_wide_raises(self, rng):
        with pytest.raises(ValueError):
            ds.tsqr(ds.array(rng.rand(4, 8)))

    def test_local_tree_path(self, rng):
        # shard rows (512/8 = 64) ≥ 16·n with power-of-two divisibility, so
        # _local_tsqr actually recurses (s > 1) instead of degrading to one
        # flat QR — pin the batched-tree path's invariants
        from dislib_tpu.decomposition.tsqr import _split_count
        assert _split_count(512, 2) > 1            # tree engaged at this shape
        x = rng.rand(512, 2)
        q, r = ds.tsqr(ds.array(x))
        qc, rc = q.collect(), r.collect()
        np.testing.assert_allclose(qc @ rc, x, atol=1e-4)
        np.testing.assert_allclose(qc.T @ qc, np.eye(2), atol=1e-4)
        assert np.allclose(rc, np.triu(rc))


class TestSVD:
    @pytest.mark.parametrize("shape", [(16, 8), (30, 30), (50, 7)])
    def test_svd(self, rng, shape):
        x = rng.rand(*shape)
        u, s, v = ds.svd(ds.array(x))
        uc, sc, vc = u.collect(), s.collect().ravel(), v.collect()
        sn = np.linalg.svd(x, compute_uv=False)
        np.testing.assert_allclose(sc, sn, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(uc * sc @ vc.T, x, atol=1e-3)
        np.testing.assert_allclose(uc.T @ uc, np.eye(shape[1]), atol=1e-3)
        np.testing.assert_allclose(vc.T @ vc, np.eye(shape[1]), atol=1e-3)

    def test_values_only(self, rng):
        x = rng.rand(12, 6)
        s = ds.svd(ds.array(x), compute_uv=False).collect().ravel()
        np.testing.assert_allclose(s, np.linalg.svd(x, compute_uv=False),
                                   rtol=1e-3, atol=1e-4)


class TestRandomSVD:
    def test_low_rank_recovery(self, rng):
        # rank-5 matrix: randomized SVD should nail the spectrum
        a = rng.rand(60, 5) @ rng.rand(5, 40)
        u, s, v = ds.random_svd(ds.array(a), nsv=5, random_state=0)
        sn = np.linalg.svd(a, compute_uv=False)[:5]
        np.testing.assert_allclose(s.collect().ravel(), sn, rtol=1e-3)
        np.testing.assert_allclose((u.collect() * s.collect().ravel()) @ v.collect().T,
                                   a, atol=1e-2)

    def test_irregular_shape(self, rng):
        # rows/cols not multiples of the device count or pad quantum
        a = rng.rand(61, 6) @ rng.rand(6, 37)
        u, s, v = ds.random_svd(ds.array(a), nsv=6, random_state=3)
        sn = np.linalg.svd(a, compute_uv=False)[:6]
        np.testing.assert_allclose(s.collect().ravel(), sn, rtol=1e-3)
        np.testing.assert_allclose((u.collect() * s.collect().ravel()) @ v.collect().T,
                                   a, atol=1e-2)

    def test_fused_matches_composed(self, rng):
        # the m >= sketch fast path is a single jitted program; the m < sketch
        # case runs the original host-composed stages.  Same seed → same
        # Gaussian test matrix → the two paths must agree on the (converged)
        # spectrum and subspace reconstruction.
        a = rng.rand(80, 5) @ rng.rand(5, 30)
        u1, s1, v1 = ds.random_svd(ds.array(a), nsv=5, random_state=7)

        from dislib_tpu.data.array import Array

        class _View(Array):  # fails the `type(a) is Array` fast-path gate
            pass

        composed = ds.array(a)
        composed.__class__ = _View
        u2, s2, v2 = ds.random_svd(composed, nsv=5, random_state=7)
        np.testing.assert_allclose(s1.collect(), s2.collect(), rtol=1e-4)
        r1 = (u1.collect() * s1.collect().ravel()) @ v1.collect().T
        r2 = (u2.collect() * s2.collect().ravel()) @ v2.collect().T
        np.testing.assert_allclose(r1, r2, atol=1e-4)

    def test_wide_fallback(self, rng):
        # m < sketch exercises the composed path's economic-QR fallback
        a = rng.rand(8, 40)
        u, s, v = ds.random_svd(ds.array(a), nsv=4, oversample=10,
                                random_state=0)
        sn = np.linalg.svd(a, compute_uv=False)[:4]
        np.testing.assert_allclose(s.collect().ravel(), sn, rtol=1e-2)


class TestLanczosSVD:
    def test_spectrum(self, rng):
        x = rng.rand(40, 20)
        _, s, _ = ds.lanczos_svd(ds.array(x), k=4)
        sn = np.linalg.svd(x, compute_uv=False)[:4]
        np.testing.assert_allclose(s.collect().ravel(), sn, rtol=1e-2)


class TestPCA:
    def test_vs_sklearn(self, rng):
        from sklearn.decomposition import PCA as SkPCA
        x = rng.rand(100, 10).astype(np.float32)
        p = ds.PCA(n_components=4).fit(ds.array(x))
        sk = SkPCA(n_components=4).fit(x)
        np.testing.assert_allclose(p.explained_variance_.collect().ravel(),
                                   sk.explained_variance_, rtol=1e-3)
        np.testing.assert_allclose(np.abs(p.components_.collect()),
                                   np.abs(sk.components_), atol=1e-3)
        np.testing.assert_allclose(p.mean_.collect().ravel(), sk.mean_, rtol=1e-4)

    def test_transform_roundtrip(self, rng):
        x = rng.rand(50, 8).astype(np.float32)
        p = ds.PCA()  # all components
        t = p.fit_transform(ds.array(x))
        back = p.inverse_transform(t).collect()
        np.testing.assert_allclose(back, x, atol=1e-3)

    def test_svd_method(self, rng):
        x = rng.rand(60, 6).astype(np.float32)
        p = ds.PCA(n_components=3, method="svd").fit(ds.array(x))
        from sklearn.decomposition import PCA as SkPCA
        sk = SkPCA(n_components=3).fit(x)
        np.testing.assert_allclose(p.explained_variance_.collect().ravel(),
                                   sk.explained_variance_, rtol=1e-3)


class TestBlockJacobiSVD:
    def test_block_tier_matches_numpy(self, rng):
        # n >= 2*_JACOBI_BLOCK engages the block tier; include a ragged n
        # so the zero pad block exercises the NaN-proof off metric
        for (m, n) in [(300, 130), (200, 150)]:
            x = rng.rand(m, n).astype(np.float32)
            u, s, v = ds.svd(ds.array(x))
            uc, sc, vc = u.collect(), np.asarray(s.collect()).ravel(), v.collect()
            s_ref = np.linalg.svd(x, compute_uv=False)
            np.testing.assert_allclose(sc, s_ref, rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(uc @ np.diag(sc) @ vc.T, x, atol=1e-3)
            np.testing.assert_allclose(uc.T @ uc, np.eye(n), atol=1e-3)
            np.testing.assert_allclose(vc.T @ vc, np.eye(n), atol=1e-3)

    def test_block_tier_engaged(self):
        from dislib_tpu.math.base import _JACOBI_BLOCK
        assert 130 >= 2 * _JACOBI_BLOCK  # shapes above actually take the tier

    def test_block_tier_ill_conditioned(self, rng):
        """6-decade geometric spectrum: errors stay at the f32 floor
        relative to sigma_max, orthogonality at machine precision, no NaN
        (the QR+small-SVD pair solve is conditioning-independent)."""
        m, n = 600, 192
        u0, _ = np.linalg.qr(rng.standard_normal((m, n)))
        v0, _ = np.linalg.qr(rng.standard_normal((n, n)))
        sv = np.logspace(3, -3, n).astype(np.float32)
        x = ((u0 * sv) @ v0.T).astype(np.float32)
        u, s, v = ds.svd(ds.array(x))
        sc = np.asarray(s.collect()).ravel()
        s_ref = np.linalg.svd(x, compute_uv=False)
        assert not np.isnan(sc).any()
        assert np.abs(sc - s_ref).max() / s_ref[0] < 1e-4
        uc, vc = u.collect(), v.collect()
        np.testing.assert_allclose(uc.T @ uc, np.eye(n), atol=1e-4)
        np.testing.assert_allclose(vc.T @ vc, np.eye(n), atol=1e-4)


class TestCholQR2:
    """Round-4 TPU fast path: CholeskyQR2 local factorisation (forced via
    DSLIB_TSQR_CHOLQR=1 on the rig — the auto policy enables it on TPU)."""

    def _force(self, monkeypatch):
        monkeypatch.setenv("DSLIB_TSQR_CHOLQR", "1")

    def test_tsqr_cholqr_matches_oracle(self, rng, monkeypatch):
        self._force(monkeypatch)
        x = rng.standard_normal((1024, 32)).astype(np.float32)
        q, r = ds.tsqr(ds.array(x, block_size=(128, 32)))
        qh, rh = np.asarray(q.collect()), np.asarray(r.collect())
        np.testing.assert_allclose(qh @ rh, x, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(qh.T @ qh, np.eye(32), atol=5e-5)
        # R upper triangular
        assert np.allclose(rh, np.triu(rh), atol=1e-6)

    def test_cholqr_breakdown_falls_back_exact(self, rng, monkeypatch):
        """Numerically singular columns break the Gram Cholesky; the
        in-program fallback must deliver tree-QR accuracy anyway."""
        self._force(monkeypatch)
        base = rng.standard_normal((512, 8)).astype(np.float32)
        x = np.hstack([base, base + 1e-8 * rng.standard_normal((512, 8))
                       .astype(np.float32)]).astype(np.float32)
        q, r = ds.tsqr(ds.array(x, block_size=(64, 16)))
        qh, rh = np.asarray(q.collect()), np.asarray(r.collect())
        np.testing.assert_allclose(qh @ rh, x, rtol=1e-3, atol=1e-3)
        # orthogonality of the RANGE part still holds to tree-QR quality
        assert np.abs(qh.T @ qh - np.eye(16)).max() < 1e-2

    @pytest.mark.skipif(os.environ.get("DSLIB_TEST_TPU") != "1",
                        reason="breakdown band is an MXU-rounding property "
                               "— meaningful on the real chip only")
    def test_cholqr_breakdown_band_on_chip(self, rng, monkeypatch):
        """Round-5 (VERDICT #3): probe the cond(A) band around u^(-1/2)
        under the actual MXU rounding the `precise`-scoped Gram gets on
        chip.  Sweep cond 1e2 → 1e8 with forced cholqr: the quality gate's
        `ok` must hold at benign cond, the fallback MUST fire by 1e6, and
        end-to-end orthogonality stays < 1e-3 at every cond (lose speed,
        never accuracy)."""
        self._force(monkeypatch)
        import jax
        from dislib_tpu.decomposition.tsqr import _cholqr2
        from dislib_tpu.ops.base import precise
        m, n = 4096, 128
        u0, _ = np.linalg.qr(rng.standard_normal((m, n)))
        v0, _ = np.linalg.qr(rng.standard_normal((n, n)))
        gate = jax.jit(precise(_cholqr2))
        oks = {}
        for cond in (1e2, 1e4, 1e6, 1e8):
            spec = np.logspace(0, -np.log10(cond), n).astype(np.float32)
            x = ((u0 * spec) @ v0.T).astype(np.float32)
            *_, ok = gate(x)
            oks[cond] = bool(ok)
            q, r = ds.tsqr(ds.array(x, block_size=(512, n)))
            qh, rh = np.asarray(q.collect()), np.asarray(r.collect())
            ortho = np.abs(qh.T @ qh - np.eye(n)).max()
            assert ortho < 1e-3, f"cond={cond:g}: orthogonality {ortho}"
            assert np.abs(qh @ rh - x).max() < 1e-3 * spec[0], \
                f"cond={cond:g}: reconstruction"
        assert oks[1e2], f"quality gate refused a benign matrix: {oks}"
        assert not oks[1e6] and not oks[1e8], \
            f"fallback did not fire in the breakdown band: {oks}"

    def test_randomsvd_and_blocked_qr_with_cholqr(self, rng, monkeypatch):
        self._force(monkeypatch)
        from dislib_tpu.decomposition import random_svd
        # decaying spectrum: randomized SVD is only accurate when the tail
        # is well separated (a flat gaussian spectrum is ~5% off for ANY
        # local-QR flavor — verified identical with the tree path)
        u0, _ = np.linalg.qr(rng.standard_normal((512, 64)))
        v0, _ = np.linalg.qr(rng.standard_normal((64, 64)))
        spec = (2.0 ** -np.arange(64)).astype(np.float32) * 100
        x = (u0 * spec) @ v0.T
        x = x.astype(np.float32)
        u, s, v = random_svd(ds.array(x, block_size=(64, 64)), iters=2,
                             nsv=8, oversample=8, random_state=0)
        s_ref = np.linalg.svd(x, compute_uv=False)
        np.testing.assert_allclose(np.asarray(s.collect()).ravel()[:8],
                                   s_ref[:8], rtol=1e-2)
        # force the BLOCKED qr path (panel loop + cholqr local factors):
        # the default _PANEL (256) would route 64 columns to the
        # replicated fallback kernel, skipping the integration under test
        import importlib
        qr_mod = importlib.import_module("dislib_tpu.math.qr")
        monkeypatch.setattr(qr_mod, "_PANEL", 16)
        qf, rf = ds.qr(ds.array(x, block_size=(64, 64)))
        np.testing.assert_allclose(
            np.asarray(qf.collect()) @ np.asarray(rf.collect()), x,
            rtol=1e-3, atol=1e-3)


def _numpy_random_svd(x, sketch, iters, seed=0):
    rng = np.random.RandomState(seed)
    omega = rng.standard_normal((x.shape[1], sketch)).astype(np.float32)
    q, _ = np.linalg.qr(x @ omega)
    for _ in range(iters):
        qz, _ = np.linalg.qr(x.T @ q)
        q, _ = np.linalg.qr(x @ qz)
    b = q.T @ x
    ub, s, vt = np.linalg.svd(b, full_matrices=False)
    return q @ ub, s, vt


def test_randomsvd_smoke_gate_margin(rng):
    """Pins ``random_svd`` against a NumPy proxy of the same sketch and
    against the exact spectrum.

    On a FLAT Gaussian spectrum with oversample=10 the device path and the
    proxy each carry ~6% subspace error and — because they draw different
    test matrices Ω (jax vs numpy RNG) — differ from EACH OTHER by up to
    ~1.5%.  So the columns are scaled by 0.95^j, the decaying spectrum
    truncated SVD is actually for: there the two must agree within 0.5%,
    and a regression in the data recipe OR the sketching path fails here."""
    from dislib_tpu.decomposition import random_svd
    m, n, nsv, iters = 1024, 128, 16, 2
    r0 = np.random.RandomState(0)
    x = (r0.standard_normal((m, n)) * 0.95 ** np.arange(n)).astype(np.float32)
    _, s_proxy, _ = _numpy_random_svd(x, nsv + 10, iters)
    a = ds.array(x, block_size=(m // 8, n))
    _, s, _ = random_svd(a, iters=iters, nsv=nsv, oversample=10,
                         random_state=0)
    s_dev = np.asarray(s.collect()).ravel()[:16]
    rel = np.max(np.abs(s_dev - s_proxy[:16]) / s_proxy[:16])
    assert rel < 5e-3, (
        f"random_svd drifted from the NumPy proxy: rel err {rel:.4f}")
    # and it must hold against the EXACT spectrum too — the proxy
    # agreeing with the device path is necessary but not sufficient
    s_ref = np.linalg.svd(x, compute_uv=False)[:16]
    np.testing.assert_allclose(s_dev, s_ref, rtol=1e-2)
