"""Mixed-precision distributed linear algebra (round-10 perf PR).

Four pillars, every assertion against single sources of truth:

1. **Accuracy bounds** — a parametrized grid comparing each policy's
   result against the f32/f64 reference across shapes AND condition
   numbers, asserted against the DOCUMENTED bounds in
   ``ops/precision.ERROR_BOUNDS`` (the user-guide table quotes the same
   dict, so docs and tests cannot drift apart).
2. **SUMMA** — the explicit panel-broadcast schedule on a genuinely 2-D
   mesh: oracle equivalence (irregular shapes, transposes, bf16), the
   algorithm-routing rule, and the ONE-dispatch contract.
3. **Newton–Schulz polar** — factorisation properties vs the SVD oracle
   and the one-dispatch-at-any-iteration-count contract (each iteration
   adds ZERO dispatches — the PR-2/PR-4 counter-pinning pattern).
4. **Pad-tail hygiene** — the shared grow/crop helpers must keep a
   padded tail out of every reduced-precision accumulation even when the
   backing's zero-pad invariant has been violated upstream.
"""

import os

import numpy as np
import pytest

import dislib_tpu as ds
from dislib_tpu.ops import precision as px
from dislib_tpu.utils import profiling as prof


def _conditioned(m, n, cond, seed=0):
    """Deterministic (m, n) float32 matrix with condition number ~cond and
    unit largest singular value."""
    rng = np.random.RandomState(seed)
    k = min(m, n)
    u, _ = np.linalg.qr(rng.standard_normal((m, k)))
    v, _ = np.linalg.qr(rng.standard_normal((n, k)))
    s = np.logspace(0, -np.log10(cond), k)
    return (u * s) @ v.T


# ---------------------------------------------------------------------------
# policy resolution
# ---------------------------------------------------------------------------

class TestPolicyResolution:
    def test_aliases(self):
        for name in ("float32", "f32", "fp32", "highest"):
            assert px.resolve(name) is px.FLOAT32
        for name in ("bfloat16", "bf16", "BF16"):
            assert px.resolve(name) is px.BFLOAT16
        assert px.resolve(px.BFLOAT16) is px.BFLOAT16

    def test_env_default(self, monkeypatch):
        monkeypatch.delenv("DSLIB_MATMUL_PRECISION", raising=False)
        assert px.resolve(None) is px.FLOAT32
        monkeypatch.setenv("DSLIB_MATMUL_PRECISION", "bf16")
        assert px.resolve(None) is px.BFLOAT16
        # explicit kwarg beats the env
        assert px.resolve("float32") is px.FLOAT32

    def test_unknown_raises(self):
        with pytest.raises(ValueError, match="unknown precision policy"):
            px.resolve("float16")

    def test_policy_is_static_cache_key(self):
        """Same operand, different policy → different jit trace (the env
        flip cannot be silently ignored)."""
        rng = np.random.RandomState(0)
        a = ds.array(rng.rand(24, 16).astype(np.float32)).force()
        b = ds.array(rng.rand(16, 8).astype(np.float32)).force()
        ds.matmul(a, b).force()
        ds.matmul(a, b, precision="bf16").force()
        prof.reset_counters()
        f32 = np.asarray(ds.matmul(a, b).force().collect())
        bf16 = np.asarray(ds.matmul(a, b, precision="bf16").force()
                          .collect())
        # both warm (no retrace), and genuinely different numerics
        assert prof.trace_count() == 0
        assert np.abs(f32 - bf16).max() > 0

    def test_f64_passthrough_under_float32_floor(self):
        """x64-mode data must not be narrowed by the DEFAULT policy (the
        ds.array dtype-policy precedent: narrowing is never implicit)."""
        import jax.numpy as jnp
        x = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
        assert px.to_compute(x, px.FLOAT32).dtype == jnp.float32
        assert px.to_compute(x, px.BFLOAT16).dtype == jnp.bfloat16
        # f32 policy upcasts bf16 (faithful floor), bf16 policy rounds
        assert px.to_compute(x.astype(jnp.bfloat16),
                             px.FLOAT32).dtype == jnp.float32


# ---------------------------------------------------------------------------
# accuracy bounds — the documented table IS the assertion
# ---------------------------------------------------------------------------

POLICIES = ("float32", "bfloat16")
CONDS = (10.0, 1e4)


class TestAccuracyBounds:
    @pytest.mark.parametrize("policy", POLICIES)
    @pytest.mark.parametrize("cond", CONDS)
    @pytest.mark.parametrize("shape", [(64, 48, 32), (96, 40, 56)])
    def test_matmul(self, policy, cond, shape):
        m, k, n = shape
        a_host = _conditioned(m, k, cond, seed=1).astype(np.float32)
        b_host = _conditioned(k, n, cond, seed=2).astype(np.float32)
        ref = a_host.astype(np.float64) @ b_host.astype(np.float64)
        got = np.asarray(ds.matmul(ds.array(a_host), ds.array(b_host),
                                   precision=policy).collect(),
                         dtype=np.float64)
        err = np.abs(got - ref).max() / np.abs(ref).max()
        assert err <= px.ERROR_BOUNDS[("matmul", policy)], \
            f"matmul {policy} cond={cond}: {err:.2e}"

    @pytest.mark.parametrize("policy", POLICIES)
    @pytest.mark.parametrize("cond", CONDS)
    def test_tsqr(self, policy, cond):
        x = _conditioned(512, 48, cond, seed=3).astype(np.float32)
        q, r = ds.tsqr(ds.array(x, block_size=(64, 48)), precision=policy)
        qh, rh = np.asarray(q.collect()), np.asarray(r.collect())
        orth = np.abs(qh.T @ qh - np.eye(48)).max()
        resid = np.linalg.norm(qh @ rh - x) / np.linalg.norm(x)
        assert orth <= px.ERROR_BOUNDS[("tsqr_orth", policy)], \
            f"tsqr {policy} cond={cond}: orth {orth:.2e}"
        assert resid <= px.ERROR_BOUNDS[("tsqr_resid", policy)], \
            f"tsqr {policy} cond={cond}: resid {resid:.2e}"

    @pytest.mark.parametrize("policy", POLICIES)
    @pytest.mark.parametrize("cond", CONDS)
    def test_blocked_qr(self, policy, cond, monkeypatch):
        import importlib
        qrmod = importlib.import_module("dislib_tpu.math.qr")
        monkeypatch.setattr(qrmod, "_PANEL", 16)   # blocked path, cheaply
        x = _conditioned(256, 40, cond, seed=4).astype(np.float32)
        a = ds.array(x, block_size=(32, 40))
        q, r = ds.qr(a, mode="economic", precision=policy)
        qh, rh = np.asarray(q.collect()), np.asarray(r.collect())
        orth = np.abs(qh.T @ qh - np.eye(40)).max()
        resid = np.linalg.norm(qh @ rh - x) / np.linalg.norm(x)
        assert orth <= px.ERROR_BOUNDS[("qr_orth", policy)], \
            f"qr {policy} cond={cond}: orth {orth:.2e}"
        assert resid <= px.ERROR_BOUNDS[("qr_resid", policy)], \
            f"qr {policy} cond={cond}: resid {resid:.2e}"

    @pytest.mark.parametrize("policy", POLICIES)
    def test_randomsvd(self, policy):
        rng = np.random.RandomState(5)
        x = (rng.standard_normal((768, 96))
             * 0.9 ** np.arange(96)).astype(np.float32)
        s_ref = np.linalg.svd(x, compute_uv=False)
        _, s, _ = ds.random_svd(ds.array(x, block_size=(96, 96)), nsv=12,
                                random_state=0, precision=policy)
        sd = np.asarray(s.collect()).ravel()
        err = np.abs(sd - s_ref[:12]).max() / s_ref[0]
        assert err <= px.ERROR_BOUNDS[("randomsvd_values", policy)], \
            f"randomsvd {policy}: {err:.2e}"

    @pytest.mark.parametrize("policy", POLICIES)
    def test_lanczos(self, policy):
        rng = np.random.RandomState(6)
        x = (rng.standard_normal((384, 64))
             * 0.9 ** np.arange(64)).astype(np.float32)
        s_ref = np.linalg.svd(x, compute_uv=False)
        _, s, _ = ds.lanczos_svd(ds.array(x), k=6, random_state=0,
                                 precision=policy)
        sd = np.asarray(s.collect()).ravel()
        err = np.abs(sd - s_ref[:6]).max() / s_ref[0]
        assert err <= px.ERROR_BOUNDS[("lanczos_values", policy)], \
            f"lanczos {policy}: {err:.2e}"

    @pytest.mark.parametrize("policy", POLICIES)
    @pytest.mark.parametrize("cond", CONDS)
    def test_polar(self, policy, cond):
        x = _conditioned(192, 40, cond, seed=7).astype(np.float32)
        u, h = ds.polar(ds.array(x), precision=policy, max_iter=60)
        uh, hh = np.asarray(u.collect()), np.asarray(h.collect())
        orth = np.abs(uh.T @ uh - np.eye(40)).max()
        resid = np.linalg.norm(uh @ hh - x) / np.linalg.norm(x)
        assert orth <= px.ERROR_BOUNDS[("polar_orth", policy)], \
            f"polar {policy} cond={cond}: orth {orth:.2e}"
        assert resid <= px.ERROR_BOUNDS[("polar_resid", policy)], \
            f"polar {policy} cond={cond}: resid {resid:.2e}"

    @pytest.mark.parametrize("policy", POLICIES)
    @pytest.mark.parametrize("cond", CONDS)
    def test_svd_block_tier(self, policy, cond):
        """Round-11 satellite (ROADMAP item 5 follow-up (b)): the
        block-Jacobi pair-update GEMMs follow the policy; values and the
        full-factor residual hold the documented bounds on the block
        tier (n >= 2*64 engages the column-block pairing)."""
        m, n = 256, 160
        x = _conditioned(m, n, cond, seed=11).astype(np.float32)
        s_ref = np.linalg.svd(x.astype(np.float64), compute_uv=False)
        u, s, v = ds.svd(ds.array(x), precision=policy)
        sv = np.asarray(s.collect()).ravel()
        uh, vh = np.asarray(u.collect()), np.asarray(v.collect())
        val_err = np.max(np.abs(sv - s_ref) / s_ref[0])
        resid = np.linalg.norm(x - (uh * sv) @ vh.T) / np.linalg.norm(x)
        assert val_err <= px.ERROR_BOUNDS[("svd_values", policy)], \
            f"svd {policy} cond={cond}: values {val_err:.2e}"
        assert resid <= px.ERROR_BOUNDS[("svd_resid", policy)], \
            f"svd {policy} cond={cond}: resid {resid:.2e}"

    def test_svd_scalar_tier_pinned_f32(self):
        """Below the block threshold there is no FLOP-dominant GEMM: the
        scalar tier ignores the policy (documented), so bf16 and f32
        requests return bit-identical factors."""
        x = _conditioned(48, 24, 10.0, seed=12).astype(np.float32)
        s32 = np.asarray(ds.svd(ds.array(x), compute_uv=False,
                                precision="float32").collect())
        sbf = np.asarray(ds.svd(ds.array(x), compute_uv=False,
                                precision="bfloat16").collect())
        np.testing.assert_array_equal(s32, sbf)

    def test_svd_bf16_eps_floor_converges(self):
        """The per-policy eps floor (5e-3) keeps a default-eps bf16 call
        from burning max_sweeps chasing unreachable 1e-6 orthogonality:
        the sweep loop must terminate well inside the budget and still
        meet the documented bounds."""
        x = _conditioned(256, 160, 10.0, seed=13).astype(np.float32)
        s_ref = np.linalg.svd(x.astype(np.float64), compute_uv=False)
        s = np.asarray(ds.svd(ds.array(x), compute_uv=False,
                              precision="bfloat16").collect()).ravel()
        err = np.max(np.abs(s - s_ref) / s_ref[0])
        assert err <= px.ERROR_BOUNDS[("svd_values", "bfloat16")]

    def test_pca_policy_close_to_f32(self):
        rng = np.random.RandomState(8)
        x = (rng.standard_normal((512, 32))
             * 0.9 ** np.arange(32)).astype(np.float32)
        a = ds.array(x)
        var32 = np.asarray(ds.PCA(n_components=4).fit(a)
                           .explained_variance_.collect())
        var16 = np.asarray(ds.PCA(n_components=4, precision="bf16").fit(a)
                           .explained_variance_.collect())
        assert np.abs(var16 - var32).max() / var32.max() <= 2e-2

    def test_composed_randomsvd_ignores_ambient_env(self, monkeypatch):
        """The composed (non-fused) random_svd path pins its tsqr
        orthonormalisations to f32 EXPLICITLY — an ambient
        DSLIB_MATMUL_PRECISION must not leak into an explicit
        precision='float32' call (review-found; m < sketch forces the
        composed path)."""
        rng = np.random.RandomState(11)
        x = rng.standard_normal((24, 64)).astype(np.float32)  # m < sketch
        a = ds.array(x)
        _, s_clean, _ = ds.random_svd(a, nsv=4, random_state=0,
                                      precision="float32")
        monkeypatch.setenv("DSLIB_MATMUL_PRECISION", "bfloat16")
        _, s_env, _ = ds.random_svd(a, nsv=4, random_state=0,
                                    precision="float32")
        np.testing.assert_array_equal(np.asarray(s_clean.collect()),
                                      np.asarray(s_env.collect()))

    def test_polar_info_err_describes_returned_factor(self, rng):
        """On a max_iter exit the reported ortho_err must measure the
        RETURNED U, not the pre-update iterate (review-found off-by-one-
        contraction)."""
        x = rng.standard_normal((96, 12)).astype(np.float32)
        u, _, info = ds.polar(ds.array(x), max_iter=3, info=True)
        uh = np.asarray(u.collect())
        true_err = np.abs(uh.T @ uh - np.eye(12)).max()
        assert abs(info["ortho_err"] - true_err) <= 1e-5 + 0.05 * true_err

    def test_env_var_routes_the_default(self, monkeypatch):
        """DSLIB_MATMUL_PRECISION=bfloat16 flips the kwarg-less path — the
        result must match the explicit precision='bfloat16' call exactly
        (same policy object → same traced program)."""
        rng = np.random.RandomState(9)
        x = rng.rand(48, 32).astype(np.float32)
        y = rng.rand(32, 24).astype(np.float32)
        a, b = ds.array(x), ds.array(y)
        explicit = np.asarray(ds.matmul(a, b, precision="bfloat16")
                              .collect())
        monkeypatch.setenv("DSLIB_MATMUL_PRECISION", "bfloat16")
        via_env = np.asarray(ds.matmul(a, b).collect())
        np.testing.assert_array_equal(explicit, via_env)


# ---------------------------------------------------------------------------
# SUMMA
# ---------------------------------------------------------------------------

class TestSumma:
    @pytest.fixture(autouse=True)
    def _mesh2d(self):
        from conftest import skip_unless_devices
        skip_unless_devices(8)
        ds.init((4, 2))
        yield
        ds.init()

    @pytest.mark.parametrize("shapes", [((64, 64), (64, 64)),
                                        ((33, 65), (65, 12)),
                                        ((17, 5), (5, 9))])
    def test_oracle(self, rng, shapes):
        (m, k), (_, n) = shapes
        x, y = (rng.rand(m, k).astype(np.float32),
                rng.rand(k, n).astype(np.float32))
        got = ds.matmul(ds.array(x), ds.array(y),
                        algorithm="summa").collect()
        np.testing.assert_allclose(got, x @ y, rtol=1e-4, atol=1e-5)

    def test_auto_picks_summa_on_2d_mesh(self, monkeypatch):
        import dislib_tpu.math.base as mb
        monkeypatch.setattr(mb, "_SUMMA_MIN_DIM", 16)    # paper-scale gate
        rng = np.random.RandomState(0)
        a = ds.array(rng.rand(32, 32).astype(np.float32)).force()
        ds.matmul(a, a).force()                          # warm
        prof.reset_counters()
        ds.matmul(a, a).force()
        assert prof.counters()["dispatch_by"].get("summa_matmul") == 1
        # transposed operands stay on the XLA fusion path under auto
        ds.matmul(a, a, transpose_a=True).force()
        prof.reset_counters()
        ds.matmul(a, a, transpose_a=True).force()
        assert "summa_matmul" not in prof.counters()["dispatch_by"]

    def test_auto_preserves_fusion_for_lazy_and_small_operands(self,
                                                               monkeypatch):
        """Auto-SUMMA must not steal a GEMM out of a pending fusion chain
        (the chain would force and gain dispatches) nor grab sub-scale
        products; both stay one fused dispatch on a 2-D mesh."""
        import dislib_tpu.math.base as mb
        rng = np.random.RandomState(0)
        a = ds.array(rng.rand(32, 32).astype(np.float32)).force()
        # small concrete operands: below _SUMMA_MIN_DIM → xla fusion node
        ds.matmul(a, a).force()
        prof.reset_counters()
        ds.matmul(a, a).force()
        assert "summa_matmul" not in prof.counters()["dispatch_by"]
        # lazy chain ending in a matmul: even at SUMMA-eligible sizes the
        # whole chain is ONE fused dispatch
        monkeypatch.setattr(mb, "_SUMMA_MIN_DIM", 16)
        y = (a * 2.0 + 1.0)                              # pending chain
        out = ds.matmul(y, a)
        assert out.is_lazy
        prof.reset_counters()
        out.force()
        assert prof.dispatch_count() == 1
        assert "summa_matmul" not in prof.counters()["dispatch_by"]

    def test_auto_picks_xla_on_1d_mesh(self):
        ds.init()                                        # (8, 1)
        rng = np.random.RandomState(0)
        a = ds.array(rng.rand(32, 32).astype(np.float32)).force()
        ds.matmul(a, a).force()
        prof.reset_counters()
        ds.matmul(a, a).force()
        assert "summa_matmul" not in prof.counters()["dispatch_by"]

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("DSLIB_MATMUL_ALGO", "xla")
        rng = np.random.RandomState(0)
        a = ds.array(rng.rand(32, 32).astype(np.float32)).force()
        ds.matmul(a, a).force()
        prof.reset_counters()
        ds.matmul(a, a).force()
        assert "summa_matmul" not in prof.counters()["dispatch_by"]

    def test_transposes_match_oracle(self, rng):
        x, y = (rng.rand(12, 40).astype(np.float32),
                rng.rand(9, 40).astype(np.float32))
        got = ds.matmul(ds.array(x), ds.array(y), transpose_b=True,
                        algorithm="summa").collect()
        np.testing.assert_allclose(got, x @ y.T, rtol=1e-4, atol=1e-5)

    def test_bf16_policy_within_bound(self, rng):
        x = rng.rand(64, 48).astype(np.float32)
        y = rng.rand(48, 40).astype(np.float32)
        ref = x.astype(np.float64) @ y.astype(np.float64)
        got = np.asarray(ds.matmul(ds.array(x), ds.array(y),
                                   algorithm="summa",
                                   precision="bf16").collect(),
                         dtype=np.float64)
        err = np.abs(got - ref).max() / np.abs(ref).max()
        assert 0 < err <= px.ERROR_BOUNDS[("matmul", "bfloat16")]

    def test_one_dispatch(self, rng):
        a = ds.array(rng.rand(64, 64).astype(np.float32)).force()
        for prec in (None, "bf16"):
            ds.matmul(a, a, algorithm="summa", precision=prec).force()
            prof.reset_counters()
            ds.matmul(a, a, algorithm="summa", precision=prec).force()
            assert prof.dispatch_count() == 1, prof.counters()

    def test_cross_mesh_operands_repad(self, rng):
        """An operand built under an older mesh quantum (here: unpadded,
        from a (1,1) mesh) must repad to the current grid instead of the
        panel loop silently dropping the K tail."""
        x = rng.rand(33, 65).astype(np.float32)
        y = rng.rand(65, 12).astype(np.float32)
        ds.init((1, 1))
        a, b = ds.array(x).force(), ds.array(y).force()
        ds.init((4, 2))
        got = ds.matmul(a, b, algorithm="summa").collect()
        np.testing.assert_allclose(got, x @ y, rtol=1e-4, atol=1e-5)

    def test_matches_xla_path_closely(self, rng):
        """Same operands, both schedules, near bit-equality (both are
        f32-faithful dots over the same zero-padded data; only the
        reduction ORDER differs, so the bound is a few ulps scaled)."""
        x = rng.rand(96, 80).astype(np.float32)
        y = rng.rand(80, 72).astype(np.float32)
        s_got = np.asarray(ds.matmul(ds.array(x), ds.array(y),
                                     algorithm="summa").collect())
        x_got = np.asarray(ds.matmul(ds.array(x), ds.array(y),
                                     algorithm="xla").collect())
        np.testing.assert_allclose(s_got, x_got, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# polar: dispatch contract + API edges
# ---------------------------------------------------------------------------

class TestPolar:
    def test_one_dispatch_at_any_iteration_count(self, rng):
        """A full Newton–Schulz run is ONE fused dispatch — iterating adds
        ZERO dispatches (the loop lives inside the program)."""
        a = ds.array(rng.standard_normal((128, 24)).astype(np.float32))
        a.force()
        for iters in (1, 8, 30):
            ds.polar(a, max_iter=iters)                  # warm this trace
            prof.reset_counters()
            ds.polar(a, max_iter=iters)
            assert prof.dispatch_count() == 1, \
                (iters, prof.counters())
            assert prof.counters()["dispatch_by"].get("polar_ns") == 1

    def test_info_and_convergence(self, rng):
        x = rng.standard_normal((96, 16)).astype(np.float32)
        u, h, info = ds.polar(ds.array(x), info=True)
        assert info["iterations"] < 30
        assert info["ortho_err"] <= 1e-5
        # H symmetric PSD
        hh = np.asarray(h.collect())
        np.testing.assert_allclose(hh, hh.T, atol=1e-6)
        assert np.linalg.eigvalsh(hh).min() > -1e-4

    def test_matches_svd_oracle(self, rng):
        x = rng.standard_normal((80, 12)).astype(np.float32)
        u, _ = ds.polar(ds.array(x))
        uo, _, vto = np.linalg.svd(x, full_matrices=False)
        np.testing.assert_allclose(np.asarray(u.collect()), uo @ vto,
                                   rtol=1e-3, atol=1e-4)

    def test_wide_raises(self, rng):
        with pytest.raises(ValueError, match="tall or square"):
            ds.polar(ds.array(rng.rand(4, 9).astype(np.float32)))

    def test_tol_clamp_warns(self, rng):
        a = ds.array(rng.standard_normal((64, 8)).astype(np.float32))
        with pytest.warns(RuntimeWarning, match="orthogonality floor"):
            ds.polar(a, precision="bf16", tol=1e-9)

    def test_irregular_pad_shapes(self, rng):
        """Quantum-padded rows/cols stay exactly zero through the iterates
        (σ = 0 fixed point) — the logical factors are pad-independent."""
        x = rng.standard_normal((37, 11)).astype(np.float32)
        u, h = ds.polar(ds.array(x))
        uh = np.asarray(u.collect())
        assert uh.shape == (37, 11)
        assert np.abs(uh.T @ uh - np.eye(11)).max() < 1e-4
        # the padded backing outside the logical block is still zero
        backing = np.asarray(u._data)
        assert np.all(backing[37:, :] == 0) and np.all(backing[:, 11:] == 0)


# ---------------------------------------------------------------------------
# pad-tail hygiene: the shared grow/crop helpers under a violated invariant
# ---------------------------------------------------------------------------

class TestPadTailHygiene:
    def _poisoned_col_tail(self, x):
        """An Array whose padded COLUMN tail is garbage — the invariant
        violation the shared helpers must be robust to."""
        a = ds.array(x)
        data = a._data
        m, n = a.shape
        if data.shape[1] == n:
            pytest.skip("no column padding at this shape/mesh")
        bad = data.at[:, n:].set(1e6)
        return ds.Array(bad, (m, n), a.block_size, False)

    def test_poisoned_pad_tail_cannot_leak_into_svd(self, rng):
        """Both Jacobi tiers re-assert the zero-pad invariant through the
        shared grow_canvas helper at ingest — a garbage tail (which at
        bf16 scales would swamp every small singular value) changes
        NOTHING."""
        x = rng.standard_normal((40, 10)).astype(np.float32)
        clean = np.asarray(ds.svd(ds.array(x), compute_uv=False).collect())
        poisoned = np.asarray(ds.svd(self._poisoned_col_tail(x),
                                     compute_uv=False).collect())
        np.testing.assert_array_equal(clean, poisoned)

    def test_poisoned_pad_tail_cannot_leak_into_blocked_qr(self, rng,
                                                           monkeypatch):
        import importlib
        qrmod = importlib.import_module("dislib_tpu.math.qr")
        monkeypatch.setattr(qrmod, "_PANEL", 16)
        x = rng.standard_normal((256, 20)).astype(np.float32)
        a_clean = ds.array(x, block_size=(32, 20))
        r_clean = np.asarray(ds.qr(a_clean, mode="r").collect())
        data = a_clean._data
        if data.shape[1] == 20:
            pytest.skip("no column padding at this shape/mesh")
        bad = ds.Array(data.at[:, 20:].set(1e6), (256, 20),
                       a_clean.block_size, False)
        r_bad = np.asarray(ds.qr(bad, mode="r", precision="bf16").collect())
        r_bad32 = np.asarray(ds.qr(bad, mode="r").collect())
        # the f32 run of the POISONED array must equal the clean run
        # exactly (the tail is masked before any accumulation)...
        np.testing.assert_array_equal(r_clean, r_bad32)
        # ...and the bf16 run must stay within its documented residual
        # bound of the clean reference rather than being 1e6-swamped
        assert np.abs(np.abs(r_bad) - np.abs(r_clean)).max() \
            / np.abs(r_clean).max() <= px.ERROR_BOUNDS[("qr_resid",
                                                        "bfloat16")]

    def test_block_jacobi_tier_masks_tail(self, rng):
        """The ≥128-column block tier routes its canvas through
        grow_canvas(valid=...) — poisoned tail, identical spectrum."""
        x = rng.standard_normal((160, 130)).astype(np.float32)
        a = ds.array(x)
        data = a._data
        if data.shape[1] == 130:
            pytest.skip("no column padding at this shape/mesh")
        bad = ds.Array(data.at[:, 130:].set(1e6), (160, 130),
                       a.block_size, False)
        s_clean = np.asarray(ds.svd(a, compute_uv=False).collect())
        s_bad = np.asarray(ds.svd(bad, compute_uv=False).collect())
        np.testing.assert_array_equal(s_clean, s_bad)


class TestPattern:
    """``pdot_pattern``: a 0/1 pattern's product with a float32 operand,
    three bfloat16 products that carry every term of the six-pass one."""

    @pytest.mark.parametrize("kind", ["ratings", "boolean"])
    def test_matches_float64_of_the_pattern(self, rng, kind):
        import jax
        r = rng.randint(0, 6, (9, 700)).astype(np.float32)
        r[r < 3] = 0
        w = r if kind == "ratings" else r != 0
        b = (rng.standard_normal((700, 33)) * 1e3).astype(np.float32)
        got = np.asarray(jax.jit(px.pdot_pattern)(w, b))
        want = (r != 0).astype(np.float64) @ b.astype(np.float64)
        scale = np.abs(b.astype(np.float64)).sum(axis=0)
        assert got.dtype == np.float32
        assert (np.abs(got - want) <= 1e-6 * scale).all()

    def test_parts_of_b_below_bfloat16_are_kept(self):
        """A b whose value lies below bfloat16's eight bits: a one-pass
        bfloat16 product would lose it, the three parts do not."""
        import jax
        b = np.full((4, 2), 1.0 + 2.0 ** -12, np.float32)
        got = np.asarray(jax.jit(px.pdot_pattern)(np.ones((1, 4), bool), b))
        np.testing.assert_array_equal(got, np.full((1, 2), 4 * b[0, 0]))
