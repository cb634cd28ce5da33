"""Distributed polar decomposition via Newton–Schulz iteration.

A = U H with U orthonormal columns (the closest partial isometry to A)
and H = UᵀA symmetric positive semi-definite.  The TPU-native fit
(arXiv:2112.09017 §polar): Newton–Schulz is PURE GEMM —

    X₀      = A / ‖A‖_F                      (spectrum scaled into (0, 1])
    G_k     = X_kᵀ X_k                        (one (n, n) Gram GEMM)
    X_{k+1} = 1.5·X_k − 0.5·X_k G_k           (one (m, n)×(n, n) GEMM)

— two MXU-shaped products per iteration and nothing else, which makes it
both a capability (polar factors feed subspace orthogonalisation, the
symmetric eigenproblem via the matrix sign function, and Procrustes
alignment) and a sustained-GEMM workload (4·m·n² FLOPs/iteration, no
factorisation on the critical path; no benchmark cell runs it yet, so
its rate on the chip is not measured).

The whole loop — scaling, every iteration, the convergence test, and the
final H = UᵀA — runs inside ONE jitted program (``lax.while_loop``), so a
polar call costs ONE dispatch regardless of iteration count; the
per-iteration dispatch cost of 0 extra is counter-pinned by
``tests/test_precision.py``.

Mixed precision: the GEMMs route through the library precision policy
(``ops/precision``) — ``precision="bfloat16"`` contracts bf16-compute /
f32-accumulate.  Newton–Schulz is self-correcting (each step contracts
the orthogonality error), so reduced-precision iterates converge to the
COMPUTE dtype's orthogonality floor rather than diverging: ~1e-6 at
float32, ~2e-2 at bfloat16 (``ops/precision.ERROR_BOUNDS``).  ``tol``
below the active policy's floor is clamped with a warning (the
``math.svd`` eps precedent).

Convergence needs σ(X₀) ⊂ (0, √3); the Frobenius scaling guarantees
σ ≤ 1.  Rank-deficient A: exact zero singular directions stay exactly
zero (0 is a fixed point), so U converges to a partial isometry on
range(A) but the convergence test — driven by ‖G − I‖ on the logical
block — never reaches ``tol``; the loop then runs ``max_iter``
iterations and returns the partial isometry.  Quantum-padded rows/cols
are zero and stay exactly zero through every iterate (σ = 0 fixed
point), so padding never perturbs the logical factors.
"""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp
from jax import lax

from dislib_tpu.data.array import Array
from dislib_tpu.ops import precision as px
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.utils.profiling import profiled_jit as _pjit

# orthogonality floors per policy: a tol below the compute dtype's
# reachable ‖XᵀX − I‖_max is unreachable and would burn max_iter on every
# call (the math.svd eps-clamp precedent)
_TOL_FLOOR = {"float32": 1e-6, "bfloat16": 5e-3}
_TOL_DEFAULT = {"float32": 1e-5, "bfloat16": 1e-2}


def polar(a: Array, precision=None, max_iter: int = 30, tol: float | None = None,
          info: bool = False):
    """Polar decomposition ``A = U @ H`` of a tall (m ≥ n) ds-array.

    Returns ``(U, H)`` ds-arrays — U (m, n) with orthonormal columns,
    H (n, n) symmetric PSD — or ``(U, H, info_dict)`` when ``info=True``
    with ``{"iterations": k, "ortho_err": ‖UᵀU − I‖_max}``.

    ``precision``: mixed-precision policy (None → ``DSLIB_MATMUL_PRECISION``
    default); ``tol``: convergence threshold on ‖X_kᵀX_k − I‖_max,
    defaulting per policy (1e-5 float32, 1e-2 bfloat16) and clamped to the
    policy's orthogonality floor.  ``max_iter`` bounds the on-device loop.
    """
    m, n = a.shape
    if m < n:
        raise ValueError(
            f"polar needs a tall or square array (m >= n), got {a.shape}; "
            "factorise a.T and transpose the identity A = (Uᵀ H)ᵀ = H Uᵀ "
            "for the left polar form")
    policy = px.resolve(precision)
    if tol is None:
        tol = _TOL_DEFAULT[policy.name]
    floor = _TOL_FLOOR[policy.name]
    if float(tol) < floor:
        import warnings
        warnings.warn(
            f"polar: tol={tol:g} is below the {policy.name} orthogonality "
            f"floor; clamping to {floor:g}", RuntimeWarning, stacklevel=2)
    tol = max(float(tol), floor)
    u_pad, h, iters, err = _polar_kernel(a._data, a.shape, policy,
                                         int(max_iter), float(tol))
    u_arr = Array._from_logical_padded(u_pad, (m, n), a._reg_shape)
    h_arr = Array._from_logical_padded(h, (n, n))
    if not info:
        return u_arr, h_arr
    return u_arr, h_arr, {"iterations": int(iters),
                          "ortho_err": float(err)}


@partial(_pjit, static_argnames=("shape", "policy", "max_iter"),
         name="polar_ns")
@px.precise
def _polar_kernel(ap, shape, policy, max_iter, tol):
    """The whole Newton–Schulz loop as one program.  Operates on the full
    padded backing: pad rows/cols are zero, contribute nothing to the
    Grams, and stay zero through every update (σ = 0 is a fixed point of
    the iteration), so the logical crop of the result is exact."""
    m, n = shape
    x = px.f32(ap)
    np_pad = x.shape[1]
    shard = _mesh.data_sharding()
    # Frobenius norm over the padded canvas == over the logical block
    # (pads are zero); scale so every singular value lies in (0, 1]
    alpha = jnp.sqrt(jnp.sum(x * x))
    x = x / jnp.maximum(alpha, jnp.asarray(1e-30, x.dtype))
    # pad-aware identity: ones only on the logical diagonal, so the
    # convergence measure ‖G − I‖ is exactly the logical orthogonality
    # error (pad rows/cols of G are zero on both sides of the subtraction)
    di = lax.broadcasted_iota(jnp.int32, (np_pad, np_pad), 0)
    dj = lax.broadcasted_iota(jnp.int32, (np_pad, np_pad), 1)
    eye = jnp.where((di == dj) & (di < n), jnp.ones((), x.dtype),
                    jnp.zeros((), x.dtype))

    def cond(carry):
        _, err, it = carry
        return (err > tol) & (it < max_iter)

    def body(carry):
        x, _, it = carry
        g = px.pdot(x.T, x, policy)                       # Gram, (n, n)
        err = jnp.max(jnp.abs(g - eye))
        x_new = 1.5 * x - 0.5 * px.pdot(x, g, policy)
        x_new = lax.with_sharding_constraint(x_new, shard)
        # a converged x must pass through unchanged: once err ≤ tol the
        # update is skipped so the returned U matches the reported err
        x = jnp.where(err > tol, x_new, x)
        return x, err, it + 1

    x, err, iters = lax.while_loop(
        cond, body, (x, jnp.asarray(jnp.inf, x.dtype), 0))
    # the loop-carried err describes the PRE-update iterate; on a
    # max_iter exit (the documented rank-deficient case) that would
    # overstate the returned U's error by one whole contraction — report
    # the RETURNED factor's Gram instead (one extra (n, n) GEMM on
    # top of the loop's 4·m·n² FLOPs an iteration)
    g_final = px.pdot(x.T, x, policy)
    err = jnp.max(jnp.abs(g_final - eye))
    h = px.pdot(x.T, px.f32(ap), policy)                  # H = Uᵀ A
    h = 0.5 * (h + h.T)                                   # exact symmetry
    return x, h, iters, err
