"""Randomized SVD (reference: `dislib/decomposition/randomsvd` — Gaussian
test matrix, power iterations with QR re-orthonormalisation, small dense SVD
of the projected matrix; SURVEY.md §3.2, BASELINE config 4).

TPU-native: the sketch Y = A Ω and the power iterations are sharded GEMMs
(MXU-bound); re-orthonormalisation uses the tsQR tree so the only collective
per iteration is the all_gather(R) + the GEMM's own partial-sum psum — the
survey's "power-iteration psum" pattern.

The whole pipeline (sketch → power iterations → projection → small SVD →
back-multiplication) is ONE jitted program — the same one-compiled-program
design the iterative estimators use for their fit loops.  A host-level
composition of the stages costs one dispatch per GEMM/tsQR (~15 for
iters=2), each a host round trip of pure latency.  Shapes are static, so
fusing is free.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dislib_tpu.data.array import Array
from dislib_tpu.math import matmul
from dislib_tpu.decomposition.tsqr import (tsqr, _tsqr_shardmap,
                                           _use_cholqr)
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.ops import base as _ops
from dislib_tpu.ops import precision as px
from dislib_tpu.ops.base import precise
from dislib_tpu.utils.profiling import new_call as _new_call
from dislib_tpu.utils.profiling import profiled_jit as _pjit
from dislib_tpu.utils.profiling import span as _span


_CALL = "dslib.rsvd.call"


def random_svd(a: Array, iters: int = 2, epsilon: float | None = None,
               tol: float = 1e-3, nsv: int | None = None, k: int | None = None,
               oversample: int = 10, random_state=None, verbose: bool = False,
               precision=None):
    """Truncated randomized SVD of ``a``.

    Returns (U, S, V) with U (m, k), S (1, k), V (n, k); ``k`` defaults to
    ``nsv`` (number of singular values) + oversampling, truncated to nsv.

    The test matrix is a contract: for an integer ``random_state`` it is
    ``jax.random.normal(jax.random.PRNGKey(random_state), (n, nsv +
    oversample), jnp.float32)`` (the sketch capped at n), so that another
    implementation of the algorithm can be handed the same draw.

    ``precision``: mixed-precision policy (None → the
    ``DSLIB_MATMUL_PRECISION`` default).  The policy governs the sketch /
    power-iteration / projection / back-multiplication GEMMs (all the
    O(mn·sketch) FLOPs); the tsQR re-orthonormalisations and the small
    (sketch, n) SVD stay float32 — bounds in
    ``ops/precision.ERROR_BOUNDS``.

    A dense float32 array takes ONE dispatch, which returns before the
    device is done.  No product of it contracts more than about 10^4 rows
    at a time: the products over the rows and the orthonormalisations'
    Grams are compensated sums over row blocks (``ops/base.py``).
    """
    with _span(_CALL, call=_new_call()):
        return _random_svd(a, iters, nsv, k, oversample, random_state,
                           px.resolve(precision))


def _random_svd(a, iters, nsv, k, oversample, random_state, policy):
    m, n = a.shape
    nsv = nsv if nsv is not None else (k if k is not None else min(m, n, 6))
    sketch = min(n, nsv + oversample)
    nsv = min(nsv, sketch)  # only `sketch` directions exist in the subspace
    seed = 0 if random_state is None else int(np.random.RandomState(random_state).randint(2**31 - 1)) \
        if not isinstance(random_state, (int, np.integer)) else int(random_state)

    if type(a) is Array and m >= sketch and a._data.dtype == jnp.float32:
        # fused single-dispatch path (sketch ≤ n always holds); f64 inputs
        # (x64-mode CPU rig) keep the composed path's dtype fidelity
        mesh = _mesh.get_mesh()
        p = mesh.shape[_mesh.ROWS]
        q = _mesh.pad_quantum()
        u, s, v = _random_svd_fused(
            a._data, jax.random.PRNGKey(seed), a.shape, iters, sketch,
            nsv, mesh, p, q, cholqr=_use_cholqr(), policy=policy)
        return (Array._from_logical_padded(u, (m, nsv)),
                Array._from_logical_padded(s, (1, nsv)),
                Array._from_logical_padded(v, (n, nsv)))

    omega = Array._from_logical(_omega_of(jax.random.PRNGKey(seed), n, sketch))

    # the orthonormalisations are PINNED f32 (matching the fused path and
    # the docstring contract) — explicitly, so an ambient
    # DSLIB_MATMUL_PRECISION can never leak into them when the caller
    # asked for float32 (review-found env-leak)
    y = matmul(a, omega, precision=policy)   # (m, sketch) sharded GEMM
    q, _ = tsqr(y, precision=px.FLOAT32) if m >= sketch else _qr_fallback(y)
    for _ in range(iters):
        z = matmul(a, q, transpose_a=True, precision=policy)   # (n, sketch)
        qz, _ = tsqr(z, precision=px.FLOAT32) if n >= sketch \
            else _qr_fallback(z)
        y = matmul(a, qz, precision=policy)
        q, _ = tsqr(y, precision=px.FLOAT32) if m >= sketch \
            else _qr_fallback(y)

    b = matmul(q, a, transpose_a=True,
               precision=policy)             # (sketch, n) small projected matrix
    bv = b._data[: b.shape[0], : b.shape[1]]
    ub, s, vt = jnp.linalg.svd(bv, full_matrices=False)
    u = matmul(q, Array._from_logical(ub), precision=policy)
    u = u[:, :nsv]
    v = Array._from_logical(vt.T[:, :nsv])
    s_arr = Array._from_logical(s[:nsv].reshape(1, -1))
    return u, s_arr, v


@partial(_pjit, name="random_svd",
         static_argnames=("a_shape", "iters", "sketch", "nsv", "cholqr",
                          "policy", "mesh", "p", "quantum"))
@precise
def _random_svd_fused(a_pad, key, a_shape, iters, sketch, nsv, mesh, p,
                      quantum, *, cholqr, policy=px.FLOAT32):
    """Sketch + power iterations + projection + SVD as one XLA program,
    its three results padded to the mesh ``quantum`` and zero beyond their
    logical shapes, as ``Array`` holds them.

    Quantum-padded rows/cols of ``a_pad`` are zero, so they contribute
    nothing to any GEMM; tsQR's Q rows at zero input rows are zero for a
    full-column-rank sketch (Q_i R = 0 with R invertible ⇒ Q_i = 0), which
    keeps the returned U's logical crop exact.

    The two products that contract the rows, AᵀQ of a power iteration and
    QᵀA of the projection (its transpose), are ONE blocked pass each
    (``ops/base.py::blocked_row_sums``): a block's product at a time, the
    blocks' products in compensated sums, one ``psum`` over the mesh's
    rows.  The products along a row (A·Ω, A·Q_z, Q·U_b) contract n or the
    sketch and stay whole."""
    m, n = a_shape
    av = px.f32(a_pad[:, :n])
    av = lax.with_sharding_constraint(av, _mesh.row_sharding())

    def ortho(y):
        # rows must be ≥ sketch per shard AND divisible by p for shard_map
        rows = y.shape[0]
        target = max(p * sketch, -(-rows // p) * p)
        if target != rows:
            y = jnp.pad(y, ((0, target - rows), (0, 0)))
        y = lax.with_sharding_constraint(y, _mesh.row_sharding())
        q, _ = _tsqr_shardmap(y, mesh, p, cholqr=cholqr)
        return q[:rows]

    def down(q):
        """Aᵀ·Q, (n, sketch), the rows contracted a block at a time."""
        return _ops.blocked_row_sums(
            av, m, n, _ops.contract_row_bytes(4 * (n + sketch)),
            lambda xb, w, _start, qb: px.pdot_tall(xb, qb * w[:, None],
                                                   policy),
            jnp.zeros((n, sketch), av.dtype), per_row=(q,))

    with jax.named_scope("dslib.rsvd.sketch"):
        q = ortho(px.pdot(av, _omega_of(key, n, sketch), policy))
    for _ in range(iters):
        with jax.named_scope("dslib.rsvd.power"):
            qz = ortho(down(q))
            q = ortho(px.pdot(av, qz, policy))

    with jax.named_scope("dslib.rsvd.project"):
        b = down(q).T                        # (sketch, n), replicated
    with jax.named_scope("dslib.rsvd.small_svd"):
        ub, s, vt = jnp.linalg.svd(b, full_matrices=False)
    with jax.named_scope("dslib.rsvd.lift"):
        u = px.pdot(q, ub[:, :nsv], policy)  # (M_pad, nsv)

    def padded(x, shape):
        fill = [(0, -(-want // quantum) * quantum - have)
                for want, have in zip(shape, x.shape)]
        x = jnp.pad(x, fill) if any(f for _, f in fill) else x
        return lax.with_sharding_constraint(x, _mesh.data_sharding())

    return (padded(u[:m], (m, nsv)), padded(s[None, :nsv], (1, nsv)),
            padded(vt.T[:, :nsv], (n, nsv)))


def _omega_of(key, n, sketch):
    """Gaussian test matrix — single definition shared by both paths so the
    fused and composed pipelines provably start from the same draw."""
    return jax.random.normal(key, (n, sketch), dtype=jnp.float32)


def _qr_fallback(y: Array):
    from dislib_tpu.math.qr import qr as _qr
    # pinned f32 like the tsqr orthonormalisations (env must not leak in)
    return _qr(y, mode="economic", precision=px.FLOAT32)
