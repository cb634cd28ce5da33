"""Plain randomized SVD: the reference ``ds.random_svd`` is held to.

Halko, Martinsson and Tropp, "Finding structure with randomness"
(arXiv:0909.4061): the randomized subspace iteration (Algorithm 4.4) for
an orthonormal Q whose range approximates A's, then the direct SVD
(Algorithm 5.1) of B = Q^T A, in straightforward ``jax.numpy``, float32
with every product at ``precision='highest'``; no kernels, no sharding,
no Cholesky, and nothing imported from the program.  For A (m, n), a
test matrix Omega (n, l) that the caller hands in, and q iterations:

- Y_0 = A Omega, Q_0 = orth(Y_0); then q times Z = A^T Q, W = orth(Z),
  Y = A W, Q = orth(Y);
- B = Q^T A (l, n); B = U_b S V^T (a dense SVD); U = Q U_b; the first r
  columns of U and V and values of S are the answer.

Departures from the paper, each because of the size (1.5M x 1024 beside
a 16 GB chip) or of the chip's arithmetic, none of the mathematics:

- ``orth`` is a Householder QR (``jnp.linalg.qr``) in two levels over row
  blocks, so that it fits: each block of ``block_rows`` rows is factored,
  the blocks' R factors are stacked and factored again, and a block's Q
  is its own times its share of the stack's (the tall-skinny QR of
  Demmel et al.; one QR of the whole panel would hold several panels of
  workspace).  A short matrix (Z, n rows) is one block.
- Every sum over the rows (A^T Q, Q^T A, and U^T U of the comparison) is
  contracted at most ``contract_rows`` rows at a time behind an
  ``optimization_barrier``, a block's pieces added on the device and the
  blocks' sums in float64 on the host: one 'highest' product that
  contracts 100 000 rows of same-signed terms reads 1.3e-5 low on the
  v5e (PERF.md, PR 29), XLA folds a sum over pieces back into the one
  long product unless a barrier stands between, and the reference must
  not share the doubt whether these mixed-signed sums are biased too.
- Algorithm 4.4 states no truncation; the sketch is l = r + oversampling
  wide and the answer is cut to r after the small SVD, as the paper's
  section 4.2 describes.

``precision`` is the control's handle: ``'high'`` (three bf16 passes) runs
every product with A, and the lift Q U_b, one step below what the
configuration states; ``'bfloat16'`` rounds those products' operands to
bfloat16 and multiplies and accumulates in float32.  The factorisations
(the QRs with their block products, the small SVD) stay float32 at
'highest' either way.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def test_matrix(random_state: int, n: int, sketch: int):
    """The draw ``ds.random_svd`` documents as its contract for an integer
    ``random_state``."""
    return jax.random.normal(jax.random.PRNGKey(int(random_state)),
                             (n, sketch), jnp.float32)


test_matrix.__test__ = False        # a name pytest would otherwise collect


def _dot(subscripts, a, b, precision):
    if precision == "bfloat16":
        # operands rounded to bfloat16's eight bits, products and sums in
        # float32: what one pass of the MXU computes, on any backend
        a, b = (lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
                for v in (a, b))
        precision = "highest"
    return jnp.einsum(subscripts, a, b, precision=precision)


@partial(jax.jit, static_argnames=("precision",))
def _along_rows(a, b, precision):
    """``a @ b`` for a tall ``a`` and a small ``b``: each row's own
    product, n or l terms long."""
    return _dot("mn,nl->ml", a, b, precision)


@partial(jax.jit, static_argnames=("block_rows", "contract_rows",
                                   "precision"))
def _down_block(a, q, i, block_rows, contract_rows, precision):
    """Block ``i``'s share of ``a.T @ q``: its rows taken ``contract_rows``
    at a time, the pieces' products added outside the product."""
    pieces = block_rows // contract_rows
    ab, qb = (lax.dynamic_slice_in_dim(v, i * block_rows, block_rows)
              .reshape(pieces, contract_rows, v.shape[1]) for v in (a, q))
    # behind a barrier, or the compiler folds the sum over the pieces back
    # into one product over all the rows
    return jnp.sum(lax.optimization_barrier(
        _dot("spn,spl->snl", ab, qb, precision)), axis=0)


def down(a, q, block_rows, contract_rows, precision="highest"):
    """``a.T @ q`` over all rows, float64 on the host: the blocks' sums
    added up there, one ``device_get`` a block."""
    m = a.shape[0]
    block_rows = min(block_rows, m)
    if m % block_rows or block_rows % contract_rows:
        raise ValueError(f"{m} rows are no multiple of the reference's "
                         f"block of {block_rows}, or that of its pieces "
                         f"of {contract_rows}")
    total = np.zeros((a.shape[1], q.shape[1]), np.float64)
    for i in range(m // block_rows):
        total += np.asarray(jax.device_get(_down_block(
            a, q, i, block_rows, contract_rows, precision)), np.float64)
    return total


@partial(jax.jit, static_argnames=("block_rows",))
def _orth(y, block_rows):
    with jax.default_matmul_precision("highest"):
        rows, l = y.shape
        if rows <= block_rows:
            return jnp.linalg.qr(y, mode="reduced")[0]
        blocks = rows // block_rows
        q0, r0 = lax.map(lambda yb: tuple(jnp.linalg.qr(yb, mode="reduced")),
                         y.reshape(blocks, block_rows, l))
        q1 = jnp.linalg.qr(r0.reshape(blocks * l, l), mode="reduced")[0]
        return jnp.einsum("bil,blk->bik", q0, q1.reshape(blocks, l, l),
                          precision="highest").reshape(rows, l)


def orth(y, block_rows):
    """An orthonormal basis of ``y``'s columns by Householder QR, two
    levels over blocks of ``block_rows`` rows."""
    if y.shape[0] > block_rows and y.shape[0] % block_rows:
        raise ValueError(f"{y.shape[0]} rows are no multiple of the "
                         f"reference's block of {block_rows}")
    return _orth(y, block_rows)


def fit(x, omega, iters, nsv, block_rows, contract_rows,
        precision="highest"):
    """``(u, s, v)``: U (m, nsv) on the device, S (nsv,) and V (n, nsv) as
    NumPy arrays, from the test matrix ``omega`` (n, l)."""
    omega = jnp.asarray(omega, jnp.float32)

    def down_f32(q):
        return jnp.asarray(down(x, q, block_rows, contract_rows, precision)
                           .astype(np.float32))

    q = orth(_along_rows(x, omega, precision), block_rows)
    for _ in range(int(iters)):
        w = orth(down_f32(q), block_rows)
        q = orth(_along_rows(x, w, precision), block_rows)
    b = down_f32(q).T                                   # (l, n)
    with jax.default_matmul_precision("highest"):
        ub, s, vt = jnp.linalg.svd(b, full_matrices=False)
    u = _along_rows(q, ub[:, :nsv], precision)
    return u, np.asarray(jax.device_get(s))[:nsv], \
        np.asarray(jax.device_get(vt)).T[:, :nsv]


def sample_rows(seed: int, m: int, count: int) -> np.ndarray:
    """``count`` distinct row indices of an m-row result, from the seed."""
    rng = np.random.default_rng([int(seed), 7])
    return np.sort(rng.choice(m, size=min(count, m), replace=False))


def take_rows(u, rows) -> np.ndarray:
    """Rows ``rows`` of a tall device array, on the host."""
    return np.asarray(jax.device_get(jnp.take(u, jnp.asarray(rows), axis=0)))


def orthogonality_gap(u, block_rows, contract_rows) -> float:
    """``max |U^T U - I|``, the Gram summed in blocks in float64."""
    gram = down(u, u, block_rows, contract_rows)
    return float(np.max(np.abs(gram - np.eye(gram.shape[0]))))


def summary(u, s, v, rows, block_rows, contract_rows) -> dict:
    """What :func:`compare` needs of one result: S, V, the sampled rows of
    U and U's orthogonality gap, all on the host."""
    return {"s": np.asarray(s, np.float64).ravel(),
            "v": np.asarray(v, np.float64),
            "u_rows": take_rows(u, rows).astype(np.float64),
            "orthogonality": orthogonality_gap(u, block_rows, contract_rows)}


def compare(got, want) -> dict:
    """The numbers one call is judged by, each against the reference; none
    changes with the sign of a singular vector or a rotation among close
    singular values.

    ``singular_values_gap``: max |s - s_ref| over s_ref[0].
    ``approx_rows_gap``: the Frobenius norm of the gap between the two
    rank-r approximations U diag(s) V^T on the sampled rows, over the
    reference's there.  ``orthogonality_gap``: max |U^T U - I| of what is
    judged (the reference's own does not enter).  ``right_subspace_gap``:
    |V V^T - V_ref V_ref^T|_F over sqrt(r), the distance between the two
    right singular subspaces."""
    def approx(d):
        return (d["u_rows"] * d["s"][None, :]) @ d["v"].T

    a_got, a_ref = approx(got), approx(want)
    r = want["s"].shape[0]
    if got["s"].shape != want["s"].shape:
        values_gap = float("inf")
    else:
        values_gap = float(np.max(np.abs(got["s"] - want["s"]))
                           / want["s"][0])
    return {"singular_values_gap": values_gap,
            "approx_rows_gap": float(np.linalg.norm(a_got - a_ref)
                                     / np.linalg.norm(a_ref)),
            "orthogonality_gap": float(got["orthogonality"]),
            "right_subspace_gap": float(np.linalg.norm(
                got["v"] @ got["v"].T - want["v"] @ want["v"].T)
                / np.sqrt(r))}
