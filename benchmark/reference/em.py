"""Plain EM for a Gaussian mixture: the reference ``GaussianMixture.fit``
is held to.

The textbook iteration as it is written, in straightforward
``jax.numpy``, float32 with every product at ``precision='highest'``; no
kernels, no sharding, no fit loop, and nothing imported from the program.
For rows x_i and components j with pi_j, mu_j, Sigma_j:

- once an iteration: L_j = chol(Sigma_j), P_j = L_j^-T, c_j = sum log
  diag P_j;
- E: y_ij = (x_i - mu_j) P_j; log p_ij = log pi_j + c_j - (d/2) log 2 pi
  - |y_ij|^2 / 2; l_i = logsumexp_j log p_ij; r_ij = exp(log p_ij - l_i);
  the lower bound is the mean of l_i;
- M, in TWO passes over the rows: first n_j = sum r_ij + 1e-10 and mu_j =
  sum r_ij x_i / n_j, then with the new means Sigma_j = sum r_ij (x_i -
  mu_j)(x_i - mu_j)^T / n_j + reg_covar I (tied: summed over j and
  divided by n; diag: the diagonal; spherical: the diagonal's mean).

Each pass computes the E-step again, so an iteration reads the rows
twice and holds a block's (block, k, d) differences at a time: rows are
visited in blocks only so that those fit beside a multi-gigabyte X.  A
block's sums are float32 on the device; the blocks' sums are added up in
float64 on the host, one ``device_get`` a block (a running float32 total
over hundreds of blocks would round by more than what the comparison is
there to see).

``precision`` is the control's handle: ``'high'`` (three bf16 passes) runs
every product over the rows one step below what the configuration states;
``'bfloat16'`` rounds the operands of those products to bfloat16 and
multiplies and accumulates in float32.  The factorisations stay float32
either way.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.scipy.linalg import solve_triangular
from jax.scipy.special import logsumexp


def _dot(subscripts, a, b, precision):
    if precision == "bfloat16":
        # operands rounded to bfloat16's eight bits, products and sums in
        # float32: what one pass of the MXU computes, on any backend
        a, b = (lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
                for v in (a, b))
        precision = "highest"
    return jnp.einsum(subscripts, a, b, precision=precision)


def precisions_chol(covs, cov_type, d):
    """``(P, log det P)``: P_j = L_j^-T of every covariance (upper
    triangular; for diag and spherical the reciprocal standard
    deviations) and the logarithm of its determinant."""
    eye = jnp.eye(d, dtype=covs.dtype)
    with jax.default_matmul_precision("highest"):
        if cov_type == "full":
            prec = jax.vmap(lambda c: solve_triangular(
                jnp.linalg.cholesky(c), eye, lower=True).T)(covs)
            return prec, jnp.sum(jnp.log(jnp.diagonal(
                prec, axis1=1, axis2=2)), axis=1)
        if cov_type == "tied":
            prec = solve_triangular(jnp.linalg.cholesky(covs), eye,
                                    lower=True).T
            return prec, jnp.sum(jnp.log(jnp.diagonal(prec)))
        prec = 1.0 / jnp.sqrt(covs)
        if cov_type == "diag":
            return prec, jnp.sum(jnp.log(prec), axis=1)
        return prec, d * jnp.log(prec)


def log_prob(xb, weights, means, prec, logdet, cov_type, precision):
    """log(pi_j N(x_i | mu_j, Sigma_j)) for a block of rows: (block, k)."""
    d = xb.shape[1]
    diff = xb[:, None, :] - means[None, :, :]            # (block, k, d)
    if cov_type == "full":
        y = _dot("bkd,kde->bke", diff, prec, precision)
    elif cov_type == "tied":
        y = _dot("bkd,de->bke", diff, prec, precision)
    elif cov_type == "diag":
        y = diff * prec[None, :, :]
    else:
        y = diff * prec[None, :, None]
    return (jnp.log(weights) + logdet - 0.5 * d * jnp.log(2.0 * jnp.pi)
            )[None, :] - 0.5 * jnp.sum(y * y, axis=2)


def _over_rows(subscripts, a, b, precision, contract_rows):
    """``_dot`` of two (block, ...) operands over their rows, the rows
    taken ``contract_rows`` at a time and the pieces' products added up
    outside the product: the matmul unit's own running sum stays short."""
    pieces = a.shape[0] // contract_rows
    a, b = (v.reshape((pieces, contract_rows) + v.shape[1:]) for v in (a, b))
    lhs, rhs = subscripts.split("->")[0].split(",")
    # behind a barrier, or the compiler folds the sum over the pieces back
    # into one product over all the rows
    return jnp.sum(lax.optimization_barrier(_dot(
        f"s{lhs},s{rhs}->s{subscripts.split('->')[1]}", a, b, precision)),
        axis=0)


_STATIC = ("cov_type", "block_rows", "precision", "contract_rows")


@partial(jax.jit, static_argnames=_STATIC)
def _first_pass(x, i, weights, means, prec, logdet, cov_type, block_rows,
                precision, contract_rows):
    """Block ``i``'s share of the first pass: ``(sum r, sum r x, sum l)``."""
    xb = lax.dynamic_slice_in_dim(x, i * block_rows, block_rows, axis=0)
    logp = log_prob(xb, weights, means, prec, logdet, cov_type, precision)
    lse = logsumexp(logp, axis=1)
    resp = jnp.exp(logp - lse[:, None])
    return (jnp.sum(resp, axis=0),
            _over_rows("bk,bd->kd", resp, xb, precision, contract_rows),
            jnp.sum(lse))


@partial(jax.jit, static_argnames=_STATIC)
def _second_pass(x, i, weights, means, prec, logdet, new_means, cov_type,
                 block_rows, precision, contract_rows):
    """Block ``i``'s share of the second pass: the scatter of its rows
    about the NEW means, weighted by the responsibilities under the old
    parameters."""
    xb = lax.dynamic_slice_in_dim(x, i * block_rows, block_rows, axis=0)
    logp = log_prob(xb, weights, means, prec, logdet, cov_type, precision)
    resp = jnp.exp(logp - logsumexp(logp, axis=1)[:, None])
    diff = xb[:, None, :] - new_means[None, :, :]
    if cov_type in ("full", "tied"):
        sub = "bkd,bke->kde" if cov_type == "full" else "bkd,bke->de"
        return _over_rows(sub, resp[:, :, None] * diff, diff, precision,
                          contract_rows)
    return jnp.sum(resp[:, :, None] * diff * diff, axis=0)


def em_iteration(x, weights, means, covs, reg_covar, cov_type, block_rows,
                 precision="highest", contract_rows=None):
    """One iteration over all rows of ``x``: ``(weights, means, covs,
    lower_bound)`` as float32 NumPy arrays and a float, the bound taken at
    the parameters that came in.  A block's sums are float32 on the
    device; the blocks' sums are added up, and the closing divisions
    made, in float64 on the host, so that what is left of rounding is a
    block's own and not the order the blocks came in."""
    n, d = x.shape
    weights, means, covs = (jnp.asarray(a, jnp.float32)
                            for a in (weights, means, covs))
    prec, logdet = precisions_chol(covs, cov_type, d)
    static = dict(cov_type=cov_type, block_rows=block_rows,
                  precision=precision,
                  contract_rows=contract_rows or block_rows)
    blocks = range(n // block_rows)

    def total(parts):
        return [np.sum([np.asarray(p[j], np.float64) for p in parts], axis=0)
                for j in range(len(parts[0]))]

    nk, sx, bound = total([jax.device_get(_first_pass(
        x, i, weights, means, prec, logdet, **static)) for i in blocks])
    nk = nk + 1e-10
    new_means = (sx / nk[:, None]).astype(np.float32)
    (scatter,) = total([(jax.device_get(_second_pass(
        x, i, weights, means, prec, logdet, jnp.asarray(new_means),
        **static)),) for i in blocks])
    if cov_type == "full":
        new_covs = scatter / nk[:, None, None] + reg_covar * np.eye(d)
    elif cov_type == "tied":
        new_covs = scatter / np.sum(nk) + reg_covar * np.eye(d)
    elif cov_type == "diag":
        new_covs = scatter / nk[:, None] + reg_covar
    else:
        new_covs = np.mean(scatter / nk[:, None], axis=1) + reg_covar
    return ((nk / n).astype(np.float32), new_means,
            new_covs.astype(np.float32), float(bound / n))


def fit(x, start, n_iter, block_rows, precision="highest",
        cov_type="full", reg_covar=1e-6, contract_rows=None):
    """``n_iter`` iterations from ``start`` = (weights, means, covs):
    ``(weights, means, covs, history)`` as NumPy arrays, ``history[t]``
    the lower bound at the parameters iteration t met."""
    if x.shape[0] % block_rows:
        raise ValueError(f"{x.shape[0]} rows are no multiple of the "
                         f"reference's block of {block_rows}")
    weights, means, covs = start
    hist = []
    for _ in range(int(n_iter)):
        weights, means, covs, bound = em_iteration(
            x, weights, means, covs, reg_covar, cov_type, block_rows,
            precision, contract_rows)
        hist.append(bound)
    return weights, means, covs, np.asarray(hist, np.float64)


@partial(jax.jit, static_argnames=("cov_type", "block_rows"))
def _e_step(x, weights, means, covs, cov_type, block_rows):
    prec, logdet = precisions_chol(covs, cov_type, x.shape[1])

    def one(xb):
        logp = log_prob(xb, weights, means, prec, logdet, cov_type,
                        "highest")
        return logsumexp(logp, axis=1), jnp.argmax(logp, axis=1)

    lse, labels = lax.map(one, x.reshape(-1, block_rows, x.shape[1]))
    return jnp.mean(lse), labels.reshape(-1)


def e_step(x, weights, means, covs, block_rows, cov_type="full"):
    """``(mean log-likelihood, labels)`` of the rows under the parameters
    given: what ``score`` and ``predict`` are held to."""
    bound, labels = _e_step(x, jnp.asarray(weights, jnp.float32),
                            jnp.asarray(means, jnp.float32),
                            jnp.asarray(covs, jnp.float32), cov_type,
                            block_rows)
    return float(bound), np.asarray(jax.device_get(labels))


def compare(got, ref_weights, ref_means, ref_covs, ref_history, start,
            max_iter) -> dict:
    """The numbers one fit is judged by, each against the reference.

    ``first_bound_gap``: the relative gap of the first iteration's lower
    bound, where both sides still hold the same parameters: the E-step
    over all rows and nothing else, so a lost pass of precision in the
    whitened differences shows.  ``means_gap``: |M - M_ref|_F over
    |M_ref - M_start|_F, the distance the reference moved the means (a
    fit that hands back its start reads 1).  ``covariances_gap`` and
    ``weights_gap``: Frobenius norms of the gaps over the reference's.
    ``bound_gap``: the widest relative gap over the per-iteration bounds
    and the final ``lower_bound_``, which is the bound of the last
    iteration.  ``n_iter_gap``: the iterations run against the traffic's
    ``max_iter`` (tol is 0, so the loop may not stop early)."""
    def rel(a, b):
        b = np.asarray(b, np.float64)
        return float(np.linalg.norm(np.asarray(a, np.float64) - b)
                     / np.linalg.norm(b))

    ref_m = np.asarray(ref_means, np.float64)
    moved = np.linalg.norm(ref_m - np.asarray(start[1], np.float64))
    hist = np.asarray(got["history"], np.float64)
    ref_h = np.asarray(ref_history, np.float64)
    if hist.shape != ref_h.shape:
        bound_gap = first_gap = float("inf")
    else:
        gaps = np.abs(hist - ref_h) / np.abs(ref_h)
        first_gap = float(gaps[0])
        bound_gap = max(float(np.max(gaps)),
                        abs(got["lower_bound"] - ref_h[-1]) / abs(ref_h[-1]))
    return {"first_bound_gap": first_gap,
            "means_gap": float(np.linalg.norm(
                np.asarray(got["means"], np.float64) - ref_m) / moved),
            "covariances_gap": rel(got["covariances"], ref_covs),
            "weights_gap": rel(got["weights"], ref_weights),
            "bound_gap": bound_gap,
            "n_iter_gap": float(abs(int(got["n_iter"]) - int(max_iter)))}
