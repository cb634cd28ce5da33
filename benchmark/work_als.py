"""Operations and bytes of one ALS iteration on sparse ratings, from the
configuration's shapes, and their registration in ``counts.py``'s table
(as ``work_em.py`` registers the mixture's: the cell's driver imports this
module before any reader runs, and no file that was there is edited).

An iteration over nnz ratings of m users and n items with f factors is a
user half-step, an item half-step and the RMSE:

- the Grams: each rating adds o o^T to its user's and to its item's
  normal equations, 2 nnz f^2 each, whole f x f;
- the moments: r o to both sides', 2 nnz f each;
- the Cholesky factorisations, f^3 / 3 each, and their two triangular
  solves, 2 f^2, for m + n systems;
- the RMSE: u . v - r for each rating, 2 nnz f.

The least traffic: each half-step and the RMSE read the entry stream once
(user, item and rating, 12 B a rating) and the factors once.  A gathered
factor row is not charged a rating: a kernel that keeps the other side's
factors on the chip need not read them again.
"""

from __future__ import annotations

from benchmark import counts


def _shape(cfg):
    return cfg["ratings"], cfg["users"], cfg["items"], cfg["n_f"]


def als_gram_flops(cfg) -> float:
    """The Grams and moments of both half-steps."""
    nnz, _, _, f = _shape(cfg)
    return 4.0 * nnz * f * f + 4.0 * nnz * f


def als_gram_bytes(cfg) -> float:
    """The two half-steps' reads of the entry stream and of the factors."""
    nnz, m, n, f = _shape(cfg)
    return 2 * 12.0 * nnz + 2 * 4.0 * (m + n) * f


def als_iter_flops(cfg) -> float:
    """One iteration: Grams and moments, m + n factorisations and solves,
    the RMSE."""
    nnz, m, n, f = _shape(cfg)
    return als_gram_flops(cfg) + (m + n) * (f ** 3 / 3.0 + 2.0 * f * f) \
        + 2.0 * nnz * f


def als_iter_bytes(cfg) -> float:
    """One iteration: three reads of the entry stream, and each factor
    matrix read twice and written once."""
    nnz, m, n, f = _shape(cfg)
    return 3 * 12.0 * nnz + 3 * 4.0 * (m + n) * f


counts.FUNCTIONS.update({f.__name__: f for f in (
    als_gram_flops, als_gram_bytes, als_iter_flops, als_iter_bytes)})
