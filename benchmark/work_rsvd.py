"""Operations and bytes of one power iteration of a randomized SVD, from
the configuration's shapes, and their registration in ``counts.py``'s
table (as ``work_em.py`` registers the mixture's: the cell's driver
imports this module before any reader runs, and no file that was there
is edited).

A call ``random_svd(A, iters=q, nsv=r, oversample=l - r)`` on A (m, n) is
counted whole and divided by q, the cell's unit being a power iteration
(a call's sketch, projection and lift ride on its iterations):

- the tall products A Omega, q times A^T Q and A W, and Q^T A: (2q + 2)
  products of 2 m n l;
- the tall orthonormalisations of an (m, l) panel, q + 1 of them: 4 m l^2
  - 4 l^3 / 3 each, a Householder QR with Q formed, whatever implements
  it (CholeskyQR2 spends about 8 m l^2; what it spends beyond the count is
  not the algorithm's need);
- the lift Q U_b: 2 m l r.

The q orthonormalisations of the (n, l) panel and the small SVD are
O(n l^2) and left out, as the count of an EM iteration leaves its
Cholesky factorisations out.
"""

from __future__ import annotations

from benchmark import counts


def _shape(cfg):
    sketch = cfg["nsv"] + cfg["oversample"]
    return cfg["rows"], cfg["features"], sketch, cfg["nsv"], cfg["iters"]


def rsvd_iter_flops(cfg) -> float:
    """A call's operations over its power iterations."""
    m, n, l, r, q = _shape(cfg)
    call = (2 * q + 2) * 2.0 * m * n * l \
        + (q + 1) * (4.0 * m * l ** 2 - 4.0 * l ** 3 / 3) \
        + 2.0 * m * l * r
    return call / q


def rsvd_iter_bytes(cfg) -> float:
    """The least traffic of a call over its power iterations: 2q + 2
    reads of A, a read and a write of the panel for each tall
    orthonormalisation, the lift's read of Q and write of U."""
    m, n, l, r, q = _shape(cfg)
    call = ((2 * q + 2) * m * n + (q + 1) * 2 * m * l + m * l + m * r) \
        * float(cfg["dtype_bytes"])
    return call / q


counts.FUNCTIONS.update({f.__name__: f
                         for f in (rsvd_iter_flops, rsvd_iter_bytes)})
