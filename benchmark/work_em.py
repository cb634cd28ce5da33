"""Operations and bytes of one EM iteration of a Gaussian mixture, from
the configuration's shapes, and their registration in ``counts.py``'s
table.

``counts.FUNCTIONS`` is a closed table and the readers look a
configuration's ``work.flops`` up in it by name; a new estimator's work is
new code, so this module adds its two functions to the table when it is
imported, which the cell's driver (``drivers/em_fit.py``) does before any
reader runs.  No file that was there is edited (PERF.md, section 7, asks
the next ``benchmark`` PR to let ``counts.work`` find such a file itself).
"""

from __future__ import annotations

from benchmark import counts


def gmm_iter_flops(cfg) -> float:
    """One EM iteration over n rows, d features, k components with full
    covariances: the k whitened differences (x - mu_j) P_j of the E-step
    (2nkd^2) and the k weighted Gram sums of the M-step (2nkd^2), dense.
    Means, weights, the log-sum-exp (O(nkd + nk)) and the k Cholesky
    factorisations (O(kd^3)) are lower order and left out, as the count of
    a Lloyd iteration leaves its norms out."""
    return 4.0 * cfg["rows"] * cfg["components"] * cfg["features"] ** 2


def gmm_iter_bytes(cfg) -> float:
    """The least traffic of one EM iteration: one read of X.  Parameters
    and sums are k d / n of it."""
    return float(cfg["rows"]) * cfg["features"] * cfg["dtype_bytes"]


counts.FUNCTIONS.update({f.__name__: f
                         for f in (gmm_iter_flops, gmm_iter_bytes)})
