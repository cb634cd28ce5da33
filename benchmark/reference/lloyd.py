"""Plain Lloyd's k-means: the reference ``KMeans.fit`` is held to.

Straightforward ``jax.numpy`` in float32 with every product at
``precision='highest'``; no kernels, no sharding, no fit loop, and nothing
imported from the program.  The textbook step: squared distances as
|x|^2 - 2 x.c + |c|^2, argmin, per-cluster sums and counts, centres =
sums / counts (an empty cluster keeps its centre).  Rows are visited in
blocks so the (rows, k) temporaries fit beside a multi-gigabyte X.

``precision`` is the control's handle: ``'high'`` (three bf16 passes) runs
both products one step below what the configuration states;
``'bfloat16'`` rounds x and the centres to bfloat16 for the distance
cross-term only and keeps the centre sums in float32, which is what the
program's own ``fast_distance`` path does.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@partial(jax.jit, static_argnames=("block_rows", "precision"))
def lloyd_iteration(x, centers, block_rows, precision="highest"):
    """One iteration over all rows of ``x``: ``(new_centers, inertia)``
    with the inertia taken at the centres that came in."""
    n, d = x.shape
    k = centers.shape[0]
    c_sq = jnp.sum(centers * centers, axis=1)

    def body(i, acc):
        sums, counts, inertia = acc
        xb = lax.dynamic_slice_in_dim(x, i * block_rows, block_rows, axis=0)
        x_sq = jnp.sum(xb * xb, axis=1, keepdims=True)
        if precision == "bfloat16":
            cross = jnp.matmul(xb.astype(jnp.bfloat16),
                               centers.astype(jnp.bfloat16).T,
                               preferred_element_type=jnp.float32)
            sum_precision = "highest"
        else:
            cross = jnp.matmul(xb, centers.T, precision=precision)
            sum_precision = precision
        dist = jnp.maximum(x_sq - 2.0 * cross + c_sq[None, :], 0.0)
        onehot = jax.nn.one_hot(jnp.argmin(dist, axis=1), k, dtype=x.dtype)
        return (sums + jnp.matmul(onehot.T, xb, precision=sum_precision),
                counts + jnp.sum(onehot, axis=0),
                inertia + jnp.sum(jnp.min(dist, axis=1)))

    zero = (jnp.zeros((k, d), x.dtype), jnp.zeros((k,), x.dtype),
            jnp.zeros((), x.dtype))
    sums, counts, inertia = lax.fori_loop(0, n // block_rows, body, zero)
    new = jnp.where(counts[:, None] > 0,
                    sums / jnp.maximum(counts, 1.0)[:, None], centers)
    return new, inertia


def fit(x, init, n_iter, block_rows, precision="highest"):
    """``n_iter`` iterations from ``init``: ``(centers, history)`` as NumPy
    arrays, ``history[t]`` the inertia at the centres iteration t met."""
    if x.shape[0] % block_rows:
        raise ValueError(f"{x.shape[0]} rows are no multiple of the "
                         f"reference's block of {block_rows}")
    centers = jnp.asarray(init, jnp.float32)
    hist = []
    for _ in range(int(n_iter)):
        centers, inertia = lloyd_iteration(x, centers, block_rows,
                                           precision)
        hist.append(inertia)
    return (np.asarray(jax.device_get(centers)),
            np.asarray(jax.device_get(jnp.stack(hist)), np.float64))


def compare(got, ref_centers, ref_history, init, max_iter) -> dict:
    """The numbers one fit is judged by, each against the reference.

    ``centers_gap``: |C - C_ref|_F over |C_ref - C_init|_F, the distance
    the reference moved the centres (a fit that hands back its start
    reads 1).  ``first_inertia_gap``: the relative gap of the first
    iteration's inertia, where both sides still hold the same centres:
    the distances and their sum over all rows and nothing else, so it is
    steady from seed to seed (sound runs read an ulp or two of float32)
    and a lost pass of precision in the distances shows.
    ``inertia_gap``: the widest relative gap over the per-
    iteration inertias and the final ``inertia_``.  ``n_iter_gap``: the
    iterations run against the traffic's ``max_iter`` (tol is 0, so the
    loop may not stop early)."""
    c = np.asarray(got["centers"], np.float64)
    ref = np.asarray(ref_centers, np.float64)
    moved = np.linalg.norm(ref - np.asarray(init, np.float64))
    hist = np.asarray(got["history"], np.float64)
    ref_h = np.asarray(ref_history, np.float64)
    if hist.shape != ref_h.shape:
        inertia_gap = first_gap = float("inf")
    else:
        gaps = np.abs(hist - ref_h) / np.abs(ref_h)
        first_gap = float(gaps[0])
        inertia_gap = max(float(np.max(gaps)),
                          abs(got["inertia"] - ref_h[-1]) / abs(ref_h[-1]))
    return {"centers_gap": float(np.linalg.norm(c - ref) / moved),
            "first_inertia_gap": first_gap,
            "inertia_gap": inertia_gap,
            "n_iter_gap": float(abs(int(got["n_iter"]) - int(max_iter)))}
