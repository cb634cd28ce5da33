"""Inputs from ``--seed``: made on the device, under the target sharding,
in one jitted call each, in the type they are used in.  The same seed
gives the same inputs; nothing is read from disk or made on the host
beyond a (k, d) table of means.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def key_of(seed: int, stream: int = 0):
    """A PRNG key for any whole-number seed (the driver's are over 2**31)
    and a stream index that keeps the arrays of one run apart."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)
    return jax.random.fold_in(key, stream)


def blob_means(seed: int, k: int, d: int) -> np.ndarray:
    """The (k, d) means of the mixture, uniform in the unit cube."""
    return np.random.default_rng([int(seed), 1]).random((k, d),
                                                        dtype=np.float32)


def seeded_centres(seed: int, i: int, means: np.ndarray,
                   sigma: float) -> np.ndarray:
    """The (k, d) starting centres of the ``i``-th fit: one draw from each
    component of the mixture, in an order drawn from the seed — what a
    careful initialiser (k-means++) hands Lloyd's iteration, without a
    pass over the rows.  Two starts in one component would leave the fit
    to tip either way on a rounding, and the comparison with the plain
    reference would measure that and not the program (PERF.md)."""
    rng = np.random.default_rng([int(seed), 2, int(i) + 1_000_000])
    k, d = means.shape
    return (means[rng.permutation(k)] + sigma * rng.standard_normal((k, d))
            ).astype(np.float32)


@partial(jax.jit, static_argnames=("rows", "chunk", "sigma", "sharding"))
def _blobs(key, means, rows, chunk, sigma, sharding):
    k, d = means.shape

    def body(i, buf):
        kz, kn = jax.random.split(jax.random.fold_in(key, i))
        z = jax.random.randint(kz, (chunk,), 0, k)
        blk = jnp.take(means, z, axis=0) \
            + sigma * jax.random.normal(kn, (chunk, d), jnp.float32)
        return lax.dynamic_update_slice(buf, blk, (i * chunk, 0))

    out = lax.fori_loop(0, rows // chunk, body,
                        jnp.zeros((rows, d), jnp.float32))
    return lax.with_sharding_constraint(out, sharding)


def blobs(seed: int, rows: int, means: np.ndarray, sigma: float,
          chunk: int, sharding):
    """``rows`` points of a k-component Gaussian mixture around ``means``
    (standard deviation ``sigma`` in every feature), float32, written
    chunk by chunk into one buffer so that the temporaries stay a chunk's
    size beside a multi-gigabyte result."""
    if rows % chunk:
        raise ValueError(f"rows {rows} must be a multiple of chunk {chunk}")
    return _blobs(key_of(seed, 3), jnp.asarray(means), rows, chunk,
                  float(sigma), sharding)


@partial(jax.jit, static_argnames=("shape", "sharding"))
def _normal(key, shape, sharding):
    return lax.with_sharding_constraint(
        jax.random.normal(key, shape, jnp.float32), sharding)


def normal_matrix(seed: int, stream: int, shape, sharding):
    """A standard-normal float32 matrix, each device making its own shard.
    Zero mean, so that an entry of a product is a sum of signed terms and
    a lost pass of precision shows against the entry's size."""
    return _normal(key_of(seed, stream), tuple(int(s) for s in shape),
                   sharding)
