"""Rows of a Gaussian mixture with full covariances, from ``--seed``: made
on the device chunk by chunk, under the target sharding, in one jitted
call.  Component c draws x = mu_c + z A_c with z standard normal and
A_c = sigma (I + G_c / (2 sqrt d)), G_c standard normal: covariances
A_c^T A_c that are full, differ between the components and are
conditioned like sigma^2 I within a factor of about four.  The means are
uniform in a cube of side ``cube``.  Nothing is made on the host beyond
the (k, d) means and the (k, d, d) factors.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import datagen


def mixture_means(seed: int, k: int, d: int, cube: float) -> np.ndarray:
    """The (k, d) means, uniform in a cube of side ``cube``."""
    return (cube * np.random.default_rng([int(seed), 21]).random(
        (k, d), dtype=np.float32)).astype(np.float32)


def mixture_factors(seed: int, k: int, d: int, sigma: float) -> np.ndarray:
    """The (k, d, d) factors A_c = sigma (I + G_c / (2 sqrt d))."""
    g = np.random.default_rng([int(seed), 22]).standard_normal(
        (k, d, d), dtype=np.float32)
    return (sigma * (np.eye(d, dtype=np.float32) + g / (2.0 * np.sqrt(d)))
            ).astype(np.float32)


@partial(jax.jit, static_argnames=("rows", "chunk", "sharding"))
def _mixture(key, means, factors, rows, chunk, sharding):
    k, d = means.shape
    # the k factors side by side: a chunk's draws meet all of them in one
    # product and each row keeps its own component's
    side = jnp.transpose(factors, (1, 0, 2)).reshape(d, k * d)

    def body(i, buf):
        kc, kn = jax.random.split(jax.random.fold_in(key, i))
        comp = jax.nn.one_hot(jax.random.randint(kc, (chunk,), 0, k), k,
                              dtype=jnp.float32)
        z = jax.random.normal(kn, (chunk, d), jnp.float32)
        za = jnp.matmul(z, side, precision="highest").reshape(chunk, k, d)
        blk = jnp.matmul(comp, means, precision="highest") \
            + jnp.sum(za * comp[:, :, None], axis=1)
        return lax.dynamic_update_slice(buf, blk, (i * chunk, 0))

    out = lax.fori_loop(0, rows // chunk, body,
                        jnp.zeros((rows, d), jnp.float32))
    return lax.with_sharding_constraint(out, sharding)


def mixture(seed: int, rows: int, means: np.ndarray, factors: np.ndarray,
            chunk: int, sharding):
    """``rows`` points of the mixture, float32, components equally likely,
    written chunk by chunk into one buffer so that the temporaries stay a
    chunk's size beside a multi-gigabyte result."""
    if rows % chunk:
        raise ValueError(f"rows {rows} must be a multiple of chunk {chunk}")
    return _mixture(datagen.key_of(seed, 23), jnp.asarray(means),
                    jnp.asarray(factors), rows, chunk, sharding)
