"""Rows of a tall matrix with a decaying spectrum, from ``--seed``: made
on the device chunk by chunk, under the target sharding, in one jitted
call.  A row is x = z diag(sigma) W^T + noise * e with z (directions,)
and e (n,) standard normal, sigma_i = ratio^i and W (n, directions) with
orthonormal columns: the matrix's singular values are close to sqrt(rows)
sigma_i over the first ``directions`` and a floor of about sqrt(rows)
noise beyond, and its right singular vectors close to W's columns.
Nothing is made on the host beyond W and sigma.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import datagen


def spectrum(directions: int, ratio: float) -> np.ndarray:
    """sigma_i = ratio^i, i = 0 .. directions - 1."""
    return (float(ratio) ** np.arange(directions)).astype(np.float32)


def basis(seed: int, n: int, directions: int) -> np.ndarray:
    """W (n, directions): orthonormal columns, the Q of a seeded normal
    matrix's QR, in float64 on the host and rounded once."""
    g = np.random.default_rng([int(seed), 31]).standard_normal(
        (n, directions))
    return np.linalg.qr(g)[0].astype(np.float32)


@partial(jax.jit, static_argnames=("rows", "chunk", "noise", "sharding"))
def _lowrank(key, mix, rows, chunk, noise, sharding):
    directions, n = mix.shape

    def body(i, buf):
        kz, ke = jax.random.split(jax.random.fold_in(key, i))
        z = jax.random.normal(kz, (chunk, directions), jnp.float32)
        blk = jnp.matmul(z, mix, precision="highest") \
            + noise * jax.random.normal(ke, (chunk, n), jnp.float32)
        return lax.dynamic_update_slice(buf, blk, (i * chunk, 0))

    out = lax.fori_loop(0, rows // chunk, body,
                        jnp.zeros((rows, n), jnp.float32))
    return lax.with_sharding_constraint(out, sharding)


def lowrank(seed: int, rows: int, sigma: np.ndarray, w: np.ndarray,
            noise: float, chunk: int, sharding):
    """``rows`` rows of the matrix, float32, written chunk by chunk into
    one buffer so that the temporaries stay a chunk's size beside a
    multi-gigabyte result."""
    if rows % chunk:
        raise ValueError(f"rows {rows} must be a multiple of chunk {chunk}")
    mix = (np.asarray(sigma, np.float64)[:, None]
           * np.asarray(w, np.float64).T).astype(np.float32)
    return _lowrank(datagen.key_of(seed, 32), jnp.asarray(mix), rows, chunk,
                    float(noise), sharding)
