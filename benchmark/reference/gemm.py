"""Plain matrix product: the reference ``ds.matmul`` is held to.

``jax.numpy.matmul`` in float32 at ``precision='highest'`` on rows of the
result drawn from the seed; no panels, no schedule, and nothing imported
from the program.  Rows are picked with a one-hot selection product
(exact at 'highest': one times a float32 is that float32), which a
sharded operand needs no gather for.

``precision`` is the control's handle: ``'high'`` is three bf16 passes,
one step below the float32 policy's six; ``'bfloat16'`` rounds both
operands to bfloat16 and accumulates in float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def sample_rows(seed: int, m: int, count: int) -> np.ndarray:
    """``count`` distinct row indices of an m-row result, from the seed."""
    rng = np.random.default_rng([int(seed), 7])
    return np.sort(rng.choice(m, size=min(count, m), replace=False))


def _selector(rows, m):
    return jax.nn.one_hot(jnp.asarray(rows), m, dtype=jnp.float32)


@partial(jax.jit, static_argnames=("precision",))
def product_rows(a, b, rows, precision="highest"):
    """Rows ``rows`` of ``a @ b``."""
    a_rows = jnp.matmul(_selector(rows, a.shape[0]), a, precision="highest")
    if precision == "bfloat16":
        return jnp.matmul(a_rows.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a_rows, b, precision=precision)


@jax.jit
def take_rows(c, rows):
    """Rows ``rows`` of a result the program made, exactly."""
    return jnp.matmul(_selector(rows, c.shape[0]), c, precision="highest")


def compare(got_rows, ref_rows) -> dict:
    """``product_max_gap``: the widest |C - C_ref| over the sampled
    entries, against the root mean square of the reference's entries (an
    entry is a sum of signed terms, so single entries come near zero).
    ``product_rms_gap``: the root mean square of C - C_ref against the
    same."""
    got = np.asarray(jax.device_get(got_rows), np.float64)
    ref = np.asarray(jax.device_get(ref_rows), np.float64)
    scale = float(np.sqrt(np.mean(ref * ref)))
    diff = got - ref
    return {"product_max_gap": float(np.max(np.abs(diff)) / scale),
            "product_rms_gap": float(np.sqrt(np.mean(diff * diff)) / scale)}
