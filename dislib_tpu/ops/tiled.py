"""Streamed ε-neighborhood passes over row/column tiles.

The quadratic estimators (DBSCAN, Daura — reference:
`dislib/cluster/dbscan` region grids, `dislib/cluster/daura` block-pair
RMSD-count tasks) need per-row reductions over the ε-adjacency relation of
the whole dataset.  The reference partitions *space* into regions because no
CPU worker can hold all pairwise distances; the TPU-native equivalent keeps
the algorithms' semantics but streams the adjacency in (tile × tile) pieces
of the distance GEMM — peak memory is O(tile²) + O(m·n) for the resident
points, never O(m²).  FLOPs are recomputed per pass (distance GEMMs are
MXU-cheap; HBM capacity is the scarce resource).

One primitive covers every consumer: for each row i,

    count_i = |{ j : adj(i,j) ∧ colmask_j }|
    min_i   = min{ vals_j : adj(i,j) ∧ colmask_j }      (sentinel if empty)

where adj(i,j) = (‖x_i − x_j‖² ≤ eps2) ∨ (i = j) — the structural diagonal
keeps every point its own neighbor regardless of fp rounding.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from dislib_tpu.ops.base import distances_sq

# tile edge for the streamed passes (module-level so tests can shrink it)
TILE = 2048


def pad_to_tiles(xv, tile):
    """Zero-pad rows to a tile multiple; returns (padded, n_tiles)."""
    n_tiles = -(-xv.shape[0] // tile)
    return jnp.pad(xv, ((0, n_tiles * tile - xv.shape[0]), (0, 0))), n_tiles


def neigh_count_min(xv, eps2, vals, colmask, sentinel, tile,
                    use_pallas=False):
    """Per-row (count, min) over the ε-adjacency, streamed in tiles.

    xv: (mp, n) with mp % tile == 0.  vals/colmask: (mp,).  Rows are NOT
    masked here — callers mask invalid rows in their own domain.
    ``use_pallas`` routes the tile distance kernel through
    ``ops/pallas_kernels`` (the ``DSLIB_OVERLAP=pallas`` inner-loop
    route; a jit static for the enclosing kernel — the single-device
    tier has no collective to overlap, so this is the only knob that
    applies to it).  Single-device means it: this tier calls the kernel
    outside any ``shard_map``, and on a TPU mesh with more than one
    device XLA refuses to partition a Mosaic kernel (an error, by
    design — the multi-row tier is the ring)."""
    mp, n = xv.shape
    nt = mp // tile
    x_tiles = xv.reshape(nt, tile, n)
    offs = jnp.arange(nt, dtype=jnp.int32) * tile
    vals_t = vals.reshape(nt, tile)
    mask_t = colmask.reshape(nt, tile)

    def row_body(_, rx):
        xrow, roff = rx
        row_ids = roff + jnp.arange(tile, dtype=jnp.int32)

        def col_body(acc, cx):
            xcol, coff, v, cm = cx
            col_ids = coff + jnp.arange(tile, dtype=jnp.int32)
            d2 = distances_sq(xrow, xcol, use_pallas=use_pallas)
            adj = ((d2 <= eps2) | (row_ids[:, None] == col_ids[None, :])) \
                & cm[None, :]
            cnt = acc[0] + jnp.sum(adj, axis=1)
            mn = jnp.minimum(acc[1],
                             jnp.min(jnp.where(adj, v[None, :], sentinel),
                                     axis=1))
            return (cnt, mn), None

        acc0 = (jnp.zeros((tile,), jnp.int32),
                jnp.full((tile,), sentinel, vals.dtype))
        (cnt, mn), _ = lax.scan(col_body, acc0, (x_tiles, offs, vals_t, mask_t))
        return None, (cnt, mn)

    _, (counts, mins) = lax.scan(row_body, None, (x_tiles, offs))
    return counts.reshape(mp), mins.reshape(mp)
