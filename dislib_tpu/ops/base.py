"""Shared device kernels used across estimators.

These are the hot inner ops the reference computes per block inside NumPy
`@task`s (e.g. `scipy cdist` in `dislib/cluster/kmeans._partial_sum`);
here each is a single MXU-friendly formulation shared by every caller so
numerical fixes land in one place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dislib_tpu.parallel import mesh as _mesh
# the f32-faithful trace scope lives in the precision-policy module (the
# one place compute precision is decided — see ops/precision.py and the
# precision-policy lint); re-exported here for the package-wide import
# path every kernel already uses
from dislib_tpu.ops import precision as px
from dislib_tpu.ops.precision import precise  # noqa: F401


def distances_sq(a, b, precision=None, use_pallas=False):
    """Pairwise squared euclidean distances (m, k) between rows of `a` (m, d)
    and rows of `b` (k, d): one GEMM + norms (‖a‖² − 2a·bᵀ + ‖b‖²), clamped
    at zero against cancellation.

    Dense ds-array operands return a ds-array and join the dispatch-fusion
    graph (`data/array.py`): the distance GEMM rides the operands' deferred
    chains and dispatches with the first force — under ``DSLIB_EAGER=1`` it
    is one dedicated kernel dispatch instead.

    ``precision=None`` inherits the enclosing scope's matmul precision —
    inside the library's kernels that is the float32-faithful scope set by
    :func:`precise`.  At TPU-native bf16 the cross-term error (~‖x‖²/256)
    dwarfs ε-thresholds — a point's distance to ITSELF comes out ≫ 0,
    breaking radius comparisons (DBSCAN/Daura) — so callers outside a
    ``precise`` kernel should pass an explicit precision.

    ``use_pallas=True`` (raw jax operands only) lowers the whole
    formulation through the ``ops/pallas_kernels`` tile kernel — the
    ``DSLIB_OVERLAP=pallas`` route for the ring/tiled ε-pass inner loop;
    callers thread it as a jit static (``ops/overlap.resolve``)."""
    import importlib
    # deferred import, cycle-free at load; the data package re-exports an
    # `array` FUNCTION, so resolve the module by its dotted name
    _arr = importlib.import_module("dislib_tpu.data.array")
    if isinstance(a, _arr.Array) or isinstance(b, _arr.Array):
        if not (type(a) is _arr.Array and type(b) is _arr.Array):
            raise TypeError(
                "distances_sq over ds-arrays needs BOTH operands as dense "
                f"Arrays, got {type(a).__name__} and {type(b).__name__}")
        return _arr._array_distances(a, b, precision)
    if use_pallas:
        from dislib_tpu.ops import pallas_kernels as _pk
        return _pk.distances_sq(a, b, precision=precision)
    a_sq = jnp.sum(a * a, axis=1, keepdims=True)
    b_sq = jnp.sum(b * b, axis=1)
    cross = jnp.matmul(a, b.T, precision=precision)
    return jnp.maximum(a_sq - 2.0 * cross + b_sq[None, :], 0.0)


# -- the Lloyd step in one pass over the rows ---------------------------------
# Tiles of the fused kernel, derived from the shapes and never from an
# option: an inner chunk of rows whose (k, chunk) distance tile stays in
# vregs, and a grid block of whole chunks whose double buffer fills
# `_LLOYD_X_VMEM` (the kernel asks Mosaic for 100 of the v5e's 128 MiB).
# Read on the chip at 12M x 100, k = 10 (PERF.md, PR 28): chunks of 1280,
# 1920, 3200 and 6400 rows cost 7.72, 7.36, 7.12 and 7.35 ms an iteration;
# blocks of 32 000 and 48 000 rows cost the same.
_LANES = 128
_LLOYD_CHUNK = 3200                  # rows of the inner loop, at small k
_LLOYD_TILE_VREGS = 50               # vregs a (k, chunk) float32 tile may take
_LLOYD_X_VMEM = 40 * 2 ** 20         # both buffers of the x block
_LLOYD_SMALL_VMEM = 4 * 2 ** 20      # centres and sums, lane-padded
_LLOYD_BACKENDS = ("tpu",)           # where the kernel is compiled, not interpreted


def lloyd_tiles(rows: int, d: int, k: int, dtype):
    """``(block, chunk)`` rows of the fused Lloyd step for ``rows`` rows
    on a device, or None where the kernel does not apply: another dtype
    than float32; ``d`` a multiple of the lane width (the TPU then holds
    X rows-major, and the kernel reads it features-major); centres too
    many or too wide for the vregs and VMEM it may take; fewer rows than
    one block.  The block is the largest whole number of chunks that fits
    the VMEM budget and divides ``rows``, if one of at least half the
    budget does; else the budget's, and the last block is ragged."""
    if jnp.dtype(dtype) != jnp.dtype(px.accum_dtype(px.FLOAT32)):
        return None
    kp = -(-k // 16) * 16               # whole bfloat16 sublane tiles
    if d % _LANES == 0 \
            or 2 * kp * -(-d // _LANES) * _LANES * 4 > _LLOYD_SMALL_VMEM:
        return None
    chunk = _LANES * min(_LLOYD_CHUNK // _LANES,
                         _LLOYD_TILE_VREGS // (kp // 8))
    if chunk < _LANES:
        return None
    most = _LLOYD_X_VMEM // (2 * -(-d // 8) * 8 * 4) // chunk
    if most < 1 or rows < most * chunk:
        return None
    whole = [c for c in range(most, (most + 1) // 2 - 1, -1)
             if rows % (c * chunk) == 0]
    return (whole[0] if whole else most) * chunk, chunk


def lloyd_step_fuses(x, k: int) -> bool:
    """Whether :func:`lloyd_step` applies to the row-sharded ``x``
    (rows, d) and ``k`` centres, by what a trace can observe: the
    backend compiles Mosaic kernels, and :func:`lloyd_tiles` finds
    tiles for one device's rows."""
    if jax.default_backend() not in _LLOYD_BACKENDS:
        return False
    local = x.shape[0] // _mesh.get_mesh().shape[_mesh.ROWS]
    return lloyd_tiles(local, x.shape[1], k, x.dtype) is not None


def load_lloyd_step() -> None:
    """Import the fused step's kernel module now, where this backend may
    run it.  For the host code that is about to call a jitted program
    which may hold the step (``_kmeans_fit`` runs it at every dispatch,
    ``profiled_jit(before=)``): the first import of Pallas in a process is
    1.0-1.6 s of host time (JAX 0.9.0 pulls its Mosaic-GPU modules in),
    and made INSIDE a jit trace, where :func:`lloyd_step` would otherwise
    make it, it reads 0.4 s longer (PERF.md, PR 28).  Costs a dictionary
    look-up once the module is there; a caller that skips it loses only
    that time."""
    if jax.default_backend() in _LLOYD_BACKENDS:
        from dislib_tpu.ops import pallas_kernels  # noqa: F401


def lloyd_step(x, x_sq, w, centers):
    """``(sums, counts, inertia)`` of one Lloyd step in ONE pass over
    ``x`` (rows, d), rows sharded over the mesh's ``rows`` axis: per
    cluster the weighted sum of the rows nearest to it ``(k, d)`` and
    their weight ``(k,)``, and the weighted sum of every row's squared
    distance to its nearest centre.  ``x_sq`` (1, rows) are the rows'
    squared norms and ``w`` (1, rows) their weights (1, or 0 on padding), as
    rows so that they lie beside X's rows on the kernel's lanes: they do
    not depend on the centres, and a caller that iterates makes them
    once, outside its loop.  ``centers`` (k, d).  The caller asks
    :func:`lloyd_step_fuses` first.

    Each device runs ``ops/pallas_kernels.py::kmeans_step`` over its own
    rows inside a ``shard_map`` (GSPMD cannot partition a Mosaic kernel),
    and the three partials cross the mesh packed into ONE ``psum``.  The
    one ``shard_map`` of the library without ``check_vma``: the Pallas
    interpreter runs the kernel's grid as a scan whose carry it cannot
    type over the mesh (JAX's own message names this switch), and the
    output is replicated by construction, a ``psum``."""
    from dislib_tpu.ops import pallas_kernels as _pk
    mesh = _mesh.get_mesh()
    k, d = centers.shape
    block, chunk = lloyd_tiles(x.shape[0] // mesh.shape[_mesh.ROWS], d, k,
                               x.dtype)

    def local(xs, sq, ws, c):
        sums, counts, inertia = _pk.kmeans_step(xs, sq, ws, c, block, chunk)
        return lax.psum(jnp.concatenate(
            [sums.reshape(-1), counts, inertia[None]]), _mesh.ROWS)

    packed = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS, None), P(None, _mesh.ROWS),
                  P(None, _mesh.ROWS), P()),
        out_specs=P(), check_vma=False)(x, x_sq, w, centers)
    return packed[:k * d].reshape(k, d), packed[k * d:-1], packed[-1]
