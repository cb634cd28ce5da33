"""Shared device kernels used across estimators.

These are the hot inner ops the reference computes per block inside NumPy
`@task`s (e.g. `scipy cdist` in `dislib/cluster/kmeans._partial_sum`);
here each is a single MXU-friendly formulation shared by every caller so
numerical fixes land in one place.
"""

from __future__ import annotations

from typing import NamedTuple

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


# -- the EM step of a Gaussian mixture in one blocked pass over the rows ------
# A block's temporaries are its rows' whitened differences and their
# weighted copies, (block, k, d) float32 each; everything else of a block
# is (block, k) or (block, d).  The block is derived from the shapes and
# never from an option: the most rows whose (block, k, d) tile stays
# under `_EM_TILE_BYTES`, in whole `_EM_ROW_QUANTUM`s (the chip holds a
# tall X with its rows on the lanes, so a block starts on a lane tile).
_EM_TILE_BYTES = 32 * 2 ** 20
_EM_ROW_QUANTUM = 512
_LOG2PI = 1.8378770664093453         # log(2 pi)
COVARIANCE_TYPES = ("full", "tied", "diag", "spherical")


def em_block(rows: int, d: int, k: int) -> int:
    """Rows of one block of the blocked EM pass over ``rows`` rows on a
    device: :func:`row_block` at what a row costs there."""
    return row_block(rows, _em_row_bytes(k, d))


def _em_row_bytes(k: int, d: int) -> int:
    """What a row costs in an EM block's largest temporary: its (k, d)
    float32 tile of whitened differences, d in whole sublane tiles."""
    return 4 * k * (d + -d % 8)


def _em_cut(i, block, local, *arrays):
    """``(start, blocks)``: where the ``i``-th block of ``block`` rows
    starts among a device's ``local`` rows (a ragged last block starts
    early, so that it ends with the rows) and that block of every array."""
    start = jnp.minimum(i * block, local - block)
    return start, [lax.dynamic_slice_in_dim(a, start, block) for a in arrays]


def blocked_row_sums(xp, m, d, row_bytes, body, zero, per_row=()):
    """The library's blocked pass over the rows: the sums over all row
    blocks of ``body(xb, w, start, *per_row_blocks)``, a block's partial
    sums (a pytree shaped like ``zero``), over the padded, row-sharded
    ``xp``: every device of the mesh's ``rows`` axis loops over its own
    rows' blocks (:func:`local_row_sums`), and ONE ``psum`` of the packed
    totals crosses the devices (none on a mesh of one).  ``xb`` (block, d)
    are a block's rows with the padding columns cropped, ``w`` (block,)
    their weights (1; 0 on the padding rows past ``m`` and on the rows of
    a ragged last block that an earlier block has seen), ``start`` the
    block's first row in the whole array.  ``per_row`` are further (rows,
    ...) arrays cut the same way.  ``row_bytes`` is the caller's tile
    budget: what one row costs in a block's largest temporary, from which
    :func:`row_block` derives the block."""
    from jax.flatten_util import ravel_pytree
    mesh = _mesh.get_mesh()
    local = xp.shape[0] // mesh.shape[_mesh.ROWS]
    block = row_block(local, row_bytes)

    def device(xs, *others):
        total = local_row_sums(
            xs, others, lax.axis_index(_mesh.ROWS) * local, m, block,
            lambda xb, *rest: body(xb[:, :d], *rest), zero)
        flat, unravel = ravel_pytree(total)
        return unravel(lax.psum(flat, _mesh.ROWS))

    row_spec = P(_mesh.ROWS, None)
    return jax.shard_map(
        device, mesh=mesh, in_specs=(row_spec,) * (1 + len(per_row)),
        out_specs=P())(xp, *per_row)


class Whitener(NamedTuple):
    """What :func:`em_whitener` makes of a mixture's parameters."""

    centre: jax.Array       # (d,) where the pass moves the origin to
    about: jax.Array        # (k, d) the means there
    proj: tuple | jax.Array | None  # full: a group's right operand each;
    #                                 tied: the one P; else None
    t: tuple | jax.Array    # the whitened means: full a group's (e, k8)
    #                         each; else (k, d)
    scale: jax.Array | None  # diag and spherical: P_j itself
    const: jax.Array        # (k,) log pi_j + log det P_j - (d/2) log 2 pi


def em_cuts(cov_type: str, dtype) -> bool:
    """Whether the E-step's product is cut along its factors' triangle
    (:func:`em_groups`): for full covariances, where
    :func:`precision.pdot_short` packs.  What a trace can observe."""
    return cov_type == "full" and px.packs_short(dtype, dtype)


def em_groups(d: int, dtype) -> tuple:
    """``((e0, e1, c), ...)``: how the full-covariance E-step cuts the d
    columns e of every factor into groups, and the ``c`` leading rows of
    the factors that the product of group ``[e0, e1)`` contracts.

    A factor is upper triangular, so column e holds nothing under row e
    and a product of the columns up to e1 needs the first e1 rows alone.
    Where the product is packed (:func:`em_cuts`) that decides its
    passes through the MXU, ``ceil(6 c / depth)`` with c the rows in
    whole chunks of 16, and the columns are cut where that number changes
    (d = 50: [0, 16) in one pass 96 deep, [16, 32) in two, 192, [32, 50)
    in three, 384: with k = 16, 15 passes of a column tile for the whole
    product's 21; d <= 16 is one group, the whole product).  Where it is
    not packed a pass costs the same whatever it holds, and there is one
    group of all d rows."""
    if not em_cuts("full", dtype):
        return ((0, d, d),)
    groups = []
    for c in range(px.SHORT_CHUNK, d + px.SHORT_CHUNK, px.SHORT_CHUNK):
        passes = -(-6 * c // px._MXU_COLUMNS)
        if groups and groups[-1][0] == passes:
            groups[-1] = (passes, groups[-1][1], min(c, d), c)
        else:
            groups.append((passes, c - px.SHORT_CHUNK, min(c, d), c))
    return tuple((e0, e1, c) for _, e0, e1, c in groups)


def em_whitener(log_weights, means, prec, cov_type) -> Whitener:
    """What the E-step needs of the parameters, made once a step outside
    the pass over the rows.

    The pass works in coordinates moved to ``centre`` (d,), the mixture's
    own mean: a Gaussian mixture does not change under a translation of
    the rows, and with the rows centred the products below round against
    the spread of the data and not against its distance from the origin.
    ``about`` (k, d) are the means there.  A row's whitened difference to
    component j is ``y_j = (x - mu_j) P_j``, ``t`` holds the whitened
    means and ``const`` (k,) = log pi_j + log det P_j - (d/2) log 2 pi.
    ``prec`` is the Cholesky factor of the precisions as
    ``GaussianMixture`` keeps it: full (k, d, d) upper, tied (d, d), diag
    (k, d) and spherical (k,) the reciprocal standard deviations.

    Full covariances: the k factors' columns lie side by side e-major,
    column (e, j) being column e of P_j, the components in whole sublane
    tiles (k8: zero columns after the k-th), so that a product's (block,
    e k8) result IS the (block, e, k8) array of the differences as the
    chip lays both out, no copy between, and the sum of their squares
    over e adds whole vregs.  ``proj`` and ``t`` hold one right operand
    and one (e, k8) slice of the whitened means a group of
    :func:`em_groups`: the group's columns of the ``c`` rows it contracts.
    Where the product packs they are split and packed HERE
    (:func:`precision.short_right`: bfloat16 (96, 256), (192, 256), (384,
    288) at d = 50, k = 16) and the pass over the rows carries them:
    nothing of them is rebuilt in a block.  Only the upper triangle of
    ``prec`` is read.  Tied: ``proj`` is the one P; diag and spherical:
    None, P_j is ``scale``."""
    k, d = means.shape
    centre = px.pdot(jnp.exp(log_weights), means)
    about = means - centre
    proj, scale = None, None
    if cov_type == "full":
        upper = jnp.triu(prec)
        cols = _pad8(jnp.transpose(upper, (1, 2, 0)))           # (d, e, k8)
        whitened = _pad8(px.peinsum("jd,jde->ej", about, upper))
        groups = em_groups(d, cols.dtype)
        proj = tuple(cols[:c, e0:e1].reshape(min(c, d), -1)
                     for e0, e1, c in groups)
        if em_cuts(cov_type, cols.dtype):
            proj = tuple(px.short_right(right, px.SHORT_CHUNK)
                         for right in proj)
        t = tuple(whitened[e0:e1] for e0, e1, _ in groups)
        logdet = jnp.sum(jnp.log(jnp.diagonal(prec, axis1=1, axis2=2)), 1)
    elif cov_type == "tied":
        proj, t = prec, px.pdot(about, prec)
        logdet = jnp.sum(jnp.log(jnp.diagonal(prec)))
    elif cov_type == "diag":
        scale, t = prec, about * prec
        logdet = jnp.sum(jnp.log(prec), axis=1)
    else:
        scale, t = prec[:, None], about * prec[:, None]
        logdet = d * jnp.log(prec)
    return Whitener(centre, about, proj, t, scale,
                    log_weights + logdet - 0.5 * d * _LOG2PI)


def _pad8(a):
    """``a`` with zeros after its last axis up to a whole number of
    sublane tiles (8)."""
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, -a.shape[-1] % 8)])


def _em_log_prob(xb, wh: Whitener):
    """log(pi_j N(x | mu_j, Sigma_j)) of a block's rows ``xb`` (block,
    d): (block, k).  The whitened differences are taken as they are,
    squared and summed: no expanded |z|^2 - 2 z.t + |t|^2, whose terms
    cancel.  Full covariances: ONE product a group of ``wh.proj`` and the
    group's share of the sum over e, its differences (block, e, k8) with
    e in the middle; where the product packs, the block's rows are split
    and packed once (:func:`precision.short_left`) and every group reads
    the leading columns it contracts.  The other types: (block, k, d)
    differences, summed over d."""
    xc = xb - wh.centre
    if isinstance(wh.proj, tuple):
        packs = em_cuts("full", xc.dtype)
        left = px.short_left(xc, px.SHORT_CHUNK) if packs else xc
        sq = 0.0
        for right, t in zip(wh.proj, wh.t):
            z = px.pdot_packed(left, right) if packs \
                else px.pdot(left, right)
            y = z.reshape(xc.shape[0], *t.shape) - t[None]
            sq = sq + jnp.sum(y * y, axis=1)
        return wh.const[None, :] - 0.5 * sq[:, :wh.const.shape[0]]
    z = xc if wh.proj is None else px.pdot_short(xc, wh.proj)
    if wh.scale is not None:
        z = z[:, None, :] * wh.scale[None]
    y = z.reshape(xc.shape[0], -1, wh.t.shape[1]) - wh.t[None]
    return wh.const[None, :] - 0.5 * jnp.sum(y * y, axis=2)


def em_packs(d: int, dtype) -> bool:
    """Whether the M-step of full covariances packs its product
    (:func:`precision.pdot_tall`, d + 1 columns wide): what a trace can
    observe, and what decides how a block's wide operand is laid out and
    whether its second moments are cut along their symmetry
    (:func:`em_moment_groups`)."""
    return px.packs_tall(d + 1, dtype)


# Whole MXU tiles of the wide operand's columns (p, j) in one group of
# :func:`em_moment_groups`: two won over one at d = 50, k = 16 (PERF.md,
# PR 39: a product's cost has a floor that goes with its wide side).
_MOMENT_TILES = 2


def em_moment_groups(d: int, k: int, dtype) -> tuple:
    """``((p0, p1), ...)``: how the full-covariance M-step cuts the d
    values of p in the wide operand's columns (p, j) into groups, one
    product (:func:`precision.pdot_tall`) each.

    S_j[p, q] is symmetric, so the rows (p, j) of group ``[p0, p1)`` need
    the narrow operand's columns q >= p0 alone (and its column of ones).
    The product holds the wide operand in the MXU in tiles of
    ``_MXU_COLUMNS`` of its columns and streams the narrow one's past
    each: a group is ``_MOMENT_TILES`` whole tiles, ``128 / k`` values of p
    a tile (d = 50, k = 16: [0, 16), [16, 32), [32, 48), [48, 50), 213
    tile-columns for the whole product's 357).  Where the product is not
    packed there is one group of all d, and the moments are taken whole."""
    if not em_packs(d, dtype):
        return ((0, d),)
    span = max(_MOMENT_TILES * px._MXU_COLUMNS // k, 1)
    return tuple((p0, min(p0 + span, d)) for p0 in range(0, d, span))


def _em_zero_sums(k, d, cov_type, dtype):
    """Zeros shaped like the M-step's sums ``(nk, s, second)`` of
    :func:`_em_block_sums`."""
    if cov_type == "full" and em_packs(d, dtype):
        second = tuple(jnp.zeros(((p1 - p0) * k, d - p0 + 1), dtype)
                       for p0, p1 in em_moment_groups(d, k, dtype))
    else:
        second = jnp.zeros({"full": (k * (d + -d % 8), d + 1),
                            "tied": (d, d)}.get(cov_type, (k, d)), dtype)
    return (jnp.zeros((k,), dtype), jnp.zeros((k, d), dtype), second)


def _em_block_sums(xc, w, resp, about, cov_type):
    """One block's share of the M-step's sums ``(nk, s, second)``, taken
    about the fixed points ``about`` (k, d): with ``diff_j = x - a_j``,
    ``nk = sum r_j``, ``s = sum r_j diff_j`` and the second moments ``sum
    r_j diff_j diff_j^T`` (full; its s rides the same GEMM as one more
    column, so ``s`` stays zero until :func:`_em_sums_about`), their
    diagonals (diag, spherical) or, tied, the rows' own ``sum w x x^T``
    from which the sum over j follows.  ``resp`` (block, k) holds the
    weights already.  For full covariances the k weighted differences of
    a block lie side by side and a product against the block's rows
    contracts over the rows (:func:`precision.pdot_tall`).  Where that
    packs, the differences are (block, p, k), unpadded since k fills
    whole sublane tiles, one array a group of :func:`em_moment_groups`,
    each against the block's columns q >= p0 and the ones: ``second`` is
    a tuple of the groups' products.  ONE fusion writes the groups'
    arrays once: the barrier keeps the reshape below it, which XLA
    otherwise pulls up to the two broadcasts and then writes each out as
    a tile of its own (36 ms of a 289 ms iteration, PERF.md, PR 30).
    Elsewhere they are (block, k d8) as the six-pass product takes them,
    in ONE product of all d + 1 columns."""
    nk = jnp.sum(resp, axis=0)
    if cov_type == "full":
        x1 = jnp.concatenate([xc, jnp.ones_like(xc[:, :1])], axis=1)
        zero = jnp.zeros(about.shape, about.dtype)
        if em_packs(xc.shape[1], xc.dtype):
            groups = em_moment_groups(xc.shape[1], about.shape[0], xc.dtype)
            wd = lax.optimization_barrier(tuple(
                resp[:, None, :] * (xc[:, p0:p1, None] - about.T[None, p0:p1])
                for p0, p1 in groups))
            return nk, zero, tuple(
                px.pdot_tall(part.reshape(xc.shape[0], -1), x1[:, p0:])
                for part, (p0, _) in zip(wd, groups))
        # columns padded as in :func:`em_whitener`, for the same reason
        wd = resp[:, :, None] * (_pad8(xc)[:, None, :] - _pad8(about)[None])
        return nk, zero, px.pdot_tall(wd.reshape(xc.shape[0], -1), x1)
    diff = xc[:, None, :] - about[None]
    wd = resp[:, :, None] * diff
    s = jnp.sum(wd, axis=0)
    if cov_type == "tied":
        return nk, s, px.peinsum("bp,bq->pq", xc * w[:, None], xc)
    return nk, s, jnp.sum(wd * diff, axis=0)


def _em_sums_about(sums, about, cov_type):
    """``(nk, s, S)`` with every moment about ``about``, from what the
    pass accumulated (full: the GEMM's products against the centred rows
    and its column of ones; tied: the rows' own second moment).  Where
    the full product was cut along the symmetry (:func:`em_moment_groups`)
    a group's rows give S_j[p, q] for q >= p0 and s_j[p] for its p: the
    upper triangle is taken from them and mirrored, so S_j is exactly
    symmetric."""
    nk, s, second = sums
    k, d = about.shape
    if cov_type == "full":
        if isinstance(second, tuple):
            groups = em_moment_groups(d, k, about.dtype)
            rows = [jnp.swapaxes(part.reshape(p1 - p0, k, d - p0 + 1), 0, 1)
                    for part, (p0, p1) in zip(second, groups)]
            s = jnp.concatenate([r[:, :, -1] for r in rows], axis=1)
            g = jnp.concatenate([
                jnp.pad(r[:, :, :-1], ((0, 0), (0, 0), (p0, 0)))
                for r, (p0, _) in zip(rows, groups)], axis=1)
            upper = g - s[:, :, None] * about[:, None, :]
            return nk, s, jnp.where(jnp.triu(jnp.ones((d, d), bool)),
                                    upper, jnp.swapaxes(upper, 1, 2))
        g = second.reshape(k, -1, d + 1)[:, :d]
        s = g[:, :, d]
        return nk, s, g[:, :, :d] - s[:, :, None] * about[:, None, :]
    if cov_type == "tied":
        sa = px.peinsum("jp,jq->pq", s, about)
        second = second - sa - sa.T \
            - px.peinsum("jp,jq->pq", about * nk[:, None], about)
    return nk, s, second


def em_step(xp, m, log_weights, means, prec, cov_type):
    """``(nk, s, S, loglik)`` of one EM step in ONE blocked pass over the
    padded rows ``xp`` (rows, >= d), rows sharded over the mesh's
    ``rows`` axis, of which the first ``m`` count: the E-step at the
    parameters given (``prec`` as in :func:`em_whitener`) and the M-step's
    sums about the means given, ``a_j``: ``nk`` (k,) the summed
    responsibilities, ``s`` (k, d) ``= sum r_j (x - a_j)``, ``S`` the
    second moments ``sum r_j (x - a_j)(x - a_j)^T``: (k, d, d) for full
    covariances, their sum over j (d, d) for tied, their diagonals (k, d)
    for diag and spherical; ``loglik`` the sum over the rows of log sum_j
    pi_j N(x | mu_j, Sigma_j).  The new means are ``a_j + s_j / nk_j`` and
    the new covariances ``S_j / nk_j - (s_j / nk_j)(s_j / nk_j)^T``: what
    is taken away is second order in how far a mean moved, where raw
    moments (a_j = 0) would cancel against the means' own size.

    No array of rows x k x d elements exists, and none of rows x k
    outlives its block: each device runs a loop over blocks of
    :func:`em_block` rows and keeps compensated running sums, and one
    packed ``psum`` over ``rows`` ends the step."""
    k, d = means.shape
    wh = em_whitener(log_weights, means, prec, cov_type)

    def block(xb, w, _start):
        with jax.named_scope("dslib.gm.e_step"):
            logp = _em_log_prob(xb, wh)
            lse = jax.scipy.special.logsumexp(logp, axis=1)
            resp = jnp.exp(logp - lse[:, None]) * w[:, None]
        with jax.named_scope("dslib.gm.m_step"):
            sums = _em_block_sums(xb - wh.centre, w, resp, wh.about,
                                  cov_type)
        return sums, jnp.sum(lse * w)

    # the walk itself (a block's cut, its row mask, the compensated running
    # sums: 0.6% of the cell's device time) lies under this scope alone
    with jax.named_scope("dslib.gm.pass"):
        sums, loglik = blocked_row_sums(
            xp, m, d, _em_row_bytes(k, d), block,
            (_em_zero_sums(k, d, cov_type, xp.dtype),
             jnp.zeros((), xp.dtype)))
    return (*_em_sums_about(sums, wh.about, cov_type), loglik)


def em_start(xp, m, about, cov_type, labels=None, key=None):
    """``(nk, s, S)`` as :func:`em_step` returns them, about the points
    ``about`` (k, d), of responsibilities that are given and not
    computed: the one-hot rows of ``labels`` (rows, 1), or with ``key``
    a seeded uniform draw normalised over the components, made block by
    block (the key folded with the block's first row) so that no (rows,
    k) array exists.  The start of a fit: the same blocked pass, without
    an E-step."""
    k, d = about.shape
    centre = jnp.mean(about, axis=0)
    about_c = about - centre

    def block(xb, w, start, *lab):
        if lab:
            resp = jax.nn.one_hot(lab[0][:, 0], k, dtype=xb.dtype)
        else:
            resp = jax.random.uniform(jax.random.fold_in(key, start),
                                      (xb.shape[0], k), xb.dtype)
            resp = resp / jnp.sum(resp, axis=1, keepdims=True)
        return _em_block_sums(xb - centre, w, resp * w[:, None], about_c,
                              cov_type)

    sums = blocked_row_sums(
        xp, m, d, _em_row_bytes(k, d), block,
        _em_zero_sums(k, d, cov_type, xp.dtype),
        per_row=() if labels is None else (labels,))
    return _em_sums_about(sums, about_c, cov_type)


def em_loglik(xp, m, log_weights, means, prec, cov_type):
    """The sum over the first ``m`` rows of log sum_j pi_j N(x | mu_j,
    Sigma_j): :func:`em_step`'s E-step alone, in the same blocks."""
    k, d = means.shape
    wh = em_whitener(log_weights, means, prec, cov_type)

    def block(xb, w, _start):
        logp = _em_log_prob(xb, wh)
        return jnp.sum(jax.scipy.special.logsumexp(logp, axis=1) * w)

    return blocked_row_sums(xp, m, d, _em_row_bytes(k, d), block,
                            jnp.zeros((), xp.dtype))


def em_labels(xp, m, log_weights, means, prec, cov_type):
    """The most probable component of every row, (rows, 1) int32 with 0
    on the padding rows: the E-step's log-probabilities block by block,
    each block's labels written where its rows lie (the rows of a ragged
    last block that an earlier block has written get the same labels
    again)."""
    k, d = means.shape
    wh = em_whitener(log_weights, means, prec, cov_type)
    mesh = _mesh.get_mesh()
    local = xp.shape[0] // mesh.shape[_mesh.ROWS]
    block = em_block(local, d, k)

    def device(xs):
        first = lax.axis_index(_mesh.ROWS) * local

        def one(i, out):
            start, (xb,) = _em_cut(i, block, local, xs)
            lab = jnp.argmax(_em_log_prob(xb[:, :d], wh), axis=1)
            rows = first + start + lax.iota(jnp.int32, block)
            lab = jnp.where(rows < m, lab, 0).astype(jnp.int32)
            return lax.dynamic_update_slice_in_dim(out, lab, start, 0)

        return lax.fori_loop(
            0, -(-local // block), one,
            lax.pcast(jnp.zeros((local,), jnp.int32), _mesh.ROWS,
                      to="varying"))[:, None]

    return jax.shard_map(device, mesh=mesh, in_specs=(P(_mesh.ROWS, None),),
                         out_specs=P(_mesh.ROWS, None))(xp)


# -- the blocked pass over the rows, for every tenant -------------------------
# (Below everything else on purpose: the KMeans fit's program keeps its
# compile-cache key only while the lines above stay where they are.)
# The EM step was its first tenant and the tile budget keeps its name; the
# tall orthonormalisation and the randomized SVD's products over the rows
# (decomposition/tsqr.py, randomsvd.py) are the second.
_CONTRACT_ROWS = 8192        # most rows one product contracts, at the budget


def row_block(rows: int, row_bytes: int) -> int:
    """Rows of one block of a blocked pass over ``rows`` rows on a device,
    ``row_bytes`` being what a row costs in a block's largest temporary:
    all of them where they fit the tile budget ``_EM_TILE_BYTES``; else the
    largest whole number of quanta under the budget that divides ``rows``,
    if one of at least half the budget does; else the budget's, and the
    last block is ragged (it then starts early and its rows that an
    earlier block has seen weigh nothing)."""
    most = max(_EM_TILE_BYTES // row_bytes // _EM_ROW_QUANTUM,
               1) * _EM_ROW_QUANTUM
    if rows <= most:
        return rows
    whole = [b for b in range(most, most // 2 - 1, -_EM_ROW_QUANTUM)
             if rows % b == 0]
    return whole[0] if whole else most


def contract_row_bytes(row_bytes: int) -> int:
    """``row_bytes`` for a pass whose body is a product that contracts a
    block's rows: at least what keeps a block to ``_CONTRACT_ROWS`` rows
    under the budget.  A float32 product that contracts more than about
    10^4 rows of same-signed terms reads low on the chip (1.3e-5 at
    100 000 rows, 5e-9 at 7 680; PERF.md, PR 29), and a Gram's diagonal
    is a sum of squares whatever the data."""
    return max(row_bytes, _EM_TILE_BYTES // _CONTRACT_ROWS)


def varying_like(z, xs):
    """``z`` typed as varying over the mesh axes ``xs`` varies over: what
    a loop's carry that starts from a constant and is updated from a
    shard's rows has to be inside a ``shard_map`` that checks it; ``z``
    itself on no mesh."""
    axes = tuple(getattr(jax.typeof(xs), "vma", ()))
    return lax.pcast(z, axes, to="varying") if axes else z


def local_row_sums(xs, others, first, m, block, body, zero):
    """One device's share of :func:`blocked_row_sums`, for a caller that
    is inside a ``shard_map`` already (or on no mesh at all): the
    compensated sum over the blocks of ``block`` rows of ``body(xb, w,
    start, *other_blocks)`` over this device's rows ``xs`` and the
    ``others`` cut the same way, ``first`` being the device's first row
    in the whole array, of which the first ``m`` rows count.

    The running totals are compensated (Kahan): what an addition rounds
    away is carried and given back, so that thousands of blocks add up as
    exactly as two, whatever the block's size.  Plain float32 totals over
    the 3125 blocks of 24M rows read 2e-6 of a lower bound and 1e-5 of a
    covariance against the reference (PERF.md, PR 29)."""
    local = xs.shape[0]

    def one(i, carry):
        total, lost = carry
        start, (xb, *cut) = _em_cut(i, block, local, xs, *others)
        rows = start + lax.iota(jnp.int32, block)
        w = ((rows >= i * block) & (first + rows < m)).astype(xs.dtype)
        # leaf by leaf, each in the shape its block's GEMM writes it
        part = jax.tree.map(jnp.subtract,
                            body(xb, w, first + start, *cut), lost)
        grown = jax.tree.map(jnp.add, total, part)
        return grown, jax.tree.map(lambda g, t, p: (g - t) - p,
                                   grown, total, part)

    start = jax.tree.map(lambda z: varying_like(z, xs), zero)
    total, lost = lax.fori_loop(0, -(-local // block), one, (start, start))
    return jax.tree.map(jnp.subtract, total, lost)
