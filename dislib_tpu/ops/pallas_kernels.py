"""Pallas kernels for the overlap schedules' hot inner loops.

``DSLIB_OVERLAP=pallas`` routes the two FLOP-dominant inner computations
of the panel pipelines — SUMMA's per-panel GEMM and the ring ε-pass's
``distances_sq`` — through explicit Pallas kernels instead of plain HLO,
and the forest fit's level histogram through a one-hot GEMM.  A Pallas
call is an opaque compute region the latency-hiding scheduler treats as
one unit, so the pipelined loop's independent collective can slide past
it.

Contract (mirrors ``ops/precision``): operands are rounded to the
policy's compute dtype, contractions accumulate in the policy's
accumulation dtype, outputs match what the plain-HLO path produces — the
Pallas route changes the SCHEDULE, not the numerics contract (values are
allclose-tested, not bit-tested: a different GEMM tiling reassociates
sums).

Every kernel tiles ALL of its operands into aligned VMEM blocks (sublane
multiples of the dtype's packing, lane multiples of 128) and grids the
contraction, zero-padding ragged dims in the wrapper and cropping the
result, so the block shapes do not depend on the caller's shard sizes.
Outputs carry the operands' varying-mesh-axes (``vma``), so the kernels
run inside the callers' ``shard_map(check_vma=True)``.  Two idioms keep
the INTERPRETER green under that check (Mosaic does not re-check kernel
bodies): each output tile is seeded from an aliased zero operand rather
than zeroed in the kernel, because the interpreter would otherwise start
the output as a mesh-invariant value; and every kernel body computes
inside a ``pl.when`` branch (:func:`_accumulate`), because the
interpreter type-checks top-level kernel ops against the mesh — where a
constant or a value leaving a branch counts as mesh-invariant and may
not meet a varying block — but evaluates a branch whole.  On a TPU the
kernels are compiled by Mosaic and a kernel Mosaic refuses is an error at
the call site (the kernel's ``name`` is in the message) — there is no
probe and no fallback schedule.  Everywhere else they run in Pallas
interpret mode, semantically identical, which keeps the router testable
on the CPU rig.

``kmeans_step`` is the one kernel here that no schedule option selects:
it is the dense KMeans fit's pass over the rows wherever its shape test
holds (``ops/base.py::lloyd_step``), because it halves the traffic of a
memory-bound step.  It alone does not pad or crop its large operand (any
op in front of a custom call that is not a bitcast copies all of X: it
reads X in the layout the chip holds and handles the ragged block
inside) and does not carry ``vma`` (``lloyd_step`` says why).

Kernels keep the library's precision-lint contract: no hardcoded compute
dtypes — every cast routes through ``ops/precision`` or derives from a
value's own dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dislib_tpu.ops import precision as px

# VMEM block edges: rows/cols of an output tile and the contraction step.
# 256x256 out + two 256x512 operand tiles, double-buffered, stay a few MiB
# — far inside the v5e's 16 MiB scoped-VMEM default at every dtype.
_BM = 256
_BN = 256
_BK = 512
_LANES = 128


def _interpret() -> bool:
    """Pallas interpret mode everywhere but real TPUs — same semantics,
    no Mosaic lowering requirement (the CPU-rig test path)."""
    return jax.default_backend() != "tpu"


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


def _sublanes(dtype) -> int:
    """Rows per packed vreg tile: 8 for 4-byte, 16 for 2-byte, 32 for
    1-byte dtypes (8-byte x64 dtypes only ever run interpreted)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _block(dim: int, target: int, quantum: int) -> int:
    """Block edge for a dim: the target, or the whole (quantum-rounded)
    dim when it is smaller.  The wrapper pads the dim to a multiple."""
    return min(target, _round_up(dim, quantum))


def _pad2(x, rows: int, cols: int):
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    return x if not (pr or pc) else jnp.pad(x, ((0, pr), (0, pc)))


def _vary_alike(*operands):
    """The operands, each cast to vary over the union of all their
    varying mesh axes (a no-op outside ``shard_map``): the kernel body
    mixes them, and under ``check_vma=True`` mixed values must agree —
    SUMMA hands a rows-varying A panel and a cols-varying B panel."""
    union = frozenset().union(*(jax.typeof(o).vma for o in operands))
    return tuple(
        o if jax.typeof(o).vma == union else
        lax.pcast(o, tuple(sorted(union - jax.typeof(o).vma)), to="varying")
        for o in operands)


def _accumulator(shape, dtype, like):
    """(zeros, out_shape) for an accumulating kernel: the output aliases
    the zero operand, so it starts out varying over the same mesh axes as
    ``like`` (none outside ``shard_map``) on the interpreter too, where
    a fresh output buffer would be mesh-invariant and fail
    ``check_vma=True`` at the first accumulate."""
    zeros, _ = _vary_alike(jnp.zeros(shape, dtype), like)
    return zeros, jax.ShapeDtypeStruct(shape, dtype,
                                       vma=jax.typeof(like).vma)


def _accumulate(o_ref, z_ref, t, term):
    """``o += term()`` over the contraction grid axis ``t``, seeding the
    resident output tile from the aliased zero tile on the first step.
    ``term`` is a thunk so the whole computation is traced INSIDE each
    ``pl.when`` arm (see the module docstring for why); only one arm
    runs per grid step."""
    @pl.when(t == 0)
    def _first():
        o_ref[...] = z_ref[...] + term()

    @pl.when(t != 0)
    def _rest():
        o_ref[...] += term()


def panel_gemm(a, b, policy=px.FLOAT32):
    """``A @ B`` as a (row, col, contraction)-tiled Pallas kernel — the
    SUMMA panel GEMM.

    Same numerics contract as :func:`ops.precision.pdot`: operands round
    to the policy compute dtype, the contraction accumulates in the
    policy accumulation dtype (promoted for x64-mode f64 operands under
    the float32-floor policy), output is the accumulation dtype."""
    a, b = _vary_alike(px.to_compute(a, policy), px.to_compute(b, policy))
    acc_dt = jnp.promote_types(px.accum_dtype(policy),
                               jnp.promote_types(a.dtype, b.dtype))
    m, k = a.shape
    _, n = b.shape
    bm = _block(m, _BM, max(_sublanes(a.dtype), _sublanes(acc_dt)))
    bn = _block(n, _BN, _LANES)
    bk = _block(k, _BK, _LANES)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)

    def kern(z_ref, a_ref, b_ref, o_ref):
        # the output tile stays resident across the contraction axis
        # (its index map ignores it).  pdot's MXU-precision guarantee
        # must survive the Pallas route — without the explicit precision
        # a f32 FLOAT32-policy call outside a `precise` scope would run
        # the backend default
        _accumulate(o_ref, z_ref, pl.program_id(2),
                    lambda: jnp.dot(a_ref[...], b_ref[...],
                                    preferred_element_type=acc_dt,
                                    precision=policy.dot_precision))

    zeros, out_shape = _accumulator((mp, np_), acc_dt, a)
    out = pl.pallas_call(
        kern,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
                  pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
                  pl.BlockSpec((bk, bn), lambda i, j, t: (t, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=out_shape,
        input_output_aliases={0: 0},
        interpret=_interpret(),
        name="dslib_panel_gemm",
    )(zeros, _pad2(a, mp, kp), _pad2(b, kp, np_))
    return out[:m, :n]


def distances_sq(a, b, precision=None):
    """Pairwise squared euclidean distances as a tiled Pallas kernel —
    the ring/tiled ε-pass inner loop (``ops/base.distances_sq``'s
    ‖a‖² − 2a·bᵀ + ‖b‖² formulation, clamped at zero against
    cancellation).  The cross GEMM and the combine run in the kernel;
    the row norms are one cheap elementwise pass outside it.  Output
    dtype matches the plain-HLO path (the operands' promoted float
    dtype); ``precision`` threads to the cross GEMM exactly as the plain
    path threads it to ``jnp.matmul`` (``None`` inherits the enclosing
    scope at trace time, as there)."""
    out_dt = jnp.promote_types(a.dtype, b.dtype)
    a, b = _vary_alike(a.astype(out_dt), b.astype(out_dt))
    m, d = a.shape
    kf, _ = b.shape
    bm = _block(m, _BM, _sublanes(out_dt))
    bn = _block(kf, _BN, _LANES)
    bk = _block(d, _BK, _LANES)
    mp, np_, kp = _round_up(m, bm), _round_up(kf, bn), _round_up(d, bk)
    a_sq = jnp.sum(a * a, axis=1, keepdims=True)          # (m, 1)
    b_sq = jnp.sum(b * b, axis=1)[None, :]                # (1, kf)
    last = kp // bk - 1

    def kern(z_ref, a_ref, b_ref, asq_ref, bsq_ref, o_ref):
        t = pl.program_id(2)
        # a·bᵀ: contract the feature (lane) dim of both tiles
        _accumulate(o_ref, z_ref, t, lambda: lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=out_dt, precision=precision))

        @pl.when(t == last)
        def _combine():
            o_ref[...] = jnp.maximum(
                asq_ref[...] - 2.0 * o_ref[...] + bsq_ref[...],
                jnp.zeros((), out_dt))

    zeros, out_shape = _accumulator((mp, np_), out_dt, a)
    out = pl.pallas_call(
        kern,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
                  pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
                  pl.BlockSpec((bn, bk), lambda i, j, t: (j, t)),
                  pl.BlockSpec((bm, 1), lambda i, j, t: (i, 0)),
                  pl.BlockSpec((1, bn), lambda i, j, t: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=out_shape,
        input_output_aliases={0: 0},
        interpret=_interpret(),
        name="dslib_distances_sq",
    )(zeros, _pad2(a, mp, kp), _pad2(b, np_, kp),
      _pad2(a_sq, mp, 1), _pad2(b_sq, 1, np_))
    return out[:m, :kf]


def node_histogram(node, bx, contrib, n_nodes, n_bins, policy=px.FLOAT32):
    """The tree level's (node, feature, bin) weighted histogram as a
    tiled Pallas kernel — the forest fit's scatter-shaped hot loop
    (``trees/decision_tree._node_histogram``) re-expressed as an MXU
    contraction: per feature, the (node, bin) scatter index one-hot
    encodes against a block of histogram columns, and the per-sample
    stats contracted with that one-hot over the samples IS the
    histogram.  Samples sit on the lane axis throughout (index rows
    ``(1, bm)``, stats ``(S, bm)``), so no block is narrower than a
    lane tile; the grid is (feature, column block, row tile) with the
    output block resident across row tiles (zero-init at tile 0).

    ``node`` (m,) int32, ``bx`` (m, n) int32 bin ids, ``contrib``
    (m, S) per-sample weighted stats (w·stats — computed by the caller
    so the kernel stays a pure contraction).  Returns (n_nodes, n,
    n_bins, S) at the policy accumulation dtype promoted with the
    contribution dtype — the plain path's f32, f64 for x64-mode f64
    stats.  With integer-representable contributions (Poisson-weight ×
    count stats — the forest's actual regime) the sums are exact, so
    this route is BIT-equal to the XLA scatter, not merely allclose."""
    node, bx, contrib = _vary_alike(node, bx,
                                    px.to_compute(contrib, policy))
    acc_dt = jnp.promote_types(px.accum_dtype(policy), contrib.dtype)
    m, n = bx.shape
    s = contrib.shape[1]
    nb = int(n_nodes) * int(n_bins)
    sp = _round_up(s, max(_sublanes(contrib.dtype), _sublanes(acc_dt)))
    bm = _block(m, _BK, _LANES)          # samples: the contraction axis
    bc = _block(nb, _BN, _LANES)         # histogram columns
    mp, nbp = _round_up(m, bm), _round_up(nb, bc)

    def kern(z_ref, n_ref, b_ref, c_ref, o_ref):
        j = pl.program_id(1)

        def partial_hist():
            idx = n_ref[...] * n_bins + b_ref[...]          # (1, bm)
            col = lax.broadcasted_iota(jnp.int32, (bc, bm), 0) + j * bc
            c = c_ref[...]
            onehot_t = jnp.where(col == idx, jnp.ones((), c.dtype),
                                 jnp.zeros((), c.dtype))    # (bc, bm)
            # (sp, bm) · (bc, bm)ᵀ: contract the sample (lane) dim of both
            return lax.dot_general(
                c, onehot_t, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dt,
                precision=policy.dot_precision)

        _accumulate(o_ref, z_ref, pl.program_id(2), partial_hist)

    # pad samples carry zero stats, so whatever column they index adds 0
    node_r = _pad2(node[None, :], 1, mp)                    # (1, mp)
    bx_r = jnp.pad(bx.T, ((0, 0), (0, mp - m)))[:, None, :]  # (n, 1, mp)
    c_t = _pad2(contrib.T, sp, mp)                          # (sp, mp)
    zeros, out_shape = _accumulator((n, sp, nbp), acc_dt, contrib)
    out = pl.pallas_call(
        kern,
        grid=(n, nbp // bc, mp // bm),
        in_specs=[pl.BlockSpec((None, sp, bc), lambda f, j, i: (f, 0, j)),
                  pl.BlockSpec((1, bm), lambda f, j, i: (0, i)),
                  pl.BlockSpec((None, 1, bm), lambda f, j, i: (f, 0, i)),
                  pl.BlockSpec((sp, bm), lambda f, j, i: (0, i))],
        out_specs=pl.BlockSpec((None, sp, bc), lambda f, j, i: (f, 0, j)),
        out_shape=out_shape,
        input_output_aliases={0: 0},
        interpret=_interpret(),
        name="dslib_node_histogram",
    )(zeros, node_r, bx_r, c_t)
    # (n, S, n_nodes·n_bins) → the scatter path's (n_nodes, n, n_bins, S)
    return out[:, :s, :nb].reshape(n, s, n_nodes, n_bins) \
        .transpose(2, 0, 3, 1)


# -- the fused Lloyd step ----------------------------------------------------

_KM_VMEM_LIMIT = 100 * 2 ** 20       # of the v5e's 128 MiB


def kmeans_step(x, x_sq, w, centers, block, chunk):
    """One Lloyd step's reductions in ONE pass over the rows of ``x``
    (rows, d): per cluster the weighted sum of its rows ``(k, d)`` and
    their weight ``(k,)``, and the weighted sum of every row's squared
    distance to its nearest centre, as ``(sums, counts, inertia)``.
    ``x_sq`` (1, rows) are the rows' squared norms and ``w`` (1, rows)
    the row weights, 1 or 0 (0 on padding: the weighted one-hot has to
    be exact in bfloat16), each a row beside X's rows on the
    lanes (loop-invariant: the caller makes them once, a relayout of
    4 bytes a row that inside an iteration loop would run every time),
    ``centers`` (k, d); ``block`` rows a grid step, a whole number of
    ``chunk`` rows, itself a multiple of the lane width
    (``ops/base.py::lloyd_step`` derives both from the shapes).

    The kernel reads ``x`` features-major, ``(d, rows)``: that is how
    the TPU holds a tall array whose ``d`` is no multiple of the lane
    width (its compact layout), so the transpose is a bitcast and no
    copy of X is made.  The grid walks blocks of rows, X's one read
    from HBM, double-buffered; a rolled loop walks the block's chunks in
    VMEM.  Everything of a chunk has its rows along the lanes: the
    distances ``(k, chunk)``, whose argmin runs over sublanes, the cross
    term ``c . xt_chunk`` and the sums ``onehot . xt_chunk^T``.

    Both products are the float32 policy's 'highest' contraction, its six
    bfloat16 passes spelled out over ``precision.highest_parts`` so that
    ONE split of the chunk serves both: the cross term in three matmuls
    of the centres' stacked parts, one per part of the chunk, summed
    smallest first; the one-hot is 0 or 1, whose lower parts are zero, so
    its three passes over the chunk's parts are all six.  Distances are
    the expansion the two-pass step uses, clamped at zero; ties go to
    the first minimum as ``jnp.argmin``.  A block's partials leave as
    they are and are summed outside, so no float32 sum runs serially over
    more than a block.  A ragged last block reads rows past the end:
    their ``w`` and ``x_sq`` are padded with zeros here and their ``x``
    is zeroed in VMEM, on that block alone."""
    rows, d = x.shape
    k = centers.shape[0]
    dt = x.dtype
    kp = _round_up(k, _sublanes(px.BFLOAT16.compute))
    nb, nc = -(-rows // block), block // chunk
    tail = rows % block
    lane_tiles = chunk // _LANES
    rows_on_lanes = (((1,), (1,)), ((), ()))     # contract the lanes of both

    def one_pass(a, b, dims=(((1,), (0,)), ((), ()))):
        return lax.dot_general(a, b, dims, precision=px.ONE_PASS,
                               preferred_element_type=dt)

    def lane_sums(v):
        """(r, chunk) -> (r, 128): whole-vreg adds, no cross-lane op."""
        return jnp.sum(v.reshape(v.shape[0], lane_tiles, _LANES), axis=1)

    def kern(xt_ref, xsq_ref, w_ref, c_ref, sums_ref, cnt_ref, in_ref):
        if tail:
            @pl.when(pl.program_id(0) == nb - 1)
            def _ragged():
                # rows past the end hold whatever the DMA left: zero
                # them, 0 * NaN would reach every sum through the MXU
                a = tail // _LANES * _LANES
                if a != tail:
                    keep = lax.broadcasted_iota(jnp.int32, (d, _LANES), 1) \
                        < tail - a
                    xt_ref[:, a:a + _LANES] = jnp.where(
                        keep, xt_ref[:, a:a + _LANES], jnp.zeros((), dt))
                    a += _LANES
                if a < block:
                    xt_ref[:, a:] = jnp.zeros((d, block - a), dt)

        c = c_ref[...]
        below = lax.broadcasted_iota(jnp.int32, (kp, 1), 0) < k
        # a padded centre is infinitely far: it never wins the argmin
        c_sq = jnp.where(below, jnp.sum(c * c, axis=1, keepdims=True),
                         jnp.full((), jnp.inf, dt))
        which = lax.broadcasted_iota(jnp.int32, (kp, chunk), 0)
        # the centres' parts stacked hi, mid, lo: a part of the chunk
        # meets every part it owes a product in one matmul
        c3 = jnp.concatenate(px.highest_parts(c), axis=0)

        def body(j, carry):
            sums, cnt, inertia = carry
            at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
            xc = xt_ref[:, at]                                   # (d, chunk)
            hi, mid, lo = px.highest_parts(xc)
            p_hi, p_mid, p_lo = one_pass(c3, hi), \
                one_pass(c3[:2 * kp], mid), one_pass(c3[:kp], lo)
            # c_lo.x_hi + c_mid.x_mid + c_hi.x_lo, then the two middle
            # products, then c_hi.x_hi: smallest first
            cross = ((p_hi[2 * kp:] + p_mid[kp:] + p_lo)
                     + (p_hi[kp:2 * kp] + p_mid[:kp])) + p_hi[:kp]
            dist = jnp.maximum(xsq_ref[:, at] - 2.0 * cross + c_sq, 0.0)
            nearest = jnp.min(dist, axis=0, keepdims=True)
            label = jnp.min(jnp.where(dist == nearest, which, kp),
                            axis=0, keepdims=True)
            wj = w_ref[:, at]
            onehot = jnp.where(which == label, wj, jnp.zeros((), dt))
            oh = px.to_compute(onehot, px.BFLOAT16)
            sums += (one_pass(oh, lo, rows_on_lanes)
                     + one_pass(oh, mid, rows_on_lanes)) \
                + one_pass(oh, hi, rows_on_lanes)
            return (sums, cnt + lane_sums(onehot),
                    inertia + lane_sums(nearest * wj))

        sums_ref[0], cnt_ref[0], in_ref[0] = lax.fori_loop(
            0, nc, body, (jnp.zeros((kp, d), dt), jnp.zeros((kp, _LANES), dt),
                          jnp.zeros((1, _LANES), dt)))

    def lanes(v):
        # to whole blocks; nothing where the blocks divide the rows
        return _pad2(v, 1, nb * block)

    side = pl.BlockSpec((1, block), lambda i: (0, i))
    part = lambda *shape: pl.BlockSpec((1,) + shape, lambda i: (i, 0, 0))
    sums, cnt, inertia = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[pl.BlockSpec((d, block), lambda i: (0, i)), side, side,
                  pl.BlockSpec((kp, d), lambda i: (0, 0))],
        out_specs=[part(kp, d), part(kp, _LANES), part(1, _LANES)],
        out_shape=[jax.ShapeDtypeStruct((nb, kp, d), dt),
                   jax.ShapeDtypeStruct((nb, kp, _LANES), dt),
                   jax.ShapeDtypeStruct((nb, 1, _LANES), dt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_KM_VMEM_LIMIT),
        interpret=_interpret(),
        name="dslib_kmeans_step",
    )(x.T, lanes(x_sq), lanes(w), _pad2(centers, kp, d))
    return (jnp.sum(sums, axis=0)[:k], jnp.sum(cnt, axis=(0, 2))[:k],
            jnp.sum(inertia))


# -- many small SPD systems at once: Cholesky and solve, a system a lane ----
# (Appended at the end: the KMeans fit's program keeps its compile-cache key
# only while the lines above stay where they are.)
_CHOL_VMEM_LIMIT = 48 * 2 ** 20


def chol_solve_lanes(a, b):
    """``x`` (f, n) with ``A_t x_t = b_t`` for n symmetric positive
    definite systems laid a system a lane: ``a`` (f, f, n), ``b`` (f, n).

    XLA's batched Cholesky walks its columns in a loop whose every step
    passes over the whole batch in HBM (13 µs a 100 x 100 system on the
    v5e; PERF.md, section 6).  Here a block of 128 systems stays in VMEM for
    the whole factorisation: step j reads column j, scales it by the root
    of its diagonal, keeps it as L's column j in row j's place and takes
    its outer product from the rows below; then one forward and one
    backward substitution over the columns.  Every operation is
    elementwise or a sum over sublanes, in float32 on the vector unit:
    the MXU's passes play no part.  n is padded to whole blocks with
    identity systems."""
    f, _, n = a.shape
    nb = -(-n // _LANES)
    pad = nb * _LANES - n
    if pad:
        eye = jnp.broadcast_to(jnp.eye(f, dtype=a.dtype)[:, :, None],
                               (f, f, pad))
        a = jnp.concatenate([a, eye], axis=2)
        b = jnp.pad(b, ((0, 0), (0, pad)))
    a, b = _vary_alike(a, b)

    def kern(a_ref, b_ref, x_ref):
        @pl.when(pl.program_id(0) >= 0)     # see the module docstring
        def _():
            row = lax.broadcasted_iota(jnp.int32, (f, _LANES), 0)
            lead = lax.broadcasted_iota(jnp.int32, (f, 1, 1), 0)

            def at(v, j):                   # v[j] as (1, lanes)
                return jnp.sum(jnp.where(row == j, v, 0.0), axis=0,
                               keepdims=True)

            def factor(j, carry):
                col = a_ref[j]
                lj = jnp.where(row >= j, col / jnp.sqrt(at(col, j)), 0.0)
                a_ref[...] = jnp.where(lead > j, a_ref[...]
                                       - lj[:, None, :] * lj[None, :, :],
                                       a_ref[...])
                a_ref[j] = lj
                return carry

            lax.fori_loop(0, f, factor, 0)

            # both substitutions in place in x_ref: y, then x bottom up
            x_ref[...] = b_ref[...]

            def forward(j, carry):
                lj, y = a_ref[j], x_ref[...]
                yj = at(y, j) / at(lj, j)
                x_ref[...] = jnp.where(row > j, y - lj * yj,
                                       jnp.where(row == j, yj, y))
                return carry

            lax.fori_loop(0, f, forward, 0)

            def backward(k, carry):
                j = f - 1 - k
                lj, x = a_ref[j], x_ref[...]
                done = jnp.sum(jnp.where(row > j, lj * x, 0.0), axis=0,
                               keepdims=True)
                xj = (at(x, j) - done) / at(lj, j)
                x_ref[...] = jnp.where(row == j, xj, x)
                return carry

            lax.fori_loop(0, f, backward, 0)

    x = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[pl.BlockSpec((f, f, _LANES), lambda i: (0, 0, i)),
                  pl.BlockSpec((f, _LANES), lambda i: (0, i))],
        out_specs=pl.BlockSpec((f, _LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((f, nb * _LANES), a.dtype,
                                       vma=jax.typeof(a).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_CHOL_VMEM_LIMIT),
        interpret=_interpret(),
        name="dslib_chol_solve",
    )(a, b)
    return x[:, :n]
