"""Nearest neighbors (reference: `dislib/neighbors` — per (query-block ×
fitted-block) local kNN tasks, pairwise merge keeping the global k-best;
SURVEY.md §3.3 "all-pairs block product then min-merge").

TPU-native: the all-pairs block product is a distance GEMM on the sharded
operands (‖q‖² − 2qᵀx + ‖x‖²) and the k-best merge is `lax.top_k`.  Small
fit sets take the direct path (one (mq, mf) distance matrix).  Large fit
sets stream in fitted-row chunks with a running top-k merge — top_k over
[current best ∥ chunk distances] per step — so peak memory is
O(mq·(k + chunk)), never O(mq·mf); this is the reference's own pairwise
merge tree, collapsed to a `lax.scan`.  Padded fit rows are masked to +inf
so they can never be neighbors.

Sparse inputs (SURVEY §8 hard part 2) are NATIVE — no densification of the
whole matrix ever happens: a sparse fit set streams as skew-bounded
row-step triplet buffers (`SparseArray.row_steps`: steps capped by both a
row count and an nnz budget) scatter-added into a bounded (chunk, n) dense
window on device, a sparse query contributes its cross-term as one spmm
per step, and ‖·‖² terms come from segment-sums over the nonzeros — the
same economics as the sparse KMeans path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from dislib_tpu.base import BaseEstimator
from dislib_tpu.data.array import Array, _repad
from dislib_tpu.ops import overlap as _ov
from dislib_tpu.ops.base import distances_sq, precise
from dislib_tpu.ops.ring import ring_auto, ring_kneighbors
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.utils import profiling as _prof


class NearestNeighbors(BaseEstimator):
    """Exact brute-force kNN index over a ds-array.

    ``ring`` selects the multi-device schedule for DENSE fit sets: True
    rotates fitted shards around the mesh 'rows' axis via ppermute with a
    running top-k (the fitted set never materialises on one chip —
    `ops/ring.py`); False forces the single-program path (direct or
    fitted-row-chunked GEMM); None (default) auto-picks ring when the mesh
    has >1 row shard and the fit set is large enough for scale-out to
    matter.  Sparse inputs ignore ``ring``: they always stream the fit
    rows as bounded dense windows, query-row-sharded by hand (`shard_map`)
    on a multi-row mesh, single-program otherwise — ring's
    shard-the-FIT-set trade-off does not apply to a streamed fit set."""

    _private_fitted_attrs = ("_fit_data",)

    def __init__(self, n_neighbors=5, ring=None):
        self.n_neighbors = n_neighbors
        self.ring = ring

    def fit(self, x: Array, y=None):
        self._fit_data = x
        return self

    def kneighbors(self, x: Array, n_neighbors=None, return_distance=True):
        """Distances/indices of the k nearest fitted rows for each query row.

        Returns (distances (mq, k) Array, indices (mq, k) int32 Array) — the
        ds-array being the library's single container (reference returns
        ds-arrays too)."""
        if not hasattr(self, "_fit_data"):
            raise RuntimeError("NearestNeighbors is not fitted")
        k = self.n_neighbors if n_neighbors is None else n_neighbors
        f = self._fit_data
        if not 1 <= k <= f.shape[0]:
            raise ValueError(f"n_neighbors {k} not in [1, {f.shape[0]}]")
        from dislib_tpu.data.sparse import SparseArray
        if isinstance(f, SparseArray) or isinstance(x, SparseArray):
            if getattr(self, "ring", None):
                import warnings
                warnings.warn(
                    "NearestNeighbors(ring=True) does not apply to sparse "
                    "inputs; using the streamed sparse schedule (bounded "
                    "dense fit windows; query-row-sharded via shard_map on "
                    "a multi-row mesh, single-program otherwise)",
                    UserWarning, stacklevel=2)
            d, idx = _kneighbors_sparse(x, f, k)
            d_arr = Array._from_logical_padded(
                _repad(d, (x.shape[0], k)), (x.shape[0], k))
            i_arr = Array._from_logical_padded(
                _repad(idx, (x.shape[0], k)), (x.shape[0], k))
            return (d_arr, i_arr) if return_distance else i_arr
        mesh = _mesh.get_mesh()
        # getattr: models loaded from pre-`ring` snapshots lack the attr.
        # The trailing rows>1 guard stays even for forced ring=True: unlike
        # the ε-pass, ring_kneighbors is not inner-tiled, so on a 1-row
        # mesh it would materialise the full (mq, mf) distance block —
        # the chunked single-program path is the memory-safe equivalent.
        if ring_auto(getattr(self, "ring", None), mesh,
                     f.shape[0] >= _RING_MIN) \
                and mesh.shape[_mesh.ROWS] > 1:
            # rotate/compute schedule: resolved at this host boundary so a
            # DSLIB_OVERLAP flip retraces via the kernel static (and the
            # routing is observable through the schedule counters)
            sched = _ov.resolve()
            _prof.count_schedule("ring_kneighbors", sched)
            d, idx = _kneighbors_ring(x._data.astype(jnp.float32),
                                      f._data.astype(jnp.float32),
                                      mesh, k, x.shape[0], f.shape[0],
                                      overlap=sched)
        else:
            d, idx = _kneighbors(x._data, f._data, x.shape, f.shape, k,
                                 chunk=_CHUNK)
        d_arr = Array._from_logical_padded(_repad(d, (x.shape[0], k)), (x.shape[0], k))
        # indices stay int32 (exact for any realistic row count; float32 would
        # corrupt indices past 2^24)
        i_arr = Array._from_logical_padded(_repad(idx, (x.shape[0], k)), (x.shape[0], k))
        if return_distance:
            return d_arr, i_arr
        return i_arr


# fitted-row chunk for the streaming path; fit sets up to 2×_CHUNK rows use
# the direct single-GEMM path (module-level so tests can shrink it)
_CHUNK = 4096

# fit-set size above which a >1-row mesh auto-routes to the ring schedule
_RING_MIN = 1 << 16


@partial(_prof.profiled_jit, static_argnames=("mesh", "k", "mq", "m_fit",
                                              "overlap"),
         name="ring_kneighbors")
def _kneighbors_ring(qp, fp, mesh, k, mq, m_fit, overlap="db"):
    # profiled (round-13): this is a HOST dispatch boundary — one program
    # per ring kneighbors call — so "the ring schedule is still exactly
    # one dispatch" is a counter assertion (tests/test_overlap.py)
    d2, idx = ring_kneighbors(qp, fp, mesh, k, m_fit, overlap=overlap)
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    valid_q = lax.broadcasted_iota(jnp.int32, (dist.shape[0], 1), 0) < mq
    return jnp.where(valid_q, dist, 0.0), jnp.where(valid_q, idx, 0)


@partial(jax.jit, static_argnames=("q_shape", "f_shape", "k", "chunk"))
@precise
def _kneighbors(qp, fp, q_shape, f_shape, k, chunk=None):
    mq, d = q_shape
    mf = f_shape[0]
    qv = qp[:, :d]
    fv = fp[:, :d]
    # chunk is a static cache key; None (internal callers) reads the module
    # default at trace time
    chunk = _CHUNK if chunk is None else chunk
    if fv.shape[0] <= 2 * chunk:
        dist = distances_sq(qv, fv)                           # (mq_pad, mf_pad)
        invalid = lax.broadcasted_iota(jnp.int32, (1, fv.shape[0]), 1) >= mf
        dist = jnp.where(invalid, jnp.inf, dist)
        neg, idx = lax.top_k(-dist, k)
        idx = idx.astype(jnp.int32)
    else:
        neg, idx = _kneighbors_chunked(qv, fv, mf, k, chunk)
    dist_k = jnp.sqrt(jnp.maximum(-neg, 0.0))
    valid_q = lax.broadcasted_iota(jnp.int32, (qv.shape[0], 1), 0) < mq
    dist_k = jnp.where(valid_q, dist_k, 0.0)
    idx = jnp.where(valid_q, idx, 0)
    return dist_k, idx


def _kneighbors_sparse(x, f, k):
    """kNN with a sparse fit set and/or sparse queries — streams the fit
    rows as bounded dense windows, never densifies a whole matrix.

    Dense queries take the SHARDED schedule (`shard_map` over 'rows': each
    device scores its own query shard against the replicated bounded
    windows — manual SPMD, because GSPMD replicates a row-sharded operand
    to partition `top_k`, which the round-4 comm audit pins).  Sparse
    queries on a >1-row mesh shard the same way via the rectangular
    `sharded_rows` buffers (BCOO itself doesn't mesh-shard); on a 1-row
    mesh they take the single-program BCOO kernel."""
    from dislib_tpu.data.sparse import SparseArray
    n = f.shape[1]
    chunk = min(_CHUNK, max(1, f.shape[0]))
    if isinstance(f, SparseArray):
        f_args = (*f.row_steps(chunk), None)
    else:
        # dense fit as full-row steps: the same kernel shape, windows cut
        # by dynamic_slice instead of scatter
        n_chunks = -(-f.shape[0] // chunk)
        row_off = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
        rows_in = jnp.minimum(chunk, f.shape[0] - row_off).astype(jnp.int32)
        f_args = (None, None, None, row_off, rows_in,
                  f._data[: f.shape[0], : f.shape[1]])
    mesh = _mesh.get_mesh()
    if isinstance(x, SparseArray):
        if mesh.shape[_mesh.ROWS] > 1 or x._sharded_rep is not None:
            # row-sharded schedule: each shard rebuilds its local BCOO
            # from the rectangular `sharded_rows` buffers and streams the
            # replicated fit windows — same shard_map reasoning as the
            # dense-query path (GSPMD would gather the top-k operand).
            # Sharded-BACKED queries take it even on a 1-row mesh: the
            # buffers are already device-resident, while the BCOO kernel
            # below would materialise host triplets first.
            qdat, qlr, qcol, qrsq = x.sharded_rows(mesh)
            return _kneighbors_sparse_sharded_sq(
                qdat, qlr, qcol, qrsq, *f_args, n=n, mq=x.shape[0],
                mf=f.shape[0], k=k, chunk=chunk, mesh=mesh)
        q_bcoo = x._bcoo
        q_rowsq = x.row_norms_sq()
        return _kneighbors_sparse_kernel(
            q_bcoo, None, q_rowsq, *f_args, n=n, mq=x.shape[0],
            mf=f.shape[0], k=k, chunk=chunk)
    return _kneighbors_sparse_sharded_q(
        x._data, *f_args[:5], n=n, mq=x.shape[0], mf=f.shape[0], k=k,
        chunk=chunk, mesh=mesh)


@partial(jax.jit, static_argnames=("n", "mq", "mf", "k", "chunk", "mesh"))
@precise
def _kneighbors_sparse_sharded_q(qp, fdat, flr, fcol, row_off, rows_in,
                                 n, mq, mf, k, chunk, mesh):
    """Dense queries over a streamed sparse fit set, row-sharded BY HAND
    (`shard_map`): queries and the running top-k never leave their shard;
    the only replicated tensors are the O(chunk·n) step windows and their
    triplet buffers.  Manual because GSPMD replicates a row-sharded
    operand to partition `lax.top_k` (observed on the 8-device rig: an
    all-gather of the whole candidate buffer), exactly the gather the comm
    audit forbids — the same reason `ops/ring.py` is a shard_map."""
    p = mesh.shape[_mesh.ROWS]
    mq_loc = qp.shape[0] // p

    def local(q_s, fdat_s, flr_s, fcol_s, ro_s, ri_s):
        qv = q_s[:, :n]
        q_rowsq = jnp.sum(qv * qv, axis=1)
        neg, idx = _stream_topk(qv, q_rowsq, None, fdat_s, flr_s, fcol_s,
                                ro_s, ri_s, None, n, mf, k, chunk,
                                varying_axes=(_mesh.ROWS,))
        d = jnp.sqrt(jnp.maximum(-neg, 0.0))
        # zero this shard's padded query rows (global pad-and-mask invariant)
        my = lax.axis_index(_mesh.ROWS)
        valid = (my * mq_loc
                 + lax.broadcasted_iota(jnp.int32, (qv.shape[0], 1), 0)) < mq
        return jnp.where(valid, d, 0.0), jnp.where(valid, idx, 0)

    repl = [P(*([None] * a.ndim)) for a in (fdat, flr, fcol, row_off,
                                            rows_in)]
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS, None), *repl),
        out_specs=(P(_mesh.ROWS, None), P(_mesh.ROWS, None)),
        check_vma=True,
    )(qp, fdat, flr, fcol, row_off, rows_in)


@partial(jax.jit, static_argnames=("n", "mq", "mf", "k", "chunk", "mesh"))
@precise
def _kneighbors_sparse_sharded_sq(qdat, qlr, qcol, qrsq, fdat, flr, fcol,
                                  row_off, rows_in, f_dense, n, mq, mf, k,
                                  chunk, mesh):
    """SPARSE queries over a streamed fit set, row-sharded by hand: each
    shard rebuilds its local-row BCOO from the rectangular `sharded_rows`
    buffers (padding entries are v=0 → contribute nothing) and runs the
    same streamed top-k; per-shard spmm work is O(nnz/p · chunk), the
    same economics as the sparse KMeans E-step."""
    from jax.experimental import sparse as jsparse
    p = mesh.shape[_mesh.ROWS]
    m_loc = qrsq.shape[1]

    def local(qd_s, qlr_s, qcol_s, qrsq_s, *f_s):
        fs = iter(f_s)
        fdat_l = next(fs) if fdat is not None else None
        flr_l = next(fs) if flr is not None else None
        fcol_l = next(fs) if fcol is not None else None
        ro_l = next(fs)
        ri_l = next(fs)
        fd_l = next(fs) if f_dense is not None else None
        idx = jnp.stack([qlr_s[0], qcol_s[0]], axis=1)
        bcoo = jsparse.BCOO((qd_s[0], idx), shape=(m_loc, n))
        neg, idxk = _stream_topk(None, qrsq_s[0], bcoo, fdat_l, flr_l,
                                 fcol_l, ro_l, ri_l, fd_l, n, mf, k, chunk,
                                 varying_axes=(_mesh.ROWS,))
        d = jnp.sqrt(jnp.maximum(-neg, 0.0))
        my = lax.axis_index(_mesh.ROWS)
        valid = (my * m_loc
                 + lax.broadcasted_iota(jnp.int32, (m_loc, 1), 0)) < mq
        return (jnp.where(valid, d, 0.0)[None],
                jnp.where(valid, idxk, 0)[None])

    f_ops = [a for a in (fdat, flr, fcol, row_off, rows_in, f_dense)
             if a is not None]
    repl = [P(*([None] * a.ndim)) for a in f_ops]
    d, idxk = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS), P(_mesh.ROWS), P(_mesh.ROWS),
                  P(_mesh.ROWS), *repl),
        out_specs=(P(_mesh.ROWS), P(_mesh.ROWS)),
        check_vma=True,
    )(qdat, qlr, qcol, qrsq, *f_ops)
    return d.reshape(p * m_loc, k), idxk.reshape(p * m_loc, k)


def _stream_topk(qv, q_rowsq, q_bcoo, fdat, flr, fcol, row_off, rows_in,
                 f_dense, n, mf, k, chunk, varying_axes=None):
    """Running top-k over fit-row steps (same merge as the dense chunked
    path).  Each step covers rows [row_off, row_off+rows_in) — its dense
    window materialises by scatter-add from the step's triplet buffer
    (sparse fit) or a dynamic slice (dense fit); the cross-term is one
    GEMM (dense queries ``qv``) or one spmm (sparse queries ``q_bcoo``).
    Window rows beyond rows_in belong to OTHER steps and are masked to
    +inf.  Traced inside both the single-program kernel and the per-shard
    body of the sharded dense-query schedule.  Returns the NEGATED best
    squared distances and indices."""
    n_steps = row_off.shape[0]

    def window(i, ro):
        if fdat is not None:
            d_e, lr, cc = fdat[i], flr[i], fcol[i]
            dense = jnp.zeros((chunk, n), q_rowsq.dtype).at[lr, cc].add(d_e)
            rowsq = jax.ops.segment_sum(d_e * d_e, lr, num_segments=chunk)
        else:
            fpad = jnp.pad(f_dense,
                           ((0, n_steps * chunk - f_dense.shape[0]), (0, 0)))
            dense = lax.dynamic_slice(fpad, (ro, 0), (chunk, n))
            rowsq = jnp.sum(dense * dense, axis=1)
        return dense, rowsq

    def body(carry, xs):
        best_neg, best_idx = carry
        i, ro, rc = xs
        dense, f_rowsq = window(i, ro)
        if q_bcoo is not None:
            from dislib_tpu.data.sparse import _spmm
            cross = _spmm(q_bcoo, dense.T)                   # (mq, chunk)
        else:
            cross = qv @ dense.T
        dist = jnp.maximum(q_rowsq[:, None] - 2.0 * cross + f_rowsq[None, :],
                           0.0)
        col = ro + lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        in_step = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) < rc
        dist = jnp.where(in_step & (col < mf), dist, jnp.inf)
        cand_neg = jnp.concatenate([best_neg, -dist], axis=1)
        cand_idx = jnp.concatenate(
            [best_idx, jnp.broadcast_to(col, (dist.shape[0], chunk))], axis=1)
        neg, sel = lax.top_k(cand_neg, k)
        return (neg, jnp.take_along_axis(cand_idx, sel, axis=1)), None

    mq_rows = q_rowsq.shape[0]
    init = (jnp.full((mq_rows, k), -jnp.inf, q_rowsq.dtype),
            jnp.zeros((mq_rows, k), jnp.int32))
    if varying_axes:
        # inside a shard_map the constant seeds become shard-varying on the
        # first merge; declaring it up front keeps check_vma provable (the
        # same pattern as ops/ring.py)
        init = tuple(lax.pcast(b, varying_axes, to="varying") for b in init)
    (best_neg, best_idx), _ = lax.scan(
        body, init,
        (jnp.arange(n_steps, dtype=jnp.int32), row_off, rows_in))
    return best_neg, best_idx


@partial(jax.jit, static_argnames=("n", "mq", "mf", "k", "chunk"))
@precise
def _kneighbors_sparse_kernel(q_bcoo, q_dense, q_rowsq, fdat, flr, fcol,
                              row_off, rows_in, f_dense, n, mq, mf, k,
                              chunk):
    """Single-program wrapper over `_stream_topk` (sparse queries; also
    the dense-fit-with-sparse-query combination)."""
    neg, idx = _stream_topk(q_dense, q_rowsq, q_bcoo, fdat, flr, fcol,
                            row_off, rows_in, f_dense, n, mf, k, chunk)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx


def _kneighbors_chunked(qv, fv, mf, k, chunk):
    """Running top-k over fitted-row chunks: each scan step merges the
    carried k-best with one chunk's distances.  Ties keep the earlier
    (lower) index — carried candidates precede the chunk in the merge, and
    chunks arrive in index order, so tie-breaking matches the direct path."""
    mq_pad = qv.shape[0]
    n_chunks = -(-fv.shape[0] // chunk)
    fpad = jnp.pad(fv, ((0, n_chunks * chunk - fv.shape[0]), (0, 0)))
    f_chunks = fpad.reshape(n_chunks, chunk, fv.shape[1])
    offsets = jnp.arange(n_chunks, dtype=jnp.int32) * chunk

    def body(carry, xs):
        best_neg, best_idx = carry
        f_chunk, off = xs
        dist = distances_sq(qv, f_chunk)                      # (mq_pad, chunk)
        col = off + lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        dist = jnp.where(col >= mf, jnp.inf, dist)
        cand_neg = jnp.concatenate([best_neg, -dist], axis=1)
        cand_idx = jnp.concatenate(
            [best_idx, jnp.broadcast_to(col, (mq_pad, chunk))], axis=1)
        neg, sel = lax.top_k(cand_neg, k)
        return (neg, jnp.take_along_axis(cand_idx, sel, axis=1)), None

    init = (jnp.full((mq_pad, k), -jnp.inf, qv.dtype),
            jnp.zeros((mq_pad, k), jnp.int32))
    (best_neg, best_idx), _ = lax.scan(body, init, (f_chunks, offsets))
    return best_neg, best_idx
