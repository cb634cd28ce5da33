"""KMeans — the north-star estimator (reference: `dislib/cluster/kmeans` —
`_partial_sum` per row block, arity-tree `_merge`, per-iteration host sync;
SURVEY.md §3.3 and §4.2; BASELINE configs 1 and ★).

TPU-native redesign (the survey's §4.2 TPU mapping, verbatim):

- The whole Lloyd's iteration is ONE jitted step inside a `lax.while_loop`
  that runs ON DEVICE — the host syncs once per *fit*, not once per
  iteration.  The reference pays B task submissions + a tree of merge tasks
  + one worker→master sync every iteration; here an iteration is one fused
  XLA program over the row-sharded data.
- `_partial_sum`'s per-block (distances → argmin → per-cluster Σx/count)
  is one Lloyd step, in one of two bodies chosen at trace time from what
  the trace can observe (`profiling.schedule_counters()` says which, as
  `kmeans_step:fused` / `kmeans_step:two_pass`).  Fused
  (`ops/base.py::lloyd_step`): ONE blocked pass over the rows, each block
  read from HBM once and serving both products — the distance cross term
  (‖x‖² − 2x·cᵀ + ‖c‖², argmin) and the per-cluster sums `onehotᵀ @ x` —
  in the Pallas kernel `ops/pallas_kernels.py::kmeans_step`, per row
  shard under a `shard_map` with one `psum`.  For dense float32 rows on a
  TPU, `fast_distance` off, k and d within the kernel's vregs and VMEM, d
  no multiple of 128 (the TPU then holds X features-major, the layout the
  kernel reads), at least one block of rows a device.  Two-pass, everything
  else: the (m, k) distance matrix via one GEMM, argmin, and the sums as
  another GEMM, XLA's own fusions, two reads of X; the arity-tree `_merge`
  is the row-axis partial-sum reduction XLA emits as a `psum` over ICI.
  The `arity` knob is gone: reduction topology belongs to the compiler
  (SURVEY §6).
- Padded (zero) rows carry weight 0 so they never perturb sums or counts.
- History: a fused E-step kernel lost to the two GEMMs in round 2 at 1M×100
  (105-111 against 124 iter/s) and was deleted.  At 12M×100 both GEMMs are
  bound by their read of X, 13.95 ms an iteration against 6.97 for the one
  pass (PERF.md, PR 28); the kernel that came back keeps the rows on the
  lane axis, which is how the TPU stores such an array.
"""

from __future__ import annotations

from functools import partial

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dislib_tpu.base import BaseEstimator
from dislib_tpu.data.array import Array, _repad, ensure_canonical, \
    fused_kernel
from dislib_tpu.data.sparse import SparseArray, _spmm
from dislib_tpu.ops import distances_sq as _distances_sq
from dislib_tpu.ops.base import lloyd_step as _lloyd_step, \
    lloyd_step_fuses as _lloyd_step_fuses, \
    load_lloyd_step as _load_lloyd_step
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.ops.base import precise
from dislib_tpu.runtime import fetch as _fetch
from dislib_tpu.runtime import fitloop as _fitloop
from dislib_tpu.runtime import health as _health
from dislib_tpu.utils.dlog import verbose_logger
from dislib_tpu.utils.profiling import profiled_jit as _pjit
from dislib_tpu.utils.profiling import count_schedule as _count_schedule
from dislib_tpu.utils.profiling import new_call as _new_call, span as _span

# device scopes of one Lloyd's iteration, shared by the dense and the
# sparse kernel (PERF.md section 3 holds the vocabulary); the fused step
# is assign and the sums of update in one kernel, under a name of its own
_NORMS = "dslib.kmeans.norms"
_ASSIGN = "dslib.kmeans.assign"
_UPDATE = "dslib.kmeans.update"
_STEP = "dslib.kmeans.step"


class KMeans(BaseEstimator):
    """Lloyd's K-means.

    Parameters (reference parity; `arity` accepted and ignored — reduction
    topology is XLA's job now)
    ----------
    n_clusters : int, default 8
    init : 'random' or ndarray (n_clusters, n_features)
    max_iter : int, default 10
    tol : float, default 1e-4 — convergence on ‖Δcenters‖².
    arity : int — ignored (reference reduction-tree fan-in).
    random_state : int or None

    Attributes
    ----------
    centers_ : ndarray (n_clusters, n_features)
    n_iter_ : int
    inertia_ : float — within-cluster sum of squared distances.
    history_ : ndarray (n_iter_,) — per-iteration inertia (SURVEY §6
        observability row).
    """

    def __init__(self, n_clusters=8, init="random", max_iter=10, tol=1e-4,
                 arity=50, random_state=None, verbose=False,
                 fast_distance=None):
        self.n_clusters = n_clusters
        self.init = init
        self.max_iter = max_iter
        self.tol = tol
        self.arity = arity
        self.random_state = random_state
        self.verbose = verbose
        # E-step distance GEMM at backend-default (bf16 MXU) precision:
        # assignment-only speed/exactness knob — possible argmin flips for
        # near-tied boundary points (~‖x‖²/256 cross-term error).  None
        # reads DSLIB_KMEANS_FAST_DISTANCE (launch-script default).
        self.fast_distance = fast_distance

    def _fast(self) -> bool:
        if self.fast_distance is not None:
            return bool(self.fast_distance)
        return os.environ.get("DSLIB_KMEANS_FAST_DISTANCE", "0") == "1"

    # -- fitting -------------------------------------------------------------

    def _init_centers(self, x):
        with _span("dslib.kmeans.init_centers"):
            k, n = self.n_clusters, x.shape[1]
            if isinstance(self.init, (np.ndarray, list)):
                c = np.asarray(self.init, dtype=np.float32)
                if c.shape != (k, n):
                    raise ValueError(
                        f"init centers must be {(k, n)}, got {c.shape}")
                return jnp.asarray(c)
            if self.init != "random":
                raise ValueError(f"unsupported init {self.init!r}")
            rng = np.random.RandomState(self.random_state)
            # sample k distinct rows — the reference inits from data rows too
            idx = rng.choice(x.shape[0], size=min(k, x.shape[0]),
                             replace=False)
            if isinstance(x, SparseArray):
                # BCOO row gather: filter the host triplets for the k chosen
                # rows and scatter into a (k, n) dense block — O(nnz) filter +
                # O(k·n) result, never an O(k·m) selection operand (the
                # sharded-rows fit path fetches these same triplets anyway)
                sidx = np.sort(idx)
                ind = np.asarray(jax.device_get(x._bcoo.indices))
                val = np.asarray(jax.device_get(x._bcoo.data), np.float32)
                pos = np.searchsorted(sidx, ind[:, 0])
                pos = np.minimum(pos, len(sidx) - 1)
                hit = sidx[pos] == ind[:, 0]
                rows_np = np.zeros((len(sidx), n), np.float32)
                np.add.at(rows_np, (pos[hit], ind[hit, 1]), val[hit])
                rows = jnp.asarray(rows_np)
            else:
                rows = x[np.sort(idx), :]._data[: len(idx), : n]
            if len(idx) < k:  # fewer samples than clusters: top up with jitter
                extra = rows[rng.randint(0, len(idx), k - len(idx))] + 1e-3
                rows = jnp.concatenate([rows, extra], axis=0)
            return rows

    def fit(self, x: Array, y=None, checkpoint=None, health=None):
        """Fit on `x`.  With ``checkpoint=FitCheckpoint(path, every=k)`` the
        device loop runs in k-iteration chunks, snapshotting (centers,
        n_iter) after each; a re-run resumes from the snapshot (SURVEY §6
        checkpoint/resume — TPU preemption recovery).  The whole per-chunk
        resilience protocol — fused health vector at zero extra
        dispatches, watchdog, verdict-gated snapshot writes,
        rollback-to-last-good with the ``health`` policy's escalation
        ladder (dense fits offer the elastic mesh-shrink tier), preemption
        polling — is owned by :class:`~dislib_tpu.runtime.ChunkedFitLoop`;
        centers are host-side logical state, so snapshots restore onto a
        different mesh/device count unchanged (elastic resume)."""
        with _span("dslib.kmeans.fit", call=_new_call()):
            sparse_in = isinstance(x, SparseArray)
            box = {"x": x, "inertia": None}
            log = verbose_logger("kmeans", self.verbose)
            # data_rebind handles BOTH backings since round 14: dense arrays
            # re-canonicalize, sparse arrays reshard their panel buffers on
            # device — the elastic mesh-shrink tier no longer degrades for
            # sparse fits
            loop = _fitloop.ChunkedFitLoop(
                "kmeans", checkpoint=checkpoint, health=health,
                max_iter=self.max_iter, carry_names=("centers",),
                carry_shapes=((self.n_clusters, x.shape[1]),),
                snapshot_expect={"centers": (self.n_clusters, x.shape[1])},
                elastic=_fitloop.data_rebind(box))

            def init(rem):
                box["inertia"] = None
                return _fitloop.LoopState(
                    (jnp.asarray(rem.perturb(self._init_centers(box["x"]))),))

            def restore(snap, rem):
                # snapshot compatibility (centers shape) is declared via
                # snapshot_expect and judged by the rollback funnel
                centers = np.asarray(snap["centers"])
                # a faulted chunk's inertia must not leak into the fitted
                # attrs if the restored state exits the loop (converged
                # snapshot): None falls back to -score(x)
                box["inertia"] = None
                return _fitloop.LoopState(
                    (jnp.asarray(rem.perturb(centers)),),
                    it=int(snap["n_iter"]),
                    done=bool(snap.get("converged", False)))

            def step(st, chunk):
                (centers,) = st.carries
                if sparse_in:
                    data, lrows, cols, rowsq = box["x"].sharded_rows()
                    centers, n_done, inertia, shift, hist, hvec = \
                        _kmeans_fit_sparse_sharded(
                            data, lrows, cols, rowsq, centers, x.shape[0],
                            chunk, float(self.tol), _mesh.get_mesh())
                else:
                    xd = box["x"]
                    centers, n_done, inertia, shift, hist, hvec = _kmeans_fit(
                        xd._data, xd.shape, centers, chunk, float(self.tol),
                        fast=self._fast())

                def commit():
                    # deferred: these scalar syncs run only AFTER the verdict,
                    # so the watchdogged hvec read is the chunk's first force
                    # point (and a faulted chunk never touches the box)
                    box["inertia"] = inertia
                    it = st.it + int(n_done)
                    done = float(shift) < self.tol
                    log.info("iter %d: inertia=%.6g shift=%.3g", it,
                             float(inertia), float(shift))
                    return _fitloop.LoopState((centers,), it, done)

                return _fitloop.ChunkOutcome(
                    commit, hvec=hvec,
                    history=lambda: _fetch(hist)[: int(n_done)])

            def snapshot(st):
                # async offload: the device->host copy starts now and the file
                # write runs on the snapshot worker, both overlapping the next
                # chunk's compute (centers are never donated)
                return {"centers": _fetch(st.carries[0], blocking=False),
                        "n_iter": st.it, "converged": st.done}

            st = loop.run(init=init, step=step, restore=restore,
                          snapshot=snapshot)
            self.centers_ = _fetch(st.carries[0])
            self.n_iter_ = st.it
            self.history_ = np.asarray(loop.history, dtype=np.float64)
            self.fit_info_ = loop.info
            # inertia is None only when resuming an already-finished fit
            self.inertia_ = float(box["inertia"]) \
                if box["inertia"] is not None else -self.score(box["x"])
            return self

    # async trial protocol (SURVEY §4.5): fit/score entirely on device, no
    # host read until GridSearchCV has dispatched every trial
    def _fit_async(self, x, y=None):
        if isinstance(x, SparseArray):
            return super()._fit_async(x, y)
        centers0 = self._init_centers(x)
        return _kmeans_fit(x._data, x.shape, centers0, self.max_iter,
                           float(self.tol), fast=self._fast())

    def _fit_finalize(self, state):
        if state is None:
            return
        centers, n_iter, inertia, _, hist, _ = state
        self.centers_ = np.asarray(jax.device_get(centers))
        self.n_iter_ = int(n_iter)
        self.inertia_ = float(inertia)
        self.history_ = np.asarray(
            jax.device_get(hist), dtype=np.float64)[: self.n_iter_]

    def _score_async(self, state, x, y=None):
        if state is None or isinstance(x, SparseArray):
            self._fit_finalize(state)
            return super()._score_async(state, x, y)
        return _kmeans_score(x._data, x.shape, state[0])

    def fit_predict(self, x: Array, y=None) -> Array:
        return self.fit(x).predict(x)

    def predict(self, x) -> Array:
        """Cluster index per row.  Dense inputs build a fusion-graph node
        (`data.array.fused_kernel`): a scaler → predict pipeline runs as
        ONE cached XLA dispatch end-to-end — the serving-layer hot path."""
        self._check_fitted()
        if isinstance(x, SparseArray):
            d = _sparse_distances(x._bcoo, x.row_norms_sq(),
                                  jnp.asarray(self.centers_))
            labels = jnp.argmin(d, axis=1).astype(jnp.int32)[:, None]
            return Array._from_logical_padded(_repad(labels, (x.shape[0], 1)),
                                              (x.shape[0], 1))
        # serve on the CURRENT mesh: an input built before an elastic
        # resize re-lands on device (never the host) — round 16
        x = ensure_canonical(x)
        (centers,) = self._predict_leaves(self.centers_)
        return fused_kernel(
            _kmeans_predict_kernel, (x.shape,), (x, centers),
            (x.shape[0], 1), jnp.int32, out_pshape=(x._pshape[0], 1))

    def score(self, x, y=None) -> float:
        """Negative inertia on x (sklearn convention)."""
        self._check_fitted()
        if isinstance(x, SparseArray):
            d = _sparse_distances(x._bcoo, x.row_norms_sq(),
                                  jnp.asarray(self.centers_))
            return -float(jnp.sum(jnp.min(d, axis=1)))
        return float(_kmeans_score(x._data, x.shape, jnp.asarray(self.centers_)))

    def _check_fitted(self):
        if not hasattr(self, "centers_"):
            raise RuntimeError("KMeans is not fitted")


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------

@partial(_pjit, static_argnames=("shape", "max_iter", "fast"),
         name="kmeans_fit", before=_load_lloyd_step)
@precise
def _kmeans_fit(xp, shape, centers0, max_iter, tol, fast=False):
    m, n = shape
    xv = xp[:, :n]  # crop padded cols; padded rows stay (weighted 0)
    xv = lax.with_sharding_constraint(xv, _mesh.row_sharding())
    w = (lax.broadcasted_iota(jnp.int32, (xv.shape[0],), 0) < m).astype(xv.dtype)
    k = centers0.shape[0]
    # loop-invariant hoists: ‖x‖² is constant across iterations, and the
    # fast path stores x ONCE as bfloat16 so the per-iteration distance
    # GEMM reads 2 bytes/element instead of 4 (same values the MXU's own
    # input rounding would produce — only the HBM traffic changes).  The
    # center-update GEMM still reads the f32 copy, keeping centers exact.
    with jax.named_scope(_NORMS):
        x_sq = jnp.sum(xv * xv, axis=1)
    xd = xv.astype(jnp.bfloat16) if fast else xv
    # one pass over X where the kernel applies, two XLA passes everywhere
    # else; decided here, once a trace, by what the trace can observe
    fused = not fast and _lloyd_step_fuses(xv, k)
    _count_schedule("kmeans_step", "fused" if fused else "two_pass")
    if fused:
        # the kernel reads the norms and the weights as rows beside X's
        # rows on its lanes: a relayout, made once here.  Behind a barrier,
        # or XLA sinks w (an iota and a compare) into the loop and writes
        # it anew every iteration
        x_sq_row, w_row = lax.optimization_barrier(
            (x_sq[None, :], w[None, :]))

    def two_pass(centers):
        with jax.named_scope(_ASSIGN):
            cross = jnp.matmul(xd, centers.astype(xd.dtype).T,
                               precision="default" if fast else None,
                               preferred_element_type=xv.dtype)
            c_sq = jnp.sum(centers * centers, axis=1)
            d = jnp.maximum(x_sq[:, None] - 2.0 * cross + c_sq[None, :], 0.0)
            labels = jnp.argmin(d, axis=1)
        with jax.named_scope(_UPDATE):
            onehot = jax.nn.one_hot(labels, k, dtype=xv.dtype) * w[:, None]
            sums = onehot.T @ xv             # (k, n) — row-axis psum under SPMD
            counts = jnp.sum(onehot, axis=0)     # (k,)
            inertia = jnp.sum(jnp.min(d, axis=1) * w)
        return sums, counts, inertia

    def one_pass(centers):
        with jax.named_scope(_STEP):
            return _lloyd_step(xv, x_sq_row, w_row, centers)

    def step(carry):
        centers, _, it, _, hist = carry
        sums, counts, inertia = (one_pass if fused else two_pass)(centers)
        with jax.named_scope(_UPDATE):
            new_centers = jnp.where(counts[:, None] > 0,
                                    sums / jnp.maximum(counts, 1.0)[:, None],
                                    centers)
            shift = jnp.sum((new_centers - centers) ** 2)
        return new_centers, shift, it + 1, inertia, hist.at[it].set(inertia)

    def cond(carry):
        _, shift, it, _, _ = carry
        return (it < max_iter) & (shift >= tol)

    init = (centers0, jnp.asarray(jnp.inf, xv.dtype), jnp.int32(0),
            jnp.asarray(0.0, xv.dtype), jnp.zeros((max_iter,), xv.dtype))
    centers, shift, n_iter, inertia, hist = lax.while_loop(cond, step, init)
    # fused health vector — same program, zero extra dispatches (inertia
    # is nonincreasing under exact Lloyd's, so `hist` is the monotone
    # signal; the guard's threshold is host-side policy)
    hvec = _health.health_vec(carries=(centers,), hist=hist, n_done=n_iter)
    return centers, n_iter, inertia, shift, hist, hvec


def _kmeans_predict_core(xp, shape, centers):
    m, n = shape
    xv = xp[:, :n]
    d = _distances_sq(xv, centers)
    # labels stay int32 (consistent with the kNN indices path — float32 is
    # exact only below 2^24)
    labels = jnp.argmin(d, axis=1).astype(jnp.int32)
    # zero out padded rows to keep the Array invariant
    valid = lax.broadcasted_iota(jnp.int32, (xv.shape[0],), 0) < m
    labels = jnp.where(valid, labels, 0)
    return labels[:, None]


def _kmeans_predict_kernel(cfg, xp, centers):
    """`predict` as a fusion-node body (cfg = (logical shape,)) — the ONE
    E-step distance + argmin, riding whatever op chain feeds it."""
    return _kmeans_predict_core(xp, cfg[0], centers)


@partial(_pjit, static_argnames=("shape",), name="kmeans_predict")
@precise
def _kmeans_predict(xp, shape, centers):
    return _kmeans_predict_core(xp, shape, centers)


def _sparse_distances(bcoo, rowsq, centers):
    """Squared distances (m, k) with the cross-term as one spmm."""
    c_sq = jnp.sum(centers * centers, axis=1)
    cross = _spmm(bcoo, centers.T)
    return jnp.maximum(rowsq[:, None] - 2.0 * cross + c_sq[None, :], 0.0)


@partial(_pjit, static_argnames=("m", "max_iter", "mesh"),
         name="kmeans_fit_sparse")
def _kmeans_fit_sparse_sharded(data, lrows, cols, rowsq, centers0, m,
                               max_iter, tol, mesh):
    """Sparse-path Lloyd's on the row-sharded rectangular representation
    (`SparseArray.sharded_rows`): per iteration each shard computes its
    rows' distance cross-term shard-locally (gather centersᵀ at the entry
    columns, scale, segment-sum by local row), and the per-cluster (Σx,
    count) partials combine with ONE `psum` over the rows axis — the same
    communication structure as the dense `_kmeans_fit` (SURVEY §8 hard
    part 2: sharded spmm + psum, not a single-device BCOO)."""
    p = mesh.shape[_mesh.ROWS]
    m_local = rowsq.shape[1]
    k = centers0.shape[0]

    def shard_fn(d_s, lr_s, cc_s, rsq_s, c0):
        d_e, lr, cc, rsq = d_s[0], lr_s[0], cc_s[0], rsq_s[0]
        offset = lax.axis_index(_mesh.ROWS) * m_local
        valid = (offset + lax.broadcasted_iota(jnp.int32, (m_local,), 0)) < m

        def step(carry):
            centers, _, it, _, hist = carry
            with jax.named_scope(_ASSIGN):
                c_sq = jnp.sum(centers * centers, axis=1)
                # cross = x_local @ centersᵀ, one gather + segment_sum
                contrib = centers.T[cc] * d_e[:, None]       # (nnz, k)
                cross = jax.ops.segment_sum(contrib, lr,
                                            num_segments=m_local)
                dist = jnp.maximum(
                    rsq[:, None] - 2.0 * cross + c_sq[None, :], 0.0)
                labels = jnp.argmin(dist, axis=1)
            with jax.named_scope(_UPDATE):
                onehot = jax.nn.one_hot(labels, k, dtype=centers.dtype) \
                    * valid[:, None].astype(centers.dtype)
                counts = lax.psum(jnp.sum(onehot, axis=0), _mesh.ROWS)
                # sums = xᵀ onehot: shard-local partial + psum
                contrib2 = onehot[lr] * d_e[:, None]         # (nnz, k)
                partial = jax.ops.segment_sum(
                    contrib2, cc, num_segments=centers.shape[1])
                sums = lax.psum(partial, _mesh.ROWS).T       # (k, n)
                inertia = lax.psum(
                    jnp.sum(jnp.min(dist, axis=1)
                            * valid.astype(centers.dtype)), _mesh.ROWS)
                new_centers = jnp.where(
                    counts[:, None] > 0,
                    sums / jnp.maximum(counts, 1.0)[:, None], centers)
                shift = jnp.sum((new_centers - centers) ** 2)
            return new_centers, shift, it + 1, inertia, hist.at[it].set(inertia)

        def cond(carry):
            _, shift, it, _, _ = carry
            return (it < max_iter) & (shift >= tol)

        init = (c0, jnp.asarray(jnp.inf, c0.dtype), jnp.int32(0),
                jnp.asarray(0.0, c0.dtype), jnp.zeros((max_iter,), c0.dtype))
        return lax.while_loop(cond, step, init)

    from jax.sharding import PartitionSpec as P
    # replication checking stays ON: every loop-carry element descends from
    # psum outputs, so the varying-axes analysis proves the P() out_specs
    centers, shift, n_iter, inertia, hist = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(_mesh.ROWS), P(_mesh.ROWS), P(_mesh.ROWS), P(_mesh.ROWS),
                  P(None, None)),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=True,
    )(data, lrows, cols, rowsq, centers0)
    # fused health vector over the replicated outputs — still inside this
    # jitted program, zero extra dispatches
    hvec = _health.health_vec(carries=(centers,), hist=hist, n_done=n_iter)
    return centers, n_iter, inertia, shift, hist, hvec


@partial(_pjit, static_argnames=("shape",), name="kmeans_score")
@precise
def _kmeans_score(xp, shape, centers):
    m, n = shape
    xv = xp[:, :n]
    w = (lax.broadcasted_iota(jnp.int32, (xv.shape[0],), 0) < m).astype(xv.dtype)
    d = _distances_sq(xv, centers)
    return -jnp.sum(jnp.min(d, axis=1) * w)
