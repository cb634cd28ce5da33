"""ALS collaborative filtering (reference: `dislib/recommendation/als` —
`_update_chunk` tasks solving per-row regularized least squares alternately
for user and item factors on a blocked sparse ratings matrix, RMSE-based
convergence; SURVEY.md §3.3).

TPU-native redesign:

- The reference alternates over the two matrix dimensions by mapping
  `_update_chunk` tasks over row blocks of R (user step) and of Rᵀ (item
  step).  Here BOTH half-steps live inside ONE jitted `lax.while_loop`
  iteration over the sharded ratings matrix: the per-user normal equations
  ``A_u = Σ_{j∈Ω_u} v_j v_jᵀ + λ n_u I`` are built for *all* users at once as
  one GEMM (``mask @ (v_f · v_g)`` reshaped to (m, f, f)) plus ``b = R @ V``
  — MXU-bound — followed by a batched Cholesky solve.  The item step is the
  same kernel on the transpose.
- Dense `Array` ratings are dense-with-mask (SURVEY §8 "Sparse support"
  fallback): entry==0 means unobserved, exactly the information the
  reference's CSR sparsity structure carries.  The ds-array padding region
  is zero by invariant, so padded rows/cols solve to λI·x=0 → zero factors
  and never perturb the observed entries.
- `SparseArray` ratings take a TRUE sparse path (`_als_fit_sparse`): the
  normal equations are segment-sums over the observed (user, item, rating)
  triplets — O(nnz·f²) work/memory, no densification — matching the
  reference's CSR-block `_update_chunk` economics.
- Convergence (|ΔRMSE| < tol, on train or held-out test ratings) is decided
  ON DEVICE inside the while_loop — host syncs once per fit, not per
  iteration (the reference syncs the RMSE scalar every iteration).
- Regularisation follows the reference's Zhou et al. weighted-λ scheme:
  λ · n_u scales with each row's observation count.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dislib_tpu.base import BaseEstimator
from dislib_tpu.data.array import Array, \
    ensure_canonical as _ensure_canonical
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.ops.base import precise
from dislib_tpu.runtime import fetch as _fetch, repad_rows as _repad_rows
from dislib_tpu.runtime import fitloop as _fitloop
from dislib_tpu.runtime import health as _health
from dislib_tpu.utils.dlog import verbose_logger
from dislib_tpu.utils.profiling import profiled_jit as _pjit


class ALS(BaseEstimator):
    """Alternating Least Squares matrix factorisation.

    Parameters (reference parity: `dislib/recommendation/als :: ALS`)
    ----------
    n_f : int, default 8
        Number of latent factors.
    lambda_ : float, default 0.065
        Regularisation strength (weighted by per-row rating counts).
    tol : float, default 1e-4
        Convergence threshold on |ΔRMSE| between iterations.
    max_iter : int, default 100
    random_state : int or None
    verbose : bool — log per-chunk RMSE under the dslib.als logger.
    arity : int — accepted and ignored (reference reduction-tree fan-in;
        reduction topology is XLA's job now).

    Attributes
    ----------
    users_ : ndarray (n_users, n_f) — user factor matrix U.
    items_ : ndarray (n_items, n_f) — item factor matrix V.
    converged_ : bool
    n_iter_ : int
    rmse_ : float — RMSE over the convergence ratings at the last iteration.
    history_ : ndarray (n_iter_,) — per-iteration held-out RMSE (SURVEY §6).
    """

    def __init__(self, n_f=8, lambda_=0.065, tol=1e-4, max_iter=100,
                 random_state=None, verbose=False, arity=48):
        self.n_f = n_f
        self.lambda_ = lambda_
        self.tol = tol
        self.max_iter = max_iter
        self.random_state = random_state
        self.verbose = verbose
        self.arity = arity

    def fit(self, x: Array, test=None, checkpoint=None, health=None):
        """Factorise the ratings matrix ``x`` (users × items, 0 = unobserved).

        ``test`` — optional held-out ratings (ndarray or ds-array with the
        same shape, 0 = unobserved) used for the convergence RMSE instead of
        the training ratings, as in the reference.
        ``checkpoint`` — optional ``FitCheckpoint``: run in `every`-iteration
        chunks, snapshot (users, items, rmse, n_iter) after each, resume from
        the snapshot on re-run (SURVEY §6 checkpoint/resume).  Between
        chunks the loop honours the preemption flag (`dislib_tpu.runtime`):
        snapshot first, then a clean ``Preempted``.  Snapshots record the
        LOGICAL factor dims, so a checkpoint written on one mesh resumes on
        a different device count (the factors are re-padded on restore —
        elastic resume).
        ``health`` — optional :class:`~dislib_tpu.runtime.HealthPolicy`;
        each chunk's kernel emits a fused health vector over the factors
        and the RMSE history.  A tripped guard rolls back to the
        last-good snapshot; the ``halve`` action additionally doubles
        ``lambda_`` per restart (the normal-equation ridge — ALS's
        damping knob against ill-conditioned solves).
        """
        if self.max_iter < 1:
            raise ValueError("max_iter must be >= 1")
        from dislib_tpu.data.sparse import SparseArray
        sparse_in = isinstance(x, SparseArray)
        t_host = None
        if not sparse_in and test is not None:
            import scipy.sparse as sp
            if isinstance(test, SparseArray):
                t_host = np.asarray(test.collect().toarray())
            else:
                t = test.collect() if isinstance(test, Array) else test
                t_host = np.asarray(t.toarray() if sp.issparse(t) else t)
            if t_host.shape != x.shape:
                raise ValueError(f"test ratings shape {t_host.shape} != "
                                 f"ratings shape {x.shape}")
        seed = self.random_state if self.random_state is not None else 0
        box = {"x": x, "lam": float(self.lambda_), "rmse": np.inf}

        def _bind_test():
            if sparse_in:
                # true sparse path: row-panel-sharded buffers for the
                # ratings AND the held-out test entries — O(nnz) storage,
                # no densification ever happens
                box["rep"] = box["x"].sharded()
                if "t_sa" not in box:
                    box["t_sa"] = None if test is None \
                        else _test_sparse(test, x.shape)
                box["trep"] = box["rep"] if box["t_sa"] is None \
                    else box["t_sa"].sharded()
            else:
                box["test_p"] = box["x"]._data if t_host is None \
                    else _pad_like(t_host, box["x"])
        _bind_test()

        def rebind(mesh):
            if mesh is None:            # pre-switch: force pending chains
                if not sparse_in:
                    box["x"].force()
                return
            if not sparse_in:
                box["x"] = _ensure_canonical(box["x"])
            _bind_test()                # sparse: reps reshard ON DEVICE
                                        # through the sparse rechunk router

        log = verbose_logger("als", self.verbose)
        loop = _fitloop.ChunkedFitLoop(
            "als", checkpoint=checkpoint, health=health,
            max_iter=self.max_iter, carry_names=("users", "items"),
            carry_shapes=((x.shape[0], int(self.n_f)),
                          (x.shape[1], int(self.n_f))),
            # snapshots carry the LOGICAL factor dims (m, n) as scalars;
            # the stored factor ROWS may be padded for a different mesh
            # (elastic resume re-pads), so only the factor width is pinned
            snapshot_expect={"m": int(x.shape[0]), "n": int(x.shape[1]),
                             "users": (None, int(self.n_f)),
                             "items": (None, int(self.n_f))},
            elastic=rebind)

        def init(rem):
            # ALS damping: the 'halve' tier raises the per-row ridge λ·n_u
            # per attempt (ill-conditioned normal equations are the
            # numeric failure mode of the batched Cholesky solves)
            box["lam"] = float(self.lambda_) * rem.damping
            box["rmse"] = np.inf
            return _fitloop.LoopState(())   # fresh: the kernel seeds itself

        def restore(snap, rem):
            # snapshot compatibility (logical dims + factor width) is
            # declared via snapshot_expect and judged by the rollback
            # funnel; elastic resume re-pads the factor rows for THIS
            # mesh (runtime.repad_rows)
            sm, sn = int(snap["m"]), int(snap["n"])
            box["lam"] = float(self.lambda_) * rem.damping
            box["rmse"] = float(snap["rmse"])
            if sparse_in:
                # the sharded kernel carries U padded to the CURRENT
                # mesh's row quantum and V at its logical length
                from dislib_tpu.data.sparse import _padded_rows
                tu = _padded_rows(x.shape[0], _mesh.get_mesh())
                tv = x.shape[1]
            else:
                tu = box["x"]._data.shape[0]
                tv = box["x"]._data.shape[1]
            return _fitloop.LoopState(
                (jnp.asarray(rem.perturb(_repad_rows(snap["users"], sm, tu))),
                 jnp.asarray(rem.perturb(_repad_rows(snap["items"], sn, tv)))),
                it=int(snap["n_iter"]),
                done=bool(snap.get("converged", False)),
                extra=float(snap["rmse"]))

        def step(st, chunk):
            state = (*st.carries, st.extra) if st.carries else None
            if sparse_in:
                rep, trep = box["rep"], box["trep"]
                u, v, rmse_dev, n_done, conv, hist, hvec = _als_fit_sparse(
                    rep.data, rep.lrows, rep.cols, rep.counts_dev,
                    trep.data, trep.lrows, trep.cols, trep.counts_dev,
                    x.shape[0], x.shape[1],
                    int(self.n_f), box["lam"], float(self.tol),
                    chunk, int(seed), _mesh.get_mesh(), init_state=state)
            else:
                u, v, rmse_dev, n_done, conv, hist, hvec = _als_fit(
                    box["x"]._data, box["test_p"], x.shape, int(self.n_f),
                    box["lam"], float(self.tol), chunk, int(seed),
                    init_state=state)

            def commit():
                # deferred scalar syncs: the watchdogged hvec read stays
                # the chunk's first force point
                box["rmse"] = float(rmse_dev)
                it = st.it + int(n_done)
                log.info("iter %d: rmse=%.6g", it, box["rmse"])
                return _fitloop.LoopState((u, v), it, bool(conv),
                                          extra=box["rmse"])

            return _fitloop.ChunkOutcome(
                commit, hvec=hvec,
                history=lambda: _fetch(hist)[: int(n_done)])

        def snapshot(st):
            # the factors are DONATED to the next chunk's kernel call
            # (their HBM is reused in place), so their device->host copies
            # must land before that dispatch: fetch blocking, and offload
            # only the checksum+write to the snapshot worker
            return {"users": _fetch(st.carries[0]),
                    "items": _fetch(st.carries[1]),
                    "m": x.shape[0], "n": x.shape[1],
                    "rmse": st.extra, "n_iter": st.it, "converged": st.done}

        st = loop.run(init=init, step=step, restore=restore,
                      snapshot=snapshot)
        u, v = st.carries
        m, n = x.shape
        self.users_ = np.asarray(jax.device_get(u))[:m]
        self.items_ = np.asarray(jax.device_get(v))[:n]
        self.rmse_ = float(box["rmse"])
        self.n_iter_ = st.it
        self.converged_ = st.done
        self.history_ = np.asarray(loop.history, dtype=np.float64)
        self.fit_info_ = loop.info
        return self

    # async trial protocol (SURVEY §4.5): the no-test, no-checkpoint fit is
    # one jitted while_loop; the handle is its device output tuple.  Sparse
    # inputs read their triplets (input prep, not fit results) at dispatch.
    def _fit_async(self, x, y=None):
        if self.max_iter < 1:
            raise ValueError("max_iter must be >= 1")
        from dislib_tpu.data.sparse import SparseArray
        seed = self.random_state if self.random_state is not None else 0
        if isinstance(x, SparseArray):
            rep = x.sharded()
            bufs = (rep.data, rep.lrows, rep.cols, rep.counts_dev)
            out = _als_fit_sparse(*bufs, *bufs,
                                  x.shape[0], x.shape[1], int(self.n_f),
                                  float(self.lambda_), float(self.tol),
                                  self.max_iter, int(seed),
                                  _mesh.get_mesh())
        else:
            out = _als_fit(x._data, x._data, x.shape, int(self.n_f),
                           float(self.lambda_), float(self.tol),
                           self.max_iter, int(seed))
        return (out, x.shape)

    def _fit_finalize(self, state):
        if state is None:
            return
        (u, v, rmse, n_iter, conv, hist, _), (m, n) = state
        self.users_ = np.asarray(jax.device_get(u))[:m]
        self.items_ = np.asarray(jax.device_get(v))[:n]
        self.rmse_ = float(rmse)
        self.n_iter_ = int(n_iter)
        self.converged_ = bool(conv)
        self.history_ = np.asarray(
            jax.device_get(hist), dtype=np.float64)[: self.n_iter_]

    def predict_user(self, user_id: int) -> np.ndarray:
        """Predicted ratings for every item for one user (reference parity)."""
        self._check_fitted()
        if not 0 <= user_id < self.users_.shape[0]:
            raise IndexError(f"user_id {user_id} out of range")
        return self.users_[user_id] @ self.items_.T

    def fold_in(self, ratings, top_n=None):
        """Score BRAND-NEW users against the trained item factors with no
        refit — the core recommendation-at-scale operation (ROADMAP item
        1's online fold-in): solve each new user's regularized normal
        equations ``(Σ_{j∈Ω} v_j v_jᵀ + λ n I) u = Σ_j r_j v_j`` against
        the FROZEN ``items_`` and return predicted ratings for every
        item, all in ONE fused dispatch (solve + predict GEMM; the item
        factors are device-cached across calls via the serving-layer
        leaf cache, so a warm fold-in re-transfers nothing).

        ``ratings``: one user's ratings or a (k, n_items) batch —
        SparseArray, scipy sparse, ndarray (0 = unobserved), or a
        pre-padded device pair ``(cols, vals)`` of shape (k, s) with
        (column 0, value 0) pads — the zero-host-transfer serving form.

        ``top_n`` — when set, rank inside the SAME dispatch
        (``lax.top_k`` fuses after the predict GEMM) and return the
        ``(item_ids, scores)`` pair of (k, top_n) ndarrays instead of the
        full score matrix: the host fetch shrinks from n_items to top_n
        per user and no host-side argsort follows.

        Returns the (k, n_items) predicted-ratings ndarray, or the
        ``(item_ids, scores)`` pair with ``top_n``."""
        out = self._fold_in_device(ratings, top_n=top_n)
        if top_n is not None:
            ids, scores = out
            return np.asarray(_fetch(ids)), np.asarray(_fetch(scores))
        return np.asarray(_fetch(out))

    def _fold_in_device(self, ratings, precision=None, top_n=None):
        """The device half of :meth:`fold_in`: returns the predictions
        as a device array, unfetched — what the sparse serving pipeline
        consumes (its response fetch is the one blessed sync)."""
        self._check_fitted()
        from dislib_tpu.ops import precision as _px
        if isinstance(ratings, tuple) and len(ratings) == 2:
            cols, vals = (jnp.asarray(a) for a in ratings)
            if not jnp.issubdtype(cols.dtype, jnp.integer):
                # the serving encoding carries ids as float32 (exact
                # below 2^24) — the gather needs integer indices
                cols = cols.astype(jnp.int32)
        else:
            cols, vals = _fold_in_pack(ratings, self.items_.shape[0])
        if cols.ndim == 1:
            cols, vals = cols[None, :], vals[None, :]
        (items,) = self._predict_leaves(self.items_)
        _, preds = _als_fold_in(vals, cols, items, float(self.lambda_),
                                int(self.n_f), _px.resolve(precision),
                                top_n=int(top_n or 0))
        return preds

    def _check_fitted(self):
        if not hasattr(self, "users_"):
            raise RuntimeError("ALS is not fitted")


def _test_sparse(test, want_shape):
    """Held-out ratings → a SparseArray (0 = unobserved) whose sharded
    buffers feed the fit kernel; accepts SparseArray, scipy sparse,
    ds-array, or ndarray without ever densifying a sparse input."""
    from dislib_tpu.data.sparse import SparseArray
    import scipy.sparse as sp
    t = test
    if isinstance(t, Array) and not isinstance(t, SparseArray):
        t = t.collect()
    if not (isinstance(t, SparseArray) or sp.issparse(t)):
        t = sp.csr_matrix(np.asarray(t, np.float32))
    if tuple(t.shape) != tuple(want_shape):
        raise ValueError(f"test ratings shape {tuple(t.shape)} != "
                         f"ratings shape {tuple(want_shape)}")
    if isinstance(t, SparseArray):
        return t
    return SparseArray.from_scipy(t)


def _fold_in_pack(ratings, n_items):
    """Host packing of new-user ratings into padded (cols, vals) device
    pairs — per-user nse = the batch's densest row (quantized up), pads
    at (column 0, value 0) so they are additive no-ops in the fold-in
    normal equations (the library pad discipline)."""
    import scipy.sparse as sp
    from dislib_tpu.data.sparse import SparseArray, nse_quantum
    t = ratings
    if isinstance(t, SparseArray):
        t = t.collect()
    if not sp.issparse(t):
        t = sp.csr_matrix(np.atleast_2d(np.asarray(t, np.float32)))
    t = t.tocsr()
    if t.shape[1] != n_items:
        raise ValueError(f"fold_in ratings have {t.shape[1]} items, the "
                         f"model was trained on {n_items}")
    k = t.shape[0]
    row_nnz = np.diff(t.indptr)
    q = nse_quantum()
    s = int(math.ceil(max(int(row_nnz.max(initial=1)), 1) / q) * q)
    cols = np.zeros((k, s), np.int32)
    vals = np.zeros((k, s), np.float32)
    for i in range(k):
        lo, hi = t.indptr[i], t.indptr[i + 1]
        cols[i, : hi - lo] = t.indices[lo:hi]
        vals[i, : hi - lo] = t.data[lo:hi]
    return jnp.asarray(cols), jnp.asarray(vals)


def _pad_like(t: np.ndarray, x: Array):
    """Pad host ratings to x's padded device shape (zeros outside logical)."""
    out = np.zeros(x._data.shape, dtype=x._data.dtype)
    out[: t.shape[0], : t.shape[1]] = t
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------

def _solve_factors(r, mask, v, lambda_, n_f):
    """Per-row regularized LS for all rows at once (the `_update_chunk` role).

    A = einsum('mn,nf,ng->mfg', mask, v, v) — XLA lowers this to one GEMM
    ``mask @ (v ⊗ v)`` of shape (m, n)×(n, f²); b = r @ v is a second GEMM.
    Batched Cholesky solve finishes the normal equations.
    """
    counts = jnp.sum(mask, axis=1)
    b = r @ v                                            # (m, f)
    vv = (v[:, :, None] * v[:, None, :]).reshape(v.shape[0], n_f * n_f)
    a = (mask @ vv).reshape(-1, n_f, n_f)
    reg = lambda_ * jnp.maximum(counts, 1.0)
    a = a + reg[:, None, None] * jnp.eye(n_f, dtype=r.dtype)
    chol = jax.scipy.linalg.cho_factor(a)
    return jax.scipy.linalg.cho_solve(chol, b[..., None])[..., 0]


# init_state (the resumed/chunked factor carries) is DONATED: XLA aliases
# u0/v0 to the output factors and reuses their HBM in place instead of
# double-buffering the two largest arrays of the fit (round-7 perf PR).
# Callers never reuse a passed init_state afterwards.
@partial(_pjit, static_argnames=("shape", "n_f", "max_iter"),
         donate_argnames=("init_state",), name="als_fit")
@precise
def _als_fit(rp, test_p, shape, n_f, lambda_, tol, max_iter, seed,
             init_state=None):
    rp = lax.with_sharding_constraint(rp, _mesh.data_sharding())
    mask = (rp != 0).astype(rp.dtype)
    tmask = (test_p != 0).astype(rp.dtype)
    key = jax.random.PRNGKey(seed)
    ku, kv = jax.random.split(key)
    # reference seeds item factors from the per-item mean rating; uniform
    # init scaled to the mean magnitude behaves equivalently
    u0 = jax.random.uniform(ku, (rp.shape[0], n_f), rp.dtype)
    v0 = jax.random.uniform(kv, (rp.shape[1], n_f), rp.dtype)
    prev0 = jnp.asarray(jnp.inf, rp.dtype)
    if init_state is not None:                 # mid-fit checkpoint resume
        u0, v0, prev0 = init_state
        prev0 = jnp.asarray(prev0, rp.dtype)

    def rmse(u, v):
        se = ((u @ v.T - test_p) * tmask) ** 2
        return jnp.sqrt(jnp.sum(se) / jnp.maximum(jnp.sum(tmask), 1.0))

    def step(carry):
        u, v, prev_rmse, it, _, hist = carry
        u = _solve_factors(rp, mask, v, lambda_, n_f)
        v = _solve_factors(rp.T, mask.T, u, lambda_, n_f)
        cur = rmse(u, v)
        conv = jnp.abs(prev_rmse - cur) < tol
        return u, v, cur, it + 1, conv, hist.at[it].set(cur)

    def cond(carry):
        _, _, _, it, conv, _ = carry
        return (it < max_iter) & (~conv)

    init = (u0, v0, prev0, jnp.int32(0), jnp.asarray(False),
            jnp.zeros((max_iter,), rp.dtype))
    u, v, cur, n_iter, conv, hist = lax.while_loop(cond, step, init)
    # fused health vector — same program, zero extra dispatches
    from dislib_tpu.runtime import health as _health
    hvec = _health.health_vec(carries=(u, v), hist=hist, n_done=n_iter)
    return u, v, cur, n_iter, conv, hist, hvec


@partial(_pjit, static_argnames=("m", "n", "n_f", "max_iter", "mesh"),
         donate_argnames=("init_state",), name="als_fit_sparse")
@precise
def _als_fit_sparse(data, lrows, cols, counts, tdata, tlrows, tcols, tcounts,
                    m, n, n_f, lambda_, tol, max_iter, seed, mesh,
                    init_state=None):
    """Sharded sparse ALS: ONE jitted ``shard_map`` over the row-sharded
    :class:`~dislib_tpu.data.sparse.ShardedSparse` ratings buffers, the
    whole while_loop inside (round-14 sparse PR — the fit rides the same
    machinery as the SpMM fast path instead of the old replicated
    single-program kernel).

    DrJAX's per-shard-update + cross-shard-reduce decomposition
    (arXiv:2403.07128), literally: the USER half-step is fully
    shard-local (each shard owns its users' entries, so their normal
    equations — segment-sums of v_j v_jᵀ outer products streamed over nse
    chunks, O(chunk·f²) peak — never leave the shard; U stays row-sharded
    for the whole fit), and the ITEM half-step is a shard-local partial
    A_i/b_i plus ONE ``psum`` over the rows axis (V is the replicated
    small factor).  The convergence RMSE reduces the same way.  Per-shard
    memory is O(nnz/p · f) + O(n·f²) — the factors of the paper-scale
    recommender shard with the data.

    Entry weights are ``(slot < count) & (value != 0)``: 0 = unobserved
    (the dense-with-mask semantics) AND the nse pads — even poisoned
    ones — carry weight zero (the slot mask, defense in depth over the
    zero-value sentinel-column pad discipline)."""
    p = mesh.shape[_mesh.ROWS]
    from dislib_tpu.data.sparse import _padded_rows
    m_local = _padded_rows(m, mesh) // p
    nse = data.shape[1]
    nse_t = tdata.shape[1]
    chunk = max(1, min(nse, _SPARSE_CHUNK, _SPARSE_BUDGET // (n_f * n_f)))
    n_chunks = -(-nse // chunk)
    pad = n_chunks * chunk - nse

    def shard_fn(d_s, lr_s, cc_s, cnt_s, td_s, tlr_s, tcc_s, tcnt_s, u0_s,
                 v0_r, prev_r):
        d_e, lr, cc, cnt = d_s[0], lr_s[0], cc_s[0], cnt_s[0]
        td, tlr, tcc, tcnt = td_s[0], tlr_s[0], tcc_s[0], tcnt_s[0]
        slot_ok = lax.broadcasted_iota(jnp.int32, (nse,), 0) < cnt
        w = (slot_ok & (d_e != 0)).astype(d_e.dtype)
        # chunk-pad the entry stream (pads carry weight 0 → additive no-op)
        d_p = jnp.pad(d_e * w, (0, pad))
        lr_p = jnp.pad(lr, (0, pad))
        cc_p = jnp.pad(cc, (0, pad))
        w_p = jnp.pad(w, (0, pad))
        tok = lax.broadcasted_iota(jnp.int32, (nse_t,), 0) < tcnt
        tw = (tok & (td != 0)).astype(d_e.dtype)
        eye = jnp.eye(n_f, dtype=d_e.dtype)

        def solve(seg_c, other, idx_c, nseg, reduce_rows):
            """Normal equations streamed over nse chunks; the item step
            (``reduce_rows``) combines per-shard partials with one psum."""

            def body(acc, cx):
                sc, ic, vc, wc = cx
                g = other[ic] * wc[:, None]           # pad rows → all-zero
                b = jax.ops.segment_sum(vc[:, None] * g, sc,
                                        num_segments=nseg)
                outer = (g[:, :, None] * g[:, None, :]) \
                    .reshape(chunk, n_f * n_f)
                a = jax.ops.segment_sum(outer, sc, num_segments=nseg)
                cnt_ = jax.ops.segment_sum(wc, sc, num_segments=nseg)
                return (acc[0] + a, acc[1] + b, acc[2] + cnt_), None

            # both half-steps add shard-local terms (the psum comes after
            # the scan), so the carry is rows-varying from the first chunk:
            # seed it that way or the scan's carry type changes mid-loop
            acc0 = tuple(
                lax.pcast(jnp.zeros(s, d_e.dtype), (_mesh.ROWS,),
                          to="varying")
                for s in ((nseg, n_f * n_f), (nseg, n_f), (nseg,)))
            (a, b, cnts), _ = lax.scan(
                body, acc0,
                (seg_c.reshape(n_chunks, chunk),
                 idx_c.reshape(n_chunks, chunk),
                 d_p.reshape(n_chunks, chunk),
                 w_p.reshape(n_chunks, chunk)))
            if reduce_rows:               # cross-shard reduce: the ONE psum
                a = lax.psum(a, _mesh.ROWS)
                b = lax.psum(b, _mesh.ROWS)
                cnts = lax.psum(cnts, _mesh.ROWS)
            a = a.reshape(nseg, n_f, n_f)
            # unobserved rows: A = λ·I, b = 0 → zero factors (harmless)
            reg = lambda_ * jnp.maximum(cnts, 1.0)
            a = a + reg[:, None, None] * eye
            chol = jax.scipy.linalg.cho_factor(a)
            return jax.scipy.linalg.cho_solve(chol, b[..., None])[..., 0]

        def rmse(u, v):
            pred = jnp.sum(u[tlr] * v[tcc], axis=1)
            se = lax.psum(jnp.sum(tw * (pred - td) ** 2), _mesh.ROWS)
            cnt_t = lax.psum(jnp.sum(tw), _mesh.ROWS)
            return jnp.sqrt(se / jnp.maximum(cnt_t, 1.0))

        def step(carry):
            u, v, prev_rmse, it, _, hist = carry
            u = solve(lr_p, v, cc_p, m_local, False)   # users: shard-local
            v = solve(cc_p, u, lr_p, n, True)          # items: psum-reduced
            cur = rmse(u, v)
            conv = jnp.abs(prev_rmse - cur) < tol
            return u, v, cur, it + 1, conv, hist.at[it].set(cur)

        def cond(carry):
            _, _, _, it, conv, _ = carry
            return (it < max_iter) & (~conv)

        if u0_s is None:
            key = jax.random.PRNGKey(seed)
            ku, kv = jax.random.split(key)
            ku = jax.random.fold_in(ku, lax.axis_index(_mesh.ROWS))
            u0 = jax.random.uniform(ku, (m_local, n_f), d_e.dtype)
            v0 = jax.random.uniform(kv, (n, n_f), d_e.dtype)
            prev0 = jnp.asarray(jnp.inf, d_e.dtype)
        else:
            u0 = u0_s
            v0 = v0_r
            prev0 = jnp.asarray(prev_r, d_e.dtype)
        # vma: a fresh u0 is rows-varying via the fold_in of axis_index;
        # v0/prev0 are replicated (same key / same scalar on every rank)
        init = (u0, v0, prev0, jnp.int32(0), jnp.asarray(False),
                jnp.zeros((max_iter,), d_e.dtype))
        u, v, cur, n_iter, conv, hist = lax.while_loop(cond, step, init)
        return u, v, cur, n_iter, conv, hist

    from jax.sharding import PartitionSpec as P
    row_spec = (P(_mesh.ROWS),) * 4
    if init_state is None:
        extra_specs = ()
        args = ()
    else:
        u0, v0, prev0 = init_state
        extra_specs = (P(_mesh.ROWS), P(), P())
        args = (u0, v0, jnp.asarray(prev0))

    def wrapper(*ops):
        if init_state is None:
            return shard_fn(*ops, None, None, None)
        return shard_fn(*ops)

    u, v, cur, n_iter, conv, hist = jax.shard_map(
        wrapper, mesh=mesh,
        in_specs=row_spec + row_spec + extra_specs,
        out_specs=(P(_mesh.ROWS), P(), P(), P(), P(), P()),
        check_vma=True,
    )(data, lrows, cols, counts, tdata, tlrows, tcols, tcounts, *args)
    # fused health vector — same program, zero extra dispatches
    from dislib_tpu.runtime import health as _health
    hvec = _health.health_vec(carries=(u, v), hist=hist, n_done=n_iter)
    return u, v, cur, n_iter, conv, hist, hvec


# nnz chunk cap for the streamed normal-equation sums, and the element
# budget for the (chunk, f²) intermediate (chunk·f² ≤ _SPARSE_BUDGET)
_SPARSE_CHUNK = 1 << 18
_SPARSE_BUDGET = 1 << 22


def _fold_in_body(vals, cols, items, lambda_, n_f, policy, top_n=0):
    """The fold-in math: per-user regularized normal equations against
    the frozen item factors, then one predict GEMM — entirely traced, so
    the serving pipeline's packed variant fuses it into the same single
    dispatch.  (value != 0) doubles as the observation mask AND the pad
    mask (pads are value-0 at the sentinel column).

    ``top_n`` > 0 ranks in the SAME program: ``lax.top_k`` fuses after
    the predict GEMM, so a recommend-top-N serve stays one dispatch and
    fetches (k, top_n) instead of the full (k, n_items) score matrix."""
    from dislib_tpu.ops import precision as px
    # weight = observed AND in-range: an out-of-range id (corrupt
    # request past the pack-time validation) becomes a no-op instead of
    # silently scoring against the clipped last item — the slot-mask
    # defense-in-depth discipline at the serving boundary
    in_range = (cols >= 0) & (cols < items.shape[0])
    w = ((vals != 0) & in_range).astype(items.dtype)
    g = items[jnp.clip(cols, 0, items.shape[0] - 1)] * w[..., None]
    a = px.peinsum("ksf,ksg->kfg", g, g, policy)           # (k, f, f)
    cnt = jnp.sum(w, axis=1)
    reg = lambda_ * jnp.maximum(cnt, 1.0)
    a = a + reg[:, None, None] * jnp.eye(n_f, dtype=a.dtype)
    b = px.peinsum("ks,ksf->kf", vals.astype(items.dtype) * w, g, policy)
    chol = jax.scipy.linalg.cho_factor(a)
    factors = jax.scipy.linalg.cho_solve(chol, b[..., None])[..., 0]
    preds = px.pdot(factors, items.T, policy)              # (k, n_items)
    if top_n:
        scores, ids = lax.top_k(preds, int(top_n))
        return factors, (ids.astype(jnp.int32), scores)
    return factors, preds


# lambda_ is STATIC: it is per-model configuration (one retrace per
# fitted model), and a dynamic scalar operand would cost one
# host->device scalar transfer per served batch — the zero-transfer
# serving boundary is counter-asserted in tests/test_spmm.py
@partial(_pjit, static_argnames=("lambda_", "n_f", "policy", "top_n"),
         name="als_fold_in")
@precise
def _als_fold_in(vals, cols, items, lambda_, n_f, policy, top_n=0):
    return _fold_in_body(vals, cols, items, lambda_, n_f, policy,
                         top_n=top_n)


@partial(_pjit, static_argnames=("lambda_", "n_f", "policy", "top_n"),
         name="als_fold_in_packed")
@precise
def _als_fold_in_packed(buf, items, lambda_, n_f, policy, top_n=0):
    """Serving entry: one PACKED sparse batch — each request row is
    ``[cols | vals]`` (2·s floats, pads (0, 0)) — split and cast ON
    DEVICE so a served batch stays ONE fused dispatch.  Column ids ride
    float32 exactly below 2^24; the pipeline validates the item count."""
    s = buf.shape[1] // 2
    cols = buf[:, :s].astype(jnp.int32)
    vals = buf[:, s:]
    return _fold_in_body(vals, cols, items, lambda_, n_f, policy,
                         top_n=top_n)
