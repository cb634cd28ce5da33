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
  users and the items are sorted into length classes, each order of the
  entries is laid out once per array in windows of its class's length
  (`_plans`, cached on the ShardedSparse), and a block of segments' Grams
  and moments are ONE batched product over its windows; the items rated
  by at least ``_DENSE_SHARE`` of the users are in no window, their Grams
  ONE GEMM a piece of users of their 0/1 rating pattern against the
  users' packed outer products (``_dense_grams``).  Users are solved
  a block at a time (on a TPU a system a lane in VMEM,
  `pallas_kernels.chol_solve_lanes`); the items' products are summed
  over the shards and solved once whole.  O(nnz·f²) work, O(nnz) memory
  for the two layouts beside the ratings, and a block's temporaries:
  nothing of size (users, f, f) exists, no densification.
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
from dislib_tpu.ops import base as _ops
from dislib_tpu.ops import precision as px
from dislib_tpu.ops.base import precise
from dislib_tpu.runtime import fetch as _fetch, repad_rows as _repad_rows
from dislib_tpu.runtime import fitloop as _fitloop
from dislib_tpu.runtime import health as _health
from dislib_tpu.utils.dlog import verbose_logger
from dislib_tpu.utils.profiling import count_schedule as _count_schedule
from dislib_tpu.utils.profiling import new_call as _new_call, span as _span
from dislib_tpu.utils.profiling import profiled_jit as _pjit

_FIT = "dslib.als.fit"


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
        Seeds the uniform draw of the item factors the first half-step
        solves the users against, where ``items_init`` is None.
    items_init : array (n_items, n_f) or None, default None
        The item factors the first half-step solves the users against (the
        fit's start; Zhou et al. start from each item's mean rating in
        column 0 and small random numbers elsewhere).  None: a uniform
        draw from ``random_state``.
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
                 random_state=None, verbose=False, arity=48,
                 items_init=None):
        self.n_f = n_f
        self.lambda_ = lambda_
        self.tol = tol
        self.max_iter = max_iter
        self.random_state = random_state
        self.verbose = verbose
        self.arity = arity
        self.items_init = items_init

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
        with _span(_FIT, call=_new_call()):
            if self.max_iter < 1:
                raise ValueError("max_iter must be >= 1")
            from dislib_tpu.data.sparse import SparseArray
            sparse_in = isinstance(x, SparseArray)
            items0 = self._items_start(x.shape[1])
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
                # ALS damping: the 'halve' tier raises the per-row ridge
                # λ·n_u per attempt (ill-conditioned normal equations are
                # the numeric failure mode of the batched Cholesky solves)
                box["lam"] = float(self.lambda_) * rem.damping
                box["rmse"] = np.inf
                if sparse_in:
                    from dislib_tpu.data.sparse import _padded_rows
                    mesh = _mesh.get_mesh()
                    return _fitloop.LoopState(_als_start(
                        items0, int(seed), _padded_rows(x.shape[0], mesh),
                        x.shape[1], int(self.n_f), mesh), extra=np.inf)
                if items0 is not None:
                    return _fitloop.LoopState(
                        _dense_start(box["x"]._data, items0, int(self.n_f)),
                        extra=np.inf)
                # fresh: the kernel seeds itself
                return _fitloop.LoopState(())

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
                    (jnp.asarray(rem.perturb(
                        _repad_rows(snap["users"], sm, tu))),
                     jnp.asarray(rem.perturb(
                         _repad_rows(snap["items"], sn, tv)))),
                    it=int(snap["n_iter"]),
                    done=bool(snap.get("converged", False)),
                    extra=float(snap["rmse"]))

            def step(st, chunk):
                state = (*st.carries, st.extra) if st.carries else None
                if sparse_in:
                    u, v, rmse_dev, n_done, conv, hist, hvec = _sparse_fit(
                        box["rep"], box["trep"], state, box["lam"],
                        float(self.tol), chunk)
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
                        "rmse": st.extra, "n_iter": st.it,
                        "converged": st.done}

            st = loop.run(init=init, step=step, restore=restore,
                          snapshot=snapshot)
            u, v = st.carries
            m, n = x.shape
            self.users_ = np.asarray(_fetch(u))[:m]
            self.items_ = np.asarray(_fetch(v))[:n]
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
            from dislib_tpu.data.sparse import _padded_rows
            rep, mesh = x.sharded(), _mesh.get_mesh()
            start = _als_start(self._items_start(x.shape[1]), int(seed),
                               _padded_rows(x.shape[0], mesh), x.shape[1],
                               int(self.n_f), mesh)
            out = _sparse_fit(rep, rep, (*start, np.inf),
                              float(self.lambda_), float(self.tol),
                              self.max_iter)
        else:
            items0 = self._items_start(x.shape[1])
            start = None if items0 is None else (
                *_dense_start(x._data, items0, int(self.n_f)), np.inf)
            out = _als_fit(x._data, x._data, x.shape, int(self.n_f),
                           float(self.lambda_), float(self.tol),
                           self.max_iter, int(seed), init_state=start)
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

    def _items_start(self, n_items):
        """``items_init`` checked against the ratings' items, as a float32
        device array; None where there is none."""
        if self.items_init is None:
            return None
        v0 = np.asarray(self.items_init, np.float32)
        if v0.shape != (n_items, int(self.n_f)):
            raise ValueError(f"items_init has shape {v0.shape}, the fit "
                             f"needs ({n_items}, {int(self.n_f)})")
        return jnp.asarray(v0)


def _sparse_fit(rep, trep, state, lambda_, tol, max_iter):
    """One chunk of the sparse fit on the sharded ratings ``rep`` from
    the carries ``state`` (U, V, previous RMSE); the RMSE over ``trep``'s
    entries where it is another array (held-out ratings), else over the
    training ratings.  Both layouts come from ``rep``'s cache (built at
    the first fit of the array)."""
    uc, ic, users, items = _plans(rep)
    test = (trep.data, trep.lrows, trep.cols, trep.counts_dev)
    # the iterations are an operand and the history's room a power of two
    # of at least 4, so that fits of 1 to 4 iterations share one program
    room = max(4, 1 << (int(max_iter) - 1).bit_length())
    return _als_fit_sparse(users, items, test, state, rep.shape[1], lambda_,
                           tol, int(max_iter), room, rep.mesh, uc, ic,
                           trep is not rep)


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


def _dense_start(rp, items, n_f):
    """``(U, V)`` a dense fit starts from given ``items``: U zero (the first
    half-step solves it before it is read), V padded like ``rp``'s
    columns."""
    return (jnp.zeros((rp.shape[0], n_f), jnp.float32),
            jnp.pad(items, ((0, rp.shape[1] - items.shape[0]), (0, 0))))


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


# -- the sparse fit: normal equations a block of segments at a time ---------
# A segment is one user's run of the row-sorted entry stream, or one item's
# run of the same entries in column order (ShardedSparse.col_major), on one
# shard.  Segments go into length classes (_class_sizes), and each order
# is laid out once per array (_plans, cached on the ShardedSparse): a
# class's segments side by side, each in a window of S slots, its entries
# first and pads after them.  A pad names the factor table's one row past
# its end, a zero row appended at each half-step, so a block of B
# segments of a class is one contiguous (B, S) slice of that layout, and
# ONE batched product of [factor rows, rating] with itself gives every
# segment's Gram A and moment b at once; the layout keeps each segment's
# number of observed entries beside its id.  Users are solved a block at
# a time into U: nothing of size (users, f, f) exists.  An item's product
# is written into the (items, f + 1, f + 1) table, summed over the shards
# by ONE psum, and solved once it is whole.
_PIECE = 2048            # most entries one product contracts (PERF.md, s. 6)
_BLOCK_ENTRIES = 1 << 18  # slots of a block
_BLOCK_SEGMENTS = 2048   # most segments a block solves
_RMSE_BLOCK = 1 << 19    # entries a block of a held-out RMSE pass reads
# An item rated by at least this share of the users leaves the windows:
# its normal equations come from a dense column of its ratings (0 where
# unrated) against U's rows as they lie (_dense_grams).  Cost: a gathered
# slot costs about 13.5 ns on a v5e, a (user, item) pair of the dense
# GEMM 0.2-0.3 ns, so dense wins from a density of about 1/50.  Memory:
# the column takes 4 bytes a user, a window about 9.6 bytes a rating (an
# id and a rating a slot, a fifth of them pads); at 1/8 the 355 densest
# items of the Netflix-shaped cell take 2.05 GB for the 1.24 GB of
# windows they leave (PERF.md, s. 6).  A lower share pays more memory
# than it saves time.
_DENSE_SHARE = 1 / 8
# where a block's systems are solved a system a lane in VMEM
# (pallas_kernels.chol_solve_lanes); XLA's batched Cholesky elsewhere
_LANE_BACKENDS = ("tpu",)


def _class_sizes(longest: int) -> tuple:
    """Window lengths S of the length classes, up to the first that holds
    ``longest`` entries: fine steps while a window is short (from 256 on
    a segment wastes at most a third of its window), then whole products
    of ``_PIECE`` entries, 2^j and 3·2^(j-1) of them."""
    sizes = [16, 32, 64, 128, 256, 384, 512, 768, 1024, 1536, _PIECE]
    j = 1
    while sizes[-1] < longest:
        sizes += [_PIECE << j, _PIECE * 3 << (j - 1)]
        j += 1
    return tuple(sizes)


def _segment_plan(counts, skip=None):
    """``(classes, tables)``: the blocking of one order of the entries,
    from the per-shard segment lengths ``counts`` (host (p, segments)).
    ``classes`` is static, ``((S, B, blocks, seg_off), ...)`` for each
    class that holds a segment on some shard; ``tables`` (p, 3, L) int32
    hold, a class's ``blocks * B`` columns from ``seg_off`` on, each
    segment's id, first slot in the stream and length, in id order
    (padding columns: id = segments, so that their writes drop, length
    0).  Every shard gets the same shapes: a class's blocks are the most
    any shard needs.  The segments where the boolean (segments,) ``skip``
    is set count as empty: they are in no class, and the others keep
    their first slots in the stream."""
    counts = np.asarray(counts, np.int64)
    p, nseg = counts.shape
    first = np.cumsum(counts, axis=1) - counts
    if skip is not None:
        counts = np.where(skip, 0, counts)
    sizes = _class_sizes(int(counts.max(initial=1)))
    cls = np.searchsorted(sizes, counts)
    classes, parts, seg_off = [], [], 0
    for c, size in enumerate(sizes):
        mine = [np.flatnonzero((cls[s] == c) & (counts[s] > 0))
                for s in range(p)]
        most = max(len(ids) for ids in mine)
        if not most:
            continue
        b = min(_BLOCK_SEGMENTS, max(1, _BLOCK_ENTRIES // size), most)
        blocks = -(-most // b)
        tab = np.zeros((p, 3, blocks * b), np.int64)
        tab[:, 0] = nseg
        for s, ids in enumerate(mine):
            tab[s, :, :len(ids)] = ids, first[s, ids], counts[s, ids]
        classes.append((int(size), int(b), int(blocks), seg_off))
        parts.append(tab)
        seg_off += blocks * b
    if not parts:
        parts = [np.zeros((p, 3, 1), np.int64)]
    return tuple(classes), np.concatenate(parts, axis=2).astype(np.int32)


def _plans(rep):
    """``(user_classes, item_classes, user_layout, item_layout)`` of the
    sharded ratings ``rep``, built on the device at the first fit of the
    array and cached with it.  A layout is ``(ids, observed, others,
    vals)``: the segments' ids and numbers of observed entries a class
    block at a time (1-D, a shard's part after another's), and for each
    class the (blocks * B, S) windows of the other side's ids (items for
    the users, local rows for the items; a pad names the row past the
    table) and of the ratings, a shard's rows after another's.  Built from
    the order's tables, which are then dropped, as is ``col_major()``:
    what stays is the two windowed copies of the entries.

    The items rated by at least ``_DENSE_SHARE`` of the users (summed over
    the shards, so every shard has the same set) are in no window: the
    item layout ends in their dense part (``_dense_layout``), None where
    no item reaches the share."""
    if "als" not in rep.plans:
        sh = jax.sharding.NamedSharding(rep.mesh,
                                        jax.sharding.PartitionSpec(_mesh.ROWS))
        users = rep.row_nnz
        users = np.concatenate(
            [users, np.zeros(rep.p * rep.m_local - users.shape[0], np.int64)])
        per_item = rep.col_counts()
        heavy = per_item.sum(axis=0) >= _DENSE_SHARE * rep.shape[0]
        ic, it = _segment_plan(per_item, skip=heavy)
        rows_t, vals_t = rep.col_major()
        items = _layout(rows_t, vals_t, jax.device_put(it, sh), ic, rep.mesh,
                        jnp.int32, rep.m_local)
        del rows_t, vals_t
        dense = None
        if heavy.any():
            ids = np.flatnonzero(heavy).astype(np.int32)
            at = np.full(rep.shape[1], ids.size, np.int32)
            at[ids] = np.arange(ids.size)
            dense = (jax.device_put(np.tile(ids, (rep.p, 1)), sh),
                     *_dense_layout(rep.data, rep.lrows, rep.cols,
                                    rep.counts_dev, jnp.asarray(at),
                                    rep.mesh, rep.m_local, int(ids.size)))
        items = (*items, dense)
        uc, ut = _segment_plan(users.reshape(rep.p, rep.m_local))
        # item ids as int16 where they and the pad fit: the largest copy
        # shrinks by a quarter
        small = rep.shape[1] < np.iinfo(np.int16).max
        users = _layout(rep.cols, rep.data, jax.device_put(ut, sh), uc,
                        rep.mesh, jnp.int16 if small else jnp.int32,
                        rep.shape[1])
        rep.plans["als"] = (uc, ic, users, items)
    return rep.plans["als"]


@partial(_pjit, static_argnames=("classes", "mesh", "ids", "pad"),
         name="als_layout")
def _layout(other, vals, tab, classes, mesh, ids, pad):
    """One order's windowed layout (see ``_plans``): a class block's
    windows are ``B`` slices of ``S`` slots of the stream, cut at each
    segment's first slot; a slot past the segment's length, or of a
    rating of 0, holds (``pad``, 0)."""
    from jax.sharding import PartitionSpec as P
    span = max(cls[0] for cls in classes) if classes else 1

    def local(o_s, v_s, t_s):
        with jax.named_scope("dslib.als.layout"):
            o = jnp.concatenate([o_s[0], jnp.zeros((span,), o_s.dtype)])
            v = jnp.concatenate([v_s[0], jnp.zeros((span,), v_s.dtype)])
            t = t_s[0]
            seen = _ops.varying_like(jnp.zeros(t.shape[1:], v.dtype), t)
            others, ratings = [], []
            for size, b, blocks, seg_off in classes:
                def body(i, out, size=size, b=b, seg_off=seg_off):
                    bufs, seen = out
                    _, first, length = lax.dynamic_slice_in_dim(
                        t, seg_off + i * b, b, 1)

                    def win(a):
                        return jax.vmap(lambda s: lax.dynamic_slice(
                            a, (s,), (size,)))(first)
                    r = win(v)
                    keep = (lax.iota(jnp.int32, size)[None, :]
                            < length[:, None]) & (r != 0)
                    got = (jnp.where(keep, win(o), pad),
                           jnp.where(keep, r, 0))
                    seen = lax.dynamic_update_slice_in_dim(
                        seen, jnp.sum(keep, axis=1).astype(seen.dtype),
                        seg_off + i * b, 0)
                    return tuple(
                        lax.dynamic_update_slice_in_dim(
                            buf, blk.astype(buf.dtype), i * b, 0)
                        for buf, blk in zip(bufs, got)), seen
                bufs = tuple(_ops.varying_like(
                    jnp.zeros((blocks * b, size), dt), t)
                    for dt in (ids, v.dtype))
                bufs, seen = lax.fori_loop(0, blocks, body, (bufs, seen))
                others.append(bufs[0])
                ratings.append(bufs[1])
            return t[0], seen, tuple(others), tuple(ratings)

    return jax.shard_map(local, mesh=mesh, in_specs=(P(_mesh.ROWS),) * 3,
                         out_specs=(P(_mesh.ROWS),) * 4,
                         check_vma=True)(other, vals, tab)


@partial(_pjit, static_argnames=("mesh", "m_local", "h"),
         name="als_dense_layout")
def _dense_layout(data, lrows, cols, counts, at, mesh, m_local, h):
    """The dense part of the item layout but the items' ids, ``(observed,
    squares, ratings)`` a shard at a time: per dense item the number of
    its nonzero ratings and their sum of squares, constants of the fit
    that the dense Grams (``_dense_grams``) leave to the plan, and the
    (h, m_local) ratings of the ``h`` dense items by the shard's users (0
    where unrated; ``at`` (items,) is an item's row there, h for the
    others), an item's users along the minor axis as the GEMM reads
    them."""
    from jax.sharding import PartitionSpec as P

    def local(d_s, lr_s, cc_s, cnt_s):
        d, lr, cc, cnt = d_s[0], lr_s[0], cc_s[0], cnt_s[0]
        with jax.named_scope("dslib.als.layout"):
            row = at[cc]
            keep = (lax.iota(jnp.int32, d.shape[0]) < cnt) & (row < h)
            flat = jnp.where(keep, row * m_local + lr, h * m_local)
            r = _ops.varying_like(jnp.zeros((h * m_local,), d.dtype), d)
            r = r.at[flat].set(d, mode="drop").reshape(h, m_local)
            seen = jnp.sum(r != 0, axis=1).astype(d.dtype)
            sq = _piece_sums(m_local, lambda start, piece, fresh: jnp.sum(
                jnp.where(fresh, lax.dynamic_slice_in_dim(
                    r, start, piece, axis=1), 0) ** 2, axis=1),
                _ops.varying_like(jnp.zeros((h,), d.dtype), d))
        return seen[None], sq[None], r

    return jax.shard_map(local, mesh=mesh, in_specs=(P(_mesh.ROWS),) * 4,
                         out_specs=(P(_mesh.ROWS),) * 3,
                         check_vma=True)(data, lrows, cols, counts)


def _piece_sums(n, body, zero):
    """The sum over the pieces of ``_PIECE`` of ``n`` users of ``body(start,
    piece, fresh)``, a pytree shaped like ``zero``: a ragged last piece
    starts early, so that it ends with the users, and ``fresh`` (piece,)
    marks the users no earlier piece has had.  Each piece's sums are
    added after an ``optimization_barrier`` (which keeps XLA from folding
    the pieces back into one product) in compensated sums, as
    ``_block_grams`` adds its pieces: a float32 product that contracts
    many same-signed terms reads low on the chip."""
    piece = min(n, _PIECE)

    def one(i, carry):
        total, lost = carry
        start = jnp.minimum(i * piece, n - piece)
        fresh = start + lax.iota(jnp.int32, piece) >= i * piece
        part = jax.tree.map(jnp.subtract, lax.optimization_barrier(
            body(start, piece, fresh)), lost)
        grown = jax.tree.map(jnp.add, total, part)
        return grown, jax.tree.map(lambda g, t, p: (g - t) - p,
                                   grown, total, part)

    total, lost = lax.fori_loop(0, -(-n // piece), one, (zero, zero))
    return jax.tree.map(jnp.subtract, total, lost)


def _packed_outer(u):
    """(rows, t): each row's outer product with itself, one triangle of
    it and a little more (t and where each entry lies: ``_packed_index``),
    a band of 8 columns at a time: the band's products with the columns
    from its first on, ``u[:, a] * u[:, c]`` for a >= 8 i, c in [8 i,
    8 i + 8).  A band's (rows, f - 8 i, 8) lies, with the rows on the
    lanes as the chip holds them, a whole sublane tile to each of its
    columns, so it flattens to (rows, 8 (f - 8 i)) as it lies, with no
    gather and no relayout (5 392 columns for the triangle's 5 050 at
    f = 100)."""
    n_f = u.shape[1]
    return jnp.concatenate(
        [(u[:, c:, None] * u[:, None, c:c + 8]).reshape(u.shape[0], -1)
         for c in range(0, n_f, 8)], axis=1)


def _packed_index(n_f):
    """(f, f) int32: where ``_packed_outer`` keeps each entry of the outer
    product, (a, c) and (c, a) at the same column."""
    out = np.zeros((n_f, n_f), np.int32)
    at = 0
    for c in range(0, n_f, 8):
        w = min(8, n_f - c)
        rows = np.arange(c, n_f)[:, None]
        cols = np.arange(c, c + w)[None, :]
        here = at + (rows - c) * w + (cols - c)
        keep = rows >= cols
        out[np.broadcast_to(rows, here.shape)[keep],
            np.broadcast_to(cols, here.shape)[keep]] = here[keep]
        at += (n_f - c) * w
    return np.maximum(out, out.T), at


def _dense_grams(dense, u):
    """``(ids, n, g)`` of the dense items on this shard: their ids, numbers
    of observed entries and (h, f + 1, f + 1) products of [U's rows,
    rating] with themselves over the shard's users, from the dense part of
    the item layout.  A is one GEMM of the items' 0/1 rating pattern
    against the users' packed outer products (``precision.pdot_pattern``:
    a 0/1 is exact in bfloat16, so three bfloat16 products carry every
    term of the six-pass one), b a 'highest' product of the ratings with
    U, Σr² and n the plan's.  Users are taken ``_PIECE`` at a time
    (``_piece_sums``); a piece's rating pattern is derived from its own
    slice of the ratings, and its outer products exist for that piece
    alone."""
    ids, seen, sq, r = dense
    h, n_f = r.shape[0], u.shape[1]

    def body(start, piece, fresh):
        ub = lax.dynamic_slice_in_dim(u, start, piece)
        rb = lax.optimization_barrier(
            lax.dynamic_slice_in_dim(r, start, piece, axis=1))
        rb = jnp.where(fresh, rb, 0)
        return (px.pdot_pattern(rb, _packed_outer(ub)),
                px.peinsum("hp,pf->hf", rb, ub, px.FLOAT32))

    where, size = _packed_index(n_f)
    a, b = _piece_sums(u.shape[0], body, tuple(
        _ops.varying_like(jnp.zeros(shape, u.dtype), u)
        for shape in ((h, size), (h, n_f))))
    full = jnp.take(a, jnp.asarray(where.ravel()), axis=1)
    g = jnp.concatenate([full.reshape(h, n_f, n_f), b[:, :, None]], axis=2)
    g = jnp.concatenate(
        [g, jnp.concatenate([b, sq[0][:, None]], axis=1)[:, None]], axis=1)
    return ids[0], seen[0], g


def _with_zero_row(factor):
    """``factor`` and one zero row after it: the row a layout's pads
    name."""
    return jnp.concatenate([factor, jnp.zeros((1, factor.shape[1]),
                                              factor.dtype)])


def _block_grams(layout, k, cls, i, factor):
    """``(ids, n, g)``: the ``i``-th block of class ``cls``, the ``k``-th
    of a layout: its segments' ids and numbers of observed entries, and
    the (B, f + 1, f + 1) products of [factor rows, rating] with
    themselves (``factor`` ends in the zero row a pad names).  A window
    longer than ``_PIECE`` is contracted a piece at a time: a float32
    product that contracts many same-signed terms reads low on the chip,
    so the pieces are added after an ``optimization_barrier`` (which keeps
    XLA from folding them back into one product) in compensated sums."""
    size, b, _, seg_off = cls
    ids, seen, others, vals = layout[:4]
    ids = lax.dynamic_slice_in_dim(ids, seg_off + i * b, b)
    n = lax.dynamic_slice_in_dim(seen, seg_off + i * b, b)
    o = lax.dynamic_slice_in_dim(others[k], i * b, b)
    r = lax.dynamic_slice_in_dim(vals[k], i * b, b)
    rows = jnp.take(factor, o, axis=0, mode="clip")
    x = jnp.concatenate([rows, r[..., None].astype(rows.dtype)], axis=-1)
    piece = min(size, _PIECE)
    x = x.reshape(b, size // piece, piece, x.shape[-1])
    g = px.peinsum("bpsf,bpsg->bpfg", x, x, px.FLOAT32)
    if size == piece:
        return ids, n, g[:, 0]
    g = lax.optimization_barrier(g)

    def add(j, carry):
        total, lost = carry
        part = g[:, j] - lost
        grown = total + part
        return grown, (grown - total) - part

    zero = jnp.zeros_like(g[:, 0])
    total, lost = lax.fori_loop(0, size // piece, add, (zero, zero))
    return ids, n, total - lost


def _solve_normal(g, n, lambda_, n_f):
    """Factors from a stack of (f + 1, f + 1) products and the segments'
    numbers of observed entries ``n``: the weighted-λ ridge λ·max(n, 1)
    on A, then a Cholesky solve against b.  A segment with no observation
    gets A = λ·I and b = 0: a zero factor."""
    a = g[:, :n_f, :n_f]
    reg = lambda_ * jnp.maximum(n, 1.0)
    a = a + reg[:, None, None] * jnp.eye(n_f, dtype=g.dtype)
    b = g[:, :n_f, n_f]
    if jax.default_backend() in _LANE_BACKENDS:
        from dislib_tpu.ops.pallas_kernels import chol_solve_lanes
        return chol_solve_lanes(jnp.transpose(a, (1, 2, 0)), b.T).T
    chol = jax.scipy.linalg.cho_factor(a)
    return jax.scipy.linalg.cho_solve(chol, b[..., None])[..., 0]


def _user_step(layout, classes, v, lambda_, u):
    """U from V, a block of users at a time, every shard its own users,
    written over the last U in place: a user with no rating is never
    written and keeps the zero factor every fit starts it with."""
    n_f = v.shape[1]
    vz = _with_zero_row(v)
    with jax.named_scope("dslib.als.users"):
        for k, cls in enumerate(classes):
            def body(i, u, k=k, cls=cls):
                with jax.named_scope("dslib.als.gram"):
                    ids, n, g = _block_grams(layout, k, cls, i, vz)
                with jax.named_scope("dslib.als.solve"):
                    return u.at[ids].set(_solve_normal(g, n, lambda_, n_f),
                                         mode="drop")
            u = lax.fori_loop(0, cls[2], body, u)
    return u


def _item_step(layout, classes, u, lambda_, n, block=_BLOCK_SEGMENTS):
    """``(V, sse, count)`` from U: every item's product and number of
    observed entries on each shard's own entries (the windowed items' a
    class block at a time, the dense items' in one pass over U's rows),
    the shards' partial sums added by ONE psum, then the n solves a block
    of items at a time, each block's squared training error
    (``_train_sse``) summed beside them."""
    n_f = u.shape[1]
    uz = _with_zero_row(u)
    with jax.named_scope("dslib.als.items"):
        acc = tuple(_ops.varying_like(jnp.zeros(shape, u.dtype), layout[0])
                    for shape in ((n, n_f + 1, n_f + 1), (n,)))
        for k, cls in enumerate(classes):
            def body(i, acc, k=k, cls=cls):
                with jax.named_scope("dslib.als.gram"):
                    ids, cnt, g = _block_grams(layout, k, cls, i, uz)
                    return (acc[0].at[ids].set(g, mode="drop"),
                            acc[1].at[ids].set(cnt, mode="drop"))
            acc = lax.fori_loop(0, cls[2], body, acc)
        if layout[4] is not None:
            with jax.named_scope("dslib.als.gram"), \
                    jax.named_scope("dslib.als.dense"):
                ids, cnt, g = _dense_grams(layout[4], u)
                acc = (acc[0].at[ids].set(g, mode="drop"),
                       acc[1].at[ids].set(cnt, mode="drop"))
        acc, seen = lax.psum(acc, _mesh.ROWS)
        b = min(block, n)

        def solve(i, carry):
            v, sse, cnt = carry
            at = jnp.minimum(i * b, n - b)      # a ragged last block ends
            g = lax.dynamic_slice_in_dim(acc, at, b)   # with the items
            nb = lax.dynamic_slice_in_dim(seen, at, b)
            with jax.named_scope("dslib.als.solve"):
                vb = _solve_normal(g, nb, lambda_, n_f)
            fresh = (at + lax.iota(jnp.int32, b)) >= i * b
            e, c = _train_sse(vb, g, nb, fresh)
            return (lax.dynamic_update_slice_in_dim(v, vb, at, 0),
                    sse + e, cnt + c)

        zero = jnp.zeros((), u.dtype)
        return lax.fori_loop(0, -(-n // b), solve,
                             (jnp.zeros((n, n_f), u.dtype), zero, zero))


def _train_sse(v, g, n, fresh):
    """Squared training error and count of a block of items from their
    products: an item's sum of squared errors is Σr² - 2 v·b + vᵀA v over
    its own entries, the numbers of its product with U; no entry is read
    again.  ``fresh`` masks the items an earlier block has counted."""
    n_f = v.shape[1]
    with jax.named_scope("dslib.als.rmse"):
        av = px.peinsum("nfg,ng->nf", g[:, :n_f, :n_f], v, px.FLOAT32)
        se = g[:, n_f, n_f] + jnp.sum(v * (av - 2.0 * g[:, :n_f, n_f]),
                                      axis=1)
        w = fresh.astype(v.dtype)
        return jnp.sum(w * se), jnp.sum(w * n)


def _heldout_rmse(u, v, td, tlr, tcc, tcnt):
    """RMSE over a shard's observed entries of held-out ratings, in blocks
    whose partial sums add up compensated, then ONE psum."""
    with jax.named_scope("dslib.als.rmse"):
        def body(d, w, _start, lr, cc):
            ok = w * (d != 0).astype(d.dtype)
            pred = jnp.sum(jnp.take(u, lr, axis=0, mode="clip")
                           * jnp.take(v, cc, axis=0, mode="clip"), axis=1)
            return jnp.sum(ok * (pred - d) ** 2), jnp.sum(ok)

        zero = (jnp.zeros((), td.dtype),) * 2
        se, cnt = _ops.local_row_sums(td, (tlr, tcc), 0, tcnt,
                                      min(td.shape[0], _RMSE_BLOCK), body,
                                      zero)
        se = lax.psum(se, _mesh.ROWS)
        cnt = lax.psum(cnt, _mesh.ROWS)
        return jnp.sqrt(se / jnp.maximum(cnt, 1.0))


# init_state is DONATED (as in _als_fit): U's and V's buffers are reused
# in place by the fit's output
@partial(_pjit, static_argnames=("n", "room", "mesh", "user_classes",
                                 "item_classes", "heldout"),
         donate_argnames=("init_state",), name="als_fit_sparse")
@precise
def _als_fit_sparse(user_layout, item_layout, test, init_state, n, lambda_,
                    tol, max_iter, room, mesh, user_classes, item_classes,
                    heldout):
    """Sharded sparse ALS: ONE jitted ``shard_map`` over the row-sharded
    ratings' two windowed layouts (``_plans``), the whole while_loop
    inside.  DrJAX's per-shard-update + cross-shard-reduce decomposition
    (arXiv:2403.07128): the USER half-step is shard-local (each shard
    owns its users' entries; U stays row-sharded), and the ITEM half-step
    is a shard-local partial product an item plus ONE ``psum`` over the
    rows axis (V is the replicated small factor); so is the RMSE.

    A shard holds, beyond its entries in both layouts, U's (m/p, f) rows
    and a copy of them with the pads' zero row, V and the items' (n,
    f + 1, f + 1) products, and a block's temporaries:
    ``_BLOCK_ENTRIES`` gathered factor rows and at most
    ``_BLOCK_SEGMENTS`` (f, f) systems.  The convergence RMSE is over the
    training ratings (from the items' products) or, where ``heldout``,
    over ``test``'s observed entries (data, local rows, columns, live
    count of a ShardedSparse; slots past the count are masked, so a
    poisoned pad cannot enter)."""
    _count_schedule("als_normal", "grouped")
    _count_schedule("als_items", "gathered" if item_layout[4] is None
                    else "dense")
    _count_schedule("als_solve", "lanes" if jax.default_backend()
                    in _LANE_BACKENDS else "xla")

    def shard_fn(ulay, ilay, test, u0, v0, prev0):
        td, tlr, tcc, tcnt = test

        def step(carry):
            u, v, prev_rmse, it, _, hist = carry
            u = _user_step(ulay, user_classes, v, lambda_, u)
            v, sse, cnt = _item_step(ilay, item_classes, u, lambda_, n)
            if heldout:
                cur = _heldout_rmse(u, v, td[0], tlr[0], tcc[0], tcnt[0])
            else:
                with jax.named_scope("dslib.als.rmse"):
                    cur = jnp.sqrt(sse / jnp.maximum(cnt, 1.0))
            conv = jnp.abs(prev_rmse - cur) < tol
            return u, v, cur, it + 1, conv, hist.at[it].set(cur)

        def cond(carry):
            _, _, _, it, conv, _ = carry
            return (it < max_iter) & (~conv)

        dt = v0.dtype
        init = (u0, v0, jnp.asarray(prev0, dt), jnp.int32(0),
                jnp.asarray(False), jnp.zeros((room,), dt))
        u, v, cur, n_iter, conv, hist = lax.while_loop(cond, step, init)
        return u, v, cur, n_iter, conv, hist

    from jax.sharding import PartitionSpec as P
    rows = P(_mesh.ROWS)
    u0, v0, prev0 = init_state
    u, v, cur, n_iter, conv, hist = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(rows, rows, rows, rows, P(), P()),
        out_specs=(rows, P(), P(), P(), P(), P()),
        check_vma=True,
    )(user_layout, item_layout, test, u0, v0, jnp.asarray(prev0))
    # fused health vector — same program, zero extra dispatches
    from dislib_tpu.runtime import health as _health
    hvec = _health.health_vec(carries=(u, v), hist=hist, n_done=n_iter)
    return u, v, cur, n_iter, conv, hist, hvec


@partial(_pjit, static_argnames=("m_pad", "n", "n_f", "mesh"),
         name="als_start")
def _als_start(items, seed, m_pad, n, n_f, mesh):
    """The carries a sparse fit starts from: U zero (the first half-step
    solves every user against V before U is read) and V the given
    ``items`` or, where there are none, a uniform draw from ``seed``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if items is None:
        items = jax.random.uniform(
            jax.random.split(jax.random.PRNGKey(seed))[1], (n, n_f),
            jnp.float32)
    u0 = jnp.zeros((m_pad, n_f), jnp.float32)
    return (lax.with_sharding_constraint(
                u0, NamedSharding(mesh, P(_mesh.ROWS, None))),
            lax.with_sharding_constraint(items, NamedSharding(mesh, P())))


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
