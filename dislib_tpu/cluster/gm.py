"""Gaussian mixture via EM (reference: `dislib/cluster/gm` — per-block E-step
responsibility tasks + M-step partial-sum tasks, Cholesky precisions per
component, per-iteration host sync on log-likelihood; SURVEY.md §3.3,
BASELINE config 5).

TPU-native redesign, same shape as KMeans (§4.2 mapping): the whole EM loop
is one jitted `lax.while_loop` on device.  An iteration is the k Cholesky
factorisations, ONE blocked pass over the row-sharded data
(`ops/base.py::em_step`: per block of rows the E-step's whitened
differences, as one GEMM for the k components, and the M-step's sums about
the old means; no (m, k, d) and no (m, k) array exists) ended by one packed
`psum` over ICI, and the closing arithmetic on (k, d, d) numbers.
Convergence on the log-likelihood delta happens on device; the host syncs
once per fit.  `score` and `predict` run the same blocked E-step.

All four covariance types of the reference are supported: full, tied, diag,
spherical.  Padded (zero) rows carry weight 0 everywhere.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dislib_tpu.base import BaseEstimator
from dislib_tpu.data.array import Array, ensure_canonical, fused_kernel
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.ops import base as _ops
from dislib_tpu.ops.base import precise
from dislib_tpu.utils.profiling import profiled_jit as _pjit
from dislib_tpu.utils.profiling import count_schedule as _count_schedule
from dislib_tpu.utils.profiling import new_call as _new_call, span as _span
from dislib_tpu.runtime import fetch as _fetch
from dislib_tpu.runtime import fitloop as _fitloop
from dislib_tpu.runtime import health as _health
from dislib_tpu.utils.dlog import verbose_logger

# the span and scope names of this module (PERF.md, section 3)
_FIT, _INIT = "dslib.gm.fit", "dslib.gm.init"
_CHOL, _CLOSE = "dslib.gm.chol", "dslib.gm.close"


class GaussianMixture(BaseEstimator):
    """Gaussian mixture model (reference parity: dislib.cluster.GaussianMixture).

    Parameters
    ----------
    n_components : int, default 1
    covariance_type : 'full' | 'tied' | 'diag' | 'spherical'
    tol : float — convergence threshold on the lower-bound delta.
    reg_covar : float — ridge added to covariance diagonals.
    max_iter : int
    init_params : 'kmeans' | 'random'
    weights_init, means_init, precisions_init : optional explicit inits
        (reference parity).
    arity : int — accepted, ignored (reduction topology is XLA's).
    random_state : int or None

    Attributes
    ----------
    weights_, means_, covariances_ : ndarrays
    converged_ : bool ;  n_iter_ : int ;  lower_bound_ : float
    history_ : ndarray (n_iter_,) — per-iteration lower bound (SURVEY §6).
    """

    def __init__(self, n_components=1, covariance_type="full", tol=1e-3,
                 reg_covar=1e-6, max_iter=100, init_params="kmeans",
                 weights_init=None, means_init=None, precisions_init=None,
                 arity=50, random_state=None, verbose=False):
        self.n_components = n_components
        self.covariance_type = covariance_type
        self.tol = tol
        self.reg_covar = reg_covar
        self.max_iter = max_iter
        self.init_params = init_params
        self.weights_init = weights_init
        self.means_init = means_init
        self.precisions_init = precisions_init
        self.arity = arity
        self.random_state = random_state
        self.verbose = verbose

    # ------------------------------------------------------------------

    def _start(self, x: Array, overrides):
        """What the program makes its first parameters from, or None when
        ``overrides`` (the explicit ``*_init``) leave nothing to make:
        hard KMeans labels and their centres, or the key of a seeded
        random draw.  Device dispatch only, and nothing of size (m, k):
        the responsibilities themselves are made block by block inside the
        program's first pass over the rows (``ops/base.py::em_start``)."""
        if all(o is not None for o in overrides):
            return None
        if self.init_params == "kmeans":
            # run the KMeans device kernels directly so the init stays on
            # device end-to-end — no host read between here and the EM loop
            # (keeps `_fit_async` dispatch-only for GridSearchCV, SURVEY §4.5)
            from dislib_tpu.cluster.kmeans import (KMeans, _kmeans_fit,
                                                   _kmeans_predict)
            km = KMeans(n_clusters=self.n_components, max_iter=10, tol=1e-4,
                        random_state=self.random_state)
            centers = _kmeans_fit(x._data, x.shape, km._init_centers(x),
                                  10, 1e-4, fast=km._fast())[0]
            return {"centers": centers,
                    "labels": _kmeans_predict(x._data, x.shape, centers)}
        if self.init_params == "random":
            seed = 0 if self.random_state is None else int(self.random_state)
            return {"key": jax.random.PRNGKey(seed)}
        raise ValueError(f"unsupported init_params {self.init_params!r}")

    def fit(self, x: Array, y=None, checkpoint=None, health=None):
        """Fit by EM.  With ``checkpoint=FitCheckpoint(path, every=k)`` the
        device loop runs in k-iteration chunks, snapshotting (weights, means,
        covariances, lower_bound, n_iter) after each; a re-run resumes from
        the snapshot (SURVEY §6 checkpoint/resume).

        ``health`` — optional :class:`~dislib_tpu.runtime.HealthPolicy`;
        each chunk's kernel emits a fused health vector over the EM
        parameters and the lower-bound history (monotone nondecreasing).
        A tripped guard rolls back to the last-good snapshot; the
        ``halve`` action additionally doubles ``reg_covar`` per restart
        (the EM damping knob — a collapsing component's singular
        covariance is the classic EM failure)."""
        self._check_params()
        with _span(_FIT, call=_new_call()):
            m, n = x.shape
            box = {"x": x, "reg_covar": float(self.reg_covar), "start": None,
                   "lb": None}
            log = verbose_logger("gm", self.verbose)
            loop = _fitloop.ChunkedFitLoop(
                "gm", checkpoint=checkpoint, health=health,
                max_iter=self.max_iter,
                increasing=True,            # EM lower bound must not fall
                carry_names=("weights", "means", "covariances"),
                carry_shapes=((self.n_components,), (self.n_components, n)),
                snapshot_expect={"weights": (self.n_components,),
                                 "means": (self.n_components, n)},
                elastic=_fitloop.data_rebind(box))

            def init(rem):
                # EM damping: the 'halve' escalation tier raises the
                # covariance ridge per tier attempt, the standard fix for a
                # component collapsing onto a point (singular covariance→NaN)
                box["reg_covar"] = float(self.reg_covar) * rem.damping
                with _span(_INIT):
                    overrides = self._explicit_inits(n)
                    box["start"] = self._start(box["x"], overrides)
                box["lb"] = None
                return _fitloop.LoopState(overrides)

            def restore(snap, rem):
                # resume: all three parameters come from the snapshot, so
                # there is no start to make and nothing of the rows' size
                box["reg_covar"] = float(self.reg_covar) * rem.damping
                box["start"] = None
                # weights/means compatibility is declared via snapshot_expect
                # and judged by the rollback funnel
                ov = tuple(jnp.asarray(rem.perturb(snap[k])) for k in
                           ("weights", "means", "covariances"))
                box["lb"] = float(snap["lower_bound"])
                return _fitloop.LoopState(
                    ov, it=int(snap["n_iter"]),
                    done=bool(snap.get("converged", False)))

            def step(st, chunk):
                xd = box["x"]
                # the start serves the first chunk alone: from then on the
                # carries hold all three parameters
                start, box["start"] = box["start"], None
                weights, means, covs, lb_dev, n_done, conv, hist, hvec = \
                    _gm_fit(xd._data, xd.shape, self.n_components,
                            self.covariance_type, box["reg_covar"],
                            float(self.tol), chunk, st.carries,
                            prev_lb0=box["lb"], start=start)

                def commit():
                    # deferred scalar syncs: the watchdogged hvec read stays
                    # the chunk's first force point
                    box["lb"] = float(lb_dev)
                    it = st.it + int(n_done)
                    log.info("iter %d: lower_bound=%.6g", it, box["lb"])
                    return _fitloop.LoopState((weights, means, covs), it,
                                              bool(conv))

                return _fitloop.ChunkOutcome(
                    commit, hvec=hvec,
                    history=lambda: _fetch(hist)[: int(n_done)])

            def snapshot(st):
                # the EM parameters are DONATED to the next chunk's kernel
                # (HBM reused in place), so their device->host copies are
                # blocking; the checksum+file write still overlaps the next
                # chunk on the snapshot worker
                weights, means, covs = st.carries
                return {"weights": _fetch(weights), "means": _fetch(means),
                        "covariances": _fetch(covs), "lower_bound": box["lb"],
                        "n_iter": st.it, "converged": st.done}

            st = loop.run(init=init, step=step, restore=restore,
                          snapshot=snapshot)
            weights, means, covs = st.carries
            self.weights_ = _fetch(weights)
            self.means_ = _fetch(means)
            self.covariances_ = _fetch(covs)
            self.lower_bound_ = box["lb"] if box["lb"] is not None else -np.inf
            self.n_iter_ = st.it
            self.converged_ = st.done
            self.history_ = np.asarray(loop.history, dtype=np.float64)
            self.fit_info_ = loop.info
            return self

    def _check_params(self):
        if self.covariance_type not in _ops.COVARIANCE_TYPES:
            raise ValueError(f"bad covariance_type {self.covariance_type!r}")
        if self.max_iter < 1:
            raise ValueError("max_iter must be >= 1")

    def score(self, x: Array, y=None) -> float:
        """Mean per-sample log-likelihood under the fitted mixture (sklearn
        convention) — also what GridSearchCV maximises by default."""
        self._check_fitted()
        return float(_gm_loglik(x._data, x.shape, jnp.asarray(self.weights_),
                                jnp.asarray(self.means_),
                                jnp.asarray(self.covariances_),
                                self.covariance_type))

    # async trial protocol (SURVEY §4.5): the whole EM fit — including the
    # KMeans init — is device dispatch only; GridSearchCV reads nothing back
    # until every trial is in flight
    def _fit_async(self, x, y=None):
        self._check_params()
        overrides = self._explicit_inits(x.shape[1])
        return _gm_fit(x._data, x.shape, self.n_components,
                       self.covariance_type, float(self.reg_covar),
                       float(self.tol), self.max_iter, overrides,
                       start=self._start(x, overrides))

    def _fit_finalize(self, state):
        if state is None:
            return
        weights, means, covs, lb, n_iter, conv, hist, _ = state
        self.weights_ = _fetch(weights)
        self.means_ = _fetch(means)
        self.covariances_ = _fetch(covs)
        self.lower_bound_ = float(lb)
        self.n_iter_ = int(n_iter)
        self.converged_ = bool(conv)
        self.history_ = np.asarray(
            _fetch(hist), dtype=np.float64)[: self.n_iter_]

    def _score_async(self, state, x, y=None):
        if state is None:
            return super()._score_async(state, x, y)
        weights, means, covs = state[0], state[1], state[2]
        return _gm_loglik(x._data, x.shape, weights, means, covs,
                          self.covariance_type)

    def _explicit_inits(self, d):
        """(weights, means, covs) overrides from the *_init params (reference
        parity: weights_init / means_init / precisions_init)."""
        w = None if self.weights_init is None else \
            jnp.asarray(np.asarray(self.weights_init, np.float32))
        mu = None if self.means_init is None else \
            jnp.asarray(np.asarray(self.means_init, np.float32))
        covs = None
        if self.precisions_init is not None:
            p = np.asarray(self.precisions_init, np.float64)
            if self.covariance_type == "full":
                covs = jnp.asarray(np.linalg.inv(p).astype(np.float32))
            elif self.covariance_type == "tied":
                covs = jnp.asarray(np.linalg.inv(p).astype(np.float32))
            else:  # diag / spherical: precisions are 1/variances
                covs = jnp.asarray((1.0 / p).astype(np.float32))
        return (w, mu, covs)

    def fit_predict(self, x: Array, y=None) -> Array:
        return self.fit(x).predict(x)

    def predict(self, x: Array) -> Array:
        """Component index per row — a fusion-graph node, so a scaler →
        predict pipeline is ONE cached dispatch (the serving hot path)."""
        self._check_fitted()
        # serve on the CURRENT mesh: an input built before an elastic
        # resize re-lands on device (never the host) — round 16
        x = ensure_canonical(x)
        weights, means, covs = self._predict_leaves(
            self.weights_, self.means_, self.covariances_)
        return fused_kernel(
            _gm_predict_kernel, (x.shape, self.covariance_type),
            (x, weights, means, covs), (x.shape[0], 1), jnp.int32,
            out_pshape=(x._pshape[0], 1))

    def _check_fitted(self):
        if not hasattr(self, "means_"):
            raise RuntimeError("GaussianMixture is not fitted")


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------

def _chol_precisions(covs, cov_type, d):
    """Cholesky factors of the precision matrices (sklearn-style)."""
    if cov_type == "full":
        chol = jnp.linalg.cholesky(covs)                      # (k, d, d)
        prec = jax.vmap(lambda c: jax.scipy.linalg.solve_triangular(
            c, jnp.eye(d, dtype=c.dtype), lower=True).T)(chol)
        return prec                                           # (k, d, d) upper
    if cov_type == "tied":
        chol = jnp.linalg.cholesky(covs)                      # (d, d)
        return jax.scipy.linalg.solve_triangular(
            chol, jnp.eye(d, dtype=chol.dtype), lower=True).T
    # diag (k, d) / spherical (k,)
    return 1.0 / jnp.sqrt(covs)


def _close(nk, s, second, about, m, cov_type, reg_covar):
    """Weights, means and covariances from the sums of one blocked pass
    (``ops/base.py::em_step``), which are taken about the points ``about``
    (the old means): ``mu_j = a_j + s_j / n_j`` and ``Sigma_j = S_j / n_j
    - (s_j / n_j)(s_j / n_j)^T + reg_covar I``, for tied covariances
    summed over j and averaged, for diag and spherical on the diagonal."""
    d = about.shape[1]
    nk = nk + 1e-10
    moved = s / nk[:, None]
    eye = jnp.eye(d, dtype=about.dtype)
    if cov_type == "full":
        covs = second / nk[:, None, None] \
            - moved[:, :, None] * moved[:, None, :]
        covs = 0.5 * (covs + jnp.swapaxes(covs, 1, 2)) + reg_covar * eye
    elif cov_type == "tied":
        covs = (second - jnp.einsum("jp,jq->pq", s, moved)) / jnp.sum(nk)
        covs = 0.5 * (covs + covs.T) + reg_covar * eye
    else:
        covs = second / nk[:, None] - moved * moved
        if cov_type == "spherical":
            covs = jnp.mean(covs, axis=1)
        covs = covs + reg_covar
    return nk / m, about + moved, covs


# `overrides` (the chunked/resumed EM parameter carries) is DONATED: XLA
# aliases weights/means/covs to their updated outputs and reuses the HBM
# in place across chunks.  Responsibilities exist for one block of rows
# at a time, inside `em_step`'s pass, and never as an (m, k) array: not
# as an argument either, the start (`start`) is KMeans labels or a key.
# Callers never reuse a passed overrides tuple afterwards.
@partial(_pjit, static_argnames=("shape", "k", "cov_type", "max_iter"),
         donate_argnames=("overrides",), name="gm_fit")
@precise
def _gm_fit(xp, shape, k, cov_type, reg_covar, tol, max_iter,
            overrides=(None, None, None), prev_lb0=None, start=None):
    m, n = shape
    _count_schedule("gm_step", "blocked")
    _count_schedule("gm_e_step", "triangle" if _ops.em_cuts(cov_type, xp.dtype)
                    else "whole")
    if cov_type == "full":
        packs = _ops.em_packs(n, xp.dtype)
        _count_schedule("gm_m_step", "packed" if packs else "six_pass")
        # the second moments' upper triangle alone, mirrored, where packed
        _count_schedule("gm_m_moments", "upper" if packs else "whole")
    weights0, means0, covs0 = overrides
    if start is not None:
        # the first parameters, where the caller gave not all three: an
        # M-step from hard labels about their centres, or from a seeded
        # random draw about the rows' mean (padding rows are zero)
        about = start["centers"] if "centers" in start else jnp.tile(
            jnp.sum(xp[:, :n], axis=0) / m, (k, 1))
        made = _close(*_ops.em_start(xp, m, about, cov_type,
                                     labels=start.get("labels"),
                                     key=start.get("key")),
                      about, m, cov_type, reg_covar)
        weights0, means0, covs0 = (
            mine if given is None else given
            for given, mine in zip(overrides, made))

    def step(carry):
        weights, means, covs, prev_lb, _, it, hist = carry
        with jax.named_scope(_CHOL):
            prec = _chol_precisions(covs, cov_type, n)
        nk, s, second, loglik = _ops.em_step(xp, m, jnp.log(weights), means,
                                             prec, cov_type)
        with jax.named_scope(_CLOSE):
            weights, means, covs = _close(nk, s, second, means, m, cov_type,
                                          reg_covar)
            lb = loglik / m                       # mean log-likelihood
            conv = jnp.abs(lb - prev_lb) < tol
        return weights, means, covs, lb, conv, it + 1, hist.at[it].set(lb)

    def cond(carry):
        _, _, _, lb, conv, it, _ = carry
        return (~conv) & (it < max_iter)

    lb0 = jnp.asarray(-jnp.inf, xp.dtype) if prev_lb0 is None else \
        jnp.asarray(prev_lb0, xp.dtype)
    init = (weights0, means0, covs0, lb0, jnp.asarray(False), jnp.int32(0),
            jnp.zeros((max_iter,), xp.dtype))
    weights, means, covs, lb, conv, n_iter, hist = \
        lax.while_loop(cond, step, init)
    # fused health vector — same program, zero extra dispatches (the EM
    # lower bound is nondecreasing, so `hist` is the monotone signal)
    hvec = _health.health_vec(carries=(weights, means, covs), hist=hist,
                              n_done=n_iter, increasing=True)
    return weights, means, covs, lb, n_iter, conv, hist, hvec


@partial(_pjit, static_argnames=("shape", "cov_type"), name="gm_loglik")
@precise
def _gm_loglik(xp, shape, weights, means, covs, cov_type):
    m, n = shape
    with jax.named_scope(_CHOL):
        prec = _chol_precisions(covs, cov_type, n)
    return _ops.em_loglik(xp, m, jnp.log(weights), means, prec, cov_type) / m


def _gm_predict_kernel(cfg, xp, weights, means, covs):
    """`predict` as a fusion-node body (cfg = (shape, cov_type)): the
    blocked E-step's most probable component per row; component ids stay
    int32 (float32 is exact only below 2^24)."""
    (m, n), cov_type = cfg
    with jax.named_scope(_CHOL):
        prec = _chol_precisions(covs, cov_type, n)
    return _ops.em_labels(xp, m, jnp.log(weights), means, prec, cov_type)
