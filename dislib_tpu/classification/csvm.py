"""Cascade SVM (reference: `dislib/classification/csvm` — per-partition
sklearn `SVC` fit tasks, pairwise merge of support vectors up an arity tree,
global SVs fed back for the next global iteration, convergence via the dual
Lagrangian objective; SURVEY.md §3.3).

TPU-native redesign — no sklearn, no ragged SV sets:

- The local solver is an **in-JAX dual SVM**: maximize
  ``W(α) = Σα − ½ αᵀQα`` s.t. ``0 ≤ α ≤ C`` with ``Q = (K + 1) ∘ yyᵀ``.
  The bias is absorbed by the K+1 kernel augmentation (equivalent to a
  penalized intercept / constant feature), which removes the equality
  constraint ``Σyα = 0`` — that constraint is what makes SMO sequential and
  scalar, i.e. hostile to the MXU.  What remains is box-constrained
  projected gradient ascent: ``α ← clip(α + η(1 − Qα), 0, C)`` — one GEMV
  per step inside a `lax.while_loop`, step size from the Gershgorin bound
  ``η = 1/max_row_sum(|Q|)``.  ``DSLIB_CSVM_SOLVER=fista`` switches to
  accelerated PG with adaptive restart (same fixed point + stopping
  rule, fewer sequential steps — the cascade's TPU latency driver; no
  benchmark cell A/Bs the two yet, see `_use_fista`).
- The reference's *growing* SV sets become **fixed-capacity index buffers
  with masking** (SURVEY §8 "hard parts" #1): a cascade node is a padded
  vector of sample indices; padded slots get ``C = 0`` so their α is pinned
  at 0 and they can never become SVs.  Each cascade level is ONE `vmap`-ed
  solve over all nodes of the level (the reference's task-level parallelism,
  recovered as batching).
- **Sparse-native** (SURVEY §8 hard part 2): a `SparseArray` fit keeps a
  host CSR copy (O(nnz) — the layout the reference's per-partition SVC
  tasks consume on CPU workers) and stages each node batch's sub-Gram
  with one sparse GEMM; the dual solves run on device from the
  precomputed K, and sparse queries classify via one spmm cross-term.
  The full matrix is never densified on either side of the fit.
- Kernel values are computed **per node** from gathered rows — a node's
  (cap, cap) sub-Gram, never the m×m Gram of the whole fit set.  Level-0
  partition height is capped (``DSLIB_CSVM_MAX_PARTITION``, default 4096)
  so an inherited default block size of m/p cannot make level 0 quadratic
  in m, and wide levels solve in node batches bounded by a byte budget
  (``DSLIB_CSVM_SOLVE_BUDGET``, default 2 GiB) — peak memory per level is
  O(batch·cap²) regardless of m, which is what lets the cascade scale
  past single-chip HBM the way the reference's partitioning does.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dislib_tpu.base import BaseEstimator
from dislib_tpu.data.array import Array, _repad, ensure_canonical, \
    fused_kernel
from dislib_tpu.ops import distances_sq
from dislib_tpu.ops.base import precise
from dislib_tpu.utils.profiling import profiled_jit as _pjit
from dislib_tpu.runtime import fetch as _fetch
from dislib_tpu.runtime import fitloop as _fitloop


class CascadeSVM(BaseEstimator):
    """Binary SVM trained by cascades of partial solves.

    Parameters (reference parity)
    ----------
    cascade_arity : int, default 2 — fan-in of the SV merge tree.
    max_iter : int, default 5 — global cascade iterations.
    tol : float, default 1e-3 — relative change of the dual objective.
    kernel : 'rbf' or 'linear'.
    c : float, default 1.0 — box constraint.
    gamma : 'auto' or float — rbf width; 'auto' = 1/n_features.
    check_convergence : bool, default True.
    random_state : unused (fit is deterministic); kept for parity.

    Attributes
    ----------
    classes_ : ndarray (2,) — original labels, index = predicted class.
    converged_ : bool
    iterations_n : int (alias n_iter_)
    support_vectors_count_ : int
    """

    _private_fitted_attrs = ("_sv_x", "_sv_y", "_sv_alpha", "_sv_idx",
                             "_gamma_fit")

    def __init__(self, cascade_arity=2, max_iter=5, tol=1e-3, kernel="rbf",
                 c=1.0, gamma="auto", check_convergence=True, random_state=None,
                 verbose=False):
        self.cascade_arity = cascade_arity
        self.max_iter = max_iter
        self.tol = tol
        self.kernel = kernel
        self.c = c
        self.gamma = gamma
        self.check_convergence = check_convergence
        self.random_state = random_state
        self.verbose = verbose

    # -- fitting -------------------------------------------------------------

    def _gamma_value(self, n_features):
        if self.gamma == "auto":
            return 1.0 / n_features
        return float(self.gamma)

    def fit(self, x: Array, y: Array, checkpoint=None, health=None):
        """Fit the cascade.  With ``checkpoint=FitCheckpoint(path, every=k)``
        the global-iteration state (SV indices/alphas, objective, counter)
        snapshots every k iterations; a re-run resumes from the snapshot and
        lands on the uninterrupted run's model (each global iteration
        depends only on the fed-back SV set and previous objective — SURVEY
        §6 checkpoint/resume).

        ``health`` — optional :class:`~dislib_tpu.runtime.HealthPolicy`.
        The cascade's per-iteration state (top-node alphas, dual
        objective) is host-side already, so the guard checks it directly
        (`check_host`) at each global iteration — no extra dispatches; a
        tripped guard rolls back to the last-good snapshot or raises a
        typed ``NumericalDivergence``."""
        if self.kernel not in ("rbf", "linear"):
            raise ValueError(f"unsupported kernel {self.kernel!r}")
        if self.max_iter < 1:
            raise ValueError("max_iter must be >= 1")
        m, n = x.shape
        y_host = np.asarray(y.collect()).ravel()
        classes = np.unique(y_host)
        if len(classes) != 2:
            raise ValueError("CascadeSVM is a binary classifier; got "
                             f"{len(classes)} classes")
        self.classes_ = classes
        y_pm = np.where(y_host == classes[1], 1.0, -1.0).astype(np.float32)

        gamma = self._gamma_value(n)
        # resolved ONCE per fit and threaded as a trace-time static (the
        # _use_cholqr pattern: flipping the env var retraces, never
        # silently ignored)
        solver = "fista" if _use_fista() else "pg"
        # SPARSE-NATIVE path (SURVEY §8 hard part 2): the matrix is never
        # densified.  A host CSR copy (O(nnz), the same layout the
        # reference's per-partition SVC tasks consume on CPU workers)
        # stages each node batch's sub-Gram; the boxed-dual solves stay on
        # device.  Dense inputs keep the all-device gather path.
        from dislib_tpu.data.sparse import SparseArray
        sparse_in = isinstance(x, SparseArray)
        ell = x_csr = k_of = None
        if sparse_in:
            # preferred staging: device-resident ELL row gather — each node
            # batch densifies its rows and computes its sub-Gram ON DEVICE,
            # no host scipy product in the cascade loop (round-3 verdict
            # #5).  Falls back to host-CSR staging when row-nnz skew makes
            # the padded ELL buffers bigger than the budget.
            ell = x.ell()
            if ell is not None:
                xv = None
                yv = jnp.asarray(y_pm)
            else:
                x_csr = x.collect().tocsr()
                rowsq = np.asarray(x_csr.multiply(x_csr).sum(axis=1),
                                   dtype=np.float32).ravel()
                k_of = _host_gram(x_csr, rowsq, self.kernel, gamma)
                xv = yv = None
        else:
            xv = x._data
            yv = jnp.asarray(np.pad(y_pm, (0, xv.shape[0] - m)))

        # level-0 partitions = row-block index chunks (reference: one SVC
        # task per row block) — BOUNDED: a partition of p rows costs a
        # (p, p) sub-Gram, so inheriting a huge default block size (m/p_mesh)
        # would make level 0 quadratic in m.  The cascade exists precisely
        # to keep solves small; cap at DSLIB_CSVM_MAX_PARTITION (4096).
        part = min(max(1, x._reg_shape[0]), _max_partition())
        nodes0 = _pack_nodes([np.arange(s, min(s + part, m))
                              for s in range(0, m, part)])

        box = {"sv_idx": None, "last_w": None, "x": x,
               "xv": xv, "yv": yv, "ell": ell}

        def rebind(mesh):
            # elastic re-staging (round 16): the cascade's node solves
            # read the staged rows, so a mesh change re-stages them —
            # dense re-canonicalizes x and re-pads y to the new quantum,
            # the sparse ELL layout re-lands its backing (the host-CSR
            # fallback and `k_of` are mesh-independent and stay put)
            if sparse_in:
                if mesh is not None:
                    box["x"].sharded(mesh)
                    if x_csr is None:
                        box["ell"] = box["x"].ell()
                return
            from dislib_tpu.data.array import ensure_canonical
            xb = box["x"]
            box["x"] = xb.force() if mesh is None else ensure_canonical(xb)
            if mesh is not None:
                xv2 = box["x"]._data
                box["xv"] = xv2
                box["yv"] = jnp.asarray(
                    np.pad(y_pm, (0, xv2.shape[0] - m)))
        self.converged_ = False
        fp = digest = None
        if checkpoint is not None:
            # fingerprint of everything the fed-back SV state depends on —
            # exact part: shape, hyperparameters, level-0 partitioning;
            # tolerant part: data digests (plain AND index-weighted sums of
            # x and y, so a row permutation changes them) compared with a
            # small relative tolerance, because float reductions differ in
            # the last ulps across mesh topologies and a legitimate
            # resume-after-preemption may land on different hardware.  A
            # sum digest is best-effort: a tiny relative perturbation at
            # very large m can evade it.  NaN digests never match (NaN
            # data fails closed — refuse the resume).  The x digests are
            # einsum reductions (no m×n temporary); pad rows are zero, so
            # padded sums equal logical sums.  Computed only for
            # checkpointed fits.
            fp = np.asarray([m, n, float(gamma), float(self.c),
                             float(self.cascade_arity),
                             float(("rbf", "linear").index(self.kernel)),
                             float(part)], np.float64)
            if sparse_in:
                # same math as the dense einsum digests, over the nonzeros
                # (Σv and Σ row·v) — works for both staging modes
                idxh = np.asarray(jax.device_get(x._bcoo.indices))
                valh = np.asarray(jax.device_get(x._bcoo.data), np.float64)
                x_sum = float(valh.sum())
                x_rowsum = float((valh * idxh[:, 0]).sum())
            else:
                # shared split-iota reduction: exact index weights past
                # 2^24 rows (a plain f32 iota collides adjacent indices)
                from dislib_tpu.utils.checkpoint import digest_sums
                x_sum, x_rowsum = digest_sums(xv)
            from dislib_tpu.utils.checkpoint import versioned_digest, \
                validate_snapshot
            digest = versioned_digest(
                x_sum, x_rowsum, float(y_pm.sum()),
                float(y_pm @ np.arange(m, dtype=np.float64)))
        loop = _fitloop.ChunkedFitLoop(
            "csvm", checkpoint=checkpoint, health=health,
            max_iter=self.max_iter, chunk_iters=1,
            save_every=checkpoint.every if checkpoint is not None else 1,
            elastic=rebind)

        def init(rem):
            box.update(sv_idx=None, sv_alpha=None, last_w=None)
            return _fitloop.LoopState(())   # state is host-side

        def restore(snap, rem):
            validate_snapshot(snap, fp, digest)
            box["sv_idx"] = np.asarray(snap["sv_idx"], np.int64)
            box["sv_alpha"] = np.asarray(snap["sv_alpha"], np.float32)
            box["last_w"] = float(snap["last_w"])
            # a converged snapshot only short-circuits when THIS fit also
            # checks convergence — resuming with check_convergence=False
            # means "run the iterations"
            return _fitloop.LoopState((), it=int(snap["n_iter"]),
                                      done=bool(snap["converged"])
                                      and self.check_convergence)

        def step(st, chunk):
            it = st.it + 1
            if box["sv_idx"] is not None and len(box["sv_idx"]):
                # feed global SVs back into every level-0 partition
                # (dedupe: a partition may already own some of them)
                rows = [np.unique(np.r_[nodes0[i][nodes0[i] >= 0],
                                        box["sv_idx"]])
                        for i in range(nodes0.shape[0])]
                nodes = _pack_nodes(rows)
            else:
                nodes = nodes0
            # cascade reduction to one node
            while True:
                alphas, objs = _solve_level_batched(box["xv"], box["yv"],
                                                    nodes,
                                                    float(self.c), n,
                                                    self.kernel, gamma,
                                                    k_of=k_of, y_host=y_pm,
                                                    ell=box["ell"],
                                                    solver=solver)
                if nodes.shape[0] == 1:
                    break
                nodes = self._merge_level(nodes, np.asarray(alphas))
            # top node: global SVs + dual objective
            top_idx, top_alpha = nodes[0], np.asarray(alphas[0])

            def commit():
                # deferred behind the verdict: a faulted iteration (or the
                # typed raise with no rollback budget left) must never
                # leave its values in the box/attrs — a refit that raises
                # keeps the previously fitted model usable
                keep = (top_alpha > 1e-8) & (top_idx >= 0)
                if not keep.any():
                    # degenerate solve (tiny C / degenerate data): an
                    # empty SV set would make decision_function
                    # identically 0 — keep the max-α sample so the model
                    # stays usable, and say so
                    import warnings
                    warnings.warn("CascadeSVM: no support vector exceeded "
                                  "alpha=1e-8; retaining the max-alpha "
                                  "sample", RuntimeWarning, stacklevel=2)
                    keep[:] = False
                    keep[int(np.argmax(np.where(top_idx >= 0, top_alpha,
                                                -np.inf)))] = True
                w = float(objs[0])   # top node's dual objective (same solve)
                done = bool(self.check_convergence
                            and box["last_w"] is not None
                            and abs(w - box["last_w"])
                            <= self.tol * max(abs(w), 1e-12))
                box.update(sv_idx=top_idx[keep], last_w=w,
                           sv_alpha=top_alpha[keep].astype(np.float32))
                from dislib_tpu.utils.dlog import verbose_logger
                verbose_logger("csvm", self.verbose).info(
                    "iter %d: W=%.6f, SVs=%d", it, w, len(box["sv_idx"]))
                return _fitloop.LoopState((), it, done)

            return _fitloop.ChunkOutcome(
                commit, host_values={"sv_alpha": top_alpha,
                                     "objective": np.asarray(objs[0])})

        def snapshot(st):
            # host-side state already — the async offload moves the
            # checksum+atomic write off the cascade's critical path
            return {"sv_idx": np.asarray(box["sv_idx"], np.int64),
                    "sv_alpha": box["sv_alpha"],
                    "last_w": box["last_w"], "n_iter": st.it, "fp": fp,
                    "digest": digest, "converged": st.done}

        st = loop.run(init=init, step=step, restore=restore,
                      snapshot=snapshot)
        self.iterations_n = self.n_iter_ = st.it
        self.converged_ = st.done
        self._sv_alpha = box["sv_alpha"]
        self.fit_info_ = loop.info
        sv_idx = box["sv_idx"]
        self._sv_idx = sv_idx
        # gather SV rows only (n_sv × n, never the dataset): from the host
        # CSR on the sparse path, on device for dense inputs
        if sparse_in:
            if box["ell"] is not None:
                self._sv_x = _fetch(_ell_rows_dense(
                    box["ell"][0], box["ell"][1], jnp.asarray(sv_idx), n))
            else:
                self._sv_x = np.asarray(x_csr[sv_idx].toarray(), np.float32)
        else:
            self._sv_x = _fetch(box["x"]._data[jnp.asarray(sv_idx), : n])
        self._sv_y = y_pm[sv_idx]
        self._gamma_fit = gamma
        self.support_vectors_count_ = len(sv_idx)
        return self

    def _merge_level(self, nodes, alphas):
        """Group nodes by cascade_arity; each group's (deduped) SV indices
        form one next-level node."""
        a = self.cascade_arity
        groups = [list(range(i, min(i + a, nodes.shape[0])))
                  for i in range(0, nodes.shape[0], a)]
        rows = []
        for g in groups:
            sv = []
            for ni in g:
                keep = (alphas[ni] > 1e-8) & (nodes[ni] >= 0)
                sv.extend(nodes[ni][keep].tolist())
            sv = np.unique(sv) if sv else \
                np.asarray([int(nodes[g[0]][0])])  # never emit an empty node
            rows.append(sv)
        return _pack_nodes(rows)

    # -- inference -----------------------------------------------------------

    def decision_function(self, x: Array) -> Array:
        """Signed margin per row.  Dense queries build a fusion-graph node
        (one cached dispatch end-to-end for a scaler → decision chain);
        sparse queries stay an eager spmm kernel."""
        self._check_fitted()
        from dislib_tpu.data.sparse import SparseArray
        if isinstance(x, SparseArray):
            # sparse queries: cross-term as one spmm against the (small)
            # dense SV block — the query matrix never densifies
            dec = _decision_sparse(x._bcoo, x.row_norms_sq(),
                                   jnp.asarray(self._sv_x),
                                   jnp.asarray(self._sv_y),
                                   jnp.asarray(self._sv_alpha),
                                   self.kernel, self._gamma_fit)
            return Array._from_logical_padded(_repad(dec, (x.shape[0], 1)),
                                              (x.shape[0], 1))
        # serve on the CURRENT mesh: an input built before an elastic
        # resize re-lands on device (never the host) — round 16
        x = ensure_canonical(x)
        sv_x, sv_y, sv_alpha, gamma = self._predict_leaves(
            self._sv_x, self._sv_y, self._sv_alpha, self._gamma_leaf())
        return fused_kernel(
            _decision_kernel, (x.shape, self.kernel),
            (x, sv_x, sv_y, sv_alpha, gamma),
            (x.shape[0], 1), jnp.float32, out_pshape=(x._pshape[0], 1))

    def predict(self, x: Array) -> Array:
        """Class label per row.  The dense path is one fusion node —
        decision values, thresholding, AND the class-value lookup all run
        on device (the old host round-trip between decision and label
        selection was a hidden per-predict sync, caught by the round-9
        `dispatches_per_predict` counters)."""
        self._check_fitted()
        from dislib_tpu.data.sparse import SparseArray
        if isinstance(x, SparseArray):
            dec = self.decision_function(x).collect().ravel()
            labels = self.classes_[(dec > 0).astype(np.int64)]
            dt = np.int32 if np.issubdtype(labels.dtype, np.integer) \
                else np.float32
            out = jnp.asarray(labels.astype(dt)[:, None])
            return Array._from_logical_padded(_repad(out, (x.shape[0], 1)),
                                              (x.shape[0], 1))
        x = ensure_canonical(x)     # serve on the CURRENT mesh (round 16)
        sv_x, sv_y, sv_alpha, gamma, classes = self._predict_leaves(
            self._sv_x, self._sv_y, self._sv_alpha, self._gamma_leaf(),
            self._classes_leaf())
        return fused_kernel(
            _csvm_predict_kernel, (x.shape, self.kernel),
            (x, sv_x, sv_y, sv_alpha, gamma, classes),
            (x.shape[0], 1), classes.dtype, out_pshape=(x._pshape[0], 1))

    def score(self, x: Array, y: Array) -> float:
        pred = self.predict(x).collect().ravel()
        truth = np.asarray(y.collect()).ravel()
        return float(np.mean(pred == truth))

    def _gamma_leaf(self):
        """``gamma`` as a host scalar array with stable identity, so the
        `_predict_leaves` device cache hits on repeat predict calls (gamma
        stays a DYNAMIC operand — one compiled decision program serves
        every gamma, as the pre-fusion jitted kernel did)."""
        cached = getattr(self, "_gamma_cache", None)
        if cached is None or cached[0] != self._gamma_fit:
            self._gamma_cache = (self._gamma_fit,
                                 np.float32(self._gamma_fit))
        return self._gamma_cache[1]

    def _check_fitted(self):
        if not hasattr(self, "_sv_x"):
            raise RuntimeError("CascadeSVM is not fitted")


def _max_partition() -> int:
    return int(os.environ.get("DSLIB_CSVM_MAX_PARTITION", 4096))


def _solve_budget() -> int:
    return int(os.environ.get("DSLIB_CSVM_SOLVE_BUDGET", 2 << 30))


def _host_gram(csr, rowsq, kernel, gamma):
    """Sub-Gram stager for the sparse path: per node, slice the node's rows
    out of the host CSR (the reference's per-partition data movement) and
    compute its (cap, cap) kernel block with one sparse GEMM — the full
    matrix is never densified; the dense footprint is the sub-Gram the
    dual solve needs anyway.  Padded node slots stay zero rows (their C is
    pinned to 0 in the solve)."""
    def k_of(nodes_chunk):
        w, cap = nodes_chunk.shape
        k = np.zeros((w, cap, cap), np.float32)
        for t in range(w):
            idx = nodes_chunk[t][nodes_chunk[t] >= 0]
            if not len(idx):
                continue
            sub = csr[idx]
            cross = np.asarray((sub @ sub.T).todense(), dtype=np.float32)
            if kernel == "rbf":
                rq = rowsq[idx]
                cross = np.exp(-gamma * np.maximum(
                    rq[:, None] + rq[None, :] - 2.0 * cross, 0.0))
            nv = len(idx)
            k[t, :nv, :nv] = cross
        return k
    return k_of


def _solve_level_batched(xv, yv, nodes, c, n_feat, kernel, gamma,
                         k_of=None, y_host=None, ell=None, solver="pg"):
    """One cascade level in node batches bounded by a byte budget.

    A level's vmapped solve holds ~3 (cap, cap) f32 buffers per node
    (K, Q, and GEMV temporaries); solving every node of a wide level at
    once would scale per-level memory with m.  Batches are padded to a
    fixed node count with all-invalid rows (C pinned to 0 → their alpha
    converges to 0 immediately) so only one shape per cap compiles.
    Sparse staging: ``ell`` gathers + densifies each node's rows ON
    DEVICE (no host product anywhere in the level); ``k_of`` is the
    host-CSR fallback that stages precomputed kernel blocks."""
    n_nodes, cap = nodes.shape
    # dense/ell paths also gather a (cap, n_feat) row block per node — at
    # n_feat >> cap that term, not the (cap, cap) buffers, bounds memory;
    # the ell gather adds the (cap, r) vals+cols staging buffers
    per_node = 3 * cap * cap * 4
    if k_of is None:
        per_node += cap * n_feat * 4
    if ell is not None:
        per_node += cap * ell[0].shape[1] * 8
    batch = min(n_nodes, max(1, _solve_budget() // per_node))

    def solve_chunk(chunk):
        if ell is not None:
            return _solve_level_ell(ell[0], ell[1], yv, jnp.asarray(chunk),
                                    c, n_feat, kernel, gamma, solver)
        if k_of is None:
            return _solve_level(xv, yv, jnp.asarray(chunk), c, n_feat,
                                kernel, gamma, solver)
        valid = chunk >= 0
        k_sub = k_of(chunk)
        y_sub = np.where(valid, y_host[np.maximum(chunk, 0)], 0.0) \
            .astype(np.float32)
        c_vec = np.where(valid, c, 0.0).astype(np.float32)
        import warnings
        with warnings.catch_warnings():
            # k_sub (the staged kernel rows, the level's dominant buffer)
            # has no same-shape output to alias, so XLA reports it
            # "not usable" for aliasing at lowering — donation still
            # releases its HBM for solver temporaries mid-program, which
            # is the point; silence exactly that advisory
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return _solve_level_k(jnp.asarray(k_sub), jnp.asarray(y_sub),
                                  jnp.asarray(c_vec), solver)

    if n_nodes <= batch and k_of is None:
        return solve_chunk(nodes)
    # batched level: the dispatch→read sequence pipelines through the
    # shared host-loop discipline — batch t's blocking reads run under
    # batch t+1's solve (one extra batch in flight), db/seq bit-equal by
    # construction; the routing is observable through the schedule
    # counter like every other overlap site
    from dislib_tpu.ops import overlap as _ov
    from dislib_tpu.utils import profiling as _prof
    sched = _ov.resolve()
    _prof.count_schedule("csvm_batches", sched)

    def fetch(i):
        chunk = nodes[i * batch:(i + 1) * batch]
        if chunk.shape[0] < batch:
            chunk = np.concatenate(
                [chunk, np.full((batch - chunk.shape[0], cap), -1, np.int64)])
        a, o = solve_chunk(chunk)
        # start the device→host DMA too, so consume()'s blocking read
        # finds the bytes already on their way
        for buf in (a, o):
            if hasattr(buf, "copy_to_host_async"):
                buf.copy_to_host_async()
        return a, o

    def consume(i, pair):
        return np.asarray(pair[0]), np.asarray(pair[1])

    res = _ov.host_pipeline(-(-n_nodes // batch), fetch, consume,
                            overlap=_ov.overlapped(sched))
    return (np.concatenate([a for a, _ in res])[:n_nodes],
            np.concatenate([o for _, o in res])[:n_nodes])


def _pack_nodes(rows):
    """Stack variable-length index rows into a (-1)-padded matrix whose cap
    is rounded up to a power of two — bounds the number of distinct shapes
    `_solve_level` ever compiles for to O(log n)."""
    cap = max(1, max(len(r) for r in rows))
    cap = 1 << (cap - 1).bit_length()
    out = np.full((len(rows), cap), -1, np.int64)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------

def _gram(a, b, kernel, gamma):
    if kernel == "rbf":
        return jnp.exp(-gamma * distances_sq(a, b))
    return a @ b.T


def _use_fista() -> bool:
    """Solver policy: DSLIB_CSVM_SOLVER in {auto (default), pg, fista}.
    'fista' is accelerated projected gradient with adaptive restart —
    same fixed point, same stopping rule, typically several-fold fewer
    sequential while_loop steps, which is exactly the latency driver of
    the cascade on TPU (each step is one small GEMV).  'auto' currently
    keeps plain PG: flipping the default waits for an on-chip A/B in a
    benchmark cell (the CholeskyQR2 precedent — policy changes ride
    measurements, not expectations)."""
    import os
    v = os.environ.get("DSLIB_CSVM_SOLVER", "auto")
    if v not in ("auto", "pg", "fista"):
        raise ValueError(
            f"DSLIB_CSVM_SOLVER={v!r} — expected auto, pg or fista")
    return v == "fista"


def _dual_ascent(q, c_vec, solver="pg"):
    """Box-constrained dual maximization on one node (shared by the
    gathered-rows and precomputed-K solvers).  ``solver``: 'pg' = plain
    projected gradient ascent; 'fista' = accelerated (Nesterov momentum,
    gradient-scheme adaptive restart so the momentum can never drive the
    objective backwards for long).  Identical stopping rule and step cap,
    so the two differ only in sequential-step count."""
    eta = 1.0 / jnp.maximum(jnp.max(jnp.sum(jnp.abs(q), axis=1)), 1e-12)
    alpha0 = jnp.zeros_like(c_vec)

    if solver == "fista":
        def body(carry):
            alpha, z, t, i, _ = carry
            grad = 1.0 - q @ z
            new = jnp.clip(z + eta * grad, 0.0, c_vec)
            # restart when the update opposes the momentum direction
            restart = jnp.sum((z - new) * (new - alpha)) > 0.0
            t_next = jnp.where(
                restart, 1.0, (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)) / 2.0)
            beta = jnp.where(restart, 0.0, (t - 1.0) / t_next)
            z_next = new + beta * (new - alpha)
            delta = jnp.max(jnp.abs(new - alpha))
            return new, z_next, t_next, i + 1, delta

        def cond(carry):
            _, _, _, i, delta = carry
            return (i < 500) & (delta > 1e-6)

        alpha, _, _, _, _ = lax.while_loop(
            cond, body, (alpha0, alpha0, jnp.float32(1.0), jnp.int32(0),
                         jnp.float32(jnp.inf)))
    else:
        def body(carry):
            alpha, i, _ = carry
            grad = 1.0 - q @ alpha
            new = jnp.clip(alpha + eta * grad, 0.0, c_vec)
            delta = jnp.max(jnp.abs(new - alpha))
            return new, i + 1, delta

        def cond(carry):
            _, i, delta = carry
            return (i < 500) & (delta > 1e-6)

        alpha, _, _ = lax.while_loop(cond, body, (alpha0, jnp.int32(0),
                                                  jnp.float32(jnp.inf)))
    # dual objective on the Q this solve already holds — callers read
    # the top node's value for the convergence check
    obj = jnp.sum(alpha) - 0.5 * alpha @ (q @ alpha)
    return alpha, obj


@partial(_pjit, static_argnames=("n_feat", "kernel", "solver"),
         name="csvm_solve_level")
@precise
def _solve_level(xv, yv, nodes, c, n_feat, kernel, gamma, solver):
    """Solve the boxed dual on every node of a cascade level (vmap).  Each
    node's (cap, cap) sub-Gram is built from its gathered rows — the m×m
    Gram is never materialised."""

    def solve_one(idx):
        valid = idx >= 0
        safe = jnp.maximum(idx, 0)
        x_sub = xv[safe, :n_feat]
        k_sub = _gram(x_sub, x_sub, kernel, gamma) + 1.0  # K+1 bias augment
        y_sub = yv[safe]
        q = k_sub * (y_sub[:, None] * y_sub[None, :])
        c_vec = jnp.where(valid, c, 0.0)            # padded slots pinned at 0
        return _dual_ascent(q, c_vec, solver)

    return jax.vmap(solve_one)(nodes)


@partial(_pjit, static_argnames=("n_feat",), name="csvm_ell_rows")
def _ell_rows_dense(ev, ec, idx, n_feat):
    """Densify the rows ``idx`` of an ELL-format sparse matrix on device:
    one scatter-add per gather — the device replacement for slicing a host
    CSR (`SparseArray.ell`)."""
    v = ev[idx]                                   # (cap, r)
    cc = ec[idx]
    cap, r = v.shape
    rows = jnp.broadcast_to(jnp.arange(cap)[:, None], (cap, r))
    return jnp.zeros((cap, n_feat), ev.dtype).at[rows, cc].add(v)


@partial(_pjit, static_argnames=("n_feat", "kernel", "solver"),
         name="csvm_solve_level_ell")
@precise
def _solve_level_ell(ev, ec, yv, nodes, c, n_feat, kernel, gamma, solver):
    """Boxed-dual solves with device-resident sparse staging: each node
    gathers its rows from the ELL buffers, densifies its (cap, n) block by
    scatter, and computes its (cap, cap) sub-Gram on device — the whole
    cascade level is one program, no host kernel products (the sparse
    analog of `_solve_level`)."""

    def solve_one(idx):
        valid = idx >= 0
        safe = jnp.maximum(idx, 0)
        x_sub = _ell_rows_dense(ev, ec, safe, n_feat)
        k_sub = _gram(x_sub, x_sub, kernel, gamma) + 1.0
        y_sub = yv[safe]
        q = k_sub * (y_sub[:, None] * y_sub[None, :])
        c_vec = jnp.where(valid, c, 0.0)
        return _dual_ascent(q, c_vec, solver)

    return jax.vmap(solve_one)(nodes)


# k_sub (per-node kernel rows) and y_sub are DONATED: both are staged
# fresh per call and dead afterwards; y_sub aliases the alpha output,
# k_sub frees the level's largest buffer for solver temporaries.
@partial(_pjit, static_argnames=("solver",),
         donate_argnames=("k_sub", "y_sub"), name="csvm_solve_level_k")
@precise
def _solve_level_k(k_sub, y_sub, c_vec, solver):
    """Same dual solves on host-staged kernel blocks (the sparse path)."""
    def solve_one(k1, y1, cv):
        q = (k1 + 1.0) * (y1[:, None] * y1[None, :])
        return _dual_ascent(q, cv, solver)
    return jax.vmap(solve_one)(k_sub, y_sub, c_vec)


@partial(_pjit, static_argnames=("kernel",), name="csvm_decision_sparse")
@precise
def _decision_sparse(bcoo, rowsq, sv_x, sv_y, sv_alpha, kernel, gamma):
    """Decision values for sparse queries: cross = one spmm (m, n_sv)."""
    from dislib_tpu.data.sparse import _spmm
    cross = _spmm(bcoo, sv_x.T)
    if kernel == "rbf":
        sv_sq = jnp.sum(sv_x * sv_x, axis=1)
        k = jnp.exp(-gamma * jnp.maximum(
            rowsq[:, None] - 2.0 * cross + sv_sq[None, :], 0.0))
    else:
        k = cross
    return ((k + 1.0) @ (sv_alpha * sv_y))[:, None]


def _decision_core(qp, q_shape, sv_x, sv_y, sv_alpha, kernel, gamma):
    mq, n = q_shape
    qv = qp[:, :n]
    if kernel == "rbf":
        k = jnp.exp(-gamma * distances_sq(qv, sv_x))
    else:
        k = qv @ sv_x.T
    dec = (k + 1.0) @ (sv_alpha * sv_y)
    valid = lax.broadcasted_iota(jnp.int32, (qv.shape[0],), 0) < mq
    return jnp.where(valid, dec, 0.0)[:, None]


def _decision_kernel(cfg, qp, sv_x, sv_y, sv_alpha, gamma):
    """`decision_function` as a fusion-node body (cfg = (q_shape, kernel);
    gamma rides as a dynamic operand so one program serves every gamma)."""
    q_shape, kernel = cfg
    return _decision_core(qp, q_shape, sv_x, sv_y, sv_alpha, kernel, gamma)


def _csvm_predict_kernel(cfg, qp, sv_x, sv_y, sv_alpha, gamma, classes):
    """`predict` as a fusion-node body: decision → threshold → on-device
    class-value lookup.  Padded rows re-zero (classes[0] may be nonzero)."""
    q_shape, kernel = cfg
    dec = _decision_core(qp, q_shape, sv_x, sv_y, sv_alpha, kernel, gamma)
    labels = jnp.where(dec > 0, classes[1], classes[0])
    valid = lax.broadcasted_iota(jnp.int32, labels.shape, 0) < q_shape[0]
    return jnp.where(valid, labels, jnp.zeros((), labels.dtype))
