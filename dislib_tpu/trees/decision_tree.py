"""Distributed decision trees (reference: `dislib/trees/decision_tree.py` +
`test_split.py` — top `distr_depth` levels split via `_compute_split` tasks,
subtrees delegated to one sklearn tree per task, file-based bootstrap-sample
side channel; SURVEY.md §3.3 "largest estimator subsystem").

TPU-native redesign — histogram trees, not sklearn delegation (SURVEY §8 M5):

- **Level-synchronous growth over padded node arrays.**  A tree of depth D is
  a heap-shaped array of 2^D − 1 internal nodes + 2^D leaves, grown one level
  at a time; every sample carries its current node id.  Data-dependent
  structure (the reference's recursive splits) becomes fixed-shape tensor
  ops: one (node, feature, bin) weighted histogram per level — a single
  scatter-add — then a vectorised best-gain argmax.  Nodes that stop
  splitting become pass-through splits (threshold +inf) so shapes never
  change.
- **Feature bins** are per-feature quantile thresholds (n_bins=32) computed
  once per fit; splits search bin boundaries, exactly the
  histogram-of-gradients trick GPU boosters use, and the analog of the
  reference's per-feature candidate-threshold search in `test_split.py`.
- **Bootstrap via Poisson(1) sample weights** per (tree, sample) — the
  dense-weights equivalent of the reference's per-tree bootstrap-index files
  (its shared-FS `.npy` side channel, SURVEY §3.3), with no random access.
- The whole forest grows together: every level is ONE jitted call `vmap`-ed
  over trees (the reference's task-per-tree parallelism, recovered as
  batching on the MXU).

`distr_depth` / `sklearn_max` are accepted for parity and ignored — they
tuned the task-distribution/delegation boundary, which doesn't exist here.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dislib_tpu.base import BaseEstimator
from dislib_tpu.data.array import Array, _repad
from dislib_tpu.ops import precision as px
from dislib_tpu.utils import profiling as _prof
from dislib_tpu.utils.profiling import profiled_jit as _pjit
from dislib_tpu.runtime import fetch as _fetch, repad_rows as _repad_rows
from dislib_tpu.runtime import fitloop as _fitloop
from dislib_tpu.runtime import health as _health

# Discretisation contract (documented divergence from the reference, which
# delegates subtrees to exact sklearn trees with arbitrary thresholds):
# split thresholds are per-feature QUANTILE bin edges, `n_bins` per feature
# (constructor param, default N_BINS).  Distributions whose class/target
# structure lives at finer granularity than ~1/n_bins quantile spacing need
# a larger `n_bins` — see tests/test_trees.py::test_n_bins_contract for a
# distribution where 32 bins provably loses and n_bins=256 recovers it.
N_BINS = 32
# Depth is capped: node arrays are heap-shaped (2^depth), so depth is a
# compiled SHAPE — the cap keeps the padded arrays (and XLA programs)
# bounded.  Requesting a finite max_depth above the cap warns loudly
# (_effective_depth); the reference's data-bounded recursion has no cap.
MAX_DEPTH_CAP = 12


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("shape", "n_bins"))
def _quantile_bins(xp, shape, n_bins=N_BINS):
    """Per-feature bin edges from quantiles of the valid rows: (n, n_bins-1)."""
    m, n = shape
    xv = xp[:m, :n]
    qs = jnp.linspace(0.0, 100.0, n_bins + 1)[1:-1]
    return jnp.percentile(xv, qs, axis=0).T          # (n, n_bins-1)


@partial(jax.jit, static_argnames=("shape",))
def _bin_data(xp, shape, edges):
    """Bin index of every (sample, feature): (m_pad, n) int32 in [0, n_bins),
    with n_bins implied by the edges width (n, n_bins-1)."""
    n = shape[1]
    xv = xp[:, :n]
    # bx[i, f] = #edges below x[i, f]
    return jnp.sum(xv[:, :, None] > edges[None, :, :], axis=2).astype(jnp.int32)


def _node_histogram(node, bx, w, stats, n_nodes, n_bins, hist="xla"):
    """Per-sample `stats` (m, S) histogrammed into (n_nodes, n, n_bins,
    S).  ``hist`` is the schedule (a jit static resolved ONCE at the
    forest-fit boundary, `hist:<sched>` counter): "xla" is the plain
    scatter-add; "pallas" routes the one-hot-GEMM Pallas kernel
    (``ops/pallas_kernels.node_histogram``) — bit-equal here because the
    forest's contributions (Poisson weights × count/target stats) are
    integer-representable, so the sums are exact under either order.

    The Pallas arm runs per row shard inside a ``shard_map`` and sums the
    partial histograms with one ``psum`` over 'rows': a Mosaic kernel is
    opaque to the SPMD partitioner (on a multi-device mesh XLA refuses to
    partition it), and shard-local histogram + all-reduce is the
    distributed form of the scatter anyway."""
    if hist == "pallas":
        from jax.sharding import PartitionSpec as P

        from dislib_tpu.ops import pallas_kernels as _pk
        from dislib_tpu.parallel import mesh as _mesh

        def local(nd, b, c):
            return lax.psum(_pk.node_histogram(nd, b, c, n_nodes, n_bins),
                            _mesh.ROWS)

        rows = P(_mesh.ROWS)
        return jax.shard_map(
            local, mesh=_mesh.get_mesh(), in_specs=(rows, rows, rows),
            out_specs=P(), check_vma=True,
        )(node, bx, w[:, None] * stats).astype(px.compute_dtype(px.FLOAT32))
    m, n = bx.shape
    acc_dt = px.compute_dtype(px.FLOAT32)
    feat = lax.broadcasted_iota(jnp.int32, (m, n), 1)
    hist_acc = jnp.zeros((n_nodes, n, n_bins, stats.shape[1]), acc_dt)
    contrib = (w[:, None, None] * stats[:, None, :]).astype(acc_dt)
    contrib = jnp.broadcast_to(contrib, (m, n, stats.shape[1]))
    return hist_acc.at[node[:, None], feat, bx].add(contrib)


def _gain_and_split(hist, criterion):
    """Best (feature, bin) per node from the level histogram.

    hist: (n_nodes, n, N_BINS, S).  Returns (feat, bin, gain, node_total)
    where node_total is the per-node stats vector (S,).
    criterion: 'gini' (S = n_classes counts) or 'mse' (S = [w, wy, wy²]).
    """
    left = jnp.cumsum(hist, axis=2)                  # stats of bins <= b
    total = left[:, :, -1:, :]                       # (n_nodes, n, 1, S)
    right = total - left

    def impurity(s):
        if criterion == "gini":
            w = jnp.sum(s, axis=-1)
            p = s / jnp.maximum(w[..., None], 1e-12)
            return w * (1.0 - jnp.sum(p * p, axis=-1))
        w, wy, wy2 = s[..., 0], s[..., 1], s[..., 2]
        return wy2 - wy * wy / jnp.maximum(w, 1e-12)  # w * variance

    parent = impurity(total)                          # (n_nodes, n, 1)
    gain = parent - impurity(left) - impurity(right)  # (n_nodes, n, N_BINS)
    # last bin puts everything left — not a real split
    gain = gain.at[:, :, -1].set(-jnp.inf)
    wl = left[..., 0] if criterion == "mse" else jnp.sum(left, axis=-1)
    wr = right[..., 0] if criterion == "mse" else jnp.sum(right, axis=-1)
    gain = jnp.where((wl > 0) & (wr > 0), gain, -jnp.inf)
    return gain, total[:, 0, 0, :]                    # per-node totals (f=0)


def _mask_features(gain, key, try_features):
    """Restrict each node's search to a random feature subset (per node)."""
    n_nodes, n, _ = gain.shape
    if try_features is None or try_features >= n:
        return gain
    score = jax.random.uniform(key, (n_nodes, n))
    kth = lax.top_k(score, try_features)[0][:, -1]
    allowed = score >= kth[:, None]
    return jnp.where(allowed[:, :, None], gain, -jnp.inf)


def _level_step(node, bx, w, stats, key, n_nodes, try_features, min_gain,
                criterion, n_bins, hist="xla"):
    """Grow one level of one tree. Returns (feat, thr_bin, is_split, new_node,
    node_totals)."""
    hist = _node_histogram(node, bx, w, stats, n_nodes, n_bins, hist=hist)
    gain, totals = _gain_and_split(hist, criterion)
    gain = _mask_features(gain, key, try_features)
    flat = gain.reshape(n_nodes, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    feat = (best // n_bins).astype(jnp.int32)
    tbin = (best % n_bins).astype(jnp.int32)
    is_split = best_gain > min_gain
    # pass-through for non-splitting nodes: everything goes left
    feat = jnp.where(is_split, feat, 0)
    tbin = jnp.where(is_split, tbin, n_bins - 1)
    # route samples: right iff bin(x_f) > threshold bin
    f_sel = feat[node]                                # (m,)
    b_sel = tbin[node]
    x_bin = jnp.take_along_axis(bx, f_sel[:, None], axis=1)[:, 0]
    go_right = (x_bin > b_sel) & is_split[node]
    new_node = node * 2 + go_right.astype(jnp.int32)
    return feat, tbin, is_split, new_node, totals


# one jitted step per (level-shape, config); vmapped over the whole forest.
# `node` (the (T, m_pad) per-sample node assignment) is DONATED: it aliases
# the returned new_node, so level growth updates the forest's largest
# carried array in place instead of double-buffering it.  The loop rebinds
# `node` to the output each level and never touches the old buffer (snapshot
# fetches read the NEW node, blocking, before the next level dispatches).
@partial(_pjit, static_argnames=("n_nodes", "try_features", "criterion",
                                 "n_bins", "hist"),
         donate_argnames=("node",), name="forest_level")
def _forest_level(node, bx, w, stats, keys, n_nodes, try_features,
                  min_gain, criterion, n_bins, hist="xla"):
    step = partial(_level_step, n_nodes=n_nodes, try_features=try_features,
                   min_gain=min_gain, criterion=criterion, n_bins=n_bins,
                   hist=hist)
    feat, tbin, is_split, new_node, totals = \
        jax.vmap(step, in_axes=(0, None, 0, None, 0))(
            node, bx, w, stats, keys)
    # fused health vector — same program, zero extra dispatches.  The
    # per-node stat totals are where a poisoned weight/stat carry first
    # shows up as NaN (feat/tbin/node are integral and cannot hold one).
    hvec = _health.health_vec(carries=(totals, w))
    return feat, tbin, is_split, new_node, totals, hvec


@partial(_pjit, static_argnames=("n_leaves",), name="leaf_stats")
def _leaf_stats(node, w, stats, n_leaves):
    """Final-level per-leaf stat sums: (T, n_leaves, S), plus the fused
    health vector over them (the forest's terminal numeric state — a NaN
    here is what would silently poison every prediction)."""
    def one(nd, wt):
        out = jnp.zeros((n_leaves, stats.shape[1]), jnp.float32)
        return out.at[nd].add(wt[:, None] * stats)
    leaves = jax.vmap(one)(node, w)
    return leaves, _health.health_vec(carries=(leaves,))


def _forest_apply_core(qp, q_shape, edges, feats, tbins, depth):
    """Leaf index of every query row in every tree: (T, mq_pad).  Plain
    traced body — shared by the jitted `_forest_apply`, the score
    kernels, and the fused predict nodes in `forest.py`."""
    bq = _bin_data(qp, q_shape, edges)                # (mq_pad, n)

    def one_tree(feat_l, tbin_l):
        node = jnp.zeros(bq.shape[0], jnp.int32)
        for lvl in range(depth):
            f = feat_l[lvl][node]
            b = tbin_l[lvl][node]
            x_bin = jnp.take_along_axis(bq, f[:, None], axis=1)[:, 0]
            node = node * 2 + (x_bin > b).astype(jnp.int32)
        return node

    return jax.vmap(one_tree)(feats, tbins)


@partial(_pjit, static_argnames=("depth", "q_shape"), name="forest_apply")
def _forest_apply(qp, q_shape, edges, feats, tbins, depth):
    return _forest_apply_core(qp, q_shape, edges, feats, tbins, depth)


# ---------------------------------------------------------------------------
# host-side tree builder shared by the estimators
# ---------------------------------------------------------------------------

def _pack_levels(levels, depth):
    """Traced pad+stack of the ragged per-level (T, 2^lvl) arrays — call
    INSIDE a jitted kernel only, where it fuses into the one program (see
    _grow_forest on why an eager pack is a deadlock hazard)."""
    wide = 2 ** (depth - 1)
    return jnp.stack([jnp.pad(a, ((0, 0), (0, wide - a.shape[1])))
                      for a in levels], axis=1)


class _BaseTreeEnsemble(BaseEstimator):
    """Shared fit/apply machinery; subclasses set `_criterion` and predictions."""

    _criterion = "gini"
    _private_fitted_attrs = ("_edges", "_feats", "_tbins", "_depth", "_leaves")

    def _effective_depth(self, m):
        d = self.max_depth
        if d is None or np.isinf(d):
            d = MAX_DEPTH_CAP
        elif d > MAX_DEPTH_CAP:
            import warnings
            warnings.warn(
                f"max_depth={d} exceeds the depth cap {MAX_DEPTH_CAP}: tree "
                f"node arrays are heap-shaped (2^depth is a compiled XLA "
                f"shape), so growth is capped at {MAX_DEPTH_CAP} levels — "
                "unlike the reference's data-bounded recursion. Deep "
                "fine-structure beyond the cap will not be modelled.",
                UserWarning, stacklevel=3)
        return int(max(1, min(d, MAX_DEPTH_CAP, int(np.ceil(np.log2(max(m, 2)))))))

    def _n_bins(self):
        nb = getattr(self, "n_bins", None)   # None: pre-n_bins snapshot load
        nb = N_BINS if nb is None else int(nb)
        if not 2 <= nb <= 1024:
            raise ValueError(f"n_bins must be in [2, 1024], got {nb}")
        return nb

    def _try_features_count(self, n):
        tf = getattr(self, "try_features", None)
        if tf in (None, "none"):
            return None
        if tf == "sqrt":
            return max(1, int(np.sqrt(n)))
        if tf == "third":
            return max(1, n // 3)
        return max(1, int(tf))

    def _grow_forest(self, x: Array, stats_host, n_trees, bootstrap,
                     checkpoint=None, health=None):
        """Dispatch the whole forest growth as device programs — no host
        read (the async-fit half; `_adopt_forest` materialises attrs).

        With ``checkpoint`` the grown-so-far state (node assignment,
        bootstrap weights, per-level splits, seed, level counter)
        snapshots every `every` LEVELS — trees grow level-synchronously,
        so a level boundary is the natural resumable point (SURVEY §6);
        the PRNG key chain is re-derived from the stored seed so a resumed
        growth is bit-identical to the uninterrupted one.  Checkpointed
        growth reads state to host between chunks (only then)."""
        m, n = x.shape
        depth = self._effective_depth(m)
        fp = digest = None
        if checkpoint is not None:
            from dislib_tpu.utils.checkpoint import (data_digest,
                                                     validate_snapshot)
            tf = self._try_features_count(n)
            rs = self.random_state
            # every knob the grown state depends on is fingerprinted —
            # resuming with a changed seed or feature-sampling width must
            # refuse, not grow a hybrid forest (round-4 review)
            fp = np.asarray([m, n, n_trees, depth, int(bootstrap),
                             float(("gini", "mse").index(self._criterion)),
                             -1.0 if tf is None else float(tf),
                             -1.0 if rs is None else float(rs),
                             float(self._n_bins())], np.float64)
            digest = data_digest(x._data, stats=stats_host)

        n_bins = self._n_bins()
        try_features = self._try_features_count(n)
        # histogram schedule: resolved ONCE here (the fit boundary — the
        # spmm/summa routing precedent, so a DSLIB_OVERLAP flip retraces
        # and the run is `hist:<sched>` counter-observable)
        from dislib_tpu.ops import overlap as _ov
        hist_sched = "pallas" if _ov.resolve(None) == "pallas" else "xla"
        _prof.count_schedule("hist", hist_sched)
        box = {"feats": [], "tbins": [], "x": x}

        def _stage():
            # everything derived from the data layout: binned data, pad
            # width, validity mask, per-sample stats.  Re-run by the
            # elastic rebind after a mesh change — the bins re-derive
            # from the re-laid-out x (the quantile edges depend only on
            # the VALID rows, so they are mesh-independent values on a
            # mesh-dependent canvas)
            xd = box["x"]._data
            mp = xd.shape[0]
            box["edges"] = _quantile_bins(xd, (m, n), n_bins)
            box["bx"] = _bin_data(xd, (m, n), box["edges"])
            box["mp"] = mp
            box["valid"] = (np.arange(mp) < m).astype(
                px.compute_dtype(px.FLOAT32))
            sh = np.asarray(stats_host)
            if sh.shape[0] != mp:       # host re-pad: pad rows carry w=0
                out = np.zeros((mp, sh.shape[1]), sh.dtype)
                out[: min(mp, sh.shape[0])] = sh[:mp]
                sh = out
            box["stats"] = jnp.asarray(sh)            # (mp, S)

        _stage()
        _data_hook = _fitloop.data_rebind(box)

        def rebind(mesh):
            _data_hook(mesh)            # force chains / re-canonicalize x
            if mesh is not None:
                _stage()

        loop = _fitloop.ChunkedFitLoop(
            "forest", checkpoint=checkpoint, health=health,
            max_iter=depth, chunk_iters=1,
            save_every=checkpoint.every if checkpoint is not None else 1,
            # the fused per-level health vector is read at snapshot
            # boundaries only (one sync per chunk, same cadence as the
            # snapshot's own blocking fetches); unchecked growth defers to
            # the adoption-time check
            check_on="save",
            # growth snapshots only resumable mid-points, never the final
            # level (leaves are derived after the loop)
            save_final=False,
            carry_names=("node_totals", "w"), elastic=rebind)

        def _keys_for(seed, lvl):
            # replay the PRNG key chain to `lvl` — a resumed or
            # rolled-back growth stays bit-identical
            key = jax.random.PRNGKey(int(seed))
            k_boot, key = jax.random.split(key)
            for _ in range(lvl):
                key, _ = jax.random.split(key)
            return k_boot, key

        def init(rem):
            if "seed" not in box:       # chosen once; rollbacks replay it
                box["seed"] = self.random_state \
                    if self.random_state is not None \
                    else np.random.randint(0, 2**31 - 1)
            k_boot, box["key"] = _keys_for(box["seed"], 0)
            box["feats"], box["tbins"] = [], []
            mp = box["mp"]
            if bootstrap:
                w = jax.random.poisson(k_boot, 1.0, (n_trees, mp)).astype(
                    px.compute_dtype(px.FLOAT32))
            else:
                w = jnp.ones((n_trees, mp), jnp.float32)
            w = w * jnp.asarray(box["valid"])[None, :]
            if rem.attempt:             # from-scratch rollback perturbs w
                w = jnp.asarray(rem.perturb(_fetch(w)))
            return _fitloop.LoopState(
                (w,), extra=jnp.zeros((n_trees, mp), jnp.int32))

        def restore(snap, rem):
            if "fp" in snap and np.size(snap["fp"]) == len(fp) - 1:
                # pre-n_bins forest snapshot (8-knob fp): the grown state
                # depends on a knob the old fp never recorded
                raise ValueError(
                    "checkpoint was written by a different library "
                    "version (forest fingerprint predates n_bins) — "
                    "delete the snapshot file to restart the fit")
            validate_snapshot(snap, fp, digest)
            box["seed"] = int(snap["seed"])
            lvl = int(snap["lvl"])
            _, box["key"] = _keys_for(box["seed"], lvl)
            # node assignment and bootstrap weights are per-(padded-)sample:
            # re-pad them for THIS mesh's quantum so an 8-device snapshot
            # resumes on a 4-device (or 2-D) mesh — pad columns carry w=0,
            # so zero-filling them is exact (elastic resume)
            node = jnp.asarray(_repad_rows(snap["node"], m, box["mp"],
                                           axis=1))
            w = jnp.asarray(rem.perturb(_repad_rows(snap["w"], m,
                                                    box["mp"], axis=1)))
            box["feats"] = [jnp.asarray(snap[f"feats_{i}"])
                            for i in range(lvl)]
            box["tbins"] = [jnp.asarray(snap[f"tbins_{i}"])
                            for i in range(lvl)]
            return _fitloop.LoopState((w,), it=lvl, extra=node)

        def step(st, chunk):
            box["key"], k_lvl = jax.random.split(box["key"])
            keys = jax.random.split(k_lvl, n_trees)
            (w,) = st.carries
            feat, tbin, is_split, node, _, hvec = _forest_level(
                st.extra, box["bx"], w, box["stats"], keys, 2 ** st.it,
                try_features, 0.0, self._criterion, n_bins,
                hist=hist_sched)
            box["feats"].append(feat)
            box["tbins"].append(tbin)
            nxt = st.it + 1
            return _fitloop.ChunkOutcome(
                _fitloop.LoopState((w,), nxt, nxt == depth, extra=node),
                hvec=hvec)

        def snapshot(st):
            # node is donated to the next level's kernel — its copy must
            # land on host before that dispatch (blocking fetch); only the
            # checksum+file write moves to the snapshot worker
            state = {"lvl": st.it, "seed": box["seed"], "fp": fp,
                     "digest": digest, "node": _fetch(st.extra),
                     "w": _fetch(st.carries[0])}
            # the per-level feats/tbins drain through the shared host-loop
            # pipeline: level i's blocking fetch runs under level i+1's
            # device→host DMA (db/seq bit-equal by construction, routed +
            # counter-observable like every overlap site)
            sched = _ov.resolve()
            _prof.count_schedule("forest_snapshot", sched)
            pairs = list(zip(box["feats"], box["tbins"]))

            def issue(i):
                for buf in pairs[i]:
                    if hasattr(buf, "copy_to_host_async"):
                        buf.copy_to_host_async()
                return pairs[i]

            def drain(i, pair):
                state[f"feats_{i}"] = _fetch(pair[0])
                state[f"tbins_{i}"] = _fetch(pair[1])

            _ov.host_pipeline(len(pairs), issue, drain,
                              overlap=_ov.overlapped(sched))
            return state

        st = loop.run(init=init, step=step, restore=restore,
                      snapshot=snapshot)
        self.fit_info_ = loop.info
        feats, tbins = box["feats"], box["tbins"]
        leaves, leaf_hvec = _leaf_stats(st.extra, st.carries[0],
                                        box["stats"], 2 ** depth)
        # feats/tbins stay as the ragged per-level device arrays: packing
        # here would dispatch eager multi-device pad/stack programs while
        # the level producers are still in flight — on a thread-starved
        # XLA:CPU pool their parked rendezvous participants can starve the
        # producers into a true deadlock (observed round 3).  The pack
        # happens on host at adoption, or traced INSIDE the score kernels.
        # `hvec` rides along so the adoption step (the first host
        # materialisation) can refuse a non-finite forest — the async
        # dispatch-only contract of this function is preserved.
        return {"edges": box["edges"], "feats": tuple(feats),
                "tbins": tuple(tbins),
                "depth": depth, "leaves": leaves, "n_features": n,
                "hvec": leaf_hvec, "guard": loop.guard}

    def _adopt_forest(self, grown):
        """Materialise fitted attributes from a `_grow_forest` handle.
        The ragged per-level (T, 2^lvl) arrays pad+stack to (T, depth,
        2^(depth-1)) in host NumPy — tiny arrays, and no extra device
        programs — so predict calls are a single gather-walk jit.

        Adoption is the first host materialisation of the grown forest,
        so the fused leaf health vector is judged here: a non-finite
        forest raises a typed ``NumericalDivergence`` instead of silently
        serving NaN predictions (rollback is no longer possible at this
        point — the checkpointed growth loop already healed what it
        could)."""
        hvec = grown.get("hvec")
        if hvec is not None:
            g = grown.get("guard") or _health.guard("forest")
            v = g.check(hvec, carry_names=("leaves",),
                        carry_shapes=(np.shape(grown["leaves"]),))
            if not v.ok:
                raise _health.NumericalDivergence(
                    f"forest: health guard {v.guard!r} tripped at adoption "
                    f"— the grown forest is not numerically usable "
                    f"(detail: {v.detail})",
                    estimator="forest", guard=v.guard, detail=v.detail)
        wide = 2 ** (grown["depth"] - 1)

        def _pack(levels):
            # adoption's per-level reads pipeline like the snapshot loop:
            # level i's host landing overlaps level i+1's device→host DMA
            from dislib_tpu.ops import overlap as _ov
            sched = _ov.resolve()
            _prof.count_schedule("forest_adopt", sched)

            def issue(i):
                if hasattr(levels[i], "copy_to_host_async"):
                    levels[i].copy_to_host_async()
                return levels[i]

            host = _ov.host_pipeline(
                len(levels), issue,
                lambda i, a: np.asarray(jax.device_get(a)),
                overlap=_ov.overlapped(sched))
            return np.stack([np.pad(a, ((0, 0), (0, wide - a.shape[1])))
                             for a in host], axis=1)

        self._edges = grown["edges"]
        self._feats = _pack(grown["feats"])
        self._tbins = _pack(grown["tbins"])
        self._depth = grown["depth"]
        self._leaves = grown["leaves"]                 # (T, 2^depth, S)
        self.n_features_ = grown["n_features"]
        return self

    def fit(self, x: Array, y: Array, checkpoint=None, health=None):
        """Shared fit = the async protocol run to completion (one recipe —
        sync and async fits cannot diverge).  ``checkpoint``: see
        `_grow_forest` (per-level snapshots + resume); ``health``: see
        `_grow_forest` (per-chunk fused guards + rollback)."""
        self._fit_finalize(self._fit_async(x, y, checkpoint=checkpoint,
                                           health=health))
        return self

    # async trial protocol (SURVEY §4.5): growth is read-free device
    # dispatch; the handle is the grown-forest dict.  Label/target encoding
    # reads the INPUT y (prep, not fit results) at dispatch time, cached
    # per (y, padding) so a search encodes each fold once, not once per
    # candidate.
    def _fit_async(self, x, y=None, checkpoint=None, health=None):
        if y is None:
            raise ValueError(f"{type(self).__name__} requires y")
        stats = self._encode_stats(x, y)
        n_trees, bootstrap = self._fit_spec()
        return self._grow_forest(x, stats, n_trees, bootstrap,
                                 checkpoint=checkpoint, health=health)

    def _fit_finalize(self, state):
        if state is None:
            return
        self._adopt_forest(state)

    def _apply(self, x: Array):
        return _forest_apply(x._data, x.shape, jnp.asarray(self._edges),
                             jnp.asarray(self._feats), jnp.asarray(self._tbins),
                             self._depth)                   # (T, mq_pad)

    def _check_fitted(self):
        if not hasattr(self, "_leaves"):
            raise RuntimeError(f"{type(self).__name__} is not fitted")
