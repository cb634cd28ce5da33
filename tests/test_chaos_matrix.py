"""Chaos MATRIX (round-12 satellite): every chunked estimator × every
numerical/liveness fault injector, including the tier-targeted
``FaultAtTier`` that defeats exactly N escalation-ladder tiers.  The one
invariant every cell must satisfy is the driver's contract: the fit
either HEALS (finite model) or raises a TYPED diagnostic — never a hang,
never a silently corrupt model.

The full matrix is `slow` (run via ``tools/chaos_soak.sh --matrix``,
which appends the machine-readable ``CHAOS_MATRIX_SUMMARY`` line — per
cell verdicts + the process resilience counters — to the JSONL file it
is given).  A 2-estimator smoke subset rides tier-1, shapes mirroring
``tests/test_health.py`` so its kernels are suite-wide cache hits.

``DSLIB_MATRIX_SEED`` (default 0) seeds the data draws, so a failing
cell reproduces from the printed seed + cell name alone.  Cells that
shrink the mesh (the elastic tier) re-init the default mesh afterwards.
"""

import json
import os
import warnings

import numpy as np
import pytest

import dislib_tpu as ds
from dislib_tpu.cluster import (DBSCAN, Daura, GaussianMixture, KMeans,
                                MiniBatchKMeans)
from dislib_tpu.classification import CascadeSVM
from dislib_tpu.recommendation import ALS
from dislib_tpu.runtime import (NumericalDivergence, Preempted,
                                WatchdogTimeout, clear_preemption)
from dislib_tpu.trees import RandomForestClassifier
from dislib_tpu.utils import FitCheckpoint, faults
from dislib_tpu.utils import profiling as prof
from dislib_tpu.utils.checkpoint import SnapshotCorrupt

TYPED = (Preempted, NumericalDivergence, WatchdogTimeout, SnapshotCorrupt)


def _blobs(rng, n=198, d=4, k=3):
    centers = rng.rand(k, d) * 10
    x = np.vstack([centers[i] + 0.3 * rng.randn(n // k, d) for i in range(k)])
    return x.astype(np.float32)


def _sparse(x_np):
    import scipy.sparse as sp
    from dislib_tpu.data.sparse import SparseArray
    m = x_np.copy()
    m[m < np.median(m)] = 0.0
    return SparseArray.from_scipy(sp.csr_matrix(m))


# name -> rng -> (fit(checkpoint, health) -> estimator, model_of)
def _estimators():
    def kmeans(rng, sparse=False):
        x_np = _blobs(rng)
        init = np.ascontiguousarray(x_np[[0, 70, 140]])
        x = _sparse(x_np) if sparse else ds.array(x_np)
        kw = dict(n_clusters=3, init=init, max_iter=12, tol=0.0)
        return (lambda ck, pol: KMeans(**kw).fit(x, checkpoint=ck,
                                                 health=pol),
                lambda e: e.centers_)

    def minibatch(rng):
        x = ds.array(_blobs(rng, n=192))
        return (lambda ck, pol: MiniBatchKMeans(
                    n_clusters=3, batch_size=64, random_state=0).fit(
                        x, checkpoint=ck, health=pol),
                lambda e: e.centers_)

    def gmm(rng):
        x = ds.array(_blobs(rng, n=150, d=3, k=2))
        kw = dict(n_components=2, max_iter=12, tol=0.0, random_state=0)
        return (lambda ck, pol: GaussianMixture(**kw).fit(x, checkpoint=ck,
                                                          health=pol),
                lambda e: e.means_)

    def als(rng, sparse=False):
        u, v = rng.rand(30, 4), rng.rand(20, 4)
        r = ((u @ v.T) * (rng.rand(30, 20) < 0.6)).astype(np.float32)
        x = _sparse(r) if sparse else ds.array(r)
        kw = dict(n_f=4, max_iter=8, tol=1e-9, random_state=0)
        return (lambda ck, pol: ALS(**kw).fit(x, checkpoint=ck, health=pol),
                lambda e: e.users_)

    def csvm(rng):
        n = 120
        xh = np.vstack([rng.randn(n // 2, 4) - 2,
                        rng.randn(n // 2, 4) + 2]).astype(np.float32)
        yh = np.r_[np.zeros(n // 2), np.ones(n // 2)].astype(np.float32)
        sh = rng.permutation(n)
        x, y = ds.array(xh[sh]), ds.array(yh[sh].reshape(-1, 1))
        kw = dict(cascade_arity=2, c=1.0, kernel="rbf", gamma=0.3,
                  check_convergence=False, max_iter=4)
        return (lambda ck, pol: CascadeSVM(**kw).fit(x, y, checkpoint=ck,
                                                     health=pol),
                lambda e: e._sv_alpha)

    def forest(rng):
        n, k = 240, 3
        centers = rng.rand(k, 6) * 8
        xh = np.vstack([centers[i] + 0.4 * rng.randn(n // k, 6)
                        for i in range(k)]).astype(np.float32)
        yh = np.repeat(np.arange(k), n // k).astype(np.float32)
        p = rng.permutation(n)
        x, y = ds.array(xh[p]), ds.array(yh[p].reshape(-1, 1))
        kw = dict(n_estimators=4, max_depth=6, random_state=7)
        return (lambda ck, pol: RandomForestClassifier(**kw).fit(
                    x, y, checkpoint=ck, health=pol),
                lambda e: np.asarray(e.predict(x).collect()))

    def ivf(rng):
        # round-20 satellite: the retrieval tier rides the matrix — the
        # coarse-quantizer build is a chunked KMeans fit (so every
        # injector lands mid-BUILD), and the model readout is a SEARCH,
        # which must auto-rebind onto whatever mesh the elastic rung
        # left behind (capacity shrink mid-fit/mid-search heals)
        from dislib_tpu.retrieval import IVFIndex
        x_np = _blobs(rng)

        def fit(ck, pol):
            ix = IVFIndex(n_lists=3, nprobe=3, kmeans_max_iter=12,
                          random_state=0)
            return ix.fit(ds.array(x_np), checkpoint=ck, health=pol)

        def readout(e):
            # restore the full mesh FIRST: when the elastic rung shrank
            # the build, this search runs on a mesh the striped buffers
            # were not laid out for — it must transparently re-stripe
            # (never refuse, never tear)
            ds.init()
            dist, _ = e.search(x_np[:8], k=3)
            return np.asarray(dist.collect())

        return fit, readout

    def dbscan(rng):
        x = ds.array(rng.rand(60, 3).astype(np.float32))
        return (lambda ck, pol: DBSCAN(eps=0.5, min_samples=3).fit(
                    x, checkpoint=ck, health=pol),
                lambda e: e.labels_)

    def daura(rng):
        # cutoff tight enough that extraction spans several chunks —
        # a single-chunk fit would end before at_chunk=2 arms and every
        # daura cell would pass vacuously
        x = ds.array(rng.rand(40, 6).astype(np.float32))
        return (lambda ck, pol: Daura(cutoff=0.35).fit(x, checkpoint=ck,
                                                       health=pol),
                lambda e: e.labels_)

    return {
        "kmeans": kmeans,
        "kmeans_sparse": lambda rng: kmeans(rng, sparse=True),
        "minibatch_kmeans": minibatch,
        "gmm": gmm,
        "als": als,
        "als_sparse": lambda rng: als(rng, sparse=True),
        "csvm": csvm,
        "forest": forest,
        "dbscan": dbscan,
        "daura": daura,
        "ivf": ivf,
    }


INJECTORS = {
    "nan": lambda: faults.NaNAtChunk(at_chunk=2),
    "ramp": lambda: faults.DivergenceRamp(at_chunk=2, repeat=False,
                                          grow_limit=1e3),
    "hang": lambda: faults.HangAtChunk(at_chunk=2, hang_s=0.3,
                                       deadline_s=0.05, times=1),
    "trip": lambda: faults.TripAtChunk(at_chunk=2),
    # defeats retry; healed by policy remediation
    "tier1": lambda: faults.FaultAtTier(tiers=1, at_chunk=2),
    # defeats retry AND remediation; healed only by the elastic
    # mesh-shrink tier — round 16: EVERY chunked estimator carries the
    # rebind hook now, so no tier2 cell is allowed to type
    "tier2": lambda: faults.FaultAtTier(tiers=2, at_chunk=2,
                                        max_restarts=3, elastic_attempts=1),
    # defeats the whole ladder; must type, never hang
    "tier3": lambda: faults.FaultAtTier(tiers=3, at_chunk=2,
                                        max_restarts=2),
}


def _run_cell(est_name, inj_name, tmp_path, seed):
    """One matrix cell.  Returns its verdict record; raises on a contract
    violation (silent non-finite model)."""
    ds.init()                   # fresh default mesh (elastic cells shrink it)
    clear_preemption()
    fit, model_of = _estimators()[est_name](np.random.RandomState(seed))
    pol = INJECTORS[inj_name]()
    ck = FitCheckpoint(str(tmp_path / f"{est_name}-{inj_name}.npz"), every=2)
    cell = {"cell": f"{est_name}x{inj_name}"}
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            est = fit(ck, pol)
    except TYPED as e:
        cell["outcome"] = f"typed:{type(e).__name__}"
    else:
        model = np.asarray(model_of(est), np.float64)
        assert np.isfinite(model).all(), \
            f"{cell['cell']} seed {seed}: SILENT NON-FINITE MODEL"
        cell["outcome"] = "healed"
        info = getattr(est, "fit_info_", None)
        if info:
            cell["rollbacks"] = info["rollbacks"]
            cell["mesh_shrinks"] = info["mesh_shrinks"]
            cell["mesh_grows"] = info.get("mesh_grows", 0)
    finally:
        clear_preemption()
        ds.init()
    cell["fired"] = int(getattr(pol, "fired", getattr(pol, "stalls", 0)))
    return cell


@pytest.mark.slow
def test_chaos_matrix_full(tmp_path, monkeypatch):
    monkeypatch.setenv("DSLIB_RETRY_BACKOFF", "0")
    seed = int(os.environ.get("DSLIB_MATRIX_SEED", "0"))
    cells = {}
    healed = typed = 0
    for est_name in _estimators():
        for inj_name in INJECTORS:
            cell = _run_cell(est_name, inj_name, tmp_path, seed)
            cells[cell.pop("cell")] = cell
            if cell["outcome"] == "healed":
                healed += 1
            else:
                typed += 1
    summary = {"metric": "chaos_matrix", "seed": seed,
               "healed": healed, "typed": typed,
               "cells": cells,
               "resilience": prof.resilience_counters()}
    print("CHAOS_MATRIX_SUMMARY " + json.dumps(summary))
    # heal-or-type on EVERY cell is asserted inside _run_cell; the
    # ladder's top tier must actually have been exercised somewhere
    assert healed + typed == len(_estimators()) * len(INJECTORS)
    assert any(c.get("mesh_shrinks") for c in cells.values()), \
        "no cell escalated to the elastic mesh-shrink tier"
    # round 16: every chunked estimator carries a rebind hook, so the
    # elastic rung HEALS everywhere — a typed tier2 cell is a regression
    bad = [k for k, c in cells.items()
           if k.endswith("xtier2") and c["outcome"] != "healed"]
    assert not bad, f"elastic rung failed to heal: {bad}"


def test_chaos_matrix_smoke(tmp_path, monkeypatch):
    """Tier-1 subset: 2 estimators (the reference chunked fit and the
    zero-bespoke-resilience streaming one) × {carry poison, ladder
    escalation} — the contract stays pinned without the slow sweep."""
    monkeypatch.setenv("DSLIB_RETRY_BACKOFF", "0")
    seed = int(os.environ.get("DSLIB_MATRIX_SEED", "0"))
    for est_name, inj_name in (("kmeans", "nan"), ("kmeans", "tier1"),
                               ("minibatch_kmeans", "nan"),
                               ("minibatch_kmeans", "hang")):
        cell = _run_cell(est_name, inj_name, tmp_path, seed)
        assert cell["outcome"] == "healed", cell
        assert cell["fired"] >= 1, f"{cell}: fault was never injected"
