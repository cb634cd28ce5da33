#!/usr/bin/env python3
"""chip_smoke.py — drive dslib's main path once on the accelerator.

One process, which holds the chip for the whole run; no child touches JAX.
It drives the library boundary a user drives — ingest, scaler, a
checkpointed KMeans fit, a Gaussian mixture fit, a randomized SVD, the
predict server, a deployment bundle, and the three Pallas kernels inside
their callers — at the full width of the north-star model of
``BASELINE.json`` (KMeans 1 000 000 x 100, k=10), of its mixture
(1 000 000 x 50, k=16) and of its randomized SVD's sketch (256 of 1024
columns), and checks every result by the repo's own means (a NumPy Lloyd
oracle, a NumPy EM oracle, NumPy's singular values in float64, the
direct predict path, bit-equality across a bundle round
trip, the XLA schedule of each kernel inside
``ops/precision.ERROR_BOUNDS``).

    python chip_smoke.py              # on a TPU: exit 0 and the result line
    python chip_smoke.py --rehearsal  # CPU dry run: tiny size, Pallas
                                      # interpreted, every line stamped so

Output: one JSON line per phase — ``platform``, ``device_kind``,
``n_devices``, wall seconds of the first call (compile included) and of a
warm call, ``peak_bytes_in_use`` per device — then, as the LAST line of
stdout, ``{"ok": true, "device": {"platform", "kind", "count"}}`` with
the device as JAX reports it.  With no accelerator (and no ``--rehearsal``)
it prints no result and exits 3.  A phase that fails raises: nothing is
caught and carried past, so the exit code is non-zero and no result line
is printed.  Walls here are bring-up facts, not benchmark numbers.

With more than one device the same run also establishes that every
operand is born sharded (no device holds the whole array), that the
compiled fit reduces over the ``rows`` axis, that per-device peak memory
is balanced, and — on four devices — that the 2x2 mesh's ``cols`` axis
carries the SUMMA collectives at 16384^2.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

# the north-star configuration of BASELINE.json, and the kernel shapes the
# routers produce at BASELINE sizes: SUMMA's 8192^2 panels of a 16384^2
# product on a 2x2 mesh, a ring eps-pass over the 200000x10 DBSCAN config,
# a forest level at 100000x20
FULL = dict(m=1_000_000, n=100, k=10, buckets=(1, 8, 64, 512),
            request_rows=(1, 3, 8, 17, 64, 200, 512), n_requests=36,
            summa_panel=8192, ring=(200_000, 10), forest=(100_000, 20),
            forest_trees=4, forest_nodes=8, mixture=(1_000_000, 50, 16),
            rsvd=(262_144, 1024, 128, 128, 512, 0.98))
# the same phases at a size the CPU backend and the Pallas interpreter
# finish in seconds
REHEARSAL = dict(m=4_000, n=20, k=4, buckets=(1, 8),
                 request_rows=(1, 3, 8), n_requests=9,
                 summa_panel=128, ring=(96, 5), forest=(200, 3),
                 forest_trees=2, forest_nodes=4, mixture=(4_000, 10, 3),
                 rsvd=(6_000, 96, 12, 12, 48, 0.9))
REHEARSAL_DEVICES = 4           # mirrors the four-chip host
GATE_TOL = 2e-3                 # device vs NumPy Lloyd
EM_GATE_TOL = 1e-4              # device vs NumPy EM (float64), two iterations
RSVD_VALUES_TOL = 1e-4          # device S vs NumPy's (float64), over S[0]
RSVD_ORTH_TOL = 5e-6            # max |U^T U - I|, summed in float64
BALANCE_MAX = 1.5               # per-device peak bytes, max over min


def lloyd_step(x, centers):
    """One NumPy Lloyd iteration — the reference the device fit is held
    to."""
    d = (x * x).sum(1)[:, None] - 2.0 * (x @ centers.T) \
        + (centers * centers).sum(1)[None]
    labels = d.argmin(1)
    onehot = np.zeros((x.shape[0], centers.shape[0]), x.dtype)
    onehot[np.arange(x.shape[0]), labels] = 1.0
    counts = onehot.sum(0)
    sums = onehot.T @ x
    return np.where(counts[:, None] > 0,
                    sums / np.maximum(counts, 1)[:, None], centers)


def e_step(x, weights, means, covs):
    """The E-step of a NumPy EM with full covariances, in float64:
    ``(log pi_j N(x | mu_j, Sigma_j), log sum_j of it)`` per row."""
    d = x.shape[1]
    logp = np.empty((x.shape[0], len(weights)))
    for j, (w, mu, cov) in enumerate(zip(weights, means, covs)):
        prec = np.linalg.inv(np.linalg.cholesky(cov)).T     # upper, P P^T
        y = (x - mu) @ prec
        logp[:, j] = np.log(w) + np.log(np.diag(prec)).sum() \
            - 0.5 * d * np.log(2 * np.pi) - 0.5 * np.einsum("ij,ij->i", y, y)
    top = logp.max(1, keepdims=True)
    return logp, top[:, 0] + np.log(np.exp(logp - top).sum(1))


def em_step(x, weights, means, covs, reg_covar=1e-6):
    """One NumPy EM iteration with full covariances, in float64 — the
    reference the device mixture fit is held to: ``(weights, means, covs,
    lower bound at the parameters that came in)``."""
    n, d = x.shape
    logp, lse = e_step(x, weights, means, covs)
    resp = np.exp(logp - lse[:, None])
    nk = resp.sum(0) + 1e-10
    new_means = resp.T @ x / nk[:, None]
    new_covs = np.empty((len(weights), d, d))
    for j, (mu, n_j) in enumerate(zip(new_means, nk)):
        diff = x - mu
        new_covs[j] = (diff * resp[:, j:j + 1]).T @ diff / n_j \
            + reg_covar * np.eye(d)
    return nk / n, new_means, new_covs, lse.mean()


class Run:
    """The run's stamp and the state the phases hand to each other."""

    def __init__(self, cfg, rehearsal, fail_phase):
        self.cfg = cfg
        self.rehearsal = rehearsal
        self.fail_phase = fail_phase
        self.stamp = {}
        self.state = {}

    def peak_bytes(self):
        import jax
        stats = [d.memory_stats() for d in jax.devices()]
        return [s.get("peak_bytes_in_use") if s else None for s in stats]

    def phase(self, name, fn):
        """Run one phase and print its stamped line.  No ``try``: a phase
        that raises ends the run with a traceback and a non-zero exit."""
        if name == self.fail_phase:
            raise RuntimeError(f"phase {name!r} forced to fail (--fail-phase)")
        t0 = time.perf_counter()
        facts = fn(self)
        line = {"phase": name, "ok": True, **self.stamp,
                "first_call_s": None, "warm_call_s": None, **facts,
                "phase_s": round(time.perf_counter() - t0, 3),
                "peak_bytes_in_use": self.peak_bytes()}
        print(json.dumps(line), flush=True)


def _wall(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 4)


def _routes(prof, kernel):
    """Which routes ``kernel``'s traces took since the counters' reset:
    the names ``profiling.schedule_counters()`` holds after ``kernel:``."""
    return sorted(key.split(":", 1)[1] for key in prof.schedule_counters()
                  if key.startswith(kernel + ":"))


def _balanced(peaks, what):
    """Per-device peak bytes within BALANCE_MAX of each other — device 0
    must not have held the whole operand on its way to the mesh."""
    if len(peaks) < 2 or any(p is None for p in peaks):
        return None
    ratio = max(peaks) / max(min(peaks), 1)
    assert ratio <= BALANCE_MAX, \
        f"{what}: per-device peak bytes unbalanced {peaks} ({ratio:.2f}x)"
    return round(ratio, 3)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(run):
    import jax
    import dislib_tpu as ds
    from dislib_tpu import native

    dev = jax.devices()[0]
    run.stamp = {"platform": dev.platform, "device_kind": dev.device_kind,
                 "n_devices": len(jax.devices())}
    if run.rehearsal:
        run.stamp["rehearsal"] = True
    mesh = ds.init()
    return {"backend": jax.default_backend(),
            "mesh_shape": [int(mesh.shape["rows"]), int(mesh.shape["cols"])],
            "compile_cache_dir": run.state["cache_dir"],
            "native_loaded": native.get_lib() is not None,
            "native_build_error": native.build_error(),
            "jax": jax.__version__}


def phase_train(run):
    import jax
    import jax.numpy as jnp
    import dislib_tpu as ds
    from dislib_tpu.cluster.kmeans import _kmeans_fit
    from dislib_tpu.utils import profiling as prof
    from dislib_tpu.utils.checkpoint import FitCheckpoint

    cfg, n_dev = run.cfg, run.stamp["n_devices"]
    m, n, k = cfg["m"], cfg["n"], cfg["k"]
    rng = np.random.RandomState(0)
    x_host = rng.rand(m, n).astype(np.float32)
    run.state["x_host"] = x_host

    # -- ingest: host data lands as one shard per device
    def ingest():
        arr = ds.array(x_host)
        jax.block_until_ready(arr._data)
        return arr
    x, ingest_s = _wall(ingest)
    shards = x._data.addressable_shards
    assert len(x._data.sharding.device_set) == n_dev, x._data.sharding
    assert all(s.data.shape[0] == x._data.shape[0] // n_dev
               for s in shards), [s.data.shape for s in shards]
    peak_ingest = run.peak_bytes()
    ingest_balance = _balanced(peak_ingest, "after ingest")

    # -- scaler: device moments against NumPy's
    scaler = ds.StandardScaler().fit(x)
    mean_h = x_host.mean(0, dtype=np.float64)
    std_h = x_host.std(0, dtype=np.float64)
    np.testing.assert_allclose(scaler.mean_.collect().ravel(), mean_h,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(scaler.var_.collect().ravel(), std_h ** 2,
                               rtol=1e-3, atol=1e-6)
    xs = scaler.transform(x).force()
    assert len(xs._data.sharding.device_set) == n_dev
    xs_host = ((x_host - mean_h) / std_h).astype(np.float32)
    init = xs_host[rng.choice(m, k, replace=False)].copy()

    # -- gate: ONE device iteration against the NumPy Lloyd step
    one = ds.KMeans(n_clusters=k, init=init, max_iter=1, tol=0).fit(xs)
    np.testing.assert_allclose(one.centers_, lloyd_step(xs_host, init),
                               rtol=GATE_TOL, atol=GATE_TOL)

    # -- the fit: two chunks of ChunkedFitLoop, health vector, async snapshot
    prof.reset_counters()
    with tempfile.TemporaryDirectory(prefix="dslib-smoke-") as td:
        def fit(tag):
            ckpt = FitCheckpoint(os.path.join(td, f"km-{tag}.npz"), every=5)
            km = ds.KMeans(n_clusters=k, init=init, max_iter=10, tol=0) \
                .fit(xs, checkpoint=ckpt)
            assert os.path.exists(ckpt.path), "no snapshot was written"
            return km
        km, first_s = _wall(lambda: fit("first"))
        _, warm_s = _wall(lambda: fit("warm"))
    info = km.fit_info_
    assert km.n_iter_ == 10, km.n_iter_
    assert info["chunks"] == 2, info
    assert info["rollbacks"] == 0 and not any(info["escalations"].values()) \
        and not info["mesh_shrinks"] and not info["mesh_grows"], info
    assert not prof.resilience_counters(), prof.resilience_counters()
    hist = np.asarray(km.history_)
    assert hist.shape == (10,) and np.all(np.isfinite(hist)), hist
    assert np.all(np.diff(hist) <= 1e-5 * np.abs(hist[:-1])), \
        f"inertia increased: {hist}"
    assert np.all(np.isfinite(km.centers_)) and km.centers_.shape == (k, n)
    # ten NumPy Lloyd steps from the same start: the same model, whatever
    # the mesh — which is also how a one-chip and a four-chip run agree
    want = init
    for _ in range(10):
        want = lloyd_step(xs_host, want)
    np.testing.assert_allclose(km.centers_, want, rtol=GATE_TOL,
                               atol=GATE_TOL)
    center_err = float(np.abs(km.centers_ - want).max())

    # -- several devices: the compiled fit reduces over 'rows' and never
    # gathers the operand
    collectives = None
    if n_dev > 1:
        hlo = _kmeans_fit.lower(xs._data, xs.shape, jnp.asarray(init), 5,
                                0.0, fast=False).compile().as_text()
        assert "all-reduce" in hlo, "no all-reduce in the compiled fit"
        assert "all-gather" not in hlo and "all-to-all" not in hlo, \
            "the compiled fit gathers"
        collectives = {"all-reduce": hlo.count(" all-reduce(")
                       + hlo.count(" all-reduce-start(")}
    fit_balance = _balanced(run.peak_bytes(), "after fit")

    run.state.update(scaler=scaler, km=km)
    return {"first_call_s": first_s, "warm_call_s": warm_s,
            "ingest_s": ingest_s, "shape": [m, n], "k": k,
            "shard_rows": int(shards[0].data.shape[0]),
            "peak_bytes_after_ingest": peak_ingest,
            "peak_balance_after_ingest": ingest_balance,
            "peak_balance_after_fit": fit_balance,
            "fit_info": {"chunks": info["chunks"],
                         "rollbacks": info["rollbacks"]},
            "inertia": float(km.inertia_),
            "centers_max_abs_err_vs_numpy": center_err,
            # which Lloyd step the fit's traces took: the fused kernel on
            # a TPU, the two XLA passes under the interpreter's backend
            "kmeans_step": _routes(prof, "kmeans_step"),
            "collectives": collectives}


def phase_mixture(run):
    """``GaussianMixture`` with full covariances at the width of
    ``BASELINE.json``'s configuration (1M x 50, k = 16): two EM iterations
    from an explicit start against NumPy's in float64, ``score`` and
    ``predict`` through the same blocked E-step, the step the program's
    traces took, and on several devices its one all-reduce a step."""
    import jax
    import jax.numpy as jnp
    import dislib_tpu as ds
    from dislib_tpu.cluster.gm import _gm_fit
    from dislib_tpu.ops.base import em_block
    from dislib_tpu.utils import profiling as prof

    n_dev = run.stamp["n_devices"]
    m, d, k = run.cfg["mixture"]
    rng = np.random.RandomState(1)
    mu = 2.0 * rng.rand(k, d)
    factors = np.eye(d) + rng.randn(k, d, d) / (2.0 * np.sqrt(d))
    comp = rng.randint(0, k, m)
    x_host = np.empty((m, d), np.float32)
    for j in range(k):
        at = comp == j
        x_host[at] = mu[j] + rng.randn(int(at.sum()), d) @ factors[j]
    x = ds.array(x_host)
    local = x._data.shape[0] // n_dev
    block = em_block(local, d, k)
    start = dict(weights_init=np.full(k, 1.0 / k, np.float32),
                 means_init=(mu + 0.5 * rng.randn(k, d)).astype(np.float32),
                 precisions_init=np.tile(np.eye(d, dtype=np.float32),
                                         (k, 1, 1)))

    prof.reset_counters()

    def fit():
        return ds.GaussianMixture(n_components=k, max_iter=2, tol=0.0,
                                  **start).fit(x)
    gm, first_s = _wall(fit)
    _, warm_s = _wall(fit)
    steps, e_steps, m_steps = (_routes(prof, key) for key in
                               ("gm_step", "gm_e_step", "gm_m_step"))

    want = (start["weights_init"].astype(np.float64),
            start["means_init"].astype(np.float64),
            np.tile(np.eye(d), (k, 1, 1)))
    x64, bounds = x_host.astype(np.float64), []
    for _ in range(2):
        *want, bound = em_step(x64, *want)
        bounds.append(bound)
    np.testing.assert_allclose(gm.history_, bounds, rtol=EM_GATE_TOL)
    np.testing.assert_allclose(gm.weights_, want[0], rtol=EM_GATE_TOL,
                               atol=EM_GATE_TOL)
    np.testing.assert_allclose(gm.means_, want[1], rtol=EM_GATE_TOL,
                               atol=EM_GATE_TOL)
    np.testing.assert_allclose(gm.covariances_, want[2], rtol=EM_GATE_TOL,
                               atol=EM_GATE_TOL)
    logp, lse = e_step(x64, *want)
    bound, labels = lse.mean(), logp.argmax(1)
    assert abs(gm.score(x) - bound) <= EM_GATE_TOL * abs(bound)
    got = gm.predict(x).collect().ravel()
    agree = float(np.mean(got == labels))
    assert agree >= 0.9999, f"predict agrees with NumPy on {agree} of rows"

    collectives = None
    if n_dev > 1:
        hlo = _gm_fit.lower(
            x._data, x.shape, k, "full", 1e-6, 0.0, 2,
            tuple(jnp.asarray(a) for a in (gm.weights_, gm.means_,
                                           gm.covariances_))
        ).compile().as_text()
        assert "all-gather" not in hlo and "all-to-all" not in hlo, \
            "the compiled mixture fit gathers"
        collectives = {"all-reduce": hlo.count(" all-reduce(")
                       + hlo.count(" all-reduce-start(")}
        assert collectives["all-reduce"] == 1, collectives
    return {"first_call_s": first_s, "warm_call_s": warm_s,
            "shape": [m, d], "k": k, "block_rows": block,
            "ragged_last_block": bool(local % block),
            "lower_bound": float(gm.lower_bound_),
            "means_max_abs_err_vs_numpy":
                float(np.abs(gm.means_ - want[1]).max()),
            "covariances_max_abs_err_vs_numpy":
                float(np.abs(gm.covariances_ - want[2]).max()),
            "predict_agreement": agree,
            "gm_step": steps, "gm_e_step": e_steps, "gm_m_step": m_steps,
            "collectives": collectives,
            "peak_balance_after_fit": _balanced(run.peak_bytes(),
                                                "after the mixture fit")}


def phase_rsvd(run):
    """``ds.random_svd`` at the sketch width of the benchmark's cell (one
    256-column block) and as many rows as the host can factor in float64:
    S against NumPy's singular values, U's orthogonality summed in
    float64, one dispatch a call, the shard-local factorisation the
    traces took (``tsqr_local``) and how Q was assembled
    (``tsqr_assemble``).  The rank is half the sketch, so that
    two power iterations bring the kept values to float32's accuracy
    (the cell keeps 246 of 256, and is held to a reference of the same
    algorithm instead)."""
    import dislib_tpu as ds
    from dislib_tpu.utils import profiling as prof

    m, n, nsv, over, directions, ratio = run.cfg["rsvd"]
    rng = np.random.RandomState(2)
    w = np.linalg.qr(rng.randn(n, directions))[0]
    mix = ((ratio ** np.arange(directions))[:, None] * w.T)
    x_host = (rng.randn(m, directions).astype(np.float32)
              @ mix.astype(np.float32)
              + 1e-5 * rng.randn(m, n).astype(np.float32))
    x = ds.array(x_host)
    prof.reset_counters()

    def call():
        u, s, v = ds.random_svd(x, iters=2, nsv=nsv, oversample=over,
                                random_state=7)
        s, v = s.collect(), v.collect()
        u.block_until_ready()
        return u, s, v
    (u, s, v), first_s = _wall(call)
    _, warm_s = _wall(call)
    assert prof.counters()["dispatch_by"] == {"random_svd": 2}
    routes = _routes(prof, "tsqr_local")
    assembled = _routes(prof, "tsqr_assemble")
    assert assembled == ["folded"], assembled

    x64 = x_host.astype(np.float64)
    want = np.sqrt(np.linalg.eigvalsh(x64.T @ x64)[::-1][:nsv])
    values_gap = float(np.abs(np.ravel(s) - want).max() / want[0])
    assert values_gap <= RSVD_VALUES_TOL, values_gap
    u64 = np.asarray(u.collect(), np.float64)
    orth = float(np.abs(u64.T @ u64 - np.eye(nsv)).max())
    assert orth <= RSVD_ORTH_TOL, orth
    v64 = np.asarray(v, np.float64)
    resid = float(np.linalg.norm(x64 @ v64 - u64 * np.ravel(s))
                  / np.linalg.norm(want))
    return {"first_call_s": first_s, "warm_call_s": warm_s,
            "shape": [m, n], "nsv": nsv, "sketch": nsv + over,
            "values_gap_vs_numpy": values_gap, "u_orthogonality": orth,
            "residual": resid, "tsqr_local": routes,
            "tsqr_assemble": assembled}


def phase_serve(run):
    import dislib_tpu as ds
    from dislib_tpu.serving import PredictServer, ServePipeline
    from dislib_tpu.utils import profiling as prof

    cfg = run.cfg
    x_host, scaler, km = (run.state[key] for key in ("x_host", "scaler",
                                                     "km"))
    pipe = ServePipeline(km, transforms=(scaler,))
    rng = np.random.RandomState(1)
    sizes = [cfg["request_rows"][i % len(cfg["request_rows"])]
             for i in range(cfg["n_requests"])]
    starts = rng.randint(0, x_host.shape[0] - max(sizes), len(sizes))
    reqs = [x_host[s:s + sz] for s, sz in zip(starts, sizes)]

    srv = PredictServer(pipeline=pipe, buckets=cfg["buckets"])
    _, warm_ladder_s = _wall(srv.start)           # AOT-warms every bucket
    try:
        prof.reset_counters()
        outs = [f.result(timeout=300) for f in [srv.submit(r) for r in reqs]]
        _, one_s = _wall(lambda: srv.predict(reqs[0]))
        traces = prof.trace_count()
        st = srv.stats()
    finally:
        srv.stop()
    assert traces == 0, f"{traces} traces after start()"
    assert st["dispatches_per_batch_max"] == 1, st
    assert st["requests"] == len(reqs) + 1 and st["shed"] == 0, st
    # every response equals the direct (per-call) predict path
    for rows, out in zip(reqs, outs):
        direct = km.predict(scaler.transform(ds.array(rows))).collect()
        np.testing.assert_array_equal(out.values, np.asarray(direct))
    run.state["pipe"] = pipe
    return {"first_call_s": warm_ladder_s, "warm_call_s": one_s,
            "buckets": list(cfg["buckets"]), "requests": st["requests"],
            "batches": st["batches"], "traces_after_start": traces,
            "dispatches_per_batch_max": st["dispatches_per_batch_max"]}


def phase_bundle(run):
    import jax
    from dislib_tpu.serving import export_bundle, load_bundle
    from dislib_tpu.utils import profiling as prof

    cfg, pipe, x_host = run.cfg, run.state["pipe"], run.state["x_host"]
    buckets = cfg["buckets"]
    probes = {b: x_host[7: 7 + b] for b in buckets}
    with tempfile.TemporaryDirectory(prefix="dslib-smoke-") as td:
        path = os.path.join(td, "model.dsb.npz")
        _, export_s = _wall(lambda: export_bundle(pipe, path,
                                                  buckets=buckets))
        want = {b: pipe.predict_bucket(probes[b], b) for b in buckets}
        size = os.path.getsize(path)
        # a fresh process's state: no jit cache, and NO build= — if the
        # executables cannot be deserialized here this raises instead of
        # quietly recompiling
        jax.clear_caches()
        prof.reset_counters()
        lb, load_s = _wall(lambda: load_bundle(path))
        assert not lb.fallback, lb
        got, first_s = _wall(lambda: {b: lb.pipeline.predict_bucket(
            probes[b], b) for b in buckets})
        _, warm_s = _wall(lambda: lb.pipeline.predict_bucket(
            probes[buckets[-1]], buckets[-1]))
        traces = prof.trace_count()
    assert traces == 0, f"{traces} traces serving from the bundle"
    for b in buckets:
        np.testing.assert_array_equal(got[b], want[b])
    return {"first_call_s": first_s, "warm_call_s": warm_s,
            "export_s": export_s, "load_s": load_s, "bundle_bytes": size,
            "buckets": list(buckets), "traces_after_load": traces,
            "fallback": lb.fallback}


def _rel_entry_err(got, want, a, b, k):
    """ERROR_BOUNDS' matmul metric: max |C - C_ref| over
    ||A||_F ||B||_F / sqrt(k)."""
    import jax.numpy as jnp
    scale = jnp.linalg.norm(a) * jnp.linalg.norm(b) / np.sqrt(k)
    return float(jnp.max(jnp.abs(got - want)) / scale)


def phase_kernels(run):
    """Each Pallas kernel inside the wrapper its caller puts it in, at
    the block shapes the routers produce at BASELINE sizes, against the
    XLA schedule of the same call.  On a TPU ``interpret`` is False: a
    kernel Mosaic refuses raises here, naming the kernel."""
    import jax
    import jax.numpy as jnp
    import dislib_tpu as ds
    from dislib_tpu.ops import pallas_kernels as pk
    from dislib_tpu.ops import precision as px
    from dislib_tpu.ops.ring import ring_neigh_count_min
    from dislib_tpu.ops.summa import summa_matmul
    from dislib_tpu.parallel import mesh as _mesh
    from dislib_tpu.trees.decision_tree import N_BINS, _forest_level
    from dislib_tpu.utils import profiling as prof

    cfg, n_dev = run.cfg, run.stamp["n_devices"]
    if n_dev >= 4:
        ds.init((2, 2))         # the mesh SUMMA routes on: both axes > 1
    mesh = _mesh.get_mesh()
    rows, cols = _mesh.mesh_shape(mesh)
    assert pk._interpret() == (jax.default_backend() != "tpu")
    bound = px.ERROR_BOUNDS[("matmul", "float32")]
    out = {"mesh_shape": [rows, cols], "interpret": pk._interpret()}

    # -- panel_gemm: SUMMA's panel loop, panels of summa_panel^2 per device
    dim = cfg["summa_panel"] * max(rows, cols)
    a = ds.random_array((dim, dim), random_state=0).force()
    b = ds.random_array((dim, dim), random_state=1).force()

    def summa(sched):
        return jax.block_until_ready(summa_matmul(
            a._data, b._data, mesh, px.FLOAT32, overlap=sched))
    prof.reset_counters()
    got, first_s = _wall(lambda: summa("pallas"))
    _, warm_s = _wall(lambda: summa("pallas"))
    err = _rel_entry_err(got, summa("db"), a._data, b._data, dim)
    assert err <= bound, f"dslib_panel_gemm vs XLA: {err} > {bound}"
    fetches = prof.schedule_counters().get("summa_fetch:direct")
    assert fetches == 3, prof.schedule_counters()
    out["panel_gemm"] = {"shape": [dim, dim], "first_call_s": first_s,
                         "warm_call_s": warm_s, "err": err, "bound": bound,
                         "summa_fetch:direct": fetches}
    del a, b, got

    # -- distances_sq: the ring eps-pass (features gathered over 'cols',
    # shards rotating over 'rows', 2048-row tiles)
    m_r, n_r = cfg["ring"]
    x = ds.random_array((m_r, n_r), random_state=2).force()
    mp = x._data.shape[0]
    ids = jax.device_put(jnp.arange(mp, dtype=jnp.int32),
                         jax.sharding.NamedSharding(
                             mesh, jax.sharding.PartitionSpec(_mesh.ROWS)))
    eps2 = jnp.float32(0.25 * n_r / 6.0)    # well inside the d^2 spread

    def ring(sched):
        return jax.block_until_ready(ring_neigh_count_min(
            x._data, eps2, ids, ids < m_r, jnp.int32(mp), mesh,
            overlap=sched))
    (cnt_p, _), first_s = _wall(lambda: ring("pallas"))
    _, warm_s = _wall(lambda: ring("pallas"))
    cnt_x, _ = ring("db")
    # a pair whose d^2 sits within rounding of eps^2 may fall either side
    # under a different GEMM tiling: counts agree but for such pairs
    differ = float(jnp.mean((cnt_p != cnt_x)[:m_r]))
    assert differ <= 1e-3, f"dslib_distances_sq: {differ} of rows differ"
    assert int(jnp.max(jnp.abs(cnt_p - cnt_x))) <= 2
    # and the distances themselves, one tile pair on one device (a Mosaic
    # kernel outside a shard_map cannot be partitioned over a mesh)
    t = min(2048, m_r)
    x_h = np.asarray(x._data[:, :n_r])
    xa, xb = jnp.asarray(x_h[:t]), jnp.asarray(x_h[-t:])
    d_p = jax.jit(lambda u, v: pk.distances_sq(u, v, precision="highest"))(
        xa, xb)
    d_x = jax.jit(lambda u, v: jnp.maximum(
        jnp.sum(u * u, 1)[:, None] - 2.0 * jnp.matmul(
            u, v.T, precision="highest") + jnp.sum(v * v, 1)[None], 0.0))(
        xa, xb)
    err = _rel_entry_err(d_p, d_x, xa, xb, n_r)
    assert err <= bound, f"dslib_distances_sq vs XLA: {err} > {bound}"
    out["distances_sq"] = {"shape": [m_r, n_r], "first_call_s": first_s,
                           "warm_call_s": warm_s, "rows_differing": differ,
                           "tile_err": err, "bound": bound}
    del x

    # -- node_histogram: one forest level (vmap over trees inside jit)
    m_f, n_f = cfg["forest"]
    trees, nodes = cfg["forest_trees"], cfg["forest_nodes"]
    rng = np.random.RandomState(3)
    # as in the forest fit: the binned data is row-sharded like the
    # operand it was binned from, the per-tree state is not committed
    bx = jax.device_put(rng.randint(0, N_BINS, (m_f, n_f)).astype(np.int32),
                        _mesh.row_sharding(mesh))
    node_h = rng.randint(0, nodes, (trees, m_f)).astype(np.int32)
    w = jnp.asarray(rng.poisson(1.0, (trees, m_f)).astype(np.float32))
    stats = jnp.asarray(np.eye(3, dtype=np.float32)[rng.randint(0, 3, m_f)])
    keys = jax.random.split(jax.random.PRNGKey(0), trees)

    def level(sched):
        # `node` is donated to the kernel: hand each call its own copy
        return jax.block_until_ready(_forest_level(
            jnp.asarray(node_h), bx, w, stats, keys, nodes, None, 0.0,
            "gini", N_BINS, hist=sched)[:5])
    lv_p, first_s = _wall(lambda: level("pallas"))
    _, warm_s = _wall(lambda: level("pallas"))
    lv_x = level("xla")
    for name, p_, x_ in zip(("feat", "tbin", "is_split", "node", "totals"),
                            lv_p, lv_x):
        # integer-valued contributions: the sums are exact either way
        np.testing.assert_array_equal(
            np.asarray(p_), np.asarray(x_),
            err_msg=f"dslib_node_histogram: level output {name!r} differs")
    out["node_histogram"] = {"shape": [m_f, n_f], "trees": trees,
                             "nodes": nodes, "first_call_s": first_s,
                             "warm_call_s": warm_s, "bit_equal": True}
    return out


def phase_summa(run):
    """Four devices only: one ``ds.matmul`` at the size and on the mesh
    where it takes the SUMMA route — the first collectives the ``cols``
    axis has carried."""
    import dislib_tpu as ds
    from dislib_tpu.parallel import mesh as _mesh
    from dislib_tpu.utils import profiling as prof

    dim = 2 * run.cfg["summa_panel"]
    assert _mesh.mesh_shape(None) == (2, 2), _mesh.mesh_shape(None)
    rng = np.random.RandomState(4)
    a_h = rng.rand(dim, dim).astype(np.float32)
    a = ds.array(a_h)
    prof.reset_counters()

    def mm():
        c = ds.matmul(a, a).force()
        return c, np.asarray(c._data[:dim, :64])
    (c, stripe), first_s = _wall(mm)
    _, warm_s = _wall(mm)
    routed = prof.schedule_counters()
    assert any(key.startswith("summa_matmul:") for key in routed), routed
    assert routed.get("summa_fetch:direct") == 2, routed
    assert len(c._data.sharding.device_set) == 4
    np.testing.assert_allclose(stripe, a_h @ a_h[:, :64], rtol=1e-4)
    return {"first_call_s": first_s, "warm_call_s": warm_s,
            "shape": [dim, dim], "mesh_shape": [2, 2], "schedules": routed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dry run at a tiny size, Pallas interpreted; "
                         "every line is stamped rehearsal / platform cpu")
    ap.add_argument("--fail-phase", default=None, metavar="PHASE",
                    help="make PHASE raise (proves a failed phase fails "
                         "the run)")
    args = ap.parse_args(argv)

    if args.rehearsal:
        # before any backend exists: CPU, with the four-chip host's shape
        from dislib_tpu.runtime import xla_flags
        xla_flags.force_host_platform_device_count(REHEARSAL_DEVICES)
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import dislib_tpu as ds

    cache_dir = ds.runtime.compile_cache.enable()
    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearsal:
        print(f"chip_smoke: no accelerator — jax.default_backend() is "
              f"{backend!r}, not 'tpu' (the CPU dry run is --rehearsal)",
              file=sys.stderr)
        return 3
    if args.rehearsal and backend != "cpu":
        raise RuntimeError(f"rehearsal must run on cpu, got {backend!r}")

    run = Run(REHEARSAL if args.rehearsal else FULL, args.rehearsal,
              args.fail_phase)
    run.state["cache_dir"] = cache_dir
    run.phase("device", phase_device)
    run.phase("train", phase_train)
    run.phase("mixture", phase_mixture)
    run.phase("rsvd", phase_rsvd)
    run.phase("serve", phase_serve)
    run.phase("bundle", phase_bundle)
    run.phase("kernels", phase_kernels)
    if run.stamp["n_devices"] >= 4:
        run.phase("summa", phase_summa)

    dev = jax.devices()[0]
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(jax.devices())}}
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
