"""Benchmark harness — the BASELINE.json configs plus the estimator tiers.

Prints ONE JSON line per config, most-important (north-star KMeans ★) LAST so
a driver that parses the final stdout line records the headline metric.
Every config is isolated: a failure prints a JSON line with an "error" field
and the harness moves on — one bad kernel can never zero a round's evidence
again (round-1 post-mortem).

Measurement rules:
- median of >= 5 timed runs after a warmup/compile run; compile excluded;
- correctness gate before timing (device result vs NumPy oracle);
- vs_baseline is measured against a NumPy single-node proxy of the same
  algorithm run in-process (no dislib+COMPSs install exists here; the proxy
  is labeled in the metric string);
- results are synced by fetching a small slice of each terminal output
  (device_get), so every timed region ends with the bytes on the host.

The persistent compile cache and the setup side files the config children
share live where ``dislib_tpu.runtime.compile_cache`` resolves
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).  The
parent exits non-zero when any config row carried an ``error``.
"""

import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np

# Watchdog architecture: a hung device call blocks the Python main thread
# inside a C call, so no in-process mechanism can skip past it.  Each
# config therefore runs in its OWN subprocess; the parent (which never
# initialises a jax backend, so it never holds the chip) enforces
# timeouts, forwards the child's JSON lines, and keeps going after a
# timeout — one slow config no longer zeroes the rest of the run's
# evidence.  Two consecutive timeouts mean the backend itself is hung
# (every later config would hang too) and abort with rc 2.  A cheap
# 60-second `jax.devices()` probe child runs first so a dead backend
# costs one minute, not fifteen.
_CONFIG_TIMEOUT_S = int(os.environ.get("DSLIB_BENCH_CONFIG_S", "900"))
_PROBE_TIMEOUT_S = int(os.environ.get("DSLIB_BENCH_PROBE_S", "60"))


def _smoke_wants_cpu() -> bool:
    """True when smoke mode should force the CPU platform: BENCH_SMOKE is
    on and the caller did not request a platform through
    ``JAX_PLATFORMS`` (smoke mode validates the harness, not the chip).
    Test hooks inject probe failures by naming a platform."""
    return bool(os.environ.get("BENCH_SMOKE")) and \
        not os.environ.get("JAX_PLATFORMS")


def _median_time(fn, repeats=5):
    """Median wall seconds of fn(), which must internally sync its outputs."""
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _sync(*arrays):
    """Force completion by fetching a tiny dependent slice of each output."""
    for a in arrays:
        data = a._data if hasattr(a, "_data") else a
        np.asarray(data[:1, :1] if data.ndim == 2 else data[:1])


def _emit(payload):
    print(json.dumps(payload), flush=True)


def _measure_rtt(repeats=7):
    """Median wall seconds of a trivial dispatch + 1-element fetch — the
    fixed per-call latency floor every timed region pays exactly once.
    Measured in-config so amortized rows can emit an RTT-corrected value
    next to the raw one (round-3 verdict: correction must be in the JSON,
    not prose)."""
    import jax
    import jax.numpy as jnp

    x = jax.device_put(jnp.ones((8, 8), jnp.float32))
    f = jax.jit(lambda a: a + 1.0)
    np.asarray(f(x)[:1, :1])  # warmup/compile
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        np.asarray(f(x)[:1, :1])
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _guard(name, fn) -> bool:
    """Run one config; a failure becomes an ``error`` row (configs stay
    isolated) and a False return, which the child turns into a non-zero
    exit so a failed run can never read as rc 0."""
    try:
        _emit(fn())
        return True
    except Exception as e:  # noqa: BLE001 — isolates configs; reported
        _emit({"metric": name, "value": None, "unit": None, "vs_baseline": None,
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc(limit=3)})
        return False


def _side_file_dir() -> str:
    """Where the config children share setup side files: the compile
    cache's directory (one resolver — ``runtime/compile_cache``)."""
    from dislib_tpu.runtime import compile_cache
    return compile_cache.resolve_dir()


# ---------------------------------------------------------------------------
# roofline: measured per-dtype peak + the vs_peak gate (round-10 perf PR)
# ---------------------------------------------------------------------------

_PEAK_CACHE: dict = {}


def _policy_of(dtype_tag):
    from dislib_tpu.ops import precision as px
    return {"f32": px.FLOAT32, "bf16": px.BFLOAT16}[dtype_tag]


def _peak_gflops(dtype_tag):
    """Measured per-chip GEMM peak for one compute dtype — the roofline
    denominator every ``vs_peak`` row divides by.

    ``DSLIB_PEAK_GFLOPS_F32`` / ``_BF16`` override with a datasheet value
    when the platform's peak is known; otherwise a dedicated probe runs a
    deep dependent-GEMM chain (the library's own ``precision.pdot``
    formulation) at an MXU-friendly square size and takes the BEST of 3
    regions — peak wants the minimum wall, not the median.  The probe is
    a proxy: a benched workload whose shape outruns the probe's can read
    ``vs_peak`` slightly above 1; the gate direction (a floor) only cares
    about collapses."""
    env = os.environ.get(f"DSLIB_PEAK_GFLOPS_{dtype_tag.upper()}")
    if env:
        return float(env)
    if dtype_tag in _PEAK_CACHE:
        return _PEAK_CACHE[dtype_tag]
    dim = 512 if os.environ.get("BENCH_SMOKE") else 4096
    # FILE-backed like the matmul setup cache: every config runs in its
    # own subprocess (watchdog architecture), so without it each
    # roofline-gated sibling would re-measure the identical probe; the
    # parent clears these at run start so a previous invocation's machine
    # load never leaks into this run's vs_peak ratios
    cache_dir = _side_file_dir()
    cache_f = os.path.join(cache_dir, f"bench_peak_{dtype_tag}_{dim}.json")
    if os.path.exists(cache_f):
        try:
            with open(cache_f) as f:
                peak = float(json.load(f)["peak_gflops"])
            _PEAK_CACHE[dtype_tag] = peak
            return peak
        except (OSError, ValueError, KeyError):
            pass                        # unreadable cache: re-measure
    import jax
    import jax.numpy as jnp
    import dislib_tpu as ds  # noqa: F401 — mesh init side effect
    chain = 8
    x = jax.device_put(jnp.asarray(
        np.random.RandomState(0).rand(dim, dim).astype(np.float32)))
    fn = _policy_chain_fn(_policy_of(dtype_tag), chain)
    np.asarray(fn(x)[:1, :1])                       # warmup/compile
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(fn(x)[:1, :1])
        walls.append(time.perf_counter() - t0)
    peak = 2.0 * dim ** 3 * chain / min(walls) / 1e9
    _PEAK_CACHE[dtype_tag] = peak
    try:
        os.makedirs(cache_dir, exist_ok=True)
        with open(cache_f, "w") as f:
            json.dump({"peak_gflops": peak}, f)
    except OSError:
        pass                            # cache is best-effort
    return peak


def _policy_chain_fn(policy, chain):
    """One dispatch of ``chain`` dependent GEMMs through the library's
    policy-routed contraction (`ops/precision.pdot`) — the same dependency
    trick as ``bench_matmul``'s chain (stops XLA hoisting), but measuring
    the policy path the library actually ships."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from dislib_tpu.ops import precision as px
    from dislib_tpu.parallel import mesh as _mesh_mod

    def _body(x):
        eps = jnp.float32(1.0 / (x.shape[0] * x.shape[0]))

        def step(i, c):
            out = px.pdot(x, x + eps * c, policy)
            return lax.with_sharding_constraint(out,
                                                _mesh_mod.data_sharding())
        return lax.fori_loop(0, chain, step,
                             jnp.zeros(x.shape, jnp.float32))

    return jax.jit(px.precise(_body))


def _apply_roofline(res, sustained_gflops, dtype_tag, floor):
    """Attach ``peak_gflops`` / ``vs_peak`` to a row and enforce the
    roofline floor — the regression gate: sustained GFLOPS falling below
    ``floor`` x measured peak FAILS the config loudly (stderr + an error
    row via ``_guard``) instead of shipping a quietly-slower number.
    ``DSLIB_VS_PEAK_MIN`` overrides every floor (noisy-rig escape)."""
    peak = _peak_gflops(dtype_tag)
    vs_peak = sustained_gflops / peak
    res["peak_gflops"] = round(peak, 1)
    res["vs_peak"] = round(vs_peak, 3)
    # record the floor the gate ACTUALLY enforces (env override included)
    # — a row must never read as having cleared a floor it was not held to
    floor = float(os.environ.get("DSLIB_VS_PEAK_MIN", floor))
    res["vs_peak_floor"] = floor
    if vs_peak < floor:
        msg = (f"ROOFLINE GATE FAILED: {res['metric']}: sustained "
               f"{sustained_gflops:.1f} GFLOPS is {vs_peak:.1%} of the "
               f"measured {dtype_tag} peak {peak:.1f} GFLOPS — below the "
               f"{floor:.0%} floor (regression in sustained throughput)")
        print(msg, file=sys.stderr, flush=True)
        raise AssertionError(msg)
    return res


# ---------------------------------------------------------------------------
# NumPy proxies (single-node, labeled as such in metric strings)
# ---------------------------------------------------------------------------

def _numpy_kmeans_iter(x, centers):
    d = (x * x).sum(1)[:, None] - 2.0 * (x @ centers.T) \
        + (centers * centers).sum(1)[None]
    labels = d.argmin(1)
    onehot = np.zeros((x.shape[0], centers.shape[0]), x.dtype)
    onehot[np.arange(x.shape[0]), labels] = 1.0
    counts = onehot.sum(0)
    sums = onehot.T @ x
    return np.where(counts[:, None] > 0,
                    sums / np.maximum(counts, 1)[:, None], centers)


def _numpy_gmm_iter(x, weights, means, covs, reg=1e-6):
    """One full-covariance EM iteration (log-domain responsibilities)."""
    m, n = x.shape
    k = means.shape[0]
    log_prob = np.empty((m, k), np.float32)
    for j in range(k):
        chol = np.linalg.cholesky(covs[j])
        dev = np.linalg.solve(chol, (x - means[j]).T)
        log_det = 2.0 * np.log(np.diag(chol)).sum()
        log_prob[:, j] = -0.5 * (n * np.log(2 * np.pi) + log_det
                                 + (dev * dev).sum(0))
    wlp = log_prob + np.log(weights)[None]
    norm = wlp.max(1, keepdims=True)
    resp = np.exp(wlp - norm)
    resp /= resp.sum(1, keepdims=True)
    nk = resp.sum(0) + 1e-10
    means = resp.T @ x / nk[:, None]
    covs = np.empty_like(covs)
    for j in range(k):
        diff = x - means[j]
        covs[j] = (resp[:, j, None] * diff).T @ diff / nk[j] \
            + reg * np.eye(n, dtype=np.float32)
    return nk / m, means, covs


def _numpy_random_svd(x, sketch, iters, seed=0):
    rng = np.random.RandomState(seed)
    omega = rng.standard_normal((x.shape[1], sketch)).astype(np.float32)
    q, _ = np.linalg.qr(x @ omega)
    for _ in range(iters):
        qz, _ = np.linalg.qr(x.T @ q)
        q, _ = np.linalg.qr(x @ qz)
    b = q.T @ x
    ub, s, vt = np.linalg.svd(b, full_matrices=False)
    return q @ ub, s, vt


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

def bench_kmeans(m, n, k, iters, tag, amortize=None):
    """KMeans iteration rate.  ``amortize``: additionally time a region of
    that many iterations per dispatch and report it as the headline value —
    the fixed per-dispatch latency otherwise dominates any config whose
    ``iters``-iteration compute is comparable to one dispatch round trip.
    The spec-``iters`` rate is kept in ``raw_value`` and the
    RTT-subtracted rate in ``rtt_corrected_value`` so raw, amortized and
    corrected are all machine-readable."""
    import jax.numpy as jnp
    import dislib_tpu as ds
    from dislib_tpu.cluster.kmeans import _kmeans_fit

    rng = np.random.RandomState(0)
    x_host = rng.rand(m, n).astype(np.float32)
    init = x_host[rng.choice(m, k, replace=False)].copy()

    t0 = time.perf_counter()
    c = init.copy()
    for _ in range(2):
        c = _numpy_kmeans_iter(x_host, c)
    cpu_iter_sec = 2.0 / (time.perf_counter() - t0)

    a = ds.array(x_host, block_size=(m, n))
    c0 = jnp.asarray(init)
    fast = tag.endswith("fastdist")
    # correctness gate: 1 device iteration vs the NumPy oracle.  The bf16-
    # assignment variant legitimately flips near-tied argmins, so its gate
    # is inertia-relative vs the full-precision device result (centers
    # averaged over ~m/k points absorb a handful of boundary flips; the
    # objective must agree to 0.1%)
    got_state = _kmeans_fit(a._data, a.shape, c0, 1, 0.0, fast=fast)
    got = np.asarray(got_state[0])
    if fast:
        exact = _kmeans_fit(a._data, a.shape, c0, 1, 0.0, fast=False)
        np.testing.assert_allclose(float(got_state[2]), float(exact[2]),
                                   rtol=1e-3)
        np.testing.assert_allclose(got, np.asarray(exact[0]),
                                   rtol=2e-2, atol=2e-2)
    else:
        np.testing.assert_allclose(got, _numpy_kmeans_iter(x_host, init),
                                   rtol=2e-3, atol=2e-3)
    np.asarray(_kmeans_fit(a._data, a.shape, c0, iters, 0.0,
                           fast=fast)[0])  # warmup
    t = _median_time(
        lambda: np.asarray(_kmeans_fit(a._data, a.shape, c0, iters, 0.0,
                                       fast=fast)[0]))
    tpu_iter_sec = iters / t
    res = {"metric": f"kmeans_{tag}_iter_per_sec (baseline: numpy single-node proxy)",
           "value": round(tpu_iter_sec, 3), "unit": "iter/s",
           "vs_baseline": round(tpu_iter_sec / cpu_iter_sec, 2)}
    # dispatch accounting (round-7 fusion PR): how many XLA dispatches one
    # estimator-level fit/predict costs, from the utils.profiling counters
    # — the "one program per result, not per op" claim as a number
    from dislib_tpu.cluster import KMeans as _KMeans
    from dislib_tpu.utils import profiling as _prof
    kw = dict(n_clusters=k, init=init, max_iter=iters, tol=0.0,
              fast_distance=fast)
    warm = _KMeans(**kw).fit(a)                 # compile both paths
    warm.predict(a).force()
    _prof.reset_counters()
    est = _KMeans(**kw).fit(a)
    res["dispatches_per_fit"] = _prof.dispatch_count()
    _prof.reset_counters()
    est.predict(a).force()
    res["dispatches_per_predict"] = _prof.dispatch_count()
    if amortize:
        np.asarray(_kmeans_fit(a._data, a.shape, c0, amortize, 0.0,
                               fast=fast)[0])  # compile for the new max_iter
        wall = _median_time(
            lambda: np.asarray(_kmeans_fit(a._data, a.shape, c0, amortize,
                                           0.0, fast=fast)[0]))
        rtt = _measure_rtt()
        sustained = amortize / wall
        res.update({
            "raw_value": res["value"],
            "raw_vs_baseline": res["vs_baseline"],
            "value": round(sustained, 3),
            "vs_baseline": round(sustained / cpu_iter_sec, 2),
            "rtt_ms": round(1e3 * rtt, 2),
            "rtt_corrected_value": round(amortize / max(wall - rtt, 1e-9), 3),
            "iters_per_dispatch": amortize,
            "note": f"value = sustained rate ({amortize} iters/dispatch); "
                    f"raw_value = spec rate ({iters} iters/dispatch, "
                    "one RTT per dispatch)"})
    return res


def bench_matmul(dim, tag, proxy_dim=None, bf16=False, chain=None,
                 precision=None, peak_floor=None):
    """GEMM GFLOPS/chip — f32-faithful, or the library's bfloat16 policy
    (bf16-compute / f32-accumulate via ``ds.matmul(precision='bfloat16')``)
    when ``bf16``; pre-round-10 captures measured bf16-STORAGE operands
    instead (same MXU passes, so rows compare).  proxy_dim: run the NumPy
    proxy at a smaller size and scale analytically (labeled) when the
    full size is too slow.  ``peak_floor``: when set (library rows only),
    the sustained value must reach that fraction of the measured
    per-dtype peak — the roofline regression gate (round-10 perf PR).

    ``chain``: additionally time ONE dispatch containing that many
    *dependent* GEMMs (``c_{i+1} = x @ (x + eps*c_i)``, same dot + sharding
    constraint + f32-faithful precision scope as the library kernel,
    ``math/base.py::_matmul_kernel``) and report the sustained GFLOPS as
    the headline value — a single dispatch's wall includes the fixed
    per-dispatch latency.  The dependency chain stops XLA hoisting the
    loop-invariant product; eps ~ 1/dim² keeps the iterate bounded (the
    perturbation contracts since eps·‖x‖₂ ≈ 1/(2·dim) ≪ 1).  Single-
    dispatch GFLOPS stays in ``raw_value``; RTT-subtracted sustained in
    ``rtt_corrected_value``.

    ``precision``: INFORMATIONAL precision override — "high" is the TPU
    3-pass bf16x3 algorithm (~2⁻²¹ relative error vs f32's 2⁻²⁴;
    theoretical ceiling ≈ peak/3 vs 'highest''s peak/6).  The library's
    own kernels stay at 'highest'; this row exists so a future round can
    decide from measured on-chip data whether the f32-faithful scope can
    drop to 3-pass (measurably-better rule).  Uses a direct jitted dot
    (the library has no 'high' path to measure)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    import dislib_tpu as ds
    from dislib_tpu.ops.base import precise
    from dislib_tpu.parallel import mesh as _mesh_mod

    # setup cache — FILE-backed, because every config runs in its own
    # subprocess (the watchdog architecture), so the f32 and bf16 siblings
    # of a dim would otherwise each re-measure the slow NumPy proxy and
    # gate stripe.  Data is deterministic (RandomState(0)), so the cached
    # gate reference is exact across children.
    rng = np.random.RandomState(0)
    pdim = proxy_dim or dim
    cache_dir = _side_file_dir()
    cache_f = os.path.join(cache_dir, f"bench_matmul_setup_{dim}_{pdim}.npz")
    if os.path.exists(cache_f):
        with np.load(cache_f) as z:
            cpu_gflops, ref = float(z["cpu_gflops"]), z["ref"]
        rng.rand(pdim, pdim)            # keep the stream position identical
        x_host = rng.rand(dim, dim).astype(np.float32)
    else:
        xp = rng.rand(pdim, pdim).astype(np.float32)
        t0 = time.perf_counter()
        xp @ xp
        cpu_gflops = 2.0 * pdim ** 3 / (time.perf_counter() - t0) / 1e9
        x_host = rng.rand(dim, dim).astype(np.float32)
        ref = x_host @ x_host[:, :64]
        try:
            os.makedirs(cache_dir, exist_ok=True)
            np.savez(cache_f, cpu_gflops=cpu_gflops, ref=ref)
        except OSError:
            pass                        # cache is best-effort

    a = ds.array(x_host, block_size=(dim // 4, dim // 4))
    # the bf16 row measures the LIBRARY precision policy (bf16-compute /
    # f32-accumulate, operands stored f32 and rounded in-kernel) — the
    # surface users actually call; pre-round-10 captures measured
    # bf16-STORAGE operands instead (same MXU passes, so rows compare)
    lib_precision = "bfloat16" if bf16 else None
    # correctness gate on a 64-column stripe (cheap on host at any dim);
    # bf16 operand rounding is ~2^-9 relative, so a 3% relative bound has
    # ample headroom while still catching mis-scaled accumulation (entries
    # are sums of positive products — nothing near zero, rtol-only works);
    # the 3-pass f32x3 variant is ~2^-21 relative — 0.5% bound
    if precision is None:
        c = ds.matmul(a, a, precision=lib_precision)
        got = np.asarray(c._data[:dim, :64], dtype=np.float32)
        np.testing.assert_allclose(got, ref, rtol=3e-2 if bf16 else 2e-2,
                                   atol=0)

        def run():
            out = ds.matmul(a, a, precision=lib_precision)
            _sync(out)
    else:
        xd = a._data
        mm = jax.jit(lambda u, v: jnp.dot(
            u, v, precision=precision,
            preferred_element_type=jnp.float32))
        got = np.asarray(mm(xd, xd)[:dim, :64], dtype=np.float32)
        np.testing.assert_allclose(got, ref, rtol=5e-3, atol=0)

        def run():
            np.asarray(mm(xd, xd)[:1, :1])
    run()  # warmup (already compiled above, keeps parity with rules)
    t = _median_time(run)
    gflops = 2.0 * dim ** 3 / t / 1e9
    label = "numpy single-node proxy" + \
        (f" measured at {pdim}^3" if proxy_dim else "")
    dt = "bf16" if bf16 else \
        ("f32x3" if precision == "high" else "f32")
    res = {"metric": f"matmul_{tag}_{dt}_gflops_per_chip (baseline: {label})",
           "value": round(gflops, 1), "unit": "GFLOPS",
           "vs_baseline": round(gflops / cpu_gflops, 2)}
    if precision is None:
        # dispatch accounting (round-7 fusion PR): a library matmul is ONE
        # dispatch — the fused expression forced, or the eager kernel
        from dislib_tpu.utils import profiling as _prof
        _prof.reset_counters()
        ds.matmul(a, a, precision=lib_precision).force()
        res["dispatches_per_op"] = _prof.dispatch_count()
    if chain:
        x = a._data
        eps = np.float32(1.0 / (float(dim) * float(dim)))

        if precision is None:
            # library rows: the policy-routed pdot chain — what ships
            chain_fn = _policy_chain_fn(
                _policy_of("bf16" if bf16 else "f32"), chain)
        else:
            def _chain_body(x):
                def body(i, c):
                    y = x + eps * c
                    # the informational f32x3 row passes "high" explicitly
                    out = jnp.dot(x, y, precision=precision,
                                  preferred_element_type=jnp.float32)
                    return lax.with_sharding_constraint(
                        out, _mesh_mod.data_sharding())
                return lax.fori_loop(0, chain, body,
                                     jnp.zeros(x.shape, jnp.float32))

            chain_fn = jax.jit(precise(_chain_body))
        np.asarray(chain_fn(x)[:1, :1])  # warmup/compile
        wall = _median_time(lambda: np.asarray(chain_fn(x)[:1, :1]))
        rtt = _measure_rtt()
        sustained = 2.0 * dim ** 3 * chain / wall / 1e9
        res.update({
            "raw_value": res["value"],
            "raw_vs_baseline": res["vs_baseline"],
            "value": round(sustained, 1),
            "vs_baseline": round(sustained / cpu_gflops, 2),
            "rtt_ms": round(1e3 * rtt, 2),
            "rtt_corrected_value": round(
                2.0 * dim ** 3 * chain / max(wall - rtt, 1e-9) / 1e9, 1),
            "gemms_per_dispatch": chain,
            "note": f"value = sustained rate ({chain} dependent GEMMs in one "
                    "dispatch); raw_value = single-GEMM dispatch incl. one "
                    "RTT"})
        if precision is None and peak_floor is not None:
            _apply_roofline(res, sustained, "bf16" if bf16 else "f32",
                            peak_floor)
    return res


def bench_matmul_mp(dim, tag, chain, min_speedup=1.5, peak_floors=(0.15, 0.15)):
    """Mixed-precision matmul A/B — the round-10 acceptance row: the
    bfloat16 policy's sustained GEMM throughput must reach
    ``min_speedup`` x the f32-faithful policy's on the same operand, with
    the measured error inside the documented bound
    (``ops/precision.ERROR_BOUNDS``), both library paths at exactly ONE
    dispatch per op, and both sustained rates above their per-dtype
    roofline floors.  Every one of those is an in-config ASSERT — a
    regression fails the row loudly instead of shipping a quieter number.
    """
    import dislib_tpu as ds
    from dislib_tpu.ops import precision as px
    from dislib_tpu.utils import profiling as _prof

    rng = np.random.RandomState(0)
    x_host = rng.rand(dim, dim).astype(np.float32)
    a = ds.array(x_host, block_size=(dim // 4, dim // 4)).force()

    # accuracy gate: normalized entry error of the bf16 policy vs the
    # in-library f32 path, against the documented bound
    ref = np.asarray(ds.matmul(a, a)._data[:dim, :64], dtype=np.float32)
    got = np.asarray(ds.matmul(a, a, precision="bfloat16")
                     ._data[:dim, :64], dtype=np.float32)
    scale = np.abs(ref).max()
    err = float(np.abs(got - ref).max() / scale)
    bound = px.ERROR_BOUNDS[("matmul", "bfloat16")]
    assert err <= bound, \
        f"bf16 matmul error {err:.2e} outside documented bound {bound:.0e}"

    # dispatch gate: one fused/eager program per op, BOTH policies
    disp = {}
    for name, prec in (("f32", None), ("bf16", "bfloat16")):
        ds.matmul(a, a, precision=prec).force()          # warm
        _prof.reset_counters()
        ds.matmul(a, a, precision=prec).force()
        disp[name] = _prof.dispatch_count()
        assert disp[name] == 1, \
            f"{name} matmul cost {disp[name]} dispatches, expected 1"

    # sustained throughput per policy (dependent-GEMM chain, one dispatch)
    walls = {}
    for name in ("f32", "bf16"):
        fn = _policy_chain_fn(_policy_of(name), chain)
        np.asarray(fn(a._data)[:1, :1])                  # warmup/compile
        walls[name] = _median_time(lambda: np.asarray(fn(a._data)[:1, :1]))
    gflops = {name: 2.0 * dim ** 3 * chain / walls[name] / 1e9
              for name in walls}
    speedup = gflops["bf16"] / gflops["f32"]
    res = {"metric": f"matmul_mp_{tag}_bf16_vs_f32_speedup (baseline: the "
                     "f32-faithful policy, same operand/chain)",
           "value": round(speedup, 2), "unit": "x",
           "vs_baseline": round(speedup, 2),
           "f32_gflops": round(gflops["f32"], 1),
           "bf16_gflops": round(gflops["bf16"], 1),
           "bf16_rel_err": round(err, 6), "err_bound": bound,
           "dispatches_per_op": disp,
           "gemms_per_dispatch": chain, "min_speedup": min_speedup,
           "note": "bf16 = bf16-compute/f32-accumulate policy; gates: "
                   "speedup >= min_speedup, error <= documented bound, "
                   "1 dispatch/op, vs_peak floors per dtype"}
    _apply_roofline(res, gflops["f32"], "f32", peak_floors[0])
    f32_peak, f32_vs = res["peak_gflops"], res["vs_peak"]
    f32_floor = res["vs_peak_floor"]
    _apply_roofline(res, gflops["bf16"], "bf16", peak_floors[1])
    res.update({"f32_peak_gflops": f32_peak, "f32_vs_peak": f32_vs,
                "f32_vs_peak_floor": f32_floor,
                "bf16_peak_gflops": res.pop("peak_gflops"),
                "bf16_vs_peak": res.pop("vs_peak"),
                "bf16_vs_peak_floor": res.pop("vs_peak_floor")})
    # The speedup gate is roofline-NORMALIZED with a platform-class
    # deadband.  MXU-class platforms (measured bf16 peak >= 1.5x f32 —
    # the r05 chip capture shows ~2.6x) must deliver the full
    # ``min_speedup`` expectation: floor = min(min_speedup,
    # 0.8 x peak_ratio), i.e. 1.5x on chip.  Parity-class platforms
    # (this rig's CPU: bf16 GEMMs upcast, peak ratio jitters ~0.9-1.15
    # between probes — the r08 smoke capture's 2.27x was a
    # host-contention artifact) get a fixed 0.7x floor: "bf16 may not be
    # MATERIALLY slower than f32" — a double-cast/upcast regression
    # (~2x slower) still fails loudly, but probe noise around parity
    # cannot flip the gate (a 0.8 x ratio floor measured 0.88-0.92 here,
    # a coin flip against an equally-noisy 0.84-0.96 speedup).
    peak_ratio = res["bf16_peak_gflops"] / res["f32_peak_gflops"]
    if peak_ratio >= 1.5:
        floor = min(float(min_speedup), 0.8 * peak_ratio)
    else:
        floor = 0.7
    floor = float(os.environ.get("DSLIB_BF16_SPEEDUP_MIN", floor))
    res["peak_ratio"] = round(peak_ratio, 2)
    res["speedup_floor"] = round(floor, 2)
    if speedup < floor:
        msg = (f"MIXED-PRECISION GATE FAILED: bf16 sustained "
               f"{gflops['bf16']:.1f} GFLOPS is only {speedup:.2f}x the "
               f"f32 policy's {gflops['f32']:.1f} — below the "
               f"{floor:.2f}x floor (min_speedup={min_speedup}, measured "
               f"peak ratio {peak_ratio:.2f})")
        print(msg, file=sys.stderr, flush=True)
        raise AssertionError(msg)
    return res


def bench_polar(m, n, tag, max_iter=30, peak_floor=0.1):
    """Newton–Schulz polar — the canonical sustained-GFLOPS workload
    (pure dependent GEMMs, zero factorisations on the critical path;
    round-10 tentpole).  Gates, all asserted in-config: U orthonormal +
    reconstruction vs the f32 SVD oracle, ONE dispatch per polar call
    REGARDLESS of iteration count (the whole loop is one program), and
    sustained GFLOPS ≥ ``peak_floor`` x the measured f32 peak.  The bf16
    policy's wall/GFLOPS ride along as fields (its iteration count can
    differ, so the ratio is informational here — the hard bf16-vs-f32
    gate lives in the matmul_mp row)."""
    import dislib_tpu as ds
    from dislib_tpu.ops import precision as px
    from dislib_tpu.utils import profiling as _prof

    rng = np.random.RandomState(0)
    x_host = rng.standard_normal((m, n)).astype(np.float32)
    a = ds.array(x_host, block_size=(max(1, m // 8), n))

    # correctness gate vs the SVD-based oracle
    u, h, info = ds.polar(a, max_iter=max_iter, info=True)
    uh = np.asarray(u.collect())
    orth = float(np.abs(uh.T @ uh - np.eye(n)).max())
    recon = float(np.linalg.norm(uh @ np.asarray(h.collect()) - x_host)
                  / np.linalg.norm(x_host))
    assert orth <= px.ERROR_BOUNDS[("polar_orth", "float32")] * 10, \
        f"polar gate: ||U'U - I|| = {orth}"
    assert recon <= 1e-4, f"polar gate: reconstruction {recon}"

    # dispatch gate: the WHOLE iteration loop is one program
    for iters in (1, max_iter):
        ds.polar(a, max_iter=iters)                     # warm
        _prof.reset_counters()
        ds.polar(a, max_iter=iters)
        d = _prof.dispatch_count()
        assert d == 1, f"polar(max_iter={iters}) cost {d} dispatches"

    def run(prec):
        _, _, nfo = ds.polar(a, precision=prec, max_iter=max_iter,
                             info=True)
        return nfo

    run(None)                                           # warmed above
    t = _median_time(lambda: run(None))
    iters = info["iterations"]
    # 2 GEMMs/iter + final-err Gram + H
    flops = 4.0 * m * n * n * iters + 4.0 * m * n * n
    gflops = flops / t / 1e9
    info_bf = run("bfloat16")                           # warmup bf16
    t_bf = _median_time(lambda: run("bfloat16"))
    gflops_bf = (4.0 * m * n * n * info_bf["iterations"]
                 + 4.0 * m * n * n) / t_bf / 1e9
    res = {"metric": f"polar_{tag}_gflops_sustained (baseline: measured "
                     "f32 GEMM peak — roofline row)",
           "value": round(gflops, 1), "unit": "GFLOPS",
           "vs_baseline": None,
           "wall_s": round(t, 4), "iterations": iters,
           "ortho_err": info["ortho_err"], "recon_err": round(recon, 8),
           "dispatches_per_op": 1,
           "bf16_gflops": round(gflops_bf, 1),
           "bf16_wall_s": round(t_bf, 4),
           "bf16_iterations": info_bf["iterations"],
           "note": "one dispatch per polar call at ANY iteration count "
                   "(asserted); flops = (4*iters + 4)*m*n^2"}
    _apply_roofline(res, gflops, "f32", peak_floor)
    res["vs_baseline"] = res["vs_peak"]
    return res


def _mesh_2d_shapes(what):
    """Near-square 2-D factorisation of the device count — (src, dst)
    mesh shapes for the tiers that need a genuine 2-D mesh (summa,
    rechunk, overlap).  Rejects < 4 devices and prime counts (whose only
    factorisation is 1-D) loudly; ONE copy of the sqrt-descend loop so a
    policy fix propagates to every tier."""
    import jax
    devs = len(jax.devices())
    if devs < 4:
        raise RuntimeError(
            f"{what} bench needs >= 4 devices for a 2-D mesh, have {devs}")
    r = int(np.sqrt(devs))
    while devs % r:
        r -= 1
    if r == 1:
        raise RuntimeError(
            f"{what} bench needs a composite device count for a 2-D mesh, "
            f"have {devs} (prime)")
    return (devs // r, r), (r, devs // r)


def bench_summa(dim, tag, peak_floor=0.05):
    """SUMMA matmul on a genuinely 2-D mesh — the explicit panel-broadcast
    schedule (`ops/summa`) vs the XLA-partitioned dot on the SAME mesh.
    Gates: values match the XLA path, ONE dispatch per op, vs_peak floor.
    The vs_xla ratio is informational: on real multi-chip ICI the panel
    schedule's bounded broadcasts are the point; on a host-core rig the
    partitioner's fused schedule usually wins wall clock."""
    import jax
    import dislib_tpu as ds

    src, _ = _mesh_2d_shapes("summa")
    ds.init(src)
    from dislib_tpu.utils import profiling as _prof

    rng = np.random.RandomState(0)
    x_host = rng.rand(dim, dim).astype(np.float32)
    a = ds.array(x_host, block_size=(dim // 4, dim // 4)).force()
    ref = np.asarray(ds.matmul(a, a, algorithm="xla")
                     ._data[:dim, :64], dtype=np.float32)
    got = np.asarray(ds.matmul(a, a, algorithm="summa")
                     ._data[:dim, :64], dtype=np.float32)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5 * ref.max())

    ds.matmul(a, a, algorithm="summa").force()          # warm
    _prof.reset_counters()
    ds.matmul(a, a, algorithm="summa").force()
    d = _prof.dispatch_count()
    assert d == 1, f"summa matmul cost {d} dispatches, expected 1"

    def run(algo):
        out = ds.matmul(a, a, algorithm=algo)
        _sync(out)

    # steady-state A/B (round-13 satellite): BOTH schedules are warmed
    # before EITHER timed region, and the regions are trace-asserted
    # compile-free — a first-call recompile inside _median_time would
    # poison the vs_xla ratio with one-off compile wall (the peak
    # probe's file-cached-setup precedent).  The hoist makes the
    # guarantee structural; the assert makes a regression loud.
    run("summa")
    run("xla")
    traces_before = _prof.trace_count()
    t = _median_time(lambda: run("summa"))
    t_xla = _median_time(lambda: run("xla"))
    assert _prof.trace_count() == traces_before, \
        "summa/xla timed region recompiled — the A/B ratio is not " \
        "steady-state"
    gflops = 2.0 * dim ** 3 / t / 1e9
    res = {"metric": f"summa_{tag}_gflops_per_chip (baseline: XLA-"
                     "partitioned dot, same 2-D mesh)",
           "value": round(gflops, 1), "unit": "GFLOPS",
           "vs_baseline": round(t_xla / t, 2),
           "wall_s": round(t, 4), "xla_wall_s": round(t_xla, 4),
           "mesh": list(ds.get_mesh().devices.shape),
           "dispatches_per_op": 1,
           "note": "vs_baseline = xla_wall / summa_wall on this mesh "
                   "(informational); gates: values == xla path, 1 "
                   "dispatch, vs_peak floor"}
    _apply_roofline(res, gflops, "f32", peak_floor)
    return res


def bench_rechunk(m, n, tag, panels=4, min_gbps=0.02, peak_ratio_max=1.5):
    """On-device collective rechunk (round-11 perf PR, ROADMAP item 4):
    the explicit masked-psum panel-exchange schedule resharding an (m, n)
    ds-array between two 2-D mesh layouts of the same devices.

    Gates (all fail the config loudly):
    - result BIT-EQUAL to the host `repad_rows` oracle, pads exactly zero;
    - ONE dispatch per reshard, ZERO host transfers (counters);
    - peak-live-buffer proxy ((out + temp) / in from XLA's own memory
      analysis of the compiled program) <= ``peak_ratio_max`` — a
      schedule that gathered a full copy sits >= 2.0, the panel schedule
      at ~1 + 1/panels (``DSLIB_RECHUNK_PEAK_RATIO_MAX`` overrides);
    - sustained bytes/s ((in + out) / wall) >= ``min_gbps``
      (``DSLIB_RECHUNK_GBPS_MIN`` overrides);
    - a mid-chain rechunk in a fused op chain costs ZERO extra
      dispatches (the chain still forces as ONE program).
    The deviceput (runtime-copy) schedule is timed alongside as the
    baseline ratio — informational, like summa's vs_xla."""
    import jax
    import dislib_tpu as ds
    from dislib_tpu.ops import rechunk as _rc
    from dislib_tpu.parallel import mesh as _mesh
    from dislib_tpu.utils import profiling as _prof

    src, dst = _mesh_2d_shapes("rechunk")
    rng = np.random.RandomState(0)
    x_host = rng.rand(m, n).astype(np.float32)
    ds.init(src)
    a = ds.array(x_host).force()
    ds.init(dst)
    q = _mesh.pad_quantum()
    pshape = (-(-m // q) * q, -(-n // q) * q)

    # correctness gate: a reshard is pure data movement — BIT-equal
    out = ds.rechunk(a, schedule="panels", panels=panels)
    got = np.asarray(out._data)
    from dislib_tpu.runtime import repad_rows
    oracle = repad_rows(repad_rows(x_host, m, pshape[0], axis=0),
                        n, pshape[1], axis=1)
    np.testing.assert_array_equal(got, oracle)

    # dispatch / transfer gate
    _prof.reset_counters()
    ds.rechunk(a, schedule="panels", panels=panels)
    d, tr = _prof.dispatch_count(), _prof.transfer_count()
    assert d == 1, f"panel rechunk cost {d} dispatches, expected 1"
    assert tr == 0, f"panel rechunk cost {tr} host transfers, expected 0"

    # peak-live-buffer proxy gate (XLA memory analysis; analytic bound as
    # the fallback on backends without it)
    ma = _rc.panel_memory_analysis(a._data, a.shape, _mesh.get_mesh(),
                                   panels)
    ratio = ma["peak_live_ratio"] if ma["peak_live_ratio"] is not None \
        else ma["analytic_ratio"]
    ratio_max = float(os.environ.get("DSLIB_RECHUNK_PEAK_RATIO_MAX",
                                     peak_ratio_max))
    if ratio > ratio_max:
        msg = (f"RECHUNK MEMORY GATE FAILED: peak-live proxy {ratio:.2f}x "
               f"the array footprint exceeds the {ratio_max:.2f}x bound "
               f"(panels={ma['panels']}) — the schedule is materialising "
               "a gathered copy")
        print(msg, file=sys.stderr, flush=True)
        raise AssertionError(msg)

    # fused mid-chain gate: a rechunk NODE adds no dispatch to a chain.
    # schedule="xla" forces the node onto the graph — the auto path's
    # metadata fast-path would make this gate vacuous (review-found)
    b = ds.array(x_host).force()          # canonical under dst mesh
    def _chain():
        mid = ds.rechunk(b * 1.0001, (max(1, m // 8), n), schedule="xla")
        assert mid.is_lazy, "mid-chain rechunk left the fusion graph"
        (mid + 0.0001).force()
    _chain()                              # warm
    _prof.reset_counters()
    _chain()
    dc = _prof.dispatch_count()
    assert dc == 1, f"fused chain with mid-chain rechunk cost {dc} dispatches"

    def run(schedule):
        y = ds.rechunk(a, schedule=schedule, panels=panels)
        _sync(y._data)

    run("panels")
    t = _median_time(lambda: run("panels"))
    run("deviceput")
    t_dput = _median_time(lambda: run("deviceput"))
    moved = (int(np.prod(a._pshape)) + int(np.prod(pshape))) * 4
    gbps = moved / t / 1e9
    floor = float(os.environ.get("DSLIB_RECHUNK_GBPS_MIN", min_gbps))
    res = {"metric": f"rechunk_{tag}_gb_per_sec (baseline: deviceput "
                     "runtime copy, same relayout)",
           "value": round(gbps, 3), "unit": "GB/s",
           "vs_baseline": round(t_dput / t, 2),
           "wall_s": round(t, 5), "deviceput_wall_s": round(t_dput, 5),
           "mesh_src": list(src), "mesh_dst": list(dst),
           "dispatches_per_op": 1, "host_transfers": 0,
           "peak_live_ratio": ratio, "peak_live_ratio_max": ratio_max,
           "panel_temp_bytes": ma["temp_bytes"],
           "analytic_ratio": ma["analytic_ratio"], "panels": ma["panels"],
           "gbps_floor": floor,
           "note": "gates: bit-equal to host repad oracle, 1 dispatch / 0 "
                   "transfers, peak-live proxy, mid-chain rechunk fuses "
                   "at 0 extra dispatches; vs_baseline = deviceput_wall / "
                   "panels_wall (informational)"}
    if gbps < floor:
        msg = (f"RECHUNK THROUGHPUT GATE FAILED: {gbps:.3f} GB/s below "
               f"the {floor:.3f} GB/s floor")
        print(msg, file=sys.stderr, flush=True)
        raise AssertionError(msg)
    return res


def bench_dcn(m, n, tag, mock_hosts=4, panels=4):
    """Hierarchical DCN-aware rechunk tier (round 19, ROADMAP item 2):
    the ``dcn`` schedule resharding an (m, n) ds-array between two
    hierarchical 2-D layouts of the same devices, judged on its ANALYTIC
    inter-host accounting (the ``spmm_masking_work`` exposure pattern) —
    counters and bytes, not prose.  ``DSLIB_MOCK_HOSTS`` partitions this
    process's devices into ``mock_hosts`` fake hosts so the whole
    protocol runs single-process (chip runs use real ``process_index``
    host maps and take the same code path).

    Gates (all fail the config loudly):
    - result BIT-EQUAL to the flat ``panels`` schedule (same relayout);
    - coalesced: inter-host messages per step <= hosts-1 — O(hosts),
      NOT O(panels) — and strictly fewer total DCN messages than the
      flat panel exchange on the same topology
      (``dcn_messages < flat_messages``);
    - no write amplification: ``dcn_bytes_moved`` <= the deviceput
      floor (the rows-whose-host-changes bytes ANY schedule must move);
    - the router actually ran the hierarchical tier (schedule counter
      ``rechunk_dcn``) and auto-routing picks it on a multi-host mesh;
    - the relayout genuinely crosses hosts (``dcn_messages > 0``) — a
      config whose padded row intervals align proves nothing.
    """
    import jax
    import dislib_tpu as ds
    from dislib_tpu.ops import rechunk as _rc
    from dislib_tpu.parallel import mesh as _mesh
    from dislib_tpu.utils import profiling as _prof

    prev = os.environ.get("DSLIB_MOCK_HOSTS")
    os.environ["DSLIB_MOCK_HOSTS"] = str(mock_hosts)
    try:
        ndev = len(jax.devices())
        if ndev % (2 * mock_hosts):
            raise RuntimeError(
                f"dcn bench needs a device count divisible by "
                f"2*mock_hosts={2 * mock_hosts}, have {ndev}")
        src, dst = (ndev, 1), (ndev // 2, 2)
        rng = np.random.RandomState(0)
        x_host = rng.rand(m, n).astype(np.float32)
        ds.init(src)
        a = ds.array(x_host).force()
        ds.init(dst)

        acct = _rc.dcn_accounting(a._data, a.shape, _mesh.get_mesh(),
                                  panels=panels)
        hosts = acct["hosts"]
        assert hosts == mock_hosts, \
            f"mock host map bled: {hosts} hosts, wanted {mock_hosts}"
        assert acct["dcn_messages"] > 0, (
            f"vacuous config: m={m} pads identically under {src} and "
            f"{dst} — no rows change host, pick a misaligning m")
        assert acct["messages_per_step_max"] <= hosts - 1, (
            f"NOT coalesced: {acct['messages_per_step_max']} messages in "
            f"one step exceeds hosts-1={hosts - 1} — O(panels) leak")
        assert acct["dcn_messages"] < acct["flat_messages"], (
            f"hierarchical schedule sends {acct['dcn_messages']} DCN "
            f"messages, the flat exchange only {acct['flat_messages']}")
        assert acct["dcn_bytes_moved"] <= acct["deviceput_bytes"], (
            f"write amplification: {acct['dcn_bytes_moved']} DCN bytes "
            f"exceed the {acct['deviceput_bytes']} deviceput floor")

        # correctness gate: bit-equal to the flat panel schedule, and the
        # router counted the hierarchical tier (+ auto picks it here)
        _prof.reset_counters()
        out_dcn = ds.rechunk(a, schedule="dcn", panels=panels)
        scheds = _prof.schedule_counters()
        ran = sum(v for k, v in scheds.items()
                  if k.startswith("rechunk_dcn:"))
        assert ran == 1, f"rechunk_dcn not counted exactly once: {scheds}"
        out_flat = ds.rechunk(a, schedule="panels", panels=panels)
        np.testing.assert_array_equal(np.asarray(out_dcn._data),
                                      np.asarray(out_flat._data),
                                      err_msg="dcn != panels (bit-equal "
                                              "gate)")
        auto = _rc.pick_schedule(a._data, _mesh.get_mesh(), "auto")
        assert auto == "dcn", \
            f"auto-routing picked {auto!r} on a {hosts}-host mesh"

        def run(schedule):
            y = ds.rechunk(a, schedule=schedule, panels=panels)
            _sync(y._data)

        run("dcn")
        t = _median_time(lambda: run("dcn"))
        t_flat = _median_time(lambda: run("panels"))
        moved = (int(np.prod(a._pshape))
                 + int(np.prod(out_dcn._pshape))) * 4
        return {"metric": f"dcn_rechunk_{tag}_gb_per_sec (baseline: flat "
                          "panel exchange, same relayout)",
                "value": round(moved / t / 1e9, 3), "unit": "GB/s",
                "vs_baseline": round(t_flat / t, 2),
                "wall_s": round(t, 5), "flat_wall_s": round(t_flat, 5),
                "mesh_src": list(src), "mesh_dst": list(dst),
                "hosts": hosts,
                "dcn_messages": acct["dcn_messages"],
                "flat_messages": acct["flat_messages"],
                "messages_per_step_max": acct["messages_per_step_max"],
                "messages_per_step_bound": hosts - 1,
                "dcn_bytes_moved": acct["dcn_bytes_moved"],
                "deviceput_bytes": acct["deviceput_bytes"],
                "steps": acct["steps"], "panels": acct["panels"],
                "note": "gates: bit-equal to the flat panel schedule, "
                        "messages/step <= hosts-1 (coalesced, O(hosts) "
                        "not O(panels)), dcn_messages < flat_messages, "
                        "dcn_bytes <= deviceput floor, rechunk_dcn "
                        "counted, auto-routing picks dcn multi-host; "
                        "mock-host overlay (DSLIB_MOCK_HOSTS) — wall "
                        "clock is intra-process, accounting is the "
                        "evidence"}
    finally:
        if prev is None:
            os.environ.pop("DSLIB_MOCK_HOSTS", None)
        else:
            os.environ["DSLIB_MOCK_HOSTS"] = prev


def bench_overlap(kind, m, n, tag, hidden_floor=0.0, panels=4, repeats=9):
    """Comm–compute overlap tier (round-13 PR): how much of the panel
    collective does the double-buffered schedule actually hide under
    compute, per schedule family (``kind`` = summa | rechunk | ring).

    ``comm_hidden_frac`` = (t_seq − t_db) / t_comm_alone, where
    t_comm_alone comes from a BROADCAST-ONLY variant of the same program
    (identical collectives, the compute replaced by a (1, 1) touch per
    panel — ``comm_only=True`` on the kernel), so the fraction is
    normalized by the comm the pipeline could possibly hide: 1.0 = the
    whole collective disappeared under compute, 0 = no overlap, < 0 =
    the pipelined program is slower (a scheduling regression).

    Gates, all failing the config loudly:
    - db and seq results BIT-EQUAL (same panel order, identical ops);
    - ONE dispatch under the db schedule (dispatch counters), and the
      router observably ran it (schedule counters);
    - ``comm_hidden_frac`` >= ``hidden_floor``
      (``DSLIB_OVERLAP_HIDDEN_MIN`` overrides — the vs_peak noisy-rig
      escape.  On host-core rigs the collectives are memcpys through
      shared caches, so the honest floor is "no pathological slowdown";
      real ICI is where the hidden fraction is the roofline claim);
    - double-buffer memory bound via ``compiled.memory_analysis()``:
      the db program's peak-live stays within the documented
      one-extra-panel budget — rechunk (out + temp)/in <= min(1 + 2/k,
      the tier's 1.5x ceiling) (``DSLIB_OVERLAP_PEAK_RATIO_MAX``
      overrides); summa/ring: temp(db) − temp(seq) <= one in-flight
      panel set (+1/2 panel slack for scheduler variance) — the double
      buffer must cost ONE panel of live memory, never an operand copy.
    Rows carry ``fresh: true`` (measured by this run)."""
    import jax
    import dislib_tpu as ds
    from dislib_tpu.utils import profiling as _prof

    src, dst = _mesh_2d_shapes("overlap")
    rng = np.random.RandomState(0)
    x_host = rng.rand(m, n).astype(np.float32)

    extra = {}
    if kind == "summa":
        from dislib_tpu.ops import precision as px
        from dislib_tpu.ops.summa import summa_matmul
        ds.init(src)
        mesh = ds.get_mesh()
        a = ds.array(x_host).force()
        b = ds.array(rng.rand(n, m).astype(np.float32)).force()
        ad, bd = a._data, b._data
        policy = px.FLOAT32

        def run(sched, comm_only=False):
            _sync(summa_matmul(ad, bd, mesh, policy, overlap=sched,
                               comm_only=comm_only))

        def lower(sched):
            return summa_matmul.lower(ad, bd, mesh, policy, overlap=sched)

        out_db = np.asarray(summa_matmul(ad, bd, mesh, policy,
                                         overlap="db"))
        out_seq = np.asarray(summa_matmul(ad, bd, mesh, policy,
                                          overlap="seq"))
        # the kernel's own step-count formula — keeps the one-extra-panel
        # memory gate anchored to ops/summa's schedule.  PER-DEVICE
        # bytes (memory_analysis accounts one device): the broadcast A
        # panel lives (M/rows, kb) on each device, the B panel (kb,
        # N/cols) (review-found: global bytes made the bound ~mesh-
        # factor too loose)
        from dislib_tpu.ops.summa import summa_steps
        steps = summa_steps(mesh)
        panel_set = (ad.size // src[0]
                     + bd.size // src[1]) * ad.dtype.itemsize // steps
        counter_key, expect = "summa_matmul", 1
        # the routed entry (math.matmul) must counter-visibly run the
        # schedule the env selects
        ds.matmul(a, b, algorithm="summa").force()
        sched_counts = _prof.schedule_counters()
        assert any(k.startswith("summa_matmul:") for k in sched_counts), \
            f"summa route left no schedule counter: {sched_counts}"
    elif kind == "rechunk":
        from dislib_tpu.ops import rechunk as _rc
        from dislib_tpu.parallel import mesh as _mesh_mod
        ds.init(src)
        a = ds.array(x_host).force()
        ds.init(dst)
        dst_mesh = _mesh_mod.get_mesh()

        def run(sched, comm_only=False):
            if comm_only:
                _sync(_rc.panel_comm_probe(a._data, a.shape, dst_mesh,
                                           panels, overlap=sched))
            else:
                _sync(_rc.panel_rechunk(a._data, a.shape, dst_mesh, panels,
                                        overlap=sched))

        out_db = np.asarray(_rc.panel_rechunk(a._data, a.shape, dst_mesh,
                                              panels, overlap="db"))
        out_seq = np.asarray(_rc.panel_rechunk(a._data, a.shape, dst_mesh,
                                               panels, overlap="seq"))
        ma_db = _rc.panel_memory_analysis(a._data, a.shape, dst_mesh,
                                          panels, overlap="db")
        ratio = ma_db["peak_live_ratio"] if ma_db["peak_live_ratio"] \
            is not None else ma_db["analytic_ratio"]
        ratio_max = float(os.environ.get(
            "DSLIB_OVERLAP_PEAK_RATIO_MAX", min(1.0 + 2.0 / panels, 1.5)))
        if ratio > ratio_max:
            msg = (f"OVERLAP MEMORY GATE FAILED: double-buffered rechunk "
                   f"peak-live {ratio:.3f}x exceeds the {ratio_max:.3f}x "
                   "bound (1 + 2/k against the tier's 1.5x ceiling) — the "
                   "extra in-flight panel must cost one panel, not a copy")
            print(msg, file=sys.stderr, flush=True)
            raise AssertionError(msg)
        extra.update({"peak_live_ratio_db": ratio,
                      "peak_live_ratio_max": ratio_max,
                      "panels": ma_db["panels"]})
        steps = ma_db["panels"]
        panel_set = ma_db["analytic_temp_bytes"]
        counter_key, expect = "rechunk_panels", 1
        lower = None
    elif kind == "ring":
        from dislib_tpu.ops.ring import ring_kneighbors
        from dislib_tpu.parallel import mesh as _mesh_mod
        ds.init(src)
        mesh = _mesh_mod.get_mesh()
        k_nn = 5
        # asymmetric shapes: FEW query rows against the full fitted set,
        # so the rotated shard (the hideable comm) is a meaningful share
        # of each step — the fold at square shapes dwarfs the rotation
        # and the hidden fraction would measure pure scheduler noise
        mq = max(64, m // 16)
        q = ds.array(x_host[:mq]).force()
        f = ds.array(x_host).force()
        qd, fd = q._data, f._data

        def run(sched, comm_only=False):
            out = ring_kneighbors(qd, fd, mesh, k_nn, m, overlap=sched,
                                  comm_only=comm_only)
            _sync(*(out if isinstance(out, tuple) else (out,)))

        def lower(sched):
            return ring_kneighbors.lower(qd, fd, mesh, k_nn, m,
                                         overlap=sched)

        d_db, i_db = ring_kneighbors(qd, fd, mesh, k_nn, m, overlap="db")
        d_seq, i_seq = ring_kneighbors(qd, fd, mesh, k_nn, m, overlap="seq")
        out_db = np.concatenate([np.asarray(d_db),
                                 np.asarray(i_db, np.float32)], axis=1)
        out_seq = np.concatenate([np.asarray(d_seq),
                                  np.asarray(i_seq, np.float32)], axis=1)
        steps = src[0]
        # rotated set per hop, PER-DEVICE (memory_analysis accounts one
        # device): the (rows_loc, n/cols) fitted block + its norms + ids
        # (review-found: the global feature dim made the bound too loose)
        rows_loc = fd.shape[0] // src[0]
        panel_set = rows_loc * (fd.shape[1] // src[1] + 2) \
            * fd.dtype.itemsize
        # counter-assert the PUBLIC path: one profiled ring dispatch per
        # kneighbors call (the estimator boundary)
        nn = ds.NearestNeighbors(n_neighbors=k_nn, ring=True).fit(f)
        nn.kneighbors(q)                    # warm
        _prof.reset_counters()
        nn.kneighbors(q)
        got = _prof.counters()["dispatch_by"].get("ring_kneighbors")
        assert got == 1, \
            f"ring kneighbors path cost {got} ring dispatches, expected 1"
    else:
        raise ValueError(f"unknown overlap bench kind {kind!r}")

    # bit-equality gate: the two schedules consume panels in identical
    # order with identical ops
    np.testing.assert_array_equal(out_db, out_seq)

    # dispatch gate under the db schedule (the ring KERNEL is counted at
    # its estimator boundary — asserted in the ring branch above)
    run("db")                               # warm
    if kind != "ring":
        _prof.reset_counters()
        run("db")
        d = _prof.counters()["dispatch_by"].get(counter_key, 0)
        assert d == expect, \
            f"{kind} db schedule cost {d} dispatches, expected {expect}"

    # summa/ring memory bound: the db program's temp may exceed seq's by
    # at most one in-flight panel set (+50% scheduler slack) — XLA's own
    # accounting of "the double buffer costs one panel, not a copy"
    if kind in ("summa", "ring") and lower is not None:
        try:
            t_db = int(lower("db").compile().memory_analysis()
                       .temp_size_in_bytes)
            t_seq = int(lower("seq").compile().memory_analysis()
                        .temp_size_in_bytes)
        except Exception:   # noqa: BLE001 — backend without the analysis
            t_db = t_seq = None
        if t_db is not None:
            slack = max(panel_set // 2, 65536)
            assert t_db <= t_seq + panel_set + slack, (
                f"OVERLAP MEMORY GATE FAILED: {kind} db temp {t_db} vs seq "
                f"{t_seq} — the double buffer costs more than one "
                f"in-flight panel set ({panel_set} B)")
            extra.update({"temp_bytes_db": t_db, "temp_bytes_seq": t_seq,
                          "panel_set_bytes": panel_set})

    # timing: both schedules + the broadcast-only probe, all steady-state.
    # INTERLEAVED rounds + BEST-of wall (the _peak_gflops precedent):
    # the hidden fraction is a DIFFERENCE of two walls divided by a
    # small third — on a cpu-shares-throttled container, (a) measuring
    # the schedules in separate blocks lets throttle drift bias the
    # difference, so each round times db, seq and the probe back to
    # back, and (b) median contention noise swamps the delta, while the
    # min wall estimates each schedule's uncontended cost
    run("seq")
    run("seq", comm_only=True)
    walls = {"db": [], "seq": [], "comm": []}
    for _ in range(repeats):
        for key, fn in (("db", lambda: run("db")),
                        ("seq", lambda: run("seq")),
                        ("comm", lambda: run("seq", comm_only=True))):
            t0 = time.perf_counter()
            fn()
            walls[key].append(time.perf_counter() - t0)
    t_db = float(min(walls["db"]))
    t_seq = float(min(walls["seq"]))
    t_comm = float(min(walls["comm"]))
    hidden = (t_seq - t_db) / t_comm if t_comm > 0 else 0.0
    floor = float(os.environ.get("DSLIB_OVERLAP_HIDDEN_MIN", hidden_floor))
    res = {"metric": f"overlap_{kind}_{tag}_comm_hidden_frac (baseline: "
                     "sequential-phase schedule, same program)",
           "value": round(hidden, 3), "unit": "frac",
           "vs_baseline": round(t_seq / t_db, 3) if t_db > 0 else None,
           "db_wall_s": round(t_db, 5), "seq_wall_s": round(t_seq, 5),
           "comm_alone_wall_s": round(t_comm, 5),
           "comm_hidden_floor": floor, "steps": steps,
           "dispatches_per_op": 1, "fresh": True,
           "note": "comm_hidden = (t_seq - t_db) / t_comm_alone; "
                   "t_comm_alone = broadcast-only variant of the same "
                   "program; gates: db==seq bit-equal, 1 dispatch, "
                   "peak-live within one extra in-flight panel",
           **extra}
    if hidden < floor:
        msg = (f"OVERLAP GATE FAILED: {kind} comm-hidden fraction "
               f"{hidden:.3f} below the {floor:.3f} floor — the "
               "double-buffered schedule is not hiding comm on this rig")
        print(msg, file=sys.stderr, flush=True)
        raise AssertionError(msg)
    return res


def bench_fused_chain(dim, n_ops, tag):
    """Fused-chain microbench (round-7 fusion PR): ONE user-visible op
    chain — scale/add/transpose rounds ending in a matmul — forced as a
    single XLA dispatch, vs the same chain under DSLIB_EAGER=1 paying one
    dispatch per op.  The chain is rebuilt inside the timed region (graph
    construction is part of the fused path's cost); results are gated
    bit-identical between the two modes.  `value` is the speedup — the
    measured answer to "what did the fusion layer buy on this rig"."""
    import dislib_tpu as ds
    from dislib_tpu.utils import profiling as prof

    rng = np.random.RandomState(0)
    x_host = rng.rand(dim, dim).astype(np.float32)
    a = ds.array(x_host, block_size=(dim, dim)).force()

    def chain():
        y = a
        for i in range(n_ops // 4):
            y = ((y * 1.0001 + 0.0001).T - 0.0001).T
        y = ds.matmul(y, a, transpose_a=True)
        return y

    def run():
        y = chain()
        y.force()
        _sync(y._data)

    old = os.environ.pop("DSLIB_EAGER", None)
    try:
        run()                                   # fused warmup/compile
        prof.reset_counters()
        run()
        fused_disp = prof.dispatch_count()
        fused_ref = chain().collect()
        t_fused = _median_time(run)

        os.environ["DSLIB_EAGER"] = "1"
        run()                                   # eager warmup/compile
        prof.reset_counters()
        run()
        eager_disp = prof.dispatch_count()
        # correctness gate: shared op bodies ⇒ identical rounding per op;
        # the one permitted divergence is XLA's in-program FMA contraction
        # (≤ 1 ulp per mul→add round — see data/array.py::_exec_program),
        # so the bound scales with the chain's contraction count
        eager_ref = chain().collect()
        np.testing.assert_allclose(fused_ref, eager_ref,
                                   rtol=n_ops * 3e-7, atol=1e-6)
        t_eager = _median_time(run)
    finally:
        if old is None:
            os.environ.pop("DSLIB_EAGER", None)
        else:
            os.environ["DSLIB_EAGER"] = old
    speedup = t_eager / t_fused
    return {"metric": f"fused_chain_{tag}_{n_ops}ops_speedup_vs_eager "
                      "(baseline: same chain, DSLIB_EAGER=1 per-op "
                      "dispatch)",
            "value": round(speedup, 2), "unit": "x",
            "vs_baseline": round(speedup, 2),
            "fused_wall_s": round(t_fused, 5),
            "eager_wall_s": round(t_eager, 5),
            "dispatches_fused": fused_disp,
            "dispatches_eager": eager_disp,
            "note": "one forced chain per region; dispatches_* from the "
                    "utils.profiling counters"}


def _predict_dispatches(est, a) -> int:
    """``dispatches_per_predict`` from the utils.profiling counters: warm
    the predict program, then count one fresh end-to-end call (force
    included) — the "one program per result, not per op" claim as a
    number, now measured for every counted estimator (round-9 satellite:
    the counters are what caught the CSVM/forest host-sync hops)."""
    from dislib_tpu.utils import profiling as _prof
    est.predict(a).force()                  # warm/compile
    _prof.reset_counters()
    est.predict(a).force()
    return _prof.dispatch_count()


def bench_serving(m, n, k, n_requests, tag, buckets=(1, 8, 64, 512),
                  deadline_ms=2):
    """Serving-layer bench (round-9 tentpole): warm request p50/p99/QPS
    through the micro-batching server vs the per-call COLD
    ``predict().force()`` path — each cold call hits a padded shape the
    jit cache has never seen, which is exactly what an unbucketed request
    loop pays (every new batch size = a fresh trace+compile).

    Hard asserts (regression gates, not just reported numbers):
    - every warm served batch is EXACTLY one fused XLA dispatch
      (profiling counters through the server's per-batch accounting);
    - served labels bit-match the direct pipeline's labels.
    """
    import dislib_tpu as ds
    from dislib_tpu.parallel import mesh as _mesh_mod
    from dislib_tpu.serving import PredictServer, ServePipeline

    rng = np.random.RandomState(0)
    x_host = rng.rand(m, n).astype(np.float32)
    a = ds.array(x_host, block_size=(m, n))
    scaler = ds.StandardScaler().fit(a)
    est = ds.KMeans(n_clusters=k, max_iter=5, random_state=0).fit(a)
    pipe = ServePipeline(est, transforms=(scaler,), n_features=n)

    # correctness gate: the served bucket path == the direct pipeline
    probe = x_host[: buckets[1]]
    direct = np.asarray(
        est.predict(scaler.transform(ds.array(probe))).collect())
    np.testing.assert_array_equal(pipe.predict_bucket(probe, buckets[1]),
                                  direct)

    # COLD path: per-call predict at FRESH padded shapes (each row count
    # below lands on a padded shape no earlier call compiled)
    q = _mesh_mod.pad_quantum()
    cold = []
    for i in range(1, 8):
        rows = x_host[: q * i + 1]
        t0 = time.perf_counter()
        out = est.predict(scaler.transform(ds.array(rows))).force()
        _sync(out._data)
        cold.append(time.perf_counter() - t0)
    cold_p50 = float(np.median(cold))

    # WARM path: the server (buckets AOT-warmed at start()) under a
    # burst-submitted request stream of mixed sizes
    sizes = rng.randint(1, min(buckets[-2], 64) + 1, n_requests)
    starts = rng.randint(0, m - int(sizes.max()), n_requests)
    reqs = [x_host[s:s + sz] for s, sz in zip(starts, sizes)]
    with PredictServer(pipeline=pipe, buckets=buckets,
                       deadline_ms=deadline_ms) as srv:
        futs = [srv.submit(r) for r in reqs]
        outs = [f.result(timeout=120) for f in futs]
        st = srv.stats()
    assert st["dispatches_per_batch_max"] == 1, \
        f"serving dispatch invariant broken: {st}"
    for r, o in zip(reqs, outs):
        assert o.values.shape == (len(r), 1) \
            and np.all(np.isfinite(o.values)), "bad served response"
    p50 = st["p50_ms"]
    return {"metric": f"serving_{tag}_warm_p50_ms (baseline: per-call "
                      "cold predict().force() at fresh shapes)",
            "value": p50, "unit": "ms",
            "vs_baseline": round(cold_p50 * 1e3 / p50, 2),
            "p99_ms": st["p99_ms"], "qps": st["qps"],
            "rows_per_s": st["rows_per_s"],
            "requests": st["requests"], "batches": st["batches"],
            "dispatches_per_batch_max": st["dispatches_per_batch_max"],
            "cold_p50_ms": round(cold_p50 * 1e3, 3),
            "deadline_ms": deadline_ms, "buckets": list(buckets),
            "note": "warm batches asserted 1 fused dispatch each; cold = "
                    "scaler+predict+force per call, fresh padded shape "
                    "(trace+compile on the request path); vs_baseline = "
                    "cold_p50 / warm_p50"}


def bench_serving_fleet(m, n, k, n_requests, tag, buckets=(1, 8, 64),
                        deadline_ms=2, coldstart_min=None):
    """Round-15 tentpole tier: AOT deployment bundles + multi-tenant
    routing.

    Leg 1 — COLD START: time-to-first-response-for-the-whole-ladder in a
    cache-cleared process, with vs without the bundle.  Without: every
    bucket pays its trace+compile (``jax.clear_caches()`` reproduces the
    fresh-process state in-process; the subprocess twin lives in
    ``tests/test_serving_fleet.py``).  With: ``load_bundle`` deserializes
    the compiled executables and serves — gated ZERO traces.

    Leg 2 — FLEET: three tenants on ONE shared server serving the
    bundle pipeline under a mixed-shape burst; QPS and per-tenant p99
    come from the server's OWN per-tenant accounting (round-15
    satellite), not from timing wrapped around it.

    Hard gates: cold/bundle ratio >= ``coldstart_min``
    (``DSLIB_BUNDLE_COLDSTART_MIN``, default 10 — calibrated ~16x on the
    reference rig), zero traces on the bundle path AND under tenant
    load, zero shed, one fused dispatch per warm batch, bundle
    predictions bit-equal to the in-process pipeline's.
    """
    import tempfile
    import jax
    import dislib_tpu as ds
    from dislib_tpu.serving import (ModelRouter, PredictServer,
                                    ServePipeline, export_bundle,
                                    load_bundle)
    from dislib_tpu.utils import profiling as _prof

    if coldstart_min is None:
        coldstart_min = float(os.environ.get("DSLIB_BUNDLE_COLDSTART_MIN",
                                             "10"))
    # the harness's persistent compilation cache (every child enables
    # it) would let the "cold" leg replay its compiles from disk and
    # understate what a genuinely fresh process pays — this config
    # measures cold start, so it opts out (it runs in its own child
    # process; no other config is affected)
    jax.config.update("jax_enable_compilation_cache", False)
    rng = np.random.RandomState(0)
    x_host = rng.rand(m, n).astype(np.float32)
    a = ds.array(x_host, block_size=(m, n))
    scaler = ds.StandardScaler().fit(a)
    est = ds.KMeans(n_clusters=k, max_iter=5, random_state=0).fit(a)
    pipe = ServePipeline(est, transforms=(scaler,), n_features=n)

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "model.dsb.npz")
        export_bundle(pipe, path, buckets=buckets)
        ref = {b: pipe.predict_bucket(x_host[: min(b, 16)], b)
               for b in buckets}

        # cold start WITHOUT the bundle: first response for every ladder
        # bucket pays trace+compile
        jax.clear_caches()
        t0 = time.perf_counter()
        for b in buckets:
            pipe.predict_bucket(x_host[:1], b)
        cold_s = time.perf_counter() - t0

        # cold start WITH the bundle: deserialize + first batch, and not
        # one trace anywhere
        jax.clear_caches()
        tr0 = _prof.trace_count()
        t0 = time.perf_counter()
        loaded = load_bundle(path)
        for b in buckets:
            loaded.pipeline.predict_bucket(x_host[:1], b)
        bundle_s = time.perf_counter() - t0
        bundle_traces = _prof.trace_count() - tr0
        ratio = cold_s / bundle_s
        for b in buckets:
            np.testing.assert_array_equal(
                loaded.pipeline.predict_bucket(x_host[: min(b, 16)], b),
                ref[b])
        if bundle_traces:
            raise AssertionError(
                f"bundle path traced {bundle_traces}x — the zero-retrace "
                "cold-start claim is broken")
        if ratio < coldstart_min:
            raise AssertionError(
                f"bundle cold-start speedup {ratio:.2f}x < gate "
                f"{coldstart_min}x (cold {cold_s * 1e3:.1f} ms, bundle "
                f"{bundle_s * 1e3:.1f} ms; override via "
                "DSLIB_BUNDLE_COLDSTART_MIN)")

        # fleet leg: 3 tenants x mixed shapes on one shared server
        tenants = ("alpha", "beta", "gamma")
        srv = PredictServer(pipeline=loaded.pipeline, buckets=buckets,
                            deadline_ms=deadline_ms, name="fleet")
        router = ModelRouter(name="fleet")
        for t in tenants:
            router.add_tenant(t, srv)
        sizes = rng.randint(1, min(buckets[-1], 64) + 1, n_requests)
        starts = rng.randint(0, m - int(sizes.max()), n_requests)
        tr0 = _prof.trace_count()
        with router:
            futs = [router.submit(x_host[s:s + sz], tenants[i % 3],
                                  key=str(i))
                    for i, (s, sz) in enumerate(zip(starts, sizes))]
            outs = [f.result(timeout=120) for f in futs]
            st = srv.stats()
        if _prof.trace_count() != tr0:
            raise AssertionError("multi-tenant load compiled something — "
                                 "executable sharing is broken")
        if st["dispatches_per_batch_max"] != 1:
            raise AssertionError(f"serving dispatch invariant broken: {st}")
        if st["shed"] or any(v["shed"] for v in st["tenants"].values()):
            raise AssertionError(f"requests shed under fleet load: {st}")
        for o in outs:
            if not np.all(np.isfinite(o.values)):
                raise AssertionError("bad served response")
        per_tenant = {t: {"requests": st["tenants"][t]["requests"],
                          "p50_ms": st["tenants"][t]["p50_ms"],
                          "p99_ms": st["tenants"][t]["p99_ms"]}
                      for t in tenants}

    return {"metric": f"serving_fleet_{tag}_coldstart_ratio (baseline: "
                      "fresh-process trace+compile of the whole ladder)",
            "value": round(ratio, 2), "unit": "x",
            "vs_baseline": round(ratio, 2),
            "coldstart_min_gate": coldstart_min,
            "cold_ms": round(cold_s * 1e3, 3),
            "bundle_ms": round(bundle_s * 1e3, 3),
            "bundle_traces": bundle_traces,
            "fleet_qps": st["qps"], "fleet_p99_ms": st["p99_ms"],
            "tenants": per_tenant,
            "requests": st["requests"], "batches": st["batches"],
            "dispatches_per_batch_max": st["dispatches_per_batch_max"],
            "shed": st["shed"],
            "deadline_ms": deadline_ms, "buckets": list(buckets),
            "fresh": True,
            "note": "leg 1: cold = clear_caches + per-bucket "
                    "trace+compile; bundle = load_bundle + first batch, "
                    "zero traces gated.  leg 2: 3 tenants share one "
                    "server/executable set; per-tenant p50/p99 read from "
                    "the server's own stats()"}


def bench_trainer(rows, n, k, generations, tag, batches_per_generation=4,
                  buckets=(1, 8, 64), deadline_ms=2):
    """Round-17 tier: the continuous-learning loop end-to-end —
    ``ContinuousTrainer`` drives train → bundle → canary → promote for
    ``generations`` cadences of a streaming ``MiniBatchKMeans`` against
    a live ``ModelRouter`` tenant, and the row reads the cadence the
    loop sustains plus where the wall goes (train vs export vs promote,
    per-phase from the promotion ledger's own timings).

    Hard gates: every generation promotes (the canary health gate passes
    a clean stream), the served generation lands on the last one, the
    post-promotion burst through the router performs ZERO traces (the
    canary serves deserialized AOT executables — promotion never
    recompiles the predict path), every response finite, and the on-disk
    ``ledger.jsonl`` replays the in-memory promotion ledger exactly."""
    import tempfile
    import dislib_tpu as ds
    from dislib_tpu.runtime import ContinuousTrainer
    from dislib_tpu.serving import ModelRouter, ServePipeline
    from dislib_tpu.utils import FitCheckpoint
    from dislib_tpu.utils import profiling as _prof

    rng = np.random.RandomState(0)
    centers = (rng.rand(k, n) * 10).astype(np.float32)

    def stream():
        while True:
            lab = rng.randint(0, k, rows)
            yield (centers[lab]
                   + 0.3 * rng.randn(rows, n)).astype(np.float32)

    probe = (centers[rng.randint(0, k, 16)]
             + 0.3 * rng.randn(16, n)).astype(np.float32)
    with tempfile.TemporaryDirectory() as td:
        router = ModelRouter(name="trainer-bench")
        tr = ContinuousTrainer(
            ds.MiniBatchKMeans(n_clusters=k, random_state=0), stream(),
            FitCheckpoint(os.path.join(td, "ck.npz"), every=2, keep=2),
            lambda est, g: ServePipeline(est, n_features=n),
            os.path.join(td, "bundles"), router=router, tenant="alpha",
            buckets=buckets, batches_per_generation=batches_per_generation,
            probe=probe, deadline_ms=deadline_ms, name="bench-trainer")
        t_train = 0.0
        burst_traces = 0
        with router:
            t_all = time.perf_counter()
            for _ in range(generations):
                t0 = time.perf_counter()
                if not tr.train_generation():
                    raise AssertionError("infinite stream exhausted?!")
                t_train += time.perf_counter() - t0
                rec = tr.publish_generation()
                if rec["verdict"] != "promoted":
                    raise AssertionError(
                        f"clean generation {rec['generation']} not "
                        f"promoted: {rec}")
                # post-promotion burst: mixed shapes through the router,
                # zero traces gated — promotion must never recompile the
                # predict path
                tr0 = _prof.trace_count()
                futs = [router.submit(probe[: 1 + (i % len(probe))],
                                      "alpha",
                                      key=f"g{rec['generation']}:{i}")
                        for i in range(16)]
                outs = [f.result(timeout=120) for f in futs]
                burst_traces += _prof.trace_count() - tr0
                for o in outs:
                    if not np.all(np.isfinite(o.values)):
                        raise AssertionError("bad served response")
            wall = time.perf_counter() - t_all
            stats = tr.stats()
            tr.close()
        if burst_traces:
            raise AssertionError(
                f"promotion bursts traced {burst_traces}x — the "
                "zero-retrace promotion claim is broken")
        if stats["promotions"] != generations \
                or stats["served_generation"] != generations:
            raise AssertionError(f"promotion ledger off: {stats}")
        with open(os.path.join(td, "bundles", "ledger.jsonl")) as f:
            disk = [json.loads(line) for line in f]
        if disk != tr.ledger:
            raise AssertionError("ledger.jsonl does not replay the "
                                 "in-memory promotion ledger")
        exp = [r["export_s"] for r in tr.ledger if "export_s" in r]
        pro = [r["promote_s"] for r in tr.ledger if "promote_s" in r]

    return {"metric": f"trainer_{tag}_generations_per_min (train -> "
                      "bundle -> canary -> promote cadence, all promoted)",
            "value": round(generations / (wall / 60.0), 2),
            "unit": "gen/min", "vs_baseline": None,
            "generations": generations,
            "batches_per_generation": batches_per_generation,
            "train_s_per_gen": round(t_train / generations, 4),
            "export_s_per_gen": round(float(np.mean(exp)), 4),
            "export_s_max": round(float(np.max(exp)), 4),
            "promote_s_per_gen": round(float(np.mean(pro)), 4),
            "burst_traces": burst_traces,
            "batches": stats["batches"],
            "quarantined_rows": stats["quarantine"]["n_quarantined"],
            "buckets": list(buckets), "fresh": True,
            "note": "per-phase walls from the promotion ledger's own "
                    "export_s/promote_s; gates: all generations promoted, "
                    "zero traces on the post-promotion burst, finite "
                    "responses, ledger.jsonl == in-memory ledger"}


def bench_resilience(m, n, k, iters, tag, every=2):
    """Resilience-layer row (round-12): a NaN-poisoned chunked KMeans fit
    heals through the fit-loop driver's rollback ladder.  Three gates,
    all hard: (1) the healed model equals the unfaulted checkpointed fit;
    (2) dispatch parity — the resilience counters are host-side integers,
    so the ONLY extra device work of the healed fit is the one re-run
    chunk (PR-2/PR-3 counter baseline + exactly 1); (3) the counters
    actually recorded the rollback.  ``value`` is the healed fit's wall —
    informational; the gates are the point."""
    import tempfile
    import dislib_tpu as ds
    from dislib_tpu.cluster import KMeans
    from dislib_tpu.utils import FitCheckpoint, faults
    from dislib_tpu.utils import profiling as _prof

    rng = np.random.RandomState(0)
    x_host = rng.rand(m, n).astype(np.float32)
    init = x_host[rng.choice(m, k, replace=False)].copy()
    a = ds.array(x_host, block_size=(m, n))
    kw = dict(n_clusters=k, init=init, max_iter=iters, tol=0.0)
    with tempfile.TemporaryDirectory() as td:
        ck = FitCheckpoint(os.path.join(td, "w.npz"), every=every)
        KMeans(**kw).fit(a, checkpoint=ck)          # warm the compiles
        ck.delete()
        _prof.reset_counters()
        ref = KMeans(**kw).fit(
            a, checkpoint=FitCheckpoint(os.path.join(td, "r.npz"),
                                        every=every))
        clean = _prof.counters()["dispatch_by"].get("kmeans_fit", 0)
        pol = faults.NaNAtChunk(at_chunk=2)
        _prof.reset_counters()
        t0 = time.perf_counter()
        res = KMeans(**kw).fit(
            a, checkpoint=FitCheckpoint(os.path.join(td, "f.npz"),
                                        every=every),
            health=pol)
        heal_wall = time.perf_counter() - t0
        faulted = _prof.counters()
    np.testing.assert_allclose(res.centers_, ref.centers_, rtol=1e-5)
    extra = faulted["dispatch_by"].get("kmeans_fit", 0) - clean
    r = faulted["resilience"]
    if pol.fired != 1:
        raise AssertionError("fault was never injected")
    if extra != 1:
        raise AssertionError(
            f"healed fit cost {extra} extra fit dispatches — the counters "
            "or the driver added device work beyond the 1 re-run chunk")
    if r.get("rollbacks") != 1 or r.get("chunk_retries") != 1:
        raise AssertionError(f"resilience counters did not record the "
                             f"rollback: {r}")
    return {"metric": f"resilience_{tag}_heal_wall_s",
            "value": round(heal_wall, 4), "unit": "s", "vs_baseline": None,
            "fault": f"NaNAtChunk(at_chunk=2) over {iters} iters, "
                     f"every={every}",
            "rollbacks": r["rollbacks"], "chunk_retries": r["chunk_retries"],
            "escalations_retry": r.get("escalations_retry", 0),
            "extra_fit_dispatches": extra,
            "clean_fit_dispatches": clean,
            "healed_equals_unfaulted": True}


def bench_mh_resilience(tag, max_wall_s=480.0, recovery_max_s=30.0):
    """Round-20 multi-host survival tier: the REAL process-killing chaos
    drill (``tools/mh_dryrun.py --chaos``) as a gated bench row.  Two
    coordinated CPU processes; one is SIGKILLed mid-fit, restarted,
    heartbeat-delayed, fed torn coordination/ledger writes, and killed
    again at the sharded-bundle load barrier.  Gates, all hard:

    - the drill PASSES — typed attributed ``RankDead``, the survivor's
      resumed model equals the shrunk-fleet oracle, the restart rejoins
      under a bumped epoch (stale writes fenced) and grows back, torn
      files heal as TRANSIENT, and BOTH barrier-abort modes are typed;
    - zero hangs — the whole episode is bounded by ``max_wall_s`` (the
      drill additionally hard-bounds every internal wait);
    - recovery wall — death → published shrunk capacity under
      ``recovery_max_s``;
    - the rank_deaths / rank_rejoins / mesh_shrinks / mesh_grows /
      bundle_barrier_abort counters all actually recorded.

    ``value`` is the full-episode wall — informational; the gates are
    the point (the ``bench_resilience`` precedent)."""
    import shutil
    import tempfile
    here = os.path.dirname(os.path.abspath(__file__))
    driver = os.path.join(here, "tools", "mh_dryrun.py")
    workdir = tempfile.mkdtemp(prefix="dslib-bench-mh-")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    try:
        try:
            proc = subprocess.run(
                [sys.executable, driver, "--chaos", workdir],
                env=env, capture_output=True, text=True,
                timeout=max_wall_s)
        except subprocess.TimeoutExpired:
            raise AssertionError(
                f"HANG: the chaos drill exceeded {max_wall_s}s")
        wall = time.perf_counter() - t0
        out = proc.stdout + proc.stderr
        if proc.returncode != 0 or "MULTIHOST CHAOS: PASS" not in out:
            raise AssertionError(
                f"chaos drill failed (rc={proc.returncode}): "
                f"{out[-2000:]}")
        with open(os.path.join(workdir, "chaos_result.json")) as f:
            result = json.load(f)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    c, t = result["counters"], result["timings"]
    for key, want in (("rank_deaths", 3), ("rank_rejoins", 2),
                      ("mesh_shrinks", 2), ("mesh_grows", 1),
                      ("bundle_barrier_abort", 2)):
        if c.get(key, 0) < want:
            raise AssertionError(
                f"counter {key}={c.get(key, 0)} < {want}: {c}")
    if t["death_to_capacity_s"] > recovery_max_s:
        raise AssertionError(
            f"recovery wall {t['death_to_capacity_s']:.2f}s exceeds "
            f"{recovery_max_s}s")
    return {"metric": f"mh_resilience_{tag}_episode_wall_s",
            "value": round(wall, 2), "unit": "s", "vs_baseline": None,
            "death_to_capacity_s": round(t["death_to_capacity_s"], 2),
            "barrier_abort_attributed_s":
                round(t["barrier_abort_attributed_s"], 2),
            "barrier_abort_deadline_s":
                round(t["barrier_abort_deadline_s"], 2),
            "counters": c, "healed_equals_shrunk_oracle": True,
            "rejoin_epoch_fenced": True, "hangs": 0}


def bench_rtt(repeats=21):
    """Fixed per-dispatch round-trip floor of this backend (informational).

    Times a trivial jitted op (8×8 add) plus a 1-element fetch — the same
    dispatch+sync structure every timed config pays exactly once per run,
    reported so short-wall-clock rows can be read against it."""
    return {"metric": "dispatch_rtt_trivial_op_ms "
                      "(informational: per-call latency floor)",
            "value": round(1e3 * _measure_rtt(repeats), 2), "unit": "ms",
            "vs_baseline": None}


def bench_tsqr(m, n):
    """tsQR wall clock — measures BOTH local-factorisation policies
    (Householder tree and CholeskyQR2) and reports the auto policy's
    number as the headline, so one on-chip capture IS the A/B that
    decides whether the TPU-gated CholeskyQR2 path stays (round-4
    measurably-better rule; the flag is a static retrace key, so flipping
    it between timed regions is sound)."""
    import dislib_tpu as ds

    rng = np.random.RandomState(0)
    x_host = rng.standard_normal((m, n)).astype(np.float32)
    t0 = time.perf_counter()
    np.linalg.qr(x_host)
    cpu_wall = time.perf_counter() - t0

    a = ds.array(x_host, block_size=(m // max(1, len(__import__("jax").devices())), n))
    q, r = ds.tsqr(a)  # warmup + correctness gate (auto policy)
    qh, rh = q.collect(), r.collect()
    np.testing.assert_allclose(qh @ rh, x_host, rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(qh.T @ qh, np.eye(n), atol=1e-2)

    def run():
        q, r = ds.tsqr(a)
        _sync(q, r)

    variants = {}
    old = os.environ.get("DSLIB_TSQR_CHOLQR")
    try:
        for name, flag in (("tree", "0"), ("cholqr2", "1")):
            os.environ["DSLIB_TSQR_CHOLQR"] = flag
            run()                                   # warmup/compile
            variants[name] = _median_time(run)
    finally:
        if old is None:
            os.environ.pop("DSLIB_TSQR_CHOLQR", None)
        else:
            os.environ["DSLIB_TSQR_CHOLQR"] = old
    # the headline is whichever variant the ambient policy selects — no
    # third timed region (it would duplicate one of the two, and label it
    # 'auto' even when the caller forced the env)
    from dislib_tpu.decomposition.tsqr import _use_cholqr
    policy = "cholqr2" if _use_cholqr() else "tree"
    t = variants[policy]
    return {"metric": f"tsqr_{m}x{n}_wall_s (baseline: numpy qr single-node)",
            "value": round(t, 4), "unit": "s",
            "vs_baseline": round(cpu_wall / t, 2),
            "tree_wall_s": round(variants["tree"], 4),
            "cholqr2_wall_s": round(variants["cholqr2"], 4),
            "note": f"value = the active policy's ({policy}) measurement; "
                    "tree/cholqr2 fields are the explicit A/B"}


def bench_randomsvd(m, n, nsv=64, iters=2):
    import dislib_tpu as ds
    from dislib_tpu.decomposition import random_svd

    rng = np.random.RandomState(0)
    # Spectral decay (0.95^j column scaling) makes the 1% gate well-posed:
    # on a FLAT Gaussian spectrum, sketch-and-project with oversample=10
    # leaves ~6% error vs the exact values for BOTH the device path and
    # the proxy, and since the two draw DIFFERENT test matrices Ω (jax vs
    # numpy RNG) their estimates differ by up to ~1.5% from each other —
    # the pre-round-8 smoke-gate flake, reproduced back to PR 1.  With
    # decay (the workload truncated SVD exists for) both land within
    # ~0.2% of the exact spectrum; the timed GEMMs are value-independent,
    # so the wall-clock metric is unaffected.  Regression-pinned by
    # tests/test_math.py::test_randomsvd_smoke_gate_margin.
    x_host = (rng.standard_normal((m, n))
              * 0.95 ** np.arange(n)).astype(np.float32)
    sketch = nsv + 10
    t0 = time.perf_counter()
    _, s_proxy, _ = _numpy_random_svd(x_host, sketch, iters)
    cpu_wall = time.perf_counter() - t0

    a = ds.array(x_host, block_size=(m // 8, n))
    u, s, v = random_svd(a, iters=iters, nsv=nsv, oversample=10,
                         random_state=0)  # warmup
    # correctness gate: top singular values match the proxy to 1%
    s_dev = np.asarray(s.collect()).ravel()[:nsv]
    np.testing.assert_allclose(s_dev[:16], s_proxy[:16], rtol=1e-2)

    def run():
        u, s, v = random_svd(a, iters=iters, nsv=nsv, oversample=10,
                             random_state=0)
        _sync(u, s, v)
    t = _median_time(run)
    return {"metric": f"randomsvd_{m}x{n}_nsv{nsv}_wall_s "
                      "(baseline: numpy same-algorithm single-node proxy)",
            "value": round(t, 4), "unit": "s",
            "vs_baseline": round(cpu_wall / t, 2)}


def bench_svd(m, n):
    """One-sided block-Jacobi SVD wall clock (informational config — the
    column-BLOCK pair tier, reference's own pairing, MXU-shaped)."""
    import dislib_tpu as ds

    rng = np.random.RandomState(0)
    x_host = rng.rand(m, n).astype(np.float32)
    t0 = time.perf_counter()
    s_ref = np.linalg.svd(x_host, compute_uv=False)
    cpu_wall = time.perf_counter() - t0

    a = ds.array(x_host, block_size=(m // 4, n))
    u, s, v = ds.svd(a)  # warmup + correctness gate
    s_dev = np.asarray(s.collect()).ravel()
    np.testing.assert_allclose(s_dev, s_ref, rtol=1e-3, atol=1e-3 * s_ref[0])

    def run():
        u, s, v = ds.svd(a)
        _sync(u, s, v)
    t = _median_time(run)
    return {"metric": f"svd_{m}x{n}_wall_s (baseline: numpy lapack svd "
                      "single-node)",
            "value": round(t, 4), "unit": "s",
            "vs_baseline": round(cpu_wall / t, 2)}


def bench_gmm(m, n, k, iters=5):
    import dislib_tpu as ds
    from dislib_tpu.cluster import GaussianMixture

    rng = np.random.RandomState(0)
    x_host = rng.standard_normal((m, n)).astype(np.float32)
    means0 = x_host[rng.choice(m, k, replace=False)].copy()

    w = np.full(k, 1.0 / k, np.float32)
    covs = np.tile(np.eye(n, dtype=np.float32)[None], (k, 1, 1))
    t0 = time.perf_counter()
    w2, mu2, covs2 = _numpy_gmm_iter(x_host, w, means0.copy(), covs)
    cpu_iter_wall = time.perf_counter() - t0
    cpu_wall = cpu_iter_wall * iters

    a = ds.array(x_host, block_size=(m, n))
    gm = GaussianMixture(n_components=k, max_iter=iters, tol=0.0,
                         init_params="random", random_state=0)
    gm.fit(a)  # warmup/compile
    assert np.isfinite(gm.lower_bound_)

    t = _median_time(lambda: GaussianMixture(
        n_components=k, max_iter=iters, tol=0.0, init_params="random",
        random_state=0).fit(a))
    return {"metric": f"gmm_{m}x{n}_k{k}_{iters}it_wall_s "
                      "(baseline: numpy full-cov EM single-node proxy x iters)",
            "value": round(t, 4), "unit": "s",
            "vs_baseline": round(cpu_wall / t, 2),
            "dispatches_per_predict": _predict_dispatches(gm, a)}


def _numpy_csvm_fit(x, y_pm, part, c, gamma, max_iter, arity=2):
    """Same-algorithm cascade proxy: K+1-augmented boxed dual solved by
    projected gradient ascent (Gershgorin step, ≤500 steps, 1e-6 delta —
    the device solver's exact loop), SV merge up an arity tree, global SV
    feedback.  Mirrors classification/csvm.py with NumPy GEMVs."""
    m = x.shape[0]

    def solve(idx):
        xs = x[idx]
        sq = (xs * xs).sum(1)
        d = np.maximum(sq[:, None] - 2.0 * (xs @ xs.T) + sq[None, :], 0.0)
        k = np.exp(-gamma * d) + 1.0
        q = k * np.outer(y_pm[idx], y_pm[idx])
        eta = 1.0 / max(np.abs(q).sum(1).max(), 1e-12)
        a = np.zeros(len(idx), np.float32)
        for _ in range(500):
            new = np.clip(a + eta * (1.0 - q @ a), 0.0, c).astype(np.float32)
            delta = np.abs(new - a).max()
            a = new
            if delta <= 1e-6:
                break
        return a, a.sum() - 0.5 * a @ (q @ a)

    sv = alpha = None
    for _ in range(max_iter):
        nodes = [np.arange(s, min(s + part, m)) for s in range(0, m, part)]
        if sv is not None and len(sv):
            nodes = [np.unique(np.r_[nd, sv]) for nd in nodes]
        while True:
            res = [solve(nd) for nd in nodes]
            if len(nodes) == 1:
                break
            merged = []
            for i in range(0, len(nodes), arity):
                grp = []
                for j in range(i, min(i + arity, len(nodes))):
                    grp.extend(nodes[j][res[j][0] > 1e-8].tolist())
                merged.append(np.unique(grp) if grp else nodes[i][:1])
            nodes = merged
        a, _ = res[0]
        keep = a > 1e-8
        sv, alpha = nodes[0][keep], a[keep]
    return sv, alpha


def bench_csvm(m, n, tag, max_iter=3, part=1024):
    """CascadeSVM fit wall clock — the first irregular-tier row (round-3
    verdict #8): cascades of masked fixed-capacity dual solves, nothing
    like the dense-linalg tier's single fused program."""
    import dislib_tpu as ds
    from dislib_tpu.classification import CascadeSVM

    rng = np.random.RandomState(0)
    half = m // 2
    x_host = np.vstack([rng.randn(half, n) + 2.0,
                        rng.randn(m - half, n) - 2.0]).astype(np.float32)
    y_host = np.r_[np.ones(half), -np.ones(m - half)].astype(np.float32)
    perm = rng.permutation(m)
    x_host, y_host = x_host[perm], y_host[perm]
    gamma = 1.0 / n

    t0 = time.perf_counter()
    sv, alpha = _numpy_csvm_fit(x_host, y_host, part, 1.0, gamma, max_iter)
    cpu_wall = time.perf_counter() - t0
    # proxy correctness gate: its SV model must classify the blobs
    k_dec = np.exp(-gamma * np.maximum(
        ((x_host * x_host).sum(1)[:, None] - 2.0 * x_host @ x_host[sv].T
         + (x_host[sv] * x_host[sv]).sum(1)[None]), 0.0)) + 1.0
    proxy_acc = float(np.mean(np.sign(k_dec @ (alpha * y_host[sv])) == y_host))
    assert proxy_acc > 0.95, f"proxy cascade degenerate: acc={proxy_acc}"

    a = ds.array(x_host, block_size=(part, n))
    ya = ds.array(y_host.reshape(-1, 1), block_size=(part, 1))

    def fit_once():
        est = CascadeSVM(kernel="rbf", c=1.0, gamma=gamma,
                         max_iter=max_iter, check_convergence=False)
        est.fit(a, ya)
        return est

    # explicit solver A/B (the tsqr tree/cholqr2 precedent): time BOTH
    # dual solvers; `value` stays the active policy's measurement so the
    # row is comparable across rounds, and the fista field is the
    # evidence for flipping the auto policy (round-5: PG's 1/k rate often
    # hits the 500-step cap; FISTA converges in fewer sequential steps —
    # the cascade's latency driver)
    from dislib_tpu.classification.csvm import _use_fista
    walls = {}
    accs = {}
    ests = {}
    old = os.environ.get("DSLIB_CSVM_SOLVER")
    try:
        for sv in ("pg", "fista"):
            os.environ["DSLIB_CSVM_SOLVER"] = sv
            est = fit_once()  # warmup/compile (per-solver trace)
            ests[sv] = est
            accs[sv] = est.score(a, ya)
            assert accs[sv] > 0.95 and accs[sv] > proxy_acc - 0.02, \
                f"device cascade ({sv}) acc {accs[sv]} vs proxy {proxy_acc}"
            walls[sv] = _median_time(lambda: fit_once())
    finally:
        if old is None:
            os.environ.pop("DSLIB_CSVM_SOLVER", None)
        else:
            os.environ["DSLIB_CSVM_SOLVER"] = old
    # the headline value is whatever THIS environment's policy ships —
    # one source of truth (_use_fista), so a future auto-flip or an
    # operator override keeps the row comparable to production
    active = "fista" if _use_fista() else "pg"
    t = walls[active]
    acc = accs[active]
    return {"metric": f"csvm_{tag}_rbf_{max_iter}it_fit_wall_s "
                      "(baseline: numpy same-algorithm cascade proxy)",
            "value": round(t, 4), "unit": "s",
            "vs_baseline": round(cpu_wall / t, 2),
            "device_train_acc": round(acc, 4),
            "proxy_train_acc": round(proxy_acc, 4),
            "dispatches_per_predict": _predict_dispatches(ests[active], a),
            "pg_wall_s": round(walls["pg"], 4),
            "fista_wall_s": round(walls["fista"], 4),
            "fista_train_acc": round(accs["fista"], 4),
            "note": f"value = the active policy's ({active}) measurement; "
                    "pg/fista fields are the explicit solver A/B"}


def bench_gridsearch(m, n, cands, folds, kmeans_iters, tag):
    """GridSearchCV wall clock over KMeans candidates — the first measured
    search-throughput row; on TPU it exercises the pipelined async-trial
    protocol (all fits of a fold in flight before any host read), which
    the cpu rig deliberately serializes (round-3 verdict weak #3)."""
    import dislib_tpu as ds
    from dislib_tpu.cluster import KMeans
    from dislib_tpu.model_selection import GridSearchCV

    rng = np.random.RandomState(0)
    x_host = rng.rand(m, n).astype(np.float32)

    # proxy: same folds (contiguous KFold splits), same fixed-iteration
    # Lloyd's per candidate, NumPy single-node
    t0 = time.perf_counter()
    bounds = np.linspace(0, m, folds + 1).astype(int)
    for k in cands:
        for f in range(folds):
            tr = np.concatenate([x_host[: bounds[f]], x_host[bounds[f + 1]:]])
            c = tr[:k].copy()
            for _ in range(kmeans_iters):
                c = _numpy_kmeans_iter(tr, c)
    cpu_wall = time.perf_counter() - t0

    a = ds.array(x_host, block_size=(max(1, m // 8), n))

    def search_once():
        gs = GridSearchCV(KMeans(random_state=0, max_iter=kmeans_iters,
                                 tol=0.0),
                          {"n_clusters": list(cands)}, cv=folds, refit=False)
        gs.fit(a)
        return gs

    gs = search_once()  # warmup/compile + gate
    scores = gs.cv_results_["mean_test_score"]
    assert np.all(np.isfinite(scores)) and len(scores) == len(cands)
    assert gs.best_index_ == int(np.argmax(scores))
    t = _median_time(lambda: search_once())
    return {"metric": f"gridsearch_kmeans_{tag}_{len(cands)}x{folds}fits_"
                      "wall_s (baseline: numpy same-folds kmeans proxy)",
            "value": round(t, 4), "unit": "s",
            "vs_baseline": round(cpu_wall / t, 2)}


# --- round-5 rows: the estimator tier (VERDICT r4 missing #3) --------------

def _blobs(m, n, k, seed=0, std=0.08):
    """k well-separated gaussian blobs on the unit cube — shared synthetic
    for the estimator-tier rows (labels = blob id)."""
    rng = np.random.RandomState(seed)
    centers = rng.rand(k, n).astype(np.float32)
    lab = rng.randint(0, k, m)
    x = centers[lab] + std * rng.standard_normal((m, n)).astype(np.float32)
    return x.astype(np.float32), lab.astype(np.int64)


def _numpy_dbscan(x, eps, min_samples, chunk=4096):
    """Same-algorithm DBSCAN: chunked ε-graph, connected components of the
    core-core graph, border points joined to their first core neighbor.
    Returns (labels, eps_wall) — the ε-pass wall is the O(m²) part and is
    reported separately so the caller can scale it quadratically and the
    graph/relabel tail sub-quadratically."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components
    m = x.shape[0]
    eps2 = eps * eps
    xsq = (x * x).sum(1)
    # ONE chunked ε-pass: all neighbor pairs are kept (counts via
    # bincount, core-core edges and border targets filtered afterwards) —
    # a second distance pass would double eps_wall and overstate the
    # baseline this proxy exists to understate
    t_eps = time.perf_counter()
    pr, pc = [], []
    for s in range(0, m, chunk):
        d = xsq[s:s + chunk, None] - 2.0 * (x[s:s + chunk] @ x.T) + xsq[None]
        r, c = np.nonzero(d <= eps2)
        pr.append(r + s)
        pc.append(c)
    pr = np.concatenate(pr)
    pc = np.concatenate(pc)
    eps_wall = time.perf_counter() - t_eps
    counts = np.bincount(pr, minlength=m)
    core = counts >= min_samples
    to_core = core[pc]
    rows = pr[to_core & core[pr]]
    cols = pc[to_core & core[pr]]
    # border target: first core neighbor of each non-core point
    border_to = np.full(m, -1, np.int64)
    bsel = to_core & ~core[pr]
    # reversed so the FIRST core neighbor (lowest col per row) wins
    border_to[pr[bsel][::-1]] = pc[bsel][::-1]
    g = sp.csr_matrix((np.ones(len(rows), np.int8), (rows, cols)),
                      shape=(m, m))
    n_comp, comp = connected_components(g, directed=False)
    labels = np.full(m, -1, np.int64)
    labels[core] = comp[core]
    join = (~core) & (border_to >= 0)
    labels[join] = comp[border_to[join]]
    # renumber compactly over the labels that survived (vectorised)
    used, inv = np.unique(labels[labels >= 0], return_inverse=True)
    labels[labels >= 0] = inv
    return labels, eps_wall


def _same_partition_on_core(lab_a, lab_b, core_mask):
    """True iff the two labelings induce the SAME partition of the core
    points (bijective label correspondence — border ties may legally
    differ between schedules)."""
    a, b = lab_a[core_mask], lab_b[core_mask]
    if (a < 0).any() or (b < 0).any():
        return False
    pairs = set(zip(a.tolist(), b.tolist()))
    return len(pairs) == len(set(p[0] for p in pairs)) == \
        len(set(p[1] for p in pairs))


def bench_dbscan(m, n, tag, proxy_m=None):
    """DBSCAN on the tiled-streamed tier (m > dense-max on a 1-row mesh).
    Proxy: same-algorithm NumPy at ``proxy_m`` rows (the matmul proxy_dim
    precedent): its ε-pass wall scales by (m/proxy)², the graph/label tail
    by (m/proxy) — a conservative under-statement of the true baseline.
    Gate: device labels at the proxy shape induce the proxy's exact core
    partition."""
    import dislib_tpu as ds
    from dislib_tpu.cluster import DBSCAN

    proxy_m = proxy_m or m
    eps, min_samples = 0.35, 5
    xp_host, _ = _blobs(proxy_m, n, k=16, seed=3)
    t0 = time.perf_counter()
    lab_proxy, eps_wall = _numpy_dbscan(xp_host, eps, min_samples)
    total_wall = time.perf_counter() - t0
    ratio = m / proxy_m
    # only the ε-pass is O(m²); the graph/label tail scales with the edge
    # count — super-linear for fixed eps but below m², so scaling it by
    # ratio (not ratio²) UNDER-states the proxy and keeps vs_baseline
    # conservative
    cpu_wall = eps_wall * ratio ** 2 + (total_wall - eps_wall) * ratio

    # correctness gate at the proxy shape
    fit_small = DBSCAN(eps=eps, min_samples=min_samples) \
        .fit(ds.array(xp_host, block_size=(4096, n)))
    core_mask = np.zeros(proxy_m, bool)
    core_mask[fit_small.core_sample_indices_] = True
    assert _same_partition_on_core(fit_small.labels_, lab_proxy, core_mask), \
        "dbscan gate: device core partition != numpy proxy"
    noise_dev = int((fit_small.labels_ < 0).sum())
    noise_prx = int((lab_proxy < 0).sum())
    assert abs(noise_dev - noise_prx) <= max(5, 0.01 * proxy_m), \
        f"dbscan gate: noise count {noise_dev} vs proxy {noise_prx}"

    x_host, _ = _blobs(m, n, k=16, seed=4)
    a = ds.array(x_host, block_size=(8192, n))
    DBSCAN(eps=eps, min_samples=min_samples).fit(a)     # warmup/compile
    t = _median_time(lambda: DBSCAN(eps=eps, min_samples=min_samples).fit(a))
    return {"metric": f"dbscan_{tag}_wall_s (baseline: numpy same-algorithm "
                      f"proxy at {proxy_m} rows; eps-pass x(m/proxy)^2, "
                      "graph tail x(m/proxy))",
            "value": round(t, 4), "unit": "s",
            "vs_baseline": round(cpu_wall / t, 2)}


def _numpy_daura(x, cutoff, chunk=2048):
    """Same-algorithm greedy GROMOS clustering: RMSD ε-adjacency
    (RMSD² = ‖xi − xj‖²/n_atoms, rows are 3·n_atoms coords), then repeat
    {pick the active frame with the most active neighbors, extract it and
    its neighbors as one cluster}."""
    m = x.shape[0]
    eps2 = cutoff * cutoff * (x.shape[1] // 3)
    xsq = (x * x).sum(1)
    adj = np.zeros((m, m), bool)
    for s in range(0, m, chunk):
        d = xsq[s:s + chunk, None] - 2.0 * (x[s:s + chunk] @ x.T) + xsq[None]
        adj[s:s + chunk] = d <= eps2
    active = np.ones(m, bool)
    labels = np.full(m, -1, np.int64)
    cid = 0
    while active.any():
        counts = (adj & active[None, :]).sum(1)
        counts[~active] = -1
        medoid = int(np.argmax(counts))
        members = active & adj[medoid]
        members[medoid] = True
        labels[members] = cid
        active &= ~members
        cid += 1
    return labels


def bench_daura(m, n, tag, proxy_m=None):
    """Daura (greedy GROMOS) on the tiled tier.  Proxy: same-algorithm
    NumPy at ``proxy_m`` rows scaled by (m/proxy)² — BOTH phases (ε-pass
    and per-cluster neighbor recounts) are quadratic.  Gate: identical
    partition at the proxy shape (well-separated blobs → the greedy order
    is unambiguous)."""
    import dislib_tpu as ds
    from dislib_tpu.cluster import Daura

    proxy_m = proxy_m or m
    cutoff = 0.3
    xp_host, _ = _blobs(proxy_m, n, k=12, seed=6, std=0.05)
    t0 = time.perf_counter()
    lab_proxy = _numpy_daura(xp_host, cutoff)
    cpu_wall = (time.perf_counter() - t0) * (m / proxy_m) ** 2

    # the gate must exercise the SAME tier the timed run takes (the
    # dbscan precedent): full-mode proxy_m sits above daura's dense-max
    # (16384) so both gate and timed fit stream tiles; smoke stays dense
    fit_small = Daura(cutoff=cutoff).fit(ds.array(xp_host,
                                                  block_size=(4096, n)))
    assert fit_small.labels_.min() >= 0
    assert _same_partition_on_core(fit_small.labels_, lab_proxy,
                                   np.ones(proxy_m, bool)), \
        "daura gate: device partition != numpy greedy proxy"

    x_host, _ = _blobs(m, n, k=12, seed=7, std=0.05)
    a = ds.array(x_host, block_size=(8192, n))
    warm = Daura(cutoff=cutoff).fit(a)                  # warmup/compile
    # sanity on the RESULT being timed, not just the gate shape
    n_clusters = int(warm.labels_.max()) + 1
    assert 1 < n_clusters < m // 10, \
        f"daura full-size result degenerate: {n_clusters} clusters"
    t = _median_time(lambda: Daura(cutoff=cutoff).fit(a))
    return {"metric": f"daura_{tag}_wall_s (baseline: numpy same-algorithm "
                      f"greedy proxy at {proxy_m} rows x (m/proxy)^2)",
            "value": round(t, 4), "unit": "s",
            "vs_baseline": round(cpu_wall / t, 2),
            "n_clusters": n_clusters}


def _numpy_hist_tree_level(bx, node, w, y_onehot, n_nodes, n_bins):
    """One level of the same histogram-tree algorithm (gini), NumPy."""
    m, n = bx.shape
    k = y_onehot.shape[1]
    hist = np.zeros((n_nodes, n, n_bins, k), np.float32)
    np.add.at(hist, (node[:, None], np.arange(n)[None, :], bx),
              (w[:, None] * y_onehot)[:, None, :])
    left = np.cumsum(hist, axis=2)
    total = left[:, :, -1:, :]
    right = total - left

    def gini(s):
        wts = s.sum(-1)
        p = s / np.maximum(wts[..., None], 1e-12)
        return wts * (1.0 - (p * p).sum(-1))

    gain = gini(total) - gini(left) - gini(right)
    gain[:, :, -1] = -np.inf
    wl, wr = left.sum(-1), right.sum(-1)
    gain[~((wl > 0) & (wr > 0))] = -np.inf
    flat = gain.reshape(n_nodes, -1)
    best = flat.argmax(1)
    feat = (best // n_bins).astype(np.int64)
    tbin = best % n_bins
    is_split = flat[np.arange(n_nodes), best] > 0.0
    feat[~is_split] = 0
    tbin[~is_split] = n_bins - 1
    go_right = (bx[np.arange(m), feat[node]] > tbin[node]) & is_split[node]
    return node * 2 + go_right.astype(node.dtype)


def bench_forest(m, n, n_trees, tag, depth=8):
    """RandomForest fit + predict.  Proxy: the same histogram-tree
    algorithm in NumPy, ONE tree's growth × n_trees (per-tree scaling —
    the trees are independent).  Gate: device train accuracy ≥ 0.95 on
    separable blobs AND ≥ proxy-tree accuracy − 5 pts."""
    import dislib_tpu as ds
    from dislib_tpu.trees import RandomForestClassifier

    n_bins = 32
    x_host, lab = _blobs(m, n, k=8, seed=5)
    y_host = (lab % 2).astype(np.float32)[:, None]

    # numpy proxy: one bootstrap tree, same binning + level loop
    rng = np.random.RandomState(0)
    t0 = time.perf_counter()
    qs = np.linspace(0, 100, n_bins + 1)[1:-1]
    edges = np.percentile(x_host, qs, axis=0).T
    bx = (x_host[:, :, None] > edges[None]).sum(2)
    w = rng.poisson(1.0, m).astype(np.float32)
    y1 = np.zeros((m, 2), np.float32)
    y1[np.arange(m), y_host.ravel().astype(np.int64)] = 1.0
    node = np.zeros(m, np.int64)
    for lvl in range(depth):
        node = _numpy_hist_tree_level(bx, node, w, y1, 2 ** lvl, n_bins)
    leaf_stats = np.zeros((2 ** depth, 2), np.float32)
    np.add.at(leaf_stats, node, w[:, None] * y1)
    proxy_tree_wall = time.perf_counter() - t0
    cpu_wall = proxy_tree_wall * n_trees
    pred_proxy = leaf_stats.argmax(1)[node]
    proxy_acc = float((pred_proxy == y_host.ravel()).mean())

    a = ds.array(x_host, block_size=(8192, n))
    yb = ds.array(y_host, block_size=(8192, 1))

    def fit_predict():
        rf = RandomForestClassifier(n_estimators=n_trees, max_depth=depth,
                                    random_state=0)
        rf.fit(a, yb)
        return rf, np.asarray(rf.predict(a).collect()).ravel()

    rf0, pred0 = fit_predict()                          # warmup/compile
    acc = float((pred0 == y_host.ravel()).mean())
    assert acc >= 0.95 and acc >= proxy_acc - 0.05, \
        f"forest gate: device {acc} vs proxy tree {proxy_acc}"
    t = _median_time(lambda: fit_predict())
    return {"metric": f"forest_{tag}_{n_trees}t_fit_predict_wall_s "
                      "(baseline: numpy same-algorithm histogram tree "
                      "x n_trees)",
            "value": round(t, 4), "unit": "s",
            "vs_baseline": round(cpu_wall / t, 2),
            "device_train_acc": round(acc, 4),
            "proxy_train_acc": round(proxy_acc, 4),
            "dispatches_per_predict": _predict_dispatches(rf0, a)}


def bench_knn(m_fit, n, mq, k, tag):
    """kNN query throughput over a streamed (chunked) fit set.  Proxy:
    chunked NumPy brute force, same algorithm.  Gate: device distances ==
    NumPy on a query subset."""
    import dislib_tpu as ds
    from dislib_tpu.neighbors import NearestNeighbors

    rng = np.random.RandomState(1)
    fit_host = rng.rand(m_fit, n).astype(np.float32)
    q_host = rng.rand(mq, n).astype(np.float32)

    def numpy_knn(q):
        out = np.empty((len(q), k), np.float32)
        fsq = (fit_host * fit_host).sum(1)
        for s in range(0, len(q), 1024):
            d = ((q[s:s + 1024] ** 2).sum(1)[:, None]
                 - 2.0 * q[s:s + 1024] @ fit_host.T + fsq[None])
            # partition, not sort: O(m) top-k is what any reasonable
            # brute-force baseline does (review: a full row sort would
            # inflate the proxy wall several-fold)
            top = np.partition(d, k - 1, axis=1)[:, :k]
            out[s:s + 1024] = np.sort(top, axis=1)
        return np.sqrt(np.maximum(out, 0.0))

    t0 = time.perf_counter()
    d_proxy = numpy_knn(q_host)
    cpu_wall = time.perf_counter() - t0

    nn = NearestNeighbors(n_neighbors=k).fit(
        ds.array(fit_host, block_size=(8192, n)))
    qa = ds.array(q_host, block_size=(8192, n))
    d_dev, _ = nn.kneighbors(qa)                        # warmup/compile
    d_dev_h = np.asarray(d_dev.collect())
    gate = np.abs(np.sort(d_dev_h, 1) - np.sort(d_proxy[: mq], 1)).max()
    assert gate < 1e-2, f"knn gate: max distance error {gate}"

    def run():
        d, i = nn.kneighbors(qa)
        _sync(d, i)
    t = _median_time(run)
    return {"metric": f"knn_{tag}_k{k}_queries_per_sec "
                      "(baseline: numpy chunked brute force)",
            "value": round(mq / t, 1), "unit": "queries/s",
            "vs_baseline": round(cpu_wall / t, 2),
            "wall_s": round(t, 4)}


def bench_ann(m, d, mq, k, nlist, nprobe, tag, kmeans_max_iter=2):
    """Round-18 IVF-ANN retrieval tier vs the EXACT kneighbors ring at
    the same scale on the same backend.  Gates: recall@k ≥
    ``DSLIB_ANN_RECALL_MIN`` (0.95, tie-tolerant: a found id counts if
    its true distance is within the k-th oracle distance + eps) and
    speedup ≥ ``DSLIB_ANN_SPEEDUP_MIN`` (3×) over the exact ring scan,
    with the warm search counter-asserted as ONE fused dispatch / 0
    transfers / 0 traces.  QPS, p99, and pad waste are informational."""
    import dislib_tpu as ds
    from dislib_tpu.neighbors import NearestNeighbors
    from dislib_tpu.retrieval import IVFIndex
    from dislib_tpu.utils import profiling as prof

    rng = np.random.RandomState(3)
    # clustered catalog — the regime IVF exists for (uniform data has no
    # list structure to exploit); blob count = nlist so the quantizer has
    # a natural partition to find even at tiny max_iter
    centers = rng.standard_normal((nlist, d)).astype(np.float32) * 4.0
    x = (centers[rng.randint(0, nlist, m)]
         + rng.standard_normal((m, d))).astype(np.float32)
    q = (centers[rng.randint(0, nlist, mq)]
         + rng.standard_normal((mq, d))).astype(np.float32)

    # exact oracle (host, f64, query-chunked so the distance slab never
    # materializes at mq×m) with the tie band
    xf = x.astype(np.float64)
    xsq = (xf ** 2).sum(1)
    kth = np.empty(mq)
    for s in range(0, mq, 256):
        qc = q[s:s + 256].astype(np.float64)
        d2c = (qc ** 2).sum(1)[:, None] - 2.0 * qc @ xf.T + xsq[None]
        kth[s:s + 256] = np.partition(d2c, k - 1, axis=1)[:, k - 1]

    ix = IVFIndex(n_lists=nlist, nprobe=nprobe,
                  kmeans_max_iter=kmeans_max_iter, random_state=0).fit(x)
    qa = ds.array(q)
    _, idx = ix.search(qa, k=k, nprobe=nprobe)          # warmup/compile
    found = np.asarray(idx.collect()).astype(np.int64)
    d_found = ((q[:, None, :].astype(np.float64)
                - xf[found]) ** 2).sum(-1)              # (mq, k) only
    hit = (d_found <= kth[:, None] + 1e-4) & (found >= 0)
    recall = float(hit.mean())
    recall_min = float(os.environ.get("DSLIB_ANN_RECALL_MIN", "0.95"))
    assert recall >= recall_min, (
        f"ann recall@{k} {recall:.4f} < {recall_min} "
        "(DSLIB_ANN_RECALL_MIN)")

    # the one-dispatch contract on the warm hot path
    prof.reset_counters()
    dist, idx = ix.search(qa, k=k, nprobe=nprobe)
    _sync(dist, idx)
    c = prof.counters()
    assert c["dispatch_by"].get("ivf_search") == 1, c["dispatch_by"]
    assert c["transfers"] == 0 and c["traces"] == 0, c

    nn = NearestNeighbors(n_neighbors=k).fit(
        ds.array(x, block_size=(8192, d)))
    de, ie = nn.kneighbors(qa)                          # warmup/compile
    _sync(de, ie)

    def run_exact():
        dd, ii = nn.kneighbors(qa)
        _sync(dd, ii)

    def run_ann():
        dd, ii = ix.search(qa, k=k, nprobe=nprobe)
        _sync(dd, ii)

    t_exact = _median_time(run_exact)
    walls = []
    for _ in range(9):
        t0 = time.perf_counter()
        run_ann()
        walls.append(time.perf_counter() - t0)
    t_ann = float(np.median(walls))
    speedup = t_exact / t_ann
    speedup_min = float(os.environ.get("DSLIB_ANN_SPEEDUP_MIN", "3"))
    assert speedup >= speedup_min, (
        f"ann speedup {speedup:.2f}x < {speedup_min}x vs the exact ring "
        f"(exact {t_exact:.4f}s, ann {t_ann:.4f}s; "
        "DSLIB_ANN_SPEEDUP_MIN)")
    return {"metric": f"ann_{tag}_k{k}_nprobe{nprobe}_queries_per_sec "
                      "(baseline: exact kneighbors ring, same backend)",
            "value": round(mq / t_ann, 1), "unit": "queries/s",
            "vs_baseline": round(speedup, 2),
            "recall_at_k": round(recall, 4),
            "p99_ms": round(1e3 * float(np.percentile(walls, 99)), 2),
            "pad_waste_frac": round(ix.pad_waste["waste_frac"], 4),
            "wall_s": round(t_ann, 4)}


def bench_als_sparse(n_users, n_items, nnz_per_user, tag, n_f=16, iters=3):
    """Sparse ALS (BCOO segment-sum path).  Proxy: same-algorithm NumPy —
    batched per-user/item normal equations from the triplets, ONE
    iteration × iters.  Gate: device training RMSE ≤ 1.3×proxy + 0.05
    (see the inline note on independent-init spread)."""
    import scipy.sparse as sp

    import dislib_tpu as ds  # noqa: F401  (package init = mesh init)
    from dislib_tpu.data.sparse import SparseArray
    from dislib_tpu.recommendation import ALS

    rng = np.random.RandomState(2)
    rows = np.repeat(np.arange(n_users), nnz_per_user)
    cols = rng.randint(0, n_items, rows.shape[0])
    u0 = rng.standard_normal((n_users, n_f)).astype(np.float32)
    v0 = rng.standard_normal((n_items, n_f)).astype(np.float32)
    vals = (u0[rows] * v0[cols]).sum(1) + \
        0.1 * rng.standard_normal(rows.shape[0]).astype(np.float32)
    csr = sp.csr_matrix((vals, (rows, cols)), shape=(n_users, n_items),
                        dtype=np.float32)
    lam = 0.065

    def numpy_als_half(fixed, rows_ix, cols_ix, v):
        """Solve one side's normal equations from the triplets (batched)."""
        nn_ = fixed.shape[1]
        g = np.zeros((int(rows_ix.max()) + 1, nn_, nn_), np.float32)
        b = np.zeros((int(rows_ix.max()) + 1, nn_), np.float32)
        f = fixed[cols_ix]
        np.add.at(g, rows_ix, f[:, :, None] * f[:, None, :])
        np.add.at(b, rows_ix, f * v[:, None])
        cnt = np.bincount(rows_ix, minlength=g.shape[0]).astype(np.float32)
        g += lam * np.maximum(cnt, 1.0)[:, None, None] * \
            np.eye(nn_, dtype=np.float32)[None]
        return np.linalg.solve(g, b[..., None])[..., 0]

    # proxy init is a FRESH random draw (not the generating factors u0/v0
    # — that would hand the proxy a converged start the device never gets)
    rng_p = np.random.RandomState(7)
    v_p = rng_p.standard_normal((n_items, n_f)).astype(np.float32)
    t0 = time.perf_counter()
    u_np = numpy_als_half(v_p, rows, cols, vals)
    _ = numpy_als_half(u_np, cols, rows, vals)
    cpu_wall = (time.perf_counter() - t0) * iters

    s_arr = SparseArray.from_scipy(csr)
    als = ALS(n_f=n_f, lambda_=lam, max_iter=iters, tol=0.0, random_state=0)
    als.fit(s_arr)                                      # warmup/compile
    pred = (als.users_[rows] * als.items_[cols]).sum(1)
    rmse_dev = float(np.sqrt(np.mean((pred - vals) ** 2)))
    # proxy RMSE after the same number of alternations from its random init
    for _ in range(iters):
        u_p = numpy_als_half(v_p, rows, cols, vals)
        v_p = numpy_als_half(u_p, cols, rows, vals)
    rmse_prx = float(np.sqrt(np.mean(
        ((u_p[rows] * v_p[cols]).sum(1) - vals) ** 2)))
    # gate width: device and proxy descend from INDEPENDENT random inits,
    # so after few iterations they sit in different basins — 1.3x + 0.05
    # catches a broken solver (rmse ~ O(1) garbage) without flaking on
    # legitimate init-to-init spread; both values are emitted for audit
    assert rmse_dev <= rmse_prx * 1.3 + 0.05, \
        f"als gate: device rmse {rmse_dev} vs proxy {rmse_prx}"

    t = _median_time(lambda: ALS(n_f=n_f, lambda_=lam, max_iter=iters,
                                 tol=0.0, random_state=0).fit(s_arr))
    return {"metric": f"als_sparse_{tag}_f{n_f}_{iters}it_wall_s "
                      "(baseline: numpy same-algorithm batched normal "
                      "equations x iters)",
            "value": round(t, 4), "unit": "s",
            "vs_baseline": round(cpu_wall / t, 2),
            "device_rmse": round(rmse_dev, 4),
            "proxy_rmse": round(rmse_prx, 4)}


def bench_sparse(m, n, k, density, tag, panels=4, min_speedup=2.0,
                 temp_ratio_max=1.0):
    """Round-14 sparse fast path: the sharded masked-psum SpMM vs the
    densify route (to_dense + dense GEMM — what every sparse matmul paid
    before this round), at recommender density, plus the fold-in serving
    dispatch.

    Gates (all fail the config loudly):
    - SpMM ≈ the densify oracle (the two contraction orders differ, so
      allclose at f32 tolerance), and db/seq overlap schedules BIT-equal;
    - ONE dispatch per SpMM, ZERO host transfers (counters);
    - O(nnz)-scaled peak-live: XLA's own memory analysis of the compiled
      SpMM — temporaries ≤ ``temp_ratio_max`` × one densified-A
      allocation (``DSLIB_SPMM_TEMP_RATIO_MAX`` overrides; the densify
      route's floor IS that allocation);
    - speedup = densify_wall / spmm_wall ≥ ``min_speedup``
      (``DSLIB_SPMM_SPEEDUP_MIN`` overrides) at ≤1% density.
    ``panels`` is recorded in the row.  Round 17: the default moved to 4
    — the col-partitioned slot-range layout collapsed per-entry masking
    work from O(steps·nse) to O(nse + steps·quantum), so the panel count
    is now a pure memory knob; the row carries the masking-work
    accounting (``spmm_masking_work``) as the evidence."""
    import scipy.sparse as sp

    import dislib_tpu as ds
    from dislib_tpu.data.sparse import SparseArray
    from dislib_tpu.ops.spmm import (spmm, spmm_masking_work,
                                     spmm_memory_analysis)
    from dislib_tpu.utils import profiling as _prof

    assert density <= 0.01 + 1e-9, "the headline gate is the ≤1% regime"
    rng = np.random.RandomState(0)
    ds.init()
    mat = sp.random(m, n, density=density, random_state=0,
                    dtype=np.float32).tocsr()
    xs = SparseArray.from_scipy(mat)
    b = ds.array(rng.rand(n, k).astype(np.float32)).force()
    xs.sharded()                                    # ingest outside timing

    # correctness gates: vs the densify route, and across schedules
    got = np.asarray(spmm(xs, b, panels=panels).collect())
    oracle = np.asarray(ds.matmul(xs, b, algorithm="densify").collect())
    np.testing.assert_allclose(got, oracle, rtol=2e-4, atol=2e-4)
    got_seq = np.asarray(spmm(xs, b, overlap="seq", panels=panels)
                         .collect())
    got_db = np.asarray(spmm(xs, b, overlap="db", panels=panels).collect())
    assert (got_db == got_seq).all(), "db/seq schedules not bit-equal"

    # dispatch / transfer gate
    _prof.reset_counters()
    y = spmm(xs, b, panels=panels)
    _sync(y._data)
    d, tr = (_prof.counters()["dispatch_by"].get("spmm_panels", 0),
             _prof.transfer_count())
    assert d == 1, f"spmm cost {d} dispatches, expected 1"
    assert tr == 0, f"spmm cost {tr} host transfers, expected 0"

    # O(nnz) peak-live gate: temporaries vs ONE densified-A allocation
    ma = spmm_memory_analysis(xs, b, panels=panels)
    ratio_max = float(os.environ.get("DSLIB_SPMM_TEMP_RATIO_MAX",
                                     temp_ratio_max))
    if ma["temp_vs_dense"] is not None and ma["temp_vs_dense"] > ratio_max:
        msg = (f"SPMM MEMORY GATE FAILED: temporaries at "
               f"{ma['temp_vs_dense']:.2f}x a densified operand exceed "
               f"the {ratio_max:.2f}x bound — the kernel is densifying")
        print(msg, file=sys.stderr, flush=True)
        raise AssertionError(msg)

    # the A/B: each densify call honestly pays the dense materialisation
    # (that IS the route's cost; it holds no cache)
    def run_spmm():
        _sync(spmm(xs, b, panels=panels)._data)

    def run_densify():
        _sync(ds.matmul(xs, b, algorithm="densify")._data)

    run_spmm()
    run_densify()
    # interleaved rounds + best-of walls (the bench_overlap precedent):
    # block-sequential medians let cpu-shares throttle drift bias the
    # ratio on this 2-vCPU rig — alternating the two arms and taking
    # each arm's best puts both under the same load profile
    t_sp, t_dn = [], []
    for _ in range(7):
        t0 = time.perf_counter()
        run_spmm()
        t_sp.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_densify()
        t_dn.append(time.perf_counter() - t0)
    t_sp, t_dn = min(t_sp), min(t_dn)
    speedup = t_dn / t_sp
    floor = float(os.environ.get("DSLIB_SPMM_SPEEDUP_MIN", min_speedup))

    # fold-in serving dispatch wall (informational): one padded sparse
    # batch of 8 users scored against n-item factors — the serve-side
    # unit of the recommender pipeline
    from dislib_tpu.recommendation import ALS
    from dislib_tpu.serving import SparseFoldInPipeline
    als = ALS(n_f=8, max_iter=2, tol=0.0, random_state=0)
    als.items_ = rng.rand(n, 8).astype(np.float32)
    als.users_ = rng.rand(1, 8).astype(np.float32)
    pipe = SparseFoldInPipeline(als, nse_cap=max(64, int(8 * density * n)))
    batch = pipe.pack(mat[:8])
    pipe.predict_bucket(batch, 8)                   # warm
    t_fold = _median_time(lambda: pipe.predict_bucket(batch, 8))

    # masking-work accounting: what the slot-range layout saves per
    # dispatch vs the legacy re-mask-everything layout at this panel
    # count — the "panels is a pure memory knob now" evidence
    mw = spmm_masking_work(xs, b, panels=panels)

    res = {"metric": f"sparse_{tag}_spmm_speedup_vs_densify (baseline: "
                     "to_dense + dense GEMM per product)",
           "value": round(speedup, 2), "unit": "x",
           "spmm_wall_s": round(t_sp, 4),
           "densify_wall_s": round(t_dn, 4),
           "shape": [m, n, k], "density": density, "nnz": int(mat.nnz),
           "panels": panels, "steps": ma["steps"],
           "masked_layout_work": mw["masked_work"],
           "slots_layout_work": mw["slots_work"],
           "masking_inflation_removed": mw["inflation"],
           "dispatches_per_op": 1, "host_transfers": 0,
           "temp_vs_dense": ma["temp_vs_dense"],
           "temp_ratio_max": ratio_max,
           "spmm_temp_bytes": ma["temp_bytes"],
           "dense_a_bytes": ma["dense_a_bytes"],
           "sparse_in_bytes": ma["sparse_in_bytes"],
           "foldin_serve_batch8_wall_s": round(t_fold, 4),
           "speedup_floor": floor, "fresh": True,
           "note": "gates: allclose vs densify oracle, db==seq bit-equal, "
                   "1 dispatch / 0 transfers, temp <= ratio_max x "
                   "densified-A bytes, speedup >= floor at <=1% density; "
                   "fold-in row is the serve-side dispatch wall "
                   "(informational)"}
    if speedup < floor:
        msg = (f"SPMM SPEEDUP GATE FAILED: {speedup:.2f}x below the "
               f"{floor:.2f}x floor vs the densify route")
        print(msg, file=sys.stderr, flush=True)
        raise AssertionError(msg)
    return res


def bench_trees(m, n_feat, n_nodes, n_bins, tag, s=3, min_speedup=1.2):
    """Round-17 Pallas tier two: the forest level histogram — the fit
    loop's scatter-shaped hot op — as the one-hot-GEMM Pallas kernel
    (``ops/pallas_kernels.node_histogram``) vs the XLA scatter-add it
    replaces, at the routed ``trees/decision_tree._node_histogram``
    surface.

    Gates (all fail the config loudly):
    - BIT-equality: pallas == xla == a NumPy scatter oracle (the forest's
      contributions — Poisson weights × count/target stats — are
      integer-representable, so both summation orders are exact);
    - the routed forest fit is counter-observable (``hist:pallas``) and a
      warm same-shape refit compiles ZERO new programs;
    - speedup = xla_wall / pallas_wall >= the floor.  MXU-class backends
      (real TPUs, where the one-hot GEMM is dense MXU work against a
      serialized scatter loop) get ``min_speedup``; interpret-mode rigs
      (this CPU box) get 0.0 — Pallas interpret mode is a correctness
      rig, not wall-clock evidence (the bf16 parity-class-floor
      precedent).  ``DSLIB_HIST_SPEEDUP_MIN`` overrides either floor."""
    import warnings

    import jax
    import jax.numpy as jnp
    import dislib_tpu as ds
    from dislib_tpu.trees import RandomForestClassifier
    from dislib_tpu.trees.decision_tree import _node_histogram
    from dislib_tpu.utils import profiling as _prof

    rng = np.random.RandomState(0)
    node_h = rng.randint(0, n_nodes, m).astype(np.int32)
    bx_h = rng.randint(0, n_bins, (m, n_feat)).astype(np.int32)
    w_h = rng.poisson(1.0, m).astype(np.float32)
    stats_h = rng.randint(0, 3, (m, s)).astype(np.float32)
    node, bx = jnp.asarray(node_h), jnp.asarray(bx_h)
    w, stats = jnp.asarray(w_h), jnp.asarray(stats_h)

    fns = {sched: jax.jit(
        lambda nd, b, ww, st, _s=sched: _node_histogram(
            nd, b, ww, st, n_nodes, n_bins, hist=_s))
        for sched in ("xla", "pallas")}

    # correctness gate: both routes vs each other AND a host oracle
    outs = {k: np.asarray(f(node, bx, w, stats)) for k, f in fns.items()}
    np.testing.assert_array_equal(outs["xla"], outs["pallas"])
    want = np.zeros((n_nodes, n_feat, n_bins, s), np.float32)
    contrib = w_h[:, None] * stats_h
    for f_i in range(n_feat):
        np.add.at(want, (node_h, f_i, bx_h[:, f_i]), contrib)
    np.testing.assert_array_equal(outs["xla"], want)

    # interleaved best-of walls (the bench_sparse precedent: alternating
    # arms under the same load profile, best per arm)
    t_x, t_p = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(fns["xla"](node, bx, w, stats)[:1])
        t_x.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        np.asarray(fns["pallas"](node, bx, w, stats)[:1])
        t_p.append(time.perf_counter() - t0)
    t_x, t_p = min(t_x), min(t_p)
    speedup = t_x / t_p

    # routed-fit evidence: the hist:<sched> counter at the fit boundary,
    # and zero new programs on a warm same-shape refit
    x_fit = rng.rand(512, 8).astype(np.float32)
    y_fit = (x_fit[:, 0] > 0.5).astype(np.float32)[:, None]
    prev = os.environ.get("DSLIB_OVERLAP")
    os.environ["DSLIB_OVERLAP"] = "pallas"
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")     # pallas warns off-TPU
            RandomForestClassifier(n_estimators=2, random_state=0).fit(
                ds.array(x_fit), ds.array(y_fit))       # warm
            _prof.reset_counters()
            RandomForestClassifier(n_estimators=2, random_state=0).fit(
                ds.array(x_fit), ds.array(y_fit))
        sc = _prof.schedule_counters()
        assert sc.get("hist:pallas", 0) >= 1, \
            f"routed forest fit left no hist:pallas counter: {sc}"
        traces = _prof.trace_count()
        assert traces == 0, \
            f"warm same-shape refit compiled {traces} new programs"
    finally:
        if prev is None:
            os.environ.pop("DSLIB_OVERLAP", None)
        else:
            os.environ["DSLIB_OVERLAP"] = prev

    interpret = jax.default_backend() != "tpu"
    floor = float(os.environ.get("DSLIB_HIST_SPEEDUP_MIN",
                                 0.0 if interpret else min_speedup))
    res = {"metric": f"trees_{tag}_hist_speedup_vs_scatter (baseline: "
                     "the XLA scatter-add histogram, same shapes)",
           "value": round(speedup, 2), "unit": "x",
           "vs_baseline": round(speedup, 2),
           "xla_wall_s": round(t_x, 5), "pallas_wall_s": round(t_p, 5),
           "shape": [m, n_feat], "n_nodes": n_nodes, "n_bins": n_bins,
           "stats_width": s, "interpret_mode": interpret,
           "speedup_floor": floor, "fresh": True,
           "note": "gates: pallas == xla == numpy oracle BIT-equal, "
                   "hist:pallas counter on a routed fit, 0 traces on a "
                   "warm refit, speedup >= floor (floor 0.0 on "
                   "interpret-mode rigs — wall clock there is a "
                   "correctness rig, not MXU evidence; "
                   "DSLIB_HIST_SPEEDUP_MIN overrides)"}
    if speedup < floor:
        msg = (f"HIST SPEEDUP GATE FAILED: one-hot-GEMM histogram at "
               f"{speedup:.2f}x the XLA scatter is below the "
               f"{floor:.2f}x floor")
        print(msg, file=sys.stderr, flush=True)
        raise AssertionError(msg)
    return res


def bench_shuffle(m, n, tag, chain=8):
    """Global all_to_all shuffle throughput.  Proxy: NumPy permutation
    take of the same matrix.  Gate: the row multiset is preserved.
    ``chain`` shuffles per timed region amortize the dispatch RTT."""
    import dislib_tpu as ds
    from dislib_tpu.utils import shuffle

    rng = np.random.RandomState(3)
    x_host = rng.rand(m, n).astype(np.float32)
    perm = rng.permutation(m)
    t0 = time.perf_counter()
    _ = x_host[perm]
    cpu_wall = time.perf_counter() - t0

    a = ds.array(x_host, block_size=(8192, n))
    out = shuffle(a, random_state=0)                    # warmup/compile
    small = ds.array(x_host[:2048], block_size=(512, n))
    sm = np.asarray(shuffle(small, random_state=1).collect())
    assert sorted(map(tuple, sm.tolist())) == \
        sorted(map(tuple, x_host[:2048].tolist())), \
        "shuffle gate: row multiset not preserved"

    rtt = _measure_rtt()

    def run():
        y = a
        for i in range(chain):
            y = shuffle(y, random_state=i)
        _sync(y._data)
    run()                                               # chain warmup
    t = _median_time(run)
    gb = m * n * 4 / 1e9
    raw_gbps = gb * chain / t
    # the correction is only meaningful when the RTTs are a MINORITY of
    # the wall; when t ≲ chain·rtt the subtraction degenerates (divide by
    # ~0 → absurd GB/s), so emit null rather than poison the artifact
    corr = t - chain * rtt
    corr_gbps = round(gb * chain / corr, 2) if corr > 0.2 * t else None
    return {"metric": f"shuffle_{tag}_gb_per_sec (baseline: numpy "
                      "permutation take)",
            "value": round(raw_gbps, 2), "unit": "GB/s",
            "vs_baseline": round((cpu_wall * chain) / t, 2),
            "rtt_ms": round(rtt * 1e3, 2),
            "rtt_corrected_value": corr_gbps,
            "shuffles_per_region": chain,
            "note": "each chained shuffle pays one host-planning RTT; "
                    "rtt_corrected_value subtracts them (null when RTT "
                    "dominates the region)"}


def _configs():
    """Ordered (name, thunk) list.  BENCH_SMOKE=1: every config at ~1/100
    scale — validates the whole harness (gates, proxies, JSON, watchdog
    orchestration) on CPU without the chip.  Full mode: BASELINE.json
    configs 1-5, then the two north stars (KMeans ★ LAST so a driver that
    parses the final stdout line records the headline)."""
    if os.environ.get("BENCH_SMOKE"):
        return [
            ("dispatch_rtt", bench_rtt),
            ("kmeans_smoke",
             lambda: bench_kmeans(1000, 20, 4, 5, "smoke", amortize=25)),
            ("matmul_smoke", lambda: bench_matmul(512, "smoke", chain=3,
                                                  peak_floor=0.15)),
            ("matmul_smoke_bf16",
             lambda: bench_matmul(512, "smoke", bf16=True, chain=3,
                                  peak_floor=0.15)),
            ("matmul_smoke_f32x3",
             lambda: bench_matmul(512, "smoke", chain=3, precision="high")),
            # round-10 mixed-precision tier: bf16-policy >= 1.5x f32
            # sustained + error-bound + 1-dispatch + roofline, all gated
            ("matmul_smoke_mp",
             lambda: bench_matmul_mp(512, "smoke", chain=3)),
            ("polar_smoke", lambda: bench_polar(2048, 96, "smoke",
                                                peak_floor=0.1)),
            ("summa_smoke", lambda: bench_summa(512, "smoke",
                                                peak_floor=0.1)),
            # round-11 rechunk tier: collective reshard, memory-bounded
            ("rechunk_smoke", lambda: bench_rechunk(2048, 256, "smoke",
                                                    min_gbps=0.02)),
            # round-19 DCN tier: hierarchical rechunk under the mock
            # host map — coalesced messages O(hosts) + bytes == deviceput
            # floor + bit-equal to the flat exchange, all counter-gated
            ("dcn_smoke", lambda: bench_dcn(2050, 96, "smoke")),
            # round-13 overlap tier: comm-hidden fraction per panel
            # schedule, db==seq bit-equal + 1-dispatch + memory-bounded
            # gated in-config.  Floors are rig-calibrated (the bf16
            # roofline-normalization precedent): rechunk/ring measure
            # +0.2-0.4 / +0.1-0.4 hidden on these host cores (thunk
            # concurrency), while summa's double buffer is CACHE-BOUND
            # here (two live panel pairs vs one: measured -0.3±0.1, no
            # ICI to win back) — its smoke floor is the documented
            # bounded-regression -1.0 and the full/chip config arms 0.0
            ("overlap_smoke_summa",
             lambda: bench_overlap("summa", 512, 512, "smoke",
                                   hidden_floor=-1.0)),
            ("overlap_smoke_rechunk",
             lambda: bench_overlap("rechunk", 2048, 256, "smoke",
                                   hidden_floor=0.02)),
            # ring floor −0.05, not 0: measured 0.38–0.67 hidden here,
            # but one run in ~5 TIES (−0.01) when the container is
            # throttled mid-region — the floor tolerates the tie, the
            # chip config arms 0.0
            ("overlap_smoke_ring",
             lambda: bench_overlap("ring", 8192, 128, "smoke",
                                   hidden_floor=-0.05, repeats=15)),
            ("kmeans_smoke_fastdist",
             lambda: bench_kmeans(1000, 20, 4, 5, "smoke_fastdist")),
            # round-12 fit-loop driver: heal == unfaulted, +1 dispatch only
            ("resilience_smoke",
             lambda: bench_resilience(1000, 20, 4, 8, "smoke")),
            # round-20 multi-host survival: the real SIGKILL chaos drill,
            # all counters + recovery wall + zero-hang gated
            ("mh_resilience_smoke",
             lambda: bench_mh_resilience("smoke")),
            ("fused_chain_smoke",
             lambda: bench_fused_chain(256, 32, "smoke")),
            ("tsqr_smoke", lambda: bench_tsqr(2048, 64)),
            ("randomsvd_smoke", lambda: bench_randomsvd(1024, 128, nsv=16)),
            ("svd_smoke", lambda: bench_svd(256, 130)),
            ("csvm_smoke", lambda: bench_csvm(600, 8, "smoke", max_iter=2,
                                              part=128)),
            ("gridsearch_smoke",
             lambda: bench_gridsearch(2000, 8, (2, 3), 2, 4, "smoke")),
            ("gmm_smoke", lambda: bench_gmm(2000, 8, 3, 2)),
            ("dbscan_smoke", lambda: bench_dbscan(3000, 6, "smoke",
                                                  proxy_m=1500)),
            ("daura_smoke", lambda: bench_daura(2000, 6, "smoke",
                                                proxy_m=1000)),
            ("forest_smoke", lambda: bench_forest(2000, 8, 4, "smoke",
                                                  depth=5)),
            ("knn_smoke", lambda: bench_knn(4000, 8, 512, 5, "smoke")),
            ("serving_smoke",
             lambda: bench_serving(2000, 8, 4, 200, "smoke",
                                   buckets=(1, 8, 64), deadline_ms=2)),
            # round-15 bundle + fleet tier: cold-start ratio gated >= 10x
            # (DSLIB_BUNDLE_COLDSTART_MIN), zero traces on the bundle
            # path and under 3-tenant mixed-shape load
            ("serving_fleet_smoke",
             lambda: bench_serving_fleet(2000, 8, 4, 300, "smoke",
                                         buckets=(1, 8, 64),
                                         deadline_ms=2)),
            # round-17 continuous-learning tier: train -> bundle ->
            # canary -> promote cadence, all promoted, zero-retrace
            # post-promotion bursts gated
            ("trainer_smoke",
             lambda: bench_trainer(512, 8, 4, 4, "smoke",
                                   batches_per_generation=3,
                                   buckets=(1, 8, 64), deadline_ms=2)),
            ("als_smoke", lambda: bench_als_sparse(1000, 400, 10, "smoke",
                                                   n_f=8, iters=2)),
            # round-14 sparse fast path (round-17: default panels=4 under
            # the slot-range layout — a pure memory knob now, masking-work
            # accounting in the row): SpMM >= 2x the densify A/B at 1%
            # density, 1 dispatch, O(nnz) peak-live, db==seq bit-equal
            ("sparse_smoke",
             lambda: bench_sparse(4096, 2048, 64, 0.01, "smoke")),
            # round-17 Pallas tier two: the forest level histogram as a
            # one-hot GEMM vs the XLA scatter — bit-equal gated; the
            # speedup floor arms on MXU-class backends only
            ("trees_smoke",
             lambda: bench_trees(2048, 8, 16, 32, "smoke")),
            # round-18 IVF-ANN retrieval tier: recall@10 >= 0.95 AND
            # >= 3x the exact kneighbors ring, 1 dispatch / 0 transfers
            ("ann_smoke",
             lambda: bench_ann(262_144, 32, 512, 10, 2048, 8, "smoke",
                               kmeans_max_iter=2)),
            ("shuffle_smoke", lambda: bench_shuffle(4096, 16, "smoke",
                                                    chain=3)),
            ("kmeans_smoke_star",
             lambda: bench_kmeans(4000, 20, 4, 5, "smoke_star")),
        ]
    return [
        # full-mode config names MATCH each metric's first token, so a
        # failure/timeout record (emitted under the config name) reads as
        # the same row a success would
        ("dispatch_rtt_trivial_op_ms", bench_rtt),
        # amortize/chain sizes pick sustained regions well past one
        # dispatch round trip
        ("kmeans_10000x100_k8_iter_per_sec",
         lambda: bench_kmeans(10_000, 100, 8, 50, "10000x100_k8",
                              amortize=2000)),
        ("matmul_4096_f32_gflops_per_chip",
         lambda: bench_matmul(4096, "4096", chain=36, peak_floor=0.3)),
        # round-10 mixed-precision / paper-scale linalg tier
        ("matmul_mp_4096_bf16_vs_f32_speedup",
         lambda: bench_matmul_mp(4096, "4096", chain=12,
                                 peak_floors=(0.3, 0.3))),
        ("polar_16384x1024_gflops_sustained",
         lambda: bench_polar(16384, 1024, "16384x1024", peak_floor=0.15)),
        ("summa_8192_gflops_per_chip",
         lambda: bench_summa(8192, "8192", peak_floor=0.1)),
        # round-11 rechunk tier: collective reshard of a paper-scale
        # operand between 2-D layouts, peak-live proxy <= 1.5x gated
        ("rechunk_16384x2048_gb_per_sec",
         lambda: bench_rechunk(16384, 2048, "16384x2048", min_gbps=0.2)),
        # round-19 DCN tier at paper scale: the hierarchical schedule's
        # accounting gates (messages O(hosts), bytes == deviceput floor)
        # under the mock host map; m chosen so the two layouts pad
        # DIFFERENTLY (aligned pads would mean zero cross-host rows and
        # a vacuous run — the tier rejects that loudly)
        ("dcn_rechunk_16500x2048_gb_per_sec",
         lambda: bench_dcn(16500, 2048, "16500x2048")),
        # round-13 overlap tier at paper scale: on real ICI the
        # double-buffered schedule must hide a strictly positive
        # fraction of the panel collective (floor 0.0, armed) —
        # DSLIB_OVERLAP_HIDDEN_MIN is the noisy-rig escape
        ("overlap_summa_4096_comm_hidden_frac",
         lambda: bench_overlap("summa", 4096, 4096, "4096",
                               hidden_floor=0.0)),
        ("overlap_rechunk_16384x2048_comm_hidden_frac",
         lambda: bench_overlap("rechunk", 16384, 2048, "16384x2048",
                               hidden_floor=0.0)),
        ("overlap_ring_65536x128_comm_hidden_frac",
         lambda: bench_overlap("ring", 65536, 128, "65536x128",
                               hidden_floor=0.0)),
        # round-7 fusion PR: one forced op chain vs per-op eager dispatch —
        # at 512² the per-dispatch RTT dominates both modes' compute, so
        # the ratio reads the dispatch savings directly
        ("fused_chain_512_32ops_speedup_vs_eager",
         lambda: bench_fused_chain(512, 32, "512")),
        ("tsqr_65536x256_wall_s", lambda: bench_tsqr(65536, 256)),
        ("randomsvd_32768x1024_nsv64_wall_s",
         lambda: bench_randomsvd(32768, 1024)),
        ("svd_4096x512_wall_s", lambda: bench_svd(4096, 512)),
        ("gmm_1000000x50_k16_5it_wall_s",
         lambda: bench_gmm(1_000_000, 50, 16, 5)),
        ("csvm_20000x20_rbf_3it_fit_wall_s",
         lambda: bench_csvm(20_000, 20, "20000x20")),
        ("gridsearch_kmeans_200000x20_3x3fits_wall_s",
         lambda: bench_gridsearch(200_000, 20, (4, 8, 12), 3, 10,
                                  "200000x20")),
        # round-5: the estimator tier (r4 VERDICT missing #3) — DBSCAN on
        # the tiled-streamed tier, forest fit+predict, kNN streamed query
        # throughput, sparse ALS, and the all_to_all shuffle
        # round-12 fit-loop driver: rollback heal at paper-ish scale —
        # gates equality with the unfaulted fit and the +1-dispatch cost
        ("resilience_100000x50_k8_heal_wall_s",
         lambda: bench_resilience(100_000, 50, 8, 20,
                                  "100000x50_k8")),
        # round-20 multi-host survival: the process-killing chaos drill
        # (always CPU-coordinated — the jax.distributed CPU service is
        # platform-independent; see tools/mh_dryrun.py)
        ("mh_resilience_episode_wall_s",
         lambda: bench_mh_resilience("full")),
        ("dbscan_200000x10_wall_s",
         lambda: bench_dbscan(200_000, 10, "200000x10", proxy_m=20_000)),
        ("daura_50000x15_wall_s",
         lambda: bench_daura(50_000, 15, "50000x15", proxy_m=20_000)),
        ("forest_100000x20_16t_fit_predict_wall_s",
         lambda: bench_forest(100_000, 20, 16, "100000x20")),
        ("knn_1000000x10_q10000_k10_queries_per_sec",
         lambda: bench_knn(1_000_000, 10, 10_000, 10, "1000000x10_q10000")),
        # round-18 IVF-ANN retrieval tier at the million-item scale the
        # subsystem exists for: recall@10 >= 0.95 AND >= 3x the exact
        # ring, ONE dispatch / 0 transfers counter-asserted in-config
        ("ann_1000000x64_q4096_k10_queries_per_sec",
         lambda: bench_ann(1_000_000, 64, 4096, 10, 1024, 32,
                           "1000000x64_q4096", kmeans_max_iter=5)),
        ("als_sparse_100000x10000_nnz100_f16_3it_wall_s",
         lambda: bench_als_sparse(100_000, 10_000, 100,
                                  "100000x10000_nnz100")),
        # round-14 sparse fast path at paper scale (round-17: default
        # panels=4 under the slot-range layout): the sharded SpMM vs
        # the densify route on this rig, same gates as the smoke tier
        ("sparse_16384x8192_spmm_speedup_vs_densify",
         lambda: bench_sparse(16_384, 8_192, 64, 0.01, "16384x8192")),
        # round-17 Pallas tier two at paper-ish shape: one-hot-GEMM
        # histogram vs the XLA scatter, bit-equal + hist:<sched> routing
        # gated; speedup floor arms on MXU-class backends
        ("trees_16384x8_hist_speedup_vs_scatter",
         lambda: bench_trees(16_384, 8, 32, 32, "16384x8")),
        # round-9 serving layer: warm micro-batched p50 vs per-call cold
        # predict, 1-dispatch-per-batch asserted in-config
        ("serving_1000000x100_k10_warm_p50_ms",
         lambda: bench_serving(1_000_000, 100, 10, 2000, "1000000x100_k10",
                               buckets=(1, 8, 64, 512), deadline_ms=5)),
        # round-15 bundle + fleet tier at paper scale: on chip the cold
        # side is tens of seconds of ladder compiles, the bundle side is
        # a deserialize — the >= 10x gate has enormous headroom there
        ("serving_fleet_1000000x100_k10_coldstart_ratio",
         lambda: bench_serving_fleet(1_000_000, 100, 10, 2000,
                                     "1000000x100_k10",
                                     buckets=(1, 8, 64, 512),
                                     deadline_ms=5)),
        # round-17 continuous-learning loop at paper-ish scale: 8k-row
        # batches through train -> bundle -> canary -> promote, same
        # all-promoted / zero-retrace-burst / ledger-replay gates
        ("trainer_8192x100_k10_generations_per_min",
         lambda: bench_trainer(8192, 100, 10, 5, "8192x100_k10",
                               batches_per_generation=6,
                               buckets=(1, 8, 64, 512), deadline_ms=5)),
        ("shuffle_2097152x64_gb_per_sec",
         lambda: bench_shuffle(2_097_152, 64, "2097152x64")),
        ("matmul_16384_f32_gflops_per_chip",
         lambda: bench_matmul(16384, "16384", proxy_dim=8192, chain=6,
                              peak_floor=0.3)),
        # informational variants — headline ★ stays the full-precision path
        ("matmul_16384_bf16_gflops_per_chip",
         lambda: bench_matmul(16384, "16384", proxy_dim=8192, bf16=True,
                              chain=15, peak_floor=0.3)),
        # 3-pass bf16x3 "f32-ish": ceiling ≈ peak/3 (~65 TF/s) vs
        # 'highest''s peak/6 — data for a future precision-policy decision
        ("matmul_16384_f32x3_gflops_per_chip",
         lambda: bench_matmul(16384, "16384", proxy_dim=8192, chain=10,
                              precision="high")),
        # sustained rate: 500 iters/dispatch amortizes the per-call RTT the
        # 10-iter headline pays once per 10 iterations
        ("kmeans_1Mx100_k10_sustained_iter_per_sec",
         lambda: bench_kmeans(1_000_000, 100, 10, 500,
                              "1Mx100_k10_sustained")),
        ("kmeans_1Mx100_k10_fastdist_iter_per_sec",
         lambda: bench_kmeans(1_000_000, 100, 10, 10, "1Mx100_k10_fastdist",
                              amortize=500)),
        ("kmeans_1Mx100_k10_iter_per_sec",
         lambda: bench_kmeans(1_000_000, 100, 10, 10, "1Mx100_k10",
                              amortize=500)),
    ]


def _run_one(name):
    """Child entry: bring up the backend and run exactly one config."""
    # test hook: comma-separated config names that should hang (exercises
    # the parent's skip-and-continue and two-timeouts-abort paths)
    if name in os.environ.get("DSLIB_BENCH_FAKE_HANG", "").split(","):
        time.sleep(10_000)
    if name.startswith(("summa", "rechunk", "overlap", "sparse", "ann",
                        "dcn")) \
            and os.environ.get("BENCH_SMOKE") \
            and (_smoke_wants_cpu()
                 or "cpu" in os.environ.get("JAX_PLATFORMS", "")):
        # the SUMMA/rechunk/sparse tiers need a sharded mesh; smoke mode
        # fakes one with virtual host devices — must land in XLA_FLAGS
        # BEFORE the backend initialises (the conftest precedent).  Chip
        # runs use the real device grid and never take this branch.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = \
                (flags + " --xla_force_host_platform_device_count=8").strip()
    try:
        if _smoke_wants_cpu():
            # smoke mode validates the harness WITHOUT the chip; the
            # platform is forced in-process before backend init
            import jax
            jax.config.update("jax_platforms", "cpu")
        import dislib_tpu as ds
        # persistent compilation cache for all config children: repeat
        # runs (and the f32/bf16 siblings of a config) skip their compiles
        ds.runtime.compile_cache.enable()
        ds.init()
    except Exception as e:  # noqa: BLE001
        _emit({"metric": "backend_init", "value": None, "unit": None,
               "vs_baseline": None, "error": f"{type(e).__name__}: {e}"})
        sys.exit(2)
    fn = dict(_configs())[name]
    if not _guard(name, fn):
        sys.exit(1)


def main():
    # the matmul setup cache (NumPy-proxy GFLOPS + gate stripe) exists to
    # share work between the f32/bf16 sibling CHILDREN of one run; a proxy
    # measured under a previous invocation's machine load must not leak
    # into this run's vs_baseline ratios (round-3 advisor) — the parent
    # clears it before spawning any child
    import glob
    side = _side_file_dir()
    for f in glob.glob(os.path.join(side, "bench_matmul_setup_*.npz")) \
            + glob.glob(os.path.join(side, "bench_peak_*.json")):
        try:
            os.remove(f)
        except OSError:
            pass
    # fast probe: a dead backend is detected in _PROBE_TIMEOUT_S, not per-
    # config watchdog time.  The parent process never initialises a jax
    # backend, so it can always report and exit cleanly.
    if _smoke_wants_cpu():
        probe_src = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
                     "jax.devices()")
    else:
        probe_src = "import jax; jax.devices()"
    try:
        subprocess.run([sys.executable, "-c", probe_src],
                       timeout=_PROBE_TIMEOUT_S, check=True,
                       stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                       text=True)
    except subprocess.TimeoutExpired:
        _emit({"metric": "backend_init", "value": None, "unit": None,
               "vs_baseline": None,
               "error": f"device probe hung past {_PROBE_TIMEOUT_S}s"})
        sys.exit(2)
    except subprocess.CalledProcessError as e:
        _emit({"metric": "backend_init", "value": None, "unit": None,
               "vs_baseline": None,
               "error": f"device probe failed (rc={e.returncode})",
               "stderr_tail": (e.stderr or "")[-400:]})
        sys.exit(2)

    consecutive_timeouts = 0
    failed = False
    for name, _ in _configs():
        try:
            res = subprocess.run([sys.executable, __file__, "--one", name],
                                 timeout=_CONFIG_TIMEOUT_S,
                                 capture_output=True, text=True)
        except subprocess.TimeoutExpired as e:
            # forward whatever the child printed before wedging
            if e.stdout:
                print(e.stdout.decode() if isinstance(e.stdout, bytes)
                      else e.stdout, end="", flush=True)
            _emit({"metric": name, "value": None, "unit": None,
                   "vs_baseline": None,
                   "error": f"watchdog: exceeded {_CONFIG_TIMEOUT_S}s "
                            "(skipped, continuing)"})
            failed = True
            consecutive_timeouts += 1
            if consecutive_timeouts >= 2:
                _emit({"metric": "abort", "value": None, "unit": None,
                       "vs_baseline": None,
                       "error": "two consecutive config timeouts — backend "
                                "wedged, aborting"})
                sys.exit(2)
            continue
        consecutive_timeouts = 0
        failed = failed or res.returncode != 0
        print(res.stdout, end="", flush=True)
        if '"metric": "backend_init"' in res.stdout:
            # the child's backend bring-up failed fast: every later config
            # would fail identically — record once and abort with evidence
            _emit({"metric": "abort", "value": None, "unit": None,
                   "vs_baseline": None,
                   "error": "child backend_init failed — aborting"})
            sys.exit(2)
        if res.returncode != 0 and not res.stdout.strip():
            _emit({"metric": name, "value": None, "unit": None,
                   "vs_baseline": None,
                   "error": f"config subprocess rc={res.returncode}",
                   "stderr_tail": res.stderr[-400:]})
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--one":
        _run_one(sys.argv[2])
    else:
        main()
