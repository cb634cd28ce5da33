"""Round-18 tentpole: the IVF-ANN retrieval tier.

- the recall oracle: ``nprobe = n_lists`` through the SAME fused program
  is the exact kneighbors result, checked against a numpy brute-force
  oracle over a (dtype incl. x64-f64 × overlap schedule) grid;
- the pad discipline: sentinel slots are provably non-load-bearing (the
  poisoned-slot regression fills them with 1e30 garbage per schedule and
  demands bit-equal results), empty lists and unfillable slots carry the
  documented (−1, +inf) contract, db/seq schedules are bit-equal;
- the one-dispatch contract: a search is ONE profiled dispatch with zero
  warm retraces, schedule routing observable via the counters;
- serving: ``RetrievalPipeline`` through the ``PredictServer`` bucket
  ladder and ``ModelRouter`` tenancy unchanged; ``export_bundle`` /
  ``load_bundle`` answer ``[ids | scores]`` in a FRESH subprocess with
  zero traces;
- the round-18 satellites: the on-device ``pack_sparse_rows`` encode,
  the sparse fold-in bundle capture, and the latency-budget admission
  control (``DeadlineShed``) riding the server's learned cost model.
"""

import os
import subprocess
import sys
from collections import deque

import numpy as np
import pytest

import jax

import dislib_tpu as ds
from dislib_tpu.retrieval import IVFIndex, RetrievalPipeline
from dislib_tpu.utils import profiling as prof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, D, NLIST, K, MQ = 256, 16, 8, 4, 8


@pytest.fixture
def rng():
    return np.random.RandomState(7)


def _mesh_now():
    from dislib_tpu.parallel import mesh as _mesh
    return _mesh.get_mesh()


def _crafted(rng, n=N, d=D, nlist=NLIST, dtype=np.float32, empty=(),
             **kw):
    """Build an index through the layout seam ``_build`` — crafted
    labels/centroids, no KMeans run (fast, and the only way to force
    empty lists or an x64 catalog deterministically)."""
    x = rng.randn(n, d).astype(dtype)
    live = [l for l in range(nlist) if l not in set(empty)]
    labels = np.asarray(live)[rng.randint(0, len(live), n)]
    cents = np.zeros((nlist, d), dtype)
    for l in live:
        m = labels == l
        if m.any():
            cents[l] = x[m].mean(axis=0)
    ix = IVFIndex(n_lists=nlist, **kw)._build(x, labels, cents)
    return ix, x


def _oracle(q, x, k):
    d2 = ((q[:, None, :].astype(np.float64)
           - x[None, :, :].astype(np.float64)) ** 2).sum(-1)
    idx = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return np.sqrt(np.take_along_axis(d2, idx, axis=1)), idx


def _recall(found, true):
    return np.mean([len(set(found[i]) & set(true[i])) / true.shape[1]
                    for i in range(true.shape[0])])


# ---------------------------------------------------------------------------
# the recall oracle: exact at full probe, over the dtype × schedule grid
# ---------------------------------------------------------------------------

class TestRecallOracle:
    @pytest.mark.parametrize("sched", ["db", "seq"])
    @pytest.mark.parametrize("xdtype", ["float32", "float64"])
    def test_full_probe_matches_brute_force(self, rng, sched, xdtype):
        """nprobe = n_lists scans every entry exactly once across the
        ring steps — the exact kneighbors result through the SAME fused
        program, for f32 and (under x64) f64 catalogs."""
        x64 = xdtype == "float64"
        ctx = jax.enable_x64(True) if x64 else _null_ctx()
        with ctx:
            ix, x = _crafted(rng, dtype=np.dtype(xdtype))
            q = x[:MQ]
            dist, idx = ix.search(ds.array(q, dtype=np.dtype(xdtype)),
                                  k=K, nprobe=NLIST, overlap=sched)
            dh, ih = dist.collect(), idx.collect()
        od, oi = _oracle(q, x, K)
        assert _recall(ih, oi) == 1.0
        # the q²−2qf+f² form loses ~sqrt(eps·‖x‖²) near zero (the ring
        # kernel's own formulation) — tolerances account for it
        np.testing.assert_allclose(dh, od, atol=1e-4 if x64 else 2e-2)
        assert dh.dtype == np.dtype(xdtype)

    def test_nprobe_one_on_separated_blobs(self, rng):
        """Well-separated blobs with exact blob centroids: a catalog
        query's own list IS the nearest centroid, so nprobe=1 already
        returns the query itself at rank 0."""
        centers = rng.randn(NLIST, D).astype(np.float32) * 50
        labels = rng.randint(0, NLIST, N)
        x = (centers[labels] + rng.randn(N, D)).astype(np.float32)
        ix = IVFIndex(n_lists=NLIST)._build(x, labels, centers)
        dist, idx = ix.search(ds.array(x[:MQ]), k=1, nprobe=1)
        np.testing.assert_array_equal(idx.collect().ravel(),
                                      np.arange(MQ))

    def test_partial_probe_recall_dials_up(self, rng):
        """More probes → recall can only improve, reaching 1 at nlist."""
        ix, x = _crafted(rng)
        q = x[:MQ]
        _, oi = _oracle(q, x, K)
        last = 0.0
        for nprobe in (1, 4, NLIST):
            _, idx = ix.search(ds.array(q), k=K, nprobe=nprobe)
            r = _recall(idx.collect(), oi)
            assert r >= last - 1e-9
            last = r
        assert last == 1.0


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


# ---------------------------------------------------------------------------
# the pad discipline: schedules bit-equal, pads non-load-bearing, edges
# ---------------------------------------------------------------------------

class TestPadDiscipline:
    def test_db_seq_bit_equal(self, rng):
        ix, x = _crafted(rng)
        q = ds.array(x[:MQ])
        outs = {}
        for sched in ("db", "seq"):
            dist, idx = ix.search(q, k=K, nprobe=3, overlap=sched)
            outs[sched] = (dist.collect(), idx.collect())
        np.testing.assert_array_equal(outs["db"][0], outs["seq"][0])
        np.testing.assert_array_equal(outs["db"][1], outs["seq"][1])

    @pytest.mark.parametrize("sched", ["db", "seq"])
    def test_poisoned_pad_slots_change_nothing(self, rng, sched):
        """Fill every sentinel slot (id < 0) with 1e30 garbage in the
        vector, norm, AND id buffers — search must be bit-equal: the
        slot<count ∧ id≥0 mask is the only thing keeping pads out."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from dislib_tpu.parallel import mesh as _mesh
        ix, x = _crafted(rng)
        q = ds.array(x[:MQ])
        clean = [a.collect() for a in ix.search(q, k=K, nprobe=NLIST,
                                                overlap=sched)]
        ids_h = np.asarray(ix._ids)
        pad = ids_h < 0
        assert pad.any()        # the quantum guarantees sentinel slots
        vecs_h = np.asarray(ix._vecs).copy()
        vsq_h = np.asarray(ix._vsq).copy()
        vecs_h[pad] = 1e30
        vsq_h[pad] = 1e30
        ids_p = ids_h.copy()
        ids_p[pad] = -999
        mesh = _mesh.get_mesh()
        ix._vecs = jax.device_put(vecs_h, _mesh.data_sharding(mesh))
        ix._ids = jax.device_put(ids_p, NamedSharding(mesh, P(_mesh.ROWS)))
        ix._vsq = jax.device_put(vsq_h, NamedSharding(mesh, P(_mesh.ROWS)))
        poisoned = [a.collect() for a in ix.search(q, k=K, nprobe=NLIST,
                                                   overlap=sched)]
        np.testing.assert_array_equal(clean[0], poisoned[0])
        np.testing.assert_array_equal(clean[1], poisoned[1])

    def test_empty_lists_and_unfillable_slots(self, rng):
        """Half the lists empty: full-probe search still exact; a tiny
        catalog with k > n_items carries the documented sentinel contract
        (id −1, distance +inf) in the unfillable slots."""
        ix, x = _crafted(rng, empty=(1, 3, 5, 7))
        q = x[:MQ]
        dist, idx = ix.search(ds.array(q), k=K, nprobe=NLIST)
        _, oi = _oracle(q, x, K)
        assert _recall(idx.collect(), oi) == 1.0

        tiny = rng.randn(3, D).astype(np.float32)
        ixt = IVFIndex(n_lists=2)._build(tiny, np.zeros(3, np.int64),
                                         np.zeros((2, D), np.float32))
        dist, idx = ixt.search(ds.array(tiny[:2]), k=8, nprobe=2)
        dh, ih = dist.collect(), idx.collect()
        assert (ih[:, 3:] == -1).all()
        assert np.isinf(dh[:, 3:]).all()
        assert (ih[:, :3] >= 0).all() and np.isfinite(dh[:, :3]).all()

    def test_pad_waste_report_and_quantum_knob(self, rng, monkeypatch):
        ix, _ = _crafted(rng)
        w = ix.pad_waste
        assert w["entries"] == N and w["quantum"] == 8
        assert w["buffer_rows"] >= N and 0.0 <= w["waste_frac"] < 1.0
        assert w["entries"] + w["list_pad_entries"] \
            + w["balance_pad_rows"] == w["buffer_rows"]
        assert sum(w["per_shard_entries"]) == N
        monkeypatch.setenv("DSLIB_IVF_LIST_QUANTUM", "16")
        ix16, _ = _crafted(rng)
        assert ix16.pad_waste["quantum"] == 16
        assert ix16.pad_waste["cap"] % 16 == 0
        # explicit arg beats the env
        ix4, _ = _crafted(rng, list_quantum=4)
        assert ix4.pad_waste["quantum"] == 4

    def test_mesh_change_heals_or_demands_refit(self, rng):
        """Round 20: a mesh change under a fitted index auto-heals —
        search re-stripes from the retained host layout inputs (counted
        ``retrieval_rebinds``) and keeps its full-probe exactness.  Only
        an index whose host inputs were dropped still raises the typed
        refit demand."""
        ix, x = _crafted(rng)
        q = x[:MQ]
        _, oi = _oracle(q, x, K)
        ds.init((4, 2))
        prof.reset_counters()
        _, idx = ix.search(ds.array(q), k=K, nprobe=NLIST)
        assert _recall(idx.collect(), oi) == 1.0
        assert prof.resilience_counters().get("retrieval_rebinds") == 1
        assert ix._fitted_mesh == (4, 2)
        # host inputs dropped → the pre-round-20 typed demand survives
        ix._items_h = None
        ds.init((8, 1))
        with pytest.raises(RuntimeError, match="refit"):
            ix.search(ds.array(q), k=K)

    def test_unfitted_and_bad_inputs_are_typed(self, rng):
        with pytest.raises(RuntimeError, match="not fitted"):
            IVFIndex().search(np.zeros((1, 4)))
        ix, x = _crafted(rng)
        with pytest.raises(ValueError, match="features"):
            ix.search(np.zeros((2, D + 1), np.float32))
        with pytest.raises(ValueError, match="k must be"):
            ix.search(x[:2], k=0)
        with pytest.raises(ValueError, match="labels"):
            IVFIndex(n_lists=2)._build(x[:4], np.array([0, 1, 2, 0]),
                                       np.zeros((2, D)))


# ---------------------------------------------------------------------------
# the one-dispatch contract
# ---------------------------------------------------------------------------

class TestDispatchContract:
    def test_search_is_one_dispatch_zero_warm_retraces(self, rng):
        ix, x = _crafted(rng)
        q = ds.array(x[:MQ])
        ix.search(q, k=K, nprobe=3)             # compile
        prof.reset_counters()
        dist, idx = ix.search(q, k=K, nprobe=3)
        dist.collect(), idx.collect()
        c = prof.counters()
        assert c["dispatch_by"].get("ivf_search") == 1
        assert c["traces"] == 0
        assert prof.schedule_counters().get("ivf_search:db", 0) >= 1

    def test_schedule_router_is_observable(self, rng, monkeypatch):
        ix, x = _crafted(rng)
        monkeypatch.setenv("DSLIB_OVERLAP", "seq")
        before = prof.schedule_counters().get("ivf_search:seq", 0)
        ix.search(ds.array(x[:MQ]), k=K, nprobe=2)
        assert prof.schedule_counters()["ivf_search:seq"] == before + 1


# ---------------------------------------------------------------------------
# fit: the KMeans quantizer path
# ---------------------------------------------------------------------------

class TestFit:
    def test_fit_builds_from_kmeans_and_searches(self, rng):
        centers = rng.randn(4, D).astype(np.float32) * 20
        x = (centers[rng.randint(0, 4, 128)]
             + rng.randn(128, D)).astype(np.float32)
        ix = IVFIndex(n_lists=4, kmeans_max_iter=5, random_state=0).fit(x)
        assert ix.quantizer_ is not None and ix.n_lists_ == 4
        assert ix.n_items == 128 and ix.d == D
        dist, idx = ix.search(ds.array(x[:MQ]), k=1, nprobe=4)
        np.testing.assert_array_equal(idx.collect().ravel(),
                                      np.arange(MQ))

    def test_default_nlist_is_sqrt_heuristic(self, rng):
        x = rng.randn(64, D).astype(np.float32)
        ix = IVFIndex(kmeans_max_iter=2, random_state=0).fit(x)
        assert ix.n_lists_ == 8


# ---------------------------------------------------------------------------
# serving: bucket ladder, tenancy, and the deployment bundle
# ---------------------------------------------------------------------------

_FRESH_PROCESS_SCRIPT = """
import os, sys, json
import numpy as np
import dislib_tpu as ds
ds.init()
from dislib_tpu.serving import load_bundle
from dislib_tpu.utils import profiling as prof
lb = load_bundle(sys.argv[1])
rows = np.asarray(json.loads(sys.argv[2]), np.float32)
t0 = prof.trace_count()
outs = {b: lb.pipeline.predict_bucket(rows, b).tolist()
        for b in lb.buckets}
print(json.dumps({"traces": prof.trace_count() - t0,
                  "fallback": lb.fallback, "outs": outs}))
"""


class TestRetrievalServing:
    def test_pipeline_through_server_ladder(self, rng):
        from dislib_tpu.serving import PredictServer
        ix, x = _crafted(rng)
        pipe = RetrievalPipeline(ix, k=K, nprobe=NLIST)
        q = x[:5]
        dist, idx = ix.search(ds.array(q), k=K, nprobe=NLIST)
        want = np.concatenate([idx.collect().astype(np.float32),
                               dist.collect()], axis=1)
        with PredictServer(pipeline=pipe, buckets=(1, 8)) as srv:
            out = srv.predict(q)
            stats = srv.stats()
        assert stats["dispatches_per_batch_max"] == 1
        assert out.shape == (5, 2 * K)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)

    def test_pipeline_rebind_through_data_rebind(self, rng):
        """Round-20 elastic rebind: ``fitloop.data_rebind`` delegates to
        a holder exposing ``rebind_mesh`` — the pipeline re-stripes the
        index onto the new mesh and drops its quantum-shaped bucket
        canvases, and the re-striped serve answers match the pre-resize
        ones on the surviving device set."""
        from dislib_tpu.runtime.fitloop import data_rebind
        ds.init((8, 1))
        ix, x = _crafted(rng)
        pipe = RetrievalPipeline(ix, k=K, nprobe=NLIST)
        q = x[:MQ]
        before = pipe.predict_bucket(q, 8)
        assert pipe._templates           # canvases built on the old mesh
        ds.init((4, 2))                  # the elastic rung's resize
        hook = data_rebind({"x": pipe})
        prof.reset_counters()
        hook(None)                       # pre-switch force phase: no-op
        assert prof.resilience_counters().get("retrieval_rebinds") is None
        hook(_mesh_now())
        assert prof.resilience_counters().get("retrieval_rebinds") == 1
        assert ix._fitted_mesh == (4, 2)
        assert not pipe._templates       # stale canvases dropped
        after = pipe.predict_bucket(q, 8)
        # full probe on both meshes: identical retrieved sets; distances
        # agree to the kernel's near-zero cancellation tolerance (the
        # q²−2qf+f² form — same bound as the recall oracle above)
        np.testing.assert_array_equal(before[:, :K], after[:, :K])
        np.testing.assert_allclose(before[:, K:], after[:, K:], atol=2e-2)
        # a second hook on an unchanged mesh is a no-op
        hook(_mesh_now())
        assert prof.resilience_counters().get("retrieval_rebinds") == 1

    def test_serve_path_heals_after_external_mesh_move(self, rng):
        """Round-20 regression (found by the multi-host soak): when the
        mesh moves UNDER a serving pipeline — a co-resident fit loop
        resizing on a capacity event, no elastic hook wired — the next
        ``predict_bucket`` must heal end-to-end: the index auto-rebinds
        in ``_check_fitted`` AND the quantum-shaped bucket canvases
        follow.  A canvas cached for the old pad staged queries into the
        wrong shape and every subsequent request tore on a dot_general
        mismatch."""
        ds.init((8, 1))
        ix, x = _crafted(rng)
        pipe = RetrievalPipeline(ix, k=K, nprobe=NLIST)
        q = x[:MQ]
        before = pipe.predict_bucket(q, 8)
        assert pipe._templates
        ds.init((4, 2))                  # external resize, nobody told us
        prof.reset_counters()
        after = pipe.predict_bucket(q, 8)    # must not tear
        assert prof.resilience_counters().get("retrieval_rebinds") == 1
        assert ix._fitted_mesh == (4, 2)
        np.testing.assert_array_equal(before[:, :K], after[:, :K])
        np.testing.assert_allclose(before[:, K:], after[:, K:], atol=2e-2)

    def test_router_tenancy_composes(self, rng):
        from dislib_tpu.serving import ModelRouter, PredictServer
        ix, x = _crafted(rng)
        pipe = RetrievalPipeline(ix, k=K, nprobe=2)
        srv = PredictServer(pipeline=pipe, buckets=(8,), name="retr")
        r = ModelRouter()
        r.add_tenant("acme", srv, quota_rows=64)
        with r:
            out = r.predict(x[:3], "acme")
            st = r.stats()
        assert out.shape == (3, 2 * K)
        assert st["acme"]["serving"]["requests"] == 1
        assert st["acme"]["deadline_shed"] == 0

    @pytest.mark.xfail(
        os.environ.get("DSLIB_TEST_TPU") != "1", strict=True,
        raises=jax.errors.JaxRuntimeError,
        reason="the CPU client cannot serialise the IVF search executable "
               "(UNIMPLEMENTED: `LessThan` is not serializable), so "
               "export_bundle raises before the child starts.  If this "
               "passes on the CPU, remove the marker.")
    def test_bundle_roundtrip_fresh_subprocess(self, rng, tmp_path):
        """The headline cold-start claim: a process that never saw the
        index serves [ids|scores] off the bundle with ZERO traces."""
        import json
        from dislib_tpu.serving import export_bundle
        ix, x = _crafted(rng)
        pipe = RetrievalPipeline(ix, k=K, nprobe=NLIST)
        q = x[:4]
        live = pipe.predict_bucket(q, 8)
        path = str(tmp_path / "retr.bundle")
        export_bundle(pipe, path, buckets=(8,))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        out = subprocess.run(
            [sys.executable, "-c", _FRESH_PROCESS_SCRIPT, path,
             json.dumps(q.tolist())],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=REPO)
        assert out.returncode == 0, out.stderr[-2000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["traces"] == 0 and not res["fallback"]
        np.testing.assert_array_equal(
            np.asarray(res["outs"]["8"], np.float32), live)

    def test_id_ceiling_is_guarded(self, rng):
        ix, _ = _crafted(rng)
        ix.n_items = 1 << 24            # simulate a too-large catalog
        with pytest.raises(ValueError, match="2\\^24"):
            RetrievalPipeline(ix)


# ---------------------------------------------------------------------------
# satellite: the on-device sparse request encode + fold-in bundle
# ---------------------------------------------------------------------------

class TestSparsePackOnDevice:
    def test_device_pack_matches_host_path_bit_for_bit(self, rng):
        import scipy.sparse as sp
        from dislib_tpu.serving import pack_sparse_rows
        dense = np.where(rng.rand(6, 40) < 0.15,
                         rng.randn(6, 40), 0.0).astype(np.float32)
        prof.reset_counters()
        a = pack_sparse_rows(dense, nse_cap=8)
        c = prof.counters()
        assert c["dispatch_by"].get("pack_sparse_rows") == 1
        assert c["transfers"] == 1      # counts packed into the payload
        b = pack_sparse_rows(sp.csr_matrix(dense), nse_cap=8)
        np.testing.assert_array_equal(a, b)
        assert a.dtype == np.float32 and a.shape == (6, 16)

    def test_device_pack_error_parity(self, rng):
        import scipy.sparse as sp
        from dislib_tpu.serving import pack_sparse_rows
        full = np.ones((2, 12), np.float32)
        msgs = []
        for req in (full, sp.csr_matrix(full)):
            with pytest.raises(ValueError) as e:
                pack_sparse_rows(req, nse_cap=4)
            msgs.append(str(e.value))
        assert msgs[0] == msgs[1]
        # out-of-range ids stay typed on the device path too
        bad = np.zeros((1, 8), np.float32)
        bad[0, 6] = 1.0
        with pytest.raises(ValueError, match="out of range"):
            pack_sparse_rows(bad, nse_cap=4, n_items=5)

    def test_cap_wider_than_catalog(self, rng):
        import scipy.sparse as sp
        from dislib_tpu.serving import pack_sparse_rows
        small = np.zeros((2, 3), np.float32)
        small[0, 1] = 2.5
        a = pack_sparse_rows(small, nse_cap=8)
        b = pack_sparse_rows(sp.csr_matrix(small), nse_cap=8)
        np.testing.assert_array_equal(a, b)


def _tiny_als(rng):
    import scipy.sparse as sp
    from dislib_tpu.data.sparse import SparseArray
    from dislib_tpu.recommendation import ALS
    u = rng.rand(30, 4).astype(np.float32)
    v = rng.rand(20, 4).astype(np.float32)
    r = np.where(rng.rand(30, 20) < 0.4, u @ v.T, 0.0).astype(np.float32)
    return ALS(n_f=4, lambda_=0.002, max_iter=5, tol=1e-7,
               random_state=0).fit(SparseArray.from_scipy(sp.csr_matrix(r)))


class TestSparseFoldInBundle:
    @pytest.mark.parametrize("top_n", [None, 3])
    def test_bundle_matches_live_serving(self, rng, tmp_path, top_n):
        from dislib_tpu.serving import (SparseFoldInPipeline,
                                        export_bundle, load_bundle)
        als = _tiny_als(rng)
        pipe = SparseFoldInPipeline(als, nse_cap=16, top_n=top_n)
        packed = pipe.pack(np.where(rng.rand(5, 20) < 0.4, 1.0, 0.0)
                           .astype(np.float32))
        live = pipe.predict_bucket(packed, 8)
        path = str(tmp_path / f"foldin_{top_n}.bundle")
        export_bundle(pipe, path, buckets=(8,))
        lb = load_bundle(path)
        assert not lb.fallback
        prof.reset_counters()
        out = lb.pipeline.predict_bucket(packed, 8)
        assert prof.counters()["traces"] == 0
        np.testing.assert_allclose(out, live, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# satellite: latency-budget admission control
# ---------------------------------------------------------------------------

class TestDeadlineShed:
    def _served(self, rng):
        from dislib_tpu.serving import PredictServer
        ix, x = _crafted(rng)
        pipe = RetrievalPipeline(ix, k=K, nprobe=2)
        return PredictServer(pipeline=pipe, buckets=(8,),
                             name="dl"), x

    def test_cost_model_learns_from_serving(self, rng):
        srv, x = self._served(rng)
        with srv:
            for _ in range(3):
                srv.predict(x[:2])
            costs = srv.bucket_cost()
            stats = srv.stats()
        assert 8 in costs and costs[8] > 0.0
        assert stats["bucket_cost_ms"][8] > 0.0
        assert srv.predict_latency(2) is not None

    def test_no_shed_on_ignorance(self, rng):
        """A cold server has no cost model — the budget must admit, not
        guess."""
        from dislib_tpu.serving import ModelRouter
        srv, x = self._served(rng)
        r = ModelRouter(deadline_ms=0.001)
        r.add_tenant("acme", srv)
        with r:
            assert srv.predict_latency(2) is None
            out = r.predict(x[:2], "acme")
        assert out.shape == (2, 2 * K)

    def test_predicted_miss_sheds_typed_and_counted(self, rng):
        from dislib_tpu.serving import DeadlineShed, ModelRouter
        srv, x = self._served(rng)
        r = ModelRouter(deadline_ms=5)
        r.add_tenant("acme", srv)
        with r:
            # seed the learned model with measured-looking 10 s walls
            with srv._cv:
                srv._bucket_wall[8] = deque([10.0, 10.0, 10.0])
            with pytest.raises(DeadlineShed) as e:
                r.submit(x[:2], "acme")
            st = r.stats()
            out = None
            # the budget gone → the same request is admitted again
            r2 = ModelRouter(deadline_ms=None)
            r2.add_tenant("acme", srv)
            out = r2.predict(x[:2], "acme")
        assert e.value.tenant == "acme"
        assert e.value.predicted_ms > e.value.deadline_ms == 5.0
        assert st["acme"]["deadline_shed"] == 1
        assert st["acme"]["inflight_rows"] == 0     # reservation released
        assert out is not None

    def test_env_knob_sets_the_budget(self, rng, monkeypatch):
        from dislib_tpu.serving import ModelRouter
        monkeypatch.setenv("DSLIB_DEADLINE_MS", "250")
        assert ModelRouter().deadline_s == 0.25
        monkeypatch.delenv("DSLIB_DEADLINE_MS")
        assert ModelRouter().deadline_s is None
