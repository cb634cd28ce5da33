"""Worker script for the multi-process (multi-"host") integration test.

Launched by tests/test_multiprocess.py as N separate processes, each with 4
virtual CPU devices — the DCN analog of the reference's COMPSs
workers-as-local-processes CI rig (SURVEY §5): process boundaries are real,
collectives cross them via gloo, and the library's own distributed
bootstrap (`dislib_tpu.parallel.distributed.initialize`) does the wiring.

Each worker: joins the job → builds the global mesh → per-host byte-range
text ingest → KMeans fit → rank 0 writes centers + ingest checksum to
`out_path`.
"""

import json
import os
import sys


def _bootstrap(rank, nprocs, port, csv_path, devs_per_proc=4, mesh=None):
    """Shared worker bring-up: join the job, build the mesh, ingest.
    Returns (ds, x, xs_host) — xs_host from the ONE collect allgather.

    ``mesh`` (rows, cols): default (global_devices, 1) puts every device
    on the cross-process rows axis; the grid mode passes (nprocs,
    devs_per_proc) — a true 2-D PROCESS mesh where each process owns one
    mesh row (rows = DCN analog, cols = intra-host)."""
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devs_per_proc}"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from dislib_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=nprocs, process_id=rank)
    assert jax.process_count() == nprocs
    import numpy as np
    import dislib_tpu as ds
    ds.init(mesh or (jax.device_count(), 1))  # rows axis spans the "DCN"
    # per-host SHARD-LOCAL ingest: each process parses only its row slab
    # and must neither run a collective nor materialise the full array
    # (SURVEY §4.1; round-2 VERDICT missing #3).  Instrumented: any
    # process_allgather during the load fails the job.
    from jax.experimental import multihost_utils as _mh
    calls = {"n": 0}
    real_ag = _mh.process_allgather

    def counting_ag(*a, **k):
        calls["n"] += 1
        return real_ag(*a, **k)

    _mh.process_allgather = counting_ag
    x = ds.load_txt_file(csv_path, block_size=(16, 5))
    if os.path.exists(csv_path + ".npy"):
        xn = ds.load_npy_file(csv_path + ".npy")
        xsv, _ = ds.load_svmlight_file(csv_path + ".svm", n_features=5,
                                       store_sparse=False)
    else:
        xn = xsv = None
    _mh.process_allgather = real_ag
    assert calls["n"] == 0, "ingest ran a collective — not shard-local"
    # addressable shards cover exactly this rank's contiguous row slab
    M = x._data.shape[0]
    imap = x._data.sharding.devices_indices_map(x._data.shape)
    spans = sorted(idx[0].indices(M)[:2]
                   for d, idx in imap.items()
                   if d.process_index == jax.process_index())
    slab = M // nprocs
    assert spans[0][0] == rank * slab, (spans, rank, slab)
    assert max(s[1] for s in spans) == (rank + 1) * slab, (spans, rank, slab)
    assert not x._data.is_fully_addressable
    xs_host = np.asarray(x.collect())
    if xn is not None:
        np.testing.assert_allclose(np.asarray(xn.collect()), xs_host,
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(xsv.collect()), xs_host,
                                   atol=2e-6)
    return ds, x, xs_host


def _arm_crash_saves():
    """DSLIB_TEST_CRASH_AFTER_SAVES=k: the whole job hard-dies (os._exit)
    right after the k-th durable snapshot — the recoverable mid-job
    host-death scenario (SURVEY §6 failure-detection row)."""
    from dislib_tpu.utils import checkpoint as ckm
    crash_after = int(os.environ.get("DSLIB_TEST_CRASH_AFTER_SAVES", "0"))
    if crash_after:
        real_save = ckm.FitCheckpoint.save
        state = {"n": 0}

        def dying_save(self, payload):
            real_save(self, payload)
            state["n"] += 1
            if state["n"] >= crash_after:
                os._exit(17)          # abrupt host death, snapshot durable
        ckm.FitCheckpoint.save = dying_save


def crashfit_main():
    """Fault-injection mode: all ranks run a checkpointed KMeans fit with
    optional crash-after-k-saves; re-running the same command resumes from
    the snapshot and writes final centers."""
    rank = int(sys.argv[2])
    nprocs = int(sys.argv[3])
    port = sys.argv[4]
    csv_path = sys.argv[5]
    ck_path = sys.argv[6]
    out_path = sys.argv[7]

    import numpy as np
    from dislib_tpu.utils import checkpoint as ckm

    _arm_crash_saves()
    _, x, xs_host = _bootstrap(rank, nprocs, port, csv_path)
    km = _ck_fit(x, xs_host, ck_path)
    if rank == 0:
        with open(out_path, "w") as f:
            json.dump({"centers": np.asarray(km.centers_).tolist(),
                       "n_iter": int(km.n_iter_)}, f)
    print(f"crashfit worker {rank} done", flush=True)


def _ck_fit(x, xs_host, ck_path):
    """The one checkpointed-fit recipe both fault-injection modes run:
    12 Lloyd iterations, init = first 3 rows, snapshot every 3 — cadence
    changes apply to crashfit and grid together."""
    from dislib_tpu.cluster import KMeans
    from dislib_tpu.utils import checkpoint as ckm
    km = KMeans(n_clusters=3, init=xs_host[:3].copy(), max_iter=12, tol=0.0)
    return km.fit(x, checkpoint=ckm.FitCheckpoint(ck_path, every=3))


def grid_main():
    """Round-5 4-process 2-D PROCESS-mesh mode (SURVEY §3.7 cross-slice /
    hierarchical row): mesh (nprocs, 2) with 2 virtual devices per
    process — every process owns exactly one mesh ROW, so the rows axis
    is a pure DCN analog (all row-axis collectives cross process
    boundaries) while cols is intra-host.  Runs: shard-local ingest,
    checkpointed KMeans fit (with optional crash-after-k-saves), a global
    all_to_all shuffle across the boundary, and collect."""
    rank = int(sys.argv[2])
    nprocs = int(sys.argv[3])
    port = sys.argv[4]
    csv_path = sys.argv[5]
    ck_path = sys.argv[6]
    out_path = sys.argv[7]

    import numpy as np

    _arm_crash_saves()
    ds, x, xs_host = _bootstrap(rank, nprocs, port, csv_path,
                                devs_per_proc=2, mesh=(nprocs, 2))
    import jax
    from dislib_tpu.parallel import mesh as _mesh
    m = _mesh.get_mesh()
    assert dict(zip(m.axis_names, m.devices.shape)) == \
        {"rows": nprocs, "cols": 2}
    # one mesh row == one process (the 2-D process-mesh contract)
    my_rows = {np.argwhere(m.devices == d)[0][0]
               for d in jax.local_devices()}
    assert len(my_rows) == 1, f"process spans mesh rows {my_rows}"

    km = _ck_fit(x, xs_host, ck_path)

    # 2-D collective mix: the Gram GEMM partitions over BOTH axes — cols
    # collectives stay intra-process, the rows reduction crosses all 4
    gram_trace = float(np.trace(np.asarray(
        ds.matmul(x, x, transpose_b=True).collect())))
    assert abs(gram_trace - float((xs_host * xs_host).sum())) \
        <= 1e-4 * max(1.0, abs(gram_trace)), f"rank {rank}: gram trace"

    from dislib_tpu.utils import shuffle
    xsh = np.asarray(shuffle(x, random_state=7).collect())
    # asserted on EVERY rank (nonzero exit), not just recorded by rank 0:
    # a gloo bug corrupting only a non-zero rank's gather must fail the job
    shuffle_ok = sorted(map(tuple, xsh.tolist())) == \
        sorted(map(tuple, xs_host.tolist()))
    assert shuffle_ok, f"rank {rank}: shuffle lost/changed rows"

    if rank == 0:
        with open(out_path, "w") as f:
            json.dump({"centers": np.asarray(km.centers_).tolist(),
                       "n_iter": int(km.n_iter_),
                       "checksum": float(xs_host.sum()),
                       "shape": list(x.shape),
                       "shuffle_ok": bool(shuffle_ok)}, f)
    print(f"grid worker {rank} done", flush=True)


def main():
    if sys.argv[1] == "crashfit":
        crashfit_main()
        return
    if sys.argv[1] == "grid":
        grid_main()
        return
    rank = int(sys.argv[1])
    nprocs = int(sys.argv[2])
    port = sys.argv[3]
    csv_path = sys.argv[4]
    out_path = sys.argv[5]

    import numpy as np
    ds, x, xs_host = _bootstrap(rank, nprocs, port, csv_path)
    from dislib_tpu.cluster import KMeans

    km = KMeans(n_clusters=3, init=xs_host[:3].copy(), max_iter=5, tol=0.0)
    km.fit(x)

    # tp: 2-D-sharded GEMM across the process boundary
    c = ds.matmul(x, x, transpose_b=True)
    gram_trace = float(np.trace(np.asarray(c.collect())))

    # sp analog: shard_map tsQR (all_gather(R) rides the cross-process axis)
    q, r = ds.tsqr(x)
    qh, rh = np.asarray(q.collect()), np.asarray(r.collect())
    qr_err = float(np.abs(qh @ rh - xs_host).max())

    # ring schedule: ppermute rotation crosses the process boundary
    from dislib_tpu.neighbors import NearestNeighbors
    d_ring, _ = NearestNeighbors(n_neighbors=3, ring=True).fit(x) \
        .kneighbors(x)
    ring_d = np.asarray(d_ring.collect())

    # all-to-all: the global shuffle exchange crosses the process boundary
    # (row content must be preserved exactly, just reordered)
    from dislib_tpu.utils import shuffle
    xsh = np.asarray(shuffle(x, random_state=7).collect())
    shuffle_ok = sorted(map(tuple, xsh.tolist())) == \
        sorted(map(tuple, xs_host.tolist()))

    # sparse tier crosses the process boundary too (round 4): row-sharded
    # BCOO KMeans (shard_map segment-sum E-step + psum over the DCN axis)
    # vs the dense path on the same matrix, and the sharded sparse-fit
    # kNN stream with dense queries
    import scipy.sparse as sp
    from dislib_tpu.data.sparse import SparseArray
    xsp_host = xs_host.copy()
    xsp_host[xsp_host < 0.5] = 0.0
    s_arr = SparseArray.from_scipy(sp.csr_matrix(xsp_host))
    km_sp = KMeans(n_clusters=3, init=xsp_host[:3].copy(), max_iter=3,
                   tol=0.0).fit(s_arr)
    km_dn = KMeans(n_clusters=3, init=xsp_host[:3].copy(), max_iter=3,
                   tol=0.0).fit(ds.array(xsp_host, block_size=(16, 5)))
    sparse_centers_close = bool(np.allclose(km_sp.centers_, km_dn.centers_,
                                            rtol=1e-3, atol=1e-3))
    d_sp, _ = NearestNeighbors(n_neighbors=3).fit(s_arr).kneighbors(x)
    sparse_knn_sum = float(np.asarray(d_sp.collect()).sum())

    # SPMD discipline: EVERY rank runs the same collectives in the same
    # order (collect() is a process_allgather) — only the file write is
    # rank-conditional
    centers = np.asarray(km.centers_)
    checksum = float(xs_host.sum())
    if rank == 0:
        with open(out_path, "w") as f:
            json.dump({"centers": centers.tolist(),
                       "checksum": checksum,
                       "shape": list(x.shape),
                       "gram_trace": gram_trace,
                       "qr_err": qr_err,
                       "shuffle_ok": bool(shuffle_ok),
                       "ring_d_sum": float(ring_d.sum()),
                       "sparse_centers_close": sparse_centers_close,
                       "sparse_knn_sum": sparse_knn_sum}, f)
    print(f"worker {rank} done", flush=True)


if __name__ == "__main__":
    main()
