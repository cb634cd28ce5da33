"""Multi-process ("multi-host") integration: the library's distributed
bootstrap, per-host byte-range ingest, and a cross-process KMeans fit —
run for real across 2 OS processes × 4 virtual CPU devices with gloo
collectives (SURVEY §3.7 / §5: the reference exercised its cross-node path
with COMPSs workers as local processes; this is the same trick for DCN).

Skipped under ``DSLIB_TEST_TPU=1``: a chip belongs to one process, so it
cannot host a 2-process gloo job."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(os.environ.get("DSLIB_TEST_TPU") == "1",
                                reason="multi-process CPU rig only")

_HERE = os.path.dirname(os.path.abspath(__file__))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_kmeans_matches_single(tmp_path):
    rng = np.random.RandomState(0)
    data = rng.rand(96, 5).astype(np.float32)
    csv = str(tmp_path / "data.csv")
    np.savetxt(csv, data, delimiter=",", fmt="%.6f")
    # same matrix for the npy / dense-svmlight shard-local loaders (the
    # worker loads all three collective-free and cross-checks them)
    parsed0 = np.loadtxt(csv, delimiter=",", dtype=np.float32, ndmin=2)
    np.save(csv + ".npy", parsed0)
    with open(csv + ".svm", "w") as f:
        for i, row in enumerate(parsed0):
            feats = " ".join(f"{j + 1}:{v:.6f}"
                             for j, v in enumerate(row) if v != 0)
            f.write(f"{i % 2} {feats}\n")
    out = str(tmp_path / "result.json")
    port = _free_port()

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = os.path.dirname(_HERE)
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(_HERE, "mp_worker.py"),
         str(r), "2", str(port), csv, out],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    outs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(stdout.decode())
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i} failed:\n{outs[i]}"

    with open(out) as f:
        got = json.load(f)
    # oracle: parse + fit in-process on the same data
    parsed = np.loadtxt(csv, delimiter=",", dtype=np.float32, ndmin=2)
    assert got["shape"] == [96, 5]
    np.testing.assert_allclose(got["checksum"], parsed.sum(), rtol=1e-5)

    centers = np.asarray(parsed[:3], np.float64)
    for _ in range(5):
        d = ((parsed[:, None, :] - centers[None]) ** 2).sum(-1)
        lab = d.argmin(1)
        centers = np.stack([
            parsed[lab == j].mean(0) if (lab == j).any() else centers[j]
            for j in range(3)])
    np.testing.assert_allclose(np.asarray(got["centers"]), centers,
                               rtol=2e-3, atol=2e-3)

    # tp / sp / ring results crossed the process boundary correctly
    np.testing.assert_allclose(got["gram_trace"],
                               np.trace(parsed @ parsed.T), rtol=1e-4)
    assert got["qr_err"] < 1e-3
    assert got["shuffle_ok"], "all-to-all shuffle lost/changed rows across hosts"
    dd = ((parsed[:, None, :] - parsed[None]) ** 2).sum(-1)
    k3 = np.sqrt(np.maximum(np.sort(dd, axis=1)[:, :3], 0.0))
    np.testing.assert_allclose(got["ring_d_sum"], k3.sum(), rtol=1e-3)

    # sparse tier across the process boundary: BCOO KMeans matched the
    # dense path in-worker, and the sharded sparse-fit kNN stream matches
    # the host oracle
    assert got["sparse_centers_close"], \
        "multi-host sparse KMeans diverged from the dense path"
    xsp = parsed.copy()
    xsp[xsp < 0.5] = 0.0
    dsp = ((parsed[:, None, :] - xsp[None]) ** 2).sum(-1)
    k3s = np.sqrt(np.maximum(np.sort(dsp, axis=1)[:, :3], 0.0))
    np.testing.assert_allclose(got["sparse_knn_sum"], k3s.sum(), rtol=1e-3)


def _run_ckfit(tmp_path, csv, tag, crash_after, mode, nprocs):
    """Launch one checkpointed-fit job: ``mode`` 'crashfit' (flat
    (n·4, 1) mesh) or 'grid' (2-D (nprocs, 2) process mesh)."""
    out = str(tmp_path / f"{tag}.json")
    ck = str(tmp_path / f"{tag}.ck.npz")
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = os.path.dirname(_HERE)
    if crash_after:
        env["DSLIB_TEST_CRASH_AFTER_SAVES"] = str(crash_after)
    else:
        env.pop("DSLIB_TEST_CRASH_AFTER_SAVES", None)
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(_HERE, "mp_worker.py"), mode,
         str(r), str(nprocs), str(port), csv, ck, out],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(nprocs)]
    rcs, outs = [], []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        rcs.append(p.returncode)
        outs.append(stdout.decode())
    return rcs, outs, out, ck


def _run_grid(tmp_path, csv, tag, crash_after, nprocs=4):
    return _run_ckfit(tmp_path, csv, tag, crash_after, "grid", nprocs)


def test_four_process_grid_mesh_and_resume(tmp_path):
    """Round-5 (SURVEY §3.7 cross-slice row, §6 fault tolerance): 4 real
    processes on a 2-D PROCESS mesh (4 rows × 2 cols, one mesh row per
    process).  KMeans + collect + checkpoint-resume + all_to_all shuffle
    all cross the 4-way process boundary; centers oracle'd against an
    in-process NumPy Lloyd run, and the kill+resume run must land on the
    uninterrupted run's centers exactly."""
    rng = np.random.RandomState(2)
    data = rng.rand(96, 5).astype(np.float32)
    csv = str(tmp_path / "data.csv")
    np.savetxt(csv, data, delimiter=",", fmt="%.6f")
    parsed = np.loadtxt(csv, delimiter=",", dtype=np.float32, ndmin=2)

    # uninterrupted run
    rcs, outs, out_ok, _ = _run_grid(tmp_path, csv, "ok", crash_after=0)
    assert rcs == [0, 0, 0, 0], outs
    with open(out_ok) as f:
        oracle = json.load(f)
    assert oracle["n_iter"] == 12
    assert oracle["shape"] == [96, 5]
    assert oracle["shuffle_ok"], "4-way all_to_all shuffle lost rows"
    np.testing.assert_allclose(oracle["checksum"], parsed.sum(), rtol=1e-5)

    # NumPy Lloyd oracle (same init = first 3 rows, 12 iterations)
    centers = np.asarray(parsed[:3], np.float64)
    for _ in range(12):
        d = ((parsed[:, None, :] - centers[None]) ** 2).sum(-1)
        lab = d.argmin(1)
        centers = np.stack([
            parsed[lab == j].mean(0) if (lab == j).any() else centers[j]
            for j in range(3)])
    np.testing.assert_allclose(np.asarray(oracle["centers"]), centers,
                               rtol=2e-3, atol=2e-3)

    # whole-job death after the 2nd durable snapshot (6 of 12 iters)
    rcs, outs, out_crash, ck = _run_grid(tmp_path, csv, "crash",
                                         crash_after=2)
    assert rcs == [17, 17, 17, 17], outs
    assert os.path.exists(ck) and not os.path.exists(out_crash)

    # resume across all 4 processes → identical final centers
    rcs, outs, out_res, _ = _run_grid(tmp_path, csv, "crash", crash_after=0)
    assert rcs == [0, 0, 0, 0], outs
    with open(out_res) as f:
        resumed = json.load(f)
    assert resumed["n_iter"] == 12
    np.testing.assert_allclose(np.asarray(resumed["centers"]),
                               np.asarray(oracle["centers"]),
                               rtol=1e-5, atol=1e-6)


def _run_crashfit(tmp_path, csv, tag, crash_after):
    return _run_ckfit(tmp_path, csv, tag, crash_after, "crashfit", 2)


def test_kill_and_resume_equivalence(tmp_path):
    """SURVEY §6 failure-detection: the whole 2-process job dies abruptly
    after the 2nd durable snapshot; re-running the same launch resumes from
    the snapshot and must land on the uninterrupted run's centers."""
    rng = np.random.RandomState(1)
    data = rng.rand(96, 5).astype(np.float32)
    csv = str(tmp_path / "data.csv")
    np.savetxt(csv, data, delimiter=",", fmt="%.6f")

    # uninterrupted oracle (same chunking via the same checkpoint cadence)
    rcs, outs, out_ok, _ = _run_crashfit(tmp_path, csv, "ok", crash_after=0)
    assert rcs == [0, 0], outs
    with open(out_ok) as f:
        oracle = json.load(f)
    assert oracle["n_iter"] == 12

    # crash run: both ranks exit 17 after the 2nd snapshot (6 of 12 iters)
    rcs, outs, out_crash, ck = _run_crashfit(tmp_path, csv, "crash",
                                             crash_after=2)
    assert rcs == [17, 17], outs
    assert os.path.exists(ck) and not os.path.exists(out_crash)

    # resume: same launch, no crash env — continues from the snapshot
    rcs, outs, out_res, _ = _run_crashfit(tmp_path, csv, "crash",
                                          crash_after=0)
    assert rcs == [0, 0], outs
    with open(out_res) as f:
        resumed = json.load(f)
    assert resumed["n_iter"] == 12
    np.testing.assert_allclose(np.asarray(resumed["centers"]),
                               np.asarray(oracle["centers"]),
                               rtol=1e-5, atol=1e-6)
