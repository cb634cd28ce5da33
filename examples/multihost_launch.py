"""Multi-host launch — the `runcompss` replacement, end to end.

The reference starts a cluster job with `runcompss` + XML resource files;
here the whole of that stack is `ds.parallel.initialize()` (one call per
host process) and a mesh over the joined devices (SURVEY §3.7,
`dislib_tpu/parallel/distributed.py`).

Run with no arguments and this script *demonstrates* a 4-process job on
one machine: it re-launches itself as 4 gloo-connected worker processes
(2 virtual CPU devices each) on a 2-D (4, 2) PROCESS mesh — one mesh row
per process, so rows-axis collectives are pure cross-process traffic —
then fits a sharded KMeans and verifies every process agrees on the
centers.  On a real cluster you run one copy per host instead:

    # host i of N (same for TPU pods — jax auto-detects and every
    # argument may be omitted):
    DSLIB_COORDINATOR=host0:8476 DSLIB_NUM_PROCS=N DSLIB_PROC_ID=i \
        python your_fit.py
"""

import json
import os
import subprocess
import sys
import tempfile

# python examples/foo.py puts examples/ (not the repo root) on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_PROCS = 4


def worker(rank: int, port: str, out_path: str) -> None:
    # demo runs on CPU
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import dislib_tpu as ds

    ds.parallel.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=N_PROCS, process_id=rank)
    ds.init((N_PROCS, 2))                          # 2-D process mesh

    rng = np.random.RandomState(0)                 # same data every rank
    xh = rng.rand(256, 8).astype(np.float32)
    x = ds.array(xh, block_size=(32, 8))
    km = ds.KMeans(n_clusters=4, init=xh[:4].copy(), max_iter=10,
                   tol=0.0).fit(x)
    centers = np.asarray(km.centers_)
    assert np.isfinite(centers).all()
    # EVERY rank writes its centers; the launcher compares all four — the
    # whole point of the demo is that the sharded fit agrees across hosts
    with open(f"{out_path}.rank{rank}", "w") as f:
        json.dump(centers.tolist(), f)
    print(f"[rank {rank}] fit done; centers[0,0]={centers[0, 0]:.4f}",
          flush=True)


def launch() -> None:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = str(s.getsockname()[1])
    s.close()
    out = os.path.join(tempfile.mkdtemp(), "centers.json")
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), str(r), port, out])
        for r in range(N_PROCS)]
    try:
        rcs = [p.wait(timeout=300) for p in procs]
    except subprocess.TimeoutExpired:
        # a worker stuck in a collective would strand its peers forever
        for p in procs:
            p.kill()
        raise
    assert rcs == [0] * N_PROCS, f"worker exit codes {rcs}"
    import numpy as np
    all_centers = []
    for r in range(N_PROCS):
        with open(f"{out}.rank{r}") as f:
            all_centers.append(np.asarray(json.load(f)))
    for r in range(1, N_PROCS):
        np.testing.assert_allclose(all_centers[r], all_centers[0],
                                   rtol=1e-6, atol=1e-7)
    print(f"4-process job OK — all {N_PROCS} ranks agree on "
          f"{all_centers[0].shape[0]} centers across the 2-D process mesh")


if __name__ == "__main__":
    if len(sys.argv) == 4:
        worker(int(sys.argv[1]), sys.argv[2], sys.argv[3])
    else:
        launch()
