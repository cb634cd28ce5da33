#!/usr/bin/env python
"""Multi-host proof harness: the round-19 DCN data-plane dryrun AND the
round-20 process-killing chaos driver.

**Dryrun mode** (``mh_dryrun.py <rank> <nprocs> <port> <workdir>``) —
one rank of the two-process DCN data-plane dryrun (round 19).

**Chaos mode** (``mh_dryrun.py --chaos [workdir]``) — the round-20
survival drill: a parent driver spawns two REAL rank processes that
coordinate through the shared-directory ``FileCoordinator``
(``DSLIB_COORD_DIR``) with heartbeat leases, then

1. SIGKILLs rank 1 mid-fit (the rank kills ITSELF right after its first
   snapshot lands — a real, uncatchable ``SIGKILL`` at a deterministic
   point in the work stream); the survivor's lease keeper confirms the
   death, publishes the shrunk capacity target, and the survivor's fit
   shrinks (2,1)→(1,1) mid-fit and lands on the shrunk-fleet oracle;
2. RESTARTS rank 1: it rejoins under a bumped epoch (asserted), its
   stale pre-crash posts are fenced out of gathers (asserted), and the
   survivor's in-flight fit GROWS BACK to the home mesh;
3. delays heartbeats past the lease (the flap): the survivor counts a
   death AND a rejoin with no process restart;
4. tears coordination files and the capacity ledger mid-write: readers
   classify TRANSIENT, retry, and heal — never a fleet kill;
5. kills rank 1 again and drives the sharded-bundle load-barrier seam:
   the survivor aborts typed (``load barrier ABORTED``) within
   ``DSLIB_BARRIER_TIMEOUT`` — with membership the abort is immediate
   (attributed ``RankDead``), without it the deadline holds.  Zero
   hangs anywhere: every wait in the harness carries a hard deadline
   and the parent bounds every child.

Why the file transport and not ``jax.distributed``: probed on this
rig's jaxlib (0.4.36), SIGKILLing one rank of a ``jax.distributed``
fleet tears down the SURVIVORS too (the coordination-service disconnect
propagates as a fatal error), and overriding the missed-heartbeat
callback crashes in native code — so no survivable kill drill exists on
that transport here.  The membership/lease layer rides the coordinator
dslib owns; the chaos scenarios therefore run on the documented
shared-filesystem rig transport, and the round-19 dryrun below keeps
covering the ``jax.distributed`` KV path for healthy fleets.

**Dryrun mode** details — launched (twice) by
``tools/run_multihost.sh``: two REAL OS processes, each owning 2
virtual CPU devices, joined through ``jax.distributed.initialize`` — 4
global devices, the 'rows' mesh axis spanning the process (DCN)
boundary.  Each rank proves, for real:

1. **rechunk parity** — the hierarchical ``dcn`` schedule relays a
   deterministic global array across mesh shapes; every rank checks its
   addressable output shards bit-for-bit against the host-side oracle,
   and the analytic accounting invariants (messages/step ≤ hosts−1,
   bytes == deviceput floor) hold;
2. **sharded-bundle load barrier** — ``export_bundle(hosts=2)`` (each
   rank writes its own shard, rank 0 the manifest), a coordinated
   ``load_bundle`` where both ranks serve bit-correct predictions; then
   the poisoned episode: rank 1 corrupts ITS shard, and BOTH ranks
   raise the same typed ``BundleShardCorrupt`` — zero hosts serve;
3. **coherent capacity episode** — rank 0 publishes shrink(2) then
   grow(4) through the shared ``CapacityLedger``; both ranks observe
   the same level at each step (asserted by exchanging observations),
   with the ledger epoch strictly increasing.

Usage: ``mh_dryrun.py <rank> <nprocs> <port> <workdir>`` (dryrun),
``mh_dryrun.py --chaos [workdir]`` (chaos driver), or
``mh_dryrun.py --chaos-rank <rank> <phase> <workdir>`` (one chaos rank —
spawned by the driver, not by hand).  Exit 0 = green.
"""

import json
import os
import signal
import subprocess
import sys
import time


def log(rank, msg):
    print(f"[dryrun r{rank}] {msg}", flush=True)


def main():
    rank, nprocs = int(sys.argv[1]), int(sys.argv[2])
    port, workdir = int(sys.argv[3]), sys.argv[4]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["DSLIB_PROC_ID"] = str(rank)
    os.environ["DSLIB_CAPACITY_LEDGER"] = os.path.join(workdir,
                                                       "cap.ledger")
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    import numpy as np
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import dislib_tpu as ds
    from dislib_tpu.ops import rechunk as rc
    from dislib_tpu.parallel import mesh as _mesh
    from dislib_tpu.runtime import BundleShardCorrupt, CapacityLedger
    from dislib_tpu.runtime.coord import get_coordinator
    from dislib_tpu.runtime.preemption import capacity_target
    from dislib_tpu.serving import ServePipeline, export_bundle, load_bundle

    ds.parallel.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=nprocs, process_id=rank)
    assert jax.process_count() == nprocs, "distributed join failed"
    assert len(jax.local_devices()) == 2
    ds.init()                           # (4, 1): rows axis spans DCN
    coord = get_coordinator()
    log(rank, f"joined: {jax.device_count()} global devices, "
              f"coordinator={type(coord).__name__}")

    # ---- phase 1: hierarchical rechunk parity --------------------------
    # The only phase needing cross-process COLLECTIVES (the coordination
    # service used by phases 2/3 is platform-independent): jaxlib < 0.6
    # CPU backends raise "Multiprocess computations aren't implemented",
    # so the parity run is version-gated here — tier-1 still proves the
    # schedule bit-equal on every run through the DSLIB_MOCK_HOSTS
    # overlay (tests/test_multihost_dataplane.py).
    src = _mesh.get_mesh()
    m, n = 50, 6
    x = (np.arange(m * n, dtype=np.float32).reshape(m, n) * 0.5 - 7.0)
    pr = src.shape[_mesh.ROWS]
    mp = -(-m // pr) * pr
    xp = np.zeros((mp, n), np.float32)
    xp[:m] = x
    sh = _mesh.data_sharding(src)
    data = jax.make_array_from_callback((mp, n), sh, lambda idx: xp[idx])
    dst = Mesh(np.asarray(list(src.devices.flat)).reshape(2, 2),
               _mesh.AXIS_NAMES)
    assert rc.dcn_supported(data, dst), "hierarchical layout not detected"
    acct = rc.dcn_accounting(data, (m, n), dst)
    assert acct["hosts"] == nprocs
    assert acct["messages_per_step_max"] <= acct["hosts"] - 1
    assert acct["dcn_bytes_moved"] == acct["deviceput_bytes"]
    out, sched = rc.reshard(data, (m, n), dst, schedule="dcn")
    assert sched == "dcn"
    # oracle: the relayout is a pure re-partition of the logical array
    mp2 = -(-m // 2) * 2
    np2 = -(-n // 2) * 2
    oracle = np.zeros((mp2, np2), np.float32)
    oracle[:m, :n] = x
    for s in out.addressable_shards:
        np.testing.assert_array_equal(np.asarray(s.data),
                                      oracle[s.index],
                                      err_msg="dcn shard mismatch")
    log(rank, f"rechunk parity OK ({acct['dcn_messages']} DCN "
              f"messages, {acct['dcn_bytes_moved']} bytes)")
    votes = coord.exchange("dryrun-rechunk", rank, True, n=nprocs)
    assert all(votes.values())

    # ---- phase 2: sharded bundle + load barrier ------------------------
    # Serving topology: each host serves ITS shard on ITS local devices
    # (the per-host serving mesh — what the sharded bundle's mesh
    # contract describes).  Everything below is collective-free: the
    # cross-process protocol rides the coordination service, compute
    # stays intra-host — so this phase runs for real on every rig.
    ds.init(mesh_shape=(len(jax.local_devices()), 1),
            devices=jax.local_devices())
    jax.clear_caches()
    NF = 4
    lr = ds.LinearRegression()
    lr.coef_ = np.arange(NF, dtype=np.float32).reshape(NF, 1)
    lr.intercept_ = np.full(1, 2.5, np.float32)
    pipe = ServePipeline(lr, n_features=NF)
    state = {"coef": lr.coef_, "intercept": lr.intercept_}
    good = os.path.join(workdir, "good.dsb.npz")
    export_bundle(pipe, good, buckets=(1, 8), state=state, hosts=nprocs)
    lb = load_bundle(good)
    assert not lb.fallback and lb.host == rank and lb.hosts == nprocs
    xq = np.linspace(0, 1, 3 * NF, dtype=np.float32).reshape(3, NF)
    got = lb.pipeline.predict_bucket(xq, 8)
    np.testing.assert_allclose(got, xq @ lr.coef_ + 2.5, atol=1e-5)
    log(rank, "sharded bundle served bit-correct after the barrier")

    bad = os.path.join(workdir, "bad.dsb.npz")
    export_bundle(pipe, bad, buckets=(1,), state=state, hosts=nprocs)
    if rank == 1:
        with open(bad + ".shard1", "r+b") as f:
            f.seek(64)
            f.write(b"\xde\xad\xbe\xef")
    coord.exchange("dryrun-corrupted", rank, True, n=nprocs)
    try:
        load_bundle(bad)
        raise AssertionError("corrupt shard served — barrier failed")
    except BundleShardCorrupt as e:
        assert e.host == 1, f"wrong host blamed: {e.host}"
    coord.exchange("dryrun-abort-seen", rank, True, n=nprocs)
    log(rank, "poisoned shard → typed abort on BOTH ranks, zero served")

    # ---- phase 3: coherent shrink→grow capacity episode ----------------
    ledger = CapacityLedger(os.environ["DSLIB_CAPACITY_LEDGER"])
    episodes = []
    for step, target in (("shrink", 2), ("grow", 4)):
        if rank == 0:
            ds.runtime.request_capacity(target)   # publishes to the ledger
        deadline = time.time() + 20
        seen, epoch = None, 0
        while time.time() < deadline:
            seen, epoch = ledger.read()
            if seen == target:
                break
            time.sleep(0.02)
        assert seen == target, f"{step}: rank {rank} saw {seen}"
        # the consumer-side view agrees (override on the writer, ledger
        # on everyone else — one coherent level either way)
        assert capacity_target() == target
        episodes.append((step, target, epoch))
        # every rank observed the same level AT the same ledger epoch —
        # the rank-0 writer publishes the next step only after this
        # barrier, so the recorded epochs are comparable fleet-wide
        obs = coord.exchange(f"dryrun-cap-{step}", rank, [seen, epoch],
                             n=nprocs)
        vals = {tuple(v) for v in obs.values()}
        assert vals == {(target, epoch)}, f"incoherent fleet: {obs}"
    assert episodes[0][2] < episodes[1][2], "ledger epoch not monotonic"
    log(rank, f"capacity episode coherent: {episodes}")

    with open(os.path.join(workdir, f"result.{rank}.json"), "w") as f:
        json.dump({"rank": rank, "rechunk": acct,
                   "episodes": episodes}, f)
    coord.exchange("dryrun-done", rank, True, n=nprocs)
    ds.parallel.shutdown()
    log(rank, "ALL PHASES GREEN")


# ===========================================================================
# round-20 chaos harness
# ===========================================================================

CHAOS_LEASE_MS = "1000"                 # short lease: deaths confirm fast
CHAOS_BARRIER_S = "6"                   # DSLIB_BARRIER_TIMEOUT for the drill


def clog(rank, msg):
    print(f"[chaos r{rank} +{time.monotonic() % 1e4:8.2f}] {msg}",
          flush=True)


def _wait_for(pred, deadline_s, what, poll=0.05):
    """Bounded wait — EVERY wait in the chaos harness goes through here,
    so 'zero hangs' is structural, not luck."""
    end = time.monotonic() + float(deadline_s)
    while True:
        v = pred()
        if v:
            return v
        if time.monotonic() >= end:
            raise AssertionError(f"HANG GUARD: {what} not observed "
                                 f"within {deadline_s}s")
        time.sleep(poll)


def _chaos_env_setup(workdir, rank):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["DSLIB_PROC_ID"] = str(rank)
    os.environ["DSLIB_COORD_DIR"] = os.path.join(workdir, "coord")
    os.environ["DSLIB_CAPACITY_LEDGER"] = os.path.join(workdir,
                                                       "cap.ledger")
    os.environ.setdefault("DSLIB_COORD_LEASE_MS", CHAOS_LEASE_MS)
    os.environ.setdefault("DSLIB_BARRIER_TIMEOUT", CHAOS_BARRIER_S)
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def _chaos_fit_setup():
    """The chaos fit: same KMeans shape as the tier-1 elastic scenarios
    (chunk results are mesh-size-independent, so ONE oracle serves every
    device set the fit lands on)."""
    import numpy as np
    rng = np.random.RandomState(0)
    centers = rng.rand(3, 4) * 10
    x_np = np.vstack([centers[i] + 0.3 * rng.randn(66, 4)
                      for i in range(3)]).astype(np.float32)
    init = np.ascontiguousarray(x_np[[0, 70, 140]])
    kw = dict(n_clusters=3, init=init, max_iter=12, tol=0.0)
    return x_np, kw


def chaos_rank0(workdir):
    """The SURVIVOR: observes the death, shrinks mid-fit, matches the
    shrunk-fleet oracle, grows back on the rejoin, survives the flap and
    the torn files, and aborts the load barrier typed when the peer dies
    at it."""
    _chaos_env_setup(workdir, 0)
    import numpy as np
    import jax

    import dislib_tpu as ds
    from dislib_tpu.cluster import KMeans
    from dislib_tpu.parallel import mesh as _mesh
    from dislib_tpu.runtime.coord import (CapacityLedger,
                                          CoordinationTimeout,
                                          FileCoordinator, LeaseKeeper,
                                          Membership, RankDead,
                                          barrier_timeout,
                                          get_coordinator,
                                          resilient_exchange,
                                          set_membership)
    from dislib_tpu.runtime.health import ChunkGuard, HealthPolicy
    from dislib_tpu.runtime.preemption import (capacity_target,
                                               clear_capacity)
    from dislib_tpu.serving.bundle import _barrier_exchange
    from dislib_tpu.utils import profiling as _prof
    from dislib_tpu.utils.checkpoint import FitCheckpoint
    from dislib_tpu.utils.faults import TornCoordWrite

    coord = get_coordinator()
    assert isinstance(coord, FileCoordinator), type(coord).__name__
    res = {"counters": None, "timings": {}}
    x_np, kw = _chaos_fit_setup()

    # the shrunk-fleet oracle: the SAME fit, clean, on one device —
    # computed before any membership machinery so no counter is touched
    ds.init((1, 1), devices=jax.devices()[:1])
    oracle = KMeans(**kw).fit(ds.array(x_np)).centers_
    clog(0, "shrunk-fleet oracle computed on (1,1)")

    class _GateAtChunk(HealthPolicy):
        """Admit-seam gate (the NaNAtChunk idiom): chunk ``at_chunk``
        does not dispatch until ``ready()`` — deterministic phasing for
        the rejoin-mid-fit scenario, through the production guard."""

        def __init__(self, at_chunk, ready, on_arm, **hkw):
            super().__init__(**hkw)
            self.at_chunk, self.ready = int(at_chunk), ready
            self.on_arm, self.fired = on_arm, 0

        def make_guard(self, name, checkpoint=None):
            pol = self

            class _G(ChunkGuard):
                def admit(self, *carries):
                    carries = super().admit(*carries)
                    if self.chunk_index >= pol.at_chunk and not pol.fired:
                        pol.fired = 1
                        pol.on_arm()
                        _wait_for(pol.ready, 180,
                                  "capacity heal after the rejoin")
                    return carries

            return _G(name, pol, checkpoint)

    _prof.reset_counters()
    m = Membership(0, 2, devices=2)
    assert m.join() == 1
    set_membership(m)
    keeper = LeaseKeeper(m, watch=True)
    keeper.start()
    try:
        resilient_exchange(coord, "chaos-ready", 0, True, 2, timeout=120)
        clog(0, "fleet up (2 ranks, file transport) — waiting for the "
                "SIGKILL")

        # -- scenario 1: death → capacity shrink → fit on the survivors -
        t0 = time.monotonic()
        _wait_for(lambda: capacity_target() == 1, 240,
                  "death → shrunk capacity target")
        res["timings"]["death_to_capacity_s"] = time.monotonic() - t0
        r = _prof.resilience_counters()
        assert r.get("rank_deaths") == 1, r
        assert m.stats()["dead_ranks"] == [1]
        clog(0, f"rank 1 death confirmed and published "
                f"(capacity → 1, {res['timings']['death_to_capacity_s']:.2f}s "
                f"after the fleet barrier)")

        ds.init((2, 1), devices=jax.devices()[:2])
        fit1 = KMeans(**kw).fit(
            ds.array(x_np),
            checkpoint=FitCheckpoint(os.path.join(workdir, "ck1.npz"),
                                     every=2))
        assert fit1.fit_info_["mesh_shrinks"] == 1, fit1.fit_info_
        assert _mesh.mesh_shape(_mesh.get_mesh()) == (1, 1)
        np.testing.assert_allclose(fit1.centers_, oracle,
                                   rtol=1e-4, atol=1e-5)
        clog(0, "fit 1: shrank (2,1)→(1,1) mid-fit, resumed from the "
                "committed snapshot, MATCHES the shrunk-fleet oracle")

        # -- scenario 2: restart → rejoin (epoch 2) → grow back mid-fit -
        def _ask_rejoin():
            open(os.path.join(workdir, "want-rejoin"), "w").close()
            clog(0, "fit 2 gated at chunk 2 — asking the driver to "
                    "restart rank 1")

        ds.init((2, 1), devices=jax.devices()[:2])
        pol = _GateAtChunk(2, lambda: capacity_target() is None,
                           _ask_rejoin)
        fit2 = KMeans(**kw).fit(
            ds.array(x_np),
            checkpoint=FitCheckpoint(os.path.join(workdir, "ck2.npz"),
                                     every=2),
            health=pol)
        assert fit2.fit_info_["mesh_shrinks"] == 1, fit2.fit_info_
        assert fit2.fit_info_["mesh_grows"] == 1, fit2.fit_info_
        assert _mesh.mesh_shape(_mesh.get_mesh()) == (2, 1)
        np.testing.assert_allclose(fit2.centers_, oracle,
                                   rtol=1e-4, atol=1e-5)
        r = _prof.resilience_counters()
        assert r.get("rank_rejoins") == 1, r
        clog(0, "fit 2: shrank while alone, GREW BACK to (2,1) when "
                "rank 1 rejoined, matches the oracle")

        # the rejoiner runs under a bumped epoch; its pre-crash post is
        # fenced out of gathers until it re-posts under the new lease
        assert m.lease_of(1)["epoch"] == 2
        assert m.gather("fence-probe") == {}, "stale epoch-1 post leaked"
        coord.post("mark-fence-checked", 0, True)
        _wait_for(lambda: coord.peek("mark-fence-reposted", 1), 120,
                  "rank 1's re-post under epoch 2")
        assert m.gather("fence-probe") == {1: "fresh"}
        resilient_exchange(coord, "rejoin-ready", 0, True, 2, timeout=120)
        clog(0, "epoch fencing held: stale post invisible, epoch-2 "
                "re-post visible")

        # -- scenario 3: delayed heartbeats (the flap) ------------------
        coord.post("mark-flap", 0, True)
        _wait_for(lambda: (
            _prof.resilience_counters().get("rank_deaths", 0) >= 2
            and _prof.resilience_counters().get("rank_rejoins", 0) >= 2
            and capacity_target() is None), 120,
            "flap: death + rejoin with no restart")
        clog(0, "heartbeat-delay flap observed: death AND rejoin "
                "counted, capacity healed, no process restart")

        # -- scenario 4: torn files are transient -----------------------
        TornCoordWrite(coord, failures=1).post("torn-own", 0, "x")
        assert coord.peek("torn-own", 0) is None     # degraded, typed
        assert _prof.resilience_counters().get("coord_torn_reads", 0) >= 1
        ledger = CapacityLedger(os.environ["DSLIB_CAPACITY_LEDGER"])
        with open(os.environ["DSLIB_CAPACITY_LEDGER"], "wb") as f:
            f.write(b'{"torn mid-wri')     # non-atomic, unparseable
        ledger.read()                      # survives: last-coherent-wins
        # a cross-process exchange whose FIRST post is torn: the peer's
        # read retries, the clean re-post heals, both sides complete
        TornCoordWrite(coord, failures=1, name="torn-x").post(
            "torn-x", 0, {"from": 0})
        time.sleep(0.3)                    # let the peer see the tear
        votes = coord.exchange("torn-x", 0, {"from": 0}, 2, timeout=90)
        assert votes[1] == {"from": 1}, votes
        clog(0, "torn coord file + torn ledger survived as TRANSIENT "
                "(retried/healed), cross-process exchange completed")

        # -- scenario 5: dead host at the load barrier ------------------
        coord.post("mark-fits-done", 0, True)      # rank 1 self-kills
        _wait_for(lambda: capacity_target() == 1, 120,
                  "second death confirmed")
        bt = barrier_timeout()
        t0 = time.monotonic()
        try:
            _barrier_exchange(coord, "chaos-load-dead", 0, {"ok": True},
                              2, bt, "chaos.dsb.npz")
            raise AssertionError("barrier passed with a dead host")
        except CoordinationTimeout as e:
            took = time.monotonic() - t0
            assert isinstance(e, RankDead), type(e).__name__
            assert "load barrier ABORTED" in str(e)
            assert took < bt, f"attributed abort burned the deadline: " \
                              f"{took:.2f}s"
        res["timings"]["barrier_abort_attributed_s"] = took
        set_membership(None)               # and WITHOUT membership:
        t0 = time.monotonic()              # the deadline still holds
        try:
            _barrier_exchange(coord, "chaos-load-deadline", 0,
                              {"ok": True}, 2, bt, "chaos.dsb.npz")
            raise AssertionError("barrier passed with a dead host")
        except CoordinationTimeout as e:
            took = time.monotonic() - t0
            assert "load barrier ABORTED" in str(e)
            assert took <= bt + 5.0, f"deadline overrun: {took:.2f}s"
        res["timings"]["barrier_abort_deadline_s"] = took
        r = _prof.resilience_counters()
        assert r.get("bundle_barrier_abort", 0) >= 2, r
        clog(0, f"load barrier: typed abort twice (attributed "
                f"{res['timings']['barrier_abort_attributed_s']:.2f}s, "
                f"deadline {took:.2f}s vs budget {bt:.0f}s) — never a "
                f"hang")
    finally:
        set_membership(None)
        keeper.stop()
        clear_capacity()

    res["counters"] = _prof.resilience_counters()
    res["pass"] = True
    with open(os.path.join(workdir, "chaos_result.json"), "w") as f:
        json.dump(res, f, indent=1)
    clog(0, f"counters: {res['counters']}")
    clog(0, "CHAOS ALL SCENARIOS GREEN")


def chaos_rank1(workdir, phase):
    """The VICTIM.  Phase 'a': join, post a fence probe, then SIGKILL
    itself right after its first snapshot lands (a real kill, mid-fit).
    Phase 'b' (the restart): rejoin under a bumped epoch, serve the
    fencing and flap scenarios, then die again at the load barrier."""
    _chaos_env_setup(workdir, 1)
    import dislib_tpu as ds                          # noqa: F401
    import jax

    from dislib_tpu.runtime.coord import (LeaseKeeper, Membership,
                                          get_coordinator,
                                          resilient_exchange)
    from dislib_tpu.utils.faults import (CallbackCheckpoint, KillRankAt)

    coord = get_coordinator()
    m = Membership(1, 2, devices=2, heal_capacity=False)
    epoch = m.join()
    keeper = LeaseKeeper(m, watch=False)
    keeper.start()

    if phase == "a":
        assert epoch == 1, f"fresh fleet should start at epoch 1: {epoch}"
        from dislib_tpu.cluster import KMeans
        m.post("fence-probe", "stale")   # epoch-1 payload, must be fenced
        resilient_exchange(coord, "chaos-ready", 1, True, 2, timeout=120)
        clog(1, "fitting — SIGKILL lands right after snapshot 1")
        x_np, kw = _chaos_fit_setup()
        ds.init((2, 1), devices=jax.devices()[:2])
        KMeans(**kw).fit(
            ds.array(x_np),
            checkpoint=CallbackCheckpoint(
                os.path.join(workdir, "ck-victim.npz"), every=2, after=1,
                callback=KillRankAt(at_call=1)))
        clog(1, "survived my own SIGKILL — impossible")
        sys.exit(7)

    assert phase == "b", phase
    assert epoch == 2, f"rejoin must bump past the dead lease: {epoch}"
    clog(1, "rejoined under epoch 2 — heartbeating")
    _wait_for(lambda: coord.peek("mark-fence-checked", 0), 300,
              "rank 0's fence check")
    m.post("fence-probe", "fresh")       # epoch-2 re-post: visible again
    coord.post("mark-fence-reposted", 1, True)
    resilient_exchange(coord, "rejoin-ready", 1, True, 2, timeout=120)

    _wait_for(lambda: coord.peek("mark-flap", 0), 180, "flap go-signal")
    clog(1, f"flapping: heartbeats delayed {2.8 * m.lease_s:.1f}s "
            f"(lease {m.lease_s:.1f}s)")
    keeper.stop()
    time.sleep(2.8 * m.lease_s)          # the delayed-heartbeat window
    keeper = LeaseKeeper(m, watch=False)
    keeper.start()

    votes = coord.exchange("torn-x", 1, {"from": 1}, 2, timeout=90)
    assert votes[0] == {"from": 0}, votes    # healed through the tear
    clog(1, "torn-first exchange completed after the writer re-posted")

    _wait_for(lambda: coord.peek("mark-fits-done", 0), 300,
              "rank 0 done with the fits")
    clog(1, "dying at the load barrier (SIGKILL self)")
    os.kill(os.getpid(), signal.SIGKILL)


def chaos_parent(workdir=None):
    """The chaos driver: spawns the ranks, delivers the restart, bounds
    every child with a hard deadline, and prints the verdict."""
    import tempfile
    own_work = workdir is None
    if own_work:
        workdir = tempfile.mkdtemp(prefix="dslib-chaos-")
    os.makedirs(os.path.join(workdir, "coord"), exist_ok=True)
    here = os.path.abspath(__file__)
    base = dict(os.environ)
    base.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "DSLIB_COORD_DIR": os.path.join(workdir, "coord"),
        "DSLIB_CAPACITY_LEDGER": os.path.join(workdir, "cap.ledger"),
        "DSLIB_COORD_LEASE_MS": os.environ.get("DSLIB_COORD_LEASE_MS",
                                               CHAOS_LEASE_MS),
        "DSLIB_BARRIER_TIMEOUT": os.environ.get("DSLIB_BARRIER_TIMEOUT",
                                                CHAOS_BARRIER_S),
    })
    procs, logs = {}, {}

    def spawn(rank, phase):
        env = dict(base)
        env["DSLIB_PROC_ID"] = str(rank)
        name = f"r{rank}{phase}"
        logs[name] = os.path.join(workdir, f"chaos.{name}.log")
        f = open(logs[name], "w")
        procs[name] = subprocess.Popen(
            [sys.executable, here, "--chaos-rank", str(rank), phase,
             workdir],
            env=env, stdout=f, stderr=subprocess.STDOUT)
        print(f"[chaos driver] spawned {name} (pid "
              f"{procs[name].pid})", flush=True)
        return procs[name]

    def reap(name, deadline_s, want):
        try:
            rc = procs[name].wait(timeout=deadline_s)
        except subprocess.TimeoutExpired:
            procs[name].kill()
            raise AssertionError(f"HANG GUARD: {name} still running "
                                 f"after {deadline_s}s")
        assert rc == want, f"{name}: exit {rc}, wanted {want}"
        print(f"[chaos driver] {name} exited {rc} (expected)", flush=True)

    verdict = 1
    try:
        p0 = spawn(0, "x")
        spawn(1, "a")
        # phase a ends in a REAL SIGKILL delivered mid-fit
        reap("r1a", 300, -signal.SIGKILL)
        marker = os.path.join(workdir, "want-rejoin")
        _wait_for(lambda: os.path.exists(marker), 300,
                  "survivor's restart request")
        spawn(1, "b")
        reap("r1b", 600, -signal.SIGKILL)  # dies again, at the barrier
        reap("r0x", 600, 0)
        with open(os.path.join(workdir, "chaos_result.json")) as f:
            result = json.load(f)
        assert result.get("pass") is True
        print(f"[chaos driver] counters: {result['counters']}",
              flush=True)
        print(f"[chaos driver] timings: "
              f"{ {k: round(v, 2) for k, v in result['timings'].items()} }",
              flush=True)
        print("MULTIHOST CHAOS: PASS", flush=True)
        verdict = 0
    except BaseException as e:   # noqa: BLE001 — verdict + logs, typed
        print(f"[chaos driver] FAILED: {type(e).__name__}: {e}",
              flush=True)
        for name, p in procs.items():
            if p.poll() is None:
                p.kill()
        for name, path in logs.items():
            print(f"---- {name} log ----", flush=True)
            try:
                with open(path) as f:
                    print(f.read(), flush=True)
            except OSError:
                pass
        print("MULTIHOST CHAOS: FAIL", flush=True)
    finally:
        if own_work and verdict == 0:
            import shutil
            shutil.rmtree(workdir, ignore_errors=True)
    sys.exit(verdict)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--chaos":
        chaos_parent(sys.argv[2] if len(sys.argv) > 2 else None)
    elif len(sys.argv) > 1 and sys.argv[1] == "--chaos-rank":
        rank, phase, wd = int(sys.argv[2]), sys.argv[3], sys.argv[4]
        (chaos_rank0 if rank == 0 else
         lambda w, p=phase: chaos_rank1(w, p))(wd)
    else:
        main()
