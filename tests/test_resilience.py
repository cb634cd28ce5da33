"""Preemption-safe elastic runtime, end to end (SURVEY §6 "Failure
detection / elastic recovery"), driven by the deterministic fault-injection
harness (`dislib_tpu.utils.faults`):

- SIGTERM (or the `DSLIB_PREEMPTION_FILE` sentinel) mid-fit → snapshot
  written at the chunk boundary → clean `Preempted` → resume reproduces
  the uninterrupted fit;
- crash-consistent snapshots: checksum + rotation; a corrupt/truncated/
  foreign newest generation falls back to the previous one (or raises a
  CLEAR error when nothing good remains);
- elastic resume: a checkpoint written on an 8-device mesh restores onto
  a 4-device (or 2-D) mesh with identical final centers/factors;
- the `Retry` policy: transient-vs-fatal classification, deterministic
  backoff, deadline — and its wiring into the ingest loaders, the
  multi-host join, and the host↔device fetch boundary.

Every fault fires on a fixed schedule (save counts, byte positions, call
counts) — no timers, no RNG — so the suite is bit-deterministic on the
8-virtual-device CPU rig.
"""

import builtins
import os
import signal

import jax
import numpy as np
import pytest

import dislib_tpu as ds
from dislib_tpu.cluster import GaussianMixture, KMeans
from dislib_tpu.recommendation import ALS
from dislib_tpu.runtime import (Preempted, PreemptionWatcher, Retry,
                                clear_preemption, is_transient_error,
                                preemption_requested, repad_rows,
                                request_preemption, retry_call)
from dislib_tpu.utils import FitCheckpoint, faults
from dislib_tpu.utils.checkpoint import SnapshotCorrupt


@pytest.fixture(autouse=True)
def _clean_preemption(monkeypatch):
    """Every test starts and ends with the preemption flag down and no
    sentinel file configured — preemption state must never leak."""
    monkeypatch.delenv("DSLIB_PREEMPTION_FILE", raising=False)
    clear_preemption()
    yield
    clear_preemption()


@pytest.fixture
def fast_retry(monkeypatch):
    """Zero backoff so retry tests don't sleep."""
    monkeypatch.setenv("DSLIB_RETRY_BACKOFF", "0")


def _blobs(rng, n=200, d=4, k=3):
    centers = rng.rand(k, d) * 10
    x = np.vstack([centers[i] + 0.3 * rng.randn(n // k, d) for i in range(k)])
    return x.astype(np.float32)


# ---------------------------------------------------------------------------
# preemption watcher
# ---------------------------------------------------------------------------

class TestPreemptionWatcher:
    def test_sigterm_sets_flag_and_handler_restores(self):
        before = signal.getsignal(signal.SIGTERM)
        with PreemptionWatcher((signal.SIGTERM,)):
            assert not preemption_requested()
            faults.sigterm_self()
            assert preemption_requested()
        assert signal.getsignal(signal.SIGTERM) == before

    def test_sentinel_file_polls(self, tmp_path, monkeypatch):
        flag = tmp_path / "drain"
        monkeypatch.setenv("DSLIB_PREEMPTION_FILE", str(flag))
        assert not preemption_requested()
        flag.touch()
        assert preemption_requested()
        # sticky: the flag stays up even after the file goes away
        flag.unlink()
        assert preemption_requested()
        clear_preemption()
        assert not preemption_requested()

    def test_uncheckpointed_fit_ignores_preemption(self, rng):
        # nothing to snapshot → nothing to raise; the flag is only honoured
        # by checkpointed chunk loops
        request_preemption()
        x = ds.array(_blobs(rng, n=60))
        km = KMeans(n_clusters=2, random_state=0, max_iter=3).fit(x)
        assert np.isfinite(km.centers_).all()

    def test_kmeans_sigterm_snapshot_resume_equals_full(self, rng, tmp_path):
        """The acceptance path: SIGTERM mid-fit → snapshot written → clean
        Preempted → resume reproduces the uninterrupted fit."""
        x_np = _blobs(rng)
        x = ds.array(x_np)
        init = np.ascontiguousarray(x_np[[0, 70, 140]])
        full = KMeans(n_clusters=3, init=init, max_iter=12, tol=0.0).fit(x)

        path = str(tmp_path / "km.npz")
        with PreemptionWatcher((signal.SIGTERM,)):
            with pytest.raises(Preempted) as exc:
                KMeans(n_clusters=3, init=init, max_iter=12, tol=0.0).fit(
                    x, checkpoint=faults.SigtermAtNthSave(path, every=2,
                                                          after=2))
        assert exc.value.checkpoint_path == path
        assert os.path.exists(path), "Preempted raised without a snapshot"
        clear_preemption()

        res = KMeans(n_clusters=3, init=init, max_iter=12, tol=0.0).fit(
            x, checkpoint=FitCheckpoint(path, every=2))
        assert res.n_iter_ == full.n_iter_
        np.testing.assert_allclose(res.centers_, full.centers_, rtol=1e-5)

    def test_gmm_sentinel_file_snapshot_resume(self, rng, tmp_path,
                                               monkeypatch):
        x = ds.array(_blobs(rng, n=150, d=3, k=2))
        # tol=0: EM never converges early, so the preemption lands with
        # work left — deterministic across rigs
        kw = dict(n_components=2, max_iter=12, tol=0.0, random_state=0)
        full = GaussianMixture(**kw).fit(x)
        flag = tmp_path / "drain"
        monkeypatch.setenv("DSLIB_PREEMPTION_FILE", str(flag))
        path = str(tmp_path / "gm.npz")
        # every=2: six chunks.  The flag is touched by the snapshot WORKER
        # after the first save, and the loop polls it right after the next
        # chunk's dispatch: with three chunks (every=4) only the second
        # chunk's poll could raise, and a chunk that dispatches faster than
        # a snapshot is written (the blocked EM step does, once compiled)
        # polls before the flag is there.  The second save waits for the
        # first, so the third poll sees the flag whatever the timing.
        ck = faults.CallbackCheckpoint(path, every=2, after=1,
                                       callback=flag.touch)
        with pytest.raises(Preempted):
            GaussianMixture(**kw).fit(x, checkpoint=ck)
        monkeypatch.delenv("DSLIB_PREEMPTION_FILE")
        clear_preemption()
        res = GaussianMixture(**kw).fit(
            x, checkpoint=FitCheckpoint(path, every=2))
        assert res.n_iter_ == full.n_iter_
        assert res.lower_bound_ == pytest.approx(full.lower_bound_, rel=1e-4)

    def test_csvm_preempt_off_boundary_snapshots_then_resumes(self, rng,
                                                              tmp_path):
        from dislib_tpu.classification import CascadeSVM
        n = 120
        xh = np.vstack([rng.randn(n // 2, 4) - 2,
                        rng.randn(n // 2, 4) + 2]).astype(np.float32)
        yh = np.r_[np.zeros(n // 2), np.ones(n // 2)].astype(np.float32)
        sh = rng.permutation(n)
        x, y = ds.array(xh[sh]), ds.array(yh[sh].reshape(-1, 1))
        kw = dict(cascade_arity=2, c=1.0, kernel="rbf", gamma=0.3,
                  check_convergence=False)
        full = CascadeSVM(max_iter=4, **kw).fit(x, y)

        path = str(tmp_path / "csvm.npz")
        # every=10 puts NO periodic snapshot inside a 4-iteration fit — the
        # preemption path must write its own off-boundary snapshot
        request_preemption()
        with pytest.raises(Preempted):
            CascadeSVM(max_iter=4, **kw).fit(
                x, y, checkpoint=FitCheckpoint(path, every=10))
        assert os.path.exists(path)
        clear_preemption()
        res = CascadeSVM(max_iter=4, **kw).fit(
            x, y, checkpoint=FitCheckpoint(path, every=10))
        assert res.n_iter_ == full.n_iter_
        np.testing.assert_array_equal(res._sv_idx, full._sv_idx)
        np.testing.assert_allclose(res._sv_alpha, full._sv_alpha, rtol=1e-5)

    def test_forest_preempt_between_levels_resumes_identical(self, rng,
                                                             tmp_path):
        from dislib_tpu.trees import RandomForestClassifier
        n, k = 240, 3
        centers = rng.rand(k, 6) * 8
        xh = np.vstack([centers[i] + 0.4 * rng.randn(n // k, 6)
                        for i in range(k)]).astype(np.float32)
        yh = np.repeat(np.arange(k), n // k).astype(np.float32)
        p = rng.permutation(n)
        x, y = ds.array(xh[p]), ds.array(yh[p].reshape(-1, 1))
        kw = dict(n_estimators=4, max_depth=6, random_state=7)
        full = RandomForestClassifier(**kw).fit(x, y)

        path = str(tmp_path / "rf.npz")
        # snapshot every 2 levels; preemption requested right after the
        # first snapshot → raise at the NEXT level boundary, off-schedule
        ck = faults.CallbackCheckpoint(path, every=2, after=1,
                                       callback=request_preemption)
        with pytest.raises(Preempted):
            RandomForestClassifier(**kw).fit(x, y, checkpoint=ck)
        clear_preemption()
        res = RandomForestClassifier(**kw).fit(
            x, y, checkpoint=FitCheckpoint(path, every=2))
        np.testing.assert_array_equal(res.predict(x).collect(),
                                      full.predict(x).collect())


# ---------------------------------------------------------------------------
# crash-consistent snapshots: checksum, rotation, fallback
# ---------------------------------------------------------------------------

class TestSnapshotIntegrity:
    def test_rotation_keeps_last_k(self, tmp_path):
        path = str(tmp_path / "s.npz")
        ck = FitCheckpoint(path, every=1, keep=2)
        for i in range(5):
            ck.save({"gen": np.asarray([i])})
        files = sorted(os.listdir(tmp_path))
        assert files == ["s.npz", "s.npz.1"]
        assert int(ck.load()["gen"][0]) == 4
        assert int(
            np.load(path + ".1", allow_pickle=False)["gen"][0]) == 3
        ck.delete()
        assert os.listdir(tmp_path) == [] and ck.load() is None

    def test_writers_sharing_a_path_do_not_race_the_rotation(self,
                                                             tmp_path):
        """Every rank of a multi-process fit saves to the SAME path; a
        check-then-rename rotation let one writer move a generation out
        from under another (FileNotFoundError → one dead rank → its
        peers hung at the next collective).  Bounded stress: more writers
        than cores, every save must succeed and a snapshot must load."""
        import sys
        import threading
        path = str(tmp_path / "s.npz")
        errors = []

        def writer(w):
            ck = FitCheckpoint(path, every=1, keep=2)
            try:
                for i in range(40):
                    ck.save({"gen": np.asarray([w, i])})
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=writer, args=(w,))
                       for w in range(2 * (os.cpu_count() or 4))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        assert FitCheckpoint(path).load() is not None

    @pytest.mark.parametrize("mode", ["flip", "truncate", "foreign"])
    def test_corrupt_newest_falls_back_to_previous(self, tmp_path, mode):
        path = str(tmp_path / "s.npz")
        ck = FitCheckpoint(path, every=1, keep=2)
        ck.save({"gen": np.asarray([0]), "a": np.arange(64.0)})
        ck.save({"gen": np.asarray([1]), "a": np.arange(64.0) * 2})
        faults.corrupt_snapshot(path, mode=mode)
        with pytest.warns(RuntimeWarning, match="falling back"):
            state = ck.load()
        assert int(state["gen"][0]) == 0
        # the corrupt newest generation is purged on fallback, so the next
        # save can never rotate it over the good one — a crash mid-save
        # must still leave the good generation on disk
        assert not os.path.exists(path)
        ck.save({"gen": np.asarray([2])})
        assert int(np.load(path + ".1",
                           allow_pickle=False)["gen"][0]) == 0

    @pytest.mark.parametrize("mode,match", [
        ("flip", "checksum|truncated or corrupt"),
        ("truncate", "truncated or corrupt"),
        ("foreign", "integrity record"),
    ])
    def test_all_generations_bad_raises_clear_error(self, tmp_path, mode,
                                                    match):
        path = str(tmp_path / "s.npz")
        ck = FitCheckpoint(path, every=1, keep=1)
        ck.save({"a": np.arange(64.0)})
        faults.corrupt_snapshot(path, mode=mode)
        # the per-generation diagnosis is specific...
        from dislib_tpu.utils.checkpoint import _load_verified
        with pytest.raises(SnapshotCorrupt, match=match):
            _load_verified(path)
        # ...and the aggregate load() error says what to do about it
        with pytest.raises(SnapshotCorrupt, match="delete the file"):
            ck.load()

    def test_missing_newest_uses_older_generation(self, tmp_path):
        # crash window between the rotation renames: path gone, path.1 good
        path = str(tmp_path / "s.npz")
        ck = FitCheckpoint(path, every=1, keep=2)
        ck.save({"gen": np.asarray([0])})
        ck.save({"gen": np.asarray([1])})
        os.remove(path)
        assert int(ck.load()["gen"][0]) == 0

    def test_failed_save_leaks_no_staging_file(self, tmp_path, monkeypatch):
        path = str(tmp_path / "s.npz")
        ck = FitCheckpoint(path, every=1, keep=2)
        ck.save({"a": np.arange(4)})

        def boom(*a, **k):
            raise OSError(5, "injected write failure")
        monkeypatch.setattr(np, "savez", boom)
        with pytest.raises(OSError):
            ck.save({"a": np.arange(8)})
        monkeypatch.undo()
        assert sorted(os.listdir(tmp_path)) == ["s.npz"], \
            "mkstemp staging file leaked on a failed save"
        assert np.array_equal(ck.load()["a"], np.arange(4)), \
            "failed save clobbered the previous snapshot"

    def test_reserved_key_refused(self, tmp_path):
        ck = FitCheckpoint(str(tmp_path / "s.npz"))
        with pytest.raises(ValueError, match="reserved"):
            ck.save({"_dslib_crc32": np.zeros(1)})

    def test_bad_keep_refused(self, tmp_path):
        with pytest.raises(ValueError, match="keep"):
            FitCheckpoint(str(tmp_path / "s.npz"), keep=0)

    def test_kmeans_resumes_from_older_generation_after_corruption(
            self, rng, tmp_path):
        """Acceptance: corrupt newest snapshot → fallback to the previous
        generation → the resumed fit still lands on the uninterrupted
        result (it just redoes one chunk)."""
        x_np = _blobs(rng)
        x = ds.array(x_np)
        init = np.ascontiguousarray(x_np[[0, 70, 140]])
        full = KMeans(n_clusters=3, init=init, max_iter=12, tol=0.0).fit(x)

        path = str(tmp_path / "km.npz")
        KMeans(n_clusters=3, init=init, max_iter=6, tol=0.0).fit(
            x, checkpoint=FitCheckpoint(path, every=3, keep=2))
        assert os.path.exists(path) and os.path.exists(path + ".1")
        faults.corrupt_snapshot(path, mode="truncate")
        with pytest.warns(RuntimeWarning, match="falling back"):
            res = KMeans(n_clusters=3, init=init, max_iter=12, tol=0.0).fit(
                x, checkpoint=FitCheckpoint(path, every=3, keep=2))
        assert res.n_iter_ == full.n_iter_
        np.testing.assert_allclose(res.centers_, full.centers_, rtol=1e-5)


# ---------------------------------------------------------------------------
# elastic resume: restore onto a different mesh
# ---------------------------------------------------------------------------

class TestElasticResume:
    def test_repad_rows_unit(self):
        a = np.arange(12.0).reshape(6, 2)
        out = repad_rows(a, 4, 8)
        assert out.shape == (8, 2)
        np.testing.assert_array_equal(out[:4], a[:4])
        assert (out[4:] == 0).all()
        np.testing.assert_array_equal(repad_rows(a, 6, 6), a)
        out = repad_rows(a.T, 4, 5, axis=1)
        assert out.shape == (2, 5) and (out[:, 4:] == 0).all()
        with pytest.raises(ValueError, match="stale or foreign"):
            repad_rows(a, 10, 12)
        with pytest.raises(ValueError, match="smaller than the logical"):
            repad_rows(a, 4, 2)

    def test_kmeans_8dev_checkpoint_resumes_on_4dev(self, rng, tmp_path):
        from conftest import skip_unless_devices
        skip_unless_devices(8)
        devs = jax.devices()
        x_np = _blobs(rng)
        init = np.ascontiguousarray(x_np[[0, 70, 140]])

        ds.init((8, 1), devices=devs[:8])
        x8 = ds.array(x_np)
        full = KMeans(n_clusters=3, init=init, max_iter=12, tol=0.0).fit(x8)
        path = str(tmp_path / "km.npz")
        KMeans(n_clusters=3, init=init, max_iter=6, tol=0.0).fit(
            x8, checkpoint=FitCheckpoint(path, every=3))

        ds.init((4, 1), devices=devs[:4])       # half the fleet survives
        x4 = ds.array(x_np)
        res = KMeans(n_clusters=3, init=init, max_iter=12, tol=0.0).fit(
            x4, checkpoint=FitCheckpoint(path, every=3))
        assert res.n_iter_ == full.n_iter_
        np.testing.assert_allclose(res.centers_, full.centers_,
                                   rtol=1e-4, atol=1e-5)

    def test_als_8dev_checkpoint_resumes_on_2x2(self, rng, tmp_path):
        """Dense ALS stores mesh-PADDED factors — the elastic path re-pads
        them for the restoring mesh (8×1 quantum 8 → 2×2 quantum 2)."""
        from conftest import skip_unless_devices
        skip_unless_devices(8)
        devs = jax.devices()
        u = rng.rand(30, 4).astype(np.float32)
        v = rng.rand(20, 4).astype(np.float32)
        r = ((u @ v.T) * (rng.rand(30, 20) < 0.6)).astype(np.float32)

        ds.init((8, 1), devices=devs[:8])
        x8 = ds.array(r)
        full = ALS(n_f=4, max_iter=20, tol=1e-7, random_state=0).fit(x8)
        path = str(tmp_path / "als.npz")
        ALS(n_f=4, max_iter=6, tol=1e-7, random_state=0).fit(
            x8, checkpoint=FitCheckpoint(path, every=3))

        ds.init((2, 2), devices=devs[:4])       # different COUNT and SHAPE
        x4 = ds.array(r)
        res = ALS(n_f=4, max_iter=20, tol=1e-7, random_state=0).fit(
            x4, checkpoint=FitCheckpoint(path, every=3))
        assert res.rmse_ == pytest.approx(full.rmse_, abs=1e-4)
        np.testing.assert_allclose(res.users_, full.users_,
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(res.items_, full.items_,
                                   rtol=1e-3, atol=1e-4)

    def test_forest_8dev_checkpoint_resumes_on_4dev(self, rng, tmp_path):
        from conftest import skip_unless_devices
        skip_unless_devices(8)
        from dislib_tpu.trees import RandomForestClassifier
        devs = jax.devices()
        n, k = 240, 3
        centers = rng.rand(k, 6) * 8
        xh = np.vstack([centers[i] + 0.4 * rng.randn(n // k, 6)
                        for i in range(k)]).astype(np.float32)
        yh = np.repeat(np.arange(k), n // k).astype(np.float32).reshape(-1, 1)
        kw = dict(n_estimators=4, max_depth=6, random_state=7)

        ds.init((8, 1), devices=devs[:8])
        x8, y8 = ds.array(xh), ds.array(yh)
        full = RandomForestClassifier(**kw).fit(x8, y8)
        path = str(tmp_path / "rf.npz")
        ck = faults.CallbackCheckpoint(path, every=2, after=1,
                                       callback=request_preemption)
        with pytest.raises(Preempted):
            RandomForestClassifier(**kw).fit(x8, y8, checkpoint=ck)
        clear_preemption()

        ds.init((4, 1), devices=devs[:4])
        x4, y4 = ds.array(xh), ds.array(yh)
        res = RandomForestClassifier(**kw).fit(
            x4, y4, checkpoint=FitCheckpoint(path, every=2))
        np.testing.assert_array_equal(res.predict(x4).collect(),
                                      full.predict(x8).collect())

    def test_als_stale_snapshot_still_refused(self, rng, tmp_path):
        x = ds.array((rng.rand(30, 20) * (rng.rand(30, 20) < 0.6))
                     .astype(np.float32))
        path = str(tmp_path / "als.npz")
        ALS(n_f=4, max_iter=4, random_state=0).fit(
            x, checkpoint=FitCheckpoint(path, every=2))
        other = ds.array((rng.rand(24, 20) * (rng.rand(24, 20) < 0.6))
                         .astype(np.float32))
        with pytest.raises(ValueError, match="stale or foreign"):
            ALS(n_f=4, max_iter=4, random_state=0).fit(
                other, checkpoint=FitCheckpoint(path, every=2))
        with pytest.raises(ValueError, match="stale or foreign"):
            ALS(n_f=8, max_iter=4, random_state=0).fit(
                x, checkpoint=FitCheckpoint(path, every=2))


# ---------------------------------------------------------------------------
# the Retry policy
# ---------------------------------------------------------------------------

class TestRetryPolicy:
    def test_transient_retries_then_succeeds(self):
        flaky = faults.FlakyCall(lambda: 42, failures=2)
        assert Retry(attempts=5, backoff=0, jitter=0).call(flaky) == 42
        assert flaky.calls == 3

    def test_fatal_not_retried(self):
        flaky = faults.FlakyCall(lambda: 42, failures=3,
                                 exc_factory=lambda: ValueError("bad shape"))
        with pytest.raises(ValueError):
            Retry(attempts=5, backoff=0).call(flaky)
        assert flaky.calls == 1

    def test_attempts_exhausted_reraises_last(self):
        flaky = faults.FlakyCall(lambda: 42, failures=10)
        with pytest.raises(ConnectionResetError):
            Retry(attempts=3, backoff=0).call(flaky)
        assert flaky.calls == 3

    def test_backoff_schedule_deterministic(self):
        delays = []

        def run(seed):
            delays.clear()
            flaky = faults.FlakyCall(lambda: 0, failures=3)
            Retry(attempts=4, backoff=0.5, jitter=0.25, seed=seed,
                  sleep=delays.append).call(flaky)
            return list(delays)
        a, b = run(7), run(7)
        assert a == b and len(a) == 3, "seeded jitter must be reproducible"
        # exponential base under the jitter envelope
        assert 0.5 <= a[0] <= 0.625 and 1.0 <= a[1] <= 1.25 \
            and 2.0 <= a[2] <= 2.5
        assert run(8) != a, "different seed, different jitter"

    def test_deadline_stops_retrying(self):
        slept = []
        flaky = faults.FlakyCall(lambda: 0, failures=10)
        with pytest.raises(ConnectionResetError):
            Retry(attempts=10, backoff=10.0, jitter=0, deadline=5.0,
                  sleep=slept.append).call(flaky)
        assert flaky.calls == 1 and slept == [], \
            "a sleep that would overrun the deadline must not happen"

    def test_classifier_override(self):
        flaky = faults.FlakyCall(lambda: 42, failures=1,
                                 exc_factory=lambda: ValueError("flaky"))
        got = Retry(attempts=3, backoff=0,
                    classify=lambda e: isinstance(e, ValueError)).call(flaky)
        assert got == 42 and flaky.calls == 2

    def test_default_classification(self):
        assert is_transient_error(
            RuntimeError("UNAVAILABLE: failed to connect to all addresses"))
        assert is_transient_error(RuntimeError("Deadline Exceeded"))
        assert is_transient_error(OSError(5, "I/O error"))
        assert is_transient_error(ConnectionResetError())
        assert not is_transient_error(FileNotFoundError("gone"))
        assert not is_transient_error(ValueError("shape mismatch"))
        assert not is_transient_error(RuntimeError("singular matrix"))
        assert not is_transient_error(Preempted("draining"))
        assert not is_transient_error(KeyboardInterrupt())

    def test_from_env_overrides(self, monkeypatch):
        monkeypatch.setenv("DSLIB_RETRY_ATTEMPTS", "7")
        monkeypatch.setenv("DSLIB_RETRY_BACKOFF", "0.125")
        monkeypatch.setenv("DSLIB_RETRY_DEADLINE", "9.5")
        r = Retry.from_env(attempts=2)
        assert r.attempts == 7 and r.backoff == 0.125 and r.deadline == 9.5

    def test_retry_call_convenience(self, fast_retry):
        flaky = faults.FlakyCall(lambda: "ok", failures=1)
        assert retry_call(flaky) == "ok"
        assert flaky.calls == 2

    def test_bad_attempts(self):
        with pytest.raises(ValueError):
            Retry(attempts=0)


# ---------------------------------------------------------------------------
# Retry wiring: ingest IO, multi-host join, host↔device fetch
# ---------------------------------------------------------------------------

class TestRetryWiring:
    def test_load_txt_survives_flaky_reads(self, rng, tmp_path, monkeypatch,
                                           fast_retry):
        x = rng.rand(16, 3).astype(np.float32)
        p = str(tmp_path / "a.csv")
        np.savetxt(p, x, delimiter=",")
        flaky = faults.FlakyOpen(p, failures=2)
        monkeypatch.setattr(builtins, "open", flaky)
        got = ds.load_txt_file(p)
        assert flaky.fails == 2
        np.testing.assert_allclose(np.asarray(got.collect()), x, rtol=1e-5)

    def test_load_txt_persistent_failure_raises(self, rng, tmp_path,
                                                monkeypatch, fast_retry):
        p = str(tmp_path / "a.csv")
        np.savetxt(p, rng.rand(4, 2), delimiter=",")
        flaky = faults.FlakyOpen(p, failures=100)
        monkeypatch.setattr(builtins, "open", flaky)
        with pytest.raises(OSError, match="injected flaky read"):
            ds.load_txt_file(p)
        assert flaky.fails == 3, "default IO policy is 3 attempts"

    def test_load_missing_file_fails_fast(self, tmp_path, fast_retry):
        # FileNotFoundError is fatal — one attempt, no backoff burned
        with pytest.raises(FileNotFoundError):
            ds.load_npy_file(str(tmp_path / "nope.npy"))

    def test_distributed_initialize_retries_coordinator(self, monkeypatch,
                                                        fast_retry):
        from dislib_tpu.parallel import distributed
        flaky = faults.FlakyCall(
            lambda **kw: None, failures=2,
            exc_factory=lambda: RuntimeError(
                "UNAVAILABLE: failed to connect to all addresses"))
        monkeypatch.setattr(jax.distributed, "initialize", flaky)
        monkeypatch.setattr(distributed, "_initialized", False)
        distributed.initialize(coordinator_address="127.0.0.1:1",
                               num_processes=1, process_id=0)
        assert flaky.calls == 3
        assert distributed.is_initialized()

    def test_distributed_initialize_fatal_config_error(self, monkeypatch,
                                                       fast_retry):
        from dislib_tpu.parallel import distributed
        flaky = faults.FlakyCall(
            lambda **kw: None, failures=5,
            exc_factory=lambda: ValueError("process_id must be set"))
        monkeypatch.setattr(jax.distributed, "initialize", flaky)
        monkeypatch.setattr(distributed, "_initialized", False)
        with pytest.raises(ValueError):
            distributed.initialize(coordinator_address="127.0.0.1:1",
                                   num_processes=2, process_id=0)
        assert flaky.calls == 1 and not distributed.is_initialized()

    def test_fetch_retries_device_get(self, monkeypatch, fast_retry):
        from dislib_tpu import runtime
        real = jax.device_get
        flaky = faults.FlakyCall(real, failures=1)
        monkeypatch.setattr(jax, "device_get", flaky)
        out = runtime.fetch(np.arange(3.0))
        np.testing.assert_array_equal(out, np.arange(3.0))
        assert flaky.calls == 2
