"""PredictServer — request micro-batching with a latency deadline.

Requests arrive one at a time (a row, or a small row block) but the
hardware wants batches: a single fused dispatch over 512 rows costs
barely more than over 1 (the fixed per-dispatch cost dominates small
batches).  The server queues submissions and flushes a batch when EITHER

- the queued rows fill the largest bucket (throughput bound), OR
- the OLDEST queued request has waited ``deadline_ms``
  (``DSLIB_SERVE_DEADLINE_MS``, default 5) — the latency bound.

A flush coalesces whole requests into the smallest covering bucket (a
request's rows never split across batches; an oversize request is
chunked internally at largest-bucket granularity) and runs ONE fused
dispatch.  Between batches the server polls its :class:`ModelPool` (when
serving one) so generation hot-swaps happen at batch boundaries — a
response is always computed entirely by one generation, never torn
across two.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from dislib_tpu.serving.buckets import bucket_for, bucket_ladder, split_rows
from dislib_tpu.serving.cache import ProgramCache
from dislib_tpu.utils import profiling as _prof

_LATENCY_WINDOW = 8192      # completions kept for the p50/p95/p99 estimate


def _default_deadline_s() -> float:
    return float(os.environ.get("DSLIB_SERVE_DEADLINE_MS", "5")) / 1e3


class QueueFull(RuntimeError):
    """Backpressure, typed (round 15): the server's queue already holds
    ``max_queue_rows`` rows — the request rate is outrunning the device
    and THIS submission was shed (the queue never grows until the
    process OOMs).  Subclasses ``RuntimeError`` so pre-round-15 callers
    matching that still catch it; carries the ``tenant`` whose request
    was shed so a router's admission layer can attribute the rejection."""

    def __init__(self, message, tenant=None):
        super().__init__(message)
        self.tenant = tenant


class ShardDrained(RuntimeError):
    """This server is part of a sharded fleet and a peer host's lease
    EXPIRED (round 20): until the fleet heals, responses assembled here
    would silently miss the dead host's shard of the model — torn
    results.  The server DRAINS instead: queued and new requests fail
    with this typed error (carrying the dead ``rank`` and ``last_seen``)
    so the caller's load balancer re-routes, and serving resumes
    automatically when the peer's lease is renewed or a restart rejoins."""

    def __init__(self, message, rank=None, last_seen=None):
        super().__init__(message)
        self.rank = rank
        self.last_seen = last_seen


class ServeResponse:
    """One request's result: ``values`` (n_rows, out_cols ndarray), the
    ``generation`` token that computed it (None for a static pipeline),
    and the request's ``latency_s`` (submit → response)."""

    __slots__ = ("values", "generation", "latency_s")

    def __init__(self, values, generation, latency_s):
        self.values = values
        self.generation = generation
        self.latency_s = latency_s

    def __repr__(self):
        return (f"ServeResponse(shape={self.values.shape}, "
                f"generation={self.generation!r}, "
                f"latency_ms={1e3 * self.latency_s:.3f})")


class _Pending:
    __slots__ = ("rows", "future", "t_submit", "tenant")

    def __init__(self, rows, tenant=None):
        self.rows = rows
        self.future = Future()
        self.t_submit = time.perf_counter()
        self.tenant = tenant


class PredictServer:
    """Micro-batching front of a :class:`ServePipeline` or
    :class:`ModelPool`.

    Use as a context manager (``with PredictServer(...) as srv``) or call
    :meth:`start`/:meth:`stop`.  ``submit`` returns a
    ``concurrent.futures.Future`` resolving to :class:`ServeResponse`;
    ``predict`` is the blocking convenience returning just the values.
    """

    def __init__(self, pipeline=None, pool=None, buckets=None,
                 deadline_ms=None, max_queue_rows=65536, name="serve",
                 elastic=None, capacity_poll_s=0.25, grow_attempts=8,
                 membership=None):
        if (pipeline is None) == (pool is None):
            raise ValueError("pass exactly one of pipeline= or pool=")
        if elastic is not None and not callable(elastic):
            if elastic and pipeline is not None and \
                    hasattr(pipeline, "rebind_mesh"):
                # elastic=True on a pipeline that owns its re-layout
                # (round 20: RetrievalPipeline/IVFIndex): the default
                # hook delegates to it — same pipeline object, re-laid
                elastic = (lambda mesh, _p=pipeline:
                           (_p.rebind_mesh(mesh), None)[1])
            else:
                elastic = (lambda mesh: None) if elastic else None
        if elastic is not None and pipeline is None:
            raise ValueError(
                "elastic= serving needs pipeline mode — a ModelPool's "
                "generations re-warm through adoption, not a rebind hook")
        self._pipeline = pipeline
        self._pool = pool
        # elastic capacity re-layout (round 19, ROADMAP 3(c)): between
        # batches the worker polls the capacity level (process override /
        # DSLIB_CAPACITY_FILE / the fleet-wide DSLIB_CAPACITY_LEDGER) and
        # re-forms the serving mesh over the home-device prefix exactly
        # as the fit loop's elastic tier does — hook(None) pre-switch,
        # mesh re-init, cache drop, hook(new_mesh) post-switch.  The hook
        # may return a REPLACEMENT pipeline (its model re-laid-out for
        # the new mesh via the rechunk schedules); the server re-warms
        # the bucket ladder before the next batch so the request hot
        # path never compiles.  The hook is optional: ``elastic=True``
        # (normalized above, before the pool-mode check) enables the
        # re-layout with the default rebind — re-warm the same pipeline
        # on the new mesh; a non-callable must never reach the worker
        # thread, where a TypeError would kill serving and strand every
        # queued future.
        self._elastic = elastic
        self.capacity_poll_s = float(capacity_poll_s)
        self._grows_left = int(grow_attempts)
        self._cap_shrunk = False        # a CAPACITY shrink is below home
        self._home_shape = None
        self._home_devices = None
        self._last_cap_poll = None
        self._mesh_resizes = 0
        # dead-shard drain (round 20): when this server fronts one shard
        # of a fleet, `membership=` (a runtime.coord.Membership) makes
        # the worker poll the peers' leases on the same cadence as
        # capacity — a confirmed-dead peer DRAINS this server (queued +
        # new requests fail typed ShardDrained, never torn fleet
        # results), a renewed lease or a rejoin resumes it
        self._membership = membership
        self._drained_rank = None       # (rank, last_seen) while draining
        self._shard_drains = 0
        if pool is not None:
            # the served ladder must be ⊆ the pool's warmed+health-gated
            # ladder: routing a request to a bucket adoption never warmed
            # would pay a trace+compile on the hot path AND run a shape
            # the health gate never validated
            self.buckets = pool.buckets if buckets is None \
                else bucket_ladder(buckets)
            extra = set(self.buckets) - set(pool.buckets)
            if extra:
                raise ValueError(
                    f"server buckets {sorted(extra)} are not in the "
                    f"pool's warmed ladder {pool.buckets} — every served "
                    "bucket must be AOT-warmed and health-gated at "
                    "adoption")
        else:
            self.buckets = bucket_ladder(buckets)
        self.deadline_s = _default_deadline_s() if deadline_ms is None \
            else float(deadline_ms) / 1e3
        self.name = name
        self.max_queue_rows = int(max_queue_rows)
        self.cache = pool.cache if pool is not None else ProgramCache()
        self._cv = threading.Condition()
        self._queue: deque[_Pending] = deque()
        self._queued_rows = 0               # backpressure accounting
        self._running = False
        self._thread: threading.Thread | None = None
        # accounting
        self._lat = deque(maxlen=_LATENCY_WINDOW)
        self._batches = 0
        self._requests = 0
        self._rows = 0
        self._dispatch_hist: deque[int] = deque(maxlen=_LATENCY_WINDOW)
        self._t_first = None
        self._t_last = None
        # per-tenant observability (round 15): latency windows, request
        # tallies, and shed counts keyed by the submit() tenant label —
        # the router and any load test read THESE numbers rather than
        # timing around the server
        self._shed = 0
        self._tenant_lat: dict[str, deque] = {}
        self._tenant_requests: dict[str, int] = {}
        self._tenant_shed: dict[str, int] = {}
        # per-bucket wall-clock cost model (round 18): measured
        # predict_bucket walls keyed by bucket, learned from the server's
        # own serving — the admission layer (ModelRouter deadline shed)
        # reads predict_latency() instead of guessing
        self._bucket_wall: dict[int, deque] = {}

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "PredictServer":
        if self._running:
            return self
        if self._elastic is not None:
            from dislib_tpu.parallel import mesh as _mesh
            m = _mesh.get_mesh()
            self._home_shape = _mesh.mesh_shape(m)
            self._home_devices = list(m.devices.flat)
        if self._pipeline is not None:
            # static pipeline: AOT-warm every bucket up front so the
            # request path never compiles (a ModelPool warms at adoption)
            self.cache.warm(self._pipeline, None, self.buckets)
        else:
            self._pool.poll(force=True)
        self._running = True
        self._thread = threading.Thread(target=self._worker,
                                        name=f"dslib-{self.name}",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Drain the queue (every accepted request gets a response), then
        stop the worker."""
        if not self._running:
            return
        with self._cv:
            self._running = False
            self._cv.notify_all()
        self._thread.join()
        self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- request side --------------------------------------------------------

    def submit(self, rows, tenant=None) -> Future:
        """Queue one request (a (k, n_features) block or a single (n,)
        row); the Future resolves to a :class:`ServeResponse`.  Raises
        :class:`QueueFull` when the queue already holds
        ``max_queue_rows`` rows — backpressure: a client outrunning the
        device must hear about it instead of growing the queue until the
        process OOMs.  ``tenant`` labels the request for the per-tenant
        latency/shed accounting in :meth:`stats` (a
        :class:`~dislib_tpu.serving.router.ModelRouter` sets it)."""
        rows = np.asarray(rows, np.float32)
        if rows.ndim == 1:
            rows = rows.reshape(1, -1)
        if rows.ndim != 2 or rows.shape[0] < 1:
            raise ValueError(f"a request is a (k, n_features) row block, "
                             f"got shape {rows.shape}")
        p = _Pending(rows, tenant)
        with self._cv:
            if not self._running:
                raise RuntimeError("PredictServer is not running — use "
                                   "start() or a with-block")
            if self._drained_rank is not None:
                r, seen = self._drained_rank
                raise ShardDrained(
                    f"{self.name}: draining — fleet peer rank {r} is "
                    f"dead (lease expired, last heartbeat {seen:.3f}); "
                    "a response computed now would be missing its shard",
                    rank=r, last_seen=seen)
            if self._queued_rows + rows.shape[0] > self.max_queue_rows:
                self._shed += 1
                if tenant is not None:
                    self._tenant_shed[tenant] = \
                        self._tenant_shed.get(tenant, 0) + 1
                raise QueueFull(
                    f"{self.name}: queue full ({self._queued_rows} rows "
                    f"queued, max_queue_rows={self.max_queue_rows}) — "
                    "the request rate is outrunning the device; back off "
                    "and retry", tenant=tenant)
            self._queued_rows += rows.shape[0]
            self._queue.append(p)
            self._cv.notify_all()
        return p.future

    def predict(self, rows, tenant=None) -> np.ndarray:
        return self.submit(rows, tenant=tenant).result().values

    # -- worker side ---------------------------------------------------------

    def _poll_membership(self):
        """Between batches: convert peer-lease state into the drain
        level.  ``membership.poll()`` also publishes the death→capacity
        statement, so a dead peer both drains THIS shard and shrinks the
        fleet's fit capacity through one observation."""
        if self._membership is None:
            return
        try:
            self._membership.poll()
            dead = self._membership.dead()
        except Exception:               # noqa: BLE001 — poll never kills serving
            return
        if dead and self._drained_rank is None:
            r, last_seen, _epoch = dead[0]
            stranded = []
            with self._cv:
                self._drained_rank = (r, last_seen)
                self._shard_drains += 1
                stranded = list(self._queue)
                self._queue.clear()
                self._queued_rows = 0
            _prof.count_resilience("serve_shard_drains")
            err = ShardDrained(
                f"{self.name}: fleet peer rank {r} died mid-serve "
                f"(lease expired, last heartbeat {last_seen:.3f}) — "
                "draining this shard instead of serving torn results",
                rank=r, last_seen=last_seen)
            for p in stranded:
                if p.future.set_running_or_notify_cancel():
                    p.future.set_exception(err)
        elif not dead and self._drained_rank is not None:
            with self._cv:
                self._drained_rank = None

    def _worker(self):
        top = self.buckets[-1]
        while True:
            self._maybe_resize()        # between batches, never mid-batch
            self._poll_membership()
            with self._cv:
                while self._running and not self._queue:
                    self._cv.wait(timeout=0.1)
                    if self._elastic is not None or \
                            self._membership is not None:
                        break   # idle: re-poll capacity / peer leases
                if not self._queue:
                    if not self._running:
                        return
                    continue
                # deadline window: wait for more work until the OLDEST
                # request's deadline, or until the largest bucket fills
                flush_at = self._queue[0].t_submit + self.deadline_s
                while self._running:
                    left = flush_at - time.perf_counter()
                    if self._queued_rows >= top or left <= 0:
                        break
                    self._cv.wait(timeout=left)
                # assemble: whole requests, smallest covering bucket
                batch = [self._queue.popleft()]
                total = batch[0].rows.shape[0]
                while self._queue and \
                        total + self._queue[0].rows.shape[0] <= top:
                    p = self._queue.popleft()
                    total += p.rows.shape[0]
                    batch.append(p)
                self._queued_rows -= total
            self._execute(batch, total)

    def _capacity_plan(self):
        """The fit loop's ``_capacity_plan`` rule, applied to the serving
        mesh: compare the published capacity level against the current
        rows and return ``("shrink"|"grow", new_rows)`` or None.  The
        mesh stays a halving-reachable row prefix of the HOME mesh;
        shrinks always honour the target, grows spend ``grow_attempts``
        budget so a flapping source cannot thrash resizes forever."""
        from dislib_tpu.parallel import mesh as _mesh
        from dislib_tpu.runtime.preemption import capacity_target
        cap = capacity_target()
        if cap is None:
            # pressure lifted (the round-20 rejoin heal CLEARS the target
            # rather than publishing a bigger level): a capacity-shrunk
            # server heads home through the same grow rungs, same budget
            if not self._cap_shrunk:
                return None
            cap = self._home_shape[0] * self._home_shape[1]
        r, c = _mesh.mesh_shape(_mesh.get_mesh())
        home_r, home_c = self._home_shape
        cap = max(c, min(int(cap), home_r * home_c))
        want = cap // c                 # usable full rows at this level
        if want < r:
            new_r = r
            while new_r > 1 and new_r > want:
                new_r //= 2
            return ("shrink", new_r) if new_r < r else None
        if want > r and r < home_r and self._grows_left > 0:
            new_r = r
            while new_r * 2 <= min(want, home_r):
                new_r *= 2
            if new_r > r:
                return ("grow", new_r)
        return None

    def _maybe_resize(self):
        """Worker-side capacity poll (throttled): re-form the serving
        mesh when the level moved, at a BATCH BOUNDARY — a response is
        always computed entirely on one mesh, never torn across two.
        Mirrors ``ChunkedFitLoop._resize_mesh``: hook(None) forces
        anything pending under the old mesh, the mesh re-forms over the
        home-device prefix, jit caches drop (stale sharding constraints
        must not replay), and the hook sees the new mesh — returning a
        replacement pipeline re-laid-out for it, which is re-warmed so
        the hot path stays compile-free."""
        if self._elastic is None:
            return
        now = time.perf_counter()
        if self._last_cap_poll is not None and \
                now - self._last_cap_poll < self.capacity_poll_s:
            return
        self._last_cap_poll = now
        plan = self._capacity_plan()
        if plan is None:
            return
        kind, new_r = plan
        import jax

        from dislib_tpu.parallel import mesh as _mesh
        if kind == "grow":
            self._grows_left -= 1
        self._elastic(None)             # pre-switch: force pending chains
        _, c = self._home_shape
        _mesh.init((new_r, c), devices=self._home_devices[: new_r * c])
        jax.clear_caches()
        self._cap_shrunk = new_r < self._home_shape[0]
        _prof.count_resilience("serve_mesh_shrinks" if kind == "shrink"
                               else "serve_mesh_grows")
        new_pipe = self._elastic(_mesh.get_mesh())
        if new_pipe is not None:
            self._pipeline = new_pipe
        # caches were dropped with the old mesh: re-warm the ladder so
        # the next batch is a cached dispatch, not a compile
        self.cache.warm(self._pipeline, None, self.buckets)
        with self._cv:
            self._mesh_resizes += 1

    def _serving(self):
        """(generation, pipeline) for the next batch — polls the pool so
        hot-swaps land at batch boundaries.  Before the FIRST adoption
        the worker waits briefly instead of failing the batch: another
        poller may hold the pool's adoption lock mid-warm (poll() yields
        to it), or the trainer may be a moment away from its first
        save."""
        if self._pool is None:
            return None, self._pipeline
        deadline = time.perf_counter() + 2.0
        while True:
            self._pool.poll()
            gen, pipe = self._pool.current()
            if pipe is not None:
                return gen, pipe
            # never expire while an adoption is actually in flight on
            # another thread: its warm phase AOT-compiles the whole
            # bucket ladder, which routinely outlives any fixed deadline
            # (first compile on a real chip is tens of seconds)
            if time.perf_counter() >= deadline and not self._pool.adopting:
                raise RuntimeError(
                    f"{self.name}: no model generation has been adopted "
                    "yet (is the checkpoint path empty?)")
            self._pool.poll(force=True)
            time.sleep(0.01)

    def _execute(self, batch, total):
        try:
            gen, pipe = self._serving()
        except Exception as e:  # noqa: BLE001 — no model: fail the batch
            for p in batch:
                if p.future.set_running_or_notify_cancel():
                    p.future.set_exception(e)
            return
        # per-request validation BEFORE the fused dispatch: one malformed
        # request must fail ITS future, not poison the whole batch
        good = []
        for p in batch:
            if p.rows.shape[1] != pipe.n_features:
                if p.future.set_running_or_notify_cancel():
                    p.future.set_exception(ValueError(
                        f"request has {p.rows.shape[1]} features, "
                        f"pipeline serves {pipe.n_features}"))
            else:
                good.append(p)
        if not good:
            return
        batch = good
        total = sum(p.rows.shape[0] for p in batch)
        try:
            rows = batch[0].rows if len(batch) == 1 else \
                np.concatenate([p.rows for p in batch], axis=0)
            pieces = []
            walls = []
            d0 = _prof.dispatch_count()
            for size in split_rows(total, self.buckets):
                bucket = bucket_for(size, self.buckets)
                t_piece = time.perf_counter()
                pieces.append(pipe.predict_bucket(rows[:size], bucket))
                walls.append((bucket, time.perf_counter() - t_piece))
                self.cache.record_hit(gen, bucket)
                rows = rows[size:]
            dispatches = _prof.dispatch_count() - d0
            out = pieces[0] if len(pieces) == 1 else \
                np.concatenate(pieces, axis=0)
        except Exception as e:  # noqa: BLE001 — fail the batch, keep serving
            for p in batch:
                if not p.future.set_running_or_notify_cancel():
                    continue
                p.future.set_exception(e)
            return
        t_done = time.perf_counter()
        # accounting mutates under the condition lock so a monitoring
        # thread's stats() snapshot never iterates a deque mid-append
        with self._cv:
            self._batches += 1
            self._dispatch_hist.append(dispatches)
            for bucket, wall in walls:
                self._bucket_wall.setdefault(
                    bucket, deque(maxlen=512)).append(wall)
            if self._t_first is None:
                self._t_first = t_done
            self._t_last = t_done
            lats = []
            for p in batch:
                lat = t_done - p.t_submit
                lats.append(lat)
                self._lat.append(lat)
                self._requests += 1
                self._rows += p.rows.shape[0]
                if p.tenant is not None:
                    self._tenant_lat.setdefault(
                        p.tenant,
                        deque(maxlen=_LATENCY_WINDOW)).append(lat)
                    self._tenant_requests[p.tenant] = \
                        self._tenant_requests.get(p.tenant, 0) + 1
        off = 0
        for p, lat in zip(batch, lats):
            k = p.rows.shape[0]
            if p.future.set_running_or_notify_cancel():
                p.future.set_result(
                    ServeResponse(out[off:off + k].copy(), gen, lat))
            off += k

    # -- cost model ----------------------------------------------------------

    def bucket_cost(self) -> dict:
        """The learned per-bucket cost model: ``{bucket: p95 wall
        seconds}`` over the measured ``predict_bucket`` walls of this
        server's own serving.  A bucket appears once it has ≥ 3 samples
        — before that the model declines to predict (None from
        :meth:`predict_latency`) rather than shed on a guess."""
        with self._cv:
            snap = {b: np.asarray(d, np.float64)
                    for b, d in self._bucket_wall.items()}
        return {b: float(np.percentile(w, 95))
                for b, w in sorted(snap.items()) if w.size >= 3}

    def predict_latency(self, n_rows: int) -> float | None:
        """Predicted submit→response seconds for an ``n_rows`` request
        arriving NOW: the deadline window the batcher may hold it, plus
        the predicted execute walls of the rows already queued ahead of
        it, plus its own bucket pieces — all read from the learned
        :meth:`bucket_cost` model.  Returns None when any needed bucket
        has no model yet (an admission layer must not shed on
        ignorance)."""
        costs = self.bucket_cost()
        with self._cv:
            backlog = self._queued_rows
        predicted = self.deadline_s

        def _pieces_cost(total: int) -> float | None:
            acc = 0.0
            for size in split_rows(int(total), self.buckets):
                c = costs.get(bucket_for(size, self.buckets))
                if c is None:
                    return None
                acc += c
            return acc

        for total in (backlog, int(n_rows)):
            if total:
                c = _pieces_cost(total)
                if c is None:
                    return None
                predicted += c
        return predicted

    # -- accounting ----------------------------------------------------------

    @staticmethod
    def _percentiles(lat: np.ndarray) -> dict:
        """p50/p95/p99 (ms) over one latency window, None when empty."""
        if not lat.size:
            return {"p50_ms": None, "p95_ms": None, "p99_ms": None}
        return {f"p{q}_ms": round(1e3 * float(np.percentile(lat, q)), 4)
                for q in (50, 95, 99)}

    def stats(self) -> dict:
        """Serving counters: request latency percentiles (p50/p95/p99
        ms, overall AND per tenant under ``tenants``), QPS over the
        completion window, rows/batches served, ``shed`` (submissions
        rejected by backpressure — total, and per tenant), and the
        per-batch dispatch distribution (the 1-dispatch-per-batch
        invariant as a number; oversize split requests legitimately cost
        one dispatch per piece).  A load test reads its headline
        numbers from here — the server is its own observability source.
        Dispatch deltas read the process-wide profiling counters —
        concurrent non-serving device work in the same process would
        inflate them."""
        with self._cv:                      # consistent snapshot vs the
            lat = np.asarray(self._lat)     # worker's accounting block
            disp = np.asarray(self._dispatch_hist, np.int64)
            t_first, t_last = self._t_first, self._t_last
            requests, rows = self._requests, self._rows
            batches, depth = self._batches, len(self._queue)
            queued_rows = self._queued_rows
            shed = self._shed
            tenant_lat = {t: np.asarray(d, np.float64)
                          for t, d in self._tenant_lat.items()}
            tenant_requests = dict(self._tenant_requests)
            tenant_shed = dict(self._tenant_shed)
        lat = lat.astype(np.float64)
        window = (t_last - t_first) \
            if t_first is not None and t_last > t_first else None
        tenants = {}
        for t in sorted(set(tenant_lat) | set(tenant_shed)):
            tenants[t] = {"requests": tenant_requests.get(t, 0),
                          "shed": tenant_shed.get(t, 0),
                          **self._percentiles(
                              tenant_lat.get(t, np.empty(0)))}
        return {
            "requests": requests,
            "rows": rows,
            "batches": batches,
            **self._percentiles(lat),
            "qps": round(requests / window, 2) if window else None,
            "rows_per_s": round(rows / window, 2) if window else None,
            "dispatches_per_batch_max": int(disp.max()) if disp.size
            else None,
            "dispatches_per_batch_mean": round(float(disp.mean()), 3)
            if disp.size else None,
            "queue_depth": depth,
            "queued_rows": queued_rows,
            "shed": shed,
            "mesh_resizes": self._mesh_resizes,
            "shard_drains": self._shard_drains,
            "draining": self._drained_rank is not None,
            "bucket_cost_ms": {b: round(1e3 * c, 4)
                               for b, c in self.bucket_cost().items()},
            "tenants": tenants,
            "swaps": self._pool.adoptions if self._pool is not None
            else None,
            "rejected_swaps": self._pool.rejections
            if self._pool is not None else None,
        }
