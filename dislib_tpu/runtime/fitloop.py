"""Unified resilient chunk-fit runtime (round-12 robustness PR; ROADMAP
item 2 — the extraction PR 3's review flagged twice).

Before this module, every chunked estimator hand-wired the same per-chunk
protocol: register a guard, admit carries (the fault-injection seam), run
the fused chunk kernel, judge its health vector under the watchdog, gate
the snapshot write on the verdict, roll back to the last-good generation
on a trip, and poll the preemption flag at the boundary — five
near-identical rollback blocks across seven estimators.
:class:`ChunkedFitLoop` owns the whole protocol; an estimator supplies
only the three things the runtime cannot know (DrJAX's per-shard-update +
cross-shard-reduce decomposition is the chunk-step shape, PAPERS.md
arXiv:2403.07128):

- ``init(rem)``    — build a fresh :class:`LoopState` (``rem`` perturbs /
  damps after a rollback with no snapshot; the initial call passes a
  neutral remediation, so closures never branch on None);
- ``restore(snap, rem)`` — rebuild state from a snapshot dict (validate,
  re-pad for the CURRENT mesh, apply ``rem``); raise ``ValueError`` on a
  stale/foreign snapshot;
- ``step(state, chunk)`` — run ONE chunk kernel on ``state.carries``
  (already passed through the guard's admit seam) and return a
  :class:`ChunkOutcome` whose ``hvec`` (fused health vector) or
  ``host_values`` the driver judges;

plus a ``snapshot(state) -> dict`` builder, called only at save
boundaries (build it lazily — it is where the device→host fetches live).

On top of the extraction, the driver adds what copy-pasted blocks could
never coordinate: a cross-attempt **escalation ladder**
(:class:`EscalationLadder`) with a shared fault budget
(``HealthPolicy.max_restarts``).  Successive trips of one fit escalate
deterministically through tiers instead of burning the whole budget at
one level:

1. **retry** — plain rollback-to-last-good and re-run (transient bit
   flips, one-off collective glitches);
2. **remediate** — rollback plus the policy action: ``halve`` doubles the
   estimator's damping knob per tier attempt, ``reseed`` perturbs the
   restored carries (systematic numerical trouble);
3. **elastic** — shrink the mesh to half its row axis (the
   PR-1/PR-6 elastic machinery: state re-pads via ``repad_rows``, data
   re-lays out on device via the estimator's ``elastic`` rebind hook) and
   resume from last-good — the "a device is bad" tier.  Opt-in:
   ``HealthPolicy(elastic_attempts=1)`` / ``DSLIB_HEALTH_ELASTIC_ATTEMPTS``,
   and only offered when the estimator passes an ``elastic`` hook;
4. **raise** — the typed ``NumericalDivergence`` / ``WatchdogTimeout``
   diagnostics, exactly as before, once the budget is spent (or
   immediately for non-recoverable trips / the 'raise' action / no
   checkpoint).

The ladder preserves the pre-extraction budget semantics exactly:
``max_restarts`` rollbacks total, then the typed raise — the tiers only
decide WHAT each rollback does.  Streaming estimators call
:meth:`ChunkedFitLoop.run_one` (one committed chunk per ``partial_fit``
call, protocol identical, budget and cadence stream-wide) — the recipe
that makes a new estimator resilient by construction
(``cluster.kmeans.MiniBatchKMeans`` is the acceptance test).

Elasticity is BIDIRECTIONAL (round-16): alongside the fault-driven
shrink tier, the driver polls the **capacity watcher**
(``runtime.preemption.capacity_target`` — the ``DSLIB_CAPACITY_FILE`` /
``request_capacity`` level) at the same chunk boundaries as the
preemption flag.  When the published device target drops, the fit
snapshots and shrinks to the largest halving-reachable mesh that fits;
when capacity RETURNS, it grows back toward the mesh it started on —
state re-pads from the snapshot via ``repad_rows``, data re-lays out on
device through the estimator's ``elastic`` hook (the ``ds.rechunk``
deviceput/panels router — never the host).  Capacity resizes spend no
rollback budget (nothing failed; the chunk just committed), but grows
are bounded by ``HealthPolicy.grow_attempts`` against a flapping
source.  Both directions report in ``info`` (``mesh_shrinks`` /
``mesh_grows``) and the process-wide resilience counters.
"""

from __future__ import annotations

from dislib_tpu.runtime import health as _health
from dislib_tpu.runtime.health import NO_REMEDIATION
from dislib_tpu.runtime.preemption import (capacity_target,
                                           preemption_requested,
                                           raise_if_preempted)
from dislib_tpu.utils.profiling import count_resilience, span

# the span of a whole run: the one name ``run`` and ``run_one`` share
_RUN = "dslib.fitloop.run"

__all__ = ["ChunkedFitLoop", "LoopState", "ChunkOutcome", "Escalation",
           "EscalationLadder", "NO_REMEDIATION", "TIERS", "data_rebind",
           "stream_state"]

TIERS = ("retry", "remediate", "elastic")


def stream_state(checkpoint, key="n_batches"):
    """``(consumed, snapshot_dict)`` of a STREAMING fit's checkpoint —
    ``(0, None)`` when there is no usable snapshot.  The producer-side
    resume point: the driver restores the MODEL state, but only the
    producer knows the batch order, so it must feed ``run_one`` batches
    from this position on (re-feeding consumed batches would apply them
    twice); a fully consumed stream adopts the snapshot as the fitted
    state.  Lives here so estimator code never reads checkpoints
    directly (the driver lint forbids it)."""
    snap = checkpoint.load() if checkpoint is not None else None
    if snap is None or key not in snap:
        return 0, None
    return int(snap[key]), snap


def data_rebind(holder, key="x"):
    """The standard elastic-tier rebind hook over a mutable data holder
    (``{key: ds_array}``): force the pending op chain BEFORE the mesh
    switch (the fusion layer's device-set contract — the driver calls the
    hook with ``mesh=None`` for this phase), re-canonicalize onto the new
    mesh after.  SPARSE holders (``SparseArray``) re-land their sharded
    buffers through the sparse rechunk schedules instead (no op chains
    to force, still never the host) — the round-14 sparse elastic rung.
    Objects exposing ``rebind_mesh(mesh)`` (round 20: an ``IVFIndex``'s
    mesh-pinned inverted-list layout) own their re-layout and are
    delegated to.  Estimators with extra rebinding (ALS's padded test
    matrix) wrap or replace it."""
    def hook(mesh):
        from dislib_tpu.data.array import ensure_canonical
        from dislib_tpu.data.sparse import SparseArray
        x = holder[key]
        if hasattr(x, "rebind_mesh"):
            x.rebind_mesh(mesh)         # the object owns its re-layout
            return
        if isinstance(x, SparseArray):
            if mesh is not None:
                x.sharded(mesh)         # on-device reshard of the backing
            return
        holder[key] = x.force() if mesh is None else ensure_canonical(x)
    return hook


class LoopState:
    """One point of a chunked fit: ``carries`` (the device arrays that
    flow chunk-to-chunk — the guard's admit/poison seam), ``it``
    (completed iterations/levels/rounds), ``done`` (converged), and
    ``extra`` (estimator-owned scalars riding along, e.g. the current
    loss)."""

    __slots__ = ("carries", "it", "done", "extra")

    def __init__(self, carries=(), it=0, done=False, extra=None):
        self.carries = tuple(carries)
        self.it = int(it)
        self.done = bool(done)
        self.extra = extra


class ChunkOutcome:
    """What one chunk produced: the successor ``state``, the fused
    health ``hvec`` (device array — judged under the watchdog) or
    ``host_values`` (name → ndarray, for loops whose state is host-side),
    and ``history`` (this chunk's per-iteration loss values; the driver
    owns the cross-rollback trimming).

    ``state`` and ``history`` may each be a CALLABLE (deferred commit):
    the driver invokes them only AFTER the chunk's verdict passed.  Step
    closures whose successor state needs device scalars (``int(n_done)``,
    ``float(shift)``, a fetched ``changed`` flag) MUST defer them this
    way: the hvec is an output of the same fused program, so resolving it
    first — under the watchdog deadline — forces the whole chunk, and a
    hung collective trips a typed ``WatchdogTimeout`` instead of blocking
    forever in an estimator-side sync (review-found: the eager ports left
    real kernel hangs outside the watchdog).  A deferred state also
    cannot leak a faulted chunk's side effects — its closure never runs
    on the rollback path.  ``check_on='save'`` loops (the forest) must
    keep ``state`` eager: the save-boundary decision reads ``state.done``
    before any check."""

    __slots__ = ("state", "hvec", "host_values", "history")

    def __init__(self, state, hvec=None, host_values=None, history=()):
        self.state = state
        self.hvec = hvec
        self.host_values = host_values
        self.history = history


class Escalation:
    """One rung of the ladder: the ``tier`` this attempt runs at, the
    global ``attempt`` number (1-based, = the guard's restart count), the
    1-based ``tier_attempt`` within the tier, and the tier-adjusted
    ``remediation`` the estimator's restore/init closures apply."""

    __slots__ = ("tier", "tier_index", "attempt", "tier_attempt",
                 "remediation")

    def __init__(self, tier, attempt, tier_attempt, remediation):
        self.tier = tier
        self.tier_index = TIERS.index(tier)
        self.attempt = attempt
        self.tier_attempt = tier_attempt
        self.remediation = remediation


class EscalationLadder:
    """Maps the guard's restart counter onto tiers.  The schedule spends
    the shared budget (``max_restarts``) as: 1 plain retry, then policy
    remediation, then ``elastic_attempts`` mesh-shrink attempts (last —
    most disruptive), then the typed raise.  The raise conditions
    (non-recoverable trip, 'raise' action, no checkpoint, spent budget)
    stay with :meth:`ChunkGuard.remediate` so diagnostics cannot drift."""

    def __init__(self, guard, elastic_ok=False):
        self.guard = guard
        pol = guard.policy
        budget = max(0, int(pol.max_restarts))
        retry_n = min(1, budget)
        elastic_n = min(max(0, int(getattr(pol, "elastic_attempts", 0))),
                        budget - retry_n) if elastic_ok else 0
        self.schedule = (["retry"] * retry_n
                         + ["remediate"] * (budget - retry_n - elastic_n)
                         + ["elastic"] * elastic_n)

    def escalate(self, verdict, it=None) -> Escalation:
        rem = self.guard.remediate(verdict, it=it)   # typed-raise gate
        a = rem.attempt
        tier = self.schedule[a - 1] if 0 < a <= len(self.schedule) \
            else "remediate"
        tier_attempt = self.schedule[: a].count(tier) or 1
        action = self.guard.policy.action if tier == "remediate" else "retry"
        esc = Escalation(tier, a, tier_attempt,
                         _health.Remediation(tier_attempt, action, rem.seed))
        count_resilience("rollbacks")
        count_resilience("escalations_" + tier)
        if tier == "retry":
            count_resilience("chunk_retries")
        self.guard.on_escalation(esc)
        return esc


class ChunkedFitLoop:
    """The one driver every chunked fit runs on.

    Parameters
    ----------
    name : str — estimator name for guards/diagnostics.
    checkpoint : FitCheckpoint | None — rollback target + save sink; None
        runs the protocol without snapshots (a recoverable trip then
        raises typed, as before).
    health : HealthPolicy | ChunkGuard | None — the fit's policy (fault
        injectors are policy subclasses; see ``utils.faults``).
    max_iter : int | None — iteration budget; None = run until a chunk
        reports ``done`` (propagation/extraction loops).
    chunk_iters : int | None — iterations per chunk; None = the
        checkpoint's ``every`` (whole budget when no checkpoint).  Loops
        whose natural chunk is one host iteration/level (cascade SVM,
        forest) pass 1 and move the cadence to ``save_every``.
    save_every : int — snapshot every N committed chunks (1 = each).
    check_on : 'chunk' | 'save' — judge every chunk, or only at save
        boundaries (the forest's cadence: its per-level health vector is
        read once per snapshot chunk, one sync per chunk either way).
        With ``check_on='save'`` and no checkpoint the loop never judges
        (the forest defers to its adoption-time check).
    save_final : bool — whether the converged/final state snapshots
        (the forest's growth loop snapshots only resumable mid-points).
    carry_names / carry_shapes / increasing — forwarded to
        ``guard.check`` for diagnostics and the monotone direction.
    snapshot_expect : dict | None — the snapshot compatibility contract
        (key -> required shape tuple with ``None`` wildcards, or required
        scalar); validated by ``health.check_snapshot`` inside the one
        rollback funnel before any ``restore`` callback runs, raising the
        shared "stale or foreign snapshot" ``ValueError`` on mismatch.
    elastic : callable(mesh) | None — rebind hook for the elastic tier
        AND the capacity-driven resizes: called after the driver changes
        the mesh; re-lay out the fit's data for the new topology
        (``ds.ensure_canonical`` / the sparse and estimator-specific
        re-staging).  None disables both for this fit.

    ``info`` carries the fit's resilience summary (chunks, rollbacks,
    escalations per tier, mesh shrinks/grows) — estimators expose it as
    ``fit_info_``; the same events also feed the process-wide
    ``utils.profiling`` resilience counters at zero extra dispatches.
    """

    def __init__(self, name, *, checkpoint=None, health=None, max_iter=None,
                 chunk_iters=None, save_every=1, check_on="chunk",
                 save_final=True, carry_names=(), carry_shapes=(),
                 increasing=False, elastic=None, snapshot_expect=None):
        self.name = name
        self.checkpoint = checkpoint
        self.guard = _health.guard(name, health, checkpoint)
        self.max_iter = max_iter
        self.chunk_iters = chunk_iters
        self.save_every = max(1, int(save_every))
        self.check_on = check_on
        self.save_final = bool(save_final)
        self.carry_names = tuple(carry_names)
        self.carry_shapes = tuple(carry_shapes)
        self.increasing = bool(increasing)
        self.elastic = elastic
        # snapshot compatibility contract, validated by the ONE rollback
        # funnel (guard.rollback -> health.check_snapshot) before any
        # restore callback sees the snapshot; streaming estimators may
        # reassign it per call (the stream's width can change the want)
        self.snapshot_expect = dict(snapshot_expect) if snapshot_expect \
            else None
        self.ladder = EscalationLadder(self.guard,
                                       elastic_ok=elastic is not None)
        self.history: list = []
        self.info = {"chunks": 0, "rollbacks": 0, "mesh_shrinks": 0,
                     "mesh_grows": 0,
                     "escalations": dict.fromkeys(TIERS, 0)}
        self._state = None
        self._esc = None
        self._it0 = None
        self._cadence = 0
        self._preempt = False
        self._cap_plan = None
        self._cap_shrunk = False
        self._grows_left = max(0, int(getattr(self.guard.policy,
                                              "grow_attempts", 0)))
        # the mesh this fit STARTED on is "home": capacity shrinks keep a
        # device prefix of it, and grow-back re-forms prefixes of it (a
        # fit never grows past its entry mesh — returned devices beyond
        # that belong to the next fit / a fresh process)
        from dislib_tpu.parallel import mesh as _mesh
        m = _mesh.get_mesh()
        self._home_shape = _mesh.mesh_shape(m)
        self._home_devices = list(m.devices.reshape(-1))

    # -- protocol pieces -------------------------------------------------

    def _load_state(self, init, restore, rem=NO_REMEDIATION) -> LoopState:
        st = self.guard.rollback(restore, init, rem,
                                 checkpoint=self.checkpoint,
                                 expect=self.snapshot_expect)
        if self._it0 is None:
            self._it0 = st.it           # this-run history starts here
        del self.history[max(0, st.it - self._it0):]
        self._cadence = 0               # snapshot cadence re-anchors
        return st

    def _plan(self, state):
        if self.max_iter is None:
            return None
        left = self.max_iter - state.it
        if self.chunk_iters is not None:
            return min(self.chunk_iters, left)
        return left if self.checkpoint is None \
            else min(self.checkpoint.every, left)

    def _one_chunk(self, st, step, chunk):
        """admit → step (the chunk's dispatch) → judge (watchdogged).
        Returns the chunk's :class:`ChunkOutcome`, its deferred commit
        still unrun, or None after a rollback was decided (``self._esc``
        holds the escalation).  The preemption flag is polled ONCE here
        and reused by ``_commit`` — two independent polls could let a
        flag arriving between them snapshot a chunk whose health vector
        was never judged (check_on='save')."""
        with span("dslib.fitloop.chunk", it=st.it):
            carries = self.guard.admit(*st.carries)
            out = step(LoopState(carries, st.it, st.done, st.extra), chunk)
            self._preempt = preemption_requested()
            self._cap_plan = self._capacity_plan()
            if self.check_on == "chunk":
                do_check = True
            else:                       # 'save': judge at save boundaries
                # a pending capacity resize forces the boundary: the resize
                # snapshots this chunk's state, so it must be judged first
                boundary = out.state.done \
                    or (self._cadence + 1) % self.save_every == 0 \
                    or self._preempt or self._cap_plan is not None
                do_check = self.checkpoint is not None and boundary
            if do_check:
                if out.host_values is not None:
                    verdict = self.guard.check_host(out.host_values,
                                                    it=st.it)
                elif out.hvec is not None:
                    verdict = self.guard.check(
                        out.hvec, carry_names=self.carry_names,
                        carry_shapes=self.carry_shapes, it=st.it,
                        increasing=self.increasing)
                else:
                    verdict = None
                if verdict is not None and not verdict.ok:
                    esc = self.ladder.escalate(verdict, it=st.it)  # may raise
                    self.info["rollbacks"] += 1
                    self.info["escalations"][esc.tier] += 1
                    if esc.tier == "elastic":
                        self._shrink_mesh()
                    self._esc = esc
                    return None
            return out

    def _commit(self, out, snapshot) -> LoopState:
        """Materialize a judged chunk's deferred state and history (the
        estimator's scalar reads — the device wait too, where the health
        check is off and ``dslib.fitloop.wait`` never opened), book the
        chunk, and write the gated snapshot.  Returns the successor
        state."""
        with span("dslib.fitloop.commit"):
            st = out.state() if callable(out.state) else out.state
            hist = out.history() if callable(out.history) else out.history
            self.info["chunks"] += 1
            self._cadence += 1
            if hist is not None and len(hist):
                self.history.extend(hist)
            if self.checkpoint is None:
                return st
            boundary = st.done or self._cadence % self.save_every == 0
            if (boundary or self._preempt or self._cap_plan is not None) \
                    and (not st.done or self.save_final):
                self.guard.save_async(self.checkpoint, snapshot(st))
            if self._preempt and not st.done \
                    and (self.max_iter is None or st.it < self.max_iter):
                raise_if_preempted(self.checkpoint)
            return st

    def _capacity_plan(self):
        """Compare the published capacity level against the current mesh
        and return ``("shrink"|"grow", new_rows)`` — or None when nothing
        to do.  The plan keeps the mesh a halving-reachable prefix of the
        HOME mesh (column count fixed; rows move by powers of two), so a
        shrink-then-grow sequence walks back through the exact shapes it
        came down by.  Grows additionally need budget (``grow_attempts``)
        so a flapping capacity source cannot thrash resizes forever;
        shrinks always honour the target (running over capacity risks
        eviction).  Stable at the fixpoint: once rows match the target,
        every poll returns None."""
        if self.elastic is None or self.checkpoint is None:
            return None
        cap = capacity_target()
        if cap is None:
            # No target published.  If a CAPACITY shrink brought us below
            # home, a cleared target means the pressure LIFTED (round-20
            # rejoin heal clears rather than publishing a bigger level) —
            # head home through the same grow rungs, same budget.  An
            # elastic-tier remediation shrink never sets the flag: nothing
            # says the bad device came back, so it stays sticky.
            if not self._cap_shrunk:
                return None
            cap = self._home_shape[0] * self._home_shape[1]
        from dislib_tpu.parallel import mesh as _mesh
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

    def _resize_mesh(self, new_r, kind):
        """Re-form the mesh at ``new_r`` rows over the home-device prefix
        and rebind the fit's data.  The hook is called TWICE: once with
        ``None`` BEFORE the switch — force any pending op chains under
        the mesh they were built for (the fusion layer's force-first
        contract for device-set changes) — and once with the new mesh to
        re-lay the data out (``ds.ensure_canonical`` / the rechunk
        schedules)."""
        from dislib_tpu.parallel import mesh as _mesh
        r, c = _mesh.mesh_shape(_mesh.get_mesh())
        if new_r == r:
            return
        if self.elastic is not None:
            self.elastic(None)          # pre-switch: force pending chains
        _mesh.init((new_r, c), devices=self._home_devices[: new_r * c])
        # drop the jit caches: a kernel whose PADDED shape is unchanged
        # across the switch would otherwise hit the trace cache and
        # replay a sharding constraint baked for the dead mesh (the PR-6
        # stale-constraint failure mode; a real elastic resume is a
        # fresh process with cold caches, so the recompile is the honest
        # cost of a resize)
        import jax
        jax.clear_caches()
        key = "mesh_shrinks" if kind == "shrink" else "mesh_grows"
        self.info[key] += 1
        count_resilience(key)
        if self.elastic is not None:
            self.elastic(_mesh.get_mesh())

    def _shrink_mesh(self):
        """Elastic tier: halve the mesh's row axis (first half of the
        device grid survives — the 'a device went bad' drill).  An
        unshrinkable mesh (single row) keeps the current one: the
        attempt degrades to a plain retry, deterministically — the hook
        still runs both phases so pending chains are forced."""
        from dislib_tpu.parallel import mesh as _mesh
        r, c = _mesh.mesh_shape(_mesh.get_mesh())
        if r >= 2:
            self._resize_mesh(r // 2, "shrink")
        elif self.elastic is not None:
            self.elastic(None)
            self.elastic(_mesh.get_mesh())

    def _apply_capacity(self, st, init, restore) -> LoopState:
        """Execute the pending capacity plan AFTER the chunk committed:
        flush the just-written snapshot (the resize's resume point),
        re-form the mesh, and reload state through the one rollback
        funnel — ``restore`` re-pads for the new mesh exactly as an
        elastic-tier resume would, but with the neutral remediation
        (nothing failed) and no budget spent."""
        kind, new_r = self._cap_plan
        self._cap_plan = None
        if self.checkpoint is not None:
            self.checkpoint.flush()     # resume point must be on disk
        if kind == "grow":
            self._grows_left -= 1
        self._resize_mesh(new_r, kind)
        self._cap_shrunk = new_r < self._home_shape[0]
        return self._load_state(init, restore)

    # -- entry points ----------------------------------------------------

    def run(self, *, init, step, restore=None, snapshot=None) -> LoopState:
        """Drive a whole fit: chunks until converged/budget-spent, the
        full protocol per chunk.  Returns the final state (also kept as
        ``self.state``); flushes the checkpoint before returning."""
        with span(_RUN):
            st = self._load_state(init, restore)
            while not st.done:
                chunk = self._plan(st)
                if chunk is not None and chunk <= 0:
                    break
                out = self._one_chunk(st, step, chunk)
                if out is None:         # rolled back: reload last-good
                    st = self._load_state(init, restore,
                                          self._esc.remediation)
                    continue
                st = self._commit(out, snapshot)
                if self._cap_plan is not None and not st.done:
                    st = self._apply_capacity(st, init, restore)
            if self.checkpoint is not None:
                self.checkpoint.flush()  # last snapshot lands before return
            self._state = st
            return st

    def run_one(self, *, init, step, restore=None, snapshot=None) -> LoopState:
        """Streaming entry (``partial_fit``): ONE committed chunk per
        call, protocol identical — admit, judge, rollback/escalate until
        the chunk commits (or the typed raise), gated save at the
        cadence, preemption poll.  The loop object persists across calls,
        so the fault budget, save cadence, and escalation state are
        stream-wide; the first call restores from the checkpoint (a
        preempted stream resumes where it snapshot)."""
        with span(_RUN):
            st = self._state if self._state is not None \
                else self._load_state(init, restore)
            while True:
                out = self._one_chunk(st, step, None)
                if out is None:
                    st = self._load_state(init, restore,
                                          self._esc.remediation)
                    continue
                st = self._commit(out, snapshot)
                if self._cap_plan is not None and not st.done:
                    st = self._apply_capacity(st, init, restore)
                self._state = st
                return st

    @property
    def state(self):
        return self._state
