"""Runtime health layer — fused numerical-health guards, chunk watchdog,
rollback-to-last-good remediation (the robustness counterpart of the
round-7 perf layer; SURVEY §6 "Failure detection / elastic recovery").

PR-1 made fits survive *external* faults (preemption, crash, flaky IO).
This layer makes them survive *internal* ones: a NaN/Inf that appears in a
loop carry, a diverging loss/inertia, a carry norm blowing up, or a chunk
whose force point never returns (hung collective).  Long-running
multi-chip jobs die most often to exactly these unguarded failures
(arXiv:2112.09017); DrJAX's lesson (PAPERS.md) is that the health signal
should ride INSIDE the compiled program, not as host round-trips.

Design, in the order a chunked fit loop meets it:

- **fused guards** — each chunk kernel computes a tiny health vector
  (:func:`health_vec`) from its final carries *inside the existing fused
  dispatch*: any-nonfinite over carries and inputs, the worst
  monotonicity violation over the chunk's loss history, and the carry
  norm.  Guarding therefore costs ZERO extra dispatches per chunk (the
  ``dispatch_count`` counters prove it in ``tests/test_health.py``).
- **watchdogged read** — :meth:`ChunkGuard.check` reads the vector
  through ``runtime.fetch(blocking=False)`` semantics (the copy is
  enqueued first) and resolves it under an optional deadline
  (``DSLIB_CHUNK_DEADLINE_S``).  A chunk whose force point hangs trips a
  typed :class:`WatchdogTimeout`; the resolution is escalated through the
  PR-1 :class:`~dislib_tpu.runtime.retry.Retry` policy before the fit
  aborts cleanly.
- **gated snapshots** — :meth:`ChunkGuard.save_async` refuses to write a
  snapshot for a chunk whose check tripped, so a bad state can never
  rotate the last GOOD generation out of the checkpoint.
- **remediation** — :meth:`ChunkGuard.remediate` applies the configured
  :class:`HealthPolicy` action: roll back to the last-good generation and
  re-run (``retry``), re-run with a doubled damping knob (``halve`` — the
  estimators that have one: GMM ``reg_covar``, ALS ``lambda_``), re-run
  with a seeded perturbation of the restored carries (``reseed``), or
  raise a diagnostic :class:`NumericalDivergence` carrying the estimator,
  iteration, tripped guard, and offending-carry coordinates (``raise``,
  and always once ``max_restarts`` is exhausted or no checkpoint exists
  to roll back to).

Only the nonfinite guards are armed by default: the monotonicity and
norm-growth thresholds are opt-in (``monotone_rtol`` / ``grow_limit``)
because legitimate fits may cross loose versions of them.  The
deterministic fault injectors driving every path live in
``dislib_tpu.utils.faults`` (NaN-at-chunk-k, divergence ramps, hung
chunks).
"""

from __future__ import annotations

import os
import threading

import numpy as np

from dislib_tpu.utils import profiling

__all__ = ["NumericalDivergence", "WatchdogTimeout", "HealthPolicy",
           "ChunkGuard", "Verdict", "Remediation", "NO_REMEDIATION",
           "guard", "health_vec", "check_snapshot", "HEALTH_BASE_LEN"]

# fixed slots of a health vector; per-carry (count, first_flat_index)
# pairs follow, one pair per guarded carry
HEALTH_BASE_LEN = 9
_SLOT_CARRY_NF = 0      # nonfinite total over carries
_SLOT_INPUT_NF = 1      # nonfinite total over inputs (not remediable)
_SLOT_RISE = 2          # worst monotonicity violation over the chunk
_SLOT_SCALE = 3         # max |loss| over the chunk (rise's reference scale)
_SLOT_MAX_ABS = 4       # max |carry| (norm-growth guard)
_SLOT_LOSS_NF = 5       # nonfinite entries in the chunk's loss history —
#                         catches a transient blow-up that washed out of
#                         the carries (e.g. one garbage E-step) but left
#                         the trajectory poisoned
_SLOT_LOSS_VALID = 6    # 1.0 when the chunk produced a loss history (an
#                         explicit flag, NOT a NaN sentinel in the value
#                         slots: fits run under jax.debug_nans in the
#                         sanitizer tier, which would flag the sentinel)
_SLOT_LOSS_FIRST = 7    # chunk's first loss value — the guard compares it
#                         against the PREVIOUS chunk's last loss so the
#                         monotone guard sees cross-chunk jumps too (and
#                         is not structurally dead at every=1, where each
#                         chunk has a single-entry history)
_SLOT_LOSS_LAST = 8     # chunk's last loss value (host-side carry-over)


class NumericalDivergence(RuntimeError):
    """A fit's numerical state went bad (non-finite carries, diverging
    loss, exploding norms) and the remediation policy could not (or was
    configured not to) heal it.  Carries everything a postmortem needs:
    the estimator, the iteration the guard tripped at, which guard, and
    the offending carry coordinates."""

    def __init__(self, message, estimator=None, iteration=None, guard=None,
                 detail=None):
        super().__init__(message)
        self.estimator = estimator
        self.iteration = iteration
        self.guard = guard
        self.detail = detail or {}


class WatchdogTimeout(TimeoutError):
    """A chunk's force point (the health-vector read) exceeded its
    deadline — a hung collective/dispatch.  Subclasses ``TimeoutError``
    so the default ``Retry`` classification treats it as transient, which
    is what lets the watchdog escalate through the PR-1 retry policy
    before the clean abort."""


class HealthPolicy:
    """Configuration for a fit's health guards.

    Parameters (env default in parentheses; the constructor wins)
    ----------
    action : 'retry' | 'halve' | 'reseed' | 'raise' (``DSLIB_HEALTH_ACTION``,
        default 'retry') — what :meth:`ChunkGuard.remediate` does on a
        recoverable trip.  'halve' doubles the guard's ``damping`` factor
        per restart (estimators with a damping knob apply it); 'reseed'
        perturbs the restored carries with a seeded jitter; both fall
        back to plain rollback-and-retry semantics where the estimator
        has no such knob.
    max_restarts : int (``DSLIB_HEALTH_MAX_RESTARTS``, default 2) —
        rollbacks allowed before the typed raise.
    deadline_s : float | None (``DSLIB_CHUNK_DEADLINE_S``, default off) —
        chunk watchdog deadline on the health read's force point.
    first_deadline_s : float | None (``DSLIB_CHUNK_FIRST_DEADLINE_S``,
        default ``10 * deadline_s``) — deadline for the guard's FIRST
        check only: that force point usually blocks on XLA compilation
        (tens of seconds for the larger kernels), which a steady-state
        deadline would misread as a hang.  Note a later chunk with a new
        static length (e.g. the final short chunk) also compiles — keep
        ``deadline_s`` above worst-case compile+chunk, not just chunk.
    monotone_rtol : float | None (``DSLIB_HEALTH_MONOTONE_RTOL``, default
        off) — trip when the chunk's loss history rises (falls, for
        increasing metrics) by more than ``rtol * max(|loss|, 1)``.
    grow_limit : float | None (``DSLIB_HEALTH_GROW_LIMIT``, default off)
        — trip when ``max|carry|`` exceeds this.
    enabled : bool (``DSLIB_HEALTH``, default on) — master switch; a
        disabled policy's guard admits everything and never trips.
    seed : int — base seed of the 'reseed' perturbation stream.
    elastic_attempts : int (``DSLIB_HEALTH_ELASTIC_ATTEMPTS``, default 0)
        — rollback attempts the fit-loop escalation ladder may spend at
        the elastic mesh-shrink tier (the LAST rungs of the shared
        ``max_restarts`` budget; see ``runtime.fitloop``).  Only fits
        whose estimator supports the on-device data rebind offer the
        tier.
    grow_attempts : int (``DSLIB_HEALTH_GROW_ATTEMPTS``, default 2) —
        mesh GROW-back resizes one fit may perform when the capacity
        watcher (``runtime.preemption.capacity_target``) reports
        returned devices.  Growing is free of rollback budget (the state
        re-pads from the last snapshot, no work is lost) but each resize
        retraces the fit kernels — the budget bounds thrash under a
        flapping capacity source.
    """

    def __init__(self, action=None, max_restarts=None, deadline_s=None,
                 monotone_rtol=None, grow_limit=None, enabled=None, seed=0,
                 first_deadline_s=None, elastic_attempts=None,
                 grow_attempts=None):
        env = os.environ
        if action is None:
            action = env.get("DSLIB_HEALTH_ACTION", "retry")
        if action not in ("retry", "halve", "reseed", "raise"):
            raise ValueError(f"unknown health action {action!r}")
        self.action = action
        self.max_restarts = int(env.get("DSLIB_HEALTH_MAX_RESTARTS", 2)) \
            if max_restarts is None else int(max_restarts)
        if deadline_s is None and env.get("DSLIB_CHUNK_DEADLINE_S"):
            deadline_s = float(env["DSLIB_CHUNK_DEADLINE_S"])
        self.deadline_s = deadline_s
        if first_deadline_s is None and env.get("DSLIB_CHUNK_FIRST_DEADLINE_S"):
            first_deadline_s = float(env["DSLIB_CHUNK_FIRST_DEADLINE_S"])
        if first_deadline_s is None and deadline_s is not None:
            first_deadline_s = 10.0 * deadline_s   # compile-time grace
        self.first_deadline_s = first_deadline_s
        if monotone_rtol is None and env.get("DSLIB_HEALTH_MONOTONE_RTOL"):
            monotone_rtol = float(env["DSLIB_HEALTH_MONOTONE_RTOL"])
        self.monotone_rtol = monotone_rtol
        if grow_limit is None and env.get("DSLIB_HEALTH_GROW_LIMIT"):
            grow_limit = float(env["DSLIB_HEALTH_GROW_LIMIT"])
        self.grow_limit = grow_limit
        self.enabled = (env.get("DSLIB_HEALTH", "1") != "0") \
            if enabled is None else bool(enabled)
        self.seed = int(seed)
        self.elastic_attempts = \
            int(env.get("DSLIB_HEALTH_ELASTIC_ATTEMPTS", 0)) \
            if elastic_attempts is None else int(elastic_attempts)
        self.grow_attempts = \
            int(env.get("DSLIB_HEALTH_GROW_ATTEMPTS", 2)) \
            if grow_attempts is None else int(grow_attempts)

    def make_guard(self, name, checkpoint=None):
        """Build the per-fit guard.  Fault-injection policies
        (``dislib_tpu.utils.faults``) override this to hand the fit a
        corrupting/hanging guard — the deterministic injection seam."""
        return ChunkGuard(name, self, checkpoint)


class Verdict:
    """Outcome of one chunk check: ``ok``, the tripped ``guard`` name
    (``None`` when ok), whether rollback can help (``recoverable``), and
    a ``detail`` dict naming the offending carries/coordinates."""

    __slots__ = ("ok", "guard", "recoverable", "detail")

    def __init__(self, ok, guard=None, recoverable=True, detail=None):
        self.ok = bool(ok)
        self.guard = guard
        self.recoverable = bool(recoverable)
        self.detail = detail or {}

    def __repr__(self):
        return (f"Verdict(ok={self.ok}, guard={self.guard!r}, "
                f"recoverable={self.recoverable}, detail={self.detail})")


class Remediation:
    """What the fit loop should do after rolling back to last-good:
    ``attempt`` (1-based restart count), ``damping`` (multiplier for the
    estimator's damping knob — 2**attempt under the 'halve' action, 1.0
    otherwise), and :meth:`perturb` (seeded jitter for 'reseed')."""

    __slots__ = ("attempt", "action", "damping", "seed")

    def __init__(self, attempt, action, seed):
        self.attempt = int(attempt)
        self.action = action
        self.damping = float(2 ** attempt) if action == "halve" else 1.0
        self.seed = int(seed)

    def perturb(self, arr, scale=1e-3):
        """Seeded relative jitter of a restored carry ('reseed' action;
        identity under every other action).  Deterministic in
        (policy.seed, attempt) so a remediated fit is reproducible."""
        arr = np.asarray(arr)
        if self.action != "reseed":
            return arr
        rng = np.random.RandomState((self.seed + 0x9E37) ^ self.attempt)
        span = np.maximum(np.abs(arr), 1.0)
        return (arr + scale * span * rng.standard_normal(arr.shape)) \
            .astype(arr.dtype, copy=False)


class _NoRemediation(Remediation):
    """The identity remediation: attempt 0, no damping, no perturbation —
    what a clean (non-rollback) state load applies."""

    def __init__(self):
        super().__init__(0, "none", 0)

    @staticmethod
    def perturb(arr, scale=1e-3):
        return arr


NO_REMEDIATION = _NoRemediation()


def guard(name, health=None, checkpoint=None):
    """Normalise a ``fit(..., health=...)`` argument into a per-fit
    :class:`ChunkGuard`: ``None`` builds the env-default policy, a
    :class:`HealthPolicy` (or fault-injection subclass) builds its own
    guard, and an existing guard passes through."""
    if isinstance(health, ChunkGuard):
        return health
    policy = health if isinstance(health, HealthPolicy) else HealthPolicy()
    return policy.make_guard(name, checkpoint)


class ChunkGuard:
    """Per-fit health guard: admits carries into each chunk (the fault
    injectors' corruption seam), checks the chunk's fused health vector
    under the watchdog, gates snapshot writes on the verdict, and runs
    the remediation bookkeeping."""

    def __init__(self, name, policy, checkpoint=None):
        self.name = name
        self.policy = policy
        self.checkpoint = checkpoint
        self.chunk_index = 0            # admits seen (0-based chunk counter)
        self.restarts = 0
        self.last_verdict = Verdict(True)
        self._prev_loss_last = None     # last HEALTHY chunk's final loss —
        #                                 the cross-chunk monotone reference
        self._checks_done = 0           # first check gets the compile grace

    # -- carry admission (fault-injection seam) -------------------------

    def admit(self, *carries):
        """Pass the chunk's input carries through the guard.  Production
        guards return them unchanged; fault-injection guards corrupt them
        at an exact chunk index.  Always call it once per chunk — it is
        also the chunk counter."""
        self.chunk_index += 1
        return carries

    # -- the watchdogged check ------------------------------------------

    def _resolve(self, handle):
        """Blocking resolution of one health read (the chunk's force
        point).  Fault injectors override this to simulate a hung
        collective."""
        return handle.result() if hasattr(handle, "result") \
            else np.asarray(handle)

    def _watched_resolve(self, handle):
        # the guard's first check usually blocks on XLA compilation, not
        # a hung collective — give it the compile-grace deadline
        deadline = self.policy.first_deadline_s if self._checks_done == 0 \
            else self.policy.deadline_s
        if deadline is None:
            return self._resolve(handle)
        box = {}

        def run():
            try:
                box["value"] = self._resolve(handle)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                box["exc"] = e

        t = threading.Thread(target=run, name="dslib-chunk-watchdog",
                             daemon=True)
        t.start()
        t.join(deadline)
        if t.is_alive():
            profiling.count_resilience("watchdog_trips")
            raise WatchdogTimeout(
                f"{self.name}: chunk {self.chunk_index} force point "
                f"exceeded its {deadline}s deadline — hung collective or "
                "dispatch")
        if "exc" in box:
            raise box["exc"]
        return box["value"]

    def check(self, hvec, carry_names=(), carry_shapes=(), it=None,
              increasing=False):
        """Classify one chunk's health vector (device array, AsyncFetch
        handle, or host ndarray) into a :class:`Verdict`.

        The device→host copy is enqueued asynchronously first
        (``fetch(blocking=False)`` semantics) and resolved under the
        watchdog deadline; resolution failures escalate through the PR-1
        ``Retry`` policy (``WatchdogTimeout`` classifies transient) and
        re-raise typed once attempts are exhausted.  ``increasing``
        states the loss direction (must match the ``health_vec`` call)
        so the cross-chunk monotone comparison is signed correctly."""
        if not self.policy.enabled:
            self.last_verdict = Verdict(True)
            return self.last_verdict
        from dislib_tpu.runtime.elastic import AsyncFetch
        from dislib_tpu.runtime.retry import Retry
        if isinstance(hvec, np.ndarray):
            handle = hvec
        elif isinstance(hvec, AsyncFetch):
            handle = hvec
        else:
            handle = AsyncFetch(hvec)   # copy enqueued before resolution
        try:
            # the chunk's force point: the host blocks here until the
            # device has run the whole chunk
            with profiling.span("dslib.fitloop.wait"):
                h = np.asarray(Retry.from_env().call(
                    lambda: self._watched_resolve(handle)),
                    np.float64).ravel()
        finally:
            self._checks_done += 1
        v = self._classify(h, carry_names, carry_shapes, it, increasing)
        self.last_verdict = v
        if v.ok and len(h) > _SLOT_LOSS_LAST and \
                h[_SLOT_LOSS_VALID] > 0:
            self._prev_loss_last = float(h[_SLOT_LOSS_LAST])
        return v

    def check_host(self, values, it=None):
        """Host-value variant for loops whose per-chunk state is already
        on host (the cascade SVM's level merges): ``values`` maps carry
        name → ndarray/scalar; trips the nonfinite guard only."""
        if not self.policy.enabled:
            self.last_verdict = Verdict(True)
            return self.last_verdict
        bad = {}
        for name, val in values.items():
            arr = np.asarray(val, np.float64)
            nf = ~np.isfinite(arr)
            if nf.any():
                bad[name] = {"count": int(nf.sum()),
                             "first_index": int(np.flatnonzero(nf.ravel())[0])}
        if bad:
            v = Verdict(False, guard="nonfinite", recoverable=True,
                        detail={"carries": bad, "iteration": it})
        else:
            v = Verdict(True)
        self.last_verdict = v
        return v

    def _classify(self, h, carry_names, carry_shapes, it,
                  increasing=False):
        pol = self.policy
        detail = {"hvec": h.tolist(), "iteration": it}
        if h[_SLOT_CARRY_NF] > 0 or h[_SLOT_INPUT_NF] > 0 \
                or h[_SLOT_LOSS_NF] > 0:
            carries = {}
            for i in range(max(0, (len(h) - HEALTH_BASE_LEN) // 2)):
                cnt = h[HEALTH_BASE_LEN + 2 * i]
                if cnt <= 0:
                    continue
                name = carry_names[i] if i < len(carry_names) else f"carry{i}"
                info = {"count": int(cnt),
                        "first_index": int(h[HEALTH_BASE_LEN + 2 * i + 1])}
                if i < len(carry_shapes) and carry_shapes[i]:
                    info["coords"] = tuple(
                        int(c) for c in np.unravel_index(
                            min(info["first_index"],
                                int(np.prod(carry_shapes[i])) - 1),
                            carry_shapes[i]))
                carries[name] = info
            detail["carries"] = carries
            if h[_SLOT_LOSS_NF] > 0:
                detail["loss_nonfinite"] = int(h[_SLOT_LOSS_NF])
            if h[_SLOT_INPUT_NF] > 0:
                detail["input_nonfinite"] = int(h[_SLOT_INPUT_NF])
                # bad *input* data: a rollback re-reads the same data, so
                # remediation cannot help — quarantine at ingest instead
                return Verdict(False, guard="input-nonfinite",
                               recoverable=False, detail=detail)
            return Verdict(False, guard="nonfinite", detail=detail)
        if pol.monotone_rtol is not None:
            rise = float(h[_SLOT_RISE])
            # cross-chunk jump: previous healthy chunk's last loss vs this
            # chunk's first — the boundary the in-chunk diffs cannot see
            # (and at every=1 the ONLY signal, each history being length 1)
            if self._prev_loss_last is not None \
                    and len(h) > _SLOT_LOSS_FIRST \
                    and h[_SLOT_LOSS_VALID] > 0:
                step = h[_SLOT_LOSS_FIRST] - self._prev_loss_last
                rise = max(rise, float(-step if increasing else step))
            if rise > pol.monotone_rtol * max(h[_SLOT_SCALE], 1.0):
                detail["rise"] = rise
                detail["scale"] = float(h[_SLOT_SCALE])
                return Verdict(False, guard="divergence", detail=detail)
        if pol.grow_limit is not None and h[_SLOT_MAX_ABS] > pol.grow_limit:
            detail["max_abs"] = float(h[_SLOT_MAX_ABS])
            return Verdict(False, guard="norm-growth", detail=detail)
        return Verdict(True)

    def on_escalation(self, escalation) -> None:
        """Notification hook the fit-loop driver fires after every
        ladder escalation (``runtime.fitloop.Escalation``).  Production
        guards ignore it; tier-targeted fault injectors
        (``utils.faults.FaultAtTier``) use it to stop firing once the
        right remediation tier is reached."""

    # -- gated snapshot writes ------------------------------------------

    def save_async(self, checkpoint, state):
        """Snapshot gate: forward to ``checkpoint.save_async`` ONLY when
        the last check was healthy — an unhealthy chunk's state must
        never rotate the last good generation away."""
        if not self.last_verdict.ok:
            return None
        return checkpoint.save_async(state)

    def save(self, checkpoint, state):
        """Blocking variant of the gated write."""
        if not self.last_verdict.ok:
            return None
        return checkpoint.save(state)

    # -- remediation ------------------------------------------------------

    def remediate(self, verdict=None, it=None):
        """Decide the response to a tripped guard: return a
        :class:`Remediation` (the caller rolls back to last-good and
        re-runs), or raise :class:`NumericalDivergence` when the policy
        says raise, the trip is not recoverable (bad input data), there
        is no checkpoint to roll back to, or ``max_restarts`` is spent."""
        v = verdict if verdict is not None else self.last_verdict
        it = v.detail.get("iteration") if it is None else it
        reasons = []
        if self.policy.action == "raise":
            reasons.append("policy action is 'raise'")
        if not v.recoverable:
            reasons.append("non-finite input data cannot be healed by "
                           "rollback (quarantine it at ingest)")
        if self.checkpoint is None:
            reasons.append("no checkpoint to roll back to (pass "
                           "checkpoint= to enable self-healing)")
        if self.restarts >= self.policy.max_restarts:
            reasons.append(f"max_restarts={self.policy.max_restarts} "
                           "exhausted")
        if reasons:
            raise NumericalDivergence(
                f"{self.name}: health guard {v.guard!r} tripped at "
                f"iteration {it} — {'; '.join(reasons)} "
                f"(detail: {v.detail})",
                estimator=self.name, iteration=it, guard=v.guard,
                detail=v.detail)
        self.restarts += 1
        # the rollback (and any halve/reseed perturbation) breaks loss
        # continuity — drop the cross-chunk monotone reference so the
        # re-run chunk is not judged against the pre-rollback trajectory
        self._prev_loss_last = None
        return Remediation(self.restarts, self.policy.action,
                           self.policy.seed + self.restarts)

    def rollback(self, restore, scratch, remediation=None, checkpoint=None,
                 expect=None):
        """Load the newest good snapshot and hand it to
        ``restore(snap, remediation)``; fall back to
        ``scratch(remediation)`` when no snapshot exists (or there is no
        checkpoint at all).  The ONE state-(re)load path every rollback,
        elastic resize, and initial warm start of the fit loop funnels
        through — so the snapshot-vs-scratch dispatch and the remediation
        threading cannot drift between call sites.  ``checkpoint``
        overrides the guard's own (the fit-loop driver passes its sink:
        an injected guard may carry none).

        ``expect`` declares what a compatible snapshot must contain (see
        :func:`check_snapshot`); a mismatch raises the shared
        "stale or foreign snapshot" ``ValueError`` BEFORE ``restore``
        runs — the estimators' five copy-pasted validation blocks
        collapsed here (round 19), and the health-guard lint keeps them
        from growing back."""
        rem = NO_REMEDIATION if remediation is None else remediation
        ck = self.checkpoint if checkpoint is None else checkpoint
        snap = ck.load() if ck is not None else None
        if snap is not None and expect:
            check_snapshot(self.name, snap, expect)
        return restore(snap, rem) if snap is not None else scratch(rem)


def check_snapshot(name, snap, expect):
    """Validate a loaded snapshot against the estimator's declared
    expectations — the one place the "stale or foreign snapshot" raise
    lives.  ``expect`` maps snapshot key -> spec:

    - a tuple is a required shape; ``None`` dims are wildcards (elastic
      factor rows repadded per mesh, e.g. ALS's ``(None, n_f)``);
    - an int is a required scalar value (logical dims like ALS's
      ``m``/``n``, which outlive any padding).

    A missing key or a mismatch raises ``ValueError`` mentioning
    "stale or foreign snapshot" (tests and callers match on the phrase).
    Estimators declare this via ``ChunkedFitLoop(snapshot_expect=...)``
    rather than hand-checking in their ``restore`` callbacks.
    """
    for key, spec in expect.items():
        if key not in snap:
            raise ValueError(
                f"{name}: checkpoint is missing {key!r} — stale or "
                "foreign snapshot")
        if isinstance(spec, tuple):
            got = tuple(np.asarray(snap[key]).shape)
            want = tuple(spec)
            if len(got) != len(want) or any(
                    w is not None and g != w for g, w in zip(got, want)):
                shown = tuple("*" if w is None else w for w in want)
                raise ValueError(
                    f"{name}: checkpoint {key!r} shape {got} does not "
                    f"match this estimator/data {shown} — stale or "
                    "foreign snapshot")
        else:
            got = int(np.asarray(snap[key]))
            if got != int(spec):
                raise ValueError(
                    f"{name}: checkpoint {key!r} = {got} does not match "
                    f"this estimator/data ({int(spec)}) — stale or "
                    "foreign snapshot")


def health_vec(carries=(), inputs=(), hist=None, n_done=None,
               increasing=False):
    """Build the (HEALTH_BASE_LEN + 2·len(carries),) float32 health vector
    INSIDE a fit kernel — call it from traced code only, on the chunk's
    final carries, so the guard rides the existing fused dispatch.

    Layout (``HEALTH_BASE_LEN`` = 9 base slots, then one pair per carry):
    ``[carry_nonfinite_total, input_nonfinite_total, rise, scale,
    max_abs_carry, loss_nonfinite, loss_valid, loss_first, loss_last,
    (count, first_flat_index) per carry]``.  ``loss_valid`` flags whether
    the chunk produced a (finite) loss history — an explicit flag rather
    than a NaN sentinel, because sanitizer-tier fits run under
    ``jax.debug_nans``; the guard carries ``loss_last`` across chunks
    host-side so the monotone guard also sees a jump that lands exactly
    on a chunk boundary (including the ``every=1`` cadence, where every
    in-chunk history has length 1).

    ``hist``/``n_done``: the chunk's per-iteration loss history (slots
    beyond ``n_done`` ignored); ``rise`` is the worst consecutive
    violation of monotonicity (losses must fall, or rise when
    ``increasing=True``) and ``scale`` its reference magnitude.  Integer
    and boolean carries contribute nothing (they can hold neither a
    non-finite value nor a meaningful norm blow-up) — pass them for the
    chunk-counting seam only.
    """
    import jax.numpy as jnp

    def _nf_pair(c):
        c = jnp.asarray(c)
        if not jnp.issubdtype(c.dtype, jnp.floating):
            z = jnp.float32(0)
            return z, z
        bad = ~jnp.isfinite(c.ravel())
        count = jnp.sum(bad).astype(jnp.float32)
        first = jnp.argmax(bad).astype(jnp.float32)  # 0 when count == 0
        return count, first

    pairs = [_nf_pair(c) for c in carries]
    carry_nf = sum((p[0] for p in pairs), jnp.float32(0))
    input_nf = sum((_nf_pair(x)[0] for x in inputs), jnp.float32(0))
    max_abs = jnp.float32(0)
    for c in carries:
        c = jnp.asarray(c)
        if jnp.issubdtype(c.dtype, jnp.floating):
            # NaNs must not mask a finite blow-up elsewhere; they already
            # trip the nonfinite guard themselves
            a = jnp.abs(c.ravel())
            max_abs = jnp.maximum(
                max_abs,
                jnp.max(jnp.where(jnp.isfinite(a), a, 0.0),
                        initial=0.0).astype(jnp.float32))
    rise = jnp.float32(0)
    scale = jnp.float32(0)
    loss_nf = jnp.float32(0)
    loss_valid = jnp.float32(0)         # 0 = "no loss this chunk": the
    loss_first = jnp.float32(0)         # guard skips the comparison (an
    loss_last = jnp.float32(0)          # explicit flag — a NaN sentinel
    #                                     would trip jax.debug_nans)
    if hist is not None:
        hist = jnp.asarray(hist, jnp.float32).ravel()
        n = hist.shape[0]
        if n >= 1:
            idx = jnp.arange(n)
            done = hist.shape[0] if n_done is None else n_done
            valid = idx < done
            loss_nf = jnp.sum(valid & ~jnp.isfinite(hist)) \
                .astype(jnp.float32)
            scale = jnp.max(jnp.where(valid & jnp.isfinite(hist),
                                      jnp.abs(hist), 0.0), initial=0.0)
            ran = jnp.asarray(done, jnp.int32) >= 1
            loss_valid = ran.astype(jnp.float32)
            # NaNs in hist itself already trip the loss_nf guard before
            # any monotone comparison, but keep the carried values clean
            # of them so debug_nans-audited paths stay silent
            h0 = hist[0]
            hl = hist[jnp.maximum(jnp.asarray(done, jnp.int32) - 1, 0)]
            loss_first = jnp.where(ran & jnp.isfinite(h0), h0, 0.0)
            loss_last = jnp.where(ran & jnp.isfinite(hl), hl, 0.0)
            loss_valid = jnp.where(
                jnp.isfinite(h0) & jnp.isfinite(hl), loss_valid, 0.0)
            if n >= 2:
                diffs = hist[1:] - hist[:-1]
                dvalid = (idx[1:] < done) & jnp.isfinite(diffs)
                viol = -diffs if increasing else diffs
                rise = jnp.max(jnp.where(dvalid, viol, 0.0), initial=0.0)
    out = [carry_nf, input_nf, rise.astype(jnp.float32),
           scale.astype(jnp.float32), max_abs, loss_nf,
           jnp.asarray(loss_valid, jnp.float32),
           jnp.asarray(loss_first, jnp.float32),
           jnp.asarray(loss_last, jnp.float32)]
    for count, first in pairs:
        out.extend([count, first])
    return jnp.stack(out)
