"""dislib_tpu.runtime — the preemption-safe elastic runtime layer.

The reference's fault tolerance is runtime-level (COMPSs resubmits failed
tasks); on TPU a preemption or chip failure kills the whole SPMD job, so
the survival story is built from four pieces that compose (SURVEY §6
"Failure detection / elastic recovery"):

- **preemption** — SIGTERM/sentinel-file watcher + the
  :class:`Preempted` contract checkpointed fits honour at chunk
  boundaries (``preemption.py``);
- **retry** — transient-vs-fatal classified retries with backoff for the
  coordinator join, ingest IO, and host↔device transfers (``retry.py``);
- **elastic** — restore snapshots onto a different device count/mesh
  shape by re-padding host-side logical state (``elastic.py``);
- **xla_flags** — the single site allowed to mutate ``XLA_FLAGS``
  (XLA:CPU collective-timeout mitigation; ``xla_flags.py``);
- **compile_cache** — the one resolver of where JAX's persistent
  compilation cache lives (``compile_cache.py``);
- **health** — the round-8 *internal*-fault layer: fused numerical-health
  guards on every chunked fit loop, a chunk watchdog, snapshot writes
  gated on healthy chunks, and rollback-to-last-good remediation
  (``health.py``);
- **adoption** — the round-9 read-side hot-swap gate: serve checkpoint
  generation N while N+1 trains; a reader adopts a new generation only
  after the checksum-verified load AND a health-gated warmup predict
  (``adoption.py``; the serving layer is lint-bound to it);
- **bundle_io** — the round-15 deployment-bundle byte seam: atomic
  checksum-embedding writes and verified reads of the AOT serving
  artifact, plus the typed :class:`BundleIncompatible`
  (``bundle_io.py``; ``serving.bundle`` assembles the artifact, this
  module owns its bytes — serving code never touches them raw);
- **coord** — the round-19 cross-process coordination seam: the named
  ranked ``exchange`` primitive over three transports (in-memory /
  shared directory / ``jax.distributed`` KV) behind the sharded-bundle
  load barrier, plus the atomically-replaced :class:`CapacityLedger`
  that makes the capacity level fleet-wide; round 20 adds lease-based
  :class:`Membership` (heartbeats, epoch fencing, the typed attributed
  :class:`RankDead`) and the death→capacity→heal flow (``coord.py``);
- **trainer** — the round-17 continuous-learning daemon:
  :class:`ContinuousTrainer` welds the quarantined stream, the chunked
  fit loop, retried bundle exports, and the router's canary/promote
  seam into one train → bundle → canary → promote loop with a promotion
  ledger, automatic stay-on-last-good rollback, and the typed
  :class:`PromotionFailed` (``trainer.py``).

Crash-consistent rotating snapshots live with the checkpoint format in
``dislib_tpu.utils.checkpoint``; the deterministic fault-injection harness
driving ``tests/test_resilience.py`` is ``dislib_tpu.utils.faults``.
"""

from dislib_tpu.runtime import xla_flags  # noqa: F401
from dislib_tpu.runtime import compile_cache  # noqa: F401
from dislib_tpu.runtime import health  # noqa: F401
from dislib_tpu.runtime.adoption import (Adoption, AdoptionRejected,
                                         adopt_latest, generation_token)
from dislib_tpu.runtime.bundle_io import (BundleIncompatible,
                                          BundleShardCorrupt, read_bundle,
                                          write_bundle)
from dislib_tpu.runtime.coord import (CapacityLedger, CoordinationTimeout,
                                      FileCoordinator, KVCoordinator,
                                      LeaseKeeper, LocalCoordinator,
                                      Membership, RankDead, TornCoordFile,
                                      barrier_timeout, current_membership,
                                      get_coordinator, lease_seconds,
                                      resilient_exchange, set_membership)
from dislib_tpu.runtime.elastic import AsyncFetch, fetch, repad_rows
from dislib_tpu.runtime.health import (ChunkGuard, HealthPolicy,
                                       NumericalDivergence, WatchdogTimeout)
from dislib_tpu.runtime.preemption import (
    Preempted, PreemptionWatcher, capacity_target, clear_capacity,
    clear_preemption, last_signal, preemption_requested,
    raise_if_preempted, request_capacity, request_preemption,
)
from dislib_tpu.runtime.retry import Retry, is_transient_error, retry_call
from dislib_tpu.runtime.fitloop import (ChunkedFitLoop, ChunkOutcome,
                                        Escalation, EscalationLadder,
                                        LoopState)
from dislib_tpu.runtime.trainer import ContinuousTrainer, PromotionFailed

__all__ = [
    "Preempted", "PreemptionWatcher", "preemption_requested",
    "request_preemption", "clear_preemption", "last_signal",
    "raise_if_preempted",
    "capacity_target", "request_capacity", "clear_capacity",
    "Retry", "retry_call", "is_transient_error",
    "repad_rows", "fetch", "AsyncFetch",
    "HealthPolicy", "ChunkGuard", "NumericalDivergence", "WatchdogTimeout",
    "Adoption", "AdoptionRejected", "adopt_latest", "generation_token",
    "BundleIncompatible", "BundleShardCorrupt", "read_bundle",
    "write_bundle",
    "CapacityLedger", "CoordinationTimeout", "get_coordinator",
    "LocalCoordinator", "FileCoordinator", "KVCoordinator",
    "Membership", "LeaseKeeper", "RankDead", "TornCoordFile",
    "set_membership", "current_membership", "resilient_exchange",
    "lease_seconds", "barrier_timeout",
    "ChunkedFitLoop", "ChunkOutcome", "LoopState", "Escalation",
    "EscalationLadder",
    "ContinuousTrainer", "PromotionFailed",
    "health", "xla_flags", "compile_cache",
]
