"""dislib_tpu.serving — the low-latency predict path (ROADMAP item 1:
the "millions of users" serving mode).

Training hardened (PRs 1–3), `predict` was still a per-call afterthought:
every request paid tracing/compile risk for its exact shape and a
dispatch round trip per op, and there was no way to serve a model while its successor trains.  This
package makes one served batch cost **one cached XLA dispatch
end-to-end**, from four pieces that compose:

- **padded batch buckets** (``buckets.py``) — requests pad to a small
  ladder of fixed row counts (default 1/8/64/512,
  ``DSLIB_SERVE_BUCKETS``), so the entire serving lifetime touches a
  handful of program shapes, all compiled at warmup.  Predict is
  row-independent, so padded rows can never affect real rows' results;
  their outputs are sliced away before the response.
- **one-dispatch pipelines** (``pipeline.py``) — a scaler → estimator →
  argmax/decision chain linearizes through the round-7 fusion layer
  (every estimator predict is a ``fused_kernel`` graph node since this
  round) into ONE cached XLA program per bucket.
- **program cache + AOT warmup** (``cache.py``) — the (model generation,
  bucket shape) ledger over XLA's executable cache: a generation serves
  only after every bucket is warmed and health-gated, so the request hot
  path never compiles and never meets an unvalidated model.
- **micro-batching + hot-swap** (``server.py`` / ``hotswap.py``) — queued
  requests coalesce into the smallest covering bucket under a latency
  deadline (``DSLIB_SERVE_DEADLINE_MS``), and the served model follows a
  rotating ``FitCheckpoint`` through the ``runtime.adoption`` gate: serve
  generation N while N+1 trains, adopting N+1 only after its checksum
  verifies and its warmup predict passes the health guard.
- **sparse fold-in serving** (``sparse.py``, round 14) — recommender
  requests arrive as PADDED SPARSE batches (``[cols | vals]`` rows, the
  fixed-width encoding) and serve through the same bucket
  ladder/server/pool machinery as one fused ALS fold-in dispatch per
  batch: score a brand-new user against the trained factors with no
  refit and no densified request vector.
- **AOT deployment bundles** (``bundle.py``, round 15) — the compiled
  predict executables for the WHOLE bucket ladder serialize into one
  checksum-verified artifact (``export_bundle``); a fresh process
  rehydrates it into a ``PredictServer``-ready pipeline with ZERO
  retraces (``load_bundle``), refusing typed-and-loud
  (``BundleIncompatible``) when jax/topology fingerprints mismatch.
- **multi-tenant routing** (``router.py``, round 15) — ``ModelRouter``
  maps tenants onto shared servers (shared shape ladder → shared
  compiled executables, ~zero extra compiles), adds per-tenant
  admission quotas (typed ``TenantQuotaExceeded`` sheds only the
  offender), and hash-splits canary/A-B traffic with a health-gated
  ``promote``.

See the user guide's "Serving & hot-swap" and "Deployment bundles &
multi-tenant serving" sections for the end-to-end story.  No benchmark
cell serves yet: serving throughput and latency on the chip are not
measured (``PERF.md`` section 7 lists the cell that would).
"""

from dislib_tpu.serving.buckets import (DEFAULT_BUCKETS, BucketLadderError,
                                        bucket_for, bucket_ladder,
                                        split_rows)
from dislib_tpu.serving.bundle import (BundlePipeline, LoadedBundle,
                                       export_bundle, load_bundle,
                                       runtime_fingerprint)
from dislib_tpu.serving.cache import ProgramCache
from dislib_tpu.serving.hotswap import ModelPool
from dislib_tpu.serving.pipeline import ServePipeline
from dislib_tpu.serving.router import (DeadlineShed, ModelRouter,
                                       TenantQuotaExceeded)
from dislib_tpu.serving.server import (PredictServer, QueueFull,
                                       ServeResponse, ShardDrained)
from dislib_tpu.serving.sparse import SparseFoldInPipeline, pack_sparse_rows

__all__ = [
    "DEFAULT_BUCKETS", "BucketLadderError", "bucket_ladder", "bucket_for",
    "split_rows",
    "ProgramCache", "ServePipeline", "PredictServer", "ServeResponse",
    "QueueFull", "ShardDrained", "ModelPool",
    "SparseFoldInPipeline", "pack_sparse_rows",
    "export_bundle", "load_bundle", "BundlePipeline", "LoadedBundle",
    "runtime_fingerprint",
    "ModelRouter", "TenantQuotaExceeded", "DeadlineShed",
]
