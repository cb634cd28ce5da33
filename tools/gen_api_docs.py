"""Generate docs/api.md from the package's public surface.

Deterministic introspection dump: every SURVEY §8 parity name plus the
estimator submodules, with signatures and first docstring paragraphs.
Run: python tools/gen_api_docs.py  (CPU; does not touch the TPU).
"""

import inspect
import os
import sys

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import dislib_tpu as ds  # noqa: E402

SECTIONS = [
    ("Mesh / parallel", "dislib_tpu",
     ["init", "get_mesh", "set_mesh"]),
    ("ds-array construction", "dislib_tpu",
     ["array", "random_array", "zeros", "full", "ones", "identity", "eye",
      "apply_along_axis", "concat_rows", "concat_cols"]),
    ("Rechunk / redistribution", "dislib_tpu",
     ["rechunk", "ensure_canonical"]),
    ("DCN-aware hierarchical rechunk (multi-host)", "dislib_tpu.ops.rechunk",
     ["dcn_accounting", "dcn_supported", "pick_schedule"]),
    ("Host topology (real map or DSLIB_MOCK_HOSTS overlay)",
     "dislib_tpu.parallel.hosts",
     ["host_of", "host_map", "n_hosts", "mock_hosts", "host_blocks"]),
    ("I/O", "dislib_tpu",
     ["load_txt_file", "load_svmlight_file", "load_npy_file",
      "load_mdcrd_file", "save_txt"]),
    ("Array / SparseArray", "dislib_tpu", ["Array", "SparseArray"]),
    ("Sharded sparse fast path", "dislib_tpu.data.sparse",
     ["ShardedSparse", "nse_quantum"]),
    ("Sparse matmul (masked-psum SpMM)", "dislib_tpu.ops.spmm",
     ["spmm", "spmm_steps", "spmm_memory_analysis", "spmm_masking_work"]),
    ("Blocked linear algebra", "dislib_tpu",
     ["matmul", "kron", "svd", "qr", "polar", "tsqr", "random_svd",
      "lanczos_svd"]),
    ("Precision policy (mixed-precision linalg)", "dislib_tpu.ops.precision",
     ["Policy", "resolve", "to_compute", "f32", "pdot", "pdot_short",
      "packs_short", "short_left", "short_right", "pdot_packed",
      "pdot_tall", "packs_tall", "peinsum", "precise"]),
    ("Overlap schedules (comm–compute pipelining)", "dislib_tpu.ops.overlap",
     ["resolve", "overlapped", "panel_pipeline", "round_pipeline",
      "host_pipeline"]),
    ("Pallas kernels", "dislib_tpu.ops.pallas_kernels",
     ["panel_gemm", "distances_sq", "node_histogram"]),
    ("Decomposition", "dislib_tpu", ["PCA"]),
    ("Clustering", "dislib_tpu.cluster",
     ["KMeans", "MiniBatchKMeans", "GaussianMixture", "DBSCAN", "Daura"]),
    ("Classification", "dislib_tpu.classification",
     ["CascadeSVM", "KNeighborsClassifier"]),
    ("Trees", "dislib_tpu.trees",
     ["RandomForestClassifier", "RandomForestRegressor",
      "DecisionTreeClassifier", "DecisionTreeRegressor"]),
    ("Neighbors", "dislib_tpu.neighbors", ["NearestNeighbors"]),
    ("Regression / optimization", "dislib_tpu",
     ["LinearRegression", "Lasso", "ADMM"]),
    ("Recommendation", "dislib_tpu", ["ALS"]),
    ("Preprocessing", "dislib_tpu", ["StandardScaler", "MinMaxScaler"]),
    ("Model selection", "dislib_tpu.model_selection",
     ["KFold", "GridSearchCV", "RandomizedSearchCV"]),
    ("Utilities", "dislib_tpu",
     ["shuffle", "train_test_split", "save_model", "load_model"]),
    ("Checkpointing", "dislib_tpu.utils.checkpoint",
     ["FitCheckpoint", "SnapshotCorrupt"]),
    ("Resilience runtime", "dislib_tpu.runtime",
     ["Preempted", "PreemptionWatcher", "preemption_requested",
      "request_preemption", "clear_preemption", "raise_if_preempted",
      "capacity_target", "request_capacity", "clear_capacity",
      "Retry", "retry_call", "is_transient_error", "repad_rows", "fetch",
      "AsyncFetch"]),
    ("Health runtime (self-healing fits)", "dislib_tpu.runtime.health",
     ["HealthPolicy", "ChunkGuard", "Verdict", "Remediation",
      "NumericalDivergence", "WatchdogTimeout", "guard", "health_vec",
      "check_snapshot"]),
    ("Chunked fit-loop driver (resilient-by-construction estimators)",
     "dislib_tpu.runtime",
     ["ChunkedFitLoop", "LoopState", "ChunkOutcome", "EscalationLadder",
      "Escalation"]),
    ("Checkpoint adoption (hot-swap read gate)", "dislib_tpu.runtime",
     ["Adoption", "AdoptionRejected", "adopt_latest", "generation_token"]),
    ("Serving", "dislib_tpu.serving",
     ["ServePipeline", "PredictServer", "ServeResponse", "ModelPool",
      "ProgramCache", "bucket_ladder", "bucket_for", "split_rows",
      "SparseFoldInPipeline", "pack_sparse_rows",
      "BucketLadderError", "QueueFull", "ShardDrained"]),
    ("Deployment bundles (AOT serving artifacts)", "dislib_tpu.serving",
     ["export_bundle", "load_bundle", "runtime_fingerprint",
      "BundlePipeline", "LoadedBundle"]),
    ("Bundle I/O (checksummed artifact seam)", "dislib_tpu.runtime",
     ["write_bundle", "read_bundle", "BundleIncompatible",
      "BundleShardCorrupt"]),
    ("Coordination service (multi-host control plane)", "dislib_tpu.runtime",
     ["get_coordinator", "LocalCoordinator", "FileCoordinator",
      "KVCoordinator", "CoordinationTimeout", "CapacityLedger"]),
    ("Membership & lease-based fault tolerance", "dislib_tpu.runtime",
     ["Membership", "LeaseKeeper", "RankDead", "TornCoordFile",
      "resilient_exchange", "set_membership", "current_membership"]),
    ("Multi-tenant routing", "dislib_tpu.serving",
     ["ModelRouter", "TenantQuotaExceeded", "DeadlineShed"]),
    ("Vector retrieval (IVF-ANN search tier)", "dislib_tpu.retrieval",
     ["IVFIndex", "RetrievalPipeline"]),
    ("Continuous-learning trainer (train → bundle → canary → promote)",
     "dislib_tpu.runtime",
     ["ContinuousTrainer", "PromotionFailed"]),
    ("Ingest quarantine", "dislib_tpu",
     ["QuarantineReport", "QuarantineLedger", "last_quarantine_report",
      "quarantine_ledger", "quarantine_batch"]),
    ("Fault injection", "dislib_tpu.utils.faults",
     ["CallbackCheckpoint", "SigtermAtNthSave", "corrupt_snapshot",
      "FlakyCall", "FlakyOpen",
      "NaNAtChunk", "DivergenceRamp", "HangAtChunk", "TripAtChunk",
      "FaultAtTier", "CapacityAtSave", "oscillation_schedule",
      "TornBundleWrite", "CanaryGateTrip",
      "KillRankAt", "LeaseExpiry", "TornCoordWrite"]),
    ("Profiling", "dislib_tpu.utils.profiling",
     ["trace", "span", "new_call", "span_totals", "annotate", "op_graph",
      "profiled_jit", "dispatch_count",
      "trace_count", "transfer_count", "counters", "reset_counters",
      "count_resilience", "resilience_counters",
      "count_schedule", "schedule_counters",
      "program_scopes", "clear_programs"]),
    ("Distributed (multi-host)", "dislib_tpu.parallel.distributed",
     ["initialize", "is_initialized", "process_info", "shutdown"]),
]


def first_para(doc):
    if not doc:
        return "(no docstring)"
    out = []
    for line in inspect.cleandoc(doc).splitlines():
        if not line.strip():
            break
        out.append(line.strip())
    return " ".join(out)


def sig_of(obj):
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(…)"


def methods_of(cls):
    """Public methods, including ones inherited from intermediate bases
    (mixins / shared ensemble bases) — but not the BaseEstimator plumbing
    every estimator shares (get_params/set_params)."""
    from dislib_tpu.base import BaseEstimator
    rows = {}
    for klass in reversed(cls.__mro__):
        if klass in (object, BaseEstimator):
            continue
        for name, fn in vars(klass).items():
            if name.startswith("_") or not callable(fn):
                continue
            rows[name] = (name, sig_of(fn), first_para(fn.__doc__))
    return [rows[k] for k in sorted(rows)]


def main():
    import importlib
    lines = ["# dislib_tpu API reference",
             "",
             "Generated by `tools/gen_api_docs.py` — regenerate after "
             "changing public signatures. Reference-parity contract: "
             "SURVEY.md §8.", ""]
    for title, modname, names in SECTIONS:
        mod = importlib.import_module(modname)
        lines.append(f"## {title}")
        lines.append("")
        for n in names:
            obj = getattr(mod, n)
            if inspect.isclass(obj):
                init_sig = sig_of(obj.__init__).replace("(self, ", "(") \
                    .replace("(self)", "()")
                lines.append(f"### `{modname}.{n}{init_sig}`")
                lines.append("")
                lines.append(first_para(obj.__doc__))
                meths = methods_of(obj)
                if meths:
                    lines.append("")
                    for m, s, d in meths:
                        sig = s.replace('(self, ', '(').replace('(self)', '()')
                        # sklearn-convention methods are documented by the
                        # class docstring; suppress the no-docstring note
                        if d == "(no docstring)":
                            lines.append(f"- `.{m}{sig}`")
                        else:
                            lines.append(f"- `.{m}{sig}` — {d}")
                lines.append("")
            else:
                lines.append(f"### `{modname}.{n}{sig_of(obj)}`")
                lines.append("")
                lines.append(first_para(obj.__doc__))
                lines.append("")
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "api.md")
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {out} ({len(lines)} lines)")


if __name__ == "__main__":
    main()
