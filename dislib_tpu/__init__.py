"""dislib_tpu — a TPU-native distributed machine-learning library.

Capabilities of the reference (Alfredu/dislib — sklearn-style estimators over
one block-partitioned distributed 2-D array; see SURVEY.md), rebuilt TPU-first
on JAX/XLA: the ds-array is a sharded ``jax.Array`` on a named device mesh,
per-block NumPy kernels become jitted sharded compute, COMPSs arity-tree
reductions become ``lax.psum``/``all_gather`` over ICI, and convergence loops
run on-device in ``lax.while_loop``.

Public API parity contract: SURVEY.md §8 "API parity contract".
"""

import os as _os


def _cpu_destined() -> bool:
    """True when this process is headed for the cpu backend (explicit env
    or jax config) — the only case the timeout mutation below targets."""
    if "cpu" in _os.environ.get("JAX_PLATFORMS", ""):
        return True
    import jax as _j
    return "cpu" in (_j.config.jax_platforms or "")


# XLA:CPU aborts the process when a collective participant waits >40 s
# (rendezvous terminate timeout).  On constrained hosts — this build's CI
# rig runs 8 virtual devices on ONE core — a long compile or any co-tenant
# load can legitimately stall a participant that long, turning a slow
# moment into a hard crash.  Raise the abort threshold well past plausible
# stalls (the warn log stays early).  Must be in XLA_FLAGS before the
# backend initialises, hence at import — and only for cpu-destined
# processes, so a TPU job's (or an embedding application's) environment
# is never mutated behind its back.  The injection itself lives in
# runtime.xla_flags (the one site allowed to mutate XLA_FLAGS).
from dislib_tpu.runtime import xla_flags as _xla_flags

if _cpu_destined():
    _xla_flags.inject_cpu_collective_timeouts()

from dislib_tpu.parallel.mesh import init, get_mesh, set_mesh
from dislib_tpu.data.array import (
    Array, array, random_array, zeros, full, ones, identity, eye,
    apply_along_axis, concat_rows, concat_cols, rechunk, ensure_canonical,
)
from dislib_tpu.data.io import (
    load_txt_file, load_svmlight_file, load_npy_file, load_mdcrd_file, save_txt,
    QuarantineLedger, QuarantineReport, last_quarantine_report,
    quarantine_ledger, quarantine_batch,
)
from dislib_tpu.data.sparse import SparseArray
from dislib_tpu.math import matmul, kron, svd, qr, polar
from dislib_tpu.ops.overlap import resolve as overlap_schedule
from dislib_tpu.decomposition import tsqr, random_svd, lanczos_svd, PCA
from dislib_tpu.utils.base import shuffle, train_test_split
from dislib_tpu.utils.saving import save_model, load_model

# subpackages (sklearn-style namespaces, reference parity; `runtime` is
# the preemption/retry/elastic resilience layer, `serving` the
# low-latency predict path with micro-batching and model hot-swap)
from dislib_tpu import cluster, classification, regression, neighbors, \
    preprocessing, optimization, model_selection, recommendation, \
    trees, runtime, serving, retrieval  # noqa: E402,F401

# estimator classes re-exported at top level so every name in the SURVEY §8
# parity contract is importable from `dislib_tpu` directly (their canonical
# homes stay the reference-parity submodules above)
from dislib_tpu.cluster import (KMeans, MiniBatchKMeans, GaussianMixture,
                                DBSCAN, Daura)
from dislib_tpu.classification import CascadeSVM, KNeighborsClassifier
from dislib_tpu.trees import (
    RandomForestClassifier, RandomForestRegressor,
    DecisionTreeClassifier, DecisionTreeRegressor,
)
from dislib_tpu.neighbors import NearestNeighbors
from dislib_tpu.regression import LinearRegression, Lasso
from dislib_tpu.optimization import ADMM
from dislib_tpu.recommendation import ALS
from dislib_tpu.preprocessing import StandardScaler, MinMaxScaler
from dislib_tpu.model_selection import (
    KFold, GridSearchCV, RandomizedSearchCV,
)

__version__ = "0.1.0"

__all__ = [
    "init", "get_mesh", "set_mesh",
    "Array", "array", "random_array", "zeros", "full", "ones", "identity",
    "eye", "apply_along_axis", "concat_rows", "concat_cols", "rechunk",
    "ensure_canonical", "SparseArray",
    "load_txt_file", "load_svmlight_file", "load_npy_file", "load_mdcrd_file",
    "save_txt",
    "QuarantineReport", "QuarantineLedger", "last_quarantine_report",
    "quarantine_ledger", "quarantine_batch",
    "matmul", "kron", "svd", "qr", "polar", "overlap_schedule",
    "tsqr", "random_svd", "lanczos_svd", "PCA",
    "shuffle", "train_test_split", "save_model", "load_model",
    "KMeans", "MiniBatchKMeans", "GaussianMixture", "DBSCAN", "Daura",
    "CascadeSVM", "KNeighborsClassifier",
    "RandomForestClassifier", "RandomForestRegressor",
    "DecisionTreeClassifier", "DecisionTreeRegressor",
    "NearestNeighbors", "LinearRegression", "Lasso", "ADMM", "ALS",
    "StandardScaler", "MinMaxScaler",
    "KFold", "GridSearchCV", "RandomizedSearchCV",
    "runtime", "serving", "retrieval",
]
