"""Host-sync lint (round-7 fusion PR satellite, the `test_xla_flags_policy`
pattern): estimator iteration loops must not read device values back to
host except through the blessed boundaries — `runtime.fetch` (retried,
async-capable, a fusion force point) or an explicit `force()`.

ONE stray `jax.device_get` / `float(device_scalar)` /
`np.asarray(device_val)` inside a fit loop reintroduces a per-iteration
host sync, which idles the device every iteration.  This lint makes that
a CPU test failure instead.

Policy, enforced by AST scan of the estimator packages:

1. inside any `for`/`while` loop, the raw sync spellings — `.device_get`,
   `np.asarray`, `.collect()`, `.block_until_ready()`, `float(<non-const>)`
   — are flagged; `fetch`/`_fetch` never is (it IS the blessed boundary);
2. flagged sites must be on the explicit allowlist below.  Every entry is
   a CHUNK-boundary loop (one sync per k-iteration device chunk, next to
   its snapshot) or the deliberately host-orchestrated irregular tier
   (cascade merges, async-trial collection) — NOT a per-iteration sync.
   Adding a new site means consciously extending the list with a reason.
"""

import ast
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ESTIMATOR_DIRS = (
    "dislib_tpu/cluster",
    "dislib_tpu/classification",
    "dislib_tpu/recommendation",
    "dislib_tpu/trees",
    "dislib_tpu/regression",
    "dislib_tpu/decomposition",
    "dislib_tpu/neighbors",
    "dislib_tpu/optimization",
    "dislib_tpu/model_selection",
    # round-9: the serving hot path — ONE fetch per served batch is the
    # whole design; a stray per-request sync here is the regression the
    # lint exists for
    "dislib_tpu/serving",
    # round-13: the overlap/panel kernels (summa, rechunk, ring, tiled,
    # overlap, pallas_kernels) — a host sync inside a panel loop would
    # serialize the very schedule the overlap PR exists to pipeline
    "dislib_tpu/ops",
    # round-18: the IVF retrieval tier — every list length is
    # host-computed at build; a device sync deciding a shape in the
    # search path would kill the one-dispatch contract
    "dislib_tpu/retrieval",
)

# single FILES scanned alongside the dirs — round-14: the sparse storage
# layer hosts the sharded buffers every sparse fast path consumes; a
# stray in-loop sync there would serialize every consumer at once.  (Its
# siblings io.py/array.py are host ingest/parsing by design.)
EXTRA_FILES = ("dislib_tpu/data/sparse.py",)

# (file, enclosing function) pairs allowed to host-sync inside a loop,
# each with the reason it is a boundary and not a per-iteration sync.
ALLOWLIST = {
    # (round-12: the chunked fit loops moved onto runtime.fitloop's
    # ChunkedFitLoop — their boundary syncs are the driver's now, and the
    # kmeans/gm/als fit() entries are gone: the lint's desired end state.
    # The estimator `step` closures sync only their chunk's convergence
    # scalars, OUTSIDE any estimator-file loop, except the cascade below.)
    # cascade SVM: the irregular tier — level merges are host-planned by
    # design (SURVEY §3.3), one sync per cascade level inside step()'s
    # level loop, never per solver iteration (those run in
    # lax.while_loop on device)
    ("dislib_tpu/classification/csvm.py", "step"),
    ("dislib_tpu/classification/csvm.py", "_merge_level"),
    ("dislib_tpu/classification/csvm.py", "k_of"),
    # (_solve_level_batched left the list in round-17: its batch loop now
    # pipelines through ops/overlap.host_pipeline — the blocking reads
    # live in the shared discipline, not in an estimator-file loop.)
    # async-trial grid search: block_until_ready/float AFTER every trial
    # of a fold is dispatched — the protocol's single collection point
    ("dislib_tpu/model_selection/search.py", "_block_tree"),
    ("dislib_tpu/model_selection/search.py", "_dispatch_fold"),
    ("dislib_tpu/model_selection/search.py", "fit"),
    # serving AOT warmup: one sync per BUCKET at warm time (adoption /
    # server start), never on the request path — the hot path's only
    # sync is the blessed runtime.fetch inside predict_bucket
    ("dislib_tpu/serving/cache.py", "warm"),
    # round-15 bundle EXPORT: one sync per operand leaf while serializing
    # the compiled ladder to disk — offline deployment packaging by
    # definition; the bundle's serve path (BundlePipeline.predict_bucket)
    # syncs only through the blessed runtime.fetch
    ("dislib_tpu/serving/bundle.py", "export_bundle"),
    # round-19 split export_bundle into the shared AOT-capture loop and
    # the sharded-fleet writer — the SAME offline packaging boundary as
    # the export_bundle entry above, one sync per leaf/state value at
    # export time, never on the serve path
    ("dislib_tpu/serving/bundle.py", "_capture_entries"),
    ("dislib_tpu/serving/bundle.py", "_export_sharded"),
}

_RAW_SYNC_ATTRS = ("device_get", "collect", "block_until_ready")


def _sync_calls(loop_node):
    """Raw host-sync spellings inside one loop body."""
    hits = []
    for sub in ast.walk(loop_node):
        if not isinstance(sub, ast.Call):
            continue
        f = sub.func
        if isinstance(f, ast.Attribute):
            if f.attr in _RAW_SYNC_ATTRS:
                hits.append(f.attr)
            elif f.attr == "asarray" and isinstance(f.value, ast.Name) \
                    and f.value.id in ("np", "numpy"):
                hits.append("np.asarray")
        elif isinstance(f, ast.Name):
            if f.id == "float" and sub.args \
                    and not isinstance(sub.args[0], ast.Constant):
                hits.append("float")
    return hits


def _scan(path):
    """Yield (function_name, lineno, syncs) for every loop with raw syncs."""
    tree = ast.parse(open(path, encoding="utf-8").read())

    def walk(node, fname):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from walk(child, child.name)
            else:
                if isinstance(child, (ast.For, ast.While)):
                    syncs = _sync_calls(child)
                    if syncs:
                        yield fname, child.lineno, sorted(set(syncs))
                yield from walk(child, fname)

    yield from walk(tree, "<module>")


def _estimator_files():
    for d in ESTIMATOR_DIRS:
        full = os.path.join(REPO, d)
        for fn in sorted(os.listdir(full)):
            if fn.endswith(".py"):
                yield f"{d}/{fn}", os.path.join(full, fn)
    for rel in EXTRA_FILES:
        yield rel, os.path.join(REPO, rel)


def test_no_unblessed_host_syncs_in_estimator_loops():
    offenders = []
    for rel, full in _estimator_files():
        for fname, lineno, syncs in _scan(full):
            if (rel, fname) not in ALLOWLIST:
                offenders.append(f"{rel}:{lineno} in {fname}(): {syncs}")
    assert not offenders, (
        "raw host syncs inside estimator iteration loops — route them "
        "through runtime.fetch (or force()) at a chunk boundary, or "
        "consciously extend the lint allowlist with a reason:\n  "
        + "\n  ".join(offenders))


# ---------------------------------------------------------------------------
# round-11 rechunk PR: host-numpy RESHARDING lint.  Estimator/pipeline
# code may not re-pad / re-lay out array data through host numpy —
# resharding flows through `ds.rechunk` (on-device collective) or
# `runtime.repad_rows` (the blessed elastic boundary, which itself
# routes device inputs on-device).  `np.pad` is the telltale spelling of
# a host reshard; the AST scan covers WHOLE files (not just loops),
# because a single one-shot host re-pad of a sharded operand still
# gathers the array through the host.
# ---------------------------------------------------------------------------

# (file, enclosing function) pairs allowed to np.pad, each a HOST-side
# ingest/serialization boundary, never a device-array reshard:
RESHARD_ALLOWLIST = {
    # cascade labels arrive host-side by design (SURVEY §3.3) and are
    # padded BEFORE first device_put — ingest, not a reshard
    ("dislib_tpu/classification/csvm.py", "fit"),
    # adoption packs ragged per-level host copies into the model's host
    # attrs (post-device_get serialization, not a layout move)
    ("dislib_tpu/trees/decision_tree.py", "_pack"),
    # elastic snapshot restore: re-pads the VERIFIED HOST snapshot state
    # to this mesh's pad width before its first device_put — the blessed
    # resize boundary itself (ingest of host bytes, not a device-array
    # gather); the density/greedy carries are integer label vectors, so
    # repad_rows' float row machinery does not apply
    ("dislib_tpu/cluster/daura.py", "restore"),
    ("dislib_tpu/cluster/dbscan.py", "restore"),
    # elastic rebind (round 14): re-pads the HOST ±1 label vector kept
    # from fit ingest to the resized mesh's pad width before device_put —
    # ingest-side twin of the restore() entries above
    ("dislib_tpu/classification/csvm.py", "rebind"),
}


def _np_pad_calls(path):
    """(enclosing_function, lineno) of every np.pad/numpy.pad call."""
    tree = ast.parse(open(path, encoding="utf-8").read())

    def walk(node, fname):
        for child in ast.iter_child_nodes(node):
            cname = fname
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cname = child.name
            if isinstance(child, ast.Call) \
                    and isinstance(child.func, ast.Attribute) \
                    and child.func.attr == "pad" \
                    and isinstance(child.func.value, ast.Name) \
                    and child.func.value.id in ("np", "numpy"):
                yield fname, child.lineno
            yield from walk(child, cname)

    yield from walk(tree, "<module>")


def test_no_host_numpy_resharding_in_estimators():
    offenders = []
    for rel, full in _estimator_files():
        for fname, lineno in _np_pad_calls(full):
            if (rel, fname) not in RESHARD_ALLOWLIST:
                offenders.append(f"{rel}:{lineno} in {fname}()")
    assert not offenders, (
        "host-numpy resharding (np.pad) in estimator/pipeline code — "
        "reshard through ds.rechunk (on-device collective) or "
        "runtime.repad_rows (elastic boundary), or consciously extend "
        "RESHARD_ALLOWLIST with a reason:\n  " + "\n  ".join(offenders))


def test_reshard_allowlist_entries_still_exist():
    live = set()
    for rel, full in _estimator_files():
        for fname, _ in _np_pad_calls(full):
            live.add((rel, fname))
    dead = {site for site in RESHARD_ALLOWLIST if site not in live}
    assert not dead, f"reshard allowlist entries match no code: {dead}"


def test_allowlist_entries_still_exist():
    """A refactor that renames or removes an allowlisted loop must prune
    the list — dead entries would quietly bless future regressions."""
    live = set()
    for rel, full in _estimator_files():
        for fname, _, _ in _scan(full):
            live.add((rel, fname))
    dead = {site for site in ALLOWLIST if site not in live}
    assert not dead, f"allowlist entries no longer match any code: {dead}"
