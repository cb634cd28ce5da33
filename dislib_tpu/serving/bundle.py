"""AOT deployment bundles — kill serving cold-start (round-15 tentpole).

PR 4 made the warm path one cached dispatch, but a FRESH process still
pays the full trace+compile for every bucket shape before its first
response (~300 ms/bucket on this rig, tens of seconds per bucket at chip
scale).  The full-program-compilation discipline of arXiv:1810.09868
says the whole predict program is an ahead-of-time artifact — so make
it one: :func:`export_bundle` serializes the COMPILED predict
executables for the whole bucket ladder (``jax.jit`` AOT
``lower().compile()`` + ``jax.experimental.serialize_executable``),
their operand leaves (model parameters, padded exactly as the programs
expect), the bucket ladder, and the checksum-verified model state into
ONE versioned artifact; :func:`load_bundle` rehydrates a
``PredictServer``-ready pipeline in a fresh process with ZERO retraces
(trace-counter-pinned by ``tests/test_serving_fleet.py``).

Failure discipline, typed and loud:

- damaged bytes (truncation, bit rot, foreign file) raise
  ``SnapshotCorrupt`` from the verified reader — serving never builds a
  pipeline from bytes that fail their checksum;
- a fingerprint mismatch (different jax/jaxlib, platform, device kind or
  count, mesh shape, pad quantum — anything that invalidates a compiled
  executable) raises :class:`~dislib_tpu.runtime.BundleIncompatible`;
  pass ``build=`` to fall back LOUDLY to a fresh trace+compile from the
  bundle's embedded (still checksum-verified) model state instead.

All artifact bytes flow through ``runtime.bundle_io`` (the write/read
seam) and checkpoint state flows through the ``runtime.adoption`` gate —
both enforced by the serving lints in ``tests/test_serving.py``.
"""

from __future__ import annotations

import json
import os
import warnings

import numpy as np

from dislib_tpu.runtime import adopt_latest, fetch as _fetch
from dislib_tpu.runtime.bundle_io import (BundleIncompatible,
                                          BundleShardCorrupt, file_crc,
                                          read_bundle, shard_path,
                                          write_bundle)
from dislib_tpu.serving.buckets import BucketTemplate, bucket_ladder
from dislib_tpu.utils import profiling as _prof

BUNDLE_FORMAT = 1

# meta entry key inside the artifact (everything else is per-bucket
# payload/leaf arrays and ``state__``-prefixed model state)
_META_KEY = "bundle_meta"
_STATE_PREFIX = "state__"

# fingerprint keys that MUST match for a serialized executable to run;
# anything else in the fingerprint is informational (statics provenance)
_HARD_KEYS = ("format", "jax", "jaxlib", "platform", "device_kind",
              "n_devices", "mesh_shape", "pad_quantum")

# a SHARDED bundle replaces the global-shape pins (device count, mesh
# shape) with the manifest's mesh CONTRACT — hosts × devices-per-host —
# so a bundle exported on one fleet layout loads on any fleet honoring
# the contract, not only a bit-identical process (round 19)
_SHARD_HARD_KEYS = tuple(k for k in _HARD_KEYS
                         if k not in ("n_devices", "mesh_shape"))


def runtime_fingerprint() -> dict:
    """The compatibility identity of THIS process for serialized
    executables: library format version, jax/jaxlib versions, device
    platform/kind/count, mesh shape, and pad quantum (it shapes every
    padded operand), plus informational statics (the overlap router
    mode and fusion cap the programs were traced under).  Hard keys
    (everything except ``statics``) must match between the exporting
    and loading process; ``load_bundle`` refuses typed-and-loud on any
    difference."""
    import jax
    import jaxlib

    from dislib_tpu.parallel import mesh as _mesh
    devs = jax.devices()
    return {
        "format": BUNDLE_FORMAT,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices": len(devs),
        "mesh_shape": list(_mesh.mesh_shape(None)),
        "pad_quantum": int(_mesh.pad_quantum()),
        "statics": {
            "overlap": os.environ.get("DSLIB_OVERLAP", "db"),
            "fusion_cap": os.environ.get("DSLIB_FUSION_CAP", "96"),
        },
    }


def _capture_bucket(pipeline, bucket: int):
    """AOT-capture one bucket's predict program WITHOUT executing it:
    build the deferred chain on a placeholder input, linearize it, and
    ``lower().compile()`` the fused program exactly as the first warm
    dispatch would have.  Returns everything a fresh process needs to
    re-invoke the compiled executable: the serialized payload, the
    canonicalized operand leaves, the input leaf's slot, and the output
    metadata."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.serialize_executable import serialize

    from dislib_tpu.data.array import (Array, _exec_program, _linearize,
                                       _padded_shape)
    from dislib_tpu.parallel import mesh as _mesh

    pshape = _padded_shape((bucket, pipeline.n_features),
                           _mesh.pad_quantum())
    placeholder = jax.device_put(np.zeros(pshape, np.float32),
                                 _mesh.data_sharding())
    out = pipeline(Array(placeholder, (bucket, pipeline.n_features)))
    if not out.is_lazy:
        raise RuntimeError(
            "the predict chain forced during capture — the pipeline is "
            "not exportable as one fused program (DSLIB_EAGER=1, or the "
            "chain exceeds DSLIB_FUSION_CAP); disable eager mode or "
            "raise the cap to export a bundle")
    program, leaves, _shared = _linearize(out._lazy)
    slots = [i for i, leaf in enumerate(leaves) if leaf is placeholder]
    if len(slots) != 1:
        raise RuntimeError(
            f"bucket {bucket}: expected the request buffer to be exactly "
            f"one program leaf, found {len(slots)} — the pipeline does "
            "not consume its input as a single operand")
    # canonicalize every leaf to a committed device array so the lowered
    # avals (dtype, weak_type) match what a host→device round trip of
    # the stored leaf reproduces at load time
    canon = [jnp.asarray(leaf) for leaf in leaves]
    compiled = _exec_program.lower(program, *canon).compile()
    payload, _in_tree, out_tree = serialize(compiled)
    return {
        "payload": np.frombuffer(payload, np.uint8),
        "leaves": canon,
        "input_slot": slots[0],
        "n_outs": out_tree.num_leaves,
        "out_cols": int(out.shape[1]),
        "pshape": list(pshape),
    }


def _resolve_state(checkpoint, state):
    if checkpoint is not None and state is not None:
        raise ValueError("pass at most one of checkpoint= or state=")
    if checkpoint is not None:
        adoption = adopt_latest(checkpoint, build=lambda s: s,
                                name="bundle-export")
        if adoption is None:
            raise ValueError(
                "checkpoint has no generation to embed — save one before "
                "exporting a bundle")
        state = adoption.state
    return state


def _capture_entries(pipeline, buckets):
    """Run the per-bucket AOT capture loop once: the payload/leaf entry
    dict plus the manifest's ``per_bucket`` metadata."""
    entries: dict = {}
    per_bucket: dict = {}
    for b in buckets:
        # capture protocol (round 18): pipelines whose predict program is
        # not a fusion-chain lazy array (the retrieval tier's shard_map
        # search, the sparse fold-in) AOT-capture their own kernel via a
        # ``capture_bucket`` method returning the same dict shape; the
        # fusion-chain linearizer stays the default
        if hasattr(pipeline, "capture_bucket"):
            cap = pipeline.capture_bucket(b)
        else:
            cap = _capture_bucket(pipeline, b)
        entries[f"exec_{b}"] = cap["payload"]
        for i, leaf in enumerate(cap["leaves"]):
            # one device→host sync per leaf at EXPORT time (offline by
            # definition); the serving hot path never comes through here
            entries[f"leaf_{b}_{i}"] = np.asarray(leaf)
        per_bucket[str(b)] = {
            "input_slot": cap["input_slot"],
            "n_leaves": len(cap["leaves"]),
            "n_outs": cap["n_outs"],
            "out_cols": cap["out_cols"],
            "pshape": cap["pshape"],
            "device_ids": _device_ids(cap["leaves"][cap["input_slot"]]),
        }
    return entries, per_bucket


def _device_ids(leaf) -> list:
    """Ids of the devices a captured program runs on, in assignment
    order, read off its (committed) input leaf: the mesh's devices for a
    mesh-sharded program, the one device of a single-device one.  The
    loader must hand exactly these to ``deserialize_and_load``, whose
    default is EVERY device of the backend — wrong for a program
    compiled for fewer (the single-device sparse fold-in on a multi-chip
    host, a fit shrunk onto a mesh prefix)."""
    sharding = leaf.sharding
    mesh = getattr(sharding, "mesh", None)
    devices = mesh.devices.flat if mesh is not None else sharding.device_set
    return [int(d.id) for d in devices]


def export_bundle(pipeline, path: str, buckets=None, checkpoint=None,
                  state=None, hosts=None) -> dict:
    """Serialize ``pipeline``'s compiled predict executables for every
    ladder bucket into ONE versioned artifact at ``path``.

    Parameters
    ----------
    pipeline : ServePipeline — the fitted chain to export.  Its fused
        program per bucket is lowered and compiled ahead of time (the
        export pays the traces so the loading process never does).
    path : str — artifact file (atomic write, embedded checksum).
    buckets : bucket ladder; default per
        :func:`~dislib_tpu.serving.buckets.bucket_ladder`.
    checkpoint : FitCheckpoint, optional — embed the newest generation's
        model state, read THROUGH the ``runtime.adoption`` gate
        (checksum verify + non-finite state gate), so the artifact's
        state carries the same trust as a hot-swap adoption.
    state : dict, optional — embed an explicit state dict instead (the
        caller already holds verified state).  Mutually exclusive with
        ``checkpoint``.
    hosts : int, optional — write a SHARDED bundle for an N-host fleet
        instead: one ``<path>.shard<r>`` artifact per host plus the
        manifest at ``path`` (per-shard checksums, runtime fingerprint,
        mesh contract).  ``load_bundle`` on such a manifest runs the
        coordinated load barrier — every host verifies its shard before
        ANY host serves.  In a multi-process job each process writes its
        own shard (``hosts`` must equal the process count, rank 0 writes
        the manifest); a single process writes all N shards — the mock
        fleet used by tier-1 and by offline export-for-a-fleet.

    Returns the manifest dict (also embedded in the artifact).
    """
    state = _resolve_state(checkpoint, state)
    buckets = bucket_ladder(buckets)
    if hosts is not None:
        return _export_sharded(pipeline, path, buckets, state, int(hosts))
    entries, per_bucket = _capture_entries(pipeline, buckets)
    manifest: dict = {"format": BUNDLE_FORMAT,
                      "fingerprint": runtime_fingerprint(),
                      "buckets": list(buckets),
                      "n_features": int(pipeline.n_features),
                      "per_bucket": per_bucket}
    if state is not None:
        for k, v in state.items():
            entries[_STATE_PREFIX + k] = np.asarray(v)
    entries[_META_KEY] = np.asarray(json.dumps(manifest))
    write_bundle(path, entries)
    return manifest


def _mesh_contract(hosts: int) -> dict:
    """What a loading fleet must LOOK like for the shards to serve: the
    host count, each host's device count, and the padded-layout facts
    (mesh shape, pad quantum) the executables were compiled against.
    This replaces the flat bundle's exact ``n_devices`` pin — any fleet
    honoring the contract can load, not only the exporting process."""
    import jax

    from dislib_tpu.parallel import mesh as _mesh
    n = len(jax.devices())
    if n % hosts:
        raise ValueError(
            f"export_bundle(hosts={hosts}): {n} devices do not split "
            f"evenly across {hosts} hosts — the mesh contract needs a "
            "uniform per-host device count")
    return {"hosts": int(hosts), "devices_per_host": n // hosts,
            "mesh_shape": list(_mesh.mesh_shape(None)),
            "pad_quantum": int(_mesh.pad_quantum())}


def _export_sharded(pipeline, path, buckets, state, hosts: int) -> dict:
    import jax

    from dislib_tpu.runtime.coord import get_coordinator
    if hosts < 1:
        raise ValueError(f"export_bundle(hosts={hosts}): need >= 1")
    pc = jax.process_count()
    if pc > 1 and hosts != pc:
        raise ValueError(
            f"export_bundle(hosts={hosts}) in a {pc}-process job: each "
            "process writes exactly its own shard, so hosts must equal "
            "the process count")
    contract = _mesh_contract(hosts)
    entries, per_bucket = _capture_entries(pipeline, buckets)
    if state is not None:
        for k, v in state.items():
            entries[_STATE_PREFIX + k] = np.asarray(v)
    common = {"format": BUNDLE_FORMAT, "sharded": True,
              "hosts": int(hosts),
              "fingerprint": runtime_fingerprint(),
              "buckets": list(buckets),
              "n_features": int(pipeline.n_features),
              "per_bucket": per_bucket,
              "mesh_contract": contract}
    my_ranks = [jax.process_index()] if pc > 1 else range(hosts)
    for r in my_ranks:
        shard_meta = dict(common, host=int(r), hosts=int(hosts))
        shard_entries = dict(entries)
        shard_entries[_META_KEY] = np.asarray(json.dumps(shard_meta))
        write_bundle(shard_path(path, r), shard_entries)
    # gather every shard's file checksum, then rank 0 publishes the
    # manifest; the exchange doubles as the export barrier (no manifest
    # can name a shard that is not fully on disk)
    base = os.path.basename(path)
    if pc > 1:
        from dislib_tpu.runtime.coord import resilient_exchange
        coord = get_coordinator()
        mine = file_crc(shard_path(path, jax.process_index()))
        crcs = resilient_exchange(coord, f"bundle-export:{base}",
                                  jax.process_index(), mine, hosts)
        shard_crcs = [int(crcs[r]) for r in range(hosts)]
    else:
        shard_crcs = [file_crc(shard_path(path, r)) for r in range(hosts)]
    manifest = dict(common, shard_crcs=shard_crcs)
    if pc <= 1 or jax.process_index() == 0:
        write_bundle(path, {_META_KEY: np.asarray(json.dumps(manifest))})
    if pc > 1:
        # all ranks block until the manifest is on disk (rank 0 posts
        # after its atomic write) — export returns only when loadable
        get_coordinator().exchange(f"bundle-manifest:{base}",
                                   jax.process_index(), True, n=hosts)
    return manifest


class _BucketExec:
    """One bucket's rehydrated executable: the loaded compiled program,
    its device-placed static leaves (model parameters — transferred once
    at load, never per request), the input slot, and output metadata."""

    __slots__ = ("call", "args", "input_slot", "in_sharding", "out_cols",
                 "template")

    def __init__(self, call, args, input_slot, in_sharding, out_cols,
                 pshape):
        self.call = call
        self.args = args
        self.input_slot = input_slot
        self.in_sharding = in_sharding
        self.out_cols = out_cols
        self.template = BucketTemplate(pshape)


class BundlePipeline:
    """A ``PredictServer``-ready pipeline rehydrated from a deployment
    bundle: ``predict_bucket`` is host staging → one input transfer →
    ONE deserialized-executable invocation → fetch → slice, with ZERO
    tracing anywhere (there is no traceable Python body left — the
    program is bytes).  Dispatches are counted under ``bundle_exec`` so
    the server's one-dispatch-per-batch invariant stays a counter
    assertion on this path too.

    Not thread-safe (same contract as ``ServePipeline``): the serving
    worker or one caller drives it.
    """

    def __init__(self, buckets, n_features, execs):
        self.buckets = tuple(buckets)
        self.n_features = int(n_features)
        self._execs = dict(execs)
        self.out_cols = next(iter(self._execs.values())).out_cols \
            if self._execs else None

    def predict_bucket(self, rows: np.ndarray, bucket: int) -> np.ndarray:
        import jax
        rows = np.asarray(rows, np.float32)
        if rows.ndim == 1:
            rows = rows.reshape(1, -1)
        if rows.shape[1] != self.n_features:
            raise ValueError(f"request has {rows.shape[1]} features, "
                             f"bundle serves {self.n_features}")
        ex = self._execs.get(int(bucket))
        if ex is None:
            raise ValueError(
                f"bucket {bucket} is not in the bundle's compiled ladder "
                f"{self.buckets} — a bundle serves exactly the shapes it "
                "was exported for")
        if rows.shape[0] > bucket:
            raise ValueError(f"{rows.shape[0]} rows exceed bucket {bucket}")
        buf = ex.template.fill(rows)
        dev = jax.device_put(buf, ex.in_sharding) \
            if ex.in_sharding is not None else jax.device_put(buf)
        args = list(ex.args)
        args[ex.input_slot] = dev
        _prof.count_dispatch("bundle_exec")
        outs = ex.call(*args)
        host = _fetch(outs[0])
        return host[: rows.shape[0], : ex.out_cols]


class LoadedBundle:
    """:func:`load_bundle`'s result: the servable ``pipeline`` (a
    :class:`BundlePipeline`, or a fresh ``build(state)`` pipeline when
    ``fallback`` is True), the embedded checksum-verified ``state``, the
    ``buckets`` ladder, the exporting process's ``fingerprint``, and
    ``fallback`` — True when the executables were unusable here and the
    pipeline will pay a fresh trace+compile per bucket instead.

    For a SHARDED bundle, ``hosts`` is the fleet size the bundle was
    exported for and ``host`` the shard this process serves; both are
    None for a flat bundle."""

    __slots__ = ("pipeline", "state", "buckets", "fingerprint", "fallback",
                 "hosts", "host")

    def __init__(self, pipeline, state, buckets, fingerprint, fallback,
                 hosts=None, host=None):
        self.pipeline = pipeline
        self.state = state
        self.buckets = tuple(buckets)
        self.fingerprint = fingerprint
        self.fallback = fallback
        self.hosts = hosts
        self.host = host

    def __repr__(self):
        shard = f", host={self.host}/{self.hosts}" \
            if self.hosts is not None else ""
        return (f"LoadedBundle(buckets={self.buckets}, "
                f"fallback={self.fallback}{shard})")


def _fallback(build, state, meta, err):
    """The loud typed fallback: the bundle's executables cannot run here
    but its model state is checksum-verified — rebuild fresh (paying
    trace+compile) when the caller gave us a builder, else raise."""
    if build is None:
        raise err
    if not state:
        raise BundleIncompatible(
            f"{err} — and the bundle embeds no model state to rebuild "
            "from (export with checkpoint= or state=)",
            expected=err.expected, found=err.found) from err
    warnings.warn(
        f"deployment bundle unusable here ({err}); falling back to a "
        "fresh trace+compile from the bundle's embedded model state — "
        "cold-start protection is LOST for this process",
        RuntimeWarning, stacklevel=3)
    return LoadedBundle(build(state), state, meta["buckets"],
                        meta["fingerprint"], fallback=True)


def load_bundle(path: str, build=None, timeout: float | None = None) \
        -> LoadedBundle:
    """Rehydrate a deployment bundle into a ``PredictServer``-ready
    pipeline with zero retraces.

    The read verifies the artifact checksum (``SnapshotCorrupt`` on any
    damage — typed, never a half-read pipeline), then compares the
    embedded fingerprint against this process (:func:`runtime_fingerprint`
    hard keys).  On mismatch — or when executable deserialization itself
    fails — raises :class:`~dislib_tpu.runtime.BundleIncompatible`;
    pass ``build`` (``state_dict -> ServePipeline``) to instead fall
    back loudly to a fresh compile from the embedded state.

    A SHARDED bundle (``export_bundle(hosts=N)``; ``path`` names the
    manifest) instead runs the coordinated load barrier first: this
    process verifies its own shard (manifest checksum + artifact CRC),
    exchanges the verdict with every peer through ``runtime.coord``,
    and only when ALL hosts verified does anyone deserialize — one
    corrupt shard raises the same typed
    :class:`~dislib_tpu.runtime.BundleShardCorrupt` on every host, and
    zero hosts serve.  ``timeout`` bounds the barrier wait — default
    ``DSLIB_BARRIER_TIMEOUT`` (30 s): one DEAD host aborts ALL hosts
    within this budget with the typed
    :class:`~dislib_tpu.runtime.RankDead` (when membership leases have
    confirmed who died) or :class:`~dislib_tpu.runtime.CoordinationTimeout`
    — never a hung fleet.
    """
    if timeout is None:
        from dislib_tpu.runtime.coord import barrier_timeout
        timeout = barrier_timeout()
    raw = read_bundle(path)
    if _META_KEY not in raw:
        raise BundleIncompatible(
            f"{path} verifies but carries no bundle manifest — not a "
            "deployment bundle")
    meta = json.loads(str(raw[_META_KEY][()]))
    if meta.get("sharded"):
        return _load_sharded(path, meta, build, timeout)
    state = {k[len(_STATE_PREFIX):]: v for k, v in raw.items()
             if k.startswith(_STATE_PREFIX)}
    here = runtime_fingerprint()
    theirs = meta.get("fingerprint", {})
    mismatched = [k for k in _HARD_KEYS if theirs.get(k) != here.get(k)]
    if mismatched:
        diff = {k: {"bundle": theirs.get(k), "here": here.get(k)}
                for k in mismatched}
        return _fallback(build, state, meta, BundleIncompatible(
            f"bundle {path} was exported under a different runtime "
            f"({diff}) — its compiled executables cannot run here",
            expected=theirs, found=here))
    try:
        execs = _build_execs(raw, meta)
    except BundleIncompatible:
        raise
    except Exception as e:  # noqa: BLE001 — deserialize failure is typed
        return _fallback(build, state, meta, BundleIncompatible(
            f"bundle {path} fingerprint matches but executable "
            f"deserialization failed ({type(e).__name__}: {e})",
            expected=theirs, found=here))
    pipe = BundlePipeline(meta["buckets"], meta["n_features"], execs)
    return LoadedBundle(pipe, state, meta["buckets"], theirs,
                        fallback=False)


def _build_execs(raw, meta) -> dict:
    """Rehydrate every bucket's compiled executable from a verified raw
    entry dict (the flat artifact, or this host's shard)."""
    import jax
    import jax.tree_util as jtu
    from jax.experimental.serialize_executable import deserialize_and_load

    execs = {}
    by_id = {d.id: d for d in jax.devices()}
    for b in meta["buckets"]:
        pb = meta["per_bucket"][str(b)]
        payload = raw[f"exec_{b}"].tobytes()
        in_tree = jtu.tree_structure(
            (tuple(range(pb["n_leaves"])), {}))
        out_tree = jtu.tree_structure(tuple(range(pb["n_outs"])))
        loaded = deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in pb["device_ids"]])
        shardings = getattr(loaded, "input_shardings", None)
        shardings = shardings[0] if shardings else None
        args = []
        for i in range(pb["n_leaves"]):
            leaf = raw[f"leaf_{b}_{i}"]
            args.append(jax.device_put(leaf, shardings[i])
                        if shardings is not None else leaf)
        execs[int(b)] = _BucketExec(
            loaded, args, pb["input_slot"],
            shardings[pb["input_slot"]] if shardings is not None
            else None,
            pb["out_cols"], pb["pshape"])
    return execs


def _verify_shard(path, manifest, r):
    """One host's shard verification: manifest CRC over the artifact
    bytes, then the checksum-verified read.  Returns ``(vote, raw)`` —
    the vote is what goes through the barrier exchange."""
    from dislib_tpu.utils.checkpoint import SnapshotCorrupt
    sp = shard_path(path, r)
    try:
        crc = file_crc(sp)
    except OSError as e:
        return {"ok": False, "reason": f"shard unreadable: {e}"}, None
    want = int(manifest["shard_crcs"][r])
    if crc != want:
        return {"ok": False,
                "reason": f"shard CRC {crc:#010x} != manifest "
                          f"{want:#010x} — damaged or replaced"}, None
    try:
        raw = read_bundle(sp)
    except SnapshotCorrupt as e:
        return {"ok": False, "reason": f"shard fails its embedded "
                                       f"checksum: {e}"}, None
    return {"ok": True}, raw


def _barrier_exchange(coord, name, rank, vote, n, timeout, path):
    """The load-barrier exchange under the round-20 degradation policy:
    transient ``CoordinationTimeout`` s retry through ``runtime.Retry``
    inside the ``DSLIB_BARRIER_TIMEOUT`` budget (``resilient_exchange``
    splits it); a confirmed ``RankDead`` — or the budget running dry —
    ABORTS typed, counted ``bundle_barrier_abort``, on every surviving
    host.  A dead fleet member can delay a load by at most ``timeout``;
    it can never hang it."""
    from dislib_tpu.runtime.coord import (CoordinationTimeout,
                                          resilient_exchange)
    try:
        return resilient_exchange(coord, name, rank, vote, n,
                                  timeout=timeout)
    except CoordinationTimeout as e:    # includes the attributed RankDead
        _prof.count_resilience("bundle_barrier_abort")
        e.args = (f"sharded bundle {path}: load barrier ABORTED "
                  f"({e.args[0] if e.args else e}) — zero hosts serve",
                  *e.args[1:])
        raise


def _load_sharded(path, manifest, build, timeout) -> LoadedBundle:
    import jax

    from dislib_tpu.runtime.coord import get_coordinator

    hosts = int(manifest["hosts"])
    contract = manifest.get("mesh_contract", {})
    here = runtime_fingerprint()
    theirs = manifest.get("fingerprint", {})
    pc = jax.process_count()
    if pc > 1:
        if pc != hosts:
            raise BundleIncompatible(
                f"sharded bundle {path} carries {hosts} shards but this "
                f"fleet has {pc} processes — the mesh contract "
                f"{contract} is not honored", expected=contract,
                found={"hosts": pc})
        if contract.get("devices_per_host") is not None and \
                int(contract["devices_per_host"]) != len(jax.local_devices()):
            raise BundleIncompatible(
                f"sharded bundle {path} expects "
                f"{contract['devices_per_host']} devices per host, this "
                f"process has {len(jax.local_devices())}",
                expected=contract,
                found={"devices_per_host": len(jax.local_devices())})
        my_host = jax.process_index()
        votes_needed = hosts
        vote, raw_mine = _verify_shard(path, manifest, my_host)
        coord = get_coordinator()
        base = os.path.basename(path)
        votes = _barrier_exchange(coord, f"bundle-load:{base}", my_host,
                                  vote, votes_needed, timeout, path)
    else:
        # single process standing in for the fleet (mock hosts, offline
        # validation): verify EVERY shard and run the same barrier
        # exchange over the local transport — the protocol decision is
        # identical, only the transport is in-memory
        my_host = 0
        coord = get_coordinator()
        base = os.path.basename(path)
        coord.clear(f"bundle-load:{base}")
        raws, votes0 = {}, {}
        for r in range(hosts):
            votes0[r], raws[r] = _verify_shard(path, manifest, r)
            coord.post(f"bundle-load:{base}", r, votes0[r])
        raw_mine = raws[0]
        votes = _barrier_exchange(coord, f"bundle-load:{base}", 0,
                                  votes0[0], hosts, timeout, path)
    bad = sorted(r for r, v in votes.items() if not v.get("ok"))
    if bad:
        _prof.count_resilience("bundle_barrier_abort")
        r0 = bad[0]
        reason = votes[r0].get("reason", "unknown")
        raise BundleShardCorrupt(
            f"sharded bundle {path}: host {r0} failed shard "
            f"verification ({reason}) — load barrier ABORTS, zero hosts "
            f"serve (failed hosts: {bad})", host=r0, reason=reason)
    _prof.count_resilience("bundle_barrier_ok")
    state = {k[len(_STATE_PREFIX):]: v for k, v in raw_mine.items()
             if k.startswith(_STATE_PREFIX)}
    mismatched = [k for k in _SHARD_HARD_KEYS
                  if theirs.get(k) != here.get(k)]
    if mismatched:
        diff = {k: {"bundle": theirs.get(k), "here": here.get(k)}
                for k in mismatched}
        return _fallback(build, state, manifest, BundleIncompatible(
            f"sharded bundle {path} was exported under a different "
            f"runtime ({diff}) — its compiled executables cannot run "
            "here", expected=theirs, found=here))
    try:
        execs = _build_execs(raw_mine, manifest)
    except Exception as e:  # noqa: BLE001 — deserialize failure is typed
        return _fallback(build, state, manifest, BundleIncompatible(
            f"sharded bundle {path} passed its load barrier but "
            f"executable deserialization failed "
            f"({type(e).__name__}: {e})", expected=theirs, found=here))
    pipe = BundlePipeline(manifest["buckets"], manifest["n_features"],
                          execs)
    return LoadedBundle(pipe, state, manifest["buckets"], theirs,
                        fallback=False, hosts=hosts, host=my_host)
