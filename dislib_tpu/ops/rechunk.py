"""On-device collective rechunk/redistribute (ROADMAP item 4).

Reference regime: "Memory-efficient array redistribution through portable
collective communication" (arXiv:2112.01075) — express a resharding as a
short sequence of collectives whose peak memory is bounded by the output
plus one in-flight panel, never a full gathered copy.  dislib_tpu needs
exactly that move at three seams:

1. **Quantum re-padding** on one mesh: a ds-array built under an older
   mesh carries a pad quantum the current grid doesn't divide.  The fix
   is a traced crop/place/re-mask (:func:`requantize_body`) that rides
   the dispatch-fusion graph as a ``"rechunk"`` instruction — a
   mid-pipeline reshard costs ZERO extra dispatches in a fused chain.
2. **Mesh-layout change over the same devices** (elastic reshape,
   1-D ↔ 2-D): the explicit *panel-exchange* schedule
   (:func:`panel_rechunk`) — a ``shard_map`` over the SOURCE mesh that
   walks the array in k row panels, broadcasting each panel with the
   masked-``psum`` idiom of ``ops/summa.py`` (one collective per panel
   per mesh axis) while every device gathers its TARGET-layout block
   from the passing panel.  The per-device output blocks are then
   re-wrapped zero-copy (``jax.make_array_from_single_device_arrays``)
   as a global array of the target mesh.  ONE jitted program; in-flight
   panel bytes ≈ ``|array| / panels``, so peak live ≈ (1 + 1/k)·|array|
   beyond the source — never a gathered copy, never the host.
3. **Device-set change** (elastic shrink/grow): the runtime's own
   device-to-device copy (:func:`deviceput_rechunk`) — still no host
   materialization; the collective schedule is XLA's (the arXiv paper
   describes exactly that implementation).

Schedule selection (``DSLIB_RECHUNK_SCHEDULE`` overrides ``"auto"``):
``"xla"`` = the fused/jit requantize path (same layout, or leave the
cross-layout collectives to the SPMD partitioner), ``"panels"`` = the
explicit exchange, ``"deviceput"`` = the runtime copy.  ``"auto"`` picks
the fused path for same-layout operands, panels for a layout change over
the same device set AND for a device-set expansion (the grow-back
schedule: panels assemble every target block on the source devices, new
devices each receive exactly one block — :func:`panel_grow_rechunk`),
deviceput otherwise.  ``DSLIB_RECHUNK_PANELS``
(default 4) sets k, the per-source-rank panel count.

The pad-and-mask invariant is re-asserted by EVERY schedule: the region
outside the logical shape is rebuilt from a zero canvas (or masked to
zero), so a poisoned pad tail cannot survive a reshard — the same
``grow_canvas`` discipline the round-10 precision PR pinned for the
blocked factorizations.
"""

from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dislib_tpu.ops import overlap as _ov
from dislib_tpu.parallel import hosts as _hosts
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.utils import profiling as _prof
from dislib_tpu.utils.profiling import profiled_jit as _pjit

__all__ = [
    "requantize_body", "repad_axis", "panel_rechunk", "panel_grow_rechunk",
    "deviceput_rechunk", "reshard", "panel_memory_analysis",
    "reshard_sparse", "pick_sparse_schedule",
    "dcn_rechunk", "dcn_supported", "dcn_accounting",
]

SCHEDULES = ("auto", "xla", "panels", "deviceput", "dcn")

# the hierarchical schedule's outer mesh axis: whole-row blocks of the
# source mesh grouped by owning host (parallel.hosts.host_blocks)
_HOSTS = "hosts"


def _padded_dim(n: int, quantum: int) -> int:
    return max(quantum, int(math.ceil(n / quantum)) * quantum)


def _out_pshape(logical_shape, mesh) -> tuple[int, int]:
    q = _mesh.pad_quantum(mesh)
    return tuple(_padded_dim(int(s), q) for s in logical_shape)


# ---------------------------------------------------------------------------
# the traced re-quantize body (shared by the fused "rechunk" instruction
# and the eager kernel) — same-mesh pad-quantum moves
# ---------------------------------------------------------------------------

def requantize_body(data, logical_shape, out_pshape, mesh="default"):
    """Re-pad ``data`` (any padded canvas holding ``logical_shape`` at its
    origin) onto a zero canvas of ``out_pshape``, re-zero everything
    outside the logical region, and constrain to the canonical sharding.

    Traced: this is the ``"rechunk"`` fusion-instruction body, so a
    mid-chain reshard fuses into the chain's ONE dispatch.  The output
    pad region is zero BY CONSTRUCTION (fresh canvas + mask), so the
    pad-and-mask invariant holds even for a poisoned input tail.

    ``mesh``: a Mesh to constrain the result to, the string "default"
    for the library default mesh, or None for no constraint (the
    deviceput path, whose input devices may not be the default mesh's)."""
    m, n = (int(s) for s in logical_shape)
    r = min(data.shape[0], out_pshape[0])
    c = min(data.shape[1], out_pshape[1])
    cropped = data[:r, :c]
    if tuple(cropped.shape) != tuple(out_pshape):
        canvas = jnp.zeros(out_pshape, data.dtype)
        out = lax.dynamic_update_slice(canvas, cropped, (0, 0))
    else:
        out = cropped
    ri = lax.broadcasted_iota(jnp.int32, out.shape, 0)
    ci = lax.broadcasted_iota(jnp.int32, out.shape, 1)
    out = jnp.where((ri < m) & (ci < n), out, jnp.zeros((), out.dtype))
    if mesh is None:
        return out
    sharding = _mesh.data_sharding(None if mesh == "default" else mesh)
    return lax.with_sharding_constraint(out, sharding)


@partial(_pjit, static_argnames=("logical_shape", "out_pshape", "mesh"),
         name="rechunk_requantize")
def _requantize_op(data, logical_shape, out_pshape, mesh):
    return requantize_body(data, logical_shape, out_pshape, mesh)


@partial(_pjit, static_argnames=("logical", "target", "axis"),
         name="repad_axis")
def repad_axis(a, logical, target, axis=0):
    """On-device :func:`dislib_tpu.runtime.repad_rows`: crop to the first
    ``logical`` slices along ``axis`` and zero-fill out to ``target`` —
    one jitted kernel, no host round trip.  N-dimensional (elastic state
    arrays are 1/2/3-D)."""
    idx = [slice(None)] * a.ndim
    idx[axis] = slice(0, logical)
    cropped = a[tuple(idx)]
    if target == logical:
        return cropped
    shape = list(cropped.shape)
    shape[axis] = target
    out = jnp.zeros(tuple(shape), a.dtype)
    return lax.dynamic_update_slice(out, cropped, (0,) * a.ndim)


# ---------------------------------------------------------------------------
# the explicit panel-exchange schedule (same device set, new mesh layout)
# ---------------------------------------------------------------------------

def _panels_per_rank(m_loc: int, requested: int) -> int:
    """Largest divisor of the per-rank row count ≤ the requested panel
    count (panels must tile a source rank's rows exactly)."""
    for j in range(max(1, min(m_loc, requested)), 0, -1):
        if m_loc % j == 0:
            return j
    return 1


def _requested_panels(panels) -> int:
    if panels is not None:
        return max(1, int(panels))
    return max(1, int(os.environ.get("DSLIB_RECHUNK_PANELS", "4")))


def _target_coord_tables(src_mesh: Mesh, dst_mesh: Mesh):
    """Per-source-linear-index target (row, col) coordinates.  A source
    device absent from the target grid gets (0, 0) — it computes a
    duplicate of the (0, 0) block that the rewrap simply drops."""
    src_flat = list(src_mesh.devices.flat)
    dst_pos = {}
    rp, cp = dst_mesh.devices.shape
    for r in range(rp):
        for c in range(cp):
            dst_pos[dst_mesh.devices[r, c]] = (r, c)
    tr = np.zeros((len(src_flat),), np.int32)
    tc = np.zeros((len(src_flat),), np.int32)
    for i, d in enumerate(src_flat):
        tr[i], tc[i] = dst_pos.get(d, (0, 0))
    return tr, tc


@partial(_pjit, static_argnames=("logical_shape", "out_pshape", "src_mesh",
                                 "dst_shape", "tr_key", "tc_key", "steps",
                                 "overlap"),
         name="rechunk_panels")
def _panel_exchange(data, logical_shape, out_pshape, src_mesh, dst_shape,
                    tr_key, tc_key, steps, overlap="db"):
    """ONE jitted program: shard_map over the SOURCE mesh; each device
    assembles its TARGET-layout block from ``steps`` masked-psum panel
    broadcasts (the ``ops/summa.py`` collective idiom, ``check_vma`` on).

    The exchange/assemble loop runs through ``ops/overlap.panel_pipeline``
    (round-13): under the default double-buffered schedule panel t+1's
    rows-axis broadcast is issued before panel t's cols-broadcast/gather
    assembly consumes it — one extra in-flight panel of live memory
    (verified by :func:`panel_memory_analysis`), bit-equal to the
    sequential schedule (``overlap="seq"``).

    ``tr_key``/``tc_key`` are the target-coordinate tables as hashable
    tuples (they ride the jit cache key: a different device mapping is a
    different program)."""
    m, n = logical_shape
    rows_s, cols_s = src_mesh.shape[_mesh.ROWS], src_mesh.shape[_mesh.COLS]
    rows_d, cols_d = dst_shape
    m_loc1, n_loc1 = data.shape[0] // rows_s, data.shape[1] // cols_s
    m_loc2, n_loc2 = out_pshape[0] // rows_d, out_pshape[1] // cols_d
    j = steps // rows_s                     # panels per source row-rank
    h = m_loc1 // j                         # panel height (global rows)
    tr_tab = jnp.asarray(np.asarray(tr_key, np.int32))
    tc_tab = jnp.asarray(np.asarray(tc_key, np.int32))

    def local(x_loc):
        my_r = lax.axis_index(_mesh.ROWS)
        my_c = lax.axis_index(_mesh.COLS)
        my_lin = my_r * cols_s + my_c
        row0 = tr_tab[my_lin] * m_loc2      # my target block origin
        col0 = tc_tab[my_lin] * n_loc2
        ri = row0 + lax.iota(jnp.int32, m_loc2)   # global coords of my
        ci = col0 + lax.iota(jnp.int32, n_loc2)   # target block entries

        def fetch(t, prev):
            del prev                        # panels slice by step
            owner_r = t // j
            pan = lax.dynamic_slice(x_loc, ((t % j) * h, 0), (h, n_loc1))
            pan = jnp.where(my_r == owner_r, pan, jnp.zeros((), pan.dtype))
            return lax.psum(pan, _mesh.ROWS)

        def _col_blocks(pan):
            """The per-col-rank broadcasts of one row panel (static loop:
            one masked psum per source col-rank)."""
            for s in range(cols_s):
                if cols_s > 1:
                    blk = jnp.where(my_c == s, pan,
                                    jnp.zeros((), pan.dtype))
                    blk = lax.psum(blk, _mesh.COLS)
                else:
                    blk = pan
                yield s, blk

        def consume(t, acc, pan):
            owner_r = t // j
            gr0 = owner_r * m_loc1 + (t % j) * h  # panel's global rows
            r_in = (ri >= gr0) & (ri < gr0 + h)
            r_idx = jnp.clip(ri - gr0, 0, h - 1)
            for s, blk in _col_blocks(pan):
                gc0 = s * n_loc1
                c_in = (ci >= gc0) & (ci < gc0 + n_loc1)
                c_idx = jnp.clip(ci - gc0, 0, n_loc1 - 1)
                gathered = blk[r_idx][:, c_idx]
                acc = jnp.where(r_in[:, None] & c_in[None, :],
                                gathered, acc)
            return acc

        acc0 = lax.pcast(jnp.zeros((m_loc2, n_loc2), x_loc.dtype),
                         (_mesh.ROWS, _mesh.COLS), to="varying")
        acc = _ov.panel_pipeline(steps, fetch(0, None), fetch, consume,
                                 acc0, _ov.overlapped(overlap))
        # re-assert the pad-and-mask invariant on the NEW canvas: entries
        # outside the logical region are zero no matter what the source
        # pad tail carried
        keep = (ri < m)[:, None] & (ci < n)[None, :]
        return jnp.where(keep, acc, jnp.zeros((), acc.dtype))

    return jax.shard_map(
        local, mesh=src_mesh,
        in_specs=P(_mesh.ROWS, _mesh.COLS),
        out_specs=P(_mesh.ROWS, _mesh.COLS),
        check_vma=True,
    )(data)


def _panel_args(data, logical_shape, dst_mesh, panels, overlap=None):
    """Static argument pack for :func:`_panel_exchange` (shared by the
    run path and the AOT memory-analysis probe).  ``overlap`` resolves
    through the ``DSLIB_OVERLAP`` router here, at the host boundary, so
    an env flip retraces (the precision-policy static contract)."""
    sharding = data.sharding
    src_mesh = sharding.mesh
    out_pshape = _out_pshape(logical_shape, dst_mesh)
    rows_s = src_mesh.shape[_mesh.ROWS]
    m_loc1 = data.shape[0] // rows_s
    j = _panels_per_rank(m_loc1, _requested_panels(panels))
    tr, tc = _target_coord_tables(src_mesh, dst_mesh)
    return dict(logical_shape=tuple(int(s) for s in logical_shape),
                out_pshape=out_pshape, src_mesh=src_mesh,
                dst_shape=(dst_mesh.shape[_mesh.ROWS],
                           dst_mesh.shape[_mesh.COLS]),
                tr_key=tuple(int(v) for v in tr),
                tc_key=tuple(int(v) for v in tc),
                steps=rows_s * j,
                overlap=_ov.resolve(overlap))


def panel_supported(data, dst_mesh) -> bool:
    """True when the explicit panel exchange can run: the source backing
    is a fully-addressable NamedSharding over our named mesh whose grid
    divides the padded shape, and every target device already holds a
    source shard (same-device-set relayout — the elastic reshape case)."""
    sharding = getattr(data, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return False
    src_mesh = sharding.mesh
    if not isinstance(src_mesh, Mesh) or \
            tuple(src_mesh.axis_names) != _mesh.AXIS_NAMES:
        return False
    if not getattr(data, "is_fully_addressable", False):
        return False
    rows_s = src_mesh.shape[_mesh.ROWS]
    cols_s = src_mesh.shape[_mesh.COLS]
    if data.shape[0] % rows_s or data.shape[1] % cols_s:
        return False
    src_devs = set(src_mesh.devices.flat)
    return set(dst_mesh.devices.flat) <= src_devs


def panel_rechunk(data, logical_shape, dst_mesh, panels=None, overlap=None):
    """The explicit collective reshard: ONE jitted panel-exchange program
    over the source mesh, then a ZERO-COPY rewrap of the per-device
    target blocks as a global array of ``dst_mesh`` — no host, no
    gathered copy, peak in-flight panel bytes ≈ |array| / panels (one
    extra panel under the default double-buffered ``overlap`` schedule —
    see :func:`panel_memory_analysis`)."""
    kw = _panel_args(data, logical_shape, dst_mesh, panels, overlap)
    _prof.count_schedule("rechunk_panels", kw["overlap"])
    out_perm = _panel_exchange(data, **kw)
    out_pshape = kw["out_pshape"]
    by_dev = {s.device: s.data for s in out_perm.addressable_shards}
    bufs = [by_dev[d] for d in dst_mesh.devices.flat]
    return jax.make_array_from_single_device_arrays(
        out_pshape, NamedSharding(dst_mesh, P(*_mesh.AXIS_NAMES)), bufs)


def _grow_assignment(src_mesh: Mesh, dst_mesh: Mesh):
    """Which source device assembles each destination block, and in
    which output slot: ``assign[t] = (q, i)`` maps destination flat
    index ``t`` to slot ``q`` of source flat index ``i``.  Blocks are
    handed out round-robin WITHIN each host — a destination device's
    block is always assembled by a source device on ITS host, so the
    placement put rides ICI and never DCN (the cross-host grow rung:
    the panel collectives already moved the data between hosts).  On a
    single host this reduces exactly to the global round-robin
    ``t = i + q * n_src``.  Returns ``(assign, slots)``."""
    src_flat = list(src_mesh.devices.flat)
    dst_flat = list(dst_mesh.devices.flat)
    src_by_host: dict[int, list[int]] = {}
    for i, d in enumerate(src_flat):
        src_by_host.setdefault(_hosts.host_of(d), []).append(i)
    taken = {h: 0 for h in src_by_host}
    assign: list[tuple[int, int]] = []
    for d in dst_flat:
        h = _hosts.host_of(d)
        owners = src_by_host.get(h)
        if owners is None:
            # no source shard on this host (panel_grow_supported refused
            # this layout); keep a defined mapping for robustness
            owners = list(range(len(src_flat)))
            h = None
            taken.setdefault(None, 0)
        k = taken[h]
        taken[h] = k + 1
        assign.append((k // len(owners), owners[k % len(owners)]))
    slots = 1 + max(q for q, _ in assign)
    return assign, slots


def _grow_coord_tables(src_mesh: Mesh, dst_mesh: Mesh):
    """Per-(slot, source-linear-index) target (row, col) coordinates for
    the GROW exchange, from the host-aware :func:`_grow_assignment`.
    An unused slot duplicates block (0, 0) — the rewrap drops it."""
    assign, slots = _grow_assignment(src_mesh, dst_mesh)
    n_src = int(src_mesh.devices.size)
    cols_d = int(dst_mesh.devices.shape[1])
    tr = np.zeros((slots, n_src), np.int32)
    tc = np.zeros((slots, n_src), np.int32)
    for t, (q, i) in enumerate(assign):
        tr[q, i], tc[q, i] = divmod(t, cols_d)
    return tr, tc


@partial(_pjit, static_argnames=("logical_shape", "out_pshape", "src_mesh",
                                 "dst_shape", "tr_key", "tc_key", "steps",
                                 "overlap"),
         name="rechunk_panels_grow")
def _panel_exchange_grow(data, logical_shape, out_pshape, src_mesh,
                         dst_shape, tr_key, tc_key, steps, overlap="db"):
    """The grow-direction panel exchange: the SAME masked-psum panel
    broadcasts as :func:`_panel_exchange` (one jitted shard_map over the
    SOURCE mesh, ``ops/overlap.panel_pipeline`` schedule), but every
    source device assembles ``slots = len(tr_key)`` TARGET blocks from
    each passing panel instead of one — the target grid has more devices
    than the source, so the blocks for the new devices must be built
    somewhere before they can be placed.  A separate jit from the
    shrink/relayout exchange: its output arity depends on the slot
    count, and keeping it apart leaves the existing compiled paths (and
    their cache keys) untouched."""
    m, n = logical_shape
    rows_s, cols_s = src_mesh.shape[_mesh.ROWS], src_mesh.shape[_mesh.COLS]
    rows_d, cols_d = dst_shape
    m_loc1, n_loc1 = data.shape[0] // rows_s, data.shape[1] // cols_s
    m_loc2, n_loc2 = out_pshape[0] // rows_d, out_pshape[1] // cols_d
    j = steps // rows_s                     # panels per source row-rank
    h = m_loc1 // j                         # panel height (global rows)
    slots = len(tr_key)
    tr_tab = jnp.asarray(np.asarray(tr_key, np.int32))
    tc_tab = jnp.asarray(np.asarray(tc_key, np.int32))

    def local(x_loc):
        my_r = lax.axis_index(_mesh.ROWS)
        my_c = lax.axis_index(_mesh.COLS)
        my_lin = my_r * cols_s + my_c
        coords = []                         # global coords per target slot
        for q in range(slots):
            row0 = tr_tab[q, my_lin] * m_loc2
            col0 = tc_tab[q, my_lin] * n_loc2
            coords.append((row0 + lax.iota(jnp.int32, m_loc2),
                           col0 + lax.iota(jnp.int32, n_loc2)))

        def fetch(t, prev):
            del prev                        # panels slice by step
            owner_r = t // j
            pan = lax.dynamic_slice(x_loc, ((t % j) * h, 0), (h, n_loc1))
            pan = jnp.where(my_r == owner_r, pan, jnp.zeros((), pan.dtype))
            return lax.psum(pan, _mesh.ROWS)

        def consume(t, acc, pan):
            owner_r = t // j
            gr0 = owner_r * m_loc1 + (t % j) * h  # panel's global rows
            acc = list(acc)
            for s in range(cols_s):         # ONE cols-broadcast per panel,
                if cols_s > 1:              # shared by every slot's gather
                    blk = jnp.where(my_c == s, pan,
                                    jnp.zeros((), pan.dtype))
                    blk = lax.psum(blk, _mesh.COLS)
                else:
                    blk = pan
                gc0 = s * n_loc1
                for q, (ri, ci) in enumerate(coords):
                    r_in = (ri >= gr0) & (ri < gr0 + h)
                    r_idx = jnp.clip(ri - gr0, 0, h - 1)
                    c_in = (ci >= gc0) & (ci < gc0 + n_loc1)
                    c_idx = jnp.clip(ci - gc0, 0, n_loc1 - 1)
                    gathered = blk[r_idx][:, c_idx]
                    acc[q] = jnp.where(r_in[:, None] & c_in[None, :],
                                       gathered, acc[q])
            return tuple(acc)

        acc0 = tuple(
            lax.pcast(jnp.zeros((m_loc2, n_loc2), x_loc.dtype),
                      (_mesh.ROWS, _mesh.COLS), to="varying")
            for _ in range(slots))
        accs = _ov.panel_pipeline(steps, fetch(0, None), fetch, consume,
                                  acc0, _ov.overlapped(overlap))
        # re-assert the pad-and-mask invariant on every NEW canvas
        out = []
        for q, (ri, ci) in enumerate(coords):
            keep = (ri < m)[:, None] & (ci < n)[None, :]
            out.append(jnp.where(keep, accs[q],
                                 jnp.zeros((), accs[q].dtype)))
        return tuple(out)

    return jax.shard_map(
        local, mesh=src_mesh,
        in_specs=P(_mesh.ROWS, _mesh.COLS),
        out_specs=(P(_mesh.ROWS, _mesh.COLS),) * slots,
        check_vma=True,
    )(data)


def panel_grow_supported(data, dst_mesh) -> bool:
    """True when the grow-direction panel exchange can run: the source
    backing passes the same NamedSharding/addressability/divisibility
    gates as :func:`panel_supported`, the target device set strictly
    CONTAINS the source's (elastic grow-back), and every target device's
    HOST already holds a source shard — so each new device's block is
    placed by an intra-host put (the cross-host rung: before round 19
    this required ``dst ⊆ local_devices`` and degraded any multi-host
    grow to per-array ``device_put``).  A host gaining devices without
    a single surviving source shard falls back to deviceput."""
    sharding = getattr(data, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return False
    src_mesh = sharding.mesh
    if not isinstance(src_mesh, Mesh) or \
            tuple(src_mesh.axis_names) != _mesh.AXIS_NAMES:
        return False
    if not getattr(data, "is_fully_addressable", False):
        return False
    rows_s = src_mesh.shape[_mesh.ROWS]
    cols_s = src_mesh.shape[_mesh.COLS]
    if data.shape[0] % rows_s or data.shape[1] % cols_s:
        return False
    src_devs = set(src_mesh.devices.flat)
    dst_devs = set(dst_mesh.devices.flat)
    if not src_devs < dst_devs:
        return False
    src_hosts = {_hosts.host_of(d) for d in src_devs}
    return all(_hosts.host_of(d) in src_hosts for d in dst_devs)


def panel_grow_rechunk(data, logical_shape, dst_mesh, panels=None,
                       overlap=None):
    """The grow-direction panel reshard (device-set EXPANSION — the
    elastic grow-back): ONE jitted panel-exchange program over the
    SOURCE mesh assembling every target block (see
    :func:`_panel_exchange_grow`), then the placement pass — a block
    whose target device already holds a source shard rewraps ZERO-COPY,
    and each NEW device receives exactly its one block via a single
    direct device-to-device put.  Per-device moved bytes are one target
    block, not the deviceput fallback's partitioner-chosen schedule; the
    host never sees the data either way."""
    kw = _panel_args_grow(data, logical_shape, dst_mesh, panels, overlap)
    _prof.count_schedule("rechunk_panels_grow", kw["overlap"])
    outs = _panel_exchange_grow(data, **kw)
    out_pshape = kw["out_pshape"]
    src_flat = list(kw["src_mesh"].devices.flat)
    dst_flat = list(dst_mesh.devices.flat)
    assign, _slots = _grow_assignment(kw["src_mesh"], dst_mesh)
    per_src = [{s.device: s.data for s in arr.addressable_shards}
               for arr in outs]
    by_dev = {}
    for t, (q, i) in enumerate(assign):
        d_src, d_dst = src_flat[i], dst_flat[t]
        blk = per_src[q].get(d_src)
        if blk is None:
            continue                # another process's shard: it places it
        by_dev[d_dst] = blk if d_dst == d_src \
            else jax.device_put(blk, d_dst)
    bufs = [by_dev[d] for d in dst_flat if d in by_dev]
    return jax.make_array_from_single_device_arrays(
        out_pshape, NamedSharding(dst_mesh, P(*_mesh.AXIS_NAMES)), bufs)


def _panel_args_grow(data, logical_shape, dst_mesh, panels, overlap=None):
    """Static argument pack for :func:`_panel_exchange_grow` — the
    :func:`_panel_args` shape with the 2-D slot coordinate tables."""
    src_mesh = data.sharding.mesh
    out_pshape = _out_pshape(logical_shape, dst_mesh)
    rows_s = src_mesh.shape[_mesh.ROWS]
    m_loc1 = data.shape[0] // rows_s
    j = _panels_per_rank(m_loc1, _requested_panels(panels))
    tr, tc = _grow_coord_tables(src_mesh, dst_mesh)
    return dict(logical_shape=tuple(int(s) for s in logical_shape),
                out_pshape=out_pshape, src_mesh=src_mesh,
                dst_shape=(dst_mesh.shape[_mesh.ROWS],
                           dst_mesh.shape[_mesh.COLS]),
                tr_key=tuple(tuple(int(v) for v in row) for row in tr),
                tc_key=tuple(tuple(int(v) for v in row) for row in tc),
                steps=rows_s * j,
                overlap=_ov.resolve(overlap))


def panel_memory_analysis(data, logical_shape, dst_mesh, panels=None,
                          overlap=None):
    """XLA's own memory accounting of the compiled panel-exchange program
    — a peak-live-buffer proxy.  Returns a dict with
    ``in_bytes``/``out_bytes``/``temp_bytes`` and ``peak_live_ratio`` =
    (out + temp) / in: a schedule that gathered a full copy would sit at
    ≥ 2.0; the sequential panel schedule stays ≈ 1 + 1/panels and the
    double-buffered one ≈ 1 + 2/panels (the pipelined carry holds ONE
    extra in-flight panel, never a copy of the operand — the bound
    ``tests/test_overlap.py`` asserts).  ``temp_bytes`` is None when the
    backend exposes no memory analysis (the analytic panel bound is
    reported alongside either way)."""
    kw = _panel_args(data, logical_shape, dst_mesh, panels, overlap)
    in_bytes = data.size * data.dtype.itemsize
    out_bytes = int(np.prod(kw["out_pshape"])) * data.dtype.itemsize
    n_dev = int(np.prod(kw["src_mesh"].devices.shape))
    # analytic in-flight bound: every device holds one (h, n_loc1) panel
    # (+ its cols-broadcast twin, + the pipelined next panel when
    # double-buffered) during a step
    cols_s = kw["src_mesh"].shape[_mesh.COLS]
    panel_bytes = in_bytes // kw["steps"]
    analytic_temp = panel_bytes * ((2 if cols_s > 1 else 1)
                                   + (1 if _ov.overlapped(kw["overlap"])
                                      else 0))
    res = {"in_bytes": in_bytes, "out_bytes": out_bytes,
           "panels": kw["steps"], "analytic_temp_bytes": analytic_temp,
           "analytic_ratio": round((out_bytes + analytic_temp) / in_bytes, 3),
           "temp_bytes": None, "peak_live_ratio": None, "n_devices": n_dev,
           "overlap": kw["overlap"]}
    try:
        compiled = _panel_exchange.lower(data, **kw).compile()
        ma = compiled.memory_analysis()
        temp = int(getattr(ma, "temp_size_in_bytes", 0))
        res["temp_bytes"] = temp
        res["peak_live_ratio"] = round((out_bytes + temp) / in_bytes, 3)
    except Exception:  # noqa: BLE001 — backend without memory analysis
        pass
    return res


# ---------------------------------------------------------------------------
# the hierarchical DCN schedule (multi-host relayout over the same devices)
#
# The flat panel exchange broadcasts one panel per (source row-rank ×
# panel) step along the FULL rows axis — on a mesh whose rows span
# hosts, every one of those O(panels) broadcasts is an inter-host
# message.  The ``dcn`` schedule restructures the loop hierarchically
# (arXiv:2112.01075's few-large-collectives shape): the source mesh is
# refactored as (hosts, local_rows, cols) and each step assembles ONE
# panel of a DESTINATION host's row block — every source host's
# contribution to that panel (the contiguous intersection of its row
# interval with the panel's) coalesces into a single (src-host →
# dst-host) message carried by one collective over the ('hosts', 'rows')
# axes; the per-local-shard gathers and the cols broadcasts stay
# intra-host (ICI).  Messages per step = O(hosts), never O(panels);
# inter-host bytes = the interval intersections — exactly the bytes any
# schedule must move (the deviceput baseline) — with both quantities
# accounted analytically by :func:`dcn_accounting` (the
# ``spmm_masking_work`` exposure pattern).  The assembled values are
# pure selections of source entries, so the schedule is BIT-EQUAL to
# ``panels``/``xla`` on any topology, including a single host (where it
# degenerates to a pure-ICI exchange with zero DCN messages).
# ---------------------------------------------------------------------------


def dcn_supported(data, dst_mesh) -> bool:
    """True when the hierarchical schedule can run: the same
    NamedSharding/divisibility gates as :func:`panel_supported`, the SAME
    device set on both meshes (relayout, not a device-set change), and a
    hierarchical row axis on BOTH meshes — contiguous equal blocks of
    whole rows per host (:func:`~dislib_tpu.parallel.hosts.host_blocks`),
    so the cols axis and the local gathers never pay DCN."""
    sharding = getattr(data, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return False
    src_mesh = sharding.mesh
    if not isinstance(src_mesh, Mesh) or \
            tuple(src_mesh.axis_names) != _mesh.AXIS_NAMES:
        return False
    rows_s = src_mesh.shape[_mesh.ROWS]
    cols_s = src_mesh.shape[_mesh.COLS]
    if data.shape[0] % rows_s or data.shape[1] % cols_s:
        return False
    if set(dst_mesh.devices.flat) != set(src_mesh.devices.flat):
        return False
    return _hosts.host_blocks(src_mesh) is not None and \
        _hosts.host_blocks(dst_mesh) is not None


@partial(_pjit, static_argnames=("logical_shape", "out_pshape", "mesh3",
                                 "dst_shape", "hblocks", "tr_key", "tc_key",
                                 "steps", "overlap"),
         name="rechunk_dcn")
def _dcn_exchange(data, logical_shape, out_pshape, mesh3, dst_shape,
                  hblocks, tr_key, tc_key, steps, overlap="db"):
    """ONE jitted program: shard_map over the source mesh refactored as
    ('hosts', 'rows', 'cols').  Step ``t`` assembles panel ``t % j`` of
    destination host-block ``t // j``: every device contributes the
    intersection of its row interval with the panel (a local gather),
    and ONE ``psum`` over ``('hosts', 'rows')`` coalesces all
    contributions — the batched inter-host exchange, one message per
    (src-host, dst-host) pair per step.  The per-col-rank broadcasts and
    the target-block gather are the flat exchange's, unchanged (and
    intra-host by the ``dcn_supported`` row-alignment gate).  Runs
    through ``ops/overlap.panel_pipeline`` like every panel loop."""
    m, n = logical_shape
    hosts_n = mesh3.shape[_HOSTS]
    rows_l = mesh3.shape[_mesh.ROWS]        # local row-ranks per host
    cols_s = mesh3.shape[_mesh.COLS]
    rows_d, cols_d = dst_shape
    m_loc1 = data.shape[0] // (hosts_n * rows_l)
    n_loc1 = data.shape[1] // cols_s
    m_loc2, n_loc2 = out_pshape[0] // rows_d, out_pshape[1] // cols_d
    block_h = (rows_d // hblocks) * m_loc2  # dst host-block height (rows)
    j = steps // hblocks                    # panels per dst host-block
    hp = block_h // j                       # panel height (global rows)
    tr_tab = jnp.asarray(np.asarray(tr_key, np.int32))
    tc_tab = jnp.asarray(np.asarray(tc_key, np.int32))

    def local(x_loc):
        hh = lax.axis_index(_HOSTS)
        rr = lax.axis_index(_mesh.ROWS)
        my_c = lax.axis_index(_mesh.COLS)
        my_lin = (hh * rows_l + rr) * cols_s + my_c
        row0 = tr_tab[my_lin] * m_loc2      # my target block origin
        col0 = tc_tab[my_lin] * n_loc2
        ri = row0 + lax.iota(jnp.int32, m_loc2)   # global coords of my
        ci = col0 + lax.iota(jnp.int32, n_loc2)   # target block entries
        r0 = (hh * rows_l + rr) * m_loc1    # my SOURCE row interval start

        def fetch(t, prev):
            del prev                        # panels slice by step
            g0 = (t // j) * block_h + (t % j) * hp
            gi = g0 + lax.iota(jnp.int32, hp)     # panel's global rows
            idx = jnp.clip(gi - r0, 0, m_loc1 - 1)
            mine = x_loc[idx, :]
            keep = (gi >= r0) & (gi < r0 + m_loc1)
            pan = jnp.where(keep[:, None], mine, jnp.zeros((), mine.dtype))
            # the coalesced exchange: every source host's contiguous
            # contribution to this dst-host panel rides ONE collective
            return lax.psum(pan, (_HOSTS, _mesh.ROWS))

        def consume(t, acc, pan):
            gr0 = (t // j) * block_h + (t % j) * hp
            r_in = (ri >= gr0) & (ri < gr0 + hp)
            r_idx = jnp.clip(ri - gr0, 0, hp - 1)
            for s in range(cols_s):         # intra-host cols broadcasts
                if cols_s > 1:
                    blk = jnp.where(my_c == s, pan,
                                    jnp.zeros((), pan.dtype))
                    blk = lax.psum(blk, _mesh.COLS)
                else:
                    blk = pan
                gc0 = s * n_loc1
                c_in = (ci >= gc0) & (ci < gc0 + n_loc1)
                c_idx = jnp.clip(ci - gc0, 0, n_loc1 - 1)
                gathered = blk[r_idx][:, c_idx]
                acc = jnp.where(r_in[:, None] & c_in[None, :],
                                gathered, acc)
            return acc

        acc0 = lax.pcast(jnp.zeros((m_loc2, n_loc2), x_loc.dtype),
                         (_HOSTS, _mesh.ROWS, _mesh.COLS), to="varying")
        acc = _ov.panel_pipeline(steps, fetch(0, None), fetch, consume,
                                 acc0, _ov.overlapped(overlap))
        # re-assert the pad-and-mask invariant on the NEW canvas
        keep = (ri < m)[:, None] & (ci < n)[None, :]
        return jnp.where(keep, acc, jnp.zeros((), acc.dtype))

    return jax.shard_map(
        local, mesh=mesh3,
        in_specs=P((_HOSTS, _mesh.ROWS), _mesh.COLS),
        out_specs=P((_HOSTS, _mesh.ROWS), _mesh.COLS),
        check_vma=True,
    )(data)


def _dcn_args(data, logical_shape, dst_mesh, panels, overlap=None):
    """Static argument pack for :func:`_dcn_exchange`: the source mesh
    refactored as ('hosts', 'rows', 'cols') from its host-block
    structure, the destination host-block count, and the panel count
    chosen as a divisor of the DST host-block height (panels subdivide
    the inter-host steps; the knob is the same ``DSLIB_RECHUNK_PANELS``)."""
    src_mesh = data.sharding.mesh
    out_pshape = _out_pshape(logical_shape, dst_mesh)
    h1, l1, _ = _hosts.host_blocks(src_mesh)
    cols_s = src_mesh.shape[_mesh.COLS]
    mesh3 = Mesh(src_mesh.devices.reshape(h1, l1, cols_s),
                 (_HOSTS, _mesh.ROWS, _mesh.COLS))
    h2, l2, _ = _hosts.host_blocks(dst_mesh)
    rows_d = dst_mesh.shape[_mesh.ROWS]
    m_loc2 = out_pshape[0] // rows_d
    j = _panels_per_rank(l2 * m_loc2, _requested_panels(panels))
    tr, tc = _target_coord_tables(src_mesh, dst_mesh)
    return dict(logical_shape=tuple(int(s) for s in logical_shape),
                out_pshape=out_pshape, mesh3=mesh3,
                dst_shape=(rows_d, dst_mesh.shape[_mesh.COLS]),
                hblocks=h2,
                tr_key=tuple(int(v) for v in tr),
                tc_key=tuple(int(v) for v in tc),
                steps=h2 * j,
                overlap=_ov.resolve(overlap))


def dcn_rechunk(data, logical_shape, dst_mesh, panels=None, overlap=None):
    """The hierarchical (DCN-aware) reshard: ONE jitted exchange over the
    host-refactored source mesh, then the zero-copy rewrap onto
    ``dst_mesh`` — :func:`panel_rechunk`'s contract with the collective
    loop restructured so inter-host messages are O(hosts) per step (see
    :func:`dcn_accounting` for the counted claim).  The rewrap places
    this process's ADDRESSABLE shards only, so every process of a
    multi-host job runs the same call on its view of the global array."""
    kw = _dcn_args(data, logical_shape, dst_mesh, panels, overlap)
    _prof.count_schedule("rechunk_dcn", kw["overlap"])
    out = _dcn_exchange(data, **kw)
    out_pshape = kw["out_pshape"]
    by_dev = {s.device: s.data for s in out.addressable_shards}
    bufs = [by_dev[d] for d in dst_mesh.devices.flat if d in by_dev]
    return jax.make_array_from_single_device_arrays(
        out_pshape, NamedSharding(dst_mesh, P(*_mesh.AXIS_NAMES)), bufs)


def dcn_accounting(data, logical_shape, dst_mesh, panels=None) -> dict:
    """Analytic inter-host traffic of the ``dcn`` schedule for this
    relayout (host-side, no dispatch — the ``spmm_masking_work``
    exposure pattern):

    - ``dcn_messages`` / ``dcn_bytes_moved`` — coalesced (src-host →
      dst-host) messages over the whole schedule and the bytes they
      carry (each step's message per pair is the contiguous intersection
      of the pair's row intervals with the step's panel);
    - ``messages_per_step_max`` — the per-step gate: ≤ hosts − 1, never
      a function of the panel count;
    - ``deviceput_bytes`` — the bytes ANY schedule must move across
      hosts (rows whose owning host changes), the floor;
    - ``flat_messages`` / ``flat_bytes_moved`` — what the FLAT panel
      exchange would cost on the same topology: every per-rank panel
      broadcast crosses to every other host (O(panels) messages).
    """
    src_mesh = data.sharding.mesh
    out_pshape = _out_pshape(logical_shape, dst_mesh)
    h1, l1, hosts_src = _hosts.host_blocks(src_mesh)
    h2, l2, hosts_dst = _hosts.host_blocks(dst_mesh)
    rows_s = src_mesh.shape[_mesh.ROWS]
    rows_d = dst_mesh.shape[_mesh.ROWS]
    m_loc1 = data.shape[0] // rows_s
    m_loc2 = out_pshape[0] // rows_d
    block_h = l2 * m_loc2
    j = _panels_per_rank(block_h, _requested_panels(panels))
    hp = block_h // j
    itemsize = data.dtype.itemsize
    row_bytes = int(data.shape[1]) * itemsize
    src_iv = [(b * l1 * m_loc1, (b + 1) * l1 * m_loc1) for b in range(h1)]
    msgs = 0
    bytes_moved = 0
    per_step_max = 0
    for d_blk in range(h2):
        for p in range(j):
            g0 = d_blk * block_h + p * hp
            step_msgs = 0
            for b in range(h1):
                if hosts_src[b] == hosts_dst[d_blk]:
                    continue            # intra-host: ICI, not DCN
                ov = min(g0 + hp, src_iv[b][1]) - max(g0, src_iv[b][0])
                if ov > 0:
                    step_msgs += 1
                    bytes_moved += ov * row_bytes
            msgs += step_msgs
            per_step_max = max(per_step_max, step_msgs)
    # the floor: rows whose owning host changes must cross DCN once
    # under ANY schedule (deviceput's XLA copy included)
    dp_bytes = 0
    for d_blk in range(h2):
        d0, d1 = d_blk * block_h, (d_blk + 1) * block_h
        for b in range(h1):
            if hosts_src[b] == hosts_dst[d_blk]:
                continue
            ov = min(d1, src_iv[b][1]) - max(d0, src_iv[b][0])
            if ov > 0:
                dp_bytes += ov * row_bytes
    all_hosts = len(set(hosts_src) | set(hosts_dst))
    j_flat = _panels_per_rank(m_loc1, _requested_panels(panels))
    flat_steps = rows_s * j_flat
    in_bytes = int(data.shape[0]) * row_bytes
    return {
        "hosts": all_hosts, "steps": h2 * j, "panels": j,
        "dcn_messages": msgs, "dcn_bytes_moved": bytes_moved,
        "messages_per_step_max": per_step_max,
        "deviceput_bytes": dp_bytes,
        "flat_messages": flat_steps * max(0, all_hosts - 1),
        "flat_bytes_moved": in_bytes * max(0, all_hosts - 1),
        "in_bytes": in_bytes,
    }


# ---------------------------------------------------------------------------
# device-set change: the runtime's device-to-device copy
# ---------------------------------------------------------------------------

def deviceput_rechunk(data, logical_shape, dst_mesh):
    """Reshard onto a mesh with a DIFFERENT device set (elastic shrink /
    grow): re-quantize under the source layout, then hand the layout
    change to the runtime's device-to-device copy.  Still no host
    materialization — ``jax.device_put`` between shardings moves shards
    directly (and ITS collective schedule is the arXiv:2112.01075
    implementation inside XLA)."""
    out_pshape = _out_pshape(logical_shape, dst_mesh)
    out = _requantize_op(data, tuple(int(s) for s in logical_shape),
                         out_pshape, None)
    return jax.device_put(out, NamedSharding(dst_mesh, P(*_mesh.AXIS_NAMES)))


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------

def pick_schedule(data, dst_mesh, schedule="auto") -> str:
    """The rechunk routing rule (the ``math.matmul`` algorithm= pattern):
    an explicit ``schedule=`` wins; ``"auto"`` consults
    ``DSLIB_RECHUNK_SCHEDULE`` and then the layouts — same-layout
    operands take the jit requantize, a relayout over the same device
    set (or a device-set EXPANSION, the elastic grow-back) takes the
    explicit panel exchange, any other device-set change falls back to
    the runtime copy."""
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown rechunk schedule {schedule!r}: expected "
                         f"one of {SCHEDULES}")
    if schedule == "auto":
        env = os.environ.get("DSLIB_RECHUNK_SCHEDULE", "auto")
        if env not in SCHEDULES:
            raise ValueError(f"bad DSLIB_RECHUNK_SCHEDULE={env!r}")
        schedule = env
    if schedule != "auto":
        return schedule
    sharding = getattr(data, "sharding", None)
    if isinstance(sharding, NamedSharding) and \
            sharding == _mesh.data_sharding(dst_mesh):
        return "xla"
    if dcn_supported(data, dst_mesh) and \
            _hosts.n_hosts(sharding.mesh) > 1:
        return "dcn"                    # hierarchical: coalesce DCN traffic
    if panel_supported(data, dst_mesh) or panel_grow_supported(data, dst_mesh):
        return "panels"
    return "deviceput"


def reshard(data, logical_shape, dst_mesh, schedule="auto", panels=None,
            overlap=None):
    """Reshard a padded device backing for ``dst_mesh``'s quantum and
    layout.  Returns ``(new_backing, schedule_used)``; never touches the
    host for an on-device operand.  ``overlap`` picks the panel
    exchange's loop schedule (None → the ``DSLIB_OVERLAP`` router)."""
    sched = pick_schedule(data, dst_mesh, schedule)
    if sched == "dcn":
        if not dcn_supported(data, dst_mesh):
            raise ValueError(
                "schedule='dcn' needs same-device-set meshes whose row "
                "axes both split into contiguous equal host blocks (the "
                "hierarchical layout `distributed.initialize` documents); "
                "use schedule='panels'/'deviceput' (or 'auto') otherwise")
        return dcn_rechunk(data, logical_shape, dst_mesh, panels,
                           overlap), sched
    if sched == "panels":
        if panel_supported(data, dst_mesh):
            return panel_rechunk(data, logical_shape, dst_mesh, panels,
                                 overlap), sched
        if panel_grow_supported(data, dst_mesh):
            return panel_grow_rechunk(data, logical_shape, dst_mesh,
                                      panels, overlap), sched
        raise ValueError(
            "schedule='panels' needs a fully-addressable source over "
            "the named mesh whose device set covers — or is strictly "
            "contained in (grow-back) — the target mesh's; use "
            "schedule='deviceput' (or 'auto') for any other device-set "
            "change")
    if sched == "deviceput":
        return deviceput_rechunk(data, logical_shape, dst_mesh), sched
    # "xla": one jitted requantize; any residual layout change is the SPMD
    # partitioner's (same-device-set inputs only, as for any jit)
    out_pshape = _out_pshape(logical_shape, dst_mesh)
    out = _requantize_op(data, tuple(int(s) for s in logical_shape),
                         out_pshape, dst_mesh)
    return out, sched


# ---------------------------------------------------------------------------
# sparse rechunk: the same three-schedule router over the row-panel-sharded
# sparse representation (round-14 sparse PR).  Block size / nse quantum /
# mesh shape are deployment details for sparse arrays too: the schedules
# move the ShardedSparse buffers between layouts ON DEVICE — never the
# host, never a densification.
#
# What makes sparse relayout cheap here is the representation's
# row-sorted / tail-padded invariant (data/sparse.py): the live entries
# form ONE global stream ordered by row, so any target layout is pure
# STATIC addressing — per-shard stream offsets computed on host from the
# layout-independent `row_nnz` histogram (control plane), with the data
# plane moved by masked-psum panel broadcasts (the summa idiom) or one
# gather.  arXiv:2112.01075's portable-redistribution shape, applied to
# a sparse payload.
# ---------------------------------------------------------------------------


def _sparse_layout(rep, dst_mesh, nse=None):
    """Host-side target-layout plan: per-dest-shard stream offsets and
    counts from the row histogram, the uniform target nse, and the
    source stream offsets from the source counts.  All O(device-count)
    host metadata — no device sync ever decides a shape."""
    from dislib_tpu.data.sparse import _padded_rows, _round_nse
    m = rep.shape[0]
    p2 = dst_mesh.shape[_mesh.ROWS]
    m_local2 = _padded_rows(m, dst_mesh) // p2
    cum = np.concatenate([[0], np.cumsum(rep.row_nnz)])
    e0_dst = tuple(int(cum[min(s * m_local2, m)]) for s in range(p2 + 1))
    cnt_dst = tuple(e0_dst[s + 1] - e0_dst[s] for s in range(p2))
    e0_src = tuple(int(v) for v in
                   np.concatenate([[0], np.cumsum(rep.counts)]))
    nse2 = _round_nse(max(cnt_dst, default=0), nse)
    return dict(e0_src=e0_src, e0_dst=e0_dst, cnt_dst=cnt_dst,
                nse2=nse2, m_local2=m_local2, p2=p2)


def pick_sparse_schedule(rep, dst_mesh, schedule="auto") -> str:
    """The sparse rechunk routing rule (the dense ``pick_schedule``
    pattern, same env override): same-device-grid moves take the fused
    nse requantize ("xla"), a relayout whose target devices all hold
    source shards takes the explicit masked-psum panel exchange
    ("panels"), a device-set change takes the gather + runtime
    device-to-device copy ("deviceput")."""
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown rechunk schedule {schedule!r}: expected "
                         f"one of {SCHEDULES}")
    if schedule == "auto":
        env = os.environ.get("DSLIB_RECHUNK_SCHEDULE", "auto")
        if env not in SCHEDULES:
            raise ValueError(f"bad DSLIB_RECHUNK_SCHEDULE={env!r}")
        schedule = env
    if schedule == "dcn":
        # no hierarchical sparse tier yet: the dense coalescing story
        # does not apply to the row-stream layout — take the panel path
        schedule = "panels"
    if schedule != "auto":
        return schedule
    src = rep.mesh
    if src.devices.shape == dst_mesh.devices.shape and \
            (src.devices == dst_mesh.devices).all():
        return "xla"
    if set(dst_mesh.devices.flat) <= set(src.devices.flat):
        return "panels"
    return "deviceput"


def reshard_sparse(rep, dst_mesh, schedule="auto", nse=None, overlap=None):
    """Re-lay out a :class:`~dislib_tpu.data.sparse.ShardedSparse` for
    ``dst_mesh`` (and/or a new uniform ``nse``) on device.  Returns the
    new representation; every schedule rebuilds the nse pads from zero
    (value 0 at the sentinel column — the poisoned-pad discipline), so a
    poisoned input tail cannot survive the reshard."""
    from dislib_tpu.data.sparse import ShardedSparse
    sched = pick_sparse_schedule(rep, dst_mesh, schedule)
    plan = _sparse_layout(rep, dst_mesh, nse)
    if sched == "xla":
        if not (rep.mesh.devices.shape == dst_mesh.devices.shape
                and (rep.mesh.devices == dst_mesh.devices).all()):
            raise ValueError(
                "schedule='xla' is the same-device-grid nse requantize — "
                "use 'panels'/'deviceput' (or 'auto') for a layout change")
        if plan["nse2"] == rep.nse and dst_mesh is rep.mesh:
            return rep                  # already canonical: metadata no-op
        d, lr, cc = _sparse_requantize(rep.data, rep.lrows, rep.cols,
                                       rep.counts_dev, plan["nse2"],
                                       dst_mesh)
        counts_dev = rep.counts_dev if dst_mesh is rep.mesh else None
        # `cols_host` rides along unchanged: relayout permutes entries
        # between shards but never reorders the global row-sorted stream
        return ShardedSparse(d, lr, cc, counts_dev, rep.counts,
                             rep.row_nnz, rep.shape, dst_mesh,
                             cols_host=rep.cols_host)
    if sched == "panels":
        if not set(dst_mesh.devices.flat) <= set(rep.mesh.devices.flat):
            raise ValueError(
                "schedule='panels' needs every target device to hold a "
                "source shard — use schedule='deviceput' (or 'auto') for "
                "a device-set change")
        return _sparse_panels_run(rep, dst_mesh, plan, overlap)
    # "deviceput": one gather re-bucketing under the source mesh, then
    # the runtime's device-to-device copy onto the target sharding
    idxmap = _sparse_index_map(plan, rep.nse)
    d, lr, cc = _sparse_regather(rep.data, rep.lrows, rep.cols,
                                 jnp.asarray(idxmap),
                                 rep.m_local, plan["m_local2"], rep.nse)
    sh1 = NamedSharding(dst_mesh, P(_mesh.ROWS))
    return ShardedSparse(
        jax.device_put(d, sh1), jax.device_put(lr, sh1),
        jax.device_put(cc, sh1), None,
        plan["cnt_dst"], rep.row_nnz, rep.shape, dst_mesh,
        cols_host=rep.cols_host)


def _sparse_index_map(plan, nse1):
    """(p2, nse2) int32 table: flat source slot feeding each target slot
    (−1 = pad) — host-built from the static stream offsets."""
    p2, nse2 = plan["p2"], plan["nse2"]
    e0s = np.asarray(plan["e0_src"], np.int64)
    out = np.full((p2, nse2), -1, np.int32)
    for s2 in range(p2):
        k = plan["cnt_dst"][s2]
        if not k:
            continue
        g = plan["e0_dst"][s2] + np.arange(k, dtype=np.int64)
        src = np.searchsorted(e0s, g, side="right") - 1
        out[s2, :k] = src * nse1 + (g - e0s[src])
    return out


@partial(_pjit, static_argnames=("nse2", "mesh"),
         name="rechunk_sparse_requantize")
def _sparse_requantize(data, lrows, cols, counts, nse2, mesh):
    """Fused nse re-pad: crop/zero-grow every buffer's nse axis to the
    new quantum and re-zero the slots past each shard's live count —
    pads rebuilt from the zero canvas whatever the input tail carried.
    ONE dispatch for all three buffers."""
    sharding = NamedSharding(mesh, P(_mesh.ROWS))
    p = data.shape[0]
    ok = lax.broadcasted_iota(jnp.int32, (p, nse2), 1) < counts[:, None]

    def one(x):
        keep = min(int(x.shape[1]), nse2)
        out = jnp.zeros((p, nse2), x.dtype)
        out = lax.dynamic_update_slice(out, x[:, :keep], (0, 0))
        out = jnp.where(ok, out, jnp.zeros((), x.dtype))
        return lax.with_sharding_constraint(out, sharding)

    return one(data), one(lrows), one(cols)


@partial(_pjit, static_argnames=("m_local1", "m_local2", "nse1"),
         name="rechunk_sparse_gather")
def _sparse_regather(data, lrows, cols, idxmap, m_local1, m_local2, nse1):
    """Re-bucket the entry stream via the host-built index map (the
    "xla"-collectives gather: the SPMD partitioner owns the movement) —
    the deviceput schedule's compute half.  Local row ids rebase from
    the source/target shard strides; pads land exactly (0, 0, 0)."""
    ok = idxmap >= 0
    li = jnp.clip(idxmap, 0, None)
    src_shard = li // nse1
    dst_shard = lax.broadcasted_iota(jnp.int32, idxmap.shape, 0)
    gd = data.reshape(-1)[li.reshape(-1)].reshape(idxmap.shape)
    glr = lrows.reshape(-1)[li.reshape(-1)].reshape(idxmap.shape) \
        + src_shard * m_local1 - dst_shard * m_local2
    gcc = cols.reshape(-1)[li.reshape(-1)].reshape(idxmap.shape)
    z32 = jnp.zeros((), jnp.int32)
    return (jnp.where(ok, gd, jnp.zeros((), data.dtype)),
            jnp.where(ok, glr.astype(jnp.int32), z32),
            jnp.where(ok, gcc, z32))


def _sparse_panels_run(rep, dst_mesh, plan, overlap=None):
    """The explicit sparse panel exchange: ONE jitted shard_map over the
    SOURCE mesh (one masked-psum broadcast of each source shard's
    buffers along 'rows', every device assembling its TARGET shard by
    static stream addressing), then a zero-copy rewrap onto the target
    mesh — the dense ``panel_rechunk`` shape with a sparse payload."""
    from dislib_tpu.data.sparse import ShardedSparse
    src_mesh = rep.mesh
    tr, _ = _target_coord_tables(src_mesh, dst_mesh)
    sched = _ov.resolve(overlap)
    _prof.count_schedule("rechunk_sparse_panels", sched)
    outs = _sparse_panel_exchange(
        rep.data, rep.lrows, rep.cols,
        src_mesh=src_mesh, tr_key=tuple(int(v) for v in tr),
        e0_src=plan["e0_src"], e0_dst=plan["e0_dst"],
        cnt_dst=plan["cnt_dst"], m_local1=rep.m_local,
        m_local2=plan["m_local2"], nse2=plan["nse2"], overlap=sched)
    sh1 = NamedSharding(dst_mesh, P(_mesh.ROWS))
    p2, nse2 = plan["p2"], plan["nse2"]

    def rewrap(arr):
        by_dev = {s.device: s.data for s in arr.addressable_shards}
        bufs = [by_dev[d] for d in dst_mesh.devices.flat]
        return jax.make_array_from_single_device_arrays(
            (p2, nse2), sh1, bufs)

    d, lr, cc = (rewrap(a) for a in outs)
    return ShardedSparse(d, lr, cc, None, plan["cnt_dst"],
                         rep.row_nnz, rep.shape, dst_mesh,
                         cols_host=rep.cols_host)


@partial(_pjit, static_argnames=("src_mesh", "tr_key", "e0_src", "e0_dst",
                                 "cnt_dst", "m_local1", "m_local2", "nse2",
                                 "overlap"),
         name="rechunk_sparse_panels")
def _sparse_panel_exchange(data, lrows, cols, src_mesh, tr_key, e0_src,
                           e0_dst, cnt_dst, m_local1, m_local2, nse2,
                           overlap="db"):
    """One masked-psum broadcast per source shard (the panel loop, run
    through ``ops/overlap.panel_pipeline`` under the ``DSLIB_OVERLAP``
    router); every device assembles its target shard's (nse2,) buffers
    by static stream addressing — slot i of target shard s' is global
    entry e0_dst[s'] + i, gathered out of whichever source panel's
    stream range covers it.  Pads assemble from the zero accumulator:
    (value 0, row 0, sentinel column 0) by construction."""
    rows_s = src_mesh.shape[_mesh.ROWS]
    cols_s = src_mesh.shape[_mesh.COLS]
    nse1 = data.shape[1]
    steps = rows_s

    def local(d_s, lr_s, cc_s):
        d, lr, cc = d_s[0], lr_s[0], cc_s[0]
        my_r = lax.axis_index(_mesh.ROWS)
        my_c = lax.axis_index(_mesh.COLS)
        my_lin = my_r * cols_s + my_c
        # stream ids fit int32: one relayout moves < 2^31 stored
        # entries (the int32 ceiling of the BCOO indices themselves)
        tr_tab = jnp.asarray(np.asarray(tr_key, np.int32))
        e0s = jnp.asarray(np.asarray(e0_src, np.int32))
        e0d = jnp.asarray(np.asarray(e0_dst, np.int32))
        cnt_tab = jnp.asarray(np.asarray(cnt_dst, np.int32))
        me = tr_tab[my_lin]                 # my TARGET row-rank
        i = lax.iota(jnp.int32, nse2)
        g = e0d[me] + i                     # global stream ids I assemble
        ok_i = i < cnt_tab[me]

        def fetch(t, prev):
            del prev                        # panels broadcast by source rank
            pan = tuple(jnp.where(my_r == t, x, jnp.zeros((), x.dtype))
                        for x in (d, lr, cc))
            return tuple(lax.psum(x, _mesh.ROWS) for x in pan)

        def consume(t, acc, pan):
            pd, plr, pcc = pan
            loc = g - e0s[t]
            ok = ok_i & (loc >= 0) & (g < e0s[t + 1])
            li = jnp.clip(loc, 0, nse1 - 1)
            glr = plr[li] + t * m_local1 - me * m_local2
            ad, alr, acc_cc = acc
            return (jnp.where(ok, pd[li], ad),
                    jnp.where(ok, glr, alr),
                    jnp.where(ok, pcc[li], acc_cc))

        acc0 = tuple(
            lax.pcast(jnp.zeros((nse2,), dt), (_mesh.ROWS, _mesh.COLS),
                      to="varying")
            for dt in (d.dtype, jnp.int32, jnp.int32))
        out = _ov.panel_pipeline(steps, fetch(0, None), fetch, consume,
                                 acc0, _ov.overlapped(overlap))
        return tuple(x[None, :] for x in out)

    return jax.shard_map(
        local, mesh=src_mesh,
        in_specs=(P(_mesh.ROWS), P(_mesh.ROWS), P(_mesh.ROWS)),
        out_specs=(P(_mesh.ROWS, _mesh.COLS),) * 3,
        check_vma=True,
    )(data, lrows, cols)
