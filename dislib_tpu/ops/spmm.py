"""Masked-psum SpMM — sparse @ dense riding the SUMMA fast path.

The recommender workload's matmul is ``ratings @ factors`` with ratings
at ≤1% density: densifying it costs O(m·n) memory and FLOPs for O(nnz)
information.  This kernel contracts the row-panel-sharded sparse buffers
(:class:`~dislib_tpu.data.sparse.ShardedSparse`) against a canonically
sharded dense operand in ONE jitted ``shard_map``, using exactly the
SUMMA panel-broadcast idiom (``ops/summa.py``): the dense operand's row
dim — the contraction dim, sharded over the mesh 'rows' axis — walks in
panels; each step the owner rank masked-``psum``-broadcasts its panel
along 'rows' (one collective per panel, ``check_vma`` on), and every
device folds the panel into its output block with a gather + segment-sum
over its LOCAL sparse entries (DrJAX's per-shard-update decomposition,
arXiv:2403.07128 — the rows of C are owned where the entries live, so
the only cross-shard movement is the B panel broadcast).

Panel schedule: the loop runs through ``ops/overlap.panel_pipeline`` —
``DSLIB_OVERLAP`` routes it (db = double-buffered default / seq /
pallas, a jit static, schedule-counter-observable as ``spmm:<sched>``),
panel t+1's broadcast issuing under panel t's gather/segment-sum.  All
schedules consume panels in identical order, so they are bit-equal
(``pallas`` pipelines like ``db``: the inner gather/scatter has no
Pallas variant, the ``panel_rechunk`` precedent).

Mixed precision: the per-entry products follow the library policy —
operands round to the policy compute dtype (``ops/precision.to_compute``)
and the segment-sums accumulate at the policy accumulation dtype (f32;
f64 for x64-mode f64 operands under the float32-floor policy) — the
``pdot`` contract expressed over a scatter contraction.

Memory: per device, the live set is the local sparse buffers (O(nnz/p)),
the local B block, the output block, and ONE in-flight panel (two under
db) of B — never a densified A, never a gathered B
(``tests/test_spmm.py`` pins this through ``compiled.memory_analysis()``).

Entry locality (the round-17 fix of the measured 0.87× panel-count
inflation): the default ``layout="slots"`` path consumes the
COL-PARTITIONED derived view (``ShardedSparse.panel_view``) — each
shard's live entries re-sorted into per-panel slot ranges, stored with
panel-local columns — so panel t touches ONLY its own contiguous
``nse_p`` slots: total per-entry work is O(nse + steps·quantum) instead
of the legacy masked path's O(steps·nse) re-mask of every entry per
panel.  That makes ``DSLIB_SPMM_PANELS`` a pure memory knob (in-flight
panel bytes ∝ 1/steps) with no arithmetic tax — the arXiv:1304.1835
discipline: move the schedule to the data.  ``layout="masked"`` remains
the view-free fallback (and the comm-probe body); the two layouts are
allclose, not bit-equal (slot regrouping reorders the segment sums),
while WITHIN a layout every overlap schedule stays bit-equal.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dislib_tpu.ops import overlap as _ov
from dislib_tpu.ops import precision as px
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.utils import profiling as _prof
from dislib_tpu.utils.profiling import profiled_jit as _pjit

__all__ = ["spmm", "spmm_panels", "spmm_steps", "spmm_memory_analysis",
           "spmm_masking_work"]


def _fit_steps(requested, k_pad):
    """Largest step count ≤ requested that divides the padded
    contraction dim (panels must tile it exactly — the dense
    ``_panels_per_rank`` precedent)."""
    for st in range(max(2, min(int(requested), k_pad)), 1, -1):
        if k_pad % st == 0:
            return st
    return 1


def spmm_steps(mesh=None, panels=None) -> int:
    """Panel count of the SpMM schedule: ``DSLIB_SPMM_PANELS`` (default
    4), clamped to ≥ 2 so the double-buffered pipeline has something to
    overlap.  The kernel's own step formula (the ``summa_steps``
    precedent).

    Unlike SUMMA's lcm-locked panel count, SpMM's panels DECOUPLE from
    the mesh: a panel may span several owner row-ranks (each
    masked-psum assembles the panel from every contributing rank).
    Under the default slot-range layout the panel count is a pure
    MEMORY knob — in-flight panel bytes ∝ 1/steps, per-entry work
    O(nse + steps·quantum) — so the default 4 keeps the panel at 1/4
    of B with no masking tax (the legacy ``layout="masked"`` path paid
    O(steps·nse): every entry re-masked per panel)."""
    del mesh
    if panels is None:
        panels = int(os.environ.get("DSLIB_SPMM_PANELS", "4"))
    return max(2, int(panels))


@partial(_pjit, static_argnames=("mesh", "policy", "overlap", "steps",
                                 "m_local", "layout"),
         name="spmm_panels")
@px.precise
def spmm_panels(data, lrows, cols, counts, bp, mesh, policy, steps,
                m_local, overlap="db", layout="masked"):
    """C = A @ B: sharded sparse buffers × canonically sharded dense.

    Under ``layout="masked"``, ``data``/``lrows``/``cols``/``counts``
    are the :class:`ShardedSparse` primary buffers (P('rows')-sharded);
    under ``layout="slots"`` they are the col-partitioned
    :class:`~dislib_tpu.data.sparse.SparsePanelView` buffers for THIS
    ``steps`` (panel-major slot ranges, panel-local columns, (p, steps)
    per-panel counts) and each panel step consumes only its own slot
    range.  ``bp`` is the dense padded (K_pad, N_pad) operand under the
    canonical (rows, cols) sharding, zero-pad invariant assumed.
    Returns the (M_pad, N_pad) product at the policy accumulation
    dtype, canonically sharded — M_pad = p · m_local by the
    representation's canonical-row-split invariant, so the output IS a
    valid dense ds-array backing.

    ONE dispatch end to end under every ``overlap`` schedule: the panel
    loop is a ``fori_loop`` inside this single jitted program.
    """
    k_pad = bp.shape[0]
    if k_pad % steps:
        raise ValueError(f"spmm: contraction dim {k_pad} not divisible "
                         f"by {steps} panels")
    if layout not in ("masked", "slots"):
        raise ValueError(f"spmm: unknown layout {layout!r}")
    if layout == "slots" and data.shape[1] % steps:
        raise ValueError(f"spmm: slot-range buffers of width "
                         f"{data.shape[1]} do not tile {steps} panels")
    h = k_pad // steps
    nse = data.shape[1]

    def local(d_s, lr_s, cc_s, cnt_s, b_loc):
        d_e, lr, cc, cnt = d_s[0], lr_s[0], cc_s[0], cnt_s[0]
        my_r = lax.axis_index(_mesh.ROWS)
        k_loc, n_loc = b_loc.shape
        bc = px.to_compute(b_loc, policy)
        if layout == "slots":
            nse_p = nse // steps
            vd = px.to_compute(d_e, policy).reshape(steps, nse_p)
            lrd = lr.reshape(steps, nse_p)
            ccd = cc.reshape(steps, nse_p)
            acc_dt = jnp.promote_types(px.accum_dtype(policy),
                                       jnp.promote_types(vd.dtype, bc.dtype))
        else:
            slot_ok = lax.broadcasted_iota(jnp.int32, (nse,), 0) < cnt
            vc = jnp.where(slot_ok, px.to_compute(d_e, policy),
                           jnp.zeros((), px.compute_dtype(policy)))
            acc_dt = jnp.promote_types(px.accum_dtype(policy),
                                       jnp.promote_types(vc.dtype, bc.dtype))

        def fetch(t, prev):
            del prev                     # broadcast panels slice by step
            # panel t covers global B rows [t·h, t·h + h); EVERY rank
            # contributes the slice it owns (zero elsewhere) and one
            # masked psum assembles the panel — a panel may span
            # several owner ranks, so the step count is a free knob
            i = lax.iota(jnp.int32, h)
            src = t * h + i - my_r * k_loc
            ok = (src >= 0) & (src < k_loc)
            pan = jnp.where(ok[:, None],
                            bc[jnp.clip(src, 0, k_loc - 1)],
                            jnp.zeros((), bc.dtype))
            return lax.psum(pan, _mesh.ROWS)

        if layout == "slots":
            def consume(t, acc, pan):
                # panel t's OWN slot range: nse_p entries, not nse — the
                # per-panel count masks the quantum tail (poisoned view
                # slots stay inert), the clip keeps a poisoned column
                # in-bounds for the (zero-weighted) gather
                ok = lax.broadcasted_iota(jnp.int32, (nse_p,), 0) < cnt[t]
                g = pan[jnp.clip(ccd[t], 0, h - 1)]       # (nse_p, n_loc)
                w = jnp.where(ok, vd[t], jnp.zeros((), vd.dtype))
                contrib = (g * w[:, None]).astype(acc.dtype)
                return acc + jax.ops.segment_sum(contrib, lrd[t],
                                                 num_segments=m_local)
        else:
            def consume(t, acc, pan):
                off = t * h              # the panel's global B-row window
                in_pan = (cc >= off) & (cc < off + h)
                g = pan[jnp.clip(cc - off, 0, h - 1)]        # (nse, n_loc)
                w = jnp.where(in_pan, vc, jnp.zeros((), vc.dtype))
                contrib = (g * w[:, None]).astype(acc.dtype)
                return acc + jax.ops.segment_sum(contrib, lr,
                                                 num_segments=m_local)

        acc0 = lax.pcast(jnp.zeros((m_local, n_loc), acc_dt),
                         (_mesh.ROWS, _mesh.COLS), to="varying")
        return _ov.panel_pipeline(steps, fetch(0, None), fetch, consume,
                                  acc0, _ov.overlapped(overlap))

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS), P(_mesh.ROWS), P(_mesh.ROWS),
                  P(_mesh.ROWS), P(_mesh.ROWS, _mesh.COLS)),
        out_specs=P(_mesh.ROWS, _mesh.COLS),
        check_vma=True,
    )(data, lrows, cols, counts, bp)


def spmm(a, b, *, precision=None, overlap=None, panels=None, layout=None):
    """sparse @ dense as one sharded masked-psum dispatch.

    ``a`` is a :class:`~dislib_tpu.data.sparse.SparseArray`, ``b`` a
    dense ds-array (re-laid-out to the canonical sharding if needed —
    the ``ensure_canonical`` ingest-guard contract).  Returns a dense
    ds-array.  This is a host routing boundary (the SUMMA entry
    precedent): the overlap schedule AND entry layout resolve here so a
    ``DSLIB_OVERLAP`` flip retraces, and the run is observable as
    ``spmm:<sched>`` + ``spmm_layout:<layout>`` schedule counters.
    ``layout`` defaults to ``"slots"`` (the col-partitioned slot-range
    view, cached on the backing); ``"masked"`` forces the legacy
    view-free path."""
    from dislib_tpu.data.array import Array, ensure_canonical
    from dislib_tpu.data.sparse import SparseArray
    if not isinstance(a, SparseArray):
        raise TypeError(f"spmm needs a SparseArray lhs, got {type(a)}")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"spmm shape mismatch: {a.shape} @ {b.shape}")
    mesh = _mesh.get_mesh()
    rep = a.sharded(mesh)
    b = ensure_canonical(b)
    sched = _ov.resolve(overlap)
    policy = px.resolve(precision)
    lay = "slots" if layout is None else layout
    _prof.count_schedule("spmm", sched)
    _prof.count_schedule("spmm_layout", lay)
    bd = b._data
    steps = _fit_steps(spmm_steps(mesh, panels), bd.shape[0])
    if lay == "slots":
        view = rep.panel_view(steps, bd.shape[0] // steps)
        out = spmm_panels(view.data, view.lrows, view.cols,
                          view.counts_dev, bd, mesh, policy, steps,
                          rep.m_local, overlap=sched, layout="slots")
    else:
        out = spmm_panels(rep.data, rep.lrows, rep.cols, rep.counts_dev,
                          bd, mesh, policy, steps, rep.m_local,
                          overlap=sched, layout="masked")
    return Array(out, (a.shape[0], b.shape[1]),
                 reg_shape=(a.block_size[0], b._reg_shape[1]))


def spmm_memory_analysis(a, b, *, precision=None, overlap=None,
                         panels=None, layout=None):
    """XLA's own accounting of the compiled SpMM program — an
    O(nnz)-scaled peak-live proxy.  Returns input/output/temp
    bytes plus ``temp_vs_dense``: temp as a fraction of what a densified
    A alone would allocate (the densify route's floor) — the number the
    O(nnz) claim rests on (``tests/test_spmm.py``).  Analyses the DEFAULT (slot-range) program
    unless ``layout="masked"``."""
    from dislib_tpu.data.array import ensure_canonical, _padded_shape
    mesh = _mesh.get_mesh()
    rep = a.sharded(mesh)
    b = ensure_canonical(b)
    lay = "slots" if layout is None else layout
    steps = _fit_steps(spmm_steps(mesh, panels), b._data.shape[0])
    kw = dict(mesh=mesh, policy=px.resolve(precision), steps=steps,
              m_local=rep.m_local, overlap=_ov.resolve(overlap),
              layout=lay)
    if lay == "slots":
        view = rep.panel_view(steps, b._data.shape[0] // steps)
        ops = (view.data, view.lrows, view.cols, view.counts_dev)
    else:
        ops = (rep.data, rep.lrows, rep.cols, rep.counts_dev)
    pm, pn = _padded_shape(a.shape, _mesh.pad_quantum(mesh))
    dense_a_bytes = 4 * pm * pn
    sparse_bytes = sum(int(x.size) * x.dtype.itemsize
                       for x in (rep.data, rep.lrows, rep.cols))
    res = {"sparse_in_bytes": sparse_bytes,
           "dense_b_bytes": int(b._data.size) * b._data.dtype.itemsize,
           "dense_a_bytes": dense_a_bytes, "temp_bytes": None,
           "temp_vs_dense": None, "steps": steps, "layout": lay}
    try:
        compiled = spmm_panels.lower(*ops, b._data, **kw).compile()
        ma = compiled.memory_analysis()
        temp = int(getattr(ma, "temp_size_in_bytes", 0))
        res["temp_bytes"] = temp
        res["temp_vs_dense"] = round(temp / max(dense_a_bytes, 1), 4)
    except Exception:  # noqa: BLE001 — backend without memory analysis
        pass
    return res


def spmm_masking_work(a, b=None, *, panels=None):
    """Per-dispatch entry-touch accounting of the two SpMM layouts — the
    masking-inflation evidence.  ``masked_work`` is what
    the legacy layout executes (every one of the nse slots re-masked on
    every panel: steps·nse); ``slots_work`` is what the slot-range
    layout executes (one nse_p slot range per panel: steps·nse_p ≈
    nnz + steps·quantum).  ``inflation`` = masked/slots — the factor
    the col-partitioned view removes, which is what turns the panel
    count into a pure memory knob."""
    from dislib_tpu.data.array import _padded_shape
    mesh = _mesh.get_mesh()
    rep = a.sharded(mesh)
    k = a.shape[1] if b is None else b.shape[0]
    k_pad = _padded_shape((k, 1), _mesh.pad_quantum(mesh))[0]
    steps = _fit_steps(spmm_steps(mesh, panels), k_pad)
    view = rep.panel_view(steps, k_pad // steps)
    masked = steps * rep.nse
    slots = steps * view.nse_p
    return {"steps": steps, "nse": rep.nse, "nse_p": view.nse_p,
            "masked_work": masked, "slots_work": slots,
            "inflation": round(masked / max(slots, 1), 4)}
