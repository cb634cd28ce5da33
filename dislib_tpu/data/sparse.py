"""Sparse ds-array — BCOO-backed storage (SURVEY.md §8 "Sparse support":
"TPU has no general CSR.  BCOO matvec covers ALS/svmlight ingestion;
dense-with-mask is the fallback; this decision gates ALS and sparse
KMeans/CSVM parity").

Reference capability: ds-array blocks may be SciPy CSR matrices
(`dislib/data/array.py`, `_sparse=True`); KMeans/CSVM/svmlight ingestion
accept them and per-block NumPy kernels dispatch to scipy.sparse ops.

TPU-native design and its honest limits:

- Storage is one `jax.experimental.sparse.BCOO` on device — O(nnz) memory,
  the role CSR plays for the reference.  Dense products against it
  materialise MXU-shaped results placed with the library sharding.
- **Row-sharded representation** (`ShardedRows`): the nonzeros are bucketed
  by row shard into rectangular (p, nnz_max) buffers — data, shard-local
  row ids, column ids — padded per shard with zero-valued entries so every
  shard is the same shape (BCOO's ragged buffers do not shard over a Mesh;
  rectangular buffers do).  `x @ B` is then shard-local (each shard owns
  disjoint output rows: gather B rows at the entry columns, scale,
  segment-sum by local row) and `xᵀ @ C` is a shard-local partial plus ONE
  `psum` over the rows axis — the identical communication structure to the
  dense KMeans path.  Sparse KMeans runs entirely on this representation.
- Per-estimator choice (recorded as SURVEY §8 directs):
  * KMeans — native sparse path (`fit`/`predict` accept SparseArray; the
    distance cross-term and the per-cluster sums are `bcoo_dot_general`
    contractions).
  * ALS — sparse-native on a SparseArray (`recommendation/als.py`: the
    row-sorted stream and its column-major order, `col_major`, laid out
    in windows a length class at a time); dense-with-mask on an Array (a
    zero rating IS the mask).
  * CascadeSVM — sparse-native: host-CSR-staged per-node sub-Grams feed
    the device dual solves; queries classify via one spmm cross-term
    (`classification/csvm.py`).
  * trees / others — densify (`to_dense()`); same stance as the
    reference's per-block `.toarray()` escape hatches.
"""

from __future__ import annotations

import math
import os
from collections import namedtuple
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from dislib_tpu.data.array import Array
from dislib_tpu.ops.base import precise
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.utils.profiling import host_read as _host_read
from dislib_tpu.utils.profiling import profiled_jit as _pjit

__all__ = ["SparseArray", "ShardedSparse", "SparsePanelView", "nse_quantum"]


def nse_quantum() -> int:
    """Per-shard nse (stored-entry) pad quantum: every shard's
    rectangular buffers are padded to a multiple of this, so two sparse
    arrays with similar per-shard fill share compiled kernel shapes (the
    dense pad-quantum discipline applied to the nse axis).
    ``DSLIB_SPARSE_NSE_QUANTUM`` overrides; default 64."""
    return max(1, int(os.environ.get("DSLIB_SPARSE_NSE_QUANTUM", "64")))


def densify_budget_bytes() -> int:
    """The byte budget above which densifying a SparseArray raises
    instead of silently OOMing a chip (``DSLIB_SPARSE_DENSIFY_BUDGET``,
    default 4 GiB) — consulted by the lazy dense escape hatch AND the
    ``math.matmul`` spmm/densify router."""
    return int(os.environ.get("DSLIB_SPARSE_DENSIFY_BUDGET", 4 << 30))


class ShardedSparse:
    """Row-panel-sharded sparse storage: the device-resident layout every
    sparse fast path (SpMM, sharded ALS, sharded KMeans, the ring tiers)
    consumes, and the unit the sparse ``ds.rechunk`` schedules move.

    Device buffers, each ``NamedSharding(mesh, P('rows'))``-sharded over
    the mesh row axis (``p`` = row-rank count):

    - ``data``  (p, nse) — entry values (float32, or float64 under x64);
    - ``lrows`` (p, nse) — shard-LOCAL row ids (global row − s·m_local);
    - ``cols``  (p, nse) — column ids;
    - ``counts_dev`` (p,) — per-shard live-entry count (the in-kernel
      slot-validity mask: ``iota < count`` — pads stay non-load-bearing
      even when poisoned).

    Layout invariants (what the rechunk schedules preserve/rebuild):

    - **canonical row split**: ``m_local = padded_rows(m) / p`` — the SAME
      row partition as a canonically sharded dense array, so SpMM's output
      block boundaries line up with the dense (rows, cols) sharding;
    - **row-sorted, tail-padded**: live entries are sorted by global row
      and occupy slots ``[0, counts[s])``; the global entry stream is the
      shard-major concatenation of the live slots (this is what makes
      relayout pure static addressing — arXiv:2112.01075's portable
      redistribution needs only offset tables);
    - **uniform nse pad** (``nse`` a :func:`nse_quantum` multiple, equal
      on every shard): pad entries are (value 0, row 0, column 0 — the
      sentinel column), so they are additive no-ops under every
      segment-sum even before the slot mask re-zeroes them — the
      poisoned-pad discipline.

    Host metadata (control plane only — never a device transfer):
    ``counts`` (tuple of per-shard ints), ``row_nnz`` (int64 (m,) per-row
    entry histogram, layout-independent: relayout target shapes are
    computed from it on host, so no device sync ever decides a shape),
    and ``cols_host`` (int32 (nnz,) global live-COLUMN stream in the
    row-sorted global entry order).  The column stream is as
    layout-independent as ``row_nnz`` — relayout permutes entries between
    shards but never reorders the global stream — so the rechunk
    schedules carry it through unchanged, and the col-partitioned panel
    view below sizes its slot ranges from it without a device sync.
    """

    __slots__ = ("data", "lrows", "cols", "_counts_dev", "counts",
                 "row_nnz", "shape", "mesh", "m_local", "nse", "_rowsq",
                 "cols_host", "_pviews", "_ell", "_rsteps", "_col_counts",
                 "plans")

    def __init__(self, data, lrows, cols, counts_dev, counts, row_nnz,
                 shape, mesh, cols_host=None):
        self.data = data
        self.lrows = lrows
        self.cols = cols
        self._counts_dev = counts_dev
        self.counts = tuple(int(c) for c in counts)
        self.row_nnz = row_nnz
        self.shape = (int(shape[0]), int(shape[1]))
        self.mesh = mesh
        self.m_local = _padded_rows(shape[0], mesh) // int(data.shape[0])
        self.nse = int(data.shape[1])
        self._rowsq = None
        self.cols_host = None if cols_host is None \
            else np.asarray(cols_host, np.int32)
        self._pviews = {}
        self._ell = None
        self._rsteps = {}
        self._col_counts = None
        # what an estimator derives from the layout and keeps with it
        # (ALS's blocking of each order), keyed by the estimator
        self.plans = {}

    @property
    def counts_dev(self):
        """Device (p,) per-shard live counts (the kernels' slot-mask
        operand), materialised LAZILY as a jit-embedded constant from
        the host metadata — a reshard-produced representation acquires
        it without a host→device transfer (transfer-guard clean)."""
        if self._counts_dev is None:
            self._counts_dev = _counts_kernel(self.counts, self.mesh)
        return self._counts_dev

    @property
    def p(self) -> int:
        return int(self.data.shape[0])

    @property
    def nnz(self) -> int:
        return int(sum(self.counts))

    def __repr__(self):
        return (f"ShardedSparse(shape={self.shape}, p={self.p}, "
                f"nse={self.nse}, nnz={self.nnz})")

    @classmethod
    def build(cls, rows, cols, vals, shape, mesh=None, nse=None):
        """Bucket host (row, col, val) triplets into the sharded layout
        (ingest: the one host-side construction path; on-device arrays
        move between layouts via the sparse rechunk schedules)."""
        mesh = mesh or _mesh.get_mesh()
        p = mesh.shape[_mesh.ROWS]
        m, n = (int(s) for s in shape)
        m_local = _padded_rows(m, mesh) // p
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals)
        if rows.size and (rows.min() < 0 or rows.max() >= m
                          or cols.min() < 0 or cols.max() >= n):
            raise ValueError(
                f"sparse indices out of range for shape {(m, n)} — "
                "quarantine the offending rows at ingest "
                "(load_svmlight_file / SparseArray.from_scipy do)")
        order = np.argsort(rows, kind="stable")
        rows, cols, vals = rows[order], cols[order], vals[order]
        row_nnz = np.bincount(rows, minlength=m).astype(np.int64)
        shard = rows // m_local
        counts = np.bincount(shard, minlength=p).astype(np.int64)
        nse_eff = _round_nse(int(counts.max(initial=0)), nse)
        data = np.zeros((p, nse_eff), vals.dtype if vals.dtype == np.float64
                        else np.float32)
        lr = np.zeros((p, nse_eff), np.int32)
        cc = np.zeros((p, nse_eff), np.int32)
        start = np.concatenate([[0], np.cumsum(counts)])
        slot = np.arange(rows.size) - start[shard]
        data[shard, slot] = vals
        lr[shard, slot] = rows - shard * m_local
        cc[shard, slot] = cols
        return cls._place(data, lr, cc, counts, row_nnz, (m, n), mesh,
                          cols_host=cols.astype(np.int32))

    @classmethod
    def _place(cls, data, lr, cc, counts, row_nnz, shape, mesh,
               cols_host=None):
        sh1 = jax.sharding.NamedSharding(mesh,
                                         jax.sharding.PartitionSpec(_mesh.ROWS))
        return cls(jax.device_put(jnp.asarray(data), sh1),
                   jax.device_put(jnp.asarray(lr), sh1),
                   jax.device_put(jnp.asarray(cc), sh1),
                   jax.device_put(jnp.asarray(np.asarray(counts, np.int32)),
                                  sh1),
                   counts, row_nnz, shape, mesh, cols_host=cols_host)

    def rowsq(self):
        """Device (p, m_local) per-row ‖x_i‖² — the KMeans/kNN distance
        term, derived ON DEVICE from the buffers (one jitted kernel,
        cached), so a rechunk-produced representation never touches the
        host to serve it."""
        if self._rowsq is None:
            self._rowsq = _rowsq_kernel(self.data, self.lrows,
                                        self.counts_dev, self.mesh,
                                        self.m_local)
        return self._rowsq

    def host_triplets(self):
        """(rows, cols, vals) global host triplets — the collect path
        (counts ONE host transfer via the blessed counter)."""
        with _host_read():
            d = np.asarray(jax.device_get(self.data))
            lr = np.asarray(jax.device_get(self.lrows))
            cc = np.asarray(jax.device_get(self.cols))
        rows_l, cols_l, vals_l = [], [], []
        for s, k in enumerate(self.counts):
            rows_l.append(lr[s, :k].astype(np.int64) + s * self.m_local)
            cols_l.append(cc[s, :k].astype(np.int64))
            vals_l.append(d[s, :k])
        cat = (np.concatenate(x) if x else np.zeros(0)
               for x in (rows_l, cols_l, vals_l))
        return tuple(cat)

    # -- col-partitioned panel view (the SpMM slot-range layout) -------------

    def _cols_stream(self):
        """Host int32 (nnz,) global live-column stream — ``cols_host``,
        or (for a representation built before the stream metadata
        existed) ONE blessed fetch through the transfer counter, cached.
        The stream is shard-major over live slots, which by the
        row-sorted invariant IS the global row-sorted entry order."""
        if self.cols_host is None:
            with _host_read():
                cc = np.asarray(jax.device_get(self.cols))
            self.cols_host = np.concatenate(
                [cc[s, :k] for s, k in enumerate(self.counts)]
            ).astype(np.int32)
        return self.cols_host

    def panel_counts(self, steps, h):
        """Host (p, steps) per-shard-per-PANEL live-entry histogram
        (panel t owns columns [t·h, (t+1)·h)) — the control-plane input
        that sizes the panel view's uniform slot ranges.  Pure host
        arithmetic over ``cols_host`` + ``counts``: no device sync ever
        decides a shape, the ``row_nnz`` discipline applied to the
        column axis."""
        cs = self._cols_stream()
        start = np.concatenate([[0], np.cumsum(self.counts)]).astype(np.int64)
        pc = np.zeros((self.p, steps), np.int64)
        for s in range(self.p):
            seg = cs[start[s]:start[s + 1]] // h
            if seg.size:
                pc[s, :] = np.bincount(seg, minlength=steps)[:steps]
        return pc

    def panel_view(self, steps, h):
        """Cached col-partitioned :class:`SparsePanelView` for a
        ``steps``-panel schedule of width ``h`` columns.

        Each shard's live entries are re-sorted (stably, so row order
        survives within a panel) into per-panel slot ranges: panel t owns
        slots [t·nse_p, (t+1)·nse_p) with nse_p the nse-quantum-rounded
        max per-(shard, panel) count.  An SpMM panel step then touches
        ONLY its own contiguous slot range — O(nse + steps·quantum) total
        masking work instead of re-masking all nse entries per panel
        (O(steps·nse)) — which is what makes ``DSLIB_SPMM_PANELS`` a pure
        memory knob.  Stored columns are PANEL-LOCAL (col − t·h); pads
        rebuild from the zero canvas (poisoned primary pads are dropped
        by the slot mask before the re-sort ever sees them).  Built on
        device in one jitted dispatch; derived + cached, so rechunk
        products simply rebuild it lazily."""
        key = (int(steps), int(h))
        if key not in self._pviews:
            pc = self.panel_counts(steps, h)
            nse_p = _round_nse(int(pc.max(initial=0)))
            d, lr, cc = _panel_view_kernel(self.data, self.lrows, self.cols,
                                           self.counts_dev, self.mesh,
                                           int(steps), int(h), nse_p)
            cdev = _pcounts_kernel(tuple(map(tuple, pc.tolist())), self.mesh)
            self._pviews[key] = SparsePanelView(d, lr, cc, cdev, nse_p,
                                                int(steps), int(h))
        return self._pviews[key]

    # -- estimator staging views (built on device, no host round-trip) -------

    def ell_buffers(self):
        """Padded ELL ``(vals (p·m_local, r), cols (p·m_local, r))`` with
        r = max row nnz, built ON DEVICE from the sharded buffers (one
        jitted shard-local scatter — the entries are row-sorted within a
        shard, so slot-within-row is position minus the row's first
        occurrence).  Rows stay P('rows')-sharded; padded rows past the
        logical m are all-zero, so a row gather past m contributes
        nothing.  Derived + cached: the device replacement for the host
        ``argsort``/bincount staging, which is what makes a sharded-backed
        CascadeSVM fit entry transfer-free."""
        if self._ell is None:
            r = max(1, int(self.row_nnz.max(initial=1)))
            self._ell = _ell_kernel(self.data, self.lrows, self.cols,
                                    self.counts_dev, self.mesh, r,
                                    self.m_local)
        return self._ell

    def row_step_plan(self, chunk):
        """Host ``(steps, budget)`` greedy row-step packing from
        ``row_nnz`` alone — identical math to the legacy host-CSR plan
        (same steps, same budget), but pure control-plane arithmetic:
        no device sync ever decides the step shapes.  Each step is
        ``(row_off, rows_in, nnz_lo, nnz_hi)`` over the global row-sorted
        entry stream; steps tile the stream contiguously."""
        m = self.shape[0]
        row_start = np.concatenate([[0], np.cumsum(self.row_nnz)])
        avg_chunk_nnz = max(1, int(np.ceil(int(row_start[-1]) * chunk
                                           / max(m, 1))))
        budget = max(64, 4 * avg_chunk_nnz, int(self.row_nnz.max(initial=1)))
        steps = []
        r = 0
        while r < m:
            r_end = r
            while (r_end < m and r_end - r < chunk
                   and (r_end == r
                        or row_start[r_end + 1] - row_start[r] <= budget)):
                r_end += 1
            steps.append((r, r_end - r, int(row_start[r]),
                          int(row_start[r_end])))
            r = r_end
        if not steps:
            steps = [(0, 0, 0, 0)]
        return steps, budget

    def row_step_buffers(self, chunk):
        """The kNN streaming buffers ``(data (s, budget), local_rows,
        cols, row_off (s,), rows_in (s,))`` gathered ON DEVICE: by the
        row-sorted invariant (and the canonical row split — shards own
        contiguous disjoint row ranges) the shard-major live stream IS the
        global row-sorted stream, so each shard scatters its own slice of
        every step and one psum replicates the result.  Bit-identical to
        the legacy host-CSR staging (same plan, same entry order).
        Cached per chunk."""
        key = int(chunk)
        if key not in self._rsteps:
            plan, budget = self.row_step_plan(chunk)
            starts = tuple(int(v) for v in
                           np.concatenate([[0], np.cumsum(self.counts)]))
            self._rsteps[key] = _row_steps_kernel(
                self.data, self.lrows, self.cols, self.counts_dev,
                self.mesh, tuple(plan), int(budget), self.m_local, starts)
        return self._rsteps[key]

    # -- the column-major order of the same entries (ALS's item half-step) ---

    def col_counts(self):
        """Host int64 (p, n) per-shard live-entry histogram over the
        columns: counted ON DEVICE and read once (p·n integers, never the
        entry stream), cached.  With ``row_nnz`` it sizes every blocking
        of either order without a look at the entries."""
        if self._col_counts is None:
            with _host_read():
                self._col_counts = np.asarray(jax.device_get(
                    _col_counts_kernel(self.cols, self.counts_dev, self.mesh,
                                       self.shape[1])), np.int64)
        return self._col_counts

    def col_major(self):
        """``(lrows, data)``, each (p, nse): every shard's live entries
        sorted by column, stably (so rows ascend within a column), live
        slots first and the pads (row 0, value 0) after them.  Column c of
        shard s occupies slots ``[C[s, c], C[s, c] + col_counts()[s, c])``
        with C the exclusive cumulative sum of :meth:`col_counts`, so the
        column ids need no buffer of their own.  Built ON DEVICE in one
        dispatch (one stable sort a shard).  Not cached: its consumer
        keeps what it derives from it (ALS its windowed item layout, in
        :attr:`plans`), and the sort's 2/3 of the entries' bytes would
        otherwise stay beside that."""
        return _col_major_kernel(self.data, self.lrows, self.cols,
                                 self.counts_dev, self.mesh, self.shape[1])


def _padded_rows(m, mesh):
    from dislib_tpu.data.array import _padded_shape
    return _padded_shape((m, 1), _mesh.pad_quantum(mesh))[0]


def _round_nse(nse_min, explicit=None):
    q = nse_quantum()
    need = max(int(nse_min), 1)
    if explicit is not None:
        if int(explicit) < need:
            raise ValueError(
                f"requested nse {explicit} < the densest shard's "
                f"{need} live entries")
        need = int(explicit)
    return int(math.ceil(need / q) * q)


@partial(_pjit, static_argnames=("counts", "mesh"), name="sparse_counts")
def _counts_kernel(counts, mesh):
    tab = jnp.asarray(np.asarray(counts, np.int32))
    return jax.lax.with_sharding_constraint(
        tab, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(_mesh.ROWS)))


@partial(_pjit, static_argnames=("mesh", "m_local"), name="sparse_rowsq")
def _rowsq_kernel(data, lrows, counts, mesh, m_local):
    from jax.sharding import PartitionSpec as P

    def local(d_s, lr_s, cnt_s):
        d, lr, cnt = d_s[0], lr_s[0], cnt_s[0]
        ok = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0) < cnt
        v = jnp.where(ok, d, jnp.zeros((), d.dtype))
        return jax.ops.segment_sum(v * v, lr,
                                   num_segments=m_local)[None, :]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS), P(_mesh.ROWS), P(_mesh.ROWS)),
        out_specs=P(_mesh.ROWS),
        check_vma=True,
    )(data, lrows, counts)


SparsePanelView = namedtuple(
    "SparsePanelView",
    ("data", "lrows", "cols", "counts_dev", "nse_p", "steps", "h"))
SparsePanelView.__doc__ = """Col-partitioned derived view of a
:class:`ShardedSparse` (see :meth:`ShardedSparse.panel_view`): ``data`` /
``lrows`` / ``cols`` are (p, steps·nse_p) buffers whose panel-t live
entries occupy slots [t·nse_p, t·nse_p + counts_dev[s, t]); ``cols``
holds PANEL-LOCAL column ids (col − t·h); ``counts_dev`` is the (p,
steps) per-shard-per-panel live-count table (a jit-embedded constant —
transfer-guard clean)."""


@partial(_pjit, static_argnames=("pcounts", "mesh"), name="sparse_pcounts")
def _pcounts_kernel(pcounts, mesh):
    tab = jnp.asarray(np.asarray(pcounts, np.int32))
    return jax.lax.with_sharding_constraint(
        tab, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(_mesh.ROWS)))


@partial(_pjit, static_argnames=("mesh", "steps", "h", "nse_p"),
         name="sparse_panel_view")
def _panel_view_kernel(data, lrows, cols, counts, mesh, steps, h, nse_p):
    """Device re-sort of each shard's live entries into per-panel slot
    ranges (ONE jitted dispatch, the staging half of the slot-range SpMM
    layout).  Stable within a panel: rank-within-panel comes from a
    cumulative one-hot count over the (row-sorted) live stream, so row
    order — and with it segment-sum determinism — survives.  Pads and
    anything the slot mask rejects scatter with ``mode="drop"`` onto the
    zero canvas: a poisoned primary-buffer tail cannot enter the view."""
    from jax.sharding import PartitionSpec as P

    def local(d_s, lr_s, cc_s, cnt_s):
        d, lr, cc, cnt = d_s[0], lr_s[0], cc_s[0], cnt_s[0]
        nse = d.shape[0]
        live = jax.lax.broadcasted_iota(jnp.int32, (nse,), 0) < cnt
        pan = jnp.where(live, cc // h, steps)          # sentinel for pads
        pan_c = jnp.clip(pan, 0, steps - 1)
        onehot = (pan[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (nse, steps), 1)).astype(jnp.int32)
        rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0),
                                   pan_c[:, None], axis=1)[:, 0] - 1
        dest = jnp.where(live, pan_c * nse_p + rank, steps * nse_p)

        def scat(src, dt):
            z = jnp.zeros((steps * nse_p,), dt)
            return z.at[dest].set(src.astype(dt), mode="drop")

        nd = scat(jnp.where(live, d, jnp.zeros((), d.dtype)), d.dtype)
        nlr = scat(jnp.where(live, lr, 0), jnp.int32)
        ncc = scat(jnp.where(live, cc - pan_c * h, 0), jnp.int32)
        return nd[None], nlr[None], ncc[None]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS),) * 4,
        out_specs=(P(_mesh.ROWS),) * 3,
        check_vma=True,
    )(data, lrows, cols, counts)


@partial(_pjit, static_argnames=("mesh", "r", "m_local"), name="sparse_ell")
def _ell_kernel(data, lrows, cols, counts, mesh, r, m_local):
    """Shard-local ELL build: entries are row-sorted within a shard, so
    slot-within-row = position − searchsorted-first-occurrence (pads are
    pushed to the ``m_local`` sentinel row first, keeping the keys
    sorted).  Pads scatter with ``mode="drop"`` onto the zero canvas —
    poisoned tails never enter the view."""
    from jax.sharding import PartitionSpec as P

    def local(d_s, lr_s, cc_s, cnt_s):
        d, lr, cc, cnt = d_s[0], lr_s[0], cc_s[0], cnt_s[0]
        nse = d.shape[0]
        pos = jax.lax.broadcasted_iota(jnp.int32, (nse,), 0)
        live = pos < cnt
        keys = jnp.where(live, lr, m_local)
        slot = pos - jnp.searchsorted(keys, keys, side="left").astype(
            jnp.int32)
        dest = jnp.where(live, lr * r + slot, m_local * r)

        def scat(src, dt):
            z = jnp.zeros((m_local * r,), dt)
            return z.at[dest].set(src.astype(dt), mode="drop")

        vals = scat(jnp.where(live, d, jnp.zeros((), d.dtype)), d.dtype)
        ccc = scat(jnp.where(live, cc, 0), jnp.int32)
        return (vals.reshape(1, m_local, r), ccc.reshape(1, m_local, r))

    ev, ec = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS),) * 4,
        out_specs=(P(_mesh.ROWS),) * 2,
        check_vma=True,
    )(data, lrows, cols, counts)
    p = data.shape[0]
    return ev.reshape(p * m_local, r), ec.reshape(p * m_local, r)


@partial(_pjit, static_argnames=("mesh", "plan", "budget", "m_local",
                                 "starts"),
         name="sparse_row_steps")
def _row_steps_kernel(data, lrows, cols, counts, mesh, plan, budget,
                      m_local, starts):
    """Device gather of the kNN row-step buffers: shard s owns global
    stream ids [starts[s], starts[s+1]) (shard-major live slots ARE the
    global row-sorted stream), so each shard scatters its slice of every
    step — destination step by searchsorted over the static step
    boundaries — and a psum over 'rows' replicates the (s, budget)
    rectangles.  Step tables are jit-embedded constants (transfer-guard
    clean)."""
    from jax.sharding import PartitionSpec as P

    s = len(plan)
    row_off_np = np.asarray([st[0] for st in plan], np.int32)
    rows_in_np = np.asarray([st[1] for st in plan], np.int32)
    nlo_np = np.asarray([st[2] for st in plan], np.int64)

    def local(d_s, lr_s, cc_s, cnt_s):
        d, lr, cc, cnt = d_s[0], lr_s[0], cc_s[0], cnt_s[0]
        nse = d.shape[0]
        my = jax.lax.axis_index(_mesh.ROWS)
        e0 = jnp.asarray(np.asarray(starts, np.int32))[my]
        pos = jax.lax.broadcasted_iota(jnp.int32, (nse,), 0)
        live = pos < cnt
        g = e0 + pos                                # global stream id
        nlo = jnp.asarray(nlo_np.astype(np.int32))
        step = jnp.clip(jnp.searchsorted(nlo, g, side="right").astype(
            jnp.int32) - 1, 0, s - 1)
        within = g - nlo[step]
        lrl = lr + my * m_local - jnp.asarray(row_off_np)[step]
        dest = jnp.where(live, step * budget + within, s * budget)

        def scat(src, dt):
            z = jnp.zeros((s * budget,), dt)
            return z.at[dest].set(src.astype(dt), mode="drop")

        out = tuple(
            jax.lax.psum(scat(jnp.where(live, v, jnp.zeros((), v.dtype)),
                              v.dtype).reshape(s, budget), _mesh.ROWS)
            for v in (d, lrl, cc))
        return out

    dta, lrl, ccl = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS),) * 4,
        out_specs=(P(None, None),) * 3,
        check_vma=True,
    )(data, lrows, cols, counts)
    return (dta, lrl, ccl, jnp.asarray(row_off_np), jnp.asarray(rows_in_np))


@partial(_pjit, static_argnames=("mesh", "n"), name="sparse_col_counts")
def _col_counts_kernel(cols, counts, mesh, n):
    from jax.sharding import PartitionSpec as P

    def local(cc_s, cnt_s):
        cc, cnt = cc_s[0], cnt_s[0]
        live = jax.lax.broadcasted_iota(jnp.int32, cc.shape, 0) < cnt
        return jax.ops.segment_sum(live.astype(jnp.int32), cc,
                                   num_segments=n)[None]

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(_mesh.ROWS), P(_mesh.ROWS)),
                         out_specs=P(_mesh.ROWS), check_vma=True)(cols, counts)


@partial(_pjit, static_argnames=("mesh", "n"), name="sparse_col_major")
def _col_major_kernel(data, lrows, cols, counts, mesh, n):
    """A shard's live entries sorted by column: ONE stable sort keyed on
    the column, the pads keyed past the last column so that they stay at
    the tail (their row and value zeroed: a poisoned pad cannot enter)."""
    from jax.sharding import PartitionSpec as P

    def local(d_s, lr_s, cc_s, cnt_s):
        d, lr, cc, cnt = d_s[0], lr_s[0], cc_s[0], cnt_s[0]
        with jax.named_scope("dslib.als.layout"):
            live = jax.lax.broadcasted_iota(jnp.int32, cc.shape, 0) < cnt
            keys = jnp.where(live, cc, n)
            _, rows, vals = jax.lax.sort(
                (keys, jnp.where(live, lr, 0),
                 jnp.where(live, d, jnp.zeros((), d.dtype))),
                num_keys=1, is_stable=True)
        return rows[None], vals[None]

    return jax.shard_map(local, mesh=mesh, in_specs=(P(_mesh.ROWS),) * 4,
                         out_specs=(P(_mesh.ROWS),) * 2,
                         check_vma=True)(data, lrows, cols, counts)


class SparseArray:
    """A 2-D sparse matrix on device (the CSR-block role).

    Two backings, one API: a single-device BCOO (ingest / host staging),
    and/or the row-panel-sharded :class:`ShardedSparse` buffers (the fast
    path — SpMM, sharded fits, serving, the sparse ``ds.rechunk``
    schedules).  A sharded-only array (the product of an on-device
    rechunk) materialises its BCOO lazily, on host, ONLY when a legacy
    path asks for it — the fast paths never do."""

    def __init__(self, bcoo: jsparse.BCOO | None = None, reg_shape=None,
                 *, sharded: "ShardedSparse | None" = None):
        if (bcoo is None) == (sharded is None):
            if bcoo is None:
                raise ValueError("SparseArray needs a BCOO or a "
                                 "ShardedSparse backing")
        self._bcoo_val = bcoo
        self._sharded_rep = sharded
        src = bcoo if bcoo is not None else sharded
        self._shape = (int(src.shape[0]), int(src.shape[1]))
        self._reg_shape = reg_shape or self._shape
        self._sparse = True
        self._dense_cache = None

    @property
    def _bcoo(self) -> jsparse.BCOO:
        """The single-device BCOO view, built from the sharded buffers on
        first touch for sharded-only arrays (a host materialisation — the
        blessed legacy escape hatch, counted as a transfer)."""
        if self._bcoo_val is None:
            rows, cols, vals = self._sharded_rep.host_triplets()
            idx = np.stack([rows, cols], axis=1).astype(np.int32)
            self._bcoo_val = jsparse.BCOO(
                (jnp.asarray(vals), jnp.asarray(idx)), shape=self._shape)
        return self._bcoo_val

    # -- sharded representation (the fast-path backing) ----------------------

    def sharded(self, mesh=None) -> "ShardedSparse":
        """The :class:`ShardedSparse` buffers for ``mesh`` (default: the
        library mesh) — the sparse analog of ``ensure_canonical``.  A
        matching backing returns as-is; a backing laid out for ANOTHER
        mesh re-lands ON DEVICE through the sparse rechunk schedules
        (never the host, never dense); a BCOO-only array buckets its host
        triplets once (ingest) and caches the result."""
        mesh = mesh or _mesh.get_mesh()
        rep = self._sharded_rep
        if rep is not None:
            if rep.mesh is mesh:
                return rep
            from dislib_tpu.ops import rechunk as _rc
            rep = _rc.reshard_sparse(rep, mesh)
            self._sharded_rep = rep
            return rep
        idx = np.asarray(jax.device_get(self._bcoo.indices))
        val = np.asarray(jax.device_get(self._bcoo.data))
        rep = ShardedSparse.build(idx[:, 0], idx[:, 1], val, self._shape,
                                  mesh)
        self._sharded_rep = rep
        return rep

    def resharded(self, mesh=None, *, schedule="auto", nse=None,
                  overlap=None) -> "SparseArray":
        """A NEW SparseArray whose sharded backing is laid out for
        ``mesh`` / ``nse`` — the ``ds.rechunk`` sparse entry.  On-device
        for an already-sharded source (fused nse re-pad / masked-psum
        panel exchange / deviceput, per the schedule router)."""
        from dislib_tpu.ops import rechunk as _rc
        mesh = mesh or _mesh.get_mesh()
        src = self._sharded_rep
        if src is None:
            src = self.sharded(mesh if schedule in ("auto", "xla")
                               else _mesh.get_mesh())
        rep = _rc.reshard_sparse(src, mesh, schedule=schedule, nse=nse,
                                 overlap=overlap)
        return SparseArray(sharded=rep, reg_shape=self._reg_shape)

    @property
    def _data(self):
        """Lazy padded dense backing — the reference's per-block
        ``.toarray()`` escape hatch, so every non-sparse-aware estimator
        transparently accepts a SparseArray (at densification memory cost).
        Sparse-aware paths (KMeans, NearestNeighbors) dispatch on the type
        before touching this.  Guarded: densification past the
        ``DSLIB_SPARSE_DENSIFY_BUDGET`` byte budget (default 4 GiB) raises
        instead of silently OOMing a chip — raise the env var to opt out."""
        if self._dense_cache is None:
            from dislib_tpu.data.array import _padded_shape
            # the dense backing is PADDED to the mesh quantum — budget on
            # the real allocation, not the logical shape
            pm, pn = _padded_shape(self._shape, _mesh.pad_quantum())
            need = 4 * pm * pn                                  # f32 bytes
            budget = densify_budget_bytes()
            if need > budget:
                raise MemoryError(
                    f"densifying this {self._shape} SparseArray needs "
                    f"~{need / 2**30:.1f} GiB (> budget "
                    f"{budget / 2**30:.1f} GiB). This estimator has no "
                    "sparse-native path; use a sparse-aware one (KMeans, "
                    "NearestNeighbors, KNeighborsClassifier, CascadeSVM, "
                    "ALS, scalers) "
                    "or raise DSLIB_SPARSE_DENSIFY_BUDGET to densify "
                    "anyway.")
            self._dense_cache = self.to_dense()._data
        return self._dense_cache

    # -- construction --------------------------------------------------------

    @classmethod
    def from_scipy(cls, mat, block_size=None, dtype=None,
                   quarantine=False, labels=None) -> "SparseArray":
        """Build from a scipy sparse matrix.

        ``dtype`` — entry dtype (default float32; float64 passes through
        on x64 rigs for the full-precision grid).  ``quarantine=True``
        routes the rows through the ingest hygiene (non-finite stored
        values quarantined per row, reported to the process
        :class:`~dislib_tpu.data.io.QuarantineLedger` with a label-aligned
        ``keep_mask``) — the row-batch sparse STREAM entry: a
        ``partial_fit`` producer building one SparseArray per batch gets
        the same hygiene as the dense loaders.  Returns the array (its
        ``.quarantine_`` carries the report); pass ``labels`` to get
        ``(array, clean_labels)`` back, kept row-aligned."""
        report = None
        if quarantine:
            from dislib_tpu.data.io import _quarantine_csr
            mat = mat.tocsr()
            y = np.zeros(mat.shape[0], np.float32) if labels is None \
                else np.asarray(labels)
            mat, y, report = _quarantine_csr(mat, y, "SparseArray.from_scipy",
                                             True)
            labels = None if labels is None else y
        coo = mat.tocoo()
        dt = np.float64 if (dtype is not None
                            and np.dtype(dtype) == np.float64) else np.float32
        data = jnp.asarray(coo.data.astype(dt))
        idx = jnp.asarray(np.stack([coo.row, coo.col], axis=1).astype(np.int32))
        bcoo = jsparse.BCOO((data, idx), shape=mat.shape)
        out = cls(bcoo, reg_shape=block_size)
        out.quarantine_ = report
        return out if labels is None else (out, labels)

    @classmethod
    def from_dense(cls, x, block_size=None, dtype=None) -> "SparseArray":
        dt = np.float64 if (dtype is not None
                            and np.dtype(dtype) == np.float64) else np.float32
        x = np.asarray(x, dtype=dt)
        return cls(jsparse.BCOO.fromdense(jnp.asarray(x)), reg_shape=block_size)

    # -- metadata ------------------------------------------------------------

    @property
    def shape(self):
        return self._shape

    @property
    def dtype(self):
        return self._bcoo.dtype

    @property
    def nnz(self) -> int:
        if self._bcoo_val is None:      # sharded-only: exact host metadata
            return self._sharded_rep.nnz
        return int(self._bcoo.nse)

    @property
    def block_size(self):
        return self._reg_shape

    def __repr__(self):
        return (f"dslib.SparseArray(shape={self._shape}, nnz={self.nnz}, "
                f"dtype={self.dtype})")

    # -- sync / conversion ---------------------------------------------------

    def collect(self):
        """Materialise as scipy CSR on host (reference sparse collect)."""
        import scipy.sparse as sp
        data = np.asarray(jax.device_get(self._bcoo.data))
        idx = np.asarray(jax.device_get(self._bcoo.indices))
        return sp.csr_matrix((data, (idx[:, 0], idx[:, 1])), shape=self._shape)

    def to_dense(self) -> Array:
        """Densify onto the mesh (the reference's `.toarray()` escape
        hatch).  A sharded-backed array densifies ON DEVICE (one jitted
        scatter onto the canonical zero canvas — the matmul router's
        ``algorithm="densify"`` path never detours through the host)."""
        if self._sharded_rep is not None:
            from dislib_tpu.data.array import _padded_shape
            rep = self._sharded_rep
            pshape = _padded_shape(self._shape, _mesh.pad_quantum(rep.mesh))
            out = _densify_kernel(rep.data, rep.lrows, rep.cols,
                                  rep.counts_dev, pshape, rep.m_local,
                                  rep.mesh)
            return Array(out, self._shape, reg_shape=self._reg_shape)
        return Array._from_logical(self._bcoo.todense())

    def _csr(self):
        """Cached host CSR mirror (O(nnz)) — the staging layout for row
        selection and the CSVM sub-Gram path."""
        if getattr(self, "_csr_cache", None) is None:
            self._csr_cache = self.collect().tocsr()
        return self._csr_cache

    def __getitem__(self, key) -> "SparseArray":
        """Slice / fancy-index rows and columns, staying sparse.

        Selection is staged through the cached host CSR (scipy's indexed
        slicing keeps exactly the selected nonzeros — the same block
        movement the reference's KFold does between CSR blocks), then
        returns a new device SparseArray.  This is what KFold /
        train_test_split / shuffle use on sparse inputs.
        """
        from dislib_tpu.data.array import _split_key, _normalize_index
        rows, cols = _split_key(key)
        r_idx, r_len = _normalize_index(rows, self._shape[0])
        c_idx, c_len = _normalize_index(cols, self._shape[1])
        del r_len, c_len  # scipy's indexed shape is already exact
        sub = self._csr()[r_idx][:, c_idx]
        return SparseArray.from_scipy(sub.tocsr())

    # -- ops -----------------------------------------------------------------

    def transpose(self) -> "SparseArray":
        return SparseArray(self._bcoo.T, reg_shape=(self._reg_shape[1],
                                                    self._reg_shape[0]))

    @property
    def T(self) -> "SparseArray":
        return self.transpose()

    def __matmul__(self, other):
        """sparse @ dense → dense Array, through the ``math.matmul``
        spmm/densify router (the sharded masked-psum SpMM when density is
        low, one densified GEMM when it is not)."""
        from dislib_tpu.math import matmul as _matmul
        if not isinstance(other, Array):
            other = Array._from_logical(
                jnp.asarray(np.asarray(other, dtype=np.float32)))
        return _matmul(self, other)

    def sum(self, axis=0) -> Array:
        if axis not in (0, 1, None):
            raise ValueError("axis must be 0, 1 or None")
        data, idx = self._bcoo.data, self._bcoo.indices
        if axis is None:
            return Array._from_logical(jnp.sum(data).reshape(1, 1))
        keep = 1 - axis                     # reduce over `axis`, group by the other
        segs = jax.ops.segment_sum(data, idx[:, keep],
                                   num_segments=self._shape[keep])
        out = segs.reshape(1, -1) if axis == 0 else segs.reshape(-1, 1)
        return Array._from_logical(out)

    def mean(self, axis=0) -> Array:
        denom = self._shape[0] if axis == 0 else \
            self._shape[1] if axis == 1 else self._shape[0] * self._shape[1]
        return self.sum(axis) * (1.0 / denom)

    def row_norms_sq(self):
        """Device vector of per-row ‖x_i‖² (KMeans distance term)."""
        data, idx = self._bcoo.data, self._bcoo.indices
        return jax.ops.segment_sum(data * data, idx[:, 0],
                                   num_segments=self._shape[0])

    # -- elementwise (weak-#6 parity: keep sparsity where it is exact) -------

    def square(self) -> "SparseArray":
        """Elementwise x² — sparsity-preserving (0² = 0)."""
        bcoo = jsparse.BCOO((self._bcoo.data * self._bcoo.data,
                             self._bcoo.indices), shape=self._bcoo.shape)
        return SparseArray(bcoo, reg_shape=self._reg_shape)

    def scale_cols(self, v) -> "SparseArray":
        """Column-wise scaling x[:, j] * v[j] — sparsity-preserving (the
        scalers' sparse transform: no densification)."""
        v = jnp.asarray(v).reshape(-1)
        if v.shape[0] != self._shape[1]:
            raise ValueError(f"scale vector length {v.shape[0]} != "
                             f"{self._shape[1]} columns")
        bcoo = jsparse.BCOO((self._bcoo.data * v[self._bcoo.indices[:, 1]],
                             self._bcoo.indices), shape=self._bcoo.shape)
        return SparseArray(bcoo, reg_shape=self._reg_shape)

    def _scaled(self, factor):
        bcoo = jsparse.BCOO((self._bcoo.data * jnp.float32(factor),
                             self._bcoo.indices), shape=self._bcoo.shape)
        return SparseArray(bcoo, reg_shape=self._reg_shape)

    def __mul__(self, other):
        if np.isscalar(other):
            return self._scaled(other)
        return NotImplemented

    __rmul__ = __mul__

    def __truediv__(self, other):
        if np.isscalar(other):
            return self._scaled(1.0 / other)
        return NotImplemented

    def __neg__(self):
        return self._scaled(-1.0)

    def __add__(self, other):
        """sparse + sparse stays sparse (concatenated-duplicate BCOO);
        sparse + dense densifies (a dense result anyway)."""
        if isinstance(other, SparseArray):
            if other.shape != self.shape:
                raise ValueError(f"shape mismatch {self.shape} + {other.shape}")
            data = jnp.concatenate([self._bcoo.data, other._bcoo.data])
            idx = jnp.concatenate([self._bcoo.indices, other._bcoo.indices])
            bcoo = jsparse.BCOO((data, idx),
                                shape=self._bcoo.shape).sum_duplicates()
            return SparseArray(bcoo, reg_shape=self._reg_shape)
        if isinstance(other, Array):
            return self.to_dense() + other
        return NotImplemented

    def __sub__(self, other):
        if isinstance(other, SparseArray):
            return self + other._scaled(-1.0)
        if isinstance(other, Array):
            return self.to_dense() - other
        return NotImplemented

    # -- row-sharded representation ------------------------------------------

    def sharded_rows(self, mesh=None):
        """(data, local_rows, cols, rowsq) rectangular per-shard buffers,
        leading axis = shard over the mesh 'rows' axis; padding entries
        are (v=0, row=0, col=0) so they contribute nothing.  A view over
        :meth:`sharded` (the :class:`ShardedSparse` backing), kept for
        the kernels that predate it (sharded KMeans, the kNN ring
        tier)."""
        rep = self.sharded(mesh)
        return (rep.data, rep.lrows, rep.cols, rep.rowsq())


    def ell(self, budget=None):
        """Padded ELL buffers ``(vals (m, r), cols (m, r))`` with r = max
        row nnz — the device-resident row-GATHER layout: ``vals[i]`` /
        ``cols[i]`` densify row i by one scatter, so an estimator that
        needs arbitrary row subsets (CascadeSVM node staging) gathers them
        entirely on device instead of slicing a host CSR per node.
        Padding entries are (v=0, col=0) and scatter-add to nothing.

        Skew guard: one dense row inflates r to n, making the buffers
        O(m·n) — when the padded bytes exceed ``budget`` (default
        ``DSLIB_SPARSE_ELL_BUDGET``, 2 GiB) this returns None and callers
        fall back to host-CSR staging.  Cached.

        A sharded-backed array builds the buffers ON DEVICE from the
        :class:`ShardedSparse` buffers (`ell_buffers` — r and the budget
        check come from the host ``row_nnz`` metadata, so the whole
        staging is transfer-free); the host ``argsort`` path below is the
        BCOO-only ingest fallback."""
        import os
        if budget is None:
            budget = int(os.environ.get("DSLIB_SPARSE_ELL_BUDGET", 2 << 30))
        rep = self._sharded_rep
        if rep is not None:
            r = max(1, int(rep.row_nnz.max(initial=1)))
            # budget on the real (padded-rows) allocation; re-checked on
            # every call so lowering the budget between fits gets the
            # fallback, not the over-budget cache
            if rep.p * rep.m_local * r * 8 > budget:
                return None
            return rep.ell_buffers()
        # budget is re-checked against the CACHED buffers too: a caller
        # lowering the budget between fits must get the fallback, not the
        # over-budget cache
        cached = getattr(self, "_ell_cache", None)
        if cached is not None:
            m_c, r_c = cached[0].shape
            return cached if m_c * r_c * 8 <= budget else None
        m = self._shape[0]
        idx = np.asarray(jax.device_get(self._bcoo.indices))
        val = np.asarray(jax.device_get(self._bcoo.data))
        row_nnz = np.bincount(idx[:, 0], minlength=m)
        r = max(1, int(row_nnz.max(initial=1)))
        if m * r * 8 > budget:      # f32 vals + i32 cols
            return None
        vals = np.zeros((m, r), np.float32)
        cols = np.zeros((m, r), np.int32)
        order = np.argsort(idx[:, 0], kind="stable")
        slot = np.arange(len(val)) - np.concatenate(
            [[0], np.cumsum(row_nnz)])[idx[order, 0]]
        vals[idx[order, 0], slot] = val[order]
        cols[idx[order, 0], slot] = idx[order, 1]
        self._ell_cache = (jnp.asarray(vals), jnp.asarray(cols))
        return self._ell_cache

    def row_steps(self, chunk):
        """Equal-shape per-step triplet buffers for streaming a bounded
        dense window of the matrix (the kNN sparse path): rows are packed
        greedily into steps bounded BOTH by ``chunk`` rows and by an nnz
        budget (4× the average chunk's nonzeros, and never below the
        densest single row), so skewed sparsity cannot inflate the
        rectangles to O(n_steps · max_chunk_nnz) — total padding is at most
        ~one budget per step.  Returns (data (s, budget), local_rows,
        cols, row_off (s,), rows_in (s,)); padding entries are (v=0,
        row=0, col=0) and scatter-add to nothing.  Cached per chunk.

        A sharded-backed array plans the steps from host ``row_nnz``
        metadata and gathers the buffers ON DEVICE (`row_step_buffers` —
        bit-identical plan and entry order to the host staging, zero
        transfers); the host path below is the BCOO-only fallback."""
        if self._sharded_rep is not None:
            # sharded() (not the raw rep): a backing laid out for another
            # mesh re-lands on the library mesh first, on device
            return self.sharded().row_step_buffers(chunk)
        cached = getattr(self, "_row_steps_cache", None)
        if cached is not None and cached[0] == chunk:
            return cached[1]
        m = self._shape[0]
        idx = np.asarray(jax.device_get(self._bcoo.indices))
        val = np.asarray(jax.device_get(self._bcoo.data))
        order = np.argsort(idx[:, 0], kind="stable")
        rows_sorted = idx[order, 0]
        row_nnz = np.bincount(rows_sorted, minlength=m)
        row_start = np.concatenate([[0], np.cumsum(row_nnz)])
        avg_chunk_nnz = max(1, int(np.ceil(len(val) * chunk / max(m, 1))))
        budget = max(64, 4 * avg_chunk_nnz, int(row_nnz.max(initial=1)))
        steps = []                       # (row_off, rows_in, nnz_lo, nnz_hi)
        r = 0
        while r < m:
            r_end = r
            while (r_end < m and r_end - r < chunk
                   and (r_end == r
                        or row_start[r_end + 1] - row_start[r] <= budget)):
                r_end += 1
            steps.append((r, r_end - r, int(row_start[r]),
                          int(row_start[r_end])))
            r = r_end
        if not steps:
            steps = [(0, 0, 0, 0)]
        s = len(steps)
        data = np.zeros((s, budget), np.float32)
        lrows = np.zeros((s, budget), np.int32)
        cols = np.zeros((s, budget), np.int32)
        row_off = np.zeros(s, np.int32)
        rows_in = np.zeros(s, np.int32)
        for i, (ro, rc, nlo, nhi) in enumerate(steps):
            c = nhi - nlo
            sel = order[nlo:nhi]
            data[i, :c] = val[sel]
            lrows[i, :c] = idx[sel, 0] - ro
            cols[i, :c] = idx[sel, 1]
            row_off[i] = ro
            rows_in[i] = rc
        out = tuple(jnp.asarray(a)
                    for a in (data, lrows, cols, row_off, rows_in))
        self._row_steps_cache = (chunk, out)
        return out


@jax.jit
@precise
def _spmm(bcoo, rhs):
    return jsparse.bcoo_dot_general(
        bcoo, rhs, dimension_numbers=(([1], [0]), ([], [])))


@partial(_pjit, static_argnames=("pshape", "m_local", "mesh"),
         name="sparse_densify")
@precise
def _densify_kernel(data, lrows, cols, counts, pshape, m_local, mesh):
    """Sharded buffers → canonical dense padded canvas, ON DEVICE: one
    masked scatter-add onto zeros (the ``algorithm="densify"`` route and
    ``to_dense`` for sharded-backed arrays).  The slot mask keeps
    poisoned pads out; the canvas starts zero, so the pad-and-mask
    invariant holds by construction."""
    p, nse = data.shape
    slot_ok = jax.lax.broadcasted_iota(jnp.int32, (p, nse), 1) \
        < counts[:, None]
    v = jnp.where(slot_ok, data, jnp.zeros((), data.dtype))
    grow = lrows + (jax.lax.broadcasted_iota(jnp.int32, (p, nse), 0)
                    * m_local)
    out = jnp.zeros(pshape, data.dtype)
    out = out.at[grow.ravel(), cols.ravel()].add(v.ravel())
    return jax.lax.with_sharding_constraint(out, _mesh.data_sharding(mesh))
