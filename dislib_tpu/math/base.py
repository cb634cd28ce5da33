"""Blocked math: matmul, kron, svd.

Reference capabilities (SURVEY.md §3.2):
- `dislib.math.matmul` — blocked GEMM, one `_multiply` task per (i,j,k) block
  triple with INOUT accumulation (SURVEY §4.3).
- `dislib.math.kron` — Kronecker product, one scaled-copy task per block pair.
- `dislib.math.svd`  — one-sided block-Jacobi SVD: round-robin pairing of
  column blocks, rotations until convergence.

TPU-native redesign: the O(p^3) task loop IS a distributed GEMM schedule —
on TPU that schedule belongs to the XLA SPMD partitioner.  `matmul` is a
single `jnp.dot` over 2-D-sharded global arrays with a sharding constraint on
the result; XLA emits the SUMMA-style collective_permute/all_gather pattern
over ICI (the survey's §4.3 TPU mapping).  Zero padding makes the contraction
exact with no masking.  `svd` keeps the reference's one-sided Jacobi
*algorithm* (it is communication-friendly and converges quadratically) but
runs the rotation sweeps as jitted device loops — scalar column pairs at
small n, the reference's column-BLOCK pairs (batched QR + small SVD per
pair, MXU-shaped) at n ≥ 128.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

import os

from dislib_tpu.data.array import (
    Array, _LazyExpr, _eager_mode, _lazy_array, _matmul_body,
    ensure_canonical as _ensure_canonical,
)
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.ops import overlap as _ov
from dislib_tpu.ops import precision as px
from dislib_tpu.ops.base import precise
from dislib_tpu.ops.summa import summa_matmul, summa_supported
from dislib_tpu.utils import profiling as _prof
from dislib_tpu.utils.profiling import profiled_jit as _pjit


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

@partial(_pjit, static_argnames=("ta", "tb", "a_shape", "b_shape", "policy"),
         name="matmul")
@precise
def _matmul_kernel(a, b, ta, tb, a_shape, b_shape, policy):
    del a_shape, b_shape
    # zero-padding invariant ⇒ padded contraction == logical contraction
    return _matmul_body(a, b, ta, tb, policy)


# auto-SUMMA size gate: below this min logical dimension an explicit
# panel schedule buys nothing over the partitioner's fused dot, and a
# small product is usually mid-chain where leaving the fusion graph would
# cost a whole extra dispatch (module-level so tests can shrink it;
# ``DSLIB_SUMMA_MIN_DIM`` overrides at runtime, so small dims can be
# swept on host rigs without editing source)
_SUMMA_MIN_DIM = 256


def _summa_min_dim() -> int:
    """The auto-SUMMA size gate the router actually enforces: the
    ``DSLIB_SUMMA_MIN_DIM`` env knob when set, else the module default
    (read per call so an env flip re-routes immediately — routing is a
    host decision, no retrace subtlety)."""
    env = os.environ.get("DSLIB_SUMMA_MIN_DIM")
    return int(env) if env else _SUMMA_MIN_DIM


def _pick_algorithm(algorithm, a, b, a_shape, b_shape, dense,
                    transpose_a, transpose_b):
    """The matmul routing rule: which schedule owns this product.

    - explicit ``algorithm=`` wins; ``"auto"`` consults ``DSLIB_MATMUL_ALGO``
      and then the mesh shape AND operand layout;
    - ``"summa"`` = the explicit panel-broadcast schedule (``ops/summa``),
      picked automatically on a genuinely 2-D mesh (both axes > 1) for
      dense, untransposed, CONCRETE operands at paper-scale sizes (every
      logical dim ≥ ``_SUMMA_MIN_DIM``) — a standalone big product.
      Lazy (fusion-graph) operands stay on the XLA path under auto: the
      PR-2/PR-4 one-dispatch-per-chain contracts hold on every mesh, and
      routing a mid-chain GEMM to an eager kernel would force the chain
      (review-found: estimator predict pipelines must not silently gain
      dispatches when the mesh goes 2-D);
    - ``"xla"`` = one sharded dot, schedule owned by the SPMD partitioner
      (optimal on 1-D meshes, and a fusion-graph node).
    """
    if algorithm not in ("auto", "summa", "xla"):
        raise ValueError(f"unknown matmul algorithm {algorithm!r}: "
                         "expected 'auto', 'summa' or 'xla'")
    if algorithm == "auto":
        env = os.environ.get("DSLIB_MATMUL_ALGO", "auto")
        if env not in ("auto", "summa", "xla"):
            raise ValueError(f"bad DSLIB_MATMUL_ALGO={env!r}")
        algorithm = env
    if algorithm == "auto":
        big = min(a_shape[0], a_shape[1], b_shape[1]) >= _summa_min_dim()
        standalone = dense and not (a.is_lazy or b.is_lazy)
        return "summa" if (standalone and big and summa_supported()
                           and not (transpose_a or transpose_b)) else "xla"
    return algorithm


def matmul(a: Array, b: Array, transpose_a: bool = False,
           transpose_b: bool = False, *, algorithm: str = "auto",
           precision=None) -> Array:
    """Distributed GEMM (reference: dislib.math.matmul, `_multiply` task).

    One entry, two schedules, picked from the mesh shape (override with
    ``algorithm=`` or ``DSLIB_MATMUL_ALGO``):

    - 2-D mesh (both axes > 1): an explicit SUMMA panel-broadcast schedule
      (``ops/summa``) — the arXiv:2112.09017 regime, one dispatch;
    - 1-D mesh / single device: one XLA dot over the 2-D-sharded operands;
      the partitioner owns the communication schedule the reference
      expressed as O(p^3) COMPSs tasks.  On dense ds-array operands this
      is a fusion-graph node: the dot joins the operands' deferred chains
      and dispatches with the first force.

    ``precision``: the mixed-precision policy (None → the
    ``DSLIB_MATMUL_PRECISION`` default) — ``"bfloat16"`` contracts
    bf16-compute / f32-accumulate with the documented error bounds
    (``ops/precision.ERROR_BOUNDS``); the default is float32-faithful.

    SPARSE lhs (:class:`~dislib_tpu.data.sparse.SparseArray`): a second
    router — ``algorithm="auto"|"spmm"|"densify"`` — keyed on density ×
    the densify budget.  ``"spmm"`` runs the sharded masked-psum SpMM
    (``ops/spmm``, O(nnz) memory, one dispatch, overlap-scheduled);
    ``"densify"`` materialises the dense operand on device (budget-
    guarded) and takes the dense path; ``"auto"`` picks spmm at or below
    ``DSLIB_SPMM_MAX_DENSITY`` (default 0.1) or whenever densifying
    would blow ``DSLIB_SPARSE_DENSIFY_BUDGET``, densify otherwise."""
    with _prof.span("dslib.matmul", call=_prof.new_call()) as sp:
        return _route_matmul(sp, a, b, transpose_a, transpose_b, algorithm,
                             precision)


def _route_matmul(sp, a, b, transpose_a, transpose_b, algorithm, precision):
    """:func:`matmul` inside its span ``sp``, which learns the route
    taken (``route=xla|summa|spmm``)."""
    from dislib_tpu.data.sparse import SparseArray
    if isinstance(a, SparseArray) or isinstance(b, SparseArray):
        return _matmul_sparse(sp, a, b, transpose_a, transpose_b, algorithm,
                              precision)
    policy = px.resolve(precision)
    a_shape = (a.shape[1], a.shape[0]) if transpose_a else a.shape
    b_shape = (b.shape[1], b.shape[0]) if transpose_b else b.shape
    if a_shape[1] != b_shape[0]:
        raise ValueError(f"matmul shape mismatch: {a_shape} @ {b_shape}")
    out_shape = (a_shape[0], b_shape[1])
    reg = (a._reg_shape[1] if transpose_a else a._reg_shape[0],
           b._reg_shape[0] if transpose_b else b._reg_shape[1])
    dense = type(a) is Array and type(b) is Array
    algo = _pick_algorithm(algorithm, a, b, a_shape, b_shape, dense,
                           transpose_a, transpose_b)
    sp.set(route=algo)
    if algo == "summa":
        if not dense:
            raise ValueError("algorithm='summa' needs dense ds-array "
                             "operands")
        return _matmul_summa(a, b, transpose_a, transpose_b, policy,
                             out_shape, reg)
    if dense and not _eager_mode():
        pa, pb = a._pshape, b._pshape
        out_pshape = (pa[1] if transpose_a else pa[0],
                      pb[0] if transpose_b else pb[1])
        dtype = jnp.promote_types(jnp.promote_types(a.dtype, b.dtype),
                                  jnp.float32)
        expr = _LazyExpr("matmul", (transpose_a, transpose_b, policy.name),
                         (a._node(), b._node()), out_pshape, dtype)
        return _lazy_array(expr, out_shape, reg, False)
    # padded inner dims must agree for the padded dot; repad if quantum differs
    ad, bd = a._data, b._data
    ad, bd = _match_inner(ad, bd, transpose_a, transpose_b)
    out = _matmul_kernel(ad, bd, transpose_a, transpose_b, a_shape, b_shape,
                         policy)
    return Array(_crop_or_keep(out, out_shape), out_shape, reg, False)


def _spmm_max_density() -> float:
    """The density at which auto stops preferring SpMM over one dense
    GEMM: SpMM's arithmetic is ~nnz · panel-count scatter work vs the
    MXU-shaped m·n dense contraction, so the crossover sits around
    1/steps — 0.1 covers the common mesh row counts.
    ``DSLIB_SPMM_MAX_DENSITY`` overrides at runtime."""
    return float(os.environ.get("DSLIB_SPMM_MAX_DENSITY", "0.1"))


def _pick_sparse_algorithm(a, algorithm):
    """The sparse matmul routing rule: explicit ``algorithm=`` wins;
    auto keys on density × the densify budget — spmm at/below the
    density threshold, densify above it UNLESS the dense materialisation
    would blow the byte budget (then spmm regardless: O(nnz) always
    fits where the data itself fits)."""
    from dislib_tpu.data.array import _padded_shape
    from dislib_tpu.data.sparse import densify_budget_bytes
    if algorithm not in ("auto", "spmm", "densify"):
        raise ValueError(
            f"unknown sparse matmul algorithm {algorithm!r}: expected "
            "'auto', 'spmm' or 'densify'")
    if algorithm != "auto":
        return algorithm
    m, n = a.shape
    density = a.nnz / max(m * n, 1)
    if density <= _spmm_max_density():
        return "spmm"
    pm, pn = _padded_shape(a.shape, _mesh.pad_quantum())
    return "spmm" if 4 * pm * pn > densify_budget_bytes() else "densify"


def _matmul_sparse(sp, a, b, transpose_a, transpose_b, algorithm,
                   precision):
    """The sparse fast-path entry: SparseArray @ dense ds-array via the
    spmm/densify router.  Transposed and sparse-rhs/sparse-sparse forms
    have no sharded schedule — they densify EXPLICITLY (never silently:
    a typed error names the escape hatch)."""
    from dislib_tpu.data.array import Array
    from dislib_tpu.data.sparse import SparseArray
    from dislib_tpu.ops.spmm import spmm as _spmm_entry
    if isinstance(b, SparseArray) or not isinstance(a, SparseArray) \
            or transpose_a or transpose_b:
        raise TypeError(
            "the sparse matmul fast path covers sparse @ dense with no "
            "transposes — transpose via SparseArray.T (sparse, O(nnz)) "
            "or densify explicitly with .to_dense() for other forms")
    if not isinstance(b, Array):
        raise TypeError(f"matmul rhs must be a dense ds-array, "
                        f"got {type(b).__name__}")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"matmul shape mismatch: {a.shape} @ {b.shape}")
    algo = _pick_sparse_algorithm(a, algorithm)
    if algo == "spmm":
        sp.set(route="spmm")
        return _spmm_entry(a, b, precision=precision)
    return _route_matmul(sp, a.to_dense(), b, False, False, "auto",
                         precision)


def _matmul_summa(a, b, transpose_a, transpose_b, policy, out_shape, reg):
    """The SUMMA route: canonical (rows, cols)-sharded operands through the
    explicit panel schedule.  Requested transposes materialise first (one
    extra dispatch each — the auto policy never picks SUMMA for transposed
    operands; an explicit ``algorithm='summa'`` accepts the cost)."""
    if transpose_a:
        a = a.transpose()
    if transpose_b:
        b = b.transpose()
    # operands built under an OLDER mesh can carry a pad quantum (or
    # layout) the current grid doesn't divide — the panel loop would
    # silently drop the K tail (and shard_map reject the row/col split);
    # the on-device rechunk ingest guard re-lays them out first
    a = _ensure_canonical(a)
    b = _ensure_canonical(b)
    ad, bd = a._data, b._data
    ad, bd = _match_inner(ad, bd, False, False)
    # panel schedule: resolved HERE (the host routing boundary) so a
    # DSLIB_OVERLAP flip retraces via the kernel's static, and the run
    # is observable through the schedule counters
    sched = _ov.resolve()
    _prof.count_schedule("summa_matmul", sched)
    out = summa_matmul(ad, bd, _mesh.get_mesh(), policy, overlap=sched)
    return Array(_crop_or_keep(out, out_shape), out_shape, reg, False)


def _match_inner(ad, bd, transpose_a, transpose_b):
    """Equalize the padded contraction dims of the two backings (quantum
    mismatch between operands built under different meshes/paddings)."""
    inner_a = ad.shape[0] if transpose_a else ad.shape[1]
    inner_b = bd.shape[1] if transpose_b else bd.shape[0]
    if inner_a != inner_b:
        pad_to = max(inner_a, inner_b)
        if transpose_a:
            ad = _grow(ad, (pad_to, ad.shape[1]))
        else:
            ad = _grow(ad, (ad.shape[0], pad_to))
        if transpose_b:
            bd = _grow(bd, (bd.shape[0], pad_to))
        else:
            bd = _grow(bd, (pad_to, bd.shape[1]))
    return ad, bd


def _grow(data, shape):
    """Host-level grow to a larger padded canvas: the traced zero-fill
    core (:func:`grow_canvas`) + the canonical resharding device_put."""
    return jax.device_put(grow_canvas(data, shape), _mesh.data_sharding())


def grow_canvas(data, shape, valid=None):
    """THE shared pad/crop-helper core (traced): place ``data`` on a zero
    canvas of ``shape`` and — when ``valid`` = (rows, cols) is given —
    re-zero everything outside the valid region.  Every blocked-linalg
    kernel that grows an operand (blocked QR panels, block-Jacobi column
    blocks, matmul quantum repads) routes through here so a padded tail
    can never enter a reduced-precision accumulation as garbage: the
    canvas is zero by construction and zero is exact in every policy
    dtype (pinned by tests/test_precision.py)."""
    grown = data
    if tuple(data.shape) != tuple(shape):
        canvas = jnp.zeros(shape, data.dtype)
        grown = lax.dynamic_update_slice(
            canvas, data[: shape[0], : shape[1]], (0, 0))
    if valid is not None:
        r = lax.broadcasted_iota(jnp.int32, grown.shape, 0) < valid[0]
        c = lax.broadcasted_iota(jnp.int32, grown.shape, 1) < valid[1]
        grown = jnp.where(r & c, grown, jnp.zeros((), grown.dtype))
    return grown


def _crop_or_keep(padded, logical_shape):
    """The dot of two quantum-padded operands is already quantum-padded for
    the output logical shape (padded dims are quantum multiples ≥ logical)."""
    return padded


# ---------------------------------------------------------------------------
# kron
# ---------------------------------------------------------------------------

def kron(a: Array, b: Array, block_size=None) -> Array:
    """Kronecker product (reference: dislib.math.kron — one scaled-copy task
    per (block of a) × (block of b)).

    Computed directly into the sharded output via the index lattice
    ``out[r, c] = a[r//mb, c//nb] · b[r%mb, c%nb]`` — row/column gathers of
    the (small) operands, never the 4-D broadcast intermediate ``jnp.kron``
    builds, so per-device peak memory is O(output shard + operands)."""
    from dislib_tpu.data.array import _padded_shape
    (ma, na), (mb, nb) = a.shape, b.shape
    shape = (ma * mb, na * nb)
    pshape = _padded_shape(shape, _mesh.pad_quantum())
    out = _kron_kernel(a._data, b._data, (a.shape, b.shape), pshape)
    return Array(out, shape, reg_shape=block_size)


@partial(_pjit, static_argnames=("shapes", "pshape"), name="kron")
def _kron_kernel(ap, bp, shapes, pshape):
    (ma, na), (mb, nb) = shapes
    av, bv = ap[:ma, :na], bp[:mb, :nb]
    ri = lax.iota(jnp.int32, pshape[0])
    ci = lax.iota(jnp.int32, pshape[1])
    # clip keeps the pad-region gathers in bounds; the mask re-zeroes them
    a_exp = av[jnp.clip(ri // mb, 0, ma - 1)][:, jnp.clip(ci // nb, 0, na - 1)]
    b_til = bv[ri % mb][:, ci % nb]
    valid = (ri < ma * mb)[:, None] & (ci < na * nb)[None, :]
    out = jnp.where(valid, a_exp * b_til, 0.0)
    return lax.with_sharding_constraint(out, _mesh.data_sharding())


# ---------------------------------------------------------------------------
# svd — one-sided block-Jacobi, the reference's algorithm, device-resident
# ---------------------------------------------------------------------------

# per-policy convergence floors (the polar tol-floor precedent): the
# off-diagonal measure can't fall below the pair-update GEMMs' own
# rounding — under the bfloat16 policy that is ~2^-9 per operand, so
# demanding 1e-6 would burn max_sweeps in full every call
_SVD_EPS_FLOOR = {"float32": 1e-6, "bfloat16": 5e-3}


def svd(a: Array, compute_uv: bool = True, sort: bool = True,
        copy: bool = True, eps: float = 1e-6, max_sweeps: int = 30,
        precision=None):
    """One-sided Jacobi SVD (reference: dislib.math.svd — round-robin
    rotations of column pairs until all pairs are ε-orthogonal; the
    reference pairs column BLOCKS, SURVEY §3.2 svd row).

    Returns (U, S, V) ds-arrays with S of shape (1, n) — or S alone when
    ``compute_uv=False``.  The sweep loop runs on device in a while_loop.
    Two tiers, both batching every disjoint pair of a round-robin round:

    - n < 2·64: scalar column pairs, one Givens rotation per pair.
    - n ≥ 2·64: the reference's COLUMN-BLOCK pairing — per pair, one
      batched tall QR, a small SVD of R, and a tall
      (m, 2b) GEMM apply.  A sweep is n/b−1 rounds instead of n−1, and
      every round is MXU-shaped GEMM work instead of skinny
      gather/scatter — the block structure is exactly why the reference
      chose block pairs too.  For rank-deficient input the null-space
      columns of V (σ = 0) are implementation-defined on this tier;
      singular vectors for σ > 0 are exact.

    ``eps`` defaults to 1e-6 (not the reference's 1e-9, which presumes
    float64 blocks): the kernels run float32, whose pairwise-orthogonality
    floor is ~5e-8, so tighter requests are unreachable and are clamped to
    1e-6 with a warning.

    ``precision`` — the mixed-precision policy (None → the
    ``DSLIB_MATMUL_PRECISION`` default).  Scope follows the round-10
    policy contract: the FLOP-dominant block-tier PAIR-UPDATE GEMMs (the
    tall ``Q_w·U_rΣ`` apply and the ``V·V_r`` rotation apply) contract at
    the policy's compute dtype with f32 accumulation; the pair QR, the
    small (2b, 2b) SVD and the convergence Gram stay pinned float32
    (factorisation interiors).  The scalar tier (n < 128) is always
    float32 — below the block threshold there is no FLOP-dominant GEMM to
    round.  Under ``bfloat16`` the convergence tolerance has a per-policy
    floor (``5e-3``, the ``polar`` precedent) and the documented error
    bounds are ``precision.ERROR_BOUNDS[("svd_values"|"svd_resid",
    policy)]``.
    """
    policy = px.resolve(precision)
    m, n = a.shape
    # Operate on the full padded backing: pad rows/cols are zero under the
    # pad-and-mask invariant, so they contribute nothing to column dot
    # products and their rotations are exact no-ops (off-diagonal = 0) —
    # the input stays row-sharded on the mesh instead of being gathered by
    # an eager logical slice (round-2 fix for the replicated-SVD ceiling).
    # the kernels run float32: an eps below f32's pairwise-orthogonality
    # floor (~5e-8 observed) is unreachable and would burn max_sweeps in
    # full every call — clamp to a floor a converged f32 sweep does reach
    if float(eps) < 1e-6:
        import warnings
        warnings.warn(
            f"svd: eps={eps:g} is below the float32 convergence floor; "
            "clamping to 1e-6 (the 1e-9-style defaults presume float64 "
            "blocks)", RuntimeWarning, stacklevel=2)
    eps = max(float(eps), 1e-6)
    # shared pad/crop helper at ingest: re-assert the zero-pad invariant
    # before ANY rotation math — a garbage padded tail would otherwise mix
    # into valid columns through the pair rotations (and at reduced
    # precision a large tail swamps small singular values outright);
    # pinned by tests/test_precision.py::test_poisoned_pad_tail_cannot_leak
    av = grow_canvas(px.f32(a._data), a._data.shape, valid=(m, n))
    # the block tier factors (m, 2b) pair panels with a reduced QR — for
    # m < 2b that QR is rank-limited and the pair update shapes collapse
    # (found by the round-10 precision suite at (80, 130)); short-wide
    # inputs take the scalar tier, which has no such constraint
    if av.shape[1] >= 2 * _JACOBI_BLOCK and av.shape[0] >= 2 * _JACOBI_BLOCK:
        # per-policy convergence floor applies HERE, where the policy
        # rounds the pair updates (silently: the default eps=1e-6 under
        # bfloat16 means "as converged as bf16 pair updates get"); the
        # scalar tier below ignores the policy, so it keeps the f32 floor
        eps = max(eps, _SVD_EPS_FLOOR.get(policy.name, 1e-6))
        u, s, v = _jacobi_svd_block(av, n, sort,
                                    eps, max_sweeps, policy)
    else:
        u, s, v = _jacobi_svd(av, n, sort, eps,
                              max_sweeps)
    s_arr = Array._from_logical(s[:n].reshape(1, -1))
    if not compute_uv:
        return s_arr
    u_arr = Array._from_logical_padded(u, (m, n), None, False)
    # v already satisfies the (n, n) pad-and-mask invariant: pad rows/cols
    # zeroed in-kernel and the stable sort keeps valid columns first
    v_arr = Array._from_logical_padded(v, (n, n), None, False)
    return (u_arr, s_arr, v_arr)


@partial(_pjit, static_argnames=("n_valid", "sort", "max_sweeps"),
         name="jacobi_svd")
@precise
def _jacobi_svd(a, n_valid, sort, eps, max_sweeps):
    m, n = a.shape
    # round-robin pairings: n-1 rounds, each pairing all columns once
    pairs = _round_robin_pairs(n)
    shard = _mesh.data_sharding()

    def rotate_round(carry, pr):
        u, v = carry
        i, j = pr[:, 0], pr[:, 1]
        ui, uj = u[:, i], u[:, j]
        aii = jnp.sum(ui * ui, axis=0)
        ajj = jnp.sum(uj * uj, axis=0)
        aij = jnp.sum(ui * uj, axis=0)
        # Jacobi rotation angle per pair
        tau = (ajj - aii) / (2.0 * jnp.where(jnp.abs(aij) < 1e-30, 1e-30, aij))
        t = jnp.sign(tau) / (jnp.abs(tau) + jnp.sqrt(1.0 + tau * tau))
        c = 1.0 / jnp.sqrt(1.0 + t * t)
        s_ = c * t
        # skip near-orthogonal pairs
        off = jnp.abs(aij) / jnp.sqrt(jnp.maximum(aii * ajj, 1e-30))
        c = jnp.where(off < eps, 1.0, c)
        s_ = jnp.where(off < eps, 0.0, s_)
        new_ui = c * ui - s_ * uj
        new_uj = s_ * ui + c * uj
        u = u.at[:, i].set(new_ui).at[:, j].set(new_uj)
        vi, vj = v[:, i], v[:, j]
        v = v.at[:, i].set(c * vi - s_ * vj).at[:, j].set(s_ * vi + c * vj)
        return (u, v), jnp.max(off)

    def sweep(carry):
        u, v, _, it = carry
        (u, v), offs = lax.scan(rotate_round, (u, v), pairs)
        # keep U row-sharded across sweeps (rotations are column-local, so
        # the mesh's row axis carries through each round; without the
        # constraint SPMD may gather the carry after the column scatters)
        u = lax.with_sharding_constraint(u, shard)
        return u, v, jnp.max(offs), it + 1

    def cond(carry):
        _, _, off, it = carry
        return (off > eps) & (it < max_sweeps)

    u0 = a
    v0 = jnp.eye(n, dtype=a.dtype)
    u, v, _, _ = lax.while_loop(cond, sweep, (u0, v0, jnp.asarray(jnp.inf), 0))
    s = jnp.linalg.norm(u, axis=0)
    u = u / jnp.where(s < 1e-30, 1.0, s)[None, :]
    # re-zero the pad block: rotations keep pad columns exactly zero in U,
    # but V's pad diagonal starts at 1 (eye) and must not leak into the
    # pad-and-mask invariant of the returned arrays
    col_ok = lax.broadcasted_iota(jnp.int32, (n,), 0) < n_valid
    s = jnp.where(col_ok, s, 0.0)
    u = u * col_ok[None, :].astype(u.dtype)
    v = v * (col_ok[None, :] & col_ok[:, None]).astype(v.dtype)
    if sort:
        order = jnp.argsort(-s, stable=True)   # pad zeros stay behind valid
        s = s[order]
        u = u[:, order]
        v = v[:, order]
    return u, s, v


_JACOBI_BLOCK = 64


@partial(_pjit, static_argnames=("n_valid", "sort", "max_sweeps", "policy"),
         name="jacobi_svd_block")
@precise
def _jacobi_svd_block(a, n_valid, sort, eps, max_sweeps, policy=px.FLOAT32):
    """One-sided BLOCK Jacobi: round-robin over column blocks of width b.

    Per disjoint block pair (I, J), batched over the round's pairs:
    W = [U_I | U_J] is factored W = Q_w R (one batched tall QR), the
    small R gets a batched SVD R = U_r Σ V_rᵀ, and the pair updates are
    U_pair ← Q_w U_r Σ (tall GEMM) and V_pair ← V_pair V_r.  V_r is
    orthogonal, so this is a valid one-sided Jacobi step, and — unlike
    the Gram+eigh formulation — the new columns are orthogonal to
    machine precision INDEPENDENT of the pair's conditioning (a Gram
    eigh's residual scales with λmax, wrecking small-σ columns; R's SVD
    is σ-relative).  Convergence follows the same cyclic-Jacobi argument
    as the scalar tier, measured on G = RᵀR.  Zero (padding) columns
    stay exactly zero (σ = 0 scales them out); V starts with pad columns
    zeroed (not identity) so degenerate null-space shuffling moves only
    zeros.  Column order migrates across rounds (each pair sorts by σ);
    the final global sort restores it, and positions ≥ n_valid are
    re-masked after the sort.
    """
    m, n_in = a.shape
    b = _JACOBI_BLOCK
    nb = -(-n_in // b)
    n = nb * b
    # shared pad/crop helper: the grown column tail is zero BY CONSTRUCTION
    # and columns ≥ n_valid are re-zeroed — a padded tail can never enter
    # the rotation Grams as garbage (tests/test_precision.py pins this)
    u0 = grow_canvas(a, (m, n), valid=(m, n_valid))
    col_ok0 = lax.broadcasted_iota(jnp.int32, (n,), 0) < n_valid
    v0 = jnp.eye(n, dtype=a.dtype) * col_ok0[None, :].astype(a.dtype)
    pairs = _round_robin_pairs(nb)            # (rounds, width, 2) block ids
    shard = _mesh.data_sharding()

    def rotate_round(carry, pr):
        u, v = carry
        i, j = pr[:, 0], pr[:, 1]                                # (w,)
        ur = u.reshape(m, nb, b)
        vr = v.reshape(n, nb, b)
        w_u = jnp.concatenate([ur[:, i], ur[:, j]], axis=-1)     # (m, w, 2b)
        qw, r = jnp.linalg.qr(w_u.transpose(1, 0, 2),
                              mode="reduced")      # (w, m, 2b), (w, 2b, 2b)
        g = jnp.einsum("wki,wkj->wij", r, r)       # G = RᵀR, small
        d = jnp.diagonal(g, axis1=1, axis2=2)
        # clamp the PRODUCT, not the factors: clamped factors of 1e-30
        # multiply to exactly 0 in f32 (underflow) and 0/0 = NaN — a NaN
        # off makes `off > eps` false and silently ends the sweep loop
        # after one iteration (the scalar tier's formula, same reason)
        denom = jnp.sqrt(jnp.maximum(d[:, :, None] * d[:, None, :], 1e-30))
        off_d = jnp.where(jnp.eye(2 * b, dtype=bool)[None],
                          0.0, jnp.abs(g) / denom)
        u_r, s_r, vh = jnp.linalg.svd(r)           # batched (2b, 2b) SVD
        # the two FLOP-dominant pair-update GEMMs follow the precision
        # policy (bf16-compute / f32-accumulate when opted in); the QR,
        # Gram and small SVD above stay pinned f32 — rounding a
        # factorisation interior buys no FLOPs and costs stability
        u_new = px.peinsum("wmi,wij->mwj", qw, u_r * s_r[:, None, :],
                           policy)
        w_v = jnp.concatenate([vr[:, i], vr[:, j]], axis=-1)
        v_new = px.peinsum("nwi,wji->nwj", w_v, vh, policy)      # V · V_r
        # a duplicated (padding) pair in a round recomputes the identical
        # q from the identical pre-round blocks — the duplicate .set
        # writes identical values (idempotent), as in the scalar tier
        u = ur.at[:, i].set(u_new[..., :b]).at[:, j].set(u_new[..., b:]) \
            .reshape(m, n)
        v = vr.at[:, i].set(v_new[..., :b]).at[:, j].set(v_new[..., b:]) \
            .reshape(n, n)
        return (u, v), jnp.max(off_d)

    def sweep(carry):
        u, v, _, it = carry
        (u, v), offs = lax.scan(rotate_round, (u, v), pairs)
        u = lax.with_sharding_constraint(u, shard)
        return u, v, jnp.max(offs), it + 1

    def cond(carry):
        _, _, off, it = carry
        return (off > eps) & (it < max_sweeps)

    u, v, _, _ = lax.while_loop(cond, sweep,
                                (u0, v0, jnp.asarray(jnp.inf), 0))
    s = jnp.linalg.norm(u, axis=0)
    u = u / jnp.where(s < 1e-30, 1.0, s)[None, :]
    if sort:
        order = jnp.argsort(-s, stable=True)
        s = s[order]
        u = u[:, order]
        v = v[:, order]
    # post-sort positional mask: σ>0 columns sort into [0, rank); anything
    # at positions ≥ n_valid is padding or null space — zero it to restore
    # the pad-and-mask invariant of the returned canvases
    keep = lax.broadcasted_iota(jnp.int32, (n,), 0) < n_valid
    s = jnp.where(keep, s, 0.0)
    u = u * keep[None, :].astype(u.dtype)
    v = v * (keep[None, :] & keep[:, None]).astype(v.dtype)
    return u[:, :n_in], s[:n_in], v[:n_in, :n_in]


def _round_robin_pairs(n):
    """Static round-robin schedule: (n-1) rounds × (n//2) disjoint pairs."""
    import numpy as np
    m = n if n % 2 == 0 else n + 1
    idx = list(range(m))
    rounds = []
    for _ in range(m - 1):
        pr = [(idx[k], idx[m - 1 - k]) for k in range(m // 2)]
        pr = [(min(i, j), max(i, j)) for i, j in pr if i < n and j < n]
        rounds.append(pr)
        idx = [idx[0]] + [idx[-1]] + idx[1:-1]
    width = max(len(r) for r in rounds)
    # Pad short rounds by repeating their last pair.  Safe because
    # rotate_round gathers all pair columns from the PRE-round matrix and
    # scatters with .set semantics: both copies of a duplicated pair compute
    # the identical rotation from identical inputs and write identical
    # values, so the duplicate write is idempotent (it does NOT rotate
    # twice).
    padded = []
    for r in rounds:
        while len(r) < width:
            r = r + [r[-1]]
        padded.append(r)
    return jnp.asarray(np.array(padded, dtype=np.int32))
