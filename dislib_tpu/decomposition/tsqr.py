"""Tall-skinny QR (reference: `dislib/decomposition/tsqr` — per-block local QR
plus a pairwise tree reduction of R factors; SURVEY.md §3.2).

TPU-native design (BASELINE config 3: "tsQR on 65536x256 — _little_qr +
all_gather(R) over ICI"): one `shard_map` over the mesh 'rows' axis, in
which a shard passes over its tall (m/p, n) panel four times and no more.

    per shard:  G₁ = AᵢᵀAᵢ = R₁ᵀR₁ ;  Q₁ = Aᵢ R₁⁻¹       (passes 1 and 2)
                G₂ = Q₁ᵀQ₁ = R₂ᵀR₂ ;  Rᵢ = R₂R₁          (pass 3)
    collective: R_stack = all_gather(Rᵢ)  — ONE all_gather over ICI; with
                n cols small this is the whole communication volume
    per shard:  R_stack = Q₂ R  (the same factorisation, of p·n rows)
                Qᵢ = Q₁ (R₂⁻¹ Q₂ᵢ)                        (pass 4)

That is a blocked CholeskyQR2 (:func:`_cholqr2`): each Gram the compensated
sum of the Grams of blocks of at most 8 192 rows (a float32 product that
contracts more rows reads low on the chip), each R⁻¹ = L⁻ᵀ formed once,
(n, n), and applied as ONE product that reads a panel and writes the
other (:func:`_apply`), in float32 'highest' throughout.  The second
round's application is left until Q₂ is known, so that R₂⁻¹ and this
shard's (n, n) block of Q₂ are multiplied first and the panel is read
once for both: there is no separate Q₁·Q₂ product.  A call holds two
panels (its operand and one more); nothing panel-sized is copied or cut.

Round 1 is judged before round 2 is applied (``ok``: ‖R₂ᵀR₂ − I‖ < 0.1 and
everything finite).  Where it fails, the same program factors the
ORIGINAL panel by a Householder reduction tree instead
(:func:`_local_tsqr`: blocks' QRs in place, the stack of their R factors
factored by the same tree, the tree's Q applied as one batched product),
and the last pass multiplies that Q by Q₂ᵢ: ill-conditioned panels lose
speed, never accuracy.  ``DSLIB_TSQR_CHOLQR`` chooses the tree outright
(the default off a TPU); the assembly is the same one application.

The reference's arity-2 reduction tree is log2(p) rounds of pairwise R
merges shipped between workers; the all_gather collapses that tree into a
single ICI collective, after which every shard redundantly factors the tiny
(p·n, n) stack — redundant FLOPs are free next to saved latency hops.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from dislib_tpu.data.array import Array
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.ops import base as _ops
from dislib_tpu.ops import precision as px
from dislib_tpu.ops.base import precise
from dislib_tpu.utils.profiling import count_schedule as _count_schedule


def tsqr(a: Array, mode: str = "reduced", indexes=None, precision=None):
    """Tall-skinny QR.

    mode='reduced' → (Q (m,n), R (n,n));  mode='r' → R only.
    ``indexes`` (reference parity): restrict the returned Q to these column
    indices after factorisation.

    ``precision``: mixed-precision policy (None → the
    ``DSLIB_MATMUL_PRECISION`` default).  The policy governs the
    Householder tree's Q-application GEMMs, where that tree runs; the
    panel factorisations, the R-stack merge and the one product that
    assembles Q (CholeskyQR2's second application with Q₂ in it) stay
    float32 — bounds in ``ops/precision.ERROR_BOUNDS``.
    """
    if mode not in ("reduced", "r"):
        raise ValueError(f"unsupported mode {mode!r}")
    policy = px.resolve(precision)
    m, n = a.shape
    if m < n:
        raise ValueError("tsqr requires a tall-skinny array (m >= n)")
    mesh = _mesh.get_mesh()
    p = mesh.shape[_mesh.ROWS]
    av = px.f32(a._data[:, :n])  # keep padded rows (zeros), crop cols
    # each shard must be at least n tall for its local R to be (n, n);
    # grow with zero rows if not (zero rows leave Q's logical rows and R exact)
    if av.shape[0] // p < n:
        extra = p * n - av.shape[0]
        av = jnp.pad(av, ((0, extra), (0, 0)))
        av = jax.device_put(av, _mesh.row_sharding())
    q_pad, r = _tsqr_shardmap(av, mesh, p, cholqr=_use_cholqr(),
                              policy=policy)
    if mode == "r":
        return Array._from_logical(r)
    q = Array._from_logical_padded(_col_repad(q_pad), (m, n), a._reg_shape)
    if indexes is not None:
        q = q[:, list(indexes)]
    return q, Array._from_logical(r)


def _use_cholqr() -> bool:
    """Policy for the CholeskyQR2 local factorisation: DSLIB_TSQR_CHOLQR
    in {auto (default), 1, 0}.  'auto' enables it on TPU only — on the MXU
    the 2 GEMM rounds (~3× the Householder FLOPs, but all matmul) beat a
    column-sequential factorisation by an order of magnitude; on CPU
    LAPACK's blocked Householder wins, so the rig keeps the tree unless a
    test forces the path."""
    import os
    v = os.environ.get("DSLIB_TSQR_CHOLQR", "auto")
    if v == "auto":
        return jax.default_backend() == "tpu"
    return v == "1"


def _panel_block(rows: int, n: int) -> int:
    """Rows of one block of a shard's (rows, n) panel: what ``ops/base.py``
    derives for a block that holds a row of n float32 four times (in, out,
    and the parts a 'highest' product splits it in), and no more rows than
    one product may contract."""
    return _ops.row_block(rows, _ops.contract_row_bytes(16 * n))


def local_qr_route(rows: int, n: int, cholqr: bool) -> str:
    """Which shard-local factorisation a (rows, n) panel takes, by what a
    trace can observe: ``blocked`` (CholeskyQR2 over row blocks),
    ``one_product`` (CholeskyQR2 of a panel that is one block) or
    ``householder_tree``.  ``_tsqr_shardmap`` counts it once a trace under
    ``schedule_counters()["tsqr_local:<route>"]``."""
    if not cholqr:
        return "householder_tree"
    return "blocked" if rows > _panel_block(rows, n) else "one_product"


def _whole_blocks(rows, block):
    """How many blocks of a panel's rows are taken whole, one like the
    other; what is left after them, if anything, is ONE more step: a block
    and the ragged rest together."""
    return max(rows // block - bool(rows % block), 0)


def _over_blocks(rows, block, step, carry):
    """``carry`` after ``step(start, size, carry)`` for every block of a
    panel's rows in turn: whole blocks in a loop, and what is left (a block
    and the ragged rest, as one step of its own static size) after it."""
    full = _whole_blocks(rows, block)
    carry = lax.fori_loop(0, full, lambda i, c: step(i * block, block, c),
                          carry)
    done = full * block
    return step(done, rows - done, carry) if done < rows else carry


def _gram(q):
    """``q.T @ q`` of a shard's tall panel as the compensated sum of its
    blocks' Grams (``ops/base.py::local_row_sums``): no product contracts
    more than a block's rows.  A ragged last block starts early and the
    rows an earlier block has seen weigh nothing."""
    rows, n = q.shape
    block = _panel_block(rows, n)
    ragged = rows % block != 0

    def one(qb, w, _start):
        return px.pdot_tall(qb * w[:, None] if ragged else qb, qb)

    with jax.named_scope("dslib.tsqr.gram"):
        return _ops.local_row_sums(q, (), 0, rows, block, one,
                                   jnp.zeros((n, n), q.dtype))


def _apply(q, right):
    """``q @ right`` for a shard's tall ``q`` (rows, n) and a small
    ``right`` (n, n): ONE product that reads the panel once and writes
    the result once, into the other of the two panels a call holds.  It
    contracts n, not the rows, so it stays whole (the bias of a long
    float32 contraction, PERF.md section 7, is the Grams' matter); XLA
    splits ``q`` into its bfloat16 parts inside the GEMM's own fusion,
    and nothing is cut out or copied around it."""
    with jax.named_scope("dslib.tsqr.apply"):
        return px.pdot(q, right)


def _cholqr2(a):
    """CholeskyQR2: two rounds of Gram → Cholesky → R⁻¹ applied, the
    second round's application left to the caller: three of the four
    passes over the panel.

    (Lit.: 'Large Scale Distributed Linear Algebra With Tensor Processing
    Units', arXiv:2112.09017 — QR via Cholesky of AᵀA is the TPU-native
    tall-skinny factorisation; the second round restores orthogonality to
    O(u) whenever the first Cholesky succeeds, i.e. cond(A) ≲ u^(-1/2).)

    Returns (Q₁, R₂⁻¹, R, ok): Q = ``_apply(Q₁, R₂⁻¹)`` and R = R₂R₁.
    Each round's Gram is :func:`_gram`, over the rows in blocks (a float32
    product that contracts a million rows of squares reads 2e-5 low on
    the chip, which is 1e-5 of orthogonality; PERF.md), its R⁻¹ = L⁻ᵀ is
    formed once, (n, n), and round 1's is applied as one product
    (:func:`_apply`) that reads ``a`` and writes Q₁ beside it: ``a`` stays
    as it is, for the fall-back, and no transposed, solved or copied
    panel exists.

    ``ok`` is False when the result is unusable — the Gram Cholesky broke
    down (NaN/inf), OR round 1's orthogonality error was too large for
    round 2's O(u) restoration to apply.  The latter is measured from the
    second factor: by construction R₂ᵀR₂ = Q₁ᵀQ₁ (to Cholesky rounding),
    so ‖R₂ᵀR₂ − I‖_max IS round 1's orthogonality error at O(n³) cost — no
    m-sized Gram of Q₂ needed, and known BEFORE R₂⁻¹ is applied, so the
    caller decides between Q₁ and the fall-back without holding both,
    and applies R₂⁻¹ together with whatever else multiplies Q.  The
    CholeskyQR2 guarantee (final orthogonality O(u)) holds whenever that
    error is ≪ 1; the 0.1 threshold is conservative.
    The explicit check matters because in the cond(A) band around u^(-1/2)
    the Cholesky can stay finite while orthogonality quietly degrades —
    finiteness alone does not guarantee quality.  The caller falls back to
    the Householder tree on ok=False, so ill-conditioned inputs lose speed,
    never accuracy."""
    n = a.shape[1]
    eye = jnp.eye(n, dtype=a.dtype)

    def factor(q):
        g = _gram(q)
        with jax.named_scope("dslib.tsqr.chol"):
            ell = jnp.linalg.cholesky(g)             # G = L Lᵀ, R = Lᵀ
            return ell.T, jax.scipy.linalg.solve_triangular(
                ell, eye, lower=True).T              # R, R⁻¹ = L⁻ᵀ

    r1, r1_inv = factor(a)
    q1 = _apply(a, r1_inv)
    r2, r2_inv = factor(q1)
    r = r2 @ r1
    round1_err = jnp.max(jnp.abs(r2.T @ r2 - eye))
    ok = jnp.all(jnp.isfinite(r2_inv)) & jnp.all(jnp.isfinite(r)) \
        & (round1_err < 0.1)
    return q1, r2_inv, r, ok


def _local_qr(a, cholqr, policy=px.FLOAT32):
    """Shard-local tall-skinny QR, its last application left to the
    caller: ``(panel, factor, R)`` with Q = ``panel @ factor``, ``factor``
    (n, n).  CholeskyQR2 when ``cholqr``: (Q₁, R₂⁻¹, R₂R₁) where round 1
    was good enough (:func:`_cholqr2`'s ``ok``), and in the same program
    (the Householder tree's Q of the ORIGINAL panel, I, its R) where it
    was not; the tree and I otherwise.  The ``cond`` chooses and applies
    nothing: its well-conditioned branch hands Q₁ back as it got it, and
    its fall-back ends in a product that writes a panel it does not read
    (:func:`_local_tsqr_blocked`), so XLA lets both results be the buffer
    Q₁ lies in and copies neither (read in the text compiled for a v5e,
    PERF.md section 5; a fall-back that ended in a loop over its own
    panel cost the well-conditioned branch a copy of Q₁).  The caller's
    one application (:func:`_tsqr_shardmap`) carries whatever else
    multiplies Q from the right.  ``cholqr`` is a trace-time static
    (threaded from `_use_cholqr()` through the jit cache key, so flipping
    the env var retraces instead of being ignored).  ``policy`` governs
    only the reduction tree's batched Q-apply GEMMs; the
    Householder/Cholesky factorisations and the applications of R⁻¹ are
    pinned f32."""
    eye = _ops.varying_like(jnp.eye(a.shape[1], dtype=a.dtype), a)

    def tree(op):
        q, r = _local_tsqr(op, policy)
        return q, eye, r

    if not cholqr:
        return tree(a)
    q1, r2_inv, r_c, ok = _cholqr2(a)
    return lax.cond(ok, lambda q, op: (q, r2_inv, r_c),
                    lambda q, op: tree(op), q1, a)


def _split_count(rows: int, n: int, target: int = 8) -> int:
    """Largest power-of-two ``s`` dividing ``rows`` with panels ≥ target·n tall."""
    s = 1
    while rows % (2 * s) == 0 and rows // (2 * s) >= target * max(n, 1):
        s *= 2
    return s


def _local_tsqr(a, policy=px.FLOAT32):
    """Shard-LOCAL tall-skinny QR as a batched reduction tree.

    A single Householder QR of an (M, n) panel is a column-sequential
    factorisation — each of the n reflector steps is a skinny matvec +
    rank-1 update, far below MXU occupancy for M ≫ n.  This applies the
    reference's tsQR reduction tree (SURVEY §3.2: per-block QR + pairwise
    R merges) *within* one chip: factor ``s`` sub-panels as ONE batched QR
    (the batch dimension feeds the MXU), then recurse on the (s·n, n)
    R-stack until it is short enough to factor directly.  Same
    Householder-tree numerics as the cross-shard tsQR, so stability is
    unchanged; shapes are static so the whole tree is one traced program.
    Degrades to a plain ``jnp.linalg.qr`` when the input is too short to
    split (the CPU-rig test shapes and the p·n R-stack at small p).  A
    panel of more than one :func:`_panel_block` takes the tree's first
    level block by block (:func:`_local_tsqr_blocked`).
    """
    rows, n = a.shape
    if rows > _panel_block(rows, n) >= n:
        return _local_tsqr_blocked(a, policy)
    s = _split_count(rows, n)
    if s == 1:
        return jnp.linalg.qr(a, mode="reduced")
    q0, r0 = jnp.linalg.qr(a.reshape(s, rows // s, n), mode="reduced")
    q1, r = _local_tsqr(r0.reshape(s * n, n), policy)
    q = px.pdot(q0, q1.reshape(s, n, n), policy)             # batched GEMM
    return q.reshape(rows, n), r


def _local_tsqr_blocked(a, policy):
    """:func:`_local_tsqr` of a panel taller than one block, its first
    level a loop: every block's Householder QR written where the block
    lay, the blocks' R factors stacked and handed to the tree, and the
    tree's Q applied to all blocks in ONE batched product that reads that
    panel and writes another (a loop that wrote each block where it lay
    cut the block out first, a copy of it).  The same tree, with a
    block's temporaries where the batched first level holds two more
    panels (at 1.5M x 256 beside a 6 GiB array that is the difference
    between fitting the chip and not)."""
    rows, n = a.shape
    block = _panel_block(rows, n)

    def factor(start, size, carry):
        q, stack = carry
        qb, rb = jnp.linalg.qr(lax.dynamic_slice_in_dim(q, start, size),
                               mode="reduced")
        return (lax.dynamic_update_slice_in_dim(q, qb, start, 0),
                lax.dynamic_update_slice_in_dim(stack, rb,
                                                start // block * n, 0))

    q0, stack = _over_blocks(
        rows, block, factor,
        (a, _ops.varying_like(jnp.zeros((rows // block * n, n), a.dtype), a)))
    q1, r = _local_tsqr(stack, policy)

    whole = _whole_blocks(rows, block)          # as the loop above cut them
    tops = q1.reshape(rows // block, n, n)
    q = px.pdot(q0[:whole * block].reshape(whole, block, n), tops[:whole],
                policy).reshape(whole * block, n)
    if whole * block < rows:
        q = jnp.concatenate([q, px.pdot(q0[whole * block:], tops[whole],
                                        policy)])
    return q, r


@partial(jax.jit, static_argnames=("mesh", "p", "cholqr", "policy"))
@precise
def _tsqr_shardmap(av, mesh, p, *, cholqr, policy=px.FLOAT32):
    """(Q, R) of a row-sharded tall ``av``: a shard's local factorisation
    (:func:`_local_qr`), ONE ``all_gather`` of the (n, n) factors, the same
    factorisation of their stack, and ONE application to the tall panel,
    which carries this shard's block of Q₂ and whatever factor the local
    route left unapplied: Q_i = panel · (factor · Q₂ᵢ).  Counted once a
    trace: ``schedule_counters()["tsqr_local:<route>"]`` and
    ``["tsqr_assemble:folded"]``.

    ``cholqr`` is REQUIRED (no default): every caller must resolve
    `_use_cholqr()` at its own trace boundary and thread it through its
    jit cache key, otherwise an env flip after the first trace would be
    silently ignored."""
    n = av.shape[1]
    _count_schedule("tsqr_local", local_qr_route(av.shape[0] // p, n, cholqr))
    _count_schedule("tsqr_assemble", "folded")

    def local(a_shard):
        panel, factor, r1 = _local_qr(a_shard, cholqr, policy)   # (m/p, n)
        r_stack = lax.all_gather(r1, _mesh.ROWS)             # (p, n, n) — ICI
        r_stack = r_stack.reshape(p * n, n)
        panel2, factor2, r = _local_qr(r_stack, cholqr, policy)  # per shard
        idx = lax.axis_index(_mesh.ROWS)
        # this shard's (n, n) block of Q₂, and with it everything that
        # multiplies the tall panel from the right, formed BEFORE the
        # panel is touched again: Q_i = panel · (factor · Q₂ᵢ)
        q2_i = px.pdot(lax.dynamic_slice(panel2, (idx * n, 0), (n, n)),
                       factor2)
        # R is computed identically on every shard, but the static
        # varying-axes analysis can't see that through the local QR; a
        # psum/p makes the replication PROVABLE so check_vma stays ON
        # (SURVEY §6 race-detection row: shard_map replication checking is
        # the collective-correctness sanitizer).  Cost: one (n, n) psum.
        r = lax.psum(r, _mesh.ROWS) / p
        return _apply(panel, px.pdot(factor, q2_i)), r

    q, r = jax.shard_map(
        local, mesh=mesh,
        in_specs=P(_mesh.ROWS, None),
        out_specs=(P(_mesh.ROWS, None), P(None, None)),
        check_vma=True,
    )(av)
    return q, r


def _col_repad(q_pad):
    """Pad Q's column dim back to the mesh quantum (rows already padded)."""
    import math
    q = _mesh.pad_quantum()
    n = q_pad.shape[1]
    target = max(q, int(math.ceil(n / q)) * q)
    if target != n:
        q_pad = jnp.pad(q_pad, ((0, 0), (0, target - n)))
    return jax.device_put(q_pad, _mesh.data_sharding())
