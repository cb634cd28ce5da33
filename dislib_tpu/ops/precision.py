"""Mixed-precision policy — the ONE place library kernels get compute dtypes.

The paper regime ("Large Scale Distributed Linear Algebra With TPUs",
arXiv:2112.09017) runs the MXU at its native bf16 input throughput
(~2x f32 per chip; this rig's r05 capture measured 2.6x) while
accumulating partial sums in float32 — "bf16-compute / f32-accumulate".
dislib_tpu exposes that as a *policy*:

- ``float32`` (default): operands contract at float32-faithful precision
  (``'highest'`` — on TPU a 6-pass bf16 decomposition, exactly the
  pre-policy behavior of every library kernel).
- ``bfloat16``: GEMM operands are rounded to bfloat16 and contracted with
  float32 accumulation (``preferred_element_type``).  Input rounding is
  2^-9 relative per operand, so results carry ~0.2-2% relative error —
  the documented bounds live in :data:`ERROR_BOUNDS` and are asserted by
  ``tests/test_precision.py``.

Selection order: an explicit ``precision=`` kwarg on the public entry
points (``math.matmul``, ``math.qr``, ``math.polar``, ``math.svd``,
``tsqr``, ``random_svd``, ``lanczos_svd``, ``PCA``) wins; otherwise the
``DSLIB_MATMUL_PRECISION`` env var; otherwise ``float32``.  Policies are
hashable named tuples and ride the jit cache key as static arguments, so
flipping the env var retraces instead of being silently ignored (the
``_use_cholqr`` precedent).

Scope of a policy inside composite factorisations (QR, tsQR, randomized
SVD, block-Jacobi SVD, Lanczos, PCA): the FLOP-dominant applied GEMMs
(panel updates, Q assembly/application, power-iteration products,
Gram/scatter products, Jacobi pair updates) follow the policy; the small
dense factorisations (Householder QR of a panel, Cholesky of a Gram, the
(sketch x sketch) or (2b x 2b) SVD) are ALWAYS pinned float32 — rounding a factorisation's interior would destroy its
backward stability for no meaningful FLOP win.  Pure-GEMM kernels
(matmul, SUMMA, Newton-Schulz polar, distances) follow the policy end to
end.

Lint contract (``tests/test_precision_lint.py``): library kernels under
``dislib_tpu/{math,ops,decomposition}`` may not hardcode compute dtypes
(``.astype(jnp.float32)`` and friends), call
``jax.default_matmul_precision`` directly, or pass literal ``precision=``
strings to dots — they route through :func:`f32` / :func:`to_compute` /
:func:`pdot` / :func:`precise` here, so a precision decision is a greppable
one-module audit instead of a per-kernel archaeology dig.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Policy(NamedTuple):
    """A compute/accumulate precision pair for library GEMMs.

    Hashable (strings only) so it can thread through ``jax.jit`` static
    arguments — a kernel traced under one policy retraces under another.
    """

    name: str             # canonical policy name ("float32" | "bfloat16")
    compute: str          # dtype operands are rounded to for GEMM passes
    accum: str            # accumulation dtype (always float32)
    dot_precision: str | None  # lax precision for f32-operand dots


FLOAT32 = Policy("float32", "float32", "float32", "highest")
BFLOAT16 = Policy("bfloat16", "bfloat16", "float32", None)

_POLICIES = {"float32": FLOAT32, "bfloat16": BFLOAT16}
_ALIASES = {
    "float32": "float32", "f32": "float32", "fp32": "float32",
    "highest": "float32",
    "bfloat16": "bfloat16", "bf16": "bfloat16",
}

# Documented relative-error bounds of the bfloat16 policy vs the float32
# reference, asserted by tests/test_precision.py and quoted in the user
# guide.  bf16 unit roundoff is 2^-9 ~= 2e-3 per operand; with f32
# accumulation the dominant term is input rounding, so well-conditioned
# results sit at a few 1e-3 and the bounds below carry ~4-8x headroom for
# shape/conditioning spread (measured on this rig across the test grid).
ERROR_BOUNDS = {
    # max_ij |C - C_ref| / (||A||_F ||B||_F / sqrt(k)) — normalized entry error
    ("matmul", "bfloat16"): 2e-2,
    # ||Q^T Q - I||_max of the assembled Q (policy applies to panel
    # updates + Q assembly; panel factorisations stay f32)
    ("qr_orth", "bfloat16"): 4e-2,
    # ||A - Q R||_F / ||A||_F
    ("qr_resid", "bfloat16"): 2e-2,
    ("tsqr_orth", "bfloat16"): 4e-2,
    ("tsqr_resid", "bfloat16"): 2e-2,
    # top singular values, relative: |s - s_ref| / s_ref[0]
    ("randomsvd_values", "bfloat16"): 2e-2,
    # the GKL recurrence AMPLIFIES matvec rounding (each step feeds the
    # next), so Lanczos carries a wider bound than the one-shot sketches;
    # prefer random_svd when running the bfloat16 policy
    ("lanczos_values", "bfloat16"): 1e-1,
    # polar orthogonality floor: ||U^T U - I||_max (Newton-Schulz is
    # self-correcting down to the compute dtype's roundoff)
    ("polar_orth", "bfloat16"): 5e-2,
    ("polar_resid", "bfloat16"): 3e-2,
    # block-Jacobi SVD (round-11 satellite): policy on the pair-update
    # GEMMs only; sweeps re-orthogonalize each round, so errors sit at
    # the per-update rounding (~2-8e-3 measured across the test grid),
    # not an accumulation of it.  values: |s - s_ref| / s_ref[0];
    # resid: ||A - U S Vt||_F / ||A||_F
    ("svd_values", "bfloat16"): 2e-2,
    ("svd_resid", "bfloat16"): 4e-2,
    # float32 policy: the f32-faithful reference itself; listed so the
    # test grid exercises both policies through one table
    ("matmul", "float32"): 1e-6,
    ("qr_orth", "float32"): 1e-4,
    ("qr_resid", "float32"): 1e-5,
    ("tsqr_orth", "float32"): 1e-4,
    ("tsqr_resid", "float32"): 1e-5,
    ("randomsvd_values", "float32"): 1e-4,
    # Lanczos at float32 is TRUNCATION-dominated (k singular values from
    # ~2k GKL steps), not rounding-dominated — the bound reflects the
    # solver's approximation error at the tested depth, same as the
    # reference's tolerance semantics
    ("lanczos_values", "float32"): 1e-2,
    ("polar_orth", "float32"): 1e-4,
    ("polar_resid", "float32"): 1e-4,
    ("svd_values", "float32"): 1e-4,
    ("svd_resid", "float32"): 1e-4,
}


def resolve(precision=None) -> Policy:
    """The library's ONE precision-selection rule.

    ``precision`` may be a :class:`Policy`, a name/alias (``"float32"``,
    ``"f32"``, ``"bfloat16"``, ``"bf16"``), or None — None reads
    ``DSLIB_MATMUL_PRECISION`` (same aliases) and falls back to float32.
    """
    if precision is None:
        precision = os.environ.get("DSLIB_MATMUL_PRECISION") or "float32"
    if isinstance(precision, Policy):
        return precision
    key = _ALIASES.get(str(precision).lower())
    if key is None:
        raise ValueError(
            f"unknown precision policy {precision!r}: expected one of "
            f"{sorted(set(_ALIASES))} (or a dislib_tpu.ops.precision.Policy)")
    return _POLICIES[key]


def of_name(name: str) -> Policy:
    """Policy from its canonical name (fused-instruction statics store the
    name, not the tuple, to keep program cache keys minimal)."""
    return _POLICIES[name]


def compute_dtype(policy: Policy):
    return jnp.dtype(policy.compute)


def accum_dtype(policy: Policy):
    return jnp.dtype(policy.accum)


def to_compute(x, policy: Policy = FLOAT32):
    """Round an operand to the policy's GEMM compute dtype (the ONE place
    library kernels cast operand precision).  Zero is exact in every
    policy dtype, so the pad-and-mask invariant survives the cast.

    The float32 policy is a *floor*, not a ceiling: float64 operands on
    an x64-mode rig pass through untouched (narrowing full-precision user
    data is never implicit — the ``ds.array`` dtype-policy precedent).
    The bfloat16 policy is an explicit opt-in to reduced precision and
    rounds every float input, float64 included."""
    dt = jnp.dtype(policy.compute)
    if policy.name == "float32" and x.dtype == jnp.float64:
        return x
    return x if x.dtype == dt else x.astype(dt)


def f32(x):
    """Pin an operand to exactly float32 — the ingest cast for
    panel/small-matrix factorisations that stay f32 under EVERY policy
    (see module docstring), and for integral inputs entering float
    kernels.  Unlike :func:`to_compute`'s float32 policy this IS a
    ceiling: the f32 kernels' shapes/numerics assume it."""
    dt = jnp.dtype(jnp.float32)
    return x if x.dtype == dt else x.astype(dt)


def pdot(a, b, policy: Policy = FLOAT32):
    """THE library GEMM: operands rounded to the policy compute dtype,
    contracted with float32 accumulation.

    float32 policy: ``precision='highest'`` — bit-identical to the
    pre-policy kernels (f32 @ f32 at float32-faithful precision).
    bfloat16 policy: operands round to bf16 and the dot accumulates f32
    via ``preferred_element_type`` — on the MXU that is the native
    single-pass bf16 systolic contraction, on CPU a bf16-input GEMM
    (measurably faster on this rig: 2.3x in the r08 smoke capture).
    Output dtype is the accumulation dtype (float32; float64 on x64-mode
    float64 operands under the float32-floor policy).  ``jnp.matmul``
    semantics, so batched (3-D) operands contract per batch."""
    return _contract(jnp.matmul, a, b, policy)


def peinsum(subscripts, a, b, policy: Policy = FLOAT32):
    """The library's policy-routed einsum — :func:`pdot` for contractions
    a plain matmul can't spell (the block-Jacobi SVD's batched pair
    updates).  Operands round to the policy compute dtype, the
    contraction accumulates float32 (``preferred_element_type``), output
    is the accumulation dtype — same contract as :func:`pdot`."""
    return _contract(functools.partial(jnp.einsum, subscripts), a, b, policy)


def _contract(contraction, a, b, policy):
    """The body :func:`pdot` and :func:`peinsum` share, under the one
    device scope that names the library's GEMMs in a trace."""
    with jax.named_scope("dslib.pdot"):
        a = to_compute(a, policy)
        b = to_compute(b, policy)
        acc = jnp.promote_types(jnp.dtype(policy.accum),
                                jnp.promote_types(a.dtype, b.dtype))
        return contraction(a, b, precision=policy.dot_precision,
                           preferred_element_type=acc)


def highest_parts(x):
    """The three bfloat16 addends ``(hi, mid, lo)`` of a float32 ``x``,
    ``hi + mid + lo == x`` to the last bit, eight significant bits each:
    what a 'highest' contraction feeds the MXU, for a kernel that spells
    the six passes out itself because ONE split of a tile then serves two
    products (``pallas_kernels.kmeans_step``).

    ``hi`` is ``x`` rounded to nearest, on the bits: what it leaves then
    has either sign, and the products a six-pass contraction drops
    (mid.lo, lo.mid, lo.lo) cancel over a sum instead of adding up one
    way (PR 27 read 4e-6 of an inertia with ``hi`` cut).  ``mid`` is what
    is left, cut to its first eight bits: it and ``lo`` take the sign
    ``hi``'s rounding left, so nothing more is to be had from rounding
    again, and the cut is one op an element less in a kernel the vector
    unit bounds (6.97 against 7.12 ms an iteration, PERF.md, PR 28).  No
    cast to bfloat16 and back does the rounding: XLA, which interprets
    the kernel on a CPU, folds that round trip away and the lower parts
    come out zero."""
    bf16 = jnp.dtype(jnp.bfloat16)
    top = jnp.uint32(0xFFFF0000)

    def head(v, nearest):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        if nearest:
            bits = bits + jnp.uint32(0x8000)
        return jax.lax.bitcast_convert_type(bits & top, v.dtype)

    x = f32(x)
    hi = head(x, nearest=True)
    rest = x - hi
    mid = head(rest, nearest=False)
    return hi.astype(bf16), mid.astype(bf16), (rest - mid).astype(bf16)


# one MXU pass of bfloat16 operands with float32 accumulation, stated on
# the dot: inside a `precise` scope an unstated precision asks for six
# passes of operands that hold no more bits than one
ONE_PASS = jax.lax.Precision.DEFAULT


# where a short contraction is packed (below): backends whose matmul unit
# is a systolic array much deeper than the contraction
_PACK_BACKENDS = ("tpu",)


def pdot_short(a, b, policy: Policy = FLOAT32):
    """:func:`pdot` for a contraction much shorter than the MXU is deep:
    ``a`` (m, c) @ ``b`` (c, n) with c a few tens against a depth of 128.

    Under the float32 policy a 'highest' contraction is six bfloat16
    passes, and each fills c of the array's 128 rows.  Here the six are
    packed along the contraction instead: ``[a_hi, a_mid, a_lo, a_hi,
    a_mid, a_hi] @ [b_hi; b_hi; b_hi; b_mid; b_mid; b_lo]``, ONE
    bfloat16 GEMM 6c deep accumulated in float32: the same six products
    of the same :func:`highest_parts`, summed in the same accumulator, in
    ceil(6 c16 / 128) passes instead of 6, c16 being c in whole tiles of
    16 (three for c = 50: 88 against 175 ms an EM iteration's E-step at
    24M x 50, k = 16; PERF.md, PR 29).
    Decided by what a trace can observe (:func:`packs_short`): the
    float32 policy, float32 operands and a backend of ``_PACK_BACKENDS``;
    everything else is :func:`pdot` as it stands.

    Its two halves are functions of their own, below ``precise`` beside
    :func:`pdot_tall`: :func:`short_right` packs ``b``, and
    :func:`pdot_packed` multiplies a packed left operand
    (:func:`short_left`) by it; this is the two in a row with the
    contraction in ONE chunk.  A caller whose ``b`` serves many products
    packs it once and keeps it, and one whose ``b`` is upper triangular
    cuts the contraction in chunks of 16, so that a group of ``b``'s
    columns reads a prefix of the packed left operand and no more.  The
    mixture's E-step does both: its factors are packed once an
    iteration, its rows' parts once a block, and it makes three products
    of them (``ops/base.py::em_whitener``, ``_em_log_prob``)."""
    if not packs_short(a.dtype, b.dtype, policy):
        return pdot(a, b, policy)
    whole = a.shape[-1] + -a.shape[-1] % SHORT_CHUNK
    return pdot_packed(short_left(a, whole), short_right(b, whole))


def precise(fn):
    """Trace-time float32-faithful matmul scope for library kernels.

    TPU matmuls default to bfloat16 passes; the reference's per-block
    kernels are NumPy float64, so dislib_tpu's own GEMMs run
    float32-faithful ('highest') unless a caller explicitly opts a kernel
    into the bfloat16 policy via ``precision=``/:func:`pdot`.  Scoped
    here (under each kernel's ``jax.jit``, active during tracing) rather
    than via the global ``jax_default_matmul_precision`` flag so user
    code's own precision configuration is never touched.  An explicit
    ``precision=`` on a dot (what :func:`pdot` passes) overrides the
    scope, so policy-routed GEMMs inside a ``precise`` kernel behave per
    their policy while every other dot stays f32-faithful."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


# (Below `precise` on purpose: a Pallas kernel's compile-cache key holds the
# lines of the frames it was traced under, `precise`'s among them, and the
# KMeans fit's program keeps its key only while those lines stay.)
# Columns of one MXU tile: a product whose narrow side fills whole tiles
# has nothing to pack.
_MXU_COLUMNS = 128


def packs_tall(n: int, dtype, policy: Policy = FLOAT32) -> bool:
    """Whether :func:`pdot_tall` packs a product ``n`` columns wide of
    operands of ``dtype``: under the float32 policy, for float32 operands,
    on a backend of ``_PACK_BACKENDS``, and where the parts of the narrow
    operand laid side by side take fewer column tiles than six passes of
    it (n = 51: 2 + 1 + 1 against 6; n = 128: 3 + 2 + 1, nothing won)."""
    def tiles(columns):
        return -(-columns // _MXU_COLUMNS)

    return policy.name == "float32" and dtype == jnp.dtype(jnp.float32) \
        and jax.default_backend() in _PACK_BACKENDS \
        and tiles(3 * n) + tiles(2 * n) + tiles(n) < 6 * tiles(n)


def pdot_tall(a, b, policy: Policy = FLOAT32):
    """``a.T @ b`` of two tall operands that share their long first axis,
    ``a`` (rows, m) and ``b`` (rows, n) with n much narrower than the MXU
    is wide: the weighted Gram product of an EM block, (7680, 800) against
    (7680, 51).

    A 'highest' contraction is six bfloat16 passes, and with n = 51 each
    holds a tile of ``a``'s part in the array for 51 columns of ``b``'s.
    Here ``b``'s parts lie side by side on the narrow side instead:
    ``a_hi.T @ [b_hi | b_mid | b_lo]``, ``a_mid.T @ [b_hi | b_mid]`` and
    ``a_lo.T @ b_hi`` are three bfloat16 GEMMs accumulated in float32,
    a part of ``a`` read once for up to three products, and their (m, n)
    slices are added, the smallest first.  The same six products of the
    same :func:`highest_parts` as a six-pass contraction and as
    :func:`pdot_short`.  Each GEMM splits ``a`` as it reads it, so ``a``
    is best ONE array in memory and not an expression XLA forms three
    times (84 against 97 ms an EM iteration's M-step product at 24M x 50,
    k = 16, the GEMMs taking a quarter of a millisecond for each of their
    306 columns; PERF.md, PR 30).
    Decided by :func:`packs_tall`; everything else is
    ``peinsum("bp,bq->pq", a, b)`` as it stands."""
    if a.dtype != b.dtype or not packs_tall(b.shape[1], a.dtype, policy):
        return peinsum("bp,bq->pq", a, b, policy)
    with jax.named_scope("dslib.pdot"):
        n = b.shape[1]
        a_hi, a_mid, a_lo = highest_parts(a)
        b_hi, b_mid, b_lo = highest_parts(b)

        def down(x, y):
            return jax.lax.dot_general(
                x, y, (((0,), (0,)), ((), ())), precision=ONE_PASS,
                preferred_element_type=jnp.dtype(jnp.float32))

        by_hi = down(a_hi, jnp.concatenate([b_hi, b_mid, b_lo], axis=1))
        by_mid = down(a_mid, jnp.concatenate([b_hi, b_mid], axis=1))
        return (by_hi[:, 2 * n:] + by_mid[:, n:] + down(a_lo, b_hi)) \
            + (by_hi[:, n:2 * n] + by_mid[:, :n]) + by_hi[:, :n]


def pdot_pattern(w, b):
    """``w @ b`` for a 0/1 pattern ``w`` (m, rows), boolean or numeric,
    and a float32 ``b`` (rows, n), to the last term of a 'highest'
    contraction.  A 0 or a 1 is exact in bfloat16, so the parts of ``w``
    are (w, 0, 0) and three of the six products are zero: what is left is
    ``w @ b_hi + w @ b_mid + w @ b_lo``, laid along the contraction as ONE
    bfloat16 GEMM 3·rows deep accumulated in float32, ``[w | w | w] @
    [b_hi; b_mid; b_lo]``.  Nothing is dropped: it is the six-pass product
    in half the passes (ALS's dense items: the items' rating pattern
    against the users' packed outer products)."""
    with jax.named_scope("dslib.pdot"):
        w = (w != 0).astype(jnp.dtype(jnp.bfloat16))
        return jnp.matmul(jnp.concatenate([w] * 3, axis=-1),
                          jnp.concatenate(highest_parts(b), axis=-2),
                          precision=ONE_PASS,
                          preferred_element_type=jnp.dtype(jnp.float32))


# Rows of a packed short contraction that lie together: one bfloat16
# sublane tile.  A chunk is a whole number of them.
SHORT_CHUNK = 16


def packs_short(a_dtype, b_dtype, policy: Policy = FLOAT32) -> bool:
    """Whether :func:`pdot_short` packs a product of operands of these
    dtypes: under the float32 policy, for float32 operands, on a backend
    of ``_PACK_BACKENDS``."""
    f32_ = jnp.dtype(jnp.float32)
    return policy.name == "float32" and a_dtype == f32_ and b_dtype == f32_ \
        and jax.default_backend() in _PACK_BACKENDS


def _short_packed(x, chunk, order, axis):
    """The bfloat16 parts of ``x`` laid along ``axis`` (its contraction,
    zero-filled to whole chunks) chunk by chunk of ``chunk`` (whole
    ``SHORT_CHUNK``s, so that the pieces lie side by side without a
    relayout), and inside a chunk in ``order``: a stack of the
    parts over the chunks, between two barriers.  The first, on the parts
    as (.., chunks, chunk) arrays, has them split ONCE, in one fusion, and
    the stack written by six that only move them.  The second, on the
    packed operand, keeps the TPU's compiler from folding what a caller
    does with the product (a reshape of its columns, a sum over them)
    into the GEMM as a convolution that runs at half its speed.  What
    the two leave is one copy of the operand, chunk-major from the
    part-major order the compiler stacks in (PERF.md, PR 37, has the
    spellings that lost)."""
    with jax.named_scope("dslib.pdot"):
        fill = [(0, 0)] * x.ndim
        fill[axis] = (0, -x.shape[axis] % chunk)
        parts = jax.lax.optimization_barrier([
            p.reshape(*p.shape[:axis], -1, chunk, *p.shape[axis + 1:])
            for p in highest_parts(jnp.pad(x, fill))])
        packed = jnp.stack([parts[p] for p in order], axis=axis + 1)
        return jax.lax.optimization_barrier(packed.reshape(
            *x.shape[:axis], -1, *x.shape[axis + 1:]))


def short_left(a, chunk):
    """The left operand of a packed short contraction: float32 ``a`` (m,
    c) as bfloat16 (m, 6 c'), ``[hi, mid, lo, hi, mid, hi]`` of the first
    ``chunk`` columns, then of the next, ..., c' being c in whole chunks.
    The leading ``6 r`` columns, r a whole number of chunks, are the
    packed operand of a product that contracts the first r columns of
    ``a`` alone (with its rows on the lanes, as the chip holds a tall
    operand, a prefix of the columns is the same memory: no copy)."""
    return _short_packed(a, chunk, (0, 1, 2, 0, 1, 0), a.ndim - 1)


def short_right(b, chunk):
    """The right operand to match :func:`short_left`: float32 ``b`` (c, n)
    as bfloat16 (6 c', n), ``[hi; hi; hi; mid; mid; lo]`` chunk by
    chunk."""
    return _short_packed(b, chunk, (0, 0, 0, 1, 1, 2), b.ndim - 2)


def pdot_packed(left, right):
    """ONE bfloat16 GEMM accumulated in float32 of the leading columns of
    a :func:`short_left` by a :func:`short_right` of those columns' rows
    alone, both in the same chunks: the six products of a 'highest'
    contraction over those columns."""
    with jax.named_scope("dslib.pdot"):
        return jnp.matmul(
            jax.lax.slice_in_dim(left, 0, right.shape[-2],
                                 axis=left.ndim - 1),
            right, precision=ONE_PASS,
            preferred_element_type=jnp.dtype(jnp.float32))
