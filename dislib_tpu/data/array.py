"""The distributed array (ds-array) — dislib_tpu's single data structure.

Reference capability (SURVEY.md §3.1, `dislib/data/array.py :: class Array`):
a dense or sparse 2-D matrix partitioned into a grid of rectangular blocks,
each block a NumPy/CSR chunk held as a PyCOMPSs future; block-level ops are
``@task`` functions and nothing computes until an explicit sync
(``collect()`` / ``compss_wait_on``).

TPU-native redesign — NOT a block-of-futures translation:

- The whole matrix is ONE global :class:`jax.Array`, laid out on the library
  mesh with ``NamedSharding(P('rows', 'cols'))``.  Placement, inter-device
  movement and overlap come from XLA SPMD + async dispatch, which already
  plays the role the COMPSs task graph plays for the reference (SURVEY.md §8
  "Design stance").
- The reference's irregular top-left block / arbitrary ``block_size`` becomes
  *pad-and-mask metadata*: ``_data`` is padded so every dimension is a
  multiple of the mesh pad quantum, and the region outside the logical
  ``shape`` is ALWAYS ZERO.  That invariant makes contractions (matmul, sum,
  norm) correct with no masking, while min/max/mean mask or rescale
  explicitly.  Ops that could make padding non-zero re-zero it.
- ``block_size`` survives as a *hint* (`_reg_shape`) for API parity and for
  algorithms whose blocking is semantic (QR panels, tsQR tree arity); it no
  longer dictates physical layout — XLA tiles for the MXU itself.
- The "cheap to build, pay on sync" contract (SURVEY.md §4.6) is preserved by
  JAX's async dispatch: every method returns immediately with a live
  ``jax.Array``; ``collect()`` is the only host sync.
- **Dispatch fusion** (round-7 perf PR): op chains don't even dispatch
  per-op.  Elementwise ops, transpose, basic slicing, reductions,
  ``math.matmul`` and ``ops.distances_sq`` build a small deferred
  expression (:class:`_LazyExpr`); the first host-forcing access
  (``collect()``, ``force()``, any internal ``_data`` read, ``float()``,
  a snapshot fetch) compiles and runs the WHOLE chain as ONE cached XLA
  program (``_exec_program``): a k-op chain costs one dispatch round
  trip instead of k.
  ``DSLIB_EAGER=1`` restores per-op dispatch for debugging, and chains
  force themselves after ``DSLIB_FUSION_CAP`` nodes (default 96) so a
  long Python loop cannot build an unboundedly large program.  Fused and
  eager paths share the same op bodies, so results match bit-for-bit up
  to XLA's in-program excess-precision FMA contraction (≤ 1 ulp; see
  ``_exec_program``) — pinned by ``tests/test_fusion.py``.

Sparse support: ``_sparse=True`` arrays keep a BCOO backing for memory-honest
storage where it pays (see `dislib_tpu/data/sparse.py`), with a dense+mask
fallback — the decision recorded per estimator as SURVEY §8 directs.
"""

from __future__ import annotations

import math
import os
from functools import partial
from numbers import Number

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dislib_tpu.ops.base import distances_sq as _raw_distances_sq, precise
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.utils.profiling import host_read as _host_read
from dislib_tpu.utils.profiling import profiled_jit as _pjit
from dislib_tpu.utils.profiling import span as _span

__all__ = [
    "Array",
    "array",
    "random_array",
    "zeros",
    "full",
    "ones",
    "identity",
    "eye",
    "apply_along_axis",
    "concat_rows",
    "concat_cols",
    "rechunk",
    "ensure_canonical",
]


# ---------------------------------------------------------------------------
# padding helpers
# ---------------------------------------------------------------------------

def _padded_dim(n: int, quantum: int) -> int:
    return max(quantum, int(math.ceil(n / quantum)) * quantum)


def _padded_shape(shape, quantum):
    return tuple(_padded_dim(int(s), quantum) for s in shape)


def _pad_mask(padded_shape, logical_shape, dtype=jnp.bool_):
    """Boolean mask: True inside the logical region."""
    r = lax.broadcasted_iota(jnp.int32, padded_shape, 0) < logical_shape[0]
    c = lax.broadcasted_iota(jnp.int32, padded_shape, 1) < logical_shape[1]
    return (r & c).astype(dtype)


def _zero_pad(data, logical_shape):
    """Force the padding region to zero (the core Array invariant)."""
    if data.shape == tuple(logical_shape):
        return data
    return jnp.where(_pad_mask(data.shape, logical_shape), data, jnp.zeros((), data.dtype))


@partial(_pjit, static_argnames=("padded_shape", "logical_shape"),
         name="place")
def _place(data, padded_shape, logical_shape):
    """Pad `data` (logical region) up to padded_shape with zeros."""
    out = jnp.zeros(padded_shape, data.dtype)
    out = lax.dynamic_update_slice(out, data.astype(out.dtype), (0, 0))
    del logical_shape
    return out


def _default_block_size(shape, mesh):
    r, c = _mesh.mesh_shape(mesh)
    return (max(1, -(-shape[0] // r)), max(1, -(-shape[1] // c)))


# ---------------------------------------------------------------------------
# dispatch fusion: the lazy expression layer
# ---------------------------------------------------------------------------

def _eager_mode() -> bool:
    """True when DSLIB_EAGER=1 — every op dispatches its own XLA program
    (the pre-fusion behavior; the debugging escape hatch)."""
    return os.environ.get("DSLIB_EAGER", "0") not in ("", "0")


def _fusion_cap() -> int:
    """Max deferred nodes per chain before an automatic force — bounds
    both compile time and the linearizer's recursion depth."""
    return int(os.environ.get("DSLIB_FUSION_CAP", "96"))


class _LazyExpr:
    """One deferred op: ``op`` names an entry in ``_INSTRS``, ``static``
    is its hashable config (shapes, op variants), ``args`` are child
    ``_LazyExpr`` nodes or concrete ``jax.Array``/ndarray leaves.
    ``pshape``/``dtype`` are the padded output shape and dtype, computed
    at build time so ``Array`` metadata never forces the chain.

    ``refs`` counts consumers (parent nodes + wrapping Arrays).  A node
    with ``refs > 1`` is a shared prefix: the force that first reaches it
    emits it as an extra program output and caches it in ``value``, so
    every other consumer linearizes it as a LEAF instead of re-running
    (and re-compiling) the whole prefix per fan-out branch."""

    __slots__ = ("op", "static", "args", "pshape", "dtype", "n_ops",
                 "refs", "value")

    def __init__(self, op, static, args, pshape, dtype):
        self.op = op
        self.static = static
        self.args = args
        self.pshape = tuple(int(s) for s in pshape)
        self.dtype = jnp.dtype(dtype)
        self.refs = 0
        self.value = None
        self.n_ops = 1
        for a in args:
            if isinstance(a, _LazyExpr):
                a.refs += 1
                self.n_ops += a.n_ops


def _linearize(root: _LazyExpr):
    """Postorder program for one chain: ``(instrs, leaves, shared)``.

    Each instruction is ``(op, static, srcs)`` with a src of
    ``(0, leaf_idx)`` or ``(1, instr_idx)``; the program's trailing
    element is the tuple of instr indices to RETURN alongside the root —
    the shared (refs > 1) interior nodes, listed in ``shared`` so the
    caller can backfill their ``value`` caches.  Shared subexpressions
    and repeated leaves dedupe by identity, valued nodes load as leaves,
    so diamond graphs and cross-Array fan-outs evaluate once."""
    instrs, leaves, shared = [], [], []
    instr_memo, leaf_memo = {}, {}

    def visit(node):
        if isinstance(node, _LazyExpr) and node.value is None:
            slot = instr_memo.get(id(node))
            if slot is None:
                srcs = tuple(visit(a) for a in node.args)
                instrs.append((node.op, node.static, srcs))
                slot = (1, len(instrs) - 1)
                instr_memo[id(node)] = slot
                if node.refs > 1 and node is not root:
                    shared.append((node, len(instrs) - 1))
            return slot
        if isinstance(node, _LazyExpr):
            node = node.value           # materialised prefix → plain leaf
        slot = leaf_memo.get(id(node))
        if slot is None:
            leaves.append(node)
            slot = (0, len(leaves) - 1)
            leaf_memo[id(node)] = slot
        return slot

    visit(root)
    program = tuple(instrs) + (tuple(idx for _, idx in shared),)
    return program, leaves, [node for node, _ in shared]


def _place_region(v, pshape):
    """Traced analog of `_repad`'s place+reshard: zero canvas, write the
    logical region at (0, 0), constrain to the library sharding."""
    if tuple(v.shape) != tuple(pshape):
        canvas = jnp.zeros(pshape, v.dtype)
        v = lax.dynamic_update_slice(canvas, v, (0, 0))
    return lax.with_sharding_constraint(v, _mesh.data_sharding())


def _matmul_body(a, b, ta, tb, policy=None):
    """The ONE GEMM body shared by the eager `math.matmul` kernel and the
    fused "matmul" instruction (zero padding ⇒ padded == logical dot).
    ``policy`` is a precision policy (None → float32-faithful): the
    contraction runs at the policy's compute dtype with f32 accumulation
    (`ops/precision.pdot`)."""
    from dislib_tpu.ops import precision as px
    if ta:
        a = a.T
    if tb:
        b = b.T
    out = px.pdot(a, b, policy if policy is not None else px.FLOAT32)
    return lax.with_sharding_constraint(out, _mesh.data_sharding())


def _instr_ew2(static, a, b):
    op, a_shape, b_shape, out_shape = static
    return _ew_array_body(a, b, a_shape, b_shape, out_shape, op)


def _instr_ew1(static, a, scalar):
    op, shape = static
    return _ew_scalar_body(a, scalar, shape, op)


def _instr_transpose(static, a):
    del static
    return lax.with_sharding_constraint(a.T, _mesh.data_sharding())


def _instr_slice(static, a):
    r0, r1, rs, c0, c1, cs, out_shape, out_pshape = static
    del out_shape
    return _place_region(a[r0:r1:rs, c0:c1:cs], out_pshape)


def _instr_reduce(static, a):
    kind, axis, in_shape, out_shape, out_pshape = static
    red = _reduce_body(a, in_shape, kind, axis)
    return _place_region(red[: out_shape[0], : out_shape[1]], out_pshape)


def _instr_matmul(static, a, b):
    ta, tb, policy_name = static
    from dislib_tpu.ops import precision as px
    inner_a = a.shape[0] if ta else a.shape[1]
    inner_b = b.shape[1] if tb else b.shape[0]
    pad_to = max(inner_a, inner_b)
    if inner_a < pad_to:                 # quantum mismatch: grow the pad
        grow = pad_to - inner_a
        a = jnp.pad(a, ((0, grow), (0, 0)) if ta else ((0, 0), (0, grow)))
    if inner_b < pad_to:
        grow = pad_to - inner_b
        b = jnp.pad(b, ((0, 0), (0, grow)) if tb else ((0, grow), (0, 0)))
    return _matmul_body(a, b, ta, tb, px.of_name(policy_name))


def _instr_dist(static, a, b):
    a_shape, b_shape, out_pshape, prec = static
    (m, n), (k, _) = a_shape, b_shape
    d = _raw_distances_sq(a[:m, :n], b[:k, :n], precision=prec)
    return _place_region(d, out_pshape)


def _instr_rechunk(static, a):
    """Round-11 rechunk PR: re-quantize a backing to a new padded canvas
    INSIDE the fused program (crop/place, re-zero outside the logical
    region, constrain to the canonical sharding) — a mid-chain reshard
    costs zero extra dispatches.  Body shared with the eager collective
    paths in ``ops/rechunk.py``.  The trailing static element is the
    mesh token: it rides the program cache key so a mesh switch that
    happens to preserve every shape (e.g. (4,2) → (2,4), same quantum)
    retraces instead of replaying a constraint to the OLD mesh."""
    from dislib_tpu.ops.rechunk import requantize_body
    logical_shape, out_pshape, _mesh_token = static
    return requantize_body(a, logical_shape, out_pshape)


def _mesh_token():
    """Hashable identity of the current default mesh (shape + device
    ids) — the cache-key ingredient for mesh-sensitive fused statics."""
    mesh = _mesh.get_mesh()
    return (tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def _instr_kernel(static, *args):
    """Round-9 serving PR: an arbitrary traced kernel body as ONE fusion
    node.  ``static`` is ``(body, cfg)``: ``body`` a module-level pure
    function (hashable by identity, stable across calls — a lambda or
    closure would defeat both the jit cache and the fusion-program
    dedup), ``cfg`` its hashable config tuple.  The body receives the
    PADDED operand arrays exactly as the graph stores them and must
    return an array of the declared padded output shape with the
    region outside the logical shape zeroed (the Array invariant) —
    estimator predict kernels already satisfy this, which is what lets
    a whole scaler → estimator → argmax pipeline linearize into one
    cached XLA program."""
    body, cfg = static
    return body(cfg, *args)


_INSTRS = {
    "ew2": _instr_ew2,
    "ew1": _instr_ew1,
    "transpose": _instr_transpose,
    "slice": _instr_slice,
    "reduce": _instr_reduce,
    "matmul": _instr_matmul,
    "dist": _instr_dist,
    "kernel": _instr_kernel,
    "rechunk": _instr_rechunk,
}


def fused_kernel(body, cfg, args, out_shape, dtype, out_pshape=None,
                 reg_shape=None, sparse=False):
    """Defer ``body(cfg, *operands)`` as a fusion-graph node and wrap it
    as an :class:`Array` — the estimator-predict entry into the dispatch
    fusion layer (round-9 serving PR).

    ``body`` must be a module-level traced function taking its hashable
    ``cfg`` tuple first, then one padded device array per entry of
    ``args`` (each an :class:`Array`, a deferred node via
    ``arr._node()``, or a concrete ``jax.Array``/ndarray leaf such as
    model parameters).  It must return the padded ``out_pshape`` result,
    zero outside ``out_shape``.  Under ``DSLIB_EAGER=1`` the node is
    forced immediately — the same single-instruction program runs as its
    own dispatch, preserving per-op debugging semantics."""
    if out_pshape is None:
        out_pshape = _padded_shape(out_shape, _mesh.pad_quantum())
    ops = tuple(a._node() if isinstance(a, Array) else a for a in args)
    expr = _LazyExpr("kernel", (body, tuple(cfg)), ops,
                     tuple(out_pshape), dtype)
    arr = _lazy_array(expr, out_shape, reg_shape, sparse)
    if _eager_mode():
        arr.force()
    return arr


@partial(_pjit, static_argnames=("program",), name="fused_chain")
@precise
def _exec_program(program, *operands):
    """Interpret one linearized chain while tracing — the whole program
    compiles (and caches) as ONE XLA executable keyed on (program,
    operand shapes/dtypes).

    Numerics vs the eager path: instruction bodies are shared verbatim,
    so every individual op rounds identically.  The ONE divergence XLA
    is permitted is excess-precision contraction WITHIN the fused
    program (a multiply feeding an add on the same element may become a
    single FMA — ≤ 1 ulp, and strictly more accurate).  Neither
    `optimization_barrier` nor an f32→f32 `reduce_precision` stops the
    backend's fp-contract inside one fused kernel (measured on XLA:CPU,
    jaxlib 0.4.36), and the global `--xla_allow_excess_precision=false`
    escape would mutate user-scope flags — so the contract is: bit-equal
    except mul→add contraction, bounded by 1 ulp
    (`tests/test_fusion.py::test_fma_contraction_is_the_only_divergence`)."""
    *instrs, shared_out = program
    vals = []
    for op, static, srcs in instrs:
        args = [operands[i] if kind == 0 else vals[i] for kind, i in srcs]
        vals.append(_INSTRS[op](static, *args))
    # root first, then each shared interior node (cached by the caller so
    # other fan-out consumers load it as a leaf instead of re-running it)
    return (vals[-1],) + tuple(vals[i] for i in shared_out)


def _unique_ops(expr: _LazyExpr) -> int:
    """Exact deferred-node count of a DAG (``n_ops`` overcounts shared
    subexpressions — exponentially so for diamond towers)."""
    seen, stack = set(), [expr]
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.extend(a for a in node.args if isinstance(a, _LazyExpr))
    return len(seen)


def _lazy_array(expr, shape, reg_shape, sparse):
    """Wrap a deferred node; force automatically past the fusion cap.
    ``n_ops`` is a cheap upper bound — only when it crosses the cap is
    the exact (deduped) count walked, so shared-subexpression DAGs are
    not forced early by the overcount."""
    arr = Array(expr, shape, reg_shape=reg_shape, sparse=sparse)
    if expr.n_ops >= _fusion_cap() and _unique_ops(expr) >= _fusion_cap():
        arr.force()
    return arr


def _ew_dtype(op, da, db):
    """Result dtype of a deferred binary op (metadata only — the traced
    body performs the real promotion; this mirrors it)."""
    dt = jnp.promote_types(da, db)
    # true division / exp / sqrt of integer operands float their result
    if op in ("div", "rdiv", "exp_", "sqrt_") \
            and jnp.issubdtype(dt, jnp.integer):
        dt = jnp.dtype(jnp.float64 if jax.config.jax_enable_x64
                       else jnp.float32)
    return dt


def _reduce_dtype(kind, dtype):
    if kind in ("mean", "norm"):
        return jnp.promote_types(dtype, jnp.float32)
    return jnp.dtype(dtype)


def _array_distances(a: "Array", b: "Array", precision=None) -> "Array":
    """ds-array pairwise squared distances — a fusable graph node (or one
    eager kernel under DSLIB_EAGER); see `ops.base.distances_sq`."""
    if a.shape[1] != b.shape[1]:
        raise ValueError(f"distances_sq: feature dims differ "
                         f"({a.shape[1]} vs {b.shape[1]})")
    out_shape = (a.shape[0], b.shape[0])
    out_pshape = _padded_shape(out_shape, _mesh.pad_quantum())
    dtype = jnp.promote_types(a.dtype, b.dtype)
    if _eager_mode():
        data = _distances_op(a._data, b._data, a._shape, b._shape,
                             out_pshape, precision)
        return Array(data, out_shape, None, False)
    expr = _LazyExpr("dist", (a._shape, b._shape, out_pshape, precision),
                     (a._node(), b._node()), out_pshape, dtype)
    return _lazy_array(expr, out_shape, None, False)


@partial(_pjit, static_argnames=("a_shape", "b_shape", "out_pshape", "prec"),
         name="distances")
@precise
def _distances_op(a, b, a_shape, b_shape, out_pshape, prec):
    return _instr_dist((a_shape, b_shape, out_pshape, prec), a, b)


# ---------------------------------------------------------------------------
# the Array
# ---------------------------------------------------------------------------

class Array:
    """A 2-D matrix sharded over the device mesh.

    Parameters are internal; users build Arrays with :func:`array`,
    :func:`random_array`, the loaders in :mod:`dislib_tpu.data.io`, or as
    results of dislib_tpu operations.
    """

    def __init__(self, data, shape, reg_shape=None, sparse=False,
                 _skip_zero_check=True):
        # data: padded, zero-outside-logical — either a concrete jax.Array
        # or a deferred _LazyExpr (the fusion layer)
        if isinstance(data, _LazyExpr):
            data.refs += 1              # this wrapper is a consumer too
            self._lazy = data
            self._concrete = None
        else:
            self._concrete = data
            self._lazy = None
        self._shape = (int(shape[0]), int(shape[1]))
        if reg_shape is None:
            reg_shape = _default_block_size(self._shape, None)
        self._reg_shape = (int(reg_shape[0]), int(reg_shape[1]))
        self._sparse = bool(sparse)

    # -- fusion plumbing -----------------------------------------------------

    @property
    def _data(self):
        """The padded device backing.  Reading it is a FORCE point: any
        deferred op chain compiles and runs as one program first."""
        if self._concrete is None:
            expr = self._lazy
            if expr.value is not None:   # prefix already materialised by
                self._concrete = expr.value  # another consumer's force
            else:
                with _span("dslib.array.force"):
                    program, leaves, shared = _linearize(expr)
                    root, *shared_vals = _exec_program(program, *leaves)
                for node, val in zip(shared, shared_vals):
                    node.value = val
                    node.args = ()      # edges are dead once cached —
                expr.value = root       # don't pin the leaf buffers
                expr.args = ()
                self._concrete = root
            self._lazy = None
        return self._concrete

    def _node(self):
        """This array as a fusion-graph operand: its deferred expression
        if one is pending, else the concrete backing as a leaf."""
        return self._lazy if self._lazy is not None else self._concrete

    @property
    def _pshape(self) -> tuple[int, int]:
        """Padded backing shape — available without forcing."""
        if self._lazy is not None:
            return self._lazy.pshape
        return tuple(self._concrete.shape)

    @property
    def is_lazy(self) -> bool:
        """True while this array is an unforced deferred op chain."""
        return self._concrete is None

    def force(self) -> "Array":
        """Materialise any deferred op chain as ONE compiled dispatch and
        return self.  A no-op on an already-concrete array.  `collect()`,
        `float()`, snapshot fetches, and every internal `_data` read
        force implicitly; call this to place the sync point explicitly."""
        self._data  # noqa: B018 — property access runs the fused program
        return self

    # -- construction helpers ------------------------------------------------

    @classmethod
    def _from_logical(cls, data: jax.Array, reg_shape=None, sparse=False) -> "Array":
        """Wrap a logically-shaped (unpadded) device/host array."""
        shape = data.shape
        q = _mesh.pad_quantum()
        pshape = _padded_shape(shape, q)
        if tuple(shape) != pshape:
            if isinstance(data, np.ndarray):
                # host data pads on the HOST, so each device receives only
                # its own shard below — never the whole operand (and its
                # padded copy) staged on the default device first
                data = np.pad(data, [(0, p - s)
                                     for p, s in zip(pshape, shape)])
            else:
                data = _place(data, pshape, tuple(shape))
        data = jax.device_put(data, _mesh.data_sharding())
        return cls(data, shape, reg_shape=reg_shape, sparse=sparse)

    # -- metadata ------------------------------------------------------------

    @property
    def shape(self) -> tuple[int, int]:
        return self._shape

    @property
    def dtype(self):
        if self._lazy is not None:       # metadata — must not force
            return self._lazy.dtype
        return self._concrete.dtype

    @property
    def _n_blocks(self) -> tuple[int, int]:
        return (-(-self._shape[0] // self._reg_shape[0]),
                -(-self._shape[1] // self._reg_shape[1]))

    @property
    def block_size(self) -> tuple[int, int]:
        return self._reg_shape

    def __repr__(self):
        return (f"dslib.Array(shape={self._shape}, block_size={self._reg_shape}, "
                f"dtype={self.dtype}, sparse={self._sparse})")

    # -- sync points ---------------------------------------------------------

    def collect(self) -> np.ndarray:
        """Materialise on host — the analog of compss_wait_on + merge (SURVEY §4.6).

        Multi-host jobs: a row-sharded global array spans non-addressable
        devices, so the gather is a `process_allgather` over DCN (every
        host ends with the full logical array, the reference's
        gather-to-master contract)."""
        data = self._data               # the force, outside the read
        with _host_read():
            if not data.is_fully_addressable:
                from jax.experimental import multihost_utils
                out = np.asarray(multihost_utils.process_allgather(
                    data, tiled=True))
            else:
                out = np.asarray(jax.device_get(data))
        out = out[: self._shape[0], : self._shape[1]]
        if self._sparse:
            import scipy.sparse as sp
            return sp.csr_matrix(out)
        return out

    def block_until_ready(self) -> "Array":
        data = self._data               # the force, with a span of its own
        with _span("dslib.array.wait"):
            data.block_until_ready()
        return self

    def __float__(self) -> float:
        """Host scalar of a (1, 1) array — a force point (the deferred
        chain runs as one program first)."""
        if self._shape != (1, 1):
            raise TypeError(
                f"only a (1, 1) ds-array converts to float, got {self._shape}")
        # read the backing directly: collect() of a sparse-flagged array
        # wraps the scalar in a csr_matrix, which float() rejects
        corner = self._data[0:1, 0:1]
        with _host_read():
            return float(np.asarray(jax.device_get(corner)).reshape(()))

    # -- layout --------------------------------------------------------------

    def rechunk(self, block_size) -> "Array":
        """Change the block-size hint — and, when the backing was laid out
        under a DIFFERENT mesh quantum (elastic mesh change), reshard it
        on-device for the current mesh via :func:`rechunk` (round-11
        collective-rechunk PR).  On an already-canonical backing this
        stays metadata-only, the reference's data-movement rechunk
        (SURVEY §3.1) collapsed to a no-op on a global jax.Array."""
        return rechunk(self, block_size)

    def astype(self, dtype) -> "Array":
        return Array(self._data.astype(dtype), self._shape, self._reg_shape, self._sparse)

    def copy(self) -> "Array":
        return Array(self._data, self._shape, self._reg_shape, self._sparse)

    # -- transpose -----------------------------------------------------------

    def transpose(self) -> "Array":
        shape = (self._shape[1], self._shape[0])
        reg = (self._reg_shape[1], self._reg_shape[0])
        if _eager_mode():
            data = _transpose_op(self._data, self._shape)
            return Array._from_logical_padded(data, shape, reg, self._sparse)
        pshape = self._pshape
        expr = _LazyExpr("transpose", (self._shape,), (self._node(),),
                         (pshape[1], pshape[0]), self.dtype)
        return _lazy_array(expr, shape, reg, self._sparse)

    @property
    def T(self) -> "Array":
        return self.transpose()

    @classmethod
    def _from_logical_padded(cls, padded_data, shape, reg_shape=None, sparse=False):
        """Wrap data already padded+zeroed for `shape`."""
        padded_data = jax.device_put(padded_data, _mesh.data_sharding())
        return cls(padded_data, shape, reg_shape=reg_shape, sparse=sparse)

    # -- elementwise ---------------------------------------------------------

    def _coerce(self, other):
        if isinstance(other, Array):
            if other._shape != self._shape:
                # allow (1, n) / (n, 1) broadcasting
                if not _broadcastable(other._shape, self._shape):
                    raise ValueError(f"shape mismatch {self._shape} vs {other._shape}")
            return other
        if isinstance(other, Number):
            return other
        return NotImplemented

    def _ew(self, other, op):
        other = self._coerce(other)
        if other is NotImplemented:
            return NotImplemented
        if isinstance(other, Array):
            out_shape = _broadcast_shape(self._shape, other._shape)
            sparse = self._sparse and other._sparse
            if _eager_mode():
                data = _ew_array_op(self._data, other._data, self._shape,
                                    other._shape, out_shape, op)
                return Array(data, out_shape, self._reg_shape, sparse)
            pa, pb = self._pshape, other._pshape
            expr = _LazyExpr(
                "ew2", (op, self._shape, other._shape, out_shape),
                (self._node(), other._node()),
                (max(pa[0], pb[0]), max(pa[1], pb[1])),
                _ew_dtype(op, self.dtype, other.dtype))
            return _lazy_array(expr, out_shape, self._reg_shape, sparse)
        scalar = float(other) if not isinstance(other, bool) else other
        # scalar mul/div/pow and the zero-preserving unaries map zeros to
        # zeros; add/sub of a nonzero scalar destroys sparsity (the flag
        # is metadata — data is dense).  exp is NOT zero-preserving
        # (exp(0)=1 densifies) — its dummy 0.0 operand must not slip it
        # through the ==0.0 clause.
        if op == "exp_":
            preserves = False
        else:
            preserves = op in ("mul", "div", "pow", "abs_", "sqrt_") \
                or float(other) == 0.0
        sparse = self._sparse and preserves
        if _eager_mode():
            data = _ew_scalar_op(self._data, scalar, self._shape, op)
            return Array(data, self._shape, self._reg_shape, sparse)
        # the scalar rides as a traced leaf (pre-rounded to this array's
        # dtype, as the eager kernel does) so new values never retrace;
        # the metadata dtype mirrors the body's promotion on SAME-dtype
        # operands (int/scalar true-division still floats, e.g.)
        leaf = np.asarray(scalar, np.dtype(self.dtype))
        expr = _LazyExpr("ew1", (op, self._shape),
                         (self._node(), leaf), self._pshape,
                         _ew_dtype(op, self.dtype, self.dtype))
        return _lazy_array(expr, self._shape, self._reg_shape, sparse)

    def __add__(self, o):  return self._ew(o, "add")
    def __radd__(self, o): return self._ew(o, "add")
    def __sub__(self, o):  return self._ew(o, "sub")
    def __rsub__(self, o): return self._ew(o, "rsub")
    def __mul__(self, o):  return self._ew(o, "mul")
    def __rmul__(self, o): return self._ew(o, "mul")
    def __truediv__(self, o):  return self._ew(o, "div")
    def __rtruediv__(self, o): return self._ew(o, "rdiv")
    def __pow__(self, o):  return self._ew(o, "pow")
    def __neg__(self):     return self._ew(-1.0, "mul")

    def __abs__(self):
        return self._ew(0.0, "abs_")

    def sqrt(self) -> "Array":
        return self._ew(0.0, "sqrt_")

    def exp(self) -> "Array":
        return self._ew(0.0, "exp_")

    # -- matmul --------------------------------------------------------------

    def __matmul__(self, other):
        from dislib_tpu.math import matmul
        return matmul(self, other)

    # -- reductions ----------------------------------------------------------

    def _reduce(self, kind: str, axis=0):
        if axis not in (0, 1, None):
            raise ValueError("axis must be 0, 1 or None")
        if axis is None:
            shape = (1, 1)
        elif axis == 0:
            shape = (1, self._shape[1])
        else:
            shape = (self._shape[0], 1)
        if _eager_mode():
            data = _reduce_op(self._data, self._shape, kind, axis)
            return Array._from_logical_padded(_repad(data, shape), shape,
                                              None, False)
        out_pshape = _padded_shape(shape, _mesh.pad_quantum())
        expr = _LazyExpr("reduce", (kind, axis, self._shape, shape,
                                    out_pshape),
                         (self._node(),), out_pshape,
                         _reduce_dtype(kind, self.dtype))
        return _lazy_array(expr, shape, None, False)

    def sum(self, axis=0):  return self._reduce("sum", axis)
    def mean(self, axis=0): return self._reduce("mean", axis)
    def min(self, axis=0):  return self._reduce("min", axis)
    def max(self, axis=0):  return self._reduce("max", axis)

    def norm(self, axis=0):
        return self._reduce("norm", axis)

    # -- indexing ------------------------------------------------------------

    def __getitem__(self, key):
        rows, cols = _split_key(key)
        r_idx, r_len = _normalize_index(rows, self._shape[0])
        c_idx, c_len = _normalize_index(cols, self._shape[1])
        new_shape = (r_len, c_len)
        if not _eager_mode() and isinstance(r_idx, slice) \
                and isinstance(c_idx, slice):
            # basic (int/slice) indexing stays on the fusion graph; fancy
            # indexing below forces — its gather shapes are data-sized
            out_pshape = _padded_shape(new_shape, _mesh.pad_quantum())
            expr = _LazyExpr(
                "slice", (r_idx.start, r_idx.stop, r_idx.step,
                          c_idx.start, c_idx.stop, c_idx.step,
                          new_shape, out_pshape),
                (self._node(),), out_pshape, self.dtype)
            return _lazy_array(expr, new_shape, None, self._sparse)
        data = _gather_op(self._data, r_idx, c_idx)
        return Array._from_logical_padded(_repad(data, new_shape), new_shape,
                                          None, self._sparse)

    # -- iteration over logical blocks (parity: Array._iterator) -------------

    def iterator(self, axis=0):
        """Yield row-block (axis=0) or col-block (axis=1) sub-arrays, one per
        `block_size` stripe — reference `Array._iterator` (SURVEY §3.1).

        Stripes are cheap contiguous slices of the padded backing (lax.slice
        + repad), not general gathers — each yield costs one slice op."""
        n = self._shape[axis]
        step = self._reg_shape[axis]
        m, c = self._shape
        for start in range(0, n, step):
            stop = min(start + step, n)
            if axis == 0:
                logical = self._data[start:stop, :c]
                shape = (stop - start, c)
            else:
                logical = self._data[:m, start:stop]
                shape = (m, stop - start)
            yield Array._from_logical_padded(_repad(logical, shape), shape,
                                             None, self._sparse)


def _broadcastable(a, b):
    return all(x == y or x == 1 or y == 1 for x, y in zip(a, b))


def _broadcast_shape(a, b):
    return tuple(max(x, y) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# op bodies + jitted kernels (module-level so jit caches by shape).  Each
# body is shared VERBATIM by its eager kernel and the fused-program
# instruction, so DSLIB_EAGER=1 results bit-match the fused path.
# ---------------------------------------------------------------------------

_BINOPS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "rsub": lambda a, b: b - a,
    "mul": lambda a, b: a * b,
    "div": lambda a, b: a / b,
    "rdiv": lambda a, b: b / a,
    "pow": lambda a, b: a ** b,
    "exp_": lambda a, b: jnp.exp(a),
    "abs_": lambda a, b: jnp.abs(a),
    "sqrt_": lambda a, b: jnp.sqrt(a),
}


def _ew_array_body(a, b, a_shape, b_shape, out_shape, op):
    # crop each operand to its logical region, broadcast, then re-pad. The
    # crop/pad pair fuses to a masked op under XLA; it keeps broadcasting
    # semantics exact when a (1, n) operand's padded rows would otherwise
    # collide with the other operand's rows.
    av = a[: a_shape[0], : a_shape[1]]
    bv = b[: b_shape[0], : b_shape[1]]
    out = _BINOPS[op](av, bv)
    res = jnp.zeros(_padded_shape_like(a, b, out_shape), out.dtype)
    res = lax.dynamic_update_slice(res, out, (0, 0))
    return res


@partial(_pjit, static_argnames=("a_shape", "b_shape", "out_shape", "op"),
         name="ew_array")
def _ew_array_op(a, b, a_shape, b_shape, out_shape, op):
    return _ew_array_body(a, b, a_shape, b_shape, out_shape, op)


def _padded_shape_like(a, b, out_shape):
    # the padded canvas big enough for out_shape under the current quantum
    q_r = max(a.shape[0], b.shape[0])
    q_c = max(a.shape[1], b.shape[1])
    # out_shape is the broadcast of the logical shapes; the matching padded
    # canvas is the max of operand canvases in each dim.
    return (q_r, q_c)


def _ew_scalar_body(a, scalar, shape, op):
    out = _BINOPS[op](a, jnp.asarray(scalar, a.dtype))
    return _zero_pad(out, shape)


@partial(_pjit, static_argnames=("shape", "op"), name="ew_scalar")
def _ew_scalar_op(a, scalar, shape, op):
    return _ew_scalar_body(a, scalar, shape, op)


@partial(_pjit, static_argnames=("shape",), name="transpose")
def _transpose_op(a, shape):
    return a.T


def _reduce_body(a, shape, kind, axis):
    mask = _pad_mask(a.shape, shape)
    if kind in ("sum", "norm", "mean"):
        x = jnp.where(mask, a, 0)
        if kind == "norm":
            x = x * x
        red = jnp.sum(x, axis=axis, keepdims=True) if axis is not None else \
            jnp.sum(x, keepdims=True).reshape(1, 1)
        if kind == "mean":
            n = shape[axis] if axis is not None else shape[0] * shape[1]
            red = red / n
        if kind == "norm":
            red = jnp.sqrt(red)
    else:
        fill = jnp.asarray(jnp.inf if kind == "min" else -jnp.inf, a.dtype)
        x = jnp.where(mask, a, fill)
        fn = jnp.min if kind == "min" else jnp.max
        red = fn(x, axis=axis, keepdims=True) if axis is not None else \
            fn(x, keepdims=True).reshape(1, 1)
    return red


@partial(_pjit, static_argnames=("shape", "kind", "axis"), name="reduce")
def _reduce_op(a, shape, kind, axis):
    return _reduce_body(a, shape, kind, axis)


def _repad(logical_data, shape):
    """Pad logical(-region) data out to the current quantum and zero-fill."""
    q = _mesh.pad_quantum()
    pshape = _padded_shape(shape, q)
    cropped = logical_data[: shape[0], : shape[1]]
    if cropped.shape == pshape:
        return jax.device_put(cropped, _mesh.data_sharding())
    out = _place(cropped, pshape, shape)
    return jax.device_put(out, _mesh.data_sharding())


def _gather_op(a, r_idx, c_idx):
    if isinstance(r_idx, slice) and isinstance(c_idx, slice):
        return a[r_idx, c_idx]
    if isinstance(r_idx, slice):
        return a[r_idx, :][:, c_idx]
    if isinstance(c_idx, slice):
        return a[r_idx, :][:, c_idx]
    return a[r_idx, :][:, c_idx]


def _split_key(key):
    if isinstance(key, tuple):
        if len(key) != 2:
            raise IndexError("ds-arrays are 2-D: index with at most two axes")
        return key
    return key, slice(None)


def _normalize_index(idx, dim):
    """Return (index object over the padded array, result length)."""
    if isinstance(idx, (int, np.integer)):
        i = int(idx)
        if i < 0:
            i += dim
        if not 0 <= i < dim:
            raise IndexError(f"index {idx} out of bounds for dim {dim}")
        return slice(i, i + 1), 1
    if isinstance(idx, slice):
        start, stop, step = idx.indices(dim)
        if step <= 0:
            raise IndexError("negative slice steps not supported")
        length = max(0, -(-(stop - start) // step))
        return slice(start, stop, step), length
    # fancy indexing with a list / ndarray of ints (or bools)
    arr = np.asarray(idx)
    if arr.dtype == bool:
        if arr.shape[0] != dim:
            raise IndexError("boolean index length mismatch")
        arr = np.nonzero(arr)[0]
    if arr.size and not np.issubdtype(arr.dtype, np.integer):
        # silent float→int truncation would index the wrong rows; an empty
        # selection (np.asarray([]) is float64) stays valid, as in NumPy
        raise IndexError(f"fancy index must be integer or boolean, got "
                         f"dtype {arr.dtype}")
    arr = arr.astype(np.int64)
    arr = np.where(arr < 0, arr + dim, arr)
    if arr.size and (arr.min() < 0 or arr.max() >= dim):
        raise IndexError("fancy index out of bounds")
    return arr, int(arr.shape[0])


# ---------------------------------------------------------------------------
# public constructors  (parity: dislib.data.array constructors, SURVEY §3.1)
# ---------------------------------------------------------------------------

def array(x, block_size=None, dtype=None) -> Array:
    """Build a ds-array from host data (ndarray, nested lists, or scipy sparse).

    ``dtype=None`` keeps the TPU-native float32 default but WARNS once when
    that silently narrows float64 input (the reference's blocks are NumPy
    float64 — a port should not change precision silently).  Pass an
    explicit ``dtype=`` to silence the warning; ``dtype=np.float64`` is
    honoured when JAX x64 mode is enabled (CPU rig) and raises a clear
    error otherwise."""
    import scipy.sparse as sp
    sparse = sp.issparse(x)
    if sparse:
        x = x.toarray()
    if not isinstance(x, jax.Array):
        x = np.asarray(x)
    if x.ndim == 1:
        x = x.reshape(1, -1)
    if x.ndim != 2:
        raise ValueError("ds-arrays are 2-dimensional")
    # one dtype policy for both inputs: a device array is coerced without
    # a host round-trip, and a host array STAYS on the host until
    # `_from_logical` hands its shards to their devices
    x = _coerce_dtype(x, dtype)
    if block_size is None:
        block_size = _default_block_size(x.shape, None)
    block_size = _check_block_size(x.shape, block_size)
    return Array._from_logical(x, reg_shape=block_size, sparse=sparse)


def _require_dtype_support(dtype):
    """Reject dtypes the backend would silently narrow (f64 without x64)."""
    if np.dtype(dtype) == np.float64 and not jax.config.jax_enable_x64:
        raise ValueError(
            "dtype=float64 requires JAX x64 mode (JAX_ENABLE_X64=1 or "
            "jax.config.update('jax_enable_x64', True)); the TPU-native "
            "default is float32")


def _coerce_dtype(x, dtype):
    """Apply the library dtype policy (see :func:`array`) — the ONE
    implementation, shared by the host (ndarray) and device (jax.Array)
    input paths."""
    if dtype is not None:
        _require_dtype_support(dtype)
        dtype = np.dtype(dtype)
        return x if x.dtype == dtype else x.astype(dtype)
    if x.dtype == np.float64:
        _warn_f64_narrowing()
        return x.astype(np.float32)
    return x


def _warn_f64_narrowing():
    import warnings
    warnings.warn(
        "ds.array received float64 data and is narrowing it to float32 "
        "(the TPU-native default). Pass dtype=np.float32 to silence, or "
        "dtype=np.float64 with JAX x64 mode to keep full precision.",
        UserWarning, stacklevel=4)


def _check_block_size(shape, block_size):
    """Validate and return the effective block size: oversized blocks clamp
    to the logical shape (physical layout is mesh-determined anyway — the
    block size only drives `iterator` stripes and `_reg_shape` metadata)."""
    br, bc = block_size
    if br <= 0 or bc <= 0:
        raise ValueError("block_size entries must be positive")
    return (min(br, shape[0]) if shape[0] > 0 else br,
            min(bc, shape[1]) if shape[1] > 0 else bc)


def random_array(shape, block_size=None, random_state=None,
                 dtype=jnp.float32) -> Array:
    """Uniform [0, 1) ds-array; deterministic per seed, seeded per the whole
    array (the reference seeds per block — an implementation artifact of
    task-parallel generation, not an API contract)."""
    _require_dtype_support(dtype)
    seed = _seed_from(random_state)
    q = _mesh.pad_quantum()
    pshape = _padded_shape(shape, q)
    data = _random_uniform(jax.random.PRNGKey(seed), pshape,
                           tuple(int(s) for s in shape), np.dtype(dtype).name,
                           _mesh.data_sharding())
    return Array(_canonical(data), shape, reg_shape=block_size)


# Device-generated constructors take the target sharding as a jit STATIC
# and constrain their result to it, so every device produces only its own
# shard (the mesh rides the cache key: a re-init retraces, never replays a
# stale layout) — the whole operand never sits on the default device.

def _canonical(data):
    """Re-label an already canonically laid-out result with THE canonical
    sharding object: jit hands back an equivalent but normalized spec, and
    `ensure_canonical` compares shardings by equality.  Moves no data."""
    return jax.device_put(data, _mesh.data_sharding())


@partial(_pjit, static_argnames=("pshape", "shape", "dtype", "sharding"),
         name="random_uniform")
def _random_uniform(key, pshape, shape, dtype, sharding):
    vals = jax.random.uniform(key, pshape, dtype=dtype)
    return lax.with_sharding_constraint(_zero_pad(vals, shape), sharding)


def _seed_from(random_state):
    if random_state is None:
        return np.random.randint(0, 2**31 - 1)
    if isinstance(random_state, (int, np.integer)):
        return int(random_state)
    if isinstance(random_state, np.random.RandomState):
        return int(random_state.randint(0, 2**31 - 1))
    raise TypeError(f"bad random_state: {random_state!r}")


def zeros(shape, block_size=None, dtype=jnp.float32) -> Array:
    """All-zeros ds-array (reference: ds.zeros)."""
    return full(shape, 0.0, block_size, dtype)


def full(shape, fill_value, block_size=None, dtype=jnp.float32) -> Array:
    """Constant-filled ds-array (reference: ds.full)."""
    q = _mesh.pad_quantum()
    pshape = _padded_shape(shape, q)
    data = _full_op(pshape, tuple(int(s) for s in shape), float(fill_value),
                    dtype, _mesh.data_sharding())
    return Array(_canonical(data), shape, reg_shape=block_size)


@partial(_pjit, static_argnames=("pshape", "shape", "dtype", "sharding"),
         name="full")
def _full_op(pshape, shape, fill_value, dtype, sharding):
    return lax.with_sharding_constraint(
        _zero_pad(jnp.full(pshape, fill_value, dtype), shape), sharding)


def ones(shape, block_size=None, dtype=jnp.float32) -> Array:
    """All-ones ds-array."""
    return full(shape, 1.0, block_size, dtype)


def identity(n, block_size=None, dtype=jnp.float32) -> Array:
    """n×n identity ds-array (reference: ds.identity)."""
    return eye(n, n, block_size, dtype)


def eye(n, m=None, block_size=None, dtype=jnp.float32) -> Array:
    """n×m eye ds-array (ones on the main diagonal; reference: ds.eye)."""
    m = n if m is None else m
    q = _mesh.pad_quantum()
    pshape = _padded_shape((n, m), q)
    data = _eye_op(pshape, (int(n), int(m)), dtype, _mesh.data_sharding())
    return Array(_canonical(data), (n, m), reg_shape=block_size)


@partial(_pjit, static_argnames=("pshape", "shape", "dtype", "sharding"),
         name="eye")
def _eye_op(pshape, shape, dtype, sharding):
    r = lax.broadcasted_iota(jnp.int32, pshape, 0)
    c = lax.broadcasted_iota(jnp.int32, pshape, 1)
    return lax.with_sharding_constraint(
        jnp.where((r == c) & (r < min(shape)), jnp.ones((), dtype),
                  jnp.zeros((), dtype)), sharding)


def rechunk(x: Array, new_blocks=None, mesh=None, *, schedule="auto",
            panels=None, overlap=None, nse=None) -> Array:
    """Reshard a ds-array to a new block-size hint and/or mesh layout —
    ON DEVICE, via a collective schedule, never a host materialization
    (round-11 rechunk PR; arXiv:2112.01075 discipline).

    Block size and mesh shape are deployment details, not API
    constraints: any estimator accepts any block size produced by any
    other stage, and this is the one primitive that moves a backing
    between pad quanta / mesh layouts when they DO differ.

    - ``new_blocks``: new block-size hint (metadata; ``None`` keeps the
      current hint).
    - ``mesh``: target :class:`jax.sharding.Mesh`; ``None`` = the library
      default mesh.
    - ``schedule``: ``"auto"`` | ``"xla"`` | ``"panels"`` | ``"dcn"`` |
      ``"deviceput"`` (see :mod:`dislib_tpu.ops.rechunk`;
      ``DSLIB_RECHUNK_SCHEDULE`` overrides auto).  Under auto, an
      already-canonical backing is a metadata-only no-op; a same-layout
      quantum change joins the dispatch-fusion graph (a mid-chain
      rechunk costs ZERO extra dispatches); a mesh-layout change over
      the same devices runs the explicit masked-psum panel exchange in
      ONE jitted program with peak in-flight bytes ≈ |array| / panels
      — on a MULTI-HOST device grid auto picks ``"dcn"``, the
      hierarchical variant that coalesces each host's contribution into
      at most ``hosts - 1`` inter-host messages per step (round-19
      DCN data-plane PR; ``dcn_accounting`` itemizes the traffic) —
      and a device-set change uses the runtime's device-to-device copy.
    - ``panels``: in-flight panel count for the collective schedule
      (default ``DSLIB_RECHUNK_PANELS`` = 4).
    - ``overlap``: the panel exchange's loop schedule — ``"db"``
      (double-buffered, the default: the next panel's broadcast is
      issued under the current panel's assemble) or ``"seq"``
      (sequential-phase); ``None`` reads ``DSLIB_OVERLAP``.  Bit-equal
      either way; the double buffer costs one extra in-flight panel
      (round-13 overlap PR — see the user guide's "Overlap &
      scheduling").

    - ``nse`` (sparse inputs only): target per-shard stored-entry pad —
      the sparse nse-quantum knob (``None`` keeps the minimum quantum
      multiple covering the densest target shard).

    SPARSE inputs (:class:`~dislib_tpu.data.sparse.SparseArray`) route
    through the SAME schedule names over the row-panel-sharded buffers
    (round-14 sparse PR): ``"xla"`` = fused nse re-pad on the same
    device grid, ``"panels"`` = one masked-psum panel exchange for a
    mesh-layout change, ``"deviceput"`` = gather + runtime copy for a
    device-set change — never the host, never a densification.

    The result re-satisfies the pad-and-mask invariant by construction:
    pad slices are exactly zero after the reshard, whatever the input
    tail carried."""
    from dislib_tpu.ops import rechunk as _rc
    from dislib_tpu.data.sparse import SparseArray
    if isinstance(x, SparseArray):
        if panels is not None:
            raise ValueError(
                "panels= applies to the DENSE panel exchange only — the "
                "sparse exchange broadcasts one panel per source "
                "row-rank (fixed); nse= is the sparse memory knob")
        out = x.resharded(mesh, schedule=schedule, nse=nse, overlap=overlap)
        if new_blocks is not None:
            out._reg_shape = _check_block_size(x._shape, new_blocks)
        return out
    if not isinstance(x, Array):
        raise TypeError(
            f"ds.rechunk needs a ds-array or SparseArray, "
            f"got {type(x).__name__}")
    reg = _check_block_size(x._shape, new_blocks) if new_blocks is not None \
        else x._reg_shape
    target = mesh if mesh is not None else _mesh.get_mesh()
    out_pshape = _padded_shape(x._shape, _mesh.pad_quantum(target))
    if target is _mesh.get_mesh() and schedule in ("auto", "xla") \
            and not _eager_mode():
        canonical = _mesh.data_sharding(target)
        if schedule == "auto":
            # already canonical: the block hint is pure metadata — share
            # the backing (concrete) or the pending expression (lazy;
            # chains are built for the current mesh by construction).
            # (An EXPLICIT schedule="xla" still emits the requantize
            # node — the user-reachable "re-assert the pad-and-mask
            # invariant" op, pinned by the poisoned-pad regressions.)
            if not x.is_lazy and tuple(x._concrete.shape) == out_pshape \
                    and x._concrete.sharding == canonical:
                return Array(x._concrete, x._shape, reg, x._sparse)
            if x.is_lazy and tuple(x._lazy.pshape) == out_pshape:
                return Array(x._lazy, x._shape, reg, x._sparse)
        if x.is_lazy or getattr(x._concrete, "sharding", None) == canonical:
            # same-layout quantum change: a fusion-graph node — the
            # reshard rides the chain and costs no dispatch of its own
            expr = _LazyExpr("rechunk",
                             (x._shape, tuple(out_pshape), _mesh_token()),
                             (x._node(),), out_pshape, x.dtype)
            return _lazy_array(expr, x._shape, reg, x._sparse)
    data, _sched = _rc.reshard(x._data, x._shape, target, schedule, panels,
                               overlap)
    return Array(data, x._shape, reg, x._sparse)


def ensure_canonical(x: Array) -> Array:
    """``x`` unchanged when its backing already matches the current
    mesh's pad quantum and layout; otherwise an on-device
    :func:`rechunk`.  The ingest guard for kernels with a hard layout
    requirement (shard_map row splits, SUMMA panels): estimators accept
    arrays built under ANY mesh and re-lay them out without a host hop."""
    pshape = _padded_shape(x._shape, _mesh.pad_quantum())
    if x.is_lazy:
        # a pending chain forces under the CURRENT mesh's constraints,
        # but its canvas shapes were fixed at build time — a chain built
        # before a quantum-changing mesh switch needs the fused
        # requantize node appended (review-found with a live repro:
        # old-quantum lazy operands crashed SUMMA's shard_map split)
        if tuple(x._lazy.pshape) == pshape:
            return x
        return rechunk(x)
    if tuple(x._concrete.shape) == pshape \
            and x._concrete.sharding == _mesh.data_sharding():
        return x
    return rechunk(x)


def _apply_axis_out_shape(out_spec, axis):
    """Logical 2-D result shape of an apply_along_axis (1-D maps get the
    reference's row/column-vector orientation)."""
    if out_spec.ndim == 1:
        return (1, int(out_spec.shape[0])) if axis == 0 \
            else (int(out_spec.shape[0]), 1)
    if out_spec.ndim == 2:
        return tuple(int(s) for s in out_spec.shape)
    raise ValueError(
        f"apply_along_axis: func produced a {out_spec.ndim}-D result; "
        "ds-arrays are 2-D")


def _apply_axis_kernel(cfg, xp):
    """``apply_along_axis`` as a fusion-node body (round-11 satellite):
    crop to the logical region, run the traced map, and place the result
    on its zero padded canvas — ONE dispatch riding whatever chain feeds
    it, instead of the old eager per-op path."""
    func, axis, in_shape, out_shape, out_pshape, fargs, fkwargs = cfg
    xv = xp[: in_shape[0], : in_shape[1]]
    out = jnp.apply_along_axis(func, axis, xv, *fargs, **dict(fkwargs))
    if out.ndim == 1:
        out = out.reshape(1, -1) if axis == 0 else out.reshape(-1, 1)
    canvas = jnp.zeros(out_pshape, out.dtype)
    return lax.dynamic_update_slice(canvas, out, (0, 0))


def apply_along_axis(func, axis, x: Array, *args, **kwargs) -> Array:
    """Apply ``func`` to 1-D slices of ``x`` along ``axis`` (reference:
    `dislib.data.array.apply_along_axis`, the generic user-level block map).

    Three tiers, fastest first (round-11 rechunk PR satellite):

    1. JAX-traceable ``func`` with hashable extra args: a fusion-graph
       node (:func:`fused_kernel`) — the whole map is ONE cached XLA
       dispatch (counter-pinned) and fuses into any surrounding op chain.
       Traceability is probed with ``jax.eval_shape`` (no execution, no
       transfer).
    2. Traceable but unhashable extras: the eager on-device
       ``jnp.apply_along_axis`` (still no host round trip).
    3. Not traceable at all: ``np.apply_along_axis`` on host — a
       device→host→device round trip that is orders of magnitude slower,
       so this tier WARNS with the original trace error."""
    logical_shape = x._shape
    spec = jax.ShapeDtypeStruct(logical_shape, x.dtype)
    try:
        out_spec = jax.eval_shape(
            lambda v: jnp.apply_along_axis(func, axis, v, *args, **kwargs),
            spec)
    except Exception as e:  # noqa: BLE001 — any trace failure → host tier
        import warnings
        warnings.warn(
            f"apply_along_axis: {getattr(func, '__name__', func)!r} is not "
            f"JAX-traceable ({type(e).__name__}: {e}); falling back to host "
            "NumPy (device->host->device round trip, far slower)",
            UserWarning, stacklevel=2)
        logical = x._data[: x._shape[0], : x._shape[1]]
        with _host_read():
            host = np.asarray(jax.device_get(logical))
        out = np.apply_along_axis(func, axis, host, *args, **kwargs)
        out = jnp.asarray(out)
        if out.ndim == 1:
            out = out.reshape(1, -1) if axis == 0 else out.reshape(-1, 1)
        return Array._from_logical(out, reg_shape=None)
    out_shape = _apply_axis_out_shape(out_spec, axis)
    cfg = (func, axis, logical_shape, out_shape,
           _padded_shape(out_shape, _mesh.pad_quantum()), tuple(args),
           tuple(sorted(kwargs.items())))
    try:
        stable = _stable_callable(func) and (hash(cfg) is not None)
    except TypeError:           # unhashable extras
        stable = False
    if not stable:
        # eager on-device tier: correct and host-free, but NOT entered
        # into the persistent fused-program cache — a fresh lambda per
        # call would pin a new executable forever (the fusion layer's
        # module-level-body contract; review-found)
        logical = x._data[: x._shape[0], : x._shape[1]]
        out = jnp.apply_along_axis(func, axis, logical, *args, **kwargs)
        if out.ndim == 1:
            out = out.reshape(1, -1) if axis == 0 else out.reshape(-1, 1)
        return Array._from_logical(out, reg_shape=None)
    return fused_kernel(_apply_axis_kernel, cfg, (x,), out_shape,
                        out_spec.dtype, out_pshape=cfg[4])


def _stable_callable(func) -> bool:
    """True when ``func`` is a module-level callable whose identity is
    stable across calls — the ``fused_kernel`` cache-key contract.  A
    per-call lambda/closure/partial gets a fresh identity every time and
    would grow the persistent executable cache without bound, so those
    route to the eager on-device tier instead."""
    import sys
    mod = getattr(func, "__module__", None)
    qual = getattr(func, "__qualname__", None)
    if not mod or not qual or "<" in qual:   # <lambda>, <locals>
        return False
    obj = sys.modules.get(mod)
    for part in qual.split("."):
        obj = getattr(obj, part, None)
        if obj is None:
            return False
    return obj is func


def concat_rows(arrays) -> Array:
    """Stack ds-arrays vertically (logical concatenation)."""
    arrays = list(arrays)
    if not arrays:
        raise ValueError("concat_rows needs at least one array")
    cols = {a.shape[1] for a in arrays}
    if len(cols) > 1:
        raise ValueError(f"concat_rows: column counts differ: {sorted(cols)}")
    datas = [a._data[: a._shape[0], : a._shape[1]] for a in arrays]
    out = jnp.concatenate(datas, axis=0)
    return Array._from_logical(out, reg_shape=arrays[0]._reg_shape)


def concat_cols(arrays) -> Array:
    """Concatenate ds-arrays along columns (block-grid hstack role)."""
    arrays = list(arrays)
    if not arrays:
        raise ValueError("concat_cols needs at least one array")
    rows = {a.shape[0] for a in arrays}
    if len(rows) > 1:
        raise ValueError(f"concat_cols: row counts differ: {sorted(rows)}")
    datas = [a._data[: a._shape[0], : a._shape[1]] for a in arrays]
    out = jnp.concatenate(datas, axis=1)
    return Array._from_logical(out, reg_shape=arrays[0]._reg_shape)
