"""SUMMA — explicitly-scheduled sharded GEMM over the 2-D device mesh.

Reference regime: "Large Scale Distributed Linear Algebra With TPUs"
(arXiv:2112.09017) runs its blocked matmul as SUMMA (Scalable Universal
Matrix Multiplication Algorithm): the (R x C) processor grid steps over
panels of the contraction dimension; at each step the column of the grid
owning the A panel broadcasts it along the mesh rows, the row owning the
B panel broadcasts it along the mesh columns, and every device
accumulates one local GEMM.  Peak per-device memory is the two resident
operand blocks plus the in-flight (panel-width) broadcast pairs plus the
output block — the panel loop is what keeps paper-scale operands (which
exist only sharded) from ever materialising per device.

`math.matmul` routes here when the mesh is genuinely 2-D (both axes > 1)
— the layout where an explicit panel schedule beats leaving the
partitioning to XLA SPMD (which on a 1-D mesh already emits the optimal
all-gather/psum form, so those shapes keep the fusion-graph dot).  The
broadcast is expressed as a masked ``lax.psum`` — the library's standard
provably-replicated collective idiom (``check_vma`` stays ON, the
SURVEY §6 race-detection row), one collective per panel per operand.

Panel schedule (round-13 overlap PR): the loop runs through
``ops/overlap.panel_pipeline``.  Under the default double-buffered
schedule panel t+1's broadcast pair is issued BEFORE panel t's local
GEMM consumes its buffers (prologue fetch, epilogue drain — still ONE
dispatch, the pipeline lives inside this jitted ``shard_map``), so the
latency-hiding scheduler can run the next collective under the current
MXU work; ``overlap="seq"`` restores the strict fetch-then-multiply
chain, and ``overlap="pallas"`` lowers the panel GEMM through
``ops/pallas_kernels``.  All schedules consume panels in identical
order, so ``db`` and ``seq`` are bit-equal (``tests/test_overlap``); the
double buffer's cost is ONE extra in-flight panel pair, never a copy of
an operand.

Mixed precision: the local panel GEMMs contract via the library precision
policy (``ops/precision.pdot``) — bf16-compute / f32-accumulate under the
bfloat16 policy, float32-faithful by default.  The accumulator is always
float32.  Zero padding is exact in both dtypes, so the padded contraction
equals the logical one with no masking.
"""

from __future__ import annotations

import math
from functools import partial

import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import jax

from dislib_tpu.ops import overlap as _ov
from dislib_tpu.ops import precision as px
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.utils.profiling import profiled_jit as _pjit


def summa_supported(mesh=None) -> bool:
    """True when the mesh shape makes the explicit SUMMA schedule the
    right algorithm: both mesh axes > 1 (a genuine 2-D processor grid).
    On 1-D meshes XLA's SPMD partitioner already emits the optimal
    collective schedule for a plain sharded dot."""
    r, c = _mesh.mesh_shape(mesh)
    return r > 1 and c > 1


def summa_steps(mesh=None) -> int:
    """Panel count of the SUMMA schedule on ``mesh``: lcm(rows, cols) —
    the panel width is the largest chunk that lives whole on exactly one
    cols-rank of A AND one rows-rank of B.  THE step-count formula of
    :func:`summa_matmul` (the kernel calls this too), exposed so
    per-panel consumers stay anchored to the kernel instead of
    re-deriving it."""
    r, c = _mesh.mesh_shape(mesh)
    return r * c // math.gcd(r, c)


@partial(_pjit, static_argnames=("mesh", "policy", "overlap"),
         name="summa_matmul")
@px.precise
def summa_matmul(ap, bp, mesh, policy, overlap="db"):
    """C = A @ B over canonically (rows, cols)-sharded padded operands.

    ``ap`` (M_pad, K_pad) and ``bp`` (K_pad, N_pad) must agree on K_pad
    (the caller repads a quantum mismatch) and carry the zero-pad
    invariant.  Returns the (M_pad, N_pad) product, float32
    (the policy's accumulation dtype), canonically sharded.

    ``overlap`` is the resolved panel schedule (``ops/overlap.resolve``
    — callers resolve so the ``DSLIB_OVERLAP`` env flip retraces as a
    static).

    ONE dispatch end to end under every schedule: the panel loop is a
    ``lax.fori_loop`` inside this single jitted program — counter-pinned
    by ``tests/test_precision.py``/``tests/test_overlap.py``.
    """
    nrows = mesh.shape[_mesh.ROWS]
    ncols = mesh.shape[_mesh.COLS]
    k_pad = ap.shape[1]
    if bp.shape[0] != k_pad:
        raise ValueError(
            f"summa: padded contraction dims differ ({k_pad} vs "
            f"{bp.shape[0]}) — repad before the kernel")
    # panel width: lcm(rows, cols) panels (K_pad is a pad_quantum
    # multiple, and pad_quantum = lcm(rows, cols), so this is exact)
    steps = summa_steps(mesh)
    kb = k_pad // steps

    def local(a, b):
        m_loc, ka = a.shape          # A block: (M/R, K/C)
        kb_loc, n_loc = b.shape      # B block: (K/R, N/C)
        my_r = lax.axis_index(_mesh.ROWS)
        my_c = lax.axis_index(_mesh.COLS)
        ac = px.to_compute(a, policy)
        bc = px.to_compute(b, policy)
        # the accumulator matches pdot's output dtype — f32 accumulation,
        # EXCEPT x64-mode f64 operands under the float32-floor policy,
        # which accumulate f64 (a f32 seed would break the fori_loop
        # carry; review-found with a live f64 repro)
        acc_dt = jnp.promote_types(px.accum_dtype(policy),
                                   jnp.promote_types(ac.dtype, bc.dtype))

        def fetch(t, prev):
            del prev                 # broadcast panels slice by step
            off = t * kb
            # broadcast the A panel from its owner cols-rank along 'cols'
            # (masked psum: non-owners contribute exact zeros); offsets
            # are computed identically on every rank, so the slice is
            # in-bounds everywhere and the mask picks the owner's panel
            with jax.named_scope("dslib.summa.fetch_a"):
                owner_c = off // ka
                a_pan = lax.dynamic_slice(ac, (0, off - owner_c * ka),
                                          (m_loc, kb))
                a_pan = jnp.where(my_c == owner_c, a_pan,
                                  jnp.zeros((), a_pan.dtype))
                a_pan = lax.psum(a_pan, _mesh.COLS)
            # broadcast the B panel from its owner rows-rank along 'rows'
            with jax.named_scope("dslib.summa.fetch_b"):
                owner_r = off // kb_loc
                b_pan = lax.dynamic_slice(bc, (off - owner_r * kb_loc, 0),
                                          (kb, n_loc))
                b_pan = jnp.where(my_r == owner_r, b_pan,
                                  jnp.zeros((), b_pan.dtype))
                b_pan = lax.psum(b_pan, _mesh.ROWS)
            return a_pan, b_pan

        def consume(t, acc, pan):
            a_pan, b_pan = pan
            with jax.named_scope("dslib.summa.gemm"):
                if overlap == "pallas":
                    from dislib_tpu.ops import pallas_kernels as _pk
                    return acc + _pk.panel_gemm(a_pan, b_pan, policy)
                return acc + px.pdot(a_pan, b_pan, policy)

        # seed the accumulator as device-varying up front so the fori_loop
        # carry's replication type is stable round over round (the ring
        # kernels' check_vma idiom)
        acc0 = lax.pcast(jnp.zeros((m_loc, n_loc), acc_dt),
                         (_mesh.ROWS, _mesh.COLS), to="varying")
        return _ov.panel_pipeline(steps, fetch(0, None), fetch, consume,
                                  acc0, _ov.overlapped(overlap))

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS, _mesh.COLS), P(_mesh.ROWS, _mesh.COLS)),
        out_specs=P(_mesh.ROWS, _mesh.COLS),
        check_vma=True,
    )(ap, bp)
