"""Ring-parallel pairwise-distance kNN over the mesh 'rows' axis.

The reference's NearestNeighbors is "all-pairs block product then pairwise
min-merge" (SURVEY.md §3.3 neighbors row) — every (query-block × fit-block)
pair becomes a task and the COMPSs runtime ships fitted blocks between
workers on demand.  The TPU-native scale-out form is a **ring**: query rows
stay resident on their shard, fitted shards rotate around the 'rows' axis
via `lax.ppermute` (one ICI hop per step), and each step folds the visiting
shard into a running top-k — the same schedule ring attention uses for long
sequences, applied to the library's long axis (rows).  After R steps every
query shard has seen every fitted row; peak memory per device is
O(mq_loc·(k + mf_loc)) and the fitted set never materialises on one chip.

Feature columns stay sharded over 'cols': each step's distance GEMM computes
a per-cols-shard partial and one `psum` over 'cols' completes it, which also
makes the result provably replicated across 'cols' (check_vma stays ON,
SURVEY §6 race-detection row).

Rotate/compute schedule (round-13 overlap PR): both ring kernels run their
step loop through ``ops/overlap.panel_pipeline``.  Under the default
double-buffered schedule the NEXT shard's ``ppermute`` hops are issued
before the current shard's distance fold consumes it, so the rotation
rides the ICI while the MXU folds — bit-equal to the sequential
rotate-then-compute schedule (``overlap="seq"``), still one jitted
program.  ``overlap="pallas"`` additionally lowers the fold's distance
kernel through ``ops/pallas_kernels``.  The ``overlap`` argument is a
jit static resolved by the CALLERS via ``ops/overlap.resolve`` (the
estimator tier pickers), so a ``DSLIB_OVERLAP`` flip retraces; both
kernels stay plain ``jax.jit`` (NOT profiled) because they are invoked
from inside other jitted programs — the dispatch-count boundary is their
outer kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dislib_tpu.ops import overlap as _ov
from dislib_tpu.ops.base import distances_sq, precise
from dislib_tpu.parallel import mesh as _mesh


def _rotate(perm, *arrays):
    """One ring hop of every carried array (the panel fetch)."""
    return tuple(lax.ppermute(a, _mesh.ROWS, perm) for a in arrays)


@partial(jax.jit, static_argnames=("mesh", "k", "m_fit", "overlap"))
@precise
def ring_kneighbors(qp, fp, mesh, k, m_fit, overlap="db"):
    """(distances², indices) of the k nearest fitted rows per query row.

    qp, fp: canonically sharded padded backings (rows over 'rows', features
    over 'cols').  Returns (d² (mq_pad, k), idx (mq_pad, k) int32), both
    row-sharded; invalid (padded) query rows carry garbage — callers crop.
    """
    nrows = mesh.shape[_mesh.ROWS]

    def local(q, f):
        mf_loc = f.shape[0]
        my = lax.axis_index(_mesh.ROWS)
        # full squared norms (features are col-sharded → psum over 'cols')
        q_sq = lax.psum(jnp.sum(q * q, axis=1), _mesh.COLS)
        f_sq0 = lax.psum(jnp.sum(f * f, axis=1), _mesh.COLS)
        ids0 = my * mf_loc + lax.broadcasted_iota(jnp.int32, (mf_loc,), 0)
        perm = [(i, (i + 1) % nrows) for i in range(nrows)]

        def fetch(t, prev):
            return _rotate(perm, *prev)     # one ICI hop per carried array

        pan0 = (f, f_sq0, ids0)

        def consume(t, carry, pan):
            best_d, best_i = carry
            f_cur, fsq_cur, ids_cur = pan
            if overlap == "pallas":
                from dislib_tpu.ops import pallas_kernels as _pk
                part = lax.psum(_pk.panel_gemm(q, f_cur.T), _mesh.COLS)
            else:
                part = lax.psum(q @ f_cur.T, _mesh.COLS)   # (mq_loc, mf_loc)
            d2 = q_sq[:, None] - 2.0 * part + fsq_cur[None, :]
            d2 = jnp.where(ids_cur[None, :] < m_fit, d2, jnp.inf)
            cand_d = jnp.concatenate([best_d, d2], axis=1)
            cand_i = jnp.concatenate(
                [best_i, jnp.broadcast_to(ids_cur[None, :],
                                          (q.shape[0], mf_loc))], axis=1)
            neg, pos = lax.top_k(-cand_d, k)
            best_d = -neg
            best_i = jnp.take_along_axis(cand_i, pos, axis=1)
            return best_d, best_i

        # the constant top-k seeds become row-varying on the first merge;
        # declaring it up front keeps check_vma provable
        acc0 = (lax.pcast(jnp.full((q.shape[0], k), jnp.inf, q.dtype),
                          (_mesh.ROWS,), to="varying"),
                lax.pcast(jnp.full((q.shape[0], k), -1, jnp.int32),
                          (_mesh.ROWS,), to="varying"))
        best_d, best_i = _ov.panel_pipeline(nrows, pan0, fetch, consume,
                                            acc0, _ov.overlapped(overlap))
        return jnp.maximum(best_d, 0.0), best_i

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS, _mesh.COLS), P(_mesh.ROWS, _mesh.COLS)),
        out_specs=(P(_mesh.ROWS, None), P(_mesh.ROWS, None)),
        check_vma=True,
    )(qp, fp)


# ---------------------------------------------------------------------------
# ring ε-neighborhood pass (DBSCAN / Daura scale-out)
# ---------------------------------------------------------------------------

# inner streaming tile edge within one ring step (per-device memory is
# O(tile²) for the distance piece; module-level so tests can shrink it)
RING_TILE = 2048


def ring_auto(flag, mesh, large):
    """Shared ring-routing policy: ``flag`` True forces the ring schedule,
    False forces it off, None auto-picks it when the mesh has >1 row shard
    and the caller's own size predicate ``large`` holds (each consumer owns
    its threshold semantics)."""
    if flag is not None:
        return bool(flag)
    return mesh.shape[_mesh.ROWS] > 1 and large


@partial(jax.jit, static_argnames=("mesh", "overlap"))
@precise
def ring_neigh_count_min(xp, eps2, vals, colmask, sentinel, mesh,
                         overlap="db"):
    """Per-row (ε-neighbor count, min over neighbor vals) of a row-sharded
    dataset against itself — `ops/tiled.neigh_count_min` distributed over
    the mesh 'rows' axis.

    Schedule: features are all-gathered over 'cols' once (contracting-dim
    gather, paid once per call), then each device's row shard stays resident
    while (shard, vals, colmask, ids) rotate around the 'rows' ring via
    ppermute; each visit streams in (tile × tile) distance pieces so peak
    memory per device is O(tile²).  adj(i,j) = (d²(i,j) ≤ eps2 ∨ i = j) ∧
    colmask_j, exactly the single-device contract.  Under the default
    double-buffered ``overlap`` the next hop's ppermutes are issued before
    the visiting shard's tile pass consumes it (see module docstring).

    xp (mp, np) canonically sharded; vals/colmask (mp,) row-sharded.
    Returns (counts int32 (mp,), mins (mp,) of vals.dtype), row-sharded.
    """
    nrows = mesh.shape[_mesh.ROWS]

    def local(x, v, cm):
        x = lax.all_gather(x, _mesh.COLS, axis=1, tiled=True)  # (m_loc, np)
        m_loc = x.shape[0]
        my = lax.axis_index(_mesh.ROWS)
        row_ids = my * m_loc + lax.broadcasted_iota(jnp.int32, (m_loc,), 0)
        perm = [(i, (i + 1) % nrows) for i in range(nrows)]
        # pad the shard to a tile multiple (shapes are static in-shard):
        # pad rows carry id −1 and colmask False, so they can never be
        # neighbors of anything; their own outputs are cropped below
        tile = min(RING_TILE, m_loc)
        nt = -(-m_loc // tile)
        m_t = nt * tile
        x = jnp.pad(x, ((0, m_t - m_loc), (0, 0)))
        row_ids = jnp.pad(row_ids, (0, m_t - m_loc), constant_values=-1)
        v = jnp.pad(v, (0, m_t - m_loc), constant_values=sentinel)
        cm = jnp.pad(cm, (0, m_t - m_loc), constant_values=False)

        def pair_pass(xc, idc, vc, cmc, cnt, mn):
            """Accumulate (cnt, mn) of local rows vs the visiting shard."""
            x_t = x.reshape(nt, tile, x.shape[1])
            r_t = row_ids.reshape(nt, tile)
            xc_t = xc.reshape(nt, tile, x.shape[1])
            id_t = idc.reshape(nt, tile)
            v_t = vc.reshape(nt, tile)
            cm_t = cmc.reshape(nt, tile)
            cnt_t = cnt.reshape(nt, tile)
            mn_t = mn.reshape(nt, tile)

            def row_body(_, rx):
                xrow, rid, c0, m0 = rx

                def col_body(acc, cx):
                    xcol, cid, vv, cmm = cx
                    d2 = distances_sq(xrow, xcol,
                                      use_pallas=(overlap == "pallas"))
                    adj = ((d2 <= eps2)
                           | (rid[:, None] == cid[None, :])) & cmm[None, :]
                    c_acc = acc[0] + jnp.sum(adj, axis=1)
                    m_acc = jnp.minimum(
                        acc[1], jnp.min(jnp.where(adj, vv[None, :], sentinel),
                                        axis=1))
                    return (c_acc, m_acc), None

                (c_out, m_out), _ = lax.scan(col_body, (c0, m0),
                                             (xc_t, id_t, v_t, cm_t))
                return None, (c_out, m_out)

            _, (cnt_o, mn_o) = lax.scan(row_body, None,
                                        (x_t, r_t, cnt_t, mn_t))
            return cnt_o.reshape(m_t), mn_o.reshape(m_t)

        def fetch(t, prev):
            return _rotate(perm, *prev)

        pan0 = (x, row_ids, v, cm)

        def consume(t, acc, pan):
            xc, idc, vc, cmc = pan
            cnt, mn = pair_pass(xc, idc, vc, cmc, acc[0], acc[1])
            return cnt, mn

        acc0 = (lax.pcast(jnp.zeros((m_t,), jnp.int32),
                          (_mesh.ROWS, _mesh.COLS), to="varying"),
                lax.pcast(jnp.full((m_t,), sentinel, v.dtype),
                          (_mesh.ROWS, _mesh.COLS), to="varying"))
        cnt, mn = _ov.panel_pipeline(nrows, pan0, fetch, consume, acc0,
                                     _ov.overlapped(overlap))
        cnt, mn = cnt[:m_loc], mn[:m_loc]      # crop the tile pad
        # every rank in a mesh row computes identical results from the
        # all-gathered features; pmax makes that invariance provable so
        # check_vma stays ON
        cnt = lax.pmax(cnt, _mesh.COLS)
        mn = lax.pmin(mn, _mesh.COLS)
        return cnt, mn

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(_mesh.ROWS, _mesh.COLS), P(_mesh.ROWS), P(_mesh.ROWS)),
        out_specs=(P(_mesh.ROWS), P(_mesh.ROWS)),
        check_vma=True,
    )(xp, vals, colmask)
