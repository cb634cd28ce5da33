"""The plain reference for ALS on sparse ratings: every number from the
definitions, in plain ``jax.numpy`` at float32 'highest', nothing of the
program.

A half-step solves, for each segment (a user, or an item), the
weighted-lambda normal equations ``(sum_j o_j o_j^T + lambda n I) x =
sum_j r_j o_j`` over the segment's own entries, o_j the other side's
factor rows (Zhou et al. 2008).  Here the segments are sorted by their
number of entries and taken in blocks padded to the block's longest
(rounded up to a power of four), at most ``slots`` entries a block; a
block's Grams are one ``einsum`` over its entries, contracted
``contract`` entries at a time behind an
``optimization_barrier`` and the pieces added in compensated sums (one
'highest' product that contracts 10^5 same-signed terms reads 1.3e-5 low
on the v5e; PERF.md, section 6).  The systems are solved ``LANES`` at a
time, one system a lane: the textbook left-looking Cholesky, column by
column, then the forward and the backward substitution, each step plain
float32 products and sums across the systems.  The column order of the
entries is this module's own stable sort.

``fit`` runs whole iterations from a start V0: U from V, then V from U,
then the RMSE of the pair, ``iters`` times.

``precision`` is the control's handle: 'high' (three bf16 passes) runs
the Gram and moment products at that precision; 'bfloat16' rounds their
operands to bfloat16 and keeps the products and sums in float32.

The RMSE is the definition: the square root of the mean of (u . v - r)^2
over the ratings, a block of entries at a time, the blocks' sums added in
float64 on the host.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LANES = 1024            # systems one solve takes, one a lane


def item_major(rows, cols, vals, live):
    """``(rows, vals)`` of the first ``live`` entries sorted by column,
    stably (users ascend within an item); the entries past them after."""
    return _sort(rows, cols, vals, live)


@jax.jit
def _sort(rows, cols, vals, live):
    key = jnp.where(lax.iota(jnp.int32, cols.shape[0]) < live, cols,
                    jnp.iinfo(jnp.int32).max)
    _, r, v = lax.sort((key, rows, vals), num_keys=1, is_stable=True)
    return r, v


def blocks(counts, slots=1 << 21, most=LANES):
    """The blocking of every segment with an entry: ``[(ids, first,
    length, S), ...]``, host arrays, segments sorted by their number of
    entries and grouped under a common window S (a power of four, at
    least 16: few windows, so few programs to compile) of at most
    ``slots`` slots a block."""
    counts = np.asarray(counts, np.int64)
    first = np.cumsum(counts) - counts
    ids = np.flatnonzero(counts)
    ids = ids[np.argsort(counts[ids], kind="stable")]
    out, at = [], 0
    while at < len(ids):
        size = max(16, 4 ** int(np.ceil(np.log(max(counts[ids[at]], 1))
                                        / np.log(4) - 1e-9)))
        same = np.searchsorted(counts[ids], size, side="right")
        b = max(1, min(most, slots // size))
        mine = ids[at:min(at + b, same)]
        # every block of a window has b rows (one compile a window): the
        # rows past the segments are id -1, length 0
        pad = b - len(mine)
        out.append((np.concatenate([mine, np.full(pad, -1)]),
                    np.concatenate([first[mine], np.zeros(pad, np.int64)]),
                    np.concatenate([counts[mine], np.zeros(pad, np.int64)]),
                    size))
        at += len(mine)
    return out


@partial(jax.jit, static_argnames=("size", "contract", "precision"))
def _grams(stream_other, stream_vals, factor, first, length, size, contract,
           precision):
    """A block's products of [o . w, r . w] with themselves over each
    segment's window of ``size`` entries, as ``(g, count)``: g (f + 1,
    f + 1, LANES) a system a lane (the lanes past the block's segments
    zero), count the observed entries."""
    pos = lax.iota(jnp.int32, size)

    def window(a):
        return jax.vmap(lambda s: lax.dynamic_slice(a, (s,), (size,)))(first)

    r = window(stream_vals)
    w = ((pos[None, :] < length[:, None]) & (r != 0)).astype(jnp.float32)
    o = factor[window(stream_other)] * w[..., None]
    x = jnp.concatenate([o, (r * w)[..., None]], axis=-1)
    piece = min(size, contract)
    x = x.reshape(x.shape[0], size // piece, piece, x.shape[-1])
    if precision == "bfloat16":
        # operands rounded to bfloat16's eight bits, products and sums in
        # float32
        x = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        precision = "highest"
    g = lax.optimization_barrier(
        jnp.einsum("bpsf,bpsg->bpfg", x, x, precision=precision))

    def add(j, carry):
        total, lost = carry
        part = g[:, j] - lost
        grown = total + part
        return grown, (grown - total) - part

    zero = jnp.zeros_like(g[:, 0])
    total, lost = lax.fori_loop(0, size // piece, add, (zero, zero))
    spare = LANES - x.shape[0]
    g = jnp.pad(jnp.transpose(total - lost, (1, 2, 0)),
                ((0, 0), (0, 0), (0, spare)))
    return g, jnp.pad(jnp.sum(w, axis=1), (0, spare))


@jax.jit
def _solve(g, count, lambda_):
    """(f, LANES) solutions of ``(A + lambda max(n, 1) I) x = b``, A and b
    the parts of ``g``."""
    n_f = g.shape[0] - 1
    reg = lambda_ * jnp.maximum(count, 1.0)
    a = g[:n_f, :n_f] + jnp.eye(n_f, dtype=g.dtype)[..., None] * reg
    low = jnp.zeros_like(a)
    for j in range(n_f):                    # L column by column
        s = a[j:, j]
        if j:
            s = s - jnp.sum(low[j:, :j] * low[j, :j][None], axis=1)
        low = low.at[j:, j].set(s / jnp.sqrt(s[0]))
    rest, y = g[:n_f, n_f], []
    for k in range(n_f):                    # L y = b
        y.append(rest[k] / low[k, k])
        rest = rest - low[:, k] * y[k]
    x = jnp.stack(y)
    for i in reversed(range(n_f)):          # L^T x = y
        x = x.at[i].set((x[i] - jnp.sum(low[i + 1:, i] * x[i + 1:], axis=0))
                        / low[i, i])
    return x


def half_step(stream_other, stream_vals, counts, factor, lambda_,
              precision="highest", contract=2048, plan=None):
    """A device float32 (len(counts), f) array: each segment's factor
    against ``factor``, zero for a segment with no observed entry."""
    n_f = factor.shape[1]
    plan = blocks(counts) if plan is None else plan
    pad = max(s for *_, s in plan) if plan else 16
    other = jnp.concatenate([stream_other,
                             jnp.zeros((pad,), stream_other.dtype)])
    vals = jnp.concatenate([stream_vals, jnp.zeros((pad,), stream_vals.dtype)])
    factor = jnp.asarray(factor)
    ids, sols = [], []
    with jax.default_matmul_precision("highest"):
        for seg, first, length, size in plan:
            g, n = _grams(other, vals, factor, jnp.asarray(first, jnp.int32),
                          jnp.asarray(length, jnp.int32), size, contract,
                          precision)
            sols.append(_solve(g, n, float(lambda_))[:, :len(seg)])
            ids.append(np.where(seg >= 0, seg, len(counts)))
            if len(sols) % 32 == 0:     # a bounded queue: few g at once
                sols[-1].block_until_ready()
    out = jnp.zeros((len(counts) + 1, n_f), jnp.float32)
    if sols:
        out = out.at[jnp.asarray(np.concatenate(ids))].set(
            jnp.concatenate(sols, axis=1).T)
    return out[:-1]


def fit(users, items, start, lambda_, iters, entries, precision="highest"):
    """``(U, V, history)``: ``iters`` whole iterations from V = ``start``.
    ``users`` and ``items`` are each order's (other side's ids, ratings,
    counts); ``entries`` the user-major (rows, cols, vals) the RMSE reads.
    U and V are host float32, history the RMSE after each iteration."""
    uplan, iplan = blocks(users[2]), blocks(items[2])
    v, hist = jnp.asarray(start), []
    for _ in range(iters):
        u = half_step(*users, v, lambda_, precision, plan=uplan)
        v = half_step(*items, u, lambda_, precision, plan=iplan)
        hist.append(rmse(u, v, *entries))
    return np.asarray(u), np.asarray(v), np.asarray(hist)


@partial(jax.jit, static_argnames=("block",))
def _sq_errors(u, v, rows, cols, vals, block):
    """(blocks, 2): each block's sum of squared errors and count."""
    pad = -rows.shape[0] % block

    def cut(a):
        return jnp.pad(a, (0, pad)).reshape(-1, block)

    def one(_, blk):
        r, c, x = blk
        w = (x != 0).astype(jnp.float32)
        pred = jnp.sum(u[r] * v[c], axis=1)
        return None, jnp.stack([jnp.sum(w * (pred - x) ** 2), jnp.sum(w)])

    return lax.scan(one, None, (cut(rows), cut(cols), cut(vals)))[1]


def rmse(u, v, rows, cols, vals, block=1 << 20):
    """RMSE of ``u . v`` over the observed entries, the blocks' sums added
    in float64."""
    parts = np.asarray(_sq_errors(jnp.asarray(u), jnp.asarray(v), rows, cols,
                                  vals, block), np.float64)
    se, n = parts.sum(axis=0)
    return float(np.sqrt(se / max(n, 1.0)))


def rel_frobenius(a, b) -> float:
    """||a - b||_F over ||b||_F, in float64."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))
