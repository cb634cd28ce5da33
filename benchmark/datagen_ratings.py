"""Netflix-Prize-shaped ratings from ``--seed``: the row-sorted entry stream
(users, each user's items, ratings) made on the device chunk by chunk.

- Ratings per user: the quantiles of a lognormal with the source's median
  and mean, cut to [1, the source's most], apportioned so that they sum
  exactly to the configuration's total, and dealt to the users in an
  order drawn from the seed (host: one number a user).  The counts, as a
  multiset, are the configuration's and not the seed's, as the source is
  one fixed matrix: every seed makes a matrix of the same shapes.
- Items per user: distinct, drawn by a heavy-tailed item popularity with
  capped systematic sampling.  Items are ranked by popularity; a rank's
  share of the ratings is ``p_r``, the mass of ``[r, r + 1)`` under the
  density ``exp(sigma * min(z(x), z_top))``, ``z(x) = Phi^-1(1 - x/n)``
  (a lognormal popularity with its top flattened, so that the most rated
  item is rated by about half the users, as in the source).  A user with
  k ratings takes the first ``R(k)`` ranks for certain and the others
  with chance ``c(k) p_r < 0.99``; these add up to k, and the user's
  offset in [0, 1) picks the ranks where the running sum of those
  chances passes offset, offset + 1, ...: every rank at most once.  A
  user's offset is a low-discrepancy number of the quantile it was dealt,
  so the number of ratings of each rank is the configuration's too.  The
  tail mass beyond a rank is closed-form (``_tail``), so a rank is found
  on the device by inverting it, with no search.  Ranks map to item ids
  through a permutation drawn from the seed.
- Ratings: ``clip(round(mu + b_u + b_i + p_u . q_i + e), 1, 5)`` of a
  planted model of rank ``planted``.

Nothing of size (ratings) is made on the host.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.scipy.special import ndtr, ndtri
from scipy.special import ndtr as np_ndtr, ndtri as np_ndtri

from benchmark import datagen

_CERTAIN = 0.99         # a rank's chance at or above this is taken for sure
_GOLDEN = (np.sqrt(5.0) - 1.0) / 2.0


def user_counts(seed, users, ratings, median, mean, most):
    """``(counts, quantile)``: int64 (users,) ratings per user and the
    lognormal quantile each user was dealt.  The quantiles' counts have
    ``median`` and ``mean`` before the cut to [1, ``most``], and sum to
    ``ratings`` exactly (the largest remainders get the last ones); the
    seed draws only which user gets which."""
    sigma = np.sqrt(2.0 * np.log(mean / median))
    z = np_ndtri((np.arange(users) + 0.5) / users)
    w = np.clip(np.exp(np.log(median) + sigma * z), 1, most)
    w *= ratings / w.sum()
    n = np.clip(np.floor(w), 1, most).astype(np.int64)
    rest = w - n
    while n.sum() != ratings:
        short = int(ratings - n.sum())
        if short > 0:
            can = np.flatnonzero(n < most)
            pick = can[np.argsort(-rest[can], kind="stable")[:short]]
            n[pick] += 1
        else:
            can = np.flatnonzero(n > 1)
            pick = can[np.argsort(rest[can], kind="stable")[:-short]]
            n[pick] -= 1
        rest[pick] = 0.0
    quantile = np.random.default_rng([int(seed), 31]).permutation(users)
    return n[quantile], quantile


class Popularity:
    """The item popularity and its closed-form tail mass.  ``tail(x)`` is
    the (unnormalised) mass of ranks beyond x; ``p`` the float64 (items,)
    shares of the ratings, rank by rank."""

    def __init__(self, items, sigma, z_top):
        self.n, self.sigma, self.z_top = int(items), float(sigma), float(z_top)
        self.x_top = self.n * (1.0 - np_ndtr(self.z_top))
        self.flat = np.exp(self.sigma * self.z_top)
        self.scale = self.n * np.exp(self.sigma ** 2 / 2.0)
        self.s_top = self.scale * np_ndtr(self.z_top - self.sigma)
        edges = self.tail(np.arange(self.n + 1, dtype=np.float64))
        self.total = edges[0]
        self.p = -np.diff(edges) / self.total

    def tail(self, x):
        z = np_ndtri(np.clip(1.0 - x / self.n, 0.0, 1.0))
        inner = self.scale * np_ndtr(z - self.sigma)
        return np.where(x < self.x_top,
                        self.s_top + (self.x_top - x) * self.flat, inner)

    def plan(self, counts):
        """float64 (R, c) for each user's count: the ranks taken for sure
        and the factor of the others' chances."""
        p = self.p
        head = np.concatenate([[0.0], np.cumsum(p)])[:-1]
        # count k takes R ranks for sure where k < h(R): h increases
        h = np.arange(self.n) + _CERTAIN * (1.0 - head) / p
        r = np.searchsorted(h, counts, side="right")
        left = np.maximum(1.0 - np.concatenate([head, [1.0]])[r], 1e-300)
        return r.astype(np.float64), (counts - r) / left


def user_table(seed, counts, quantile, pop, planted, b_user):
    """float32 (users, 5 + planted) a row a user: count, R, c, the tail mass
    at R, the user's offset (a golden-ratio sequence over the quantiles),
    b_u and p_u."""
    r, c = pop.plan(counts)
    rng = np.random.default_rng([int(seed), 32])
    offset = np.modf((quantile + 0.5) * _GOLDEN)[0]
    return np.concatenate([
        np.stack([counts, r, c, pop.tail(r), offset,
                  b_user * rng.standard_normal(counts.shape[0])], axis=1),
        rng.standard_normal((counts.shape[0], planted)) / np.sqrt(planted)
    ], axis=1).astype(np.float32)


def item_table(seed, items, planted, b_item, spread):
    """``(order, table)``: the item id of each popularity rank, and float32
    (items, 1 + planted) a row an item id: b_i and q_i."""
    rng = np.random.default_rng([int(seed), 33])
    order = rng.permutation(items).astype(np.int32)
    table = np.concatenate([
        b_item * rng.standard_normal((items, 1)),
        spread * rng.standard_normal((items, planted))], axis=1)
    return order, table.astype(np.float32)


@partial(jax.jit, static_argnames=("ratings", "chunk", "pop_args",
                                   "mu", "noise"))
def _stream(key, first, users, order, items, ratings, chunk, pop_args, mu,
            noise):
    n_items, sigma, z_top, x_top, flat, scale, s_top, total = pop_args
    m = users.shape[0]
    n_chunks = -(-ratings // chunk)
    size = n_chunks * chunk
    marks = jnp.zeros((size,), jnp.int32).at[first[1:]].add(1, mode="drop")
    owner = jnp.cumsum(marks)
    starts = jnp.where(marks > 0, lax.iota(jnp.int32, size), 0)
    within = lax.iota(jnp.int32, size) - lax.cummax(starts)

    def inverse_tail(s):
        """Rank x whose tail mass is s (the inverse of Popularity.tail)."""
        z = sigma + ndtri(jnp.clip(s / scale, 1e-38, 1.0))
        deep = n_items * ndtr(-z)
        return jnp.where(s > s_top, x_top - (s - s_top) / flat, deep)

    def body(i, bufs):
        rows_b, cols_b, vals_b = bufs
        at = i * chunk
        u = lax.dynamic_slice_in_dim(owner, at, chunk)
        k = lax.dynamic_slice_in_dim(within, at, chunk).astype(jnp.float32)
        row = jnp.take(users, jnp.minimum(u, m - 1), axis=0)
        sure, fac, s_r, off = row[:, 1], row[:, 2], row[:, 3], row[:, 4]
        x = inverse_tail(s_r - (off + k - sure) * total / fac)
        rank = jnp.where(k < sure, k, jnp.clip(jnp.floor(x), sure,
                                               n_items - 1))
        item = jnp.take(order, rank.astype(jnp.int32))
        it = jnp.take(items, item, axis=0)
        e = noise * jax.random.normal(jax.random.fold_in(key, i), (chunk,))
        r = mu + row[:, 5] + it[:, 0] + jnp.sum(row[:, 6:] * it[:, 1:], 1) + e
        live = at + lax.iota(jnp.int32, chunk) < ratings
        r = jnp.where(live, jnp.clip(jnp.round(r), 1.0, 5.0), 0.0)
        return tuple(lax.dynamic_update_slice_in_dim(b, v, at, 0)
                     for b, v in zip(bufs, (jnp.where(live, u, 0),
                                            jnp.where(live, item, 0), r)))

    bufs = (jnp.zeros((size,), jnp.int32), jnp.zeros((size,), jnp.int32),
            jnp.zeros((size,), jnp.float32))
    return lax.fori_loop(0, n_chunks, body, bufs)


def ratings(seed, cfg):
    """``(rows, cols, vals, counts)``: the configuration's ratings as
    device arrays of ``rows_padded`` entries (user, item, rating), sorted
    by user, the entries past ``ratings`` (0, 0, 0); and the host int64
    (users,) counts."""
    data = cfg["data"]
    users, items, total = cfg["users"], cfg["items"], cfg["ratings"]
    counts, quantile = user_counts(seed, users, total, data["user_median"],
                                   data["user_mean"], data["user_most"])
    pop = Popularity(items, data["item_sigma"], data["item_z_top"])
    utab = user_table(seed, counts, quantile, pop, data["planted"],
                      data["b_user"])
    order, itab = item_table(seed, items, data["planted"], data["b_item"],
                             data["spread"])
    first = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    pop_args = (pop.n, pop.sigma, pop.z_top, pop.x_top, pop.flat, pop.scale,
                pop.s_top, pop.total)
    rows, cols, vals = _stream(
        datagen.key_of(seed, 34), jnp.asarray(first[:-1]), jnp.asarray(utab),
        jnp.asarray(order), jnp.asarray(itab), int(total),
        int(data["chunk"]), tuple(float(a) for a in pop_args),
        float(data["mu"]), float(data["noise"]))
    return rows, cols, vals, counts
