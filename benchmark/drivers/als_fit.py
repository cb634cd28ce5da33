"""Traffic of ``ALS.fit`` calls back to back on resident sparse ratings.

One timed call is one whole fit through the public estimator from an
explicit start (``items_init``: column 0 each item's mean rating, the
others small uniform numbers from the seed and the fit index, as Zhou et
al. start), no checkpoint, so one chunk of ``max_iter`` iterations with
its health vector, ended by the host holding ``users_``, ``items_``,
``rmse_`` and ``history_``.  Work is counted in ALS iterations.

The comparison: the plain reference (``benchmark/reference/als.py``) runs
its own ``max_iter`` iterations from each compared fit's start, and the
fit's U, V and RMSEs are held against that trajectory.  The fits compared
are the ``check_fits`` last that the program ran: the window's last and
the ones before it, the warm-up's fit counting as the one before the
window's first.  The warm-up starts where the window's first fit starts,
so where the window holds one fit (a fit takes longer than the window)
the two compared fits share one reference trajectory.
"""

from __future__ import annotations

import collections
import importlib

import numpy as np

from benchmark import datagen_ratings
from benchmark import work_als  # noqa: F401  (registers the cell's work)


class Driver:
    unit = "iterations"

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.est = dict(ctx.traffic["estimator"])
        self.x = None
        self.raw = self.flat = None
        self.fits = collections.deque(
            maxlen=int(ctx.traffic.get("check_fits", 2)))
        self.ref = importlib.import_module(
            "benchmark.reference." + self.cfg["reference"])
        self._item_major = None
        self._trajectories = {}

    # -- set-up ------------------------------------------------------------

    def make_data(self):
        import jax
        import jax.numpy as jnp
        from dislib_tpu.data.sparse import ShardedSparse, SparseArray
        from dislib_tpu.parallel import mesh as _mesh
        cfg = self.cfg
        rows, cols, vals, counts = datagen_ratings.ratings(self.ctx.seed, cfg)
        size = rows.shape[0]
        self.user_counts = counts
        # the stream as the library holds it: ONE shard of (1, size)
        self.raw = tuple(a.reshape(1, size) for a in (vals, rows, cols))
        del rows, cols, vals
        self.raw[0].block_until_ready()
        rep = ShardedSparse(*self.raw, None, (cfg["ratings"],), counts,
                            (cfg["users"], cfg["items"]), _mesh.get_mesh())
        self.x = SparseArray(sharded=rep)
        sums, total, per_item = jax.jit(lambda v, c: (
            jax.ops.segment_sum(v[0], c[0], num_segments=cfg["items"]),
            jnp.sum(v), jax.ops.segment_sum((v[0] != 0).astype(jnp.int32),
                                            c[0], num_segments=cfg["items"])
        ))(self.raw[0], self.raw[2])
        self.item_counts = np.asarray(per_item).astype(np.int64)
        self.item_means = np.where(
            self.item_counts > 0,
            np.asarray(sums) / np.maximum(self.item_counts, 1),
            float(total) / cfg["ratings"])

    def start_of(self, i):
        """The ``i``-th fit's item factors: column 0 each item's mean
        rating, the others uniform in [0, spread) from seed and index."""
        n, f = self.cfg["items"], self.cfg["n_f"]
        rng = np.random.default_rng([int(self.ctx.seed), 6, int(i) + 1_000])
        v0 = self.cfg["start_spread"] * rng.random((n, f))
        v0[:, 0] = self.item_means
        return v0.astype(np.float32)

    def _fit(self, i):
        from dislib_tpu.recommendation import ALS
        als = ALS(n_f=self.cfg["n_f"], lambda_=self.cfg["lambda_"],
                  max_iter=self.est["max_iter"], tol=self.est["tol"],
                  items_init=self.start_of(i))
        als.fit(self.x)
        return {"i": i, "users": np.asarray(als.users_),
                "items": np.asarray(als.items_), "rmse": float(als.rmse_),
                "history": np.asarray(als.history_),
                "n_iter": int(als.n_iter_)}

    def warm_up(self):
        self.fits.append(self._fit(0))

    # -- the window ----------------------------------------------------------

    def call(self, i) -> int:
        got = self._fit(i)
        self.fits.append(got)
        return got["n_iter"]

    def end_to_end(self, units, calls, seconds) -> float:
        return units / seconds

    # -- after the window ----------------------------------------------------

    def sample(self):
        """The fits that are compared, oldest first: the window's last and
        the ``check_fits`` - 1 the program ran before it."""
        return list(self.fits)

    def release(self):
        """Free what the program holds; the ratings stay for the
        reference, one flat copy of each part."""
        self.x = None
        if self.raw is not None:        # (vals, rows, cols), one copy each
            self.flat = tuple(a.reshape(-1) for a in self.raw)
            self.raw = None

    def _users(self):
        return self.flat[2], self.flat[0], self.user_counts

    def _items(self):
        if self._item_major is None:
            self._item_major = self.ref.item_major(
                self.flat[1], self.flat[2], self.flat[0], self.cfg["ratings"])
        return (*self._item_major, self.item_counts)

    def trajectory(self, i, precision="highest", halved=False):
        """The reference's fit from the ``i``-th start at ``precision``, as
        the dictionary a timed fit hands back; ``halved`` leaves out the
        ratings of the first half of the users (a fault)."""
        key = (i, precision, halved)
        if key not in self._trajectories:
            import jax.numpy as jnp
            users, items = self._users(), self._items()
            entries = (self.flat[1], self.flat[2], self.flat[0])
            if halved:
                half = self.cfg["users"] // 2
                users = (users[0], jnp.where(self.flat[1] < half, users[1],
                                             0.0), users[2])
                items = (items[0], jnp.where(items[0] < half, items[1], 0.0),
                         items[2])
                entries = (*entries[:2], users[1])
            u, v, hist = self.ref.fit(users, items, self.start_of(i),
                                      self.cfg["lambda_"],
                                      self.est["max_iter"], entries,
                                      precision)
            self._trajectories[key] = {
                "i": i, "users": u, "items": v, "history": hist,
                "rmse": float(hist[-1]), "n_iter": self.est["max_iter"]}
        return self._trajectories[key]

    def _compare(self, got, want):
        ref = self.ref
        hist = np.asarray(got["history"], np.float64)
        wh = np.asarray(want["history"], np.float64)
        if hist.shape != wh.shape:
            rmse_gap = float("inf")
        else:
            rmse_gap = float(max(np.max(np.abs(hist - wh) / wh),
                                 abs(got["rmse"] - want["rmse"])
                                 / want["rmse"]))
        return {"users_gap": ref.rel_frobenius(got["users"], want["users"]),
                "items_gap": ref.rel_frobenius(got["items"], want["items"]),
                "rmse_gap": rmse_gap,
                "n_iter_gap": float(abs(got["n_iter"]
                                        - self.est["max_iter"]))}

    def check(self, precision="highest") -> dict:
        """Each compared fit against the reference's iterations from the
        same start; every number is the worst over the sample.  With
        ``precision`` below 'highest' the reference at that precision
        stands in the program's place (the control)."""
        worst = {}
        for c in self.sample():
            want = self.trajectory(c["i"])
            got = c if precision == "highest" \
                else self.trajectory(c["i"], precision)
            for name, v in self._compare(got, want).items():
                worst[name] = max(worst.get(name, 0.0), v) \
                    if v == v else float("nan")
        return worst

    def faults(self) -> dict:
        """Readings of the faults this cell can have on the window's last
        fit: the reference in the program's place with the ratings of half
        the users left out; one item factor altered where it is produced
        (one coordinate moved by 0.1); and the start handed back as the
        fit (V the start, U zero)."""
        c = self.sample()[-1]
        want = self.trajectory(c["i"])
        out = {"half_batch": self._compare(
            self.trajectory(c["i"], halved=True), want)}
        altered = dict(c, items=c["items"].copy())
        altered["items"][0, 0] += 0.1
        out["answer_altered"] = self._compare(altered, want)
        handed = dict(c, items=self.start_of(c["i"]),
                      users=np.zeros_like(c["users"]))
        out["state_unchanged"] = self._compare(handed, want)
        return out


def make(ctx) -> Driver:
    return Driver(ctx)
