"""Traffic of ``KMeans.fit`` calls back to back on one resident array.

One timed call is one whole fit through the public estimator — the fit
loop with no checkpoint, so one chunk of ``max_iter`` iterations with its
health vector — ended by the host's reads of ``centers_``, ``inertia_``
and ``history_``.  Work is counted in Lloyd iterations (``n_iter_``).
"""

from __future__ import annotations

import importlib

import numpy as np

from benchmark import datagen


class Driver:
    unit = "iterations"

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.est = dict(ctx.traffic["estimator"])
        self.fits = []              # what every timed fit handed back
        self.x_raw = None
        self.x = None

    # -- set-up ------------------------------------------------------------

    def make_data(self):
        import dislib_tpu as ds
        from dislib_tpu.parallel import mesh as _mesh
        cfg = self.cfg
        self.means = datagen.blob_means(self.ctx.seed, cfg["clusters"],
                                        cfg["features"])
        self.x_raw = datagen.blobs(self.ctx.seed, cfg["rows"], self.means,
                                   cfg["data"]["sigma"],
                                   cfg["data"]["chunk_rows"],
                                   _mesh.data_sharding())
        self.x_raw.block_until_ready()
        self.x = ds.array(self.x_raw)

    def init_of(self, i):
        return datagen.seeded_centres(self.ctx.seed, i, self.means,
                                      self.cfg["data"]["sigma"])

    def _fit(self, i):
        from dislib_tpu.cluster import KMeans
        km = KMeans(n_clusters=self.cfg["clusters"], init=self.init_of(i),
                    max_iter=self.est["max_iter"], tol=self.est["tol"])
        km.fit(self.x)
        return {"i": i, "centers": np.asarray(km.centers_),
                "inertia": float(km.inertia_),
                "history": np.asarray(km.history_),
                "n_iter": int(km.n_iter_)}

    def warm_up(self):
        self._fit(-1)

    # -- the window ----------------------------------------------------------

    def call(self, i) -> int:
        got = self._fit(i)
        self.fits.append(got)
        return got["n_iter"]

    def end_to_end(self, units, calls, seconds) -> float:
        return units / seconds

    # -- after the window ----------------------------------------------------

    def release(self):
        """Free what the program holds; the benchmark's own X stays for the
        reference."""
        self.x = None

    def sample(self):
        """The fits that are compared: the window's last and, drawn from
        the seed, as many others as the traffic asks for."""
        n = len(self.fits)
        want = min(int(self.ctx.traffic.get("check_fits", 2)), n)
        rng = np.random.default_rng([int(self.ctx.seed), 5])
        others = rng.choice(n - 1, size=want - 1, replace=False) \
            if want > 1 else []
        return [self.fits[j] for j in sorted(int(o) for o in others)] \
            + [self.fits[-1]]

    def check(self, precision="highest") -> dict:
        """Each sampled fit against the plain reference run from the same
        start; every number is the worst over the sample.  With
        ``precision`` below 'highest' the reference stands in the
        program's place (the control) and is compared with itself at
        'highest'."""
        ref = importlib.import_module(
            "benchmark.reference." + self.cfg["reference"])
        block = self.cfg["reference_block_rows"]
        worst = {}
        for got in self.sample():
            init = self.init_of(got["i"])
            n_iter = self.est["max_iter"]
            ref_c, ref_h = ref.fit(self.x_raw, init, n_iter, block)
            if precision != "highest":
                c, h = ref.fit(self.x_raw, init, n_iter, block, precision)
                got = {"i": got["i"], "centers": c, "history": h,
                       "inertia": float(h[-1]), "n_iter": n_iter}
            for name, v in ref.compare(got, ref_c, ref_h, init,
                                       n_iter).items():
                worst[name] = max(worst.get(name, 0.0), v) \
                    if v == v else float("nan")
        return worst


    def faults(self) -> dict:
        """Readings of the faults this cell can have, each planted in the
        reference put in the program's place, on the window's last fit:
        half of the rows left out with the mean taken over the rest, and
        an answer altered where it is produced (one coordinate of one
        centre moved by a tenth of the data's sigma).  A fit that returns
        its start unchanged reads centers_gap 1 by that number's
        definition and needs no run."""
        ref = importlib.import_module(
            "benchmark.reference." + self.cfg["reference"])
        block = self.cfg["reference_block_rows"]
        got = self.fits[-1]
        init, n_iter = self.init_of(got["i"]), self.est["max_iter"]
        ref_c, ref_h = ref.fit(self.x_raw, init, n_iter, block)
        half = (self.x_raw.shape[0] // 2) // block * block
        c, h = ref.fit(self.x_raw[:half], init, n_iter, block)
        out = {"half_batch": ref.compare(
            {"centers": c, "history": h, "inertia": float(h[-1]),
             "n_iter": n_iter}, ref_c, ref_h, init, n_iter)}
        altered = dict(got, centers=got["centers"].copy())
        altered["centers"][0, 0] += 0.1 * self.cfg["data"]["sigma"]
        out["answer_altered"] = ref.compare(altered, ref_c, ref_h, init,
                                            n_iter)
        return out


def make(ctx) -> Driver:
    return Driver(ctx)
