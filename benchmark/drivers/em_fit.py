"""Traffic of ``GaussianMixture.fit`` calls back to back on one resident
array.

One timed call is one whole fit through the public estimator from an
explicit start (weights, means and precisions given, so neither the
KMeans start nor the host's ``rng.choice`` runs) — the fit loop with no
checkpoint, so one chunk of ``max_iter`` EM iterations with its health
vector — ended by the host holding ``weights_``, ``means_``,
``covariances_``, ``lower_bound_`` and ``history_``.  Work is counted in
EM iterations (``n_iter_``).
"""

from __future__ import annotations

import importlib

import numpy as np

from benchmark import datagen, datagen_mixture
from benchmark import work_em  # noqa: F401  (registers the cell's work)


class Driver:
    unit = "iterations"

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.est = dict(ctx.traffic["estimator"])
        self.fits = []              # what every timed fit handed back
        self.x_raw = None
        self.x = None
        self.ref = importlib.import_module(
            "benchmark.reference." + self.cfg["reference"])

    # -- set-up ------------------------------------------------------------

    def make_data(self):
        import dislib_tpu as ds
        from dislib_tpu.parallel import mesh as _mesh
        cfg, data, seed = self.cfg, self.cfg["data"], self.ctx.seed
        k, d = cfg["components"], cfg["features"]
        self.means = datagen_mixture.mixture_means(seed, k, d, data["cube"])
        self.x_raw = datagen_mixture.mixture(
            seed, cfg["rows"], self.means,
            datagen_mixture.mixture_factors(seed, k, d, data["sigma"]),
            data["chunk_rows"], _mesh.data_sharding())
        self.x_raw.block_until_ready()
        self.x = ds.array(self.x_raw)

    def start_of(self, i):
        """``(weights, means, precisions)`` the ``i``-th fit starts from:
        equal weights, one point near each component in an order drawn
        from the seed, and the precision of a round Gaussian of the
        data's sigma."""
        k, d = self.means.shape
        sigma = self.cfg["data"]["sigma"]
        return (np.full((k,), 1.0 / k, np.float32),
                datagen.seeded_centres(self.ctx.seed, i, self.means, sigma),
                np.tile(np.eye(d, dtype=np.float32) / sigma ** 2, (k, 1, 1)))

    def reference_start(self, i):
        """The same start as the reference takes it: covariances, inverted
        as ``GaussianMixture`` inverts ``precisions_init``."""
        weights, means, prec = self.start_of(i)
        return weights, means, np.linalg.inv(
            prec.astype(np.float64)).astype(np.float32)

    def _fit(self, i):
        from dislib_tpu.cluster import GaussianMixture
        weights, means, prec = self.start_of(i)
        gm = GaussianMixture(
            n_components=self.cfg["components"],
            covariance_type=self.cfg["covariance_type"],
            max_iter=self.est["max_iter"], tol=self.est["tol"],
            reg_covar=self.cfg["reg_covar"], weights_init=weights,
            means_init=means, precisions_init=prec)
        gm.fit(self.x)
        return {"i": i, "weights": np.asarray(gm.weights_),
                "means": np.asarray(gm.means_),
                "covariances": np.asarray(gm.covariances_),
                "lower_bound": float(gm.lower_bound_),
                "history": np.asarray(gm.history_),
                "n_iter": int(gm.n_iter_)}

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

    def _reference(self, x, i, precision="highest"):
        """The plain reference's fit from the ``i``-th start, as the
        dictionary a timed fit hands back."""
        n_iter = self.est["max_iter"]
        w, mu, cov, hist = self.ref.fit(
            x, self.reference_start(i), n_iter,
            self.cfg["reference_block_rows"], precision,
            self.cfg["covariance_type"], self.cfg["reg_covar"],
            self.cfg.get("reference_contract_rows"))
        return {"i": i, "weights": w, "means": mu, "covariances": cov,
                "history": hist, "lower_bound": float(hist[-1]),
                "n_iter": n_iter}

    def _compare(self, got, want):
        return self.ref.compare(got, want["weights"], want["means"],
                                want["covariances"], want["history"],
                                self.reference_start(got["i"]),
                                self.est["max_iter"])

    def check(self, precision="highest") -> dict:
        """Each sampled fit against the plain reference run from the same
        start; every number is the worst over the sample.  With
        ``precision`` below 'highest' the reference stands in the
        program's place (the control) and is compared with itself at
        'highest'."""
        worst = {}
        for got in self.sample():
            want = self._reference(self.x_raw, got["i"])
            if precision != "highest":
                got = self._reference(self.x_raw, got["i"], precision)
            for name, v in self._compare(got, want).items():
                worst[name] = max(worst.get(name, 0.0), v) \
                    if v == v else float("nan")
        return worst

    def faults(self) -> dict:
        """Readings of the faults this cell can have, each planted in the
        reference put in the program's place, on the window's last fit:
        half of the rows left out, an answer altered where it is produced
        (one coordinate of one mean moved by a tenth of the data's sigma),
        and the start handed back as the fit."""
        got = self.fits[-1]
        i = got["i"]
        want = self._reference(self.x_raw, i)
        block = self.cfg["reference_block_rows"]
        half = (self.x_raw.shape[0] // 2) // block * block
        out = {"half_batch": self._compare(
            self._reference(self.x_raw[:half], i), want)}
        altered = dict(got, means=got["means"].copy())
        altered["means"][0, 0] += 0.1 * self.cfg["data"]["sigma"]
        out["answer_altered"] = self._compare(altered, want)
        weights, means, covs = self.reference_start(i)
        out["state_unchanged"] = self._compare(
            dict(got, weights=weights, means=means, covariances=covs), want)
        return out


def make(ctx) -> Driver:
    return Driver(ctx)
