"""Traffic of ``ds.random_svd`` calls back to back on one resident array.

One timed call is the public entry on the resident array with the
configuration's rank, oversampling and power iterations and a test matrix
drawn from seed and call index, the previous call's results dropped
first, ended when ``s`` and ``v`` are on the host and
``u.block_until_ready()`` has returned.  A closed loop of one caller, no
pause.  Work is counted in power iterations (``iters`` a call).
"""

from __future__ import annotations

import importlib

import numpy as np

from benchmark import datagen_lowrank
from benchmark import work_rsvd  # noqa: F401  (registers the cell's work)


class Driver:
    unit = "iterations"

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.last = None            # the newest call's results
        self.kept = None            # those of the call drawn from the seed
        self.x_raw = None
        self.x = None
        # which of the window's first calls is compared besides the last;
        # drawn before the window, since a call's U is too big to keep
        # every call's until the window's length is known
        among = int(ctx.traffic.get("check_among_first", 1))
        self.keep_index = int(np.random.default_rng(
            [int(ctx.seed), 5]).integers(among))
        self.ref = importlib.import_module(
            "benchmark.reference." + self.cfg["reference"])

    # -- set-up ------------------------------------------------------------

    def make_data(self):
        import dislib_tpu as ds
        from dislib_tpu.parallel import mesh as _mesh
        cfg, data, seed = self.cfg, self.cfg["data"], self.ctx.seed
        self.x_raw = datagen_lowrank.lowrank(
            seed, cfg["rows"],
            datagen_lowrank.spectrum(data["directions"], data["ratio"]),
            datagen_lowrank.basis(seed, cfg["features"], data["directions"]),
            data["noise"], data["chunk_rows"], _mesh.data_sharding())
        self.x_raw.block_until_ready()
        self.x = ds.array(self.x_raw)

    def state_of(self, i) -> int:
        """The ``random_state`` of the ``i``-th call: seed and call index,
        within the 32 bits a ``PRNGKey`` takes."""
        return (int(self.ctx.seed) + int(i)) % 2 ** 32

    def _call(self, i):
        import dislib_tpu as ds
        cfg = self.cfg
        self.last = None
        u, s, v = ds.random_svd(self.x, iters=cfg["iters"], nsv=cfg["nsv"],
                                oversample=cfg["oversample"],
                                random_state=self.state_of(i))
        s, v = s.collect(), v.collect()
        u.block_until_ready()
        self.last = {"i": i, "u": u, "s": np.asarray(s), "v": np.asarray(v)}

    def warm_up(self):
        self._call(-1)

    # -- the window ----------------------------------------------------------

    def call(self, i) -> int:
        self._call(i)
        if i == self.keep_index:
            self.kept = self.last
        return int(self.cfg["iters"])

    def end_to_end(self, units, calls, seconds) -> float:
        return units / seconds

    # -- after the window ----------------------------------------------------

    def release(self):
        """Free what the program holds but the compared calls' results;
        the benchmark's own X stays for the reference."""
        self.x = None

    def sample(self):
        """The calls that are compared: the one drawn from the seed, if
        the window got that far, and the window's last."""
        kept = [] if self.kept is None or self.kept is self.last \
            else [self.kept]
        return kept + ([] if self.last is None else [self.last])

    def _blocks(self):
        return self.cfg["reference_block_rows"], \
            self.cfg["reference_contract_rows"]

    def _rows(self):
        return self.ref.sample_rows(self.ctx.seed, self.cfg["rows"],
                                    self.ctx.traffic["check_rows"])

    def _reference(self, x, i, precision="highest", iters=None):
        """The plain reference's summary for the ``i``-th call's test
        matrix; its U is dropped when its rows and Gram are read."""
        cfg = self.cfg
        omega = self.ref.test_matrix(self.state_of(i), cfg["features"],
                                     cfg["nsv"] + cfg["oversample"])
        u, s, v = self.ref.fit(x, omega, cfg["iters"] if iters is None
                               else iters, cfg["nsv"], *self._blocks(),
                               precision)
        if u.shape[0] < cfg["rows"]:        # rows that never arrived
            import jax.numpy as jnp
            u = jnp.pad(u, ((0, cfg["rows"] - u.shape[0]), (0, 0)))
        return self.ref.summary(u, s, v, self._rows(), *self._blocks())

    def _summaries(self):
        """The compared calls' summaries, each call's U dropped as soon as
        its rows and its Gram are read (two more panels beside the
        reference's would not fit the chip)."""
        m, r = self.cfg["rows"], self.cfg["nsv"]
        for c in self.sample():
            if "u" in c:
                u = c.pop("u").force()._data
                c["summary"] = self.ref.summary(
                    u[:m, :r] if u.shape != (m, r) else u, c["s"], c["v"],
                    self._rows(), *self._blocks())
        return [(c["i"], c["summary"]) for c in self.sample()]

    def check(self, precision="highest") -> dict:
        """Each compared call against the plain reference run from the
        same test matrix; every number is the worst over the sample.
        With ``precision`` below 'highest' the reference stands in the
        program's place (the control) and is compared with itself at
        'highest'."""
        worst = {}
        for i, got in self._summaries():
            want = self._reference(self.x_raw, i)
            if precision != "highest":
                got = self._reference(self.x_raw, i, precision)
            for name, v in self.ref.compare(got, want).items():
                worst[name] = max(worst.get(name, 0.0), v) \
                    if v == v else float("nan")
        return worst

    def faults(self) -> dict:
        """Readings of the faults this cell can have, each planted in the
        reference put in the program's place, on the window's last call:
        half of the rows left out, an answer altered where it is produced
        (the first singular value moved by a thousandth of itself), and
        the power iterations left out."""
        i = self._summaries()[-1][0]
        want = self._reference(self.x_raw, i)
        block = self.cfg["reference_block_rows"]
        half = max((self.x_raw.shape[0] // 2) // block, 1) * block
        out = {"half_batch": self.ref.compare(
            self._reference(self.x_raw[:half], i), want)}
        altered = dict(want, s=want["s"].copy())
        altered["s"][0] *= 1.001
        out["answer_altered"] = self.ref.compare(altered, want)
        out["no_power_iterations"] = self.ref.compare(
            self._reference(self.x_raw, i, iters=0), want)
        return out


def make(ctx) -> Driver:
    return Driver(ctx)
