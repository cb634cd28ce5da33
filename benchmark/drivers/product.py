"""Traffic of ``ds.matmul`` calls back to back on two resident operands.

One timed call is one product through the public entry, ended by
``block_until_ready`` on the result; the previous result is dropped
before the call, so the device holds A, B and one C.  Work is counted in
products.
"""

from __future__ import annotations

import gc
import importlib

from benchmark import counts, datagen


class Driver:
    unit = "products"

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.m, self.k, self.n = counts.matmul_dims(ctx.config)
        # the collector pass a caller makes before each product, where the
        # traffic file asks for one (its gc_why says why and until when)
        self.gc_generation = ctx.traffic.get("gc_before_call")
        self.a_raw = self.b_raw = self.a = self.b = self.last = None

    # -- set-up ------------------------------------------------------------

    def make_data(self):
        import dislib_tpu as ds
        from dislib_tpu.parallel import mesh as _mesh
        cfg, seed = self.cfg, self.ctx.seed
        sh = _mesh.data_sharding()
        self.a_raw = datagen.normal_matrix(seed, 11, (self.m, self.k), sh)
        self.b_raw = datagen.normal_matrix(seed, 12, (self.k, self.n), sh)
        self.b_raw.block_until_ready()
        self.a, self.b = ds.array(self.a_raw), ds.array(self.b_raw)

    def _product(self):
        import dislib_tpu as ds
        self.last = None
        if self.gc_generation is not None:
            gc.collect(int(self.gc_generation))
        c = ds.matmul(self.a, self.b, precision=self.cfg["policy"])
        c.block_until_ready()
        self.last = c

    def warm_up(self):
        self._product()

    # -- the window ----------------------------------------------------------

    def call(self, i) -> int:
        self._product()
        return 1

    def end_to_end(self, units, calls, seconds) -> float:
        return counts.matmul_flops(self.cfg) * units / seconds \
            / self.ctx.chips / 1e12

    # -- after the window ----------------------------------------------------

    def release(self):
        """Keep the window's last result, as a plain device array, and the
        benchmark's own A and B; drop the program's wrappers."""
        self.last = self.last.force()._data if self.last is not None \
            else None
        self.a = self.b = None

    def check(self, precision="highest") -> dict:
        """Rows of the window's last product, drawn from the seed, against
        the plain reference's.  With ``precision`` below 'highest' the
        reference stands in the program's place (the control)."""
        ref = importlib.import_module(
            "benchmark.reference." + self.cfg["reference"])
        rows = ref.sample_rows(self.ctx.seed, self.m,
                               self.ctx.traffic["check_rows"])
        want = ref.product_rows(self.a_raw, self.b_raw, rows)
        if precision != "highest":
            got = ref.product_rows(self.a_raw, self.b_raw, rows, precision)
        else:
            c = self.last
            m, n = self.m, self.n
            got = ref.take_rows(c[:m, :n] if c.shape != (m, n) else c, rows)
        return ref.compare(got, want)


    def faults(self) -> dict:
        """Readings of the faults this cell can have, each planted in the
        reference put in the program's place: the exchange between chips
        left out (the second half of the contraction never arrives), and
        an answer altered where it is produced (one sampled entry moved by
        the entries' root mean square)."""
        import jax.numpy as jnp
        ref = importlib.import_module(
            "benchmark.reference." + self.cfg["reference"])
        rows = ref.sample_rows(self.ctx.seed, self.m,
                               self.ctx.traffic["check_rows"])
        want = ref.product_rows(self.a_raw, self.b_raw, rows)
        k2 = self.k // 2
        keep = (jnp.arange(self.k) < k2).astype(jnp.float32)
        out = {"exchange_left_out": ref.compare(
            ref.product_rows(self.a_raw * keep[None, :], self.b_raw, rows),
            want)}
        rms = float(jnp.sqrt(jnp.mean(want * want)))
        out["answer_altered"] = ref.compare(want.at[0, 0].add(rms), want)
        return out


def make(ctx) -> Driver:
    return Driver(ctx)
