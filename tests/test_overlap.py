"""Comm–compute overlap schedules (round-13 PR).

The library-wide panel-schedule contract: the double-buffered (``db``)
schedules of SUMMA, the panel rechunk and the ring kernels must be
BIT-EQUAL to their sequential (``seq``) counterparts — same panels, same
ops, same order — still exactly ONE dispatch, routed by ``DSLIB_OVERLAP``
(observable through the schedule counters), green under
``jax_debug_nans``, and the pipelined program must actually decouple the
next panel's collective from the current panel's compute (audit by
opcode: under db at least one collective neither feeds the first dot nor
waits for it; under seq every one does one or the other; compiled for a
described v5e:2x2, a copy's start and its done straddle a GEMM under db
and never under seq).
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import dislib_tpu as ds
from dislib_tpu.ops import overlap as _ov
from dislib_tpu.ops import precision as px
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.utils import profiling as _prof

from conftest import skip_unless_devices


def _mk(shape, dtype=np.float32, seed=0):
    return np.random.RandomState(seed).rand(*shape).astype(dtype)


# ---------------------------------------------------------------------------
# 1. the DSLIB_OVERLAP router
# ---------------------------------------------------------------------------

class TestRouter:
    def test_default_is_double_buffered(self, monkeypatch):
        monkeypatch.delenv("DSLIB_OVERLAP", raising=False)
        assert _ov.resolve() == "db"

    @pytest.mark.parametrize("raw,want", [
        ("db", "db"), ("auto", "db"), ("1", "db"), ("on", "db"),
        ("seq", "seq"), ("0", "seq"), ("off", "seq"),
        ("sequential", "seq"),
    ])
    def test_aliases(self, raw, want):
        assert _ov.resolve(raw) == want

    def test_env_routes_the_default(self, monkeypatch):
        monkeypatch.setenv("DSLIB_OVERLAP", "seq")
        assert _ov.resolve() == "seq"
        monkeypatch.setenv("DSLIB_OVERLAP", "pallas")
        assert _ov.resolve() == "pallas"

    def test_unknown_raises(self):
        with pytest.raises(ValueError, match="unknown overlap schedule"):
            _ov.resolve("bogus")
        with pytest.raises(ValueError):
            _ov.overlapped("bogus")

    def test_overlapped_predicate(self):
        assert _ov.overlapped("db") and _ov.overlapped("pallas")
        assert not _ov.overlapped("seq")

    def test_pallas_is_never_rerouted(self):
        """A requested schedule is the schedule that runs: there is no
        availability probe that could turn a Mosaic refusal on a TPU into
        a quiet switch to another schedule."""
        assert _ov.resolve("pallas") == "pallas"
        from dislib_tpu.ops import pallas_kernels as _pk
        assert not hasattr(_pk, "available")
        assert not hasattr(_pk, "hist_available")

    def test_public_observability_entry(self, monkeypatch):
        monkeypatch.delenv("DSLIB_OVERLAP", raising=False)
        assert ds.overlap_schedule() == "db"


# ---------------------------------------------------------------------------
# 2. the shared pipeline helper: same folds, either order
# ---------------------------------------------------------------------------

class TestPanelPipeline:
    @pytest.mark.parametrize("steps", [1, 2, 3, 5])
    def test_bit_equal_and_order_preserving(self, steps):
        vals = jnp.asarray(np.random.RandomState(3).rand(8, 4)
                           .astype(np.float32))

        def fetch(t, prev):
            return vals[t]

        def consume(t, acc, pan):
            # non-commutative fold: order changes the bits, so equality
            # proves the schedules consume panels identically.  add THEN
            # scale — a mul+add chain could legally FMA-contract
            # differently in the two compiled programs (the fusion
            # layer's documented ±1-ulp divergence), which would test
            # XLA, not the pipeline
            return (acc + pan) * (1.0 + (t + 1) * 0.001)

        acc0 = jnp.zeros((4,), jnp.float32)
        seq = _ov.panel_pipeline(steps, vals[0], fetch, consume, acc0, False)
        db = _ov.panel_pipeline(steps, vals[0], fetch, consume, acc0, True)
        np.testing.assert_array_equal(np.asarray(seq), np.asarray(db))
        # oracle: explicit in-order fold
        acc = acc0
        for t in range(steps):
            acc = consume(t, acc, vals[t])
        np.testing.assert_array_equal(np.asarray(seq), np.asarray(acc))

    def test_zero_steps_is_identity(self):
        acc0 = jnp.ones((2,))
        for ov in (False, True):
            out = _ov.panel_pipeline(0, None, None, None, acc0, ov)
            assert out is acc0


# ---------------------------------------------------------------------------
# 2b. the host-loop pipeline (round 17: panel_pipeline's discipline
#     lifted to the fit drivers' dispatch→read sequences)
# ---------------------------------------------------------------------------

class TestHostPipeline:
    @pytest.mark.parametrize("steps", [0, 1, 2, 5])
    def test_same_pairs_same_order_both_schedules(self, steps):
        logs = {}
        for ov in (False, True):
            calls = []

            def fetch(t):
                calls.append(("fetch", t))
                return t * 10

            def consume(t, h):
                calls.append(("consume", t))
                assert h == t * 10, "handle paired with the wrong step"
                return h + t

            out = _ov.host_pipeline(steps, fetch, consume, overlap=ov)
            assert out == [t * 11 for t in range(steps)]
            logs[ov] = calls
        # both schedules evaluate the same consume(t, fetch(t)) pairs in
        # the same consume order (bit-equal by construction); what the
        # pipelined order changes is ONLY the issue point — fetch(t+1)
        # lands before consume(t), where the strict chain interleaves
        consumed = [c for c in logs[True] if c[0] == "consume"]
        assert consumed == [c for c in logs[False] if c[0] == "consume"]
        if steps >= 2:
            assert logs[True].index(("fetch", 1)) \
                < logs[True].index(("consume", 0))
            assert logs[False].index(("consume", 0)) \
                < logs[False].index(("fetch", 1))

    def test_exactly_one_extra_step_in_flight(self):
        for ov, want_peak in ((False, 1), (True, 2)):
            live = {"now": 0, "peak": 0}

            def fetch(t):
                live["now"] += 1
                live["peak"] = max(live["peak"], live["now"])
                return t

            def consume(t, h):
                live["now"] -= 1
                return h

            _ov.host_pipeline(6, fetch, consume, overlap=ov)
            assert live["now"] == 0, "a step was never drained"
            assert live["peak"] == want_peak, \
                (ov, live["peak"], "pipelined carry must hold exactly ONE "
                                   "extra in-flight step")

    def test_csvm_batched_level_routed_and_counted(self, monkeypatch):
        """A partition cap + tiny solve budget force the CSVM level solve
        into multiple batches — the batch loop must pipeline through the
        host-loop router (counter-observable) and both schedules must
        pick the same support vectors."""
        import scipy.sparse as sp
        from dislib_tpu.classification import CascadeSVM
        from dislib_tpu.data.sparse import SparseArray
        rs = np.random.RandomState(7)
        m_sp = sp.random(200, 24, density=0.08, format="coo",
                         random_state=rs, dtype=np.float32)
        row_sum = np.asarray(m_sp.sum(axis=1)).ravel()
        y = ds.array((row_sum > np.median(row_sum))
                     .astype(np.float32).reshape(-1, 1))
        monkeypatch.setenv("DSLIB_CSVM_MAX_PARTITION", "64")
        monkeypatch.setenv("DSLIB_CSVM_SOLVE_BUDGET", str(1 << 16))
        svs = {}
        for sched in ("db", "seq"):
            monkeypatch.setenv("DSLIB_OVERLAP", sched)
            _prof.reset_counters()
            est = CascadeSVM(cascade_arity=2, max_iter=2, c=1.0,
                             gamma=0.1).fit(SparseArray.from_scipy(m_sp), y)
            sc = _prof.schedule_counters()
            assert sc.get(f"csvm_batches:{sched}", 0) >= 1, (sched, sc)
            svs[sched] = np.sort(np.asarray(est._sv_idx))
        np.testing.assert_array_equal(svs["db"], svs["seq"])

    def test_forest_snapshot_and_adopt_routed_and_counted(
            self, tmp_path, monkeypatch, rng):
        """A checkpointed forest fit drains its per-level snapshot fetches
        and the adoption reads through the host-loop router — both sites
        counter-observable, predictions bit-equal across schedules."""
        from dislib_tpu.trees import RandomForestClassifier
        from dislib_tpu.utils.checkpoint import FitCheckpoint
        x = rng.rand(200, 4).astype(np.float32)
        y = (x[:, 0] > 0.5).astype(np.float32).reshape(-1, 1)
        probs = {}
        for sched in ("db", "seq"):
            monkeypatch.setenv("DSLIB_OVERLAP", sched)
            _prof.reset_counters()
            f = RandomForestClassifier(n_estimators=2, random_state=0).fit(
                ds.array(x), ds.array(y),
                checkpoint=FitCheckpoint(
                    str(tmp_path / f"ck_{sched}"), every=1))
            probs[sched] = np.asarray(
                f.predict_proba(ds.array(x)).collect())
            sc = _prof.schedule_counters()
            assert sc.get(f"forest_snapshot:{sched}", 0) >= 1, (sched, sc)
            assert sc.get(f"forest_adopt:{sched}", 0) >= 1, (sched, sc)
        np.testing.assert_array_equal(probs["db"], probs["seq"])


# ---------------------------------------------------------------------------
# 3. schedule-equivalence grid: SUMMA
# ---------------------------------------------------------------------------

def _init(grid):
    """``ds.init`` on the first rows x cols of the rig's eight devices."""
    skip_unless_devices(8)
    ds.init(grid, devices=jax.devices()[:grid[0] * grid[1]])
    return _mesh.get_mesh()


def _masked_psum_summa(ap, bp, mesh):
    """The data movement ``summa_matmul`` had until PR 32, kept as its
    oracle: every panel a masked ``psum`` over its mesh axis (zeros from
    every rank but the owner), all chips on panel ``t`` in step ``t``."""
    from dislib_tpu.ops.summa import summa_steps
    steps = summa_steps(mesh)
    kb = ap.shape[1] // steps

    def local(a, b):
        (m, ka), (kr, n) = a.shape, b.shape
        acc = jnp.zeros((m, n), jnp.float32)
        for off in range(0, steps * kb, kb):
            a_pan = lax.dynamic_slice(a, (0, off % ka), (m, kb))
            a_pan = lax.psum(jnp.where(
                lax.axis_index(_mesh.COLS) == off // ka, a_pan, 0.), _mesh.COLS)
            b_pan = lax.dynamic_slice(b, (off % kr, 0), (kb, n))
            b_pan = lax.psum(jnp.where(
                lax.axis_index(_mesh.ROWS) == off // kr, b_pan, 0.), _mesh.ROWS)
            acc = acc + px.pdot(a_pan, b_pan, px.FLOAT32)
        return acc
    spec = jax.sharding.PartitionSpec(_mesh.ROWS, _mesh.COLS)
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=spec, check_vma=True))(ap, bp)


_OP_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?[\]})]) ([\w\-]+)\(")


def _permutes(hlo):
    """``(bytes, [(source, target), ...])`` of every collective-permute
    in a compiled text (a start/done pair counts once, by its start)."""
    out = []
    for line in hlo.splitlines():
        m = _OP_RE.match(line)
        if not m or m.group(3) not in ("collective-permute",
                                       "collective-permute-start"):
            continue
        dims = re.search(r"f32\[([\d,]+)\]", m.group(2)).group(1)
        pairs = re.search(r"source_target_pairs=\{(.*?)\}\}", line).group(1)
        out.append((4 * int(np.prod([int(d) for d in dims.split(",")])),
                    [tuple(map(int, p)) for p in
                     re.findall(r"\{?(\d+),(\d+)", pairs)]))
    return out


class TestSummaSchedules:
    @pytest.mark.parametrize("grid", [(4, 2), (2, 4), (2, 2)])
    @pytest.mark.parametrize("policy", ["float32", "bfloat16"])
    def test_db_bit_equals_seq(self, grid, policy):
        from dislib_tpu.ops.summa import summa_matmul
        mesh = _init(grid)
        a = ds.array(_mk((96, 64))).force()
        b = ds.array(_mk((64, 80), seed=1)).force()
        pol = px.resolve(policy)
        db = np.asarray(summa_matmul(a._data, b._data, mesh, pol,
                                     overlap="db"))
        seq = np.asarray(summa_matmul(a._data, b._data, mesh, pol,
                                      overlap="seq"))
        np.testing.assert_array_equal(db, seq)
        # absolute correctness vs the host oracle
        oracle = _mk((96, 64)) @ _mk((64, 80), seed=1)
        tol = 2e-2 if policy == "bfloat16" else 1e-5
        np.testing.assert_allclose(db[:96, :80], oracle, rtol=tol,
                                   atol=tol * np.abs(oracle).max())

    @pytest.mark.parametrize("grid", [(2, 2), (4, 2), (2, 4)])
    def test_matches_the_masked_psum_order(self, grid):
        """The direct fetch against the broadcast it replaced: on (2, 2)
        a chip adds the same two panel products in the other order, which
        commutes, so the result is the old one to the bit; on a mesh with
        more panels the rotated order is another float32 sum."""
        from dislib_tpu.ops.summa import summa_matmul
        mesh = _init(grid)
        a = ds.array(_mk((96, 64))).force()
        b = ds.array(_mk((64, 80), seed=1)).force()
        got = np.asarray(summa_matmul(a._data, b._data, mesh, px.FLOAT32))
        want = np.asarray(_masked_psum_summa(a._data, b._data, mesh))
        if grid == (2, 2):
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-6,
                                       atol=1e-6 * np.abs(want).max())

    @pytest.mark.parametrize("grid", [(2, 2), (4, 2), (2, 4)])
    def test_every_block_crosses_its_axis_once(self, grid):
        """No reduction is left in the compiled program, and what its
        copies bring a chip from OTHER chips is exactly the rest of its
        row of A blocks and of its column of B blocks: (C-1) |A block| +
        (R-1) |B block|."""
        from dislib_tpu.ops.summa import summa_matmul
        mesh = _init(grid)
        rows, cols = grid
        a = ds.array(_mk((96, 64))).force()
        b = ds.array(_mk((64, 80), seed=1)).force()
        text = summa_matmul.lower(a._data, b._data, mesh, px.FLOAT32) \
            .compile().as_text()
        assert "all-reduce" not in text and "all-gather" not in text
        received = np.zeros(rows * cols, np.int64)
        for nbytes, pairs in _permutes(text):
            assert sorted(t for _, t in pairs) == list(range(rows * cols))
            for src, dst in pairs:
                received[dst] += nbytes * (src != dst)
        a_block = a._data.size * 4 // (rows * cols)
        b_block = b._data.size * 4 // (rows * cols)
        assert received.tolist() == [(cols - 1) * a_block
                                     + (rows - 1) * b_block] * (rows * cols)

    def test_temporaries_no_larger_than_the_masked_psum_program(self):
        """``summa_matmul`` compiled at PR 31 for these operands on this
        (2, 2) CPU mesh held 28 752 bytes of temporaries (db and seq)."""
        from dislib_tpu.ops.summa import summa_matmul
        mesh = _init((2, 2))
        a = ds.array(_mk((96, 64))).force()
        b = ds.array(_mk((64, 80), seed=1)).force()
        for ov in ("db", "seq"):
            ma = summa_matmul.lower(a._data, b._data, mesh, px.FLOAT32,
                                    overlap=ov).compile().memory_analysis()
            assert ma.temp_size_in_bytes <= 28752, (ov, ma)

    def test_fetch_counter(self):
        from dislib_tpu.ops.summa import summa_matmul
        _init((2, 2))
        a = ds.array(_mk((256, 256))).force()
        _prof.reset_counters()
        summa_matmul(a._data, a._data, _mesh.get_mesh(), px.FLOAT32)
        ds.matmul(a, a).force()          # the router's own call
        assert _prof.schedule_counters().get("summa_fetch:direct") == 2
        assert _prof.counters()["dispatch_by"].get("summa_matmul") == 2

    def test_f64_x64_mode(self):
        skip_unless_devices(8)
        from dislib_tpu.ops.summa import summa_matmul
        ds.init((4, 2))
        mesh = _mesh.get_mesh()
        with jax.enable_x64(True):
            x = _mk((32, 32)).astype(np.float64)
            ad = jax.device_put(
                np.pad(x, ((0, 0), (0, 0))), _mesh.data_sharding())
            db = np.asarray(summa_matmul(ad, ad, mesh, px.FLOAT32,
                                         overlap="db"))
            seq = np.asarray(summa_matmul(ad, ad, mesh, px.FLOAT32,
                                          overlap="seq"))
            assert db.dtype == np.float64   # f32 floor passes f64 through
            np.testing.assert_array_equal(db, seq)
            np.testing.assert_allclose(db, x @ x, rtol=1e-12)

    def test_one_dispatch_per_schedule(self):
        skip_unless_devices(8)
        from dislib_tpu.ops.summa import summa_matmul
        ds.init((4, 2))
        mesh = _mesh.get_mesh()
        a = ds.array(_mk((96, 64))).force()
        b = ds.array(_mk((64, 80), seed=1)).force()
        for ov in ("db", "seq"):
            summa_matmul(a._data, b._data, mesh, px.FLOAT32, overlap=ov)
            _prof.reset_counters()
            summa_matmul(a._data, b._data, mesh, px.FLOAT32, overlap=ov)
            assert _prof.dispatch_count() == 1, \
                f"summa overlap={ov} is not one dispatch"

    def test_env_routes_matmul_and_counts_schedule(self, monkeypatch):
        skip_unless_devices(8)
        ds.init((4, 2))
        a = ds.array(_mk((96, 64))).force()
        b = ds.array(_mk((64, 80), seed=1)).force()
        monkeypatch.setenv("DSLIB_OVERLAP", "seq")
        _prof.reset_counters()
        ds.matmul(a, b, algorithm="summa").force()
        assert _prof.schedule_counters().get("summa_matmul:seq") == 1
        monkeypatch.delenv("DSLIB_OVERLAP", raising=False)
        _prof.reset_counters()
        ds.matmul(a, b, algorithm="summa").force()
        assert _prof.schedule_counters().get("summa_matmul:db") == 1

    def test_db_green_under_debug_nans(self):
        skip_unless_devices(8)
        from dislib_tpu.ops.summa import summa_matmul
        ds.init((4, 2))
        mesh = _mesh.get_mesh()
        a = ds.array(_mk((32, 32))).force()
        jax.config.update("jax_debug_nans", True)
        try:
            out = summa_matmul(a._data, a._data, mesh, px.FLOAT32,
                               overlap="db")
            np.asarray(out)
        finally:
            jax.config.update("jax_debug_nans", False)


# ---------------------------------------------------------------------------
# 4. overlap audit: the collective/compute dependence shape, by opcode
# ---------------------------------------------------------------------------

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                   "reduce-scatter", "collective-permute",
                   "collective-broadcast")


def _panel_def_use(hlo):
    """(def→operands map, collectives, dots), in program order, of the
    computation of an HLO text that carries the panel rounds: the one
    holding both a collective and a dot, each known by its OPCODE (a
    ``-start``/``-done`` pair counts as its ``-done``, whose result the
    consumer reads)."""
    for block in re.split(r"\n\}\n", hlo):
        defs, colls, dots = {}, [], []
        for line in block.splitlines():
            m = _OP_RE.match(line)
            if not m:
                continue
            dst, op = m.group(1), m.group(3)
            rhs = line.split("=", 1)[1].split(", metadata=")[0]
            defs[dst] = [t for t in re.findall(r"%?([A-Za-z_][\w.\-]*)", rhs)
                         if t != dst]
            base = op[:-5] if op.endswith("-done") else op
            if base in _COLLECTIVE_OPS and not op.endswith("-start"):
                colls.append(dst)
            elif op == "dot":
                dots.append(dst)
        if colls and dots:
            return defs, colls, dots
    raise AssertionError("no computation with a collective and a dot")


def _transitive_inputs(defs, roots):
    seen, stack = set(), list(roots)
    while stack:
        cur = stack.pop()
        for op in defs.get(cur, ()):
            if op not in seen:
                seen.add(op)
                stack.append(op)
    return seen


@pytest.fixture(scope="module")
def describe_v5e():
    """``describe_v5e(name)``: a described (not attached) v5e topology,
    for the TPU compiler's own schedule; skips where none can be."""
    from jax.experimental import topologies

    def describe(name):
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name=name)
        except Exception as e:
            pytest.skip(f"no {name} topology can be described here: {e}")
    return describe


@contextlib.contextmanager
def _compile_cache_off():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: off around such compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


class TestCompiledOverlapAudit:
    """The tentpole's scheduling claim.  ``round_pipeline`` traces one
    flat block whose order is data dependence through its barriers, so
    the dependence shape is audited on the program handed to the
    compiler (the CPU's compiler drops the barriers and runs every copy
    first, under either schedule): under db the next round's copies
    neither feed the first dot nor wait for it, so they may run under
    it; under seq every collective does one or the other (one strict
    chain), proving the audit is not vacuous.  What the TPU's compiler
    makes of it is audited on ITS schedule, for a described v5e:2x2."""

    def _hlo(self, overlap):
        from dislib_tpu.ops.summa import summa_matmul
        ds.init((4, 2))
        mesh = _mesh.get_mesh()
        a = ds.array(_mk((96, 64))).force()
        b = ds.array(_mk((64, 80), seed=1)).force()
        return summa_matmul.lower(a._data, b._data, mesh, px.FLOAT32,
                                  overlap=overlap).as_text(dialect="hlo")

    def _free_of_first_dot(self, overlap):
        defs, colls, dots = _panel_def_use(self._hlo(overlap))
        assert len(colls) == 8 and len(dots) == 4
        feeding = _transitive_inputs(defs, dots[:1])
        return [c for c in colls if c not in feeding
                and dots[0] not in _transitive_inputs(defs, [c])]

    def test_db_decouples_collective_from_dot(self):
        skip_unless_devices(8)
        free = self._free_of_first_dot("db")
        assert len(free) == 2, (
            "double-buffered SUMMA: exactly the next round's two copies "
            f"are independent of the first dot, got {free}")

    def test_seq_is_a_strict_chain(self):
        skip_unless_devices(8)
        stray = self._free_of_first_dot("seq")
        assert not stray, (
            "sequential SUMMA has a collective outside the dot chain — "
            "the seq baseline no longer is the strict-phase schedule "
            f"(stray: {stray})")

    @pytest.mark.parametrize("overlap", ["db", "seq"])
    @pytest.mark.parametrize("topology,grid", [("v5e:2x2", (2, 2)),
                                               ("v5e:2x4", (4, 2))])
    def test_tpu_schedule_runs_copies_under_gemms(self, describe_v5e,
                                                  topology, grid, overlap):
        """The TPU compiler's own schedule, for the cell's mesh and for
        one with four rounds: collective-permutes alone, no ``while``,
        the fold's add fused into every GEMM but the first, and under db
        the next round's two copies are in flight across every GEMM but
        the last; under seq none ever is."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from dislib_tpu.ops.summa import summa_matmul, summa_steps
        topo = describe_v5e(topology)
        mesh = Mesh(np.array(topo.devices).reshape(grid),
                    (_mesh.ROWS, _mesh.COLS))
        arg = jax.ShapeDtypeStruct(
            (2048, 2048), jnp.float32, sharding=NamedSharding(
                mesh, PartitionSpec(_mesh.ROWS, _mesh.COLS)))
        with _compile_cache_off():
            text = summa_matmul.lower(arg, arg, mesh, px.FLOAT32,
                                      overlap=overlap).compile().as_text()
        assert "all-reduce" not in text and " while(" not in text
        gemms, in_flight, across = [], set(), []
        for line in text[text.index("\nENTRY "):].splitlines():
            m = _OP_RE.match(line)
            if not m:
                continue
            name, op = m.group(1), m.group(3)
            if op == "collective-permute-start":
                in_flight.add(name)
            elif op == "collective-permute-done":
                in_flight.discard(re.search(
                    r"collective-permute-done\(%?([\w.\-]+)", line).group(1))
            elif op == "fusion" and "kind=kOutput" in line:
                gemms.append(name)
                across.append(len(in_flight))
        rounds = summa_steps(mesh)
        assert len(gemms) == rounds, gemms
        assert sum("convolution_add" in n for n in gemms) == rounds - 1
        assert across == ([2] * (rounds - 1) + [0] if overlap == "db"
                          else [0] * rounds), across


class TestCompiledTallOrthonormalisation:
    """What the TPU's compiler makes of ``decomposition/tsqr.py`` (it
    lives here because one file a run may describe a topology: the
    fixture above).  The CPU's compiler gives the conditional between Q1
    and the fall-back a panel of its own; the TPU's does not, and that is
    what the cell runs."""

    def test_tpu_program_copies_no_panel_and_holds_two(self, describe_v5e):
        """A shard's blocked CholeskyQR2 with its Householder fall-back,
        compiled for a described v5e (8 blocks of 8 192 rows, 128
        columns): no ``copy`` of panel size anywhere (the conditional's
        well-conditioned branch hands Q1 back in the buffer it came in,
        its fall-back ends in a product that writes that buffer), and one
        panel of temporaries beside the operand's and the result's."""
        import importlib
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        tsqr = importlib.import_module("dislib_tpu.decomposition.tsqr")
        topo = describe_v5e("v5e:2x2")
        mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                    (_mesh.ROWS, _mesh.COLS))
        m, n = 65_536, 128
        assert tsqr.local_qr_route(m, n, True) == "blocked"
        arg = jax.ShapeDtypeStruct(
            (m, n), jnp.float32, sharding=NamedSharding(
                mesh, PartitionSpec(_mesh.ROWS, None)))
        with _compile_cache_off():
            # the panel a temporary of the program's own, as random_svd's is
            compiled = jax.jit(lambda x: tsqr._tsqr_shardmap(
                x + 1.0, mesh, 1, cholqr=True)).lower(arg).compile()
        text = compiled.as_text()
        assert " conditional(" in text          # the fall-back is there
        copies = re.findall(rf"(\S+) = f32\[{m},{n}\]\S* copy\(", text)
        assert not copies, copies
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 1.1 * m * n * 4, \
            mem.temp_size_in_bytes


class TestCompiledMixtureEStep:
    """What the TPU's compiler makes of the mixture's cut E-step
    (``ops/base.py::_em_log_prob`` over ``precision.short_left``,
    ``short_right``, ``pdot_packed``), at the widths of the cell
    ``gmm_fit_sustained`` (it lives here for the fixture above)."""

    def test_tpu_block_loop_has_three_products_and_no_split_of_the_factors(
            self, describe_v5e, monkeypatch):
        """``_gm_fit`` for a described v5e, 8 blocks of 7 680 rows, d =
        50, k = 16.  In the loop over the blocks: three E-step products
        and no more, plain GEMMs whose result is the whole (block, e k8)
        array (not the convolution over e that the compiler folds a
        group's sum into where nothing stands in its way), each against
        an operand the loop carries (nothing in the loop writes anything
        shaped like one: the factors are split and packed once an
        iteration); the block's rows split in ONE fusion, packed by six
        that only move the parts, and copied once into the chunk-major
        order the products read; and no more ops a block than the
        one-GEMM program had (27)."""
        import importlib
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        gm = importlib.import_module("dislib_tpu.cluster.gm")
        topo = describe_v5e("v5e:2x2")
        mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                    (_mesh.ROWS, _mesh.COLS))
        # a described chip is not the default backend: say that it packs
        monkeypatch.setattr(px, "_PACK_BACKENDS", (jax.default_backend(),))
        was = _mesh.get_mesh()
        _mesh.set_mesh(mesh)
        m, d, k = 8 * 7_680, 50, 16
        rows = NamedSharding(mesh, PartitionSpec(_mesh.ROWS, None))
        whole = NamedSharding(mesh, PartitionSpec())
        try:
            with _compile_cache_off():
                text = gm._gm_fit.lower(
                    jax.ShapeDtypeStruct((m, d), jnp.float32, sharding=rows),
                    (m, d), k, "full", 1e-6, 0.0, 2,
                    (jax.ShapeDtypeStruct((k,), jnp.float32, sharding=whole),
                     jax.ShapeDtypeStruct((k, d), jnp.float32,
                                          sharding=whole),
                     jax.ShapeDtypeStruct((k, d, d), jnp.float32,
                                          sharding=whole))
                ).compile().as_text()
        finally:
            _mesh.set_mesh(was)
            jax.clear_caches()          # no packed program is left behind
        e_gemm = "dslib.gm.e_step/dslib.pdot/dot_general"
        body = next(block for block in re.split(r"\n\}\n", text)
                    if re.search(rf" fusion\(.*kind=kOutput.*{e_gemm}",
                                 block))
        ops = [_OP_RE.match(line) for line in body.splitlines()]
        ops = [(m_.group(1), m_.group(2), m_.group(3), m_.string)
               for m_ in ops if m_]
        gemms = [name for name, _, op, line in ops
                 if op == "fusion" and "kind=kOutput" in line
                 and e_gemm in line]
        assert len(gemms) == 3, gemms
        shapes = sorted(shape.split("{")[0] for name, shape, _, _ in ops
                        if name in gemms)
        assert shapes == ["f32[7680,256]", "f32[7680,256]",
                          "f32[7680,288]"], shapes
        # the factors' operands: read, never written, in the loop
        for right in ("bf16[96,256]", "bf16[192,256]", "bf16[384,288]"):
            made = [name for name, shape, op, _ in ops
                    if shape.startswith(right) and op not in (
                        "get-tuple-element", "bitcast", "parameter")]
            assert not made, (right, made)
        # the block's operand: one split, six writes, one copy
        split = [name for name, shape, op, _ in ops if op == "fusion"
                 and shape.startswith("(bf16[7680,64]")]
        assert len(split) == 1, split
        left = [(name, op) for name, shape, op, _ in ops
                if shape.startswith("bf16[7680,4,6,16]")]
        assert [op for _, op in left] == ["fusion"] * 6 + ["copy"], left
        device_ops = [name for name, shape, op, _ in ops
                      if op in ("fusion", "copy", "slice", "convolution")
                      and not shape.startswith(("f32[]", "s32[]", "pred[]"))]
        assert len(device_ops) <= 27, device_ops


# ---------------------------------------------------------------------------
# 5. schedule-equivalence grid: panel rechunk
# ---------------------------------------------------------------------------

class TestRechunkSchedules:
    @pytest.mark.parametrize("dtype", [np.float32, np.int32])
    def test_db_bit_equals_seq(self, dtype):
        skip_unless_devices(8)
        from dislib_tpu.ops import rechunk as _rc
        ds.init((4, 2))
        x = (_mk((40, 12)) * 100).astype(dtype)
        a = ds.array(x).force()
        ds.init((2, 4))
        dst = _mesh.get_mesh()
        db = np.asarray(_rc.panel_rechunk(a._data, a.shape, dst, 4,
                                          overlap="db"))
        seq = np.asarray(_rc.panel_rechunk(a._data, a.shape, dst, 4,
                                           overlap="seq"))
        np.testing.assert_array_equal(db, seq)
        np.testing.assert_array_equal(db[:40, :12], x)

    def test_f64_x64_mode(self):
        skip_unless_devices(8)
        from dislib_tpu.ops import rechunk as _rc
        with jax.enable_x64(True):
            ds.init((4, 2))
            x = _mk((24, 8)).astype(np.float64)
            a = ds.array(x, dtype=np.float64).force()
            ds.init((2, 4))
            dst = _mesh.get_mesh()
            db = np.asarray(_rc.panel_rechunk(a._data, a.shape, dst, 2,
                                              overlap="db"))
            seq = np.asarray(_rc.panel_rechunk(a._data, a.shape, dst, 2,
                                               overlap="seq"))
            assert db.dtype == np.float64
            np.testing.assert_array_equal(db, seq)

    def test_one_dispatch_and_schedule_counter(self):
        skip_unless_devices(8)
        from dislib_tpu.ops import rechunk as _rc
        ds.init((4, 2))
        a = ds.array(_mk((40, 12))).force()
        ds.init((2, 4))
        dst = _mesh.get_mesh()
        _rc.panel_rechunk(a._data, a.shape, dst, 4, overlap="db")  # warm
        _prof.reset_counters()
        _rc.panel_rechunk(a._data, a.shape, dst, 4, overlap="db")
        assert _prof.dispatch_count() == 1
        assert _prof.schedule_counters().get("rechunk_panels:db") == 1

    def test_db_poisoned_pad_rezeroes(self):
        """Poisoned-pad regression for the NEW schedule: the
        double-buffered exchange rebuilds pads from a zero canvas."""
        skip_unless_devices(8)
        ds.init((4, 2))
        x = _mk((20, 6), seed=7)
        a = ds.array(x).force()
        bad = a._data.at[20:, :].set(jnp.nan).at[:, 6:].set(jnp.inf)
        from dislib_tpu.data.array import Array
        a_bad = Array(bad, (20, 6))
        ds.init((2, 4))
        out = ds.rechunk(a_bad, schedule="panels", overlap="db")
        full = np.asarray(out._data)
        np.testing.assert_array_equal(full[:20, :6], x)
        assert np.all(full[20:] == 0) and np.all(full[:, 6:] == 0)

    def test_memory_analysis_reports_db_budget(self):
        skip_unless_devices(8)
        from dislib_tpu.ops import rechunk as _rc
        ds.init((4, 2))
        a = ds.array(_mk((64, 16))).force()
        ds.init((2, 4))
        dst = _mesh.get_mesh()
        ma_db = _rc.panel_memory_analysis(a._data, a.shape, dst, 4,
                                          overlap="db")
        ma_seq = _rc.panel_memory_analysis(a._data, a.shape, dst, 4,
                                           overlap="seq")
        assert ma_db["overlap"] == "db" and ma_seq["overlap"] == "seq"
        # the documented analytic budget: exactly one extra in-flight
        # panel for the double buffer
        panel = ma_db["in_bytes"] // ma_db["panels"]
        assert ma_db["analytic_temp_bytes"] \
            == ma_seq["analytic_temp_bytes"] + panel
        if ma_db["peak_live_ratio"] is not None:
            k = 4
            assert ma_db["peak_live_ratio"] <= min(1 + 2 / k, 1.5), \
                "double-buffered peak-live exceeds the documented bound"


# ---------------------------------------------------------------------------
# 6. schedule-equivalence grid: ring kernels + estimators
# ---------------------------------------------------------------------------

class TestRingSchedules:
    def test_kneighbors_db_bit_equals_seq(self):
        skip_unless_devices(8)
        from dislib_tpu.ops.ring import ring_kneighbors
        ds.init((4, 2))
        mesh = _mesh.get_mesh()
        q = ds.array(_mk((37, 5))).force()
        f = ds.array(_mk((53, 5), seed=1)).force()
        d_db, i_db = ring_kneighbors(q._data, f._data, mesh, 5, 53,
                                     overlap="db")
        d_seq, i_seq = ring_kneighbors(q._data, f._data, mesh, 5, 53,
                                       overlap="seq")
        np.testing.assert_array_equal(np.asarray(d_db), np.asarray(d_seq))
        np.testing.assert_array_equal(np.asarray(i_db), np.asarray(i_seq))

    def test_neigh_count_min_db_bit_equals_seq(self):
        skip_unless_devices(8)
        from dislib_tpu.ops.ring import ring_neigh_count_min
        ds.init((4, 2))
        mesh = _mesh.get_mesh()
        a = ds.array(_mk((48, 5))).force()
        mp = a._data.shape[0]
        ids = jnp.arange(mp, dtype=jnp.int32)
        valid = ids < 48
        outs = {}
        for ov in ("db", "seq"):
            c, mn = ring_neigh_count_min(a._data, jnp.float32(0.3), ids,
                                         valid, jnp.int32(mp), mesh,
                                         overlap=ov)
            outs[ov] = (np.asarray(c), np.asarray(mn))
        np.testing.assert_array_equal(outs["db"][0], outs["seq"][0])
        np.testing.assert_array_equal(outs["db"][1], outs["seq"][1])

    def test_kneighbors_estimator_one_dispatch_and_env_routing(
            self, monkeypatch):
        skip_unless_devices(8)
        ds.init((4, 2))
        f = ds.array(_mk((64, 4))).force()
        q = ds.array(_mk((16, 4), seed=2)).force()
        nn = ds.NearestNeighbors(n_neighbors=3, ring=True).fit(f)
        nn.kneighbors(q)                                     # warm
        _prof.reset_counters()
        nn.kneighbors(q)
        assert _prof.counters()["dispatch_by"].get("ring_kneighbors") == 1
        assert _prof.schedule_counters().get("ring_kneighbors:db") == 1
        monkeypatch.setenv("DSLIB_OVERLAP", "seq")
        _prof.reset_counters()
        d_seq, i_seq = nn.kneighbors(q)
        assert _prof.schedule_counters().get("ring_kneighbors:seq") == 1
        monkeypatch.delenv("DSLIB_OVERLAP", raising=False)
        d_db, i_db = nn.kneighbors(q)
        np.testing.assert_array_equal(np.asarray(i_db.collect()),
                                      np.asarray(i_seq.collect()))
        np.testing.assert_array_equal(np.asarray(d_db.collect()),
                                      np.asarray(d_seq.collect()))

    def test_ring_dbscan_schedules_agree(self, monkeypatch):
        skip_unless_devices(8)
        from dislib_tpu.cluster import dbscan as dbmod
        ds.init((4, 2))
        monkeypatch.setattr(dbmod, "_RING", True)
        x = np.vstack([_mk((40, 4)), _mk((40, 4), seed=1) + 3.0]) \
            .astype(np.float32)
        labels = {}
        for ov in ("db", "seq"):
            monkeypatch.setenv("DSLIB_OVERLAP", ov)
            _prof.reset_counters()
            model = ds.DBSCAN(eps=0.8, min_samples=3).fit(ds.array(x))
            assert any(k == f"ring_neigh:{ov}"
                       for k in _prof.schedule_counters()), \
                f"dbscan ring tier did not record schedule {ov}"
            labels[ov] = model.labels_.copy()
        np.testing.assert_array_equal(labels["db"], labels["seq"])

    def test_ring_daura_schedules_agree(self, monkeypatch):
        skip_unless_devices(8)
        from dislib_tpu.cluster import daura as damod
        ds.init((4, 2))
        monkeypatch.setattr(damod, "_RING", True)
        x = _mk((60, 6), seed=5)
        labels = {}
        for ov in ("db", "seq"):
            monkeypatch.setenv("DSLIB_OVERLAP", ov)
            model = ds.Daura(cutoff=0.45).fit(ds.array(x))
            labels[ov] = model.labels_.copy()
        np.testing.assert_array_equal(labels["db"], labels["seq"])

    def test_db_poisoned_fit_pad_rows_stay_masked(self):
        """Poisoned-pad regression for the db ring schedule: garbage in
        the fitted backing's pad rows must never become a neighbor
        (the ids >= m_fit mask, preserved by the pipelined fold)."""
        skip_unless_devices(8)
        from dislib_tpu.ops.ring import ring_kneighbors
        ds.init((4, 2))
        mesh = _mesh.get_mesh()
        q = ds.array(_mk((16, 4))).force()
        f = ds.array(_mk((20, 4), seed=1)).force()
        clean = ring_kneighbors(q._data, f._data, mesh, 3, 20, overlap="db")
        # pad rows moved to the query cloud's center: unmasked, they
        # would beat most real rows into the top-k
        poisoned = f._data.at[20:, :].set(0.5)
        got = ring_kneighbors(q._data, poisoned, mesh, 3, 20, overlap="db")
        np.testing.assert_array_equal(np.asarray(clean[1]),
                                      np.asarray(got[1]))
        np.testing.assert_array_equal(np.asarray(clean[0]),
                                      np.asarray(got[0]))

    def test_db_green_under_debug_nans(self):
        skip_unless_devices(8)
        from dislib_tpu.ops.ring import ring_neigh_count_min
        ds.init((4, 2))
        mesh = _mesh.get_mesh()
        a = ds.array(_mk((24, 4))).force()
        mp = a._data.shape[0]
        ids = jnp.arange(mp, dtype=jnp.int32)
        jax.config.update("jax_debug_nans", True)
        try:
            c, _ = ring_neigh_count_min(a._data, jnp.float32(0.3), ids,
                                        ids < 24, jnp.int32(mp), mesh,
                                        overlap="db")
            np.asarray(c)
        finally:
            jax.config.update("jax_debug_nans", False)


# ---------------------------------------------------------------------------
# 7. the Pallas fallback route
# ---------------------------------------------------------------------------

class TestPallasRoute:
    def test_panel_gemm_matches_pdot(self):
        from dislib_tpu.ops import pallas_kernels as _pk
        a = jnp.asarray(_mk((48, 32)))
        b = jnp.asarray(_mk((32, 40), seed=1))
        got = np.asarray(_pk.panel_gemm(a, b, px.FLOAT32))
        want = np.asarray(px.pdot(a, b, px.FLOAT32))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        assert got.dtype == want.dtype

    def test_distances_matches_xla_formulation(self):
        from dislib_tpu.ops import pallas_kernels as _pk
        from dislib_tpu.ops.base import distances_sq
        a = jnp.asarray(_mk((24, 6)))
        b = jnp.asarray(_mk((20, 6), seed=1))
        got = np.asarray(_pk.distances_sq(a, b))
        want = np.asarray(distances_sq(np.asarray(a), np.asarray(b)))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert (got >= 0).all()

    def test_summa_pallas_schedule_matches(self):
        skip_unless_devices(8)
        from dislib_tpu.ops.summa import summa_matmul
        ds.init((4, 2))
        mesh = _mesh.get_mesh()
        a = ds.array(_mk((96, 64))).force()
        b = ds.array(_mk((64, 80), seed=1)).force()
        db = np.asarray(summa_matmul(a._data, b._data, mesh, px.FLOAT32,
                                     overlap="db"))
        pl = np.asarray(summa_matmul(a._data, b._data, mesh, px.FLOAT32,
                                     overlap="pallas"))
        np.testing.assert_allclose(pl, db, rtol=1e-6,
                                   atol=1e-6 * np.abs(db).max())

    def test_ring_pallas_schedule_matches(self):
        skip_unless_devices(8)
        from dislib_tpu.ops.ring import ring_neigh_count_min
        ds.init((4, 2))
        mesh = _mesh.get_mesh()
        a = ds.array(_mk((48, 5))).force()
        mp = a._data.shape[0]
        ids = jnp.arange(mp, dtype=jnp.int32)
        valid = ids < 48
        c_db, m_db = ring_neigh_count_min(a._data, jnp.float32(0.3), ids,
                                          valid, jnp.int32(mp), mesh,
                                          overlap="db")
        c_pl, m_pl = ring_neigh_count_min(a._data, jnp.float32(0.3), ids,
                                          valid, jnp.int32(mp), mesh,
                                          overlap="pallas")
        np.testing.assert_array_equal(np.asarray(c_db), np.asarray(c_pl))
        np.testing.assert_array_equal(np.asarray(m_db), np.asarray(m_pl))

    def test_distances_threads_explicit_precision(self):
        """Regression: the pallas branch of ``ops/base.distances_sq`` must
        pass the caller's explicit MXU precision to the cross GEMM, not
        silently drop it (review-found)."""
        from dislib_tpu.ops.base import distances_sq
        a = jnp.asarray(_mk((24, 6)))
        b = jnp.asarray(_mk((20, 6), seed=1))
        got = np.asarray(distances_sq(a, b, precision="highest",
                                      use_pallas=True))
        want = np.asarray(distances_sq(a, b, precision="highest"))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("route", ["db", "pallas"])
    def test_tiled_dbscan_routes_and_matches(self, monkeypatch, route):
        """The single-device tiled tier has no collective to overlap, but
        ``DSLIB_OVERLAP=pallas`` must still pick the Pallas inner kernel
        (observable via the ``tiled_neigh`` schedule counter) and cluster
        identically (review-found: the knob used to be a silent no-op
        here)."""
        from dislib_tpu.cluster import dbscan as dbmod
        from dislib_tpu.ops import tiled as _tiled
        if route == "pallas" and _ov.resolve("pallas") != "pallas":
            pytest.skip("pallas unavailable on this backend")
        monkeypatch.setattr(dbmod, "_RING", False)
        monkeypatch.setattr(dbmod, "_DENSE_MAX", 0)
        monkeypatch.setattr(_tiled, "TILE", 64)
        x = np.vstack([_mk((25, 4)), _mk((25, 4), seed=1) + 3.0]) \
            .astype(np.float32)
        monkeypatch.setenv("DSLIB_OVERLAP", route)
        _prof.reset_counters()
        model = ds.DBSCAN(eps=0.8, min_samples=3).fit(ds.array(x))
        assert _prof.schedule_counters().get(f"tiled_neigh:{route}"), \
            f"dbscan tiled tier did not record schedule {route}"
        oracle = ds.DBSCAN(eps=0.8, min_samples=3)
        monkeypatch.setenv("DSLIB_OVERLAP", "seq")
        oracle.fit(ds.array(x))
        np.testing.assert_array_equal(model.labels_, oracle.labels_)

    def test_tiled_daura_routes_pallas(self, monkeypatch):
        from dislib_tpu.cluster import daura as damod
        from dislib_tpu.ops import tiled as _tiled
        if _ov.resolve("pallas") != "pallas":
            pytest.skip("pallas unavailable on this backend")
        monkeypatch.setattr(damod, "_RING", False)
        monkeypatch.setattr(damod, "_DENSE_MAX", 0)
        monkeypatch.setattr(_tiled, "TILE", 64)
        x = _mk((40, 6), seed=5)
        monkeypatch.setenv("DSLIB_OVERLAP", "pallas")
        _prof.reset_counters()
        model = ds.Daura(cutoff=0.45).fit(ds.array(x))
        assert _prof.schedule_counters().get("tiled_neigh:pallas"), \
            "daura tiled tier did not record the pallas schedule"
        oracle = ds.Daura(cutoff=0.45)
        monkeypatch.setenv("DSLIB_OVERLAP", "db")
        oracle.fit(ds.array(x))
        np.testing.assert_array_equal(model.labels_, oracle.labels_)


# ---------------------------------------------------------------------------
# 8. the DSLIB_SUMMA_MIN_DIM router knob
# ---------------------------------------------------------------------------

class TestSummaMinDimKnob:
    def test_env_knob_routes_small_dims_to_summa(self, monkeypatch):
        skip_unless_devices(8)
        ds.init((4, 2))
        a = ds.array(_mk((64, 64))).force()
        b = ds.array(_mk((64, 64), seed=1)).force()
        # default gate (256): a 64-dim CONCRETE product stays on the
        # fusion-graph XLA path
        monkeypatch.delenv("DSLIB_SUMMA_MIN_DIM", raising=False)
        out = ds.matmul(a, b)
        assert out.is_lazy, "small concrete product left the fusion graph"
        # knob lowered: the same product auto-routes to SUMMA
        monkeypatch.setenv("DSLIB_SUMMA_MIN_DIM", "16")
        _prof.reset_counters()
        out = ds.matmul(a, b)
        assert not out.is_lazy
        assert _prof.counters()["dispatch_by"].get("summa_matmul") == 1
        assert any(k.startswith("summa_matmul:")
                   for k in _prof.schedule_counters())

    def test_env_knob_respected_by_module_default(self, monkeypatch):
        from dislib_tpu.math import base as mb
        monkeypatch.delenv("DSLIB_SUMMA_MIN_DIM", raising=False)
        assert mb._summa_min_dim() == mb._SUMMA_MIN_DIM
        monkeypatch.setenv("DSLIB_SUMMA_MIN_DIM", "512")
        assert mb._summa_min_dim() == 512
