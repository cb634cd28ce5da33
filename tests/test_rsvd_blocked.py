"""``ds.random_svd`` and the tall orthonormalisation under it
(``decomposition/randomsvd.py``, ``decomposition/tsqr.py``) walking the
rows in blocks: held to the plain reference (``benchmark/reference/rsvd.py``:
Halko, Martinsson and Tropp's subspace iteration with Householder QR,
nothing of the program) from the same test matrix, on one device and on
the suite's virtual mesh, with ragged last blocks and padding rows; and
what the issue that brought it promised: Grams that are compensated sums
of blocks' Grams, an ill-conditioned panel that still takes the
Householder tree, no temporary of A's size, one dispatch a call, the
counter, the span and the scopes, and the draw of the test matrix as a
contract; and what the issue that folded the assembly promised: a tall
orthonormalisation passes over its panel four times (Gram, application,
Gram, application), its Q the old composition (Q1 R2^-1) Q2_i, counted
under ``tsqr_assemble:folded``.

Tolerances.  Program and reference are both float32 at 'highest' and
orthonormalise by different factorisations (CholeskyQR2 or the program's
tree against a two-level Householder QR): singular values agree to a few
1e-7 of the largest, a rank-r approximation's rows and the right
subspace to a few 1e-6 (a singular vector turns by rounding over the gap
to its neighbour, 10% here), U is orthogonal to 1e-6 (its lift is one
float32 product).  Readings on this rig are 3 to 10 times smaller than
the limits below.
"""

import importlib
import itertools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dislib_tpu as ds
from dislib_tpu.ops import base as _ops
from dislib_tpu.utils import profiling

# the package re-exports the functions under the modules' names
_rsvd = importlib.import_module("dislib_tpu.decomposition.randomsvd")
_tsqr = importlib.import_module("dislib_tpu.decomposition.tsqr")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import rsvd as ref  # noqa: E402

N, NSV, OVER = 96, 20, 12
# 8 x 626 rows on the mesh, 5001 on one device: with blocks of 512 rows
# (below) every device ends on a ragged block, and the last rows are padding
ROWS = 5001
LIMITS = {"singular_values_gap": 3e-6, "approx_rows_gap": 2e-5,
          "orthogonality_gap": 1e-5, "right_subspace_gap": 2e-5}


def _mesh_of(devices):
    if devices == 1:
        ds.init((1, 1), devices=jax.devices()[:1])
    elif len(jax.devices()) < devices:
        pytest.skip(f"needs {devices} devices")
    else:
        ds.init((devices, 1))


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of one quantum (512 rows), so that a few thousand rows are
    several blocks and a ragged last one."""
    monkeypatch.setattr(_ops, "_EM_TILE_BYTES", 512 * 4 * 4 * (NSV + OVER))


@pytest.fixture(params=["1", "0"], ids=["cholqr", "householder"])
def local_qr(request, monkeypatch):
    monkeypatch.setenv("DSLIB_TSQR_CHOLQR", request.param)
    return "blocked" if request.param == "1" else "householder_tree"


def _data(seed, rows=ROWS, n=N, ratio=0.9, directions=48):
    rng = np.random.RandomState(seed)
    w = np.linalg.qr(rng.randn(n, directions))[0]
    x = (rng.randn(rows, directions) * ratio ** np.arange(directions)) \
        @ w.T + 1e-4 * rng.randn(rows, n)
    return x.astype(np.float32)


def _reference(x, state, rows_idx, iters=2, nsv=NSV, over=OVER):
    """The reference's summary from the contract's draw; its blocks take
    all the rows at once (no sum here is long enough to round)."""
    m, n = x.shape
    u, s, v = ref.fit(jnp.asarray(x), ref.test_matrix(state, n, nsv + over),
                      iters, nsv, m, m)
    return ref.summary(u, s, v, rows_idx, m, m)


def _program(x, state, rows_idx, iters=2, nsv=NSV, over=OVER):
    u, s, v = ds.random_svd(ds.array(x), iters=iters, nsv=nsv,
                            oversample=over, random_state=state)
    m = x.shape[0]
    assert u.shape == (m, nsv) and s.shape == (1, nsv) \
        and v.shape == (x.shape[1], nsv)
    return ref.summary(jnp.asarray(u.collect()), s.collect(), v.collect(),
                       rows_idx, m, m)


# -- (a) the call against the plain reference ---------------------------------

@pytest.mark.parametrize("devices", [1, 8])
def test_call_agrees_with_the_plain_reference(small_blocks, local_qr,
                                              devices):
    _mesh_of(devices)
    x = _data(3)
    rows_idx = ref.sample_rows(3, ROWS, 256)
    profiling.reset_counters()
    got = _program(x, 11, rows_idx)
    want = _reference(x, 11, rows_idx)
    for name, value in ref.compare(got, want).items():
        assert value <= LIMITS[name], (name, value)
    routes = [k for k in profiling.schedule_counters()
              if k.startswith("tsqr_local:")]
    if routes:                         # this shape traced in this process
        assert f"tsqr_local:{local_qr}" in routes


@pytest.mark.parametrize("iters,nsv,over", [(0, 8, 8), (1, 30, 2),
                                            (3, 5, 10)])
def test_other_ranks_and_iteration_counts_agree_too(small_blocks, iters,
                                                    nsv, over):
    _mesh_of(8)
    x = _data(4)
    rows_idx = ref.sample_rows(4, ROWS, 256)
    got = _program(x, 5, rows_idx, iters, nsv, over)
    want = _reference(x, 5, rows_idx, iters, nsv, over)
    for name, value in ref.compare(got, want).items():
        assert value <= LIMITS[name], (name, value)


def test_the_reference_finds_the_singular_values_numpy_finds():
    """The reference is Halko's algorithm and no copy of the program: on a
    spectrum that falls by 10% a direction its leading values are the
    matrix's own."""
    x = _data(6, rows=2000)
    u, s, v = ref.fit(jnp.asarray(x), ref.test_matrix(0, N, 40), 2, 10,
                      2000, 500)
    want = np.linalg.svd(x.astype(np.float64), compute_uv=False)
    np.testing.assert_allclose(s, want[:10], rtol=1e-4)
    u = np.asarray(u, np.float64)
    np.testing.assert_allclose((u * s) @ v.T @ v, x.astype(np.float64) @ v,
                               atol=2e-4 * want[0])


# -- (b) blocks --------------------------------------------------------------

def test_blocks_are_derived_from_the_shapes():
    # the cell's panel: 192 whole blocks of 8192 rows, a row of 256 float32
    # held four times
    assert _tsqr._panel_block(1_572_864, 256) == 8_192
    assert _tsqr.local_qr_route(1_572_864, 256, True) == "blocked"
    # a narrow panel is capped by the rows one product may contract
    assert _tsqr._panel_block(1_000_000, 16) == 8_192
    # a wide one by the tile budget
    assert _tsqr._panel_block(1_000_000, 1024) == 2_048
    # one block where the rows fit
    assert _tsqr._panel_block(1_024, 256) == 1_024
    assert _tsqr.local_qr_route(1_024, 256, True) == "one_product"
    assert _tsqr.local_qr_route(1_572_864, 256, False) == "householder_tree"
    # the products over A's rows: a row of 1024 + 256 float32
    assert _ops.row_block(1_572_864, _ops.contract_row_bytes(
        4 * (1024 + 256))) == 6_144
    # the EM step's blocks are what they were
    assert _ops.em_block(24_000_000, 50, 16) == 7_680


@pytest.mark.parametrize("rows", [512, 4096, 4097, 5001])
def test_the_blocked_gram_is_the_float64_gram(small_blocks, rows):
    """Rows far beyond a block (512), whole blocks and ragged: the
    compensated sum of the blocks' Grams against float64, on positive data
    (a Gram's diagonal is a sum of squares whatever the data)."""
    rng = np.random.RandomState(rows)
    q = (rng.rand(rows, 32) + 0.5).astype(np.float32)
    got = np.asarray(jax.jit(_ops.precise(_tsqr._gram))(jnp.asarray(q)),
                     np.float64)
    want = q.astype(np.float64).T @ q.astype(np.float64)
    # a block of 512 rows rounds by 5e-7 of a sum on this rig; the blocks
    # add nothing to that
    assert np.max(np.abs(got - want) / want) < 1e-6


@pytest.mark.parametrize("rows", [512, 4096, 5001])
def test_a_product_applied_to_a_panel_is_the_product(small_blocks, rows):
    rng = np.random.RandomState(rows)
    q = rng.randn(rows, 32).astype(np.float32)
    right = rng.randn(32, 32).astype(np.float32)
    got = jax.jit(_ops.precise(_tsqr._apply))(jnp.asarray(q),
                                              jnp.asarray(right))
    np.testing.assert_allclose(np.asarray(got), q.astype(np.float64) @ right,
                               atol=2e-5)


# -- (c) the fall-back ----------------------------------------------------------

def _conditioned(rows, n, cond, seed=0):
    rng = np.random.RandomState(seed)
    u0 = np.linalg.qr(rng.randn(rows, n))[0]
    v0 = np.linalg.qr(rng.randn(n, n))[0]
    return ((u0 * np.logspace(0, -np.log10(cond), n)) @ v0.T
            ).astype(np.float32)


@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("cond,ok", [(1e1, True), (1e3, True),
                                     (1e6, False), (1e8, False)])
def test_an_ill_conditioned_panel_takes_the_tree_and_reads_orthogonal(
        small_blocks, monkeypatch, devices, cond, ok):
    monkeypatch.setenv("DSLIB_TSQR_CHOLQR", "1")
    _mesh_of(devices)
    n = 16
    x = _conditioned(ROWS, n, cond)
    *_, took_cholesky = jax.jit(_ops.precise(_tsqr._cholqr2))(jnp.asarray(x))
    assert bool(took_cholesky) is ok
    q, r = ds.tsqr(ds.array(x))
    qh, rh = (np.asarray(a.collect(), np.float64) for a in (q, r))
    assert np.abs(qh.T @ qh - np.eye(n)).max() < 2e-6
    assert np.abs(qh @ rh - x).max() < 1e-6


def test_the_tree_over_blocks_is_a_householder_qr(small_blocks, monkeypatch):
    """The fall-back's first level as a loop (a panel of several blocks,
    ragged): Q orthogonal, R upper triangular, Q R the panel."""
    x = _conditioned(ROWS, 16, 1e5, seed=1)
    q, r = jax.jit(_ops.precise(_tsqr._local_tsqr))(jnp.asarray(x))
    qh, rh = np.asarray(q, np.float64), np.asarray(r, np.float64)
    assert np.abs(qh.T @ qh - np.eye(16)).max() < 2e-6
    assert np.abs(qh @ rh - x).max() < 1e-6
    assert np.allclose(rh, np.triu(rh))


def _shards(x, n, p):
    """The rows ``ds.tsqr`` hands each of p devices: the array's padded
    backing, cut evenly."""
    av = np.asarray(ds.array(x)._data[:, :n])
    return np.split(av, p)


def _old_composition(shards):
    """Q as the program assembled it before the fold, (Q1 R2^-1) Q2_i, in
    float64 from the program's own factors: every shard's local
    factorisation, the same factorisation of the stacked R factors, and
    the two products a shard, one after the other."""
    n = shards[0].shape[1]
    local = jax.jit(_ops.precise(
        lambda a: _tsqr._local_qr(a, True)))
    firsts = [[np.asarray(t, np.float64) for t in local(jnp.asarray(a))]
              for a in shards]
    stack = np.concatenate([r for _, _, r in firsts]).astype(np.float32)
    panel2, factor2, r = (np.asarray(t, np.float64)
                          for t in local(jnp.asarray(stack)))
    q2 = panel2 @ factor2
    return np.concatenate([(panel @ factor) @ q2[i * n:(i + 1) * n]
                           for i, (panel, factor, _) in enumerate(firsts)]), r


@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("cond", [1e1, 1e3, 1e6, 1e8])
def test_the_folded_assembly_is_the_old_composition(small_blocks,
                                                    monkeypatch, devices,
                                                    cond):
    """One application of R2^-1 Q2_i against two, one after the other: the
    same Q to rounding, on both routes (the fall-back's factor is I), on
    one device and across eight; Q orthogonal, Q R the panel, R upper
    triangular with a positive diagonal where Cholesky made it (a
    Householder R's signs are the reflectors')."""
    monkeypatch.setenv("DSLIB_TSQR_CHOLQR", "1")
    _mesh_of(devices)
    n = 16
    x = _conditioned(ROWS, n, cond)
    q, r = ds.tsqr(ds.array(x))
    qh, rh = (np.asarray(a.collect(), np.float64) for a in (q, r))
    assert np.abs(qh.T @ qh - np.eye(n)).max() < 2e-6
    assert np.abs(qh @ rh - x).max() < 1e-6
    assert np.array_equal(rh, np.triu(rh))
    if cond <= 1e3:
        assert (np.diag(rh) > 0).all()
    want_q, want_r = _old_composition(_shards(x, n, devices))
    assert np.abs(qh - want_q[:ROWS]).max() < 5e-6
    assert np.abs(rh - want_r).max() < 5e-6 * np.abs(want_r).max()


def _sub_jaxprs(eqn):
    """The jaxprs an equation runs, in order; of a ``cond`` the branch of
    a true predicate alone (the well-conditioned one), of a ``while`` its
    body."""
    if eqn.primitive.name == "cond":
        return [eqn.params["branches"][1].jaxpr]
    if eqn.primitive.name == "while":
        return [eqn.params["body_jaxpr"].jaxpr]
    found = []
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            v = getattr(v, "jaxpr", v)
            if hasattr(v, "eqns"):
                found.append(v)
    return found


def _panel_passes(jaxpr, rows, block, in_loop=False):
    """What touches a shard's (rows, n) panel, in program order along the
    well-conditioned path: ``gram`` for every product that contracts the
    panel's rows (whole, or a block of them inside a loop), ``apply`` for
    every one that keeps them."""
    tall = {rows, block} if in_loop else {rows}
    passes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            if lhs and lhs[0] in tall:
                passes.append("gram" if 0 in contract else "apply")
        for sub in _sub_jaxprs(eqn):
            passes += _panel_passes(
                sub, rows, block,
                in_loop or eqn.primitive.name in ("while", "scan"))
    return passes


@pytest.mark.parametrize("devices", [1, 8])
def test_a_tall_panel_is_passed_over_four_times(small_blocks, devices):
    """The shard body of ``_tsqr_shardmap`` on the Cholesky route: Gram,
    application, Gram, and exactly ONE product that touches the panel
    after the second Gram, the application that carries R2^-1 and the
    tree's Q2 together (before the fold: R2^-1's, then Q1 Q2)."""
    _mesh_of(devices)
    from dislib_tpu.parallel import mesh as _mesh
    n, rows = 16, 20480 // devices
    block = _tsqr._panel_block(rows, n)
    assert n * devices < block < rows      # several blocks; a stack is one
    jaxpr = jax.make_jaxpr(lambda a: _tsqr._tsqr_shardmap(
        a, _mesh.get_mesh(), devices, cholqr=True))(
            jnp.ones((20480, n), jnp.float32))
    # a Gram that a TPU packs into three products (``pdot_tall``) is one
    # pass; two applications one after the other are two
    passes = [kind for kind, run in itertools.groupby(
        _panel_passes(jaxpr.jaxpr, rows, block))
        for _ in (run if kind == "apply" else [kind])]
    assert passes == ["gram", "apply", "gram", "apply"]


# -- (d) no temporary of A's size -----------------------------------------------

def test_the_compiled_call_holds_less_than_a_beside_a():
    """262 144 x 1024 to rank 246, compiled for the CPU backend and not
    run: the call's temporaries stay at three (m, 256) panels and a
    block's tiles (two panels of the orthonormalisation's own, and one
    that the CPU's compiler gives the conditional between Q1 and the
    fall-back; the TPU's gives it none: two panels, audited on its own
    compiled text in ``test_overlap.py``), and its results are what they
    are."""
    if jax.default_backend() != "cpu":
        pytest.skip("what the TPU's compiler holds is audited on its own "
                    "text, tests/test_overlap.py")
    ds.init((1, 1), devices=jax.devices()[:1])
    m, n, sketch, nsv = 262_144, 1024, 256, 246
    from dislib_tpu.parallel import mesh as _mesh
    mem = _rsvd._random_svd_fused.lower(
        jax.ShapeDtypeStruct((m, n), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), (m, n), 2, sketch, nsv,
        _mesh.get_mesh(), 1, 1, cholqr=True).compile().memory_analysis()
    if mem is None:
        pytest.skip("backend reports no memory analysis")
    assert mem.temp_size_in_bytes < 3.1 * m * sketch * 4, \
        mem.temp_size_in_bytes
    assert mem.output_size_in_bytes < 1.01 * 4 * (m * nsv + nsv + n * nsv)


# -- (e) counter, span, scopes ---------------------------------------------------

def test_the_counter_the_span_and_the_one_dispatch(small_blocks, monkeypatch):
    monkeypatch.setenv("DSLIB_TSQR_CHOLQR", "1")
    ds.init((1, 1), devices=jax.devices()[:1])
    x = ds.array(_data(9, rows=2000))
    jax.clear_caches()                  # so that this call traces
    profiling.reset_counters()
    u, s, v = ds.random_svd(x, iters=2, nsv=NSV, oversample=OVER,
                            random_state=1)
    c = profiling.counters()
    # one dispatch, no read: the call returns before the device is done
    assert c["dispatch_by"] == {"random_svd": 1}
    assert c["trace_by"]["random_svd"] == 1 and c["transfers"] == 0
    assert c["spans"]["dslib.rsvd.call"]["count"] == 1
    assert "dslib.host_read" not in c["spans"]
    # the tall panels walk their rows in blocks; the (n, sketch) panels of
    # the power iterations are one block each
    assert c["schedules"]["tsqr_local:blocked"] == 1
    assert c["schedules"]["tsqr_local:one_product"] == 1
    # and each of the two traces assembled Q in one application
    assert c["schedules"]["tsqr_assemble:folded"] == 2
    s.collect(), v.collect()
    assert profiling.span_totals()["dslib.host_read"]["count"] == 2
    # a second call of the same shapes traces nothing and bumps nothing
    ds.random_svd(x, iters=2, nsv=NSV, oversample=OVER, random_state=2)
    c = profiling.counters()
    assert c["dispatch_by"] == {"random_svd": 2}
    assert c["trace_by"]["random_svd"] == 1
    assert c["schedules"]["tsqr_local:blocked"] == 1
    assert c["schedules"]["tsqr_assemble:folded"] == 2


@pytest.mark.parametrize("route", ["1", "0"])
def test_the_assembly_is_counted_once_a_trace(monkeypatch, route):
    """``tsqr_assemble:folded`` beside ``tsqr_local:<route>``: once a
    trace of ``_tsqr_shardmap``, on the Cholesky route and on the tree's,
    and not again for a call the cache serves."""
    monkeypatch.setenv("DSLIB_TSQR_CHOLQR", route)
    ds.init((1, 1), devices=jax.devices()[:1])
    x = ds.array(_conditioned(1000, 16, 1e1, seed=int(route)))
    jax.clear_caches()
    profiling.reset_counters()
    ds.tsqr(x)
    ds.tsqr(x)
    assert profiling.schedule_counters() == {
        "tsqr_assemble:folded": 1,
        "tsqr_local:" + ("one_product" if route == "1"
                         else "householder_tree"): 1}


@pytest.mark.parametrize("scope", [
    "dslib.rsvd.sketch", "dslib.rsvd.power", "dslib.rsvd.project",
    "dslib.rsvd.small_svd", "dslib.rsvd.lift", "dslib.tsqr.gram",
    "dslib.tsqr.chol", "dslib.tsqr.apply", "dslib.pdot"])
def test_device_scope_is_in_an_op_name(scope):
    ds.init((1, 1), devices=jax.devices()[:1])
    from dislib_tpu.parallel import mesh as _mesh
    text = profiling.op_graph(
        lambda a, key: _rsvd._random_svd_fused(
            a, key, (64, 24), 1, 8, 4, _mesh.get_mesh(), 1, 1, cholqr=True),
        jnp.ones((64, 24)), jax.random.PRNGKey(0))
    assert f"/{scope}/" in text and 'op_name="' in text, scope


# -- (f) the test matrix ----------------------------------------------------------

def test_the_draw_of_the_test_matrix_is_the_documented_one():
    want = jax.random.normal(jax.random.PRNGKey(12345), (N, NSV + OVER),
                             jnp.float32)
    np.testing.assert_array_equal(
        _rsvd._omega_of(jax.random.PRNGKey(12345), N, NSV + OVER), want)
    np.testing.assert_array_equal(ref.test_matrix(12345, N, NSV + OVER),
                                  want)
    assert "jax.random.normal(jax.random.PRNGKey(random_state)" \
        in " ".join(ds.random_svd.__doc__.split())


def test_another_draw_gives_another_answer_and_the_same_draw_the_same():
    """The reference handed the call's own draw agrees (above); handed
    another seed's it does not, so the agreement is the draw's."""
    ds.init((1, 1), devices=jax.devices()[:1])
    x = _data(7, rows=1500)
    rows_idx = ref.sample_rows(7, 1500, 128)
    got = _program(x, 21, rows_idx, nsv=NSV, over=2)
    same = ref.compare(got, _reference(x, 21, rows_idx, nsv=NSV, over=2))
    other = ref.compare(got, _reference(x, 22, rows_idx, nsv=NSV, over=2))
    assert same["right_subspace_gap"] <= LIMITS["right_subspace_gap"]
    assert other["right_subspace_gap"] > 100 * same["right_subspace_gap"]
